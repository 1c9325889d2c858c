"""The served model's edge half inside the closed loop: one compiled program
per epoch (``kind "edge"`` in planning.compile_log, XLA module ``jit_edge``)
over slot caches, dispatched after the epoch program with no host read
between them.

The loop's batcher owns admission: the epoch program moves queued requests
into its slots. Each slot here holds one request's token history and its
key/value caches, one per layer: a ring of ``window`` positions for a
sliding layer, ``max_context`` positions for a full one. A cache entry's
position follows from the slot's length, so a slot is reused with no reset.
In one call the program

  (a) adopts the requests the batcher admitted since the last call (a
      request id, and a prompt and an output length drawn from it),
  (b) prefills the oldest unfinished prompts, ``chunks`` chunks of
      ``chunk`` tokens (the per-epoch token budget), through every layer
      into the slot caches; the last prompt token's logits give the first
      output token,
  (c) runs ``decode_steps`` decode steps for every decoding slot, each
      consuming the slot's last token and appending the greedy next one,
  (d) frees a slot whose request has its output length: the batcher's
      next epoch completes it (``work`` 0).

Every chunk and step does the same work whatever the load: a chunk with no
prompt to prefill and a slot that is not decoding compute on masked
positions and write nothing.

The planned split s* is a device operand. Layers below it stand for the
users' phones: the chip computes them too, under the scope ``user_half``,
and the activation that crosses the split is cast to the profile's
``act_bits``; a split that moves compiles nothing. Device scopes:
``edge_prefill``, ``edge_decode``, ``attn_window``, ``attn_full`` (the
attention core and nothing of the projections), ``moe_router``,
``moe_experts`` (the held and the shared experts) and ``user_half``.

What the decode steps produced is kept for the comparison with a plain
reference, so that it can be replayed after the window without a host read
in it: every slot's tokens and request id, and, for each decode step of the
last ``KEPT_EPOCHS`` epochs, the position it consumed, its logits and, at
every MoE layer, the layer's normalised input, its top-k experts and its
expert output.

The weights are given, drawn by a given function from the episode key, or
drawn here (:func:`init_weights`). The embedding and output head may be
padded past the vocabulary; logits are over the vocabulary alone.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.types import Array
from repro.faults import guards
from repro.models import attention, blocks, moe
from repro.models.layers import (
    COMPUTE_DTYPE,
    ParamDef,
    einsum_f32,
    embed_lookup,
    init_params,
    logits_out,
    mlp_apply,
    pad_vocab,
    rms_norm,
    rope,
)
from repro.obs import recorded
from repro.online.batcher import BatchState

NEG_INF = attention.NEG_INF
KEPT_EPOCHS = 4         # epochs of decode logits kept for the comparison
_FLOAT_OF_BITS = {8: jnp.float8_e4m3fn, 16: jnp.bfloat16, 32: jnp.float32}


@dataclasses.dataclass(frozen=True)
class EdgeConfig:
    """The served edge: ``slots`` request slots with caches of up to
    ``max_context`` positions, a prefill budget of ``prefill_budget``
    tokens an epoch in chunks of ``chunk``, ``decode_steps`` decode steps
    an epoch. Prompt and output lengths are log-normal per request:
    (median, sigma, min, max) in tokens. ``act_bits`` is the width of the
    activation crossing the split (the planner profile's)."""

    slots: int = 64
    max_context: int = 8704
    chunk: int = 512
    prefill_budget: int = 4096
    decode_steps: int = 4
    prompt: tuple = (3072, 0.5, 1024, 8192)
    output: tuple = (192, 0.5, 64, 512)
    act_bits: int = 16

    def __post_init__(self):
        for name in ("prompt", "output"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        if self.prompt[3] + self.output[3] > self.max_context:
            raise ValueError("the longest prompt and output exceed "
                             f"max_context {self.max_context}")
        if self.prefill_budget % self.chunk:
            raise ValueError("prefill_budget must be a multiple of chunk")

    @property
    def chunks(self) -> int:
        return self.prefill_budget // self.chunk


class EdgeCounters(NamedTuple):
    """Episode totals, device scalars."""
    prefill_tokens: Array   # () int32 prompt tokens prefilled
    prefill_chunks: Array   # () int32 prefill chunks run
    decode_tokens: Array    # () int32 tokens decoded (slot-steps)
    decode_steps: Array     # () int32 decode steps run
    requests_done: Array    # () int32 requests given their output length
    moe_local: Array        # () int32 (token, expert) pairs routed to held experts
    attn_pairs: Array       # () f32 query-key pairs the results need
    attn_keys: Array        # () f32 cached key positions the results read


class EdgeState(NamedTuple):
    k: tuple                # per layer, per KV head (B, S_l, hd) bf16
    v: tuple
    tokens: Array           # (B, max_context + chunk) int32 token history
    length: Array           # (B,) int32 positions in the caches
    prompt: Array           # (B,) int32 prompt length
    out_len: Array          # (B,) int32 output length
    n_out: Array            # (B,) int32 tokens generated so far
    busy: Array             # (B,) bool slot holds an unfinished request
    rid: Array              # (B,) int32 request id
    next_rid: Array         # () int32
    kept_logits: Array      # (B, H, V) f32 decode logits, step c at c % H
    kept_pos: Array         # (B, H) int32 position that step consumed
    kept_rid: Array         # (B, H) int32 request that decoded it (-1: none)
    kept_moe_in: Array      # (B, H, n_moe, d) bf16 each MoE layer's input
                            # after its norm
    kept_experts: Array     # (B, H, n_moe, k) int32 its top-k experts
    kept_moe_out: Array     # (B, H, n_moe, d) f32 its held and shared
                            # experts' output
    counters: EdgeCounters


def layer_specs(cfg) -> list[blocks.StageSpec]:
    """One StageSpec per layer, in order."""
    return [dataclasses.replace(s, n_layers=1)
            for s in blocks.stages_for(cfg) for _ in range(s.n_layers)]


def init_weights(cfg, key: Array) -> dict:
    """Seeded random weights in the compute type: the embedding and output
    head (the vocabulary padded to 256 rows), the final norm and one dict
    per layer (``blocks.block_defs``)."""
    specs = layer_specs(cfg)
    vp = pad_vocab(cfg.vocab_size)
    top = {"embed": ParamDef((vp, cfg.d_model), ("vocab", "embed")),
           "unembed": ParamDef((vp, cfg.d_model), ("vocab", "embed")),
           "final_norm_w": ParamDef((cfg.d_model,), ("embed",), init="zeros")}
    keys = jax.random.split(key, len(specs) + 1)
    params = init_params(top, keys[0])
    params["layers"] = [init_params(blocks.block_defs(cfg, s), k)
                        for s, k in zip(specs, keys[1:])]
    return jax.tree.map(lambda x: x.astype(COMPUTE_DTYPE), params)


def cache_positions(length: Array, size: int) -> Array:
    """(..., size) absolute position held by each cache index of a slot
    whose caches hold ``length`` positions (negative: empty). Position p lives
    at index p % size: a ring of ``size`` = window positions for a sliding
    layer, the identity for a full one (size >= every length)."""
    i = jnp.arange(size, dtype=jnp.int32)
    last = length[..., None] - 1
    return last - jnp.mod(last - i, size)


def _cross(x: Array, bits: int) -> Array:
    """The activation as it crosses the radio link: ``bits`` wide."""
    return x.astype(_FLOAT_OF_BITS[bits]).astype(x.dtype)


def _scope(name):
    return jax.named_scope(name) if name else contextlib.nullcontext()


def _qkv(p: dict, h: Array, cfg, pos: Array):
    b, t, _ = h.shape
    dt = COMPUTE_DTYPE
    q = (h @ p["wq"].astype(dt)).reshape(b, t, cfg.n_heads, cfg.hd)
    k = (h @ p["wk"].astype(dt)).reshape(b, t, cfg.n_kv_heads, cfg.hd)
    v = (h @ p["wv"].astype(dt)).reshape(b, t, cfg.n_kv_heads, cfg.hd)
    return (rope(q, pos, cfg.rope_theta), rope(k, pos, cfg.rope_theta), v)


def _prefill_attend(cfg, window, kc, vc, kpos, q_pos, new_pos, q, k, v):
    """A chunk's queries over its slot's cached keys and its own: the
    cache rows (per KV head (S, hd)) and the chunk's keys at ``new_pos``
    (-1 for padding), causal, within ``window``."""
    b, t = q.shape[:2]
    g = cfg.n_heads // cfg.n_kv_heads
    row = lambda c: jnp.stack(c, axis=1)[None]  # noqa: E731  (1, S, KV, hd)
    out = attention._chunked_mha(
        q.reshape(b, t, cfg.n_kv_heads, g, cfg.hd),
        jnp.concatenate([row(kc), k], axis=1),
        jnp.concatenate([row(vc), v], axis=1),
        q_pos, jnp.concatenate([kpos, new_pos], axis=1), None,
        causal=True, window=window)
    return out.reshape(b, t, cfg.n_heads * cfg.hd)


def _decode_attend(cfg, window, kc, vc, kpos, q_pos, new_pos, q, k, v):
    """One query per slot over the slot's cached keys (per KV head
    (B, S, hd)), at positions before its own, and its new key, in one
    softmax: the cache is only read."""
    del new_pos
    b = q.shape[0]
    kvh, g, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.hd
    qf = (q.reshape(b, kvh, g, hd) * hd ** -0.5).astype(COMPUTE_DTYPE)
    s_c = jnp.stack([einsum_f32("bgd,bsd->bgs", qf[:, i], c)
                     for i, c in enumerate(kc)], axis=1)
    qp = q_pos[:, :1]                                         # (B, 1)
    valid = (kpos >= 0) & (kpos < qp)
    if window:
        valid &= qp - kpos < window
    s_c = jnp.where(valid[:, None, None, :], s_c, NEG_INF)
    s_n = einsum_f32("bkgd,bkd->bkg", qf, k[:, 0])
    m = jnp.maximum(jnp.max(s_c, -1), s_n)
    p_c = jnp.exp(s_c - m[..., None])
    p_n = jnp.exp(s_n - m)
    acc = jnp.stack([einsum_f32("bgs,bsd->bgd", p_c[:, i].astype(COMPUTE_DTYPE),
                                c) for i, c in enumerate(vc)], axis=1)
    acc = acc + p_n[..., None] * v[:, 0, :, None, :].astype(jnp.float32)
    out = acc / (jnp.sum(p_c, -1) + p_n)[..., None]
    return out.astype(COMPUTE_DTYPE).reshape(b, 1, cfg.n_heads * hd)


def _layer(cfg, spec, p, x, pos, attend, real, scope):
    """One pre-norm block with read-only caches: returns the new residual
    stream, the tokens' new keys and values (for the caller to write), on
    an MoE layer its (normalised input, top-k experts, expert output) (else
    None) and the (token, held expert) pairs of ``real`` tokens."""
    with _scope(scope):
        b, t, d = x.shape
        dt = COMPUTE_DTYPE
        h = rms_norm(x, p["ln1_w"], cfg.norm_eps)
        q, k, v = _qkv(p["attn"], h, cfg, pos)
        with jax.named_scope("attn_window" if spec.window else "attn_full"):
            o = attend(q, k, v)
        x = x + o @ p["attn"]["wo"].astype(dt)
        h = rms_norm(x, p["ln2_w"], cfg.norm_eps).reshape(b * t, d)
        if not spec.moe:
            y = mlp_apply(p["mlp"], h, cfg.act)
            return (x + y.reshape(b, t, d).astype(x.dtype), k, v, None,
                    jnp.int32(0))
        with jax.named_scope("moe_router"):
            gates, idx, _, _ = moe.route(p["moe"], h, cfg)
        with jax.named_scope("moe_experts"):
            y = moe._experts_dense(p["moe"], h, gates, idx, cfg)
            if cfg.n_shared_experts:
                y = y + moe.shared_expert(p["moe"], h).astype(jnp.float32)
        first, stop = cfg.held
        here = (idx >= first) & (idx < stop) & real.reshape(b * t, 1)
        kept = (h.reshape(b, t, d), idx.reshape(b, t, -1), y.reshape(b, t, d))
        return (x + y.reshape(b, t, d).astype(x.dtype), k, v, kept,
                jnp.sum(here).astype(jnp.int32))


def _layers(cfg, ecfg, params, st, s, x, pos, attend_fn, real, write):
    """Every layer over ``x`` at positions ``pos``: layer l under
    ``user_half`` where l < s, the activation cast where it crosses the
    split, each layer's new keys written by ``write``. Returns (x, new
    caches, per MoE layer its (input, top-k, output), held-expert
    pairs)."""
    ks, vs, moes, local = list(st.k), list(st.v), [], jnp.int32(0)
    for l, (spec, p) in enumerate(zip(layer_specs(cfg), params["layers"])):
        attend = attend_fn(spec.window, ks[l], vs[l])

        def run(scope, spec=spec, p=p, attend=attend, x=x):
            return _layer(cfg, spec, p, x, pos, attend, real, scope)
        x, k_new, v_new, kept, n_here = jax.lax.cond(
            l < s, functools.partial(run, "user_half"),
            functools.partial(run, None))
        ks[l], vs[l] = write(ks[l], k_new), write(vs[l], v_new)
        if kept is not None:
            moes.append(kept)
            local = local + n_here
        x = jnp.where(l + 1 == s, _cross(x, ecfg.act_bits), x)
    return x, tuple(ks), tuple(vs), moes, local


def _head(cfg, params, x: Array) -> Array:
    """(..., V) float32 logits over the vocabulary slice."""
    x = rms_norm(x, params["final_norm_w"], cfg.norm_eps)
    return logits_out(x, params["unembed"], cfg.vocab_size)[
        ..., :cfg.vocab_size]


def _lengths(key: Array, dist: tuple) -> Array:
    median, sigma, lo, hi = dist
    z = jax.random.normal(key, (), jnp.float32)
    n = jnp.round(median * jnp.exp(sigma * z))
    return jnp.clip(n, lo, hi).astype(jnp.int32)


def _adopt(cfg, ecfg, st: EdgeState, bt: BatchState, key: Array) -> EdgeState:
    """Give every slot the batcher admitted since the last call a request:
    the next id, lengths and a prompt drawn from (key, id)."""
    new = bt.active & ~st.busy
    rid = jnp.where(new, st.next_rid + jnp.cumsum(new) - 1, st.rid)
    keys = jax.vmap(lambda r: jax.random.fold_in(key, r))(rid)
    prompt = jax.vmap(lambda k: _lengths(jax.random.fold_in(k, 1),
                                         ecfg.prompt))(keys)
    out_len = jax.vmap(lambda k: _lengths(jax.random.fold_in(k, 2),
                                          ecfg.output))(keys)
    drawn = jax.vmap(lambda k: jax.random.randint(
        jax.random.fold_in(k, 3), st.tokens.shape[1:], 0, cfg.vocab_size,
        jnp.int32))(keys)
    sel = lambda a, b: jnp.where(  # noqa: E731
        new.reshape((-1,) + (1,) * (b.ndim - 1)), a, b)
    return st._replace(
        tokens=sel(drawn, st.tokens), length=sel(0, st.length),
        prompt=sel(prompt, st.prompt), out_len=sel(out_len, st.out_len),
        n_out=sel(0, st.n_out), busy=st.busy | new, rid=rid,
        next_rid=st.next_rid + jnp.sum(new).astype(jnp.int32))


def _window_keys(window: int, n_keys: Array) -> Array:
    return jnp.minimum(n_keys, window) if window else n_keys


def _prefill_chunk(cfg, ecfg, params, s, st: EdgeState, _):
    """One chunk of the oldest unfinished prompt (or, with none, a chunk
    that writes nothing)."""
    c, b_slots = ecfg.chunk, ecfg.slots
    cand = st.busy & (st.length < st.prompt)
    has = jnp.any(cand)
    b = jnp.argmin(jnp.where(cand, st.rid, jnp.iinfo(jnp.int32).max))
    l0 = st.length[b]
    n = jnp.where(has, jnp.minimum(c, st.prompt[b] - l0), 0)
    j = jnp.arange(c, dtype=jnp.int32)
    real = j < n
    toks = jax.lax.dynamic_slice(st.tokens[b], (l0,), (c,))
    q_pos = (l0 + j)[None]
    new_pos = jnp.where(real, l0 + j, -1)[None]
    x = embed_lookup(params["embed"], toks)[None]

    def attend_fn(window, kc, vc):
        return functools.partial(
            _prefill_attend, cfg, window, [c[b] for c in kc],
            [c[b] for c in vc], cache_positions(l0[None], kc[0].shape[1]),
            q_pos, new_pos)

    def write(cache, new):
        size = cache[0].shape[1]
        idx = jnp.where(real, (l0 + j) % size, size)
        return tuple(c.at[b, idx].set(new[0, :, i].astype(c.dtype),
                                      mode="drop")
                     for i, c in enumerate(cache))

    x, ks, vs, _, local = _layers(cfg, ecfg, params, st, s, x, q_pos,
                                  attend_fn, real[None], write)
    done = has & (l0 + n == st.prompt[b])
    logits = _head(cfg, params, x[0, jnp.maximum(n - 1, 0)])
    first = jnp.argmax(logits).astype(jnp.int32)
    t_max = st.tokens.shape[1]
    tokens = st.tokens.at[b, jnp.where(done, l0 + n, t_max)].set(
        first, mode="drop")
    onehot = (jnp.arange(b_slots) == b)
    pairs = keys = jnp.float32(0)
    for spec in layer_specs(cfg):
        pairs += jnp.sum(jnp.where(
            real, _window_keys(spec.window, l0 + j + 1), 0)).astype(
                jnp.float32)
        keys += jnp.where(has, _window_keys(spec.window, l0), 0).astype(
            jnp.float32)
    ct = st.counters
    ct = ct._replace(prefill_tokens=ct.prefill_tokens + n,
                     prefill_chunks=ct.prefill_chunks + 1,
                     moe_local=ct.moe_local + local,
                     attn_pairs=ct.attn_pairs + pairs,
                     attn_keys=ct.attn_keys + keys)
    st = st._replace(
        k=ks, v=vs, tokens=tokens, length=st.length + onehot * n,
        n_out=st.n_out + (onehot & done).astype(jnp.int32),
        counters=ct)
    return st, jnp.all(jnp.isfinite(logits))


def _decode_step(cfg, ecfg, params, s, st: EdgeState, _):
    """One decode step of every decoding slot."""
    b_slots = ecfg.slots
    dec = st.busy & (st.length >= st.prompt) & (st.n_out < st.out_len)
    pos = st.length
    rows = jnp.arange(b_slots)
    tok = st.tokens[rows, pos][:, None]
    x = embed_lookup(params["embed"], tok)
    q_pos = pos[:, None]

    def attend_fn(window, kc, vc):
        return functools.partial(
            _decode_attend, cfg, window, kc, vc,
            cache_positions(pos, kc[0].shape[1]), q_pos, None)

    def write(cache, new):
        size = cache[0].shape[1]
        idx = jnp.where(dec, pos % size, size)
        return tuple(c.at[rows, idx].set(new[:, 0, i].astype(c.dtype),
                                         mode="drop")
                     for i, c in enumerate(cache))

    x, ks, vs, moes, local = _layers(cfg, ecfg, params, st, s, x, q_pos,
                                     attend_fn, dec[:, None], write)
    logits = _head(cfg, params, x[:, 0])                      # (B, V)
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    t_max = st.tokens.shape[1]
    tokens = st.tokens.at[rows, jnp.where(dec, pos + 1, t_max)].set(
        nxt, mode="drop")
    pairs = keys = jnp.float32(0)
    for spec in layer_specs(cfg):
        n_keys = jnp.where(dec, _window_keys(spec.window, pos + 1), 0)
        pairs += jnp.sum(n_keys).astype(jnp.float32)
        keys += jnp.sum(n_keys).astype(jnp.float32)
    ct = st.counters
    n_dec = jnp.sum(dec).astype(jnp.int32)
    ct = ct._replace(decode_tokens=ct.decode_tokens + n_dec,
                     decode_steps=ct.decode_steps + 1,
                     moe_local=ct.moe_local + local,
                     attn_pairs=ct.attn_pairs + pairs,
                     attn_keys=ct.attn_keys + keys)
    h = st.counters.decode_steps % st.kept_rid.shape[1]
    if moes:
        # (B, n_moe, ...) per kept quantity: input, top-k, output
        moe_in, moe_idx, moe_out = (jnp.stack([m[i][:, 0] for m in moes], 1)
                                    for i in range(3))
        st = st._replace(
            kept_moe_in=st.kept_moe_in.at[:, h].set(moe_in),
            kept_experts=st.kept_experts.at[:, h].set(moe_idx),
            kept_moe_out=st.kept_moe_out.at[:, h].set(moe_out))
    st = st._replace(
        k=ks, v=vs, tokens=tokens,
        length=st.length + dec.astype(jnp.int32),
        n_out=st.n_out + dec.astype(jnp.int32),
        kept_logits=st.kept_logits.at[:, h].set(logits),
        kept_pos=st.kept_pos.at[:, h].set(pos),
        kept_rid=st.kept_rid.at[:, h].set(jnp.where(dec, st.rid, -1)),
        counters=ct)
    return st, jnp.all(jnp.isfinite(logits))


def edge_epoch(cfg, ecfg: EdgeConfig, dt: float, params: dict,
               st: EdgeState, bt: BatchState, s: Array, key: Array,
               health: Array):
    """One epoch of the served edge (module docstring). ``s`` is the
    planned split, ``key`` the episode's base key, ``health`` the epoch
    program's health word; returns (state, batch state, health word with
    the edge's bit)."""
    s = s.astype(jnp.int32)
    st = _adopt(cfg, ecfg, st, bt, jax.random.fold_in(key, 0xED6E))
    with jax.named_scope("edge_prefill"):
        st, ok_p = jax.lax.scan(
            functools.partial(_prefill_chunk, cfg, ecfg, params, s), st,
            None, length=ecfg.chunks)
    with jax.named_scope("edge_decode"):
        st, ok_d = jax.lax.scan(
            functools.partial(_decode_step, cfg, ecfg, params, s), st,
            None, length=ecfg.decode_steps)
    done = st.busy & (st.n_out >= st.out_len)
    ct = st.counters
    st = st._replace(busy=st.busy & ~done, counters=ct._replace(
        requests_done=ct.requests_done + jnp.sum(done).astype(jnp.int32)))
    bt = bt._replace(work=jnp.where(done, 0, bt.work),
                     serv=bt.serv + jnp.where(bt.active, dt, 0.0))
    return st, bt, health | guards.edge_health(jnp.all(ok_p) & jnp.all(ok_d))


def init_state(cfg, ecfg: EdgeConfig) -> EdgeState:
    b, h = ecfg.slots, KEPT_EPOCHS * ecfg.decode_steps
    ks, vs = [], []
    for spec in layer_specs(cfg):
        size = min(spec.window, ecfg.max_context) if spec.window \
            else ecfg.max_context
        heads = lambda: tuple(  # noqa: E731
            jnp.zeros((b, size, cfg.hd), COMPUTE_DTYPE)
            for _ in range(cfg.n_kv_heads))
        ks.append(heads())
        vs.append(heads())
    n_moe = sum(s.moe for s in layer_specs(cfg))
    # every leaf its own buffer: the state is donated
    zi = lambda: jnp.zeros((), jnp.int32)  # noqa: E731
    zf = lambda: jnp.zeros((), jnp.float32)  # noqa: E731
    zeros = lambda: jnp.zeros((b,), jnp.int32)  # noqa: E731
    return EdgeState(
        k=tuple(ks), v=tuple(vs),
        tokens=jnp.zeros((b, ecfg.max_context + ecfg.chunk), jnp.int32),
        length=zeros(), prompt=zeros(), out_len=zeros(), n_out=zeros(),
        busy=jnp.zeros((b,), bool), rid=jnp.full((b,), -1, jnp.int32),
        next_rid=zi(),
        kept_logits=jnp.zeros((b, h, cfg.vocab_size), jnp.float32),
        kept_pos=jnp.zeros((b, h), jnp.int32),
        kept_rid=jnp.full((b, h), -1, jnp.int32),
        kept_moe_in=jnp.zeros((b, h, n_moe, cfg.d_model), COMPUTE_DTYPE),
        kept_experts=jnp.zeros((b, h, n_moe, cfg.top_k), jnp.int32),
        kept_moe_out=jnp.zeros((b, h, n_moe, cfg.d_model), jnp.float32),
        counters=EdgeCounters(zi(), zi(), zi(), zi(), zi(), zi(), zf(),
                              zf()))


@functools.lru_cache(maxsize=None)
def program(cfg, ecfg: EdgeConfig, dt: float):
    """The jitted edge program for one model and edge configuration,
    shared by every loop that serves them: (params, state, batch state, s,
    key, health) -> (state, batch state, health), the two states donated."""
    return jax.jit(recorded(functools.partial(edge_epoch, cfg, ecfg, dt),
                            "edge", name="edge"),
                   donate_argnums=(1, 2))


@functools.lru_cache(maxsize=None)
def _init_weights_jit(cfg):
    return jax.jit(functools.partial(init_weights, cfg))


class EdgeServer:
    """The served model at the edge: architecture, weights, configuration
    and the shared compiled program. ``params`` is the weights, or a
    function that draws them from the key :meth:`init` is given, kept as
    ``key`` (None: :func:`init_weights`)."""

    def __init__(self, cfg, ecfg: EdgeConfig, dt: float, params=None):
        if cfg.family != "moe" and cfg.family != "dense":
            raise ValueError(f"edge serving takes a decoder LM, got "
                             f"family {cfg.family!r}")
        windows = [s.window for s in layer_specs(cfg) if s.window]
        if windows and ecfg.chunk > min(windows):
            raise ValueError("a prefill chunk must fit the smallest window")
        if params is None:
            params = _init_weights_jit(cfg)
        self.cfg, self.ecfg, self.dt = cfg, ecfg, float(dt)
        self._draw = params if callable(params) else None
        self.params = None if callable(params) else params
        self.key = None
        self._program = program(cfg, ecfg, self.dt)

    def init(self, key: Array) -> EdgeState:
        """Fresh slots, and the weights drawn from ``key`` unless given."""
        self.key = key
        if self._draw is not None:
            self.params = self._draw(key)
        return init_state(self.cfg, self.ecfg)

    def step(self, st: EdgeState, bt: BatchState, s: Array, key: Array,
             health: Array):
        return self._program(self.params, st, bt, s, key, health)
