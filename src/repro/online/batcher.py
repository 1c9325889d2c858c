"""Continuous batching on the edge decode path.

The edge serves a fixed-capacity batch of B request *slots*: arrivals are
enqueued into a bounded FIFO ring, free slots are refilled from the queue
head every epoch (admit), and one batched edge step serves every active
slot at once (tick) -- edge throughput scales with concurrency instead of
serializing per request, exactly the stream_router/task_dispatcher shape of
the related sparse_framework serving stack. Everything is a compiled
program over a single BatchState pytree (donated across epochs by the
loop); slot admission, eviction, and completion accounting are where/scan
ops, so the hot path never syncs to host.

Two consumers:

* The planning-only closed loop (repro.online.loop, benchmarks) drives the
  queueing core alone: per-request service time comes from the measured
  delay model and occupancy converts to slot epochs.
* Real split-serving reuses the decode-step cache machinery from
  runtime/serve.py: ``DecodeBatcher`` keeps one capacity-sized KV/state
  cache (model.make_caches) alive across requests, writes a per-request
  prefill into its slot at admission (slot_update), and advances every
  active slot with one masked decode step per epoch (inactive slots'
  caches are frozen via slot_where and overwritten at their next
  admission). ``EdgeBatcher`` is the single-shot analogue over stacked
  split activations for the paper's CNN-style one-pass inference.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.types import Array


class BatchState(NamedTuple):
    """Slots + FIFO ring + counters; all device arrays, static shapes."""

    # slots (capacity B)
    active: Array    # (B,) bool
    user: Array      # (B,) int32, -1 when free
    t_arr: Array     # (B,) f32 arrival time (s) of the occupying request
    wait: Array      # (B,) f32 queue wait (s) accrued before admission
    serv: Array      # (B,) f32 modeled service seconds of the request
    work: Array      # (B,) int32 remaining edge steps
    # FIFO ring (depth Q)
    q_user: Array    # (Q,) int32
    q_t: Array       # (Q,) f32 arrival times
    q_head: Array    # () int32
    q_size: Array    # () int32
    # counters
    dropped: Array   # () int32 arrivals rejected on a full ring
    completed: Array  # () int32 requests fully served
    shed: Array      # () int32 requests shed by the degradation ladder
                     # (admission control under faults; 0 when disabled)


class Completions(NamedTuple):
    """Per-epoch completion record, fixed shape (B,): at most one request
    per slot completes per tick."""

    valid: Array     # (B,) bool
    user: Array      # (B,) int32
    latency: Array   # (B,) f32 end-to-end seconds (wait + service)
    wait: Array      # (B,) f32 queue-wait component
    serv: Array      # (B,) f32 service component


def init_state(capacity: int, queue_depth: int) -> BatchState:
    b, q = int(capacity), int(queue_depth)
    return BatchState(
        active=jnp.zeros((b,), bool),
        user=jnp.full((b,), -1, jnp.int32),
        t_arr=jnp.zeros((b,), jnp.float32),
        wait=jnp.zeros((b,), jnp.float32),
        serv=jnp.zeros((b,), jnp.float32),
        work=jnp.zeros((b,), jnp.int32),
        q_user=jnp.full((q,), -1, jnp.int32),
        q_t=jnp.zeros((q,), jnp.float32),
        q_head=jnp.int32(0),
        q_size=jnp.int32(0),
        dropped=jnp.int32(0),
        completed=jnp.int32(0),
        shed=jnp.int32(0),
    )


def enqueue(state: BatchState, counts: Array, now: Array,
            max_per_user: int) -> BatchState:
    """Append this epoch's arrivals (per-user counts, capped at
    ``max_per_user``) to the FIFO ring; overflow increments ``dropped``."""
    u = counts.shape[0]
    q = state.q_user.shape[0]
    # (U, K) candidate grid flattened in user-major order: request j of user
    # i exists iff j < counts[i].
    k = int(max_per_user)
    valid = (jnp.arange(k)[None, :] < counts[:, None]).reshape(-1)
    users = jnp.broadcast_to(jnp.arange(u, dtype=jnp.int32)[:, None],
                             (u, k)).reshape(-1)

    def push(carry, x):
        q_user, q_t, head, size, dropped = carry
        is_valid, uid = x
        fits = is_valid & (size < q)
        slot = (head + size) % q
        q_user = jnp.where(fits, q_user.at[slot].set(uid), q_user)
        q_t = jnp.where(fits, q_t.at[slot].set(now.astype(jnp.float32)), q_t)
        size = size + fits.astype(jnp.int32)
        dropped = dropped + (is_valid & ~fits).astype(jnp.int32)
        return (q_user, q_t, head, size, dropped), None

    (q_user, q_t, head, size, dropped), _ = jax.lax.scan(
        push, (state.q_user, state.q_t, state.q_head, state.q_size,
               state.dropped), (valid, users))
    return state._replace(q_user=q_user, q_t=q_t, q_size=size,
                          dropped=dropped)


def admit(state: BatchState, now: Array, service_s: Array,
          work_steps: Array, shed: Array | None = None) -> BatchState:
    """Refill free slots from the queue head (FIFO). ``service_s``: (U,)
    modeled service seconds per user at the current operating point;
    ``work_steps``: (U,) int32 slot epochs the request will occupy.

    ``shed`` (optional, (U,) bool) is the degradation ladder's admission
    gate: a queue head whose user is flagged is popped and counted into
    ``state.shed`` instead of occupying a slot -- under a persistent deep
    fade its modeled work would pin the slot for ``max_work_epochs``,
    starving every healthy user behind it. None preserves the exact
    ungated behavior."""
    q = state.q_user.shape[0]

    def fill(carry, slot):
        st = carry
        free = ~st.active[slot]
        have = st.q_size > 0
        pop = free & have
        uid = st.q_user[st.q_head % q]
        t0 = st.q_t[st.q_head % q]
        if shed is None:
            doomed = jnp.bool_(False)
        else:
            doomed = pop & shed[jnp.maximum(uid, 0)]
        take = pop & ~doomed
        nowf = now.astype(jnp.float32)
        st = st._replace(
            active=st.active.at[slot].set(jnp.where(take, True,
                                                    st.active[slot])),
            user=st.user.at[slot].set(jnp.where(take, uid, st.user[slot])),
            t_arr=st.t_arr.at[slot].set(jnp.where(take, t0, st.t_arr[slot])),
            wait=st.wait.at[slot].set(jnp.where(take, nowf - t0,
                                                st.wait[slot])),
            serv=st.serv.at[slot].set(jnp.where(take, service_s[uid],
                                                st.serv[slot])),
            work=st.work.at[slot].set(jnp.where(take, work_steps[uid],
                                                st.work[slot])),
            q_head=(st.q_head + pop.astype(jnp.int32)) % q,
            q_size=st.q_size - pop.astype(jnp.int32),
            shed=st.shed + doomed.astype(jnp.int32),
        )
        return st, take

    b = state.active.shape[0]
    state, admitted = jax.lax.scan(fill, state, jnp.arange(b))
    del admitted
    return state


def tick(state: BatchState, advance: bool = True
         ) -> tuple[BatchState, Completions]:
    """One batched edge step: every active slot advances one unit of work
    (with ``advance``; without, the served model has set ``work`` itself);
    slots reaching zero complete and free."""
    work = (state.work - state.active.astype(jnp.int32) if advance
            else state.work)
    done = state.active & (work <= 0)
    comp = Completions(
        valid=done,
        user=jnp.where(done, state.user, -1),
        latency=jnp.where(done, state.wait + state.serv, 0.0),
        wait=jnp.where(done, state.wait, 0.0),
        serv=jnp.where(done, state.serv, 0.0),
    )
    state = state._replace(
        active=state.active & ~done,
        user=jnp.where(done, -1, state.user),
        work=jnp.maximum(work, 0),
        completed=state.completed + jnp.sum(done).astype(jnp.int32),
    )
    return state, comp


def occupancy(state: BatchState) -> Array:
    """() int32: active slots (the edge batch's instantaneous load)."""
    return jnp.sum(state.active).astype(jnp.int32)


def backlog(state: BatchState) -> Array:
    """() int32: requests waiting in the ring behind the batch."""
    return state.q_size


class ContinuousBatcher:
    """The queueing core as one compiled per-epoch program.

    ``step(state, counts, now, service_s, work_steps)`` runs
    enqueue -> admit -> tick and returns (state', completions). The state
    argument is donated: the caller threads the returned state, so XLA
    reuses the buffers in place across epochs."""

    def __init__(self, capacity: int, queue_depth: int,
                 max_per_user_epoch: int):
        if capacity < 1 or queue_depth < 1:
            raise ValueError(
                f"capacity/queue_depth must be >= 1, got "
                f"{capacity}/{queue_depth}")
        self.capacity = int(capacity)
        self.queue_depth = int(queue_depth)
        self.max_per_user_epoch = int(max_per_user_epoch)

    def init(self) -> BatchState:
        return init_state(self.capacity, self.queue_depth)

    @functools.cached_property
    def _step(self):
        k = self.max_per_user_epoch

        def step(state, counts, now, service_s, work_steps):
            state = enqueue(state, counts, now, k)
            state = admit(state, now, service_s, work_steps)
            return tick(state)

        return jax.jit(step, donate_argnums=(0,))

    def step(self, state: BatchState, counts: Array, now: Array,
             service_s: Array, work_steps: Array
             ) -> tuple[BatchState, Completions]:
        return self._step(state, counts, now, service_s, work_steps)


# --------------------------------------------------------------------------
# real-model edge batching: slot-masked programs over serve.py machinery
# --------------------------------------------------------------------------
def _slot_axis(path) -> int:
    """Batch-axis index of a cache leaf: stage caches are stacked over the
    stage's layers first (make_cache leaves are (L, B, ...)), everything
    else (pos, enc_out, frontend) leads with B."""
    return 1 if any(getattr(p, "key", None) == "stages" for p in path) else 0


def slot_update(caches, slot: Array | int, one):
    """Write a single-request cache pytree (batch dim 1, e.g. from
    model.prefill at batch 1) into slot ``slot`` of a capacity-sized cache:
    the decode-cache analogue of admitting a request."""
    def write(path, full, single):
        ax = _slot_axis(path)
        return jax.lax.dynamic_update_index_in_dim(
            full, jnp.take(single, 0, axis=ax).astype(full.dtype), slot, ax)
    return jax.tree_util.tree_map_with_path(write, caches, one)


def slot_where(active: Array, new, old):
    """Per-slot select over a cache pytree: active slots take ``new``,
    inactive keep ``old`` (frozen until their next admission)."""
    def sel(path, n, o):
        ax = _slot_axis(path)
        shape = [1] * n.ndim
        shape[ax] = active.shape[0]
        return jnp.where(active.reshape(shape), n, o)
    return jax.tree_util.tree_map_with_path(sel, new, old)


class EdgeBatcher:
    """Single-shot split inference over stacked activations: admitted
    requests write their device-side activation into a (B, S, D) buffer;
    one edge_fn call per epoch serves every active slot (masked-slot
    continuous batching -- inactive lanes compute garbage that is never
    read, the standard slot-batching tradeoff)."""

    def __init__(self, capacity: int, seq: int, d_model: int,
                 dtype=jnp.float32):
        self.capacity = int(capacity)
        self.buf = jnp.zeros((capacity, seq, d_model), dtype)

    def write(self, buf: Array, slot: Array | int, act: Array) -> Array:
        """Insert one request's (S, D) (or (1, S, D)) activation at slot."""
        if act.ndim == 3:
            act = act[0]
        return jax.lax.dynamic_update_index_in_dim(
            buf, act.astype(buf.dtype), slot, 0)

    def run(self, edge_fn, buf: Array) -> Array:
        """One batched edge pass over the whole buffer: (B, S, vocab)."""
        return edge_fn(buf)


class DecodeBatcher:
    """Edge decode path with per-slot KV/state caches, reusing the
    runtime/serve.py decode-step machinery: one capacity-sized cache from
    model.make_caches, per-request prefill written into its slot at
    admission, one masked decode step per epoch for all active slots."""

    def __init__(self, model, params, capacity: int, max_len: int):
        self.model = model
        self.params = params
        self.capacity = int(capacity)
        self.max_len = int(max_len)
        self.caches = model.make_caches(capacity, max_len)
        self._prefill = jax.jit(
            lambda p, toks: model.prefill(p, {"tokens": toks}, max_len))

        def masked_step(params, caches, token, active):
            token = jnp.where(active[:, None], token, 0)
            logits, new_caches = model.decode_step(params, caches, token)
            # Inactive slots' caches are frozen (their next admission
            # overwrites them); their logits lanes are garbage by contract.
            return logits, slot_where(active, new_caches, caches)

        self._step = jax.jit(masked_step, donate_argnums=(1,))

    def admit(self, slot: int, tokens: Array) -> Array:
        """Prefill one request (tokens (1, S)) into ``slot``; returns its
        next-token logits (vocab,)."""
        logits, one = self._prefill(self.params, tokens)
        self.caches = slot_update(self.caches, slot, one)
        return logits[0]

    def step(self, token: Array, active: Array) -> Array:
        """One masked decode step: token (B, 1), active (B,) bool ->
        logits (B, vocab). Every active slot advances together."""
        logits, self.caches = self._step(self.params, self.caches, token,
                                         active)
        return logits

    def export_caches(self):
        """Host copies of the slot caches (repro.state serving snapshot):
        device_get on the caller's thread, so the returned tree is immune
        to the donated in-place update of the next step()."""
        return jax.device_get(self.caches)

    def import_caches(self, caches) -> None:
        """Restore exported slot caches. Avals must match the live caches
        (same model/capacity/max_len) or the compiled masked step would
        retrace; a mismatch raises ValueError."""
        live = jax.tree_util.tree_flatten(self.caches)
        new = jax.tree_util.tree_flatten(caches)
        if live[1] != new[1]:
            raise ValueError("cache treedef mismatch on import")
        for i, (a, b) in enumerate(zip(new[0], live[0])):
            if (jnp.shape(a) != jnp.shape(b)
                    or jnp.result_type(a) != jnp.result_type(b)):
                raise ValueError(
                    f"cache leaf {i}: got {jnp.result_type(a)}"
                    f"{list(jnp.shape(a))}, live caches have "
                    f"{jnp.result_type(b)}{list(jnp.shape(b))}")
        self.caches = jax.tree_util.tree_unflatten(
            new[1], [jnp.asarray(x) for x in new[0]])
