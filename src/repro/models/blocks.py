"""Residual blocks: one param-def + apply pair per block kind.

Kinds:
  attn    pre-norm GQA self-attention + MLP (optionally MoE, optionally
          local-window; the moe family tiles its window/full pattern)
  cross   cross-attention block (VLM image layers / used inside dec)
  enc     bidirectional attention + MLP, LayerNorm (whisper encoder)
  dec     causal self-attn + cross-attn + MLP, LayerNorm (whisper decoder)
  rec     RG-LRU temporal-mixing block + MLP (recurrentgemma)
  mlstm / slstm   xLSTM blocks

block_apply(cfg, spec, p, x, aux, cache) -> (x, new_cache, aux_loss)
`aux` carries {"pos": (B,S), "frontend": (B,Sf,D) or None}.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro.models import attention, moe, recurrent, xlstm
from repro.models.layers import (
    COMPUTE_DTYPE,
    ParamDef,
    layer_norm,
    mlp_apply,
    mlp_defs,
    rms_norm,
)


@dataclasses.dataclass(frozen=True)
class StageSpec:
    kind: str
    n_layers: int
    moe: bool = False
    window: int = 0
    causal: bool = True
    cache: str | None = "kv"     # kv | rglru | mlstm | slstm | None


def _norm_defs(cfg, name, layernorm=False):
    if layernorm:
        return {
            f"{name}_w": ParamDef((cfg.d_model,), ("embed",), init="ones"),
            f"{name}_b": ParamDef((cfg.d_model,), ("embed",), init="zeros"),
        }
    return {f"{name}_w": ParamDef((cfg.d_model,), ("embed",), init="zeros")}


def _norm(cfg, p, name, x, layernorm=False):
    if layernorm:
        return layer_norm(x, p[f"{name}_w"], p[f"{name}_b"], cfg.norm_eps)
    return rms_norm(x, p[f"{name}_w"], cfg.norm_eps)


def block_defs(cfg, spec: StageSpec) -> dict:
    ln = cfg.family == "audio"
    d: dict = {}
    if spec.kind in ("attn", "enc", "dec"):
        d.update(_norm_defs(cfg, "ln1", ln))
        d["attn"] = attention.attn_defs(cfg)
        if spec.kind == "dec":
            d.update(_norm_defs(cfg, "lnx", ln))
            d["xattn"] = attention.attn_defs(cfg, cross=True)
        d.update(_norm_defs(cfg, "ln2", ln))
        if spec.moe:
            d["moe"] = moe.moe_defs(cfg)
        else:
            d["mlp"] = mlp_defs(cfg.d_model, cfg.d_ff, cfg.act)
    elif spec.kind == "cross":
        d.update(_norm_defs(cfg, "ln1", ln))
        d["xattn"] = attention.attn_defs(cfg, cross=True)
        d["xgate"] = ParamDef((1,), (None,), init="zeros")
        d.update(_norm_defs(cfg, "ln2", ln))
        d["mlp"] = mlp_defs(cfg.d_model, cfg.d_ff, cfg.act)
    elif spec.kind == "rec":
        d.update(_norm_defs(cfg, "ln1", ln))
        d["rglru"] = recurrent.rglru_defs(cfg)
        d.update(_norm_defs(cfg, "ln2", ln))
        d["mlp"] = mlp_defs(cfg.d_model, cfg.d_ff, cfg.act)
    elif spec.kind == "mlstm":
        d.update(_norm_defs(cfg, "ln1", ln))
        d["mlstm"] = xlstm.mlstm_defs(cfg)
    elif spec.kind == "slstm":
        d.update(_norm_defs(cfg, "ln1", ln))
        d["slstm"] = xlstm.slstm_defs(cfg)
    else:
        raise ValueError(spec.kind)
    return d


def block_apply(cfg, spec: StageSpec, p: dict, x, aux: dict, cache=None):
    """Returns (x, new_cache, aux_loss)."""
    ln = cfg.family == "audio"
    pos = aux["pos"]
    zero = jnp.zeros((), jnp.float32)
    new_cache = None

    if spec.kind in ("attn", "enc", "dec"):
        h, kv_cache = attention.attn_apply(
            p["attn"], _norm(cfg, p, "ln1", x, ln), cfg, pos,
            cache=None if cache is None else cache.get("kv"),
            causal=spec.causal, window=spec.window,
        )
        x = x + h
        if spec.kind == "dec":
            hx, _ = attention.attn_apply(
                p["xattn"], _norm(cfg, p, "lnx", x, ln), cfg, pos,
                kv_src=aux["frontend"], causal=False,
            )
            x = x + hx
        aux_l = zero
        if spec.moe:
            y, aux_l = moe.moe_apply(
                p["moe"], _norm(cfg, p, "ln2", x, ln), cfg,
                impl=aux.get("moe_impl", "sorted"),
                capacity_factor=aux.get("moe_capacity", 1.25),
            )
        else:
            y = mlp_apply(p["mlp"], _norm(cfg, p, "ln2", x, ln), cfg.act)
        x = x + y
        if kv_cache is not None:
            new_cache = {"kv": kv_cache}
        return x, new_cache, aux_l

    if spec.kind == "cross":
        hx, _ = attention.attn_apply(
            p["xattn"], _norm(cfg, p, "ln1", x, ln), cfg, pos,
            kv_src=aux["frontend"], causal=False,
        )
        x = x + jnp.tanh(p["xgate"].astype(jnp.float32)).astype(x.dtype) * hx
        y = mlp_apply(p["mlp"], _norm(cfg, p, "ln2", x, ln), cfg.act)
        return x + y, (None if cache is None else {}), zero

    if spec.kind == "rec":
        h, st = recurrent.rglru_apply(
            p["rglru"], _norm(cfg, p, "ln1", x, ln), cfg,
            state=None if cache is None else cache.get("rglru"),
        )
        x = x + h
        y = mlp_apply(p["mlp"], _norm(cfg, p, "ln2", x, ln), cfg.act)
        x = x + y
        return x, (None if st is None else {"rglru": st}), zero

    if spec.kind == "mlstm":
        h, st = xlstm.mlstm_apply(
            p["mlstm"], _norm(cfg, p, "ln1", x, ln), cfg,
            state=None if cache is None else cache.get("mlstm"),
        )
        return x + h, (None if st is None else {"mlstm": st}), zero

    if spec.kind == "slstm":
        h, st = xlstm.slstm_apply(
            p["slstm"], _norm(cfg, p, "ln1", x, ln), cfg,
            state=None if cache is None else cache.get("slstm"),
        )
        return x + h, (None if st is None else {"slstm": st}), zero

    raise ValueError(spec.kind)


def stages_for(cfg) -> list[StageSpec]:
    """Build the stage list (consecutive same-kind blocks grouped) that
    realizes each assigned architecture's topology."""
    fam = cfg.family
    if fam in ("dense",):
        return [StageSpec("attn", cfg.n_layers)]
    if fam == "moe":
        # leading dense layers, then experts; each layer's window from the
        # window/full pattern (cfg.layer_window)
        stages = []
        for i in range(cfg.n_layers):
            spec = StageSpec("attn", 1, moe=i >= cfg.first_dense_layers,
                             window=cfg.layer_window(i))
            if stages and dataclasses.replace(stages[-1], n_layers=1) == spec:
                stages[-1] = dataclasses.replace(
                    stages[-1], n_layers=stages[-1].n_layers + 1)
            else:
                stages.append(spec)
        return stages
    if fam == "hybrid":
        # tile block_pattern (e.g. rec,rec,attn) over depth, grouping runs
        pattern = cfg.block_pattern
        kinds = [pattern[i % len(pattern)] for i in range(cfg.n_layers)]
        stages = []
        for k in kinds:
            spec = StageSpec(
                "rec" if k == "rec" else "attn",
                1,
                window=cfg.window if k == "attn" else 0,
                cache="rglru" if k == "rec" else "kv",
            )
            if stages and stages[-1].kind == spec.kind:
                stages[-1] = dataclasses.replace(
                    stages[-1], n_layers=stages[-1].n_layers + 1)
            else:
                stages.append(spec)
        return stages
    if fam == "ssm":
        pattern = cfg.block_pattern
        kinds = [pattern[i % len(pattern)] for i in range(cfg.n_layers)]
        stages = []
        for k in kinds:
            spec = StageSpec(k, 1, cache=k)
            if stages and stages[-1].kind == spec.kind:
                stages[-1] = dataclasses.replace(
                    stages[-1], n_layers=stages[-1].n_layers + 1)
            else:
                stages.append(spec)
        return stages
    if fam == "vlm":
        # every cross_attn_every-th layer is followed by a cross block
        period = cfg.cross_attn_every
        n_cross = cfg.n_layers // period
        n_self = cfg.n_layers - n_cross
        stages = []
        self_per_group = period - 1
        done_self = 0
        for _ in range(n_cross):
            take = min(self_per_group, n_self - done_self)
            if take:
                stages.append(StageSpec("attn", take))
                done_self += take
            stages.append(StageSpec("cross", 1, cache=None))
        if done_self < n_self:
            stages.append(StageSpec("attn", n_self - done_self))
        return stages
    if fam == "audio":
        return [
            StageSpec("enc", cfg.encoder_layers, causal=False, cache=None),
            StageSpec("dec", cfg.n_layers),
        ]
    raise ValueError(fam)
