"""Model profiles: per-layer FLOPs + inter-layer activation sizes.

Three sources:
  * chain CNNs the paper evaluates (NiN-9, YOLOv2-17, VGG16-24), built from
    real conv arithmetic (MACs, feature-map sizes) on CIFAR-scale inputs;
  * any assigned LM architecture config (per layer, priced by the layer's
    kind: window or full attention, dense or MoE FFN), so the ECC planner
    applies to every assigned arch (DESIGN.md Sec. 5);
  * *measured* profiles produced by the closed-loop serving telemetry
    (repro.online.telemetry): EMA-smoothed effective per-layer costs under
    live traffic, rebuilt every feedback epoch via ``ModelProfile.like`` so
    they are shape-, dtype-, and name-compatible with the static profile
    here and hit the planner's already-compiled programs as plain operands.
    The static profiles below are both the planner's prior and the
    telemetry accumulator's initial state; ``ModelProfile.validate_like``
    enforces the contract once at loop start (clear layer-count error
    instead of a recompile or a failure inside a jitted trace).

Layer enumeration follows the paper's stated counts (NiN 9 / YOLOv2 17 /
VGG16 24): ReLUs are folded into their producing layer; VGG pools, flatten
and softmax are kept as explicit (cheap) layers to reach the paper's count.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core.types import ModelProfile

ACT_BITS = 16          # activations transmitted as fp16/bf16
INPUT_BITS = 8         # raw images are 8-bit per channel
RESULT_BITS_CLS = 10 * 32   # 10-class logits


def _conv_chain(layers, in_hwc, result_bits, name) -> ModelProfile:
    """layers: list of ('conv', out_c, k, stride) | ('pool', k, stride) |
    ('fc', out_dim) | ('gap',) | ('softmax',). Pools may also be folded via
    ('conv+pool', out_c, k, stride, pool_k)."""
    h, w, c = in_hwc
    fl, acts = [], []
    for spec in layers:
        kind = spec[0]
        if kind in ("conv", "conv+pool"):
            out_c, k, stride = spec[1], spec[2], spec[3]
            h = max(1, (h + stride - 1) // stride)
            w = max(1, (w + stride - 1) // stride)
            flops = 2.0 * k * k * c * out_c * h * w
            c = out_c
            if kind == "conv+pool":
                pk = spec[4]
                flops += float(h * w * c * pk * pk)
                h, w = max(1, h // pk), max(1, w // pk)
        elif kind == "pool":
            k, stride = spec[1], spec[2]
            flops = float(h * w * c * k * k)
            h, w = max(1, h // stride), max(1, w // stride)
        elif kind == "gap":
            flops = float(h * w * c)
            h, w = 1, 1
        elif kind == "fc":
            out_dim = spec[1]
            flops = 2.0 * (h * w * c) * out_dim
            h, w, c = 1, 1, out_dim
        elif kind == "norm":
            flops = 2.0 * h * w * c
        elif kind == "flatten":
            flops = 0.0
        elif kind == "softmax":
            flops = 5.0 * c
        else:
            raise ValueError(kind)
        fl.append(flops)
        acts.append(h * w * c * ACT_BITS)
    f = len(fl)
    w_bits = np.empty(f + 1)
    w_bits[0] = in_hwc[0] * in_hwc[1] * in_hwc[2] * INPUT_BITS
    w_bits[1:] = acts
    w_bits[f] = 0.0                       # split at F: nothing uploaded
    m_down = np.full(f + 1, float(result_bits))
    m_down[f] = 0.0                       # split at F: nothing comes back
    return ModelProfile(
        fl=jnp.asarray(fl, jnp.float32),
        w=jnp.asarray(w_bits, jnp.float32),
        m_down=jnp.asarray(m_down, jnp.float32),
        name=name,
    )


def nin() -> ModelProfile:
    """Network-in-Network, 9 conv/mlpconv layers (pools folded), CIFAR-10."""
    layers = [
        ("conv", 192, 5, 1), ("conv", 160, 1, 1), ("conv+pool", 96, 1, 1, 2),
        ("conv", 192, 5, 1), ("conv", 192, 1, 1), ("conv+pool", 192, 1, 1, 2),
        ("conv", 192, 3, 1), ("conv", 192, 1, 1), ("conv", 10, 1, 1),
    ]
    return _conv_chain(layers, (32, 32, 3), RESULT_BITS_CLS, "nin")


def yolov2() -> ModelProfile:
    """YOLOv2-style chain, 17 conv layers (pools folded), 64x64 input."""
    layers = [
        ("conv+pool", 32, 3, 1, 2),
        ("conv+pool", 64, 3, 1, 2),
        ("conv", 128, 3, 1), ("conv", 64, 1, 1), ("conv+pool", 128, 3, 1, 2),
        ("conv", 256, 3, 1), ("conv", 128, 1, 1), ("conv+pool", 256, 3, 1, 2),
        ("conv", 512, 3, 1), ("conv", 256, 1, 1), ("conv", 512, 3, 1),
        ("conv", 256, 1, 1), ("conv+pool", 512, 3, 1, 2),
        ("conv", 1024, 3, 1), ("conv", 512, 1, 1), ("conv", 1024, 3, 1),
        ("conv", 125, 1, 1),
    ]
    # detection output: SxSx125 fp16
    return _conv_chain(layers, (64, 64, 3), 2 * 2 * 125 * ACT_BITS, "yolov2")


def vgg16() -> ModelProfile:
    """VGG16, enumerated to the paper's 24 layers (input-norm + 13 conv +
    5 pool + flatten + 3 fc + softmax)."""
    layers = [
        ("norm",),
        ("conv", 64, 3, 1), ("conv", 64, 3, 1), ("pool", 2, 2),
        ("conv", 128, 3, 1), ("conv", 128, 3, 1), ("pool", 2, 2),
        ("conv", 256, 3, 1), ("conv", 256, 3, 1), ("conv", 256, 3, 1), ("pool", 2, 2),
        ("conv", 512, 3, 1), ("conv", 512, 3, 1), ("conv", 512, 3, 1), ("pool", 2, 2),
        ("conv", 512, 3, 1), ("conv", 512, 3, 1), ("conv", 512, 3, 1), ("pool", 2, 2),
        ("flatten",),
        ("fc", 512), ("fc", 512), ("fc", 10),
        ("softmax",),
    ]
    return _conv_chain(layers, (32, 32, 3), RESULT_BITS_CLS, "vgg16")


PAPER_MODELS = {"nin": nin, "yolov2": yolov2, "vgg16": vgg16}


# --------------------------------------------------------------------------
# LM architecture profiles (per layer, by the layer's kind)
# --------------------------------------------------------------------------
TOKEN_BITS = 32        # token ids travel as int32


def _keys(seq: int, decode: int, window: int) -> float:
    """Query-key pairs one attention layer scores for a request: a prefill
    of ``seq`` queries over ``min(seq, window)`` keys each (the whole
    prompt's scores, as the chunked kernel computes them), then ``decode``
    steps, step t over the ``seq + t + 1`` keys of its context, at most
    ``window`` (0 = no window)."""
    ctx = np.arange(seq + 1, seq + decode + 1, dtype=np.float64)
    if window:
        ctx = np.minimum(ctx, window)
    return float(seq) * (min(seq, window) if window else seq) + float(ctx.sum())


def from_arch_config(cfg, seq: int, batch: int = 1, decode: int = 0,
                     act_bits: int = ACT_BITS, token_bits: int = TOKEN_BITS,
                     flop_unit: float = 1.0) -> ModelProfile:
    """Per-layer profile of an LM arch for ``batch`` requests of ``seq``
    prompt tokens and ``decode`` generated tokens, each layer priced by its
    kind from the model's stage specs (``models.blocks.stages_for``):

    * attention: the q/k/v/o projections of every token, plus 4 FLOPs per
      query-key pair and head dimension (scores and AV) over the layer's
      window (:func:`_keys`);
    * the FFN: a dense SwiGLU (or GELU) of width ``d_ff``, or the MoE's
      router plus its ``top_k + n_shared_experts`` active experts.

    Stages of another kind (recurrent, cross-attention, encoder, decoder)
    are priced as a full-attention layer.

    w[s] is the residual stream of all ``seq + decode`` positions crossing
    the split (``act_bits`` each), w[0] the prompt's token ids, w[F] = 0;
    the result (m_down) is the generated token ids, or the last position's
    logits when ``decode`` is 0, and nothing at s = F. FLOPs are counted
    in units of ``flop_unit`` (the compute constants' unit)."""
    from repro.models.blocks import stages_for
    d, hd = cfg.d_model, cfg.hd
    tokens = float(seq + decode)
    proj = 2.0 * tokens * d * (2 * cfg.n_heads + 2 * cfg.n_kv_heads) * hd
    ffn_per_token = (6.0 if cfg.act == "swiglu" else 4.0) * d
    fl = []
    for spec in stages_for(cfg):
        core = 4.0 * _keys(seq, decode, spec.window) * cfg.n_heads * hd
        if spec.moe:
            active = cfg.top_k + cfg.n_shared_experts
            ffn = tokens * (ffn_per_token * cfg.moe_d_ff * active
                            + 2.0 * d * cfg.n_experts)
        else:
            ffn = tokens * ffn_per_token * cfg.d_ff
        fl += [(proj + core + ffn) * batch / flop_unit] * spec.n_layers
    n = len(fl)
    w = np.full(n + 1, batch * tokens * d * act_bits)
    w[0] = batch * seq * float(token_bits)
    w[n] = 0.0
    result = (decode * token_bits if decode else cfg.vocab_size * act_bits)
    m_down = np.full(n + 1, float(batch * result))
    m_down[n] = 0.0
    return ModelProfile(
        fl=jnp.asarray(fl, jnp.float32),
        w=jnp.asarray(w, jnp.float32),
        m_down=jnp.asarray(m_down, jnp.float32),
        name=getattr(cfg, "name", "lm"),
    )


def lm(arch: dict, seq: int, decode: int = 0, batch: int = 1,
       act_bits: int = ACT_BITS, token_bits: int = TOKEN_BITS,
       flop_unit: float = 1.0) -> ModelProfile:
    """:func:`from_arch_config` from JSON arguments: ``arch`` holds the
    ``ArchConfig`` fields (a configuration file's ``model.profile_args``)."""
    from repro.configs import ArchConfig
    return from_arch_config(ArchConfig(**arch), seq, batch, decode,
                            act_bits, token_bits, flop_unit)
