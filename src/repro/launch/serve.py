"""Serving driver with ECC split inference.

The ECC planner (the paper's contribution) picks the split layer s* and the
radio resource allocation for a fleet of devices sharing a NOMA cell; the
runtime then builds the device-side and edge-side programs and serves
batched requests, reporting per-phase times including the simulated NOMA
uplink.

Example (CPU, reduced config):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --reduced \
      --requests 4 --seq 64 --new-tokens 8

main() returns the model, params, programs, plan, prompt, first edge
logits and generated tokens, so a caller can check the run
(chip_smoke.py).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.core import GdConfig, make_env, make_weights, profiles
from repro.data import make_batch
from repro.launch.cache import enable_compile_cache
from repro.models import Model
from repro.planning import PlannerEngine
from repro.runtime.serve import make_split_serve, transfer_seconds
from repro.core import channel


def main(argv=None) -> dict:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--users", type=int, default=12)
    ap.add_argument("--aps", type=int, default=3)
    ap.add_argument("--subchannels", type=int, default=4)
    ap.add_argument("--w-delay", type=float, default=0.7)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    # 1. ECC planning over the arch's per-block profile. The PlannerEngine
    # owns the compiled solver; a serving deployment keeps it around and
    # replan()s the returned state as the channel evolves.
    env = make_env(jax.random.PRNGKey(args.seed), args.users, args.aps,
                   args.subchannels)
    prof = profiles.from_arch_config(cfg, seq=args.seq)
    w = make_weights(env.n_users, args.w_delay)
    engine = PlannerEngine(prof, weights=w, cfg=GdConfig(max_iters=150))
    plan = engine.plan(env).plan
    s = int(plan.s)
    r_up, _ = channel.user_rates(
        env,
        jax.nn.one_hot(plan.sub_up, env.n_sub),
        jax.nn.one_hot(plan.sub_dn, env.n_sub),
        plan.p_up, plan.p_dn,
    )
    rate0 = float(r_up[0])
    print(f"[plan] split layer s*={s}/{cfg.n_layers}, "
          f"uplink rate {rate0 / 1e6:.2f} Mb/s, "
          f"utility {float(plan.utility):.4f}")

    # 2. build device/edge programs
    model = Model(cfg, remat=False, moe_capacity=4.0)
    params = model.init(jax.random.PRNGKey(1))
    progs = make_split_serve(model, params, s)

    # 3. serve batched requests
    batch = make_batch(args.seed, 0, args.requests, args.seq, cfg.vocab_size)
    tokens = batch["tokens"]
    t0 = time.time()
    act = jax.block_until_ready(progs.device_fn(tokens))
    t_dev = time.time() - t0
    t_link = transfer_seconds(tokens.size, cfg.d_model, rate0)
    t0 = time.time()
    logits = jax.block_until_ready(progs.edge_fn(act))
    t_edge = time.time() - t0
    nxt = jnp.argmax(logits[:, -1], -1)
    print(f"[serve] {args.requests} reqs x {args.seq} tok: device {t_dev:.3f}s"
          f" + NOMA uplink {t_link:.3f}s (simulated) + edge {t_edge:.3f}s")
    print(f"[serve] first new tokens: {jax.device_get(nxt)[:8]}")

    # greedy continuation (device-side embedding, edge-side rest — each new
    # token repeats the split path)
    seq, generated = tokens, [nxt]
    for _ in range(args.new_tokens - 1):
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
        nxt = jnp.argmax(progs.edge_fn(progs.device_fn(seq))[:, -1], -1)
        generated.append(nxt)
    print(f"[serve] generated {args.new_tokens} tokens/request; done")
    return {"model": model, "params": params, "plan": plan,
            "tokens": tokens, "logits": logits,
            "generated": jnp.stack(generated, axis=1)}


if __name__ == "__main__":
    main()
