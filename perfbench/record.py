#!/usr/bin/env python3
"""Trace a few epochs of one cell on the chip and keep the trace, with its
breakdown by the program's own marks (``perfbench/spans.py``).

    python3 perfbench/record.py --workload vgg16-einsum.replan10 --seed 7 \
        --epochs 12 --out chiprun_out/einsum.xplane.pb [--tiny]

The loop is built and warmed up as ``run.py`` builds it (``--tiny`` cuts
the deployment to the CPU rehearsal's, still on the chip), then
``--epochs`` epochs run under the profiler with ``run.py``'s options,
marked with the same window and epoch spans. The ``.xplane.pb`` is written
to ``--out``; the last line of standard output is one JSON object: the
epochs, replans and GD iterations of the traced epochs and the breakdown of
:func:`perfbench.spans.report`. It checks nothing, and exits non-zero
where JAX finds no TPU.

It exists because ``run.py`` deletes its trace before the readers run, so
no reader sees the name stacks of the ops (``spans.op_scopes``) that the
solver-phase split needs.
"""
import argparse
import copy
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--epochs", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)

    for p in (str(CHECKOUT / "src"), str(CHECKOUT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from perfbench.run import configure_jax
    configure_jax()
    import jax

    from perfbench import cell as celllib
    from perfbench import rehearse, spans, spec
    from perfbench import trace as tracelib

    if jax.devices()[0].platform != "tpu":
        raise SystemExit(
            f"no TPU: JAX platform is {jax.devices()[0].platform!r}")
    cell = spec.cell(args.workload)
    cfg = copy.deepcopy(cell.config)
    if args.tiny:
        cfg["deployment"] = dict(rehearse.TINY_DEPLOYMENT)
    jax.config.update("jax_default_matmul_precision",
                      cfg["precision"]["matmul_precision"])
    loop = celllib.build(cfg, cell.traffic)
    loop.reset(celllib.key_from_seed(args.seed))
    for _ in range(cell.traffic["service"]["replan_every"]):
        out, _ = loop.step_epoch()
        jax.block_until_ready((out.health, loop.server.state.plan.utility))
    iters0, replans0 = loop.server.total_iters, loop.server.replans

    trace_dir = tempfile.mkdtemp(prefix="perfbench-record-")
    try:
        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation(tracelib.WINDOW):
            for _ in range(args.epochs):
                with jax.profiler.TraceAnnotation(tracelib.EPOCH):
                    out, _ = loop.step_epoch()
                    jax.block_until_ready(
                        (out.health, loop.server.state.plan.utility))
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        stop_s = time.perf_counter() - t_stop
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        shutil.move(tracelib.find_xplane(trace_dir), args.out)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)

    line = {"workload": cell.name, "tiny": args.tiny, "epochs": args.epochs,
            "replans": loop.server.replans - replans0,
            "gd_iters": loop.server.total_iters - iters0,
            "stop_s": stop_s, "device": jax.devices()[0].device_kind}
    line.update(spans.report(tracelib.load(args.out),
                             spans.op_scopes(args.out)))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
