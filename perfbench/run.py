#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip.

    python3 perfbench/run.py --workload vgg16-einsum.replan10 --seed 7 \
        --seconds 30 --trace 0

The cell, its configuration and its traffic come from ``BENCHMARK.json``
and the files it names. With ``--trace 0`` the last line of standard
output is one JSON object with the cell's end-to-end metrics; with
``--trace 1`` the window is traced and it carries the per-layer metrics,
the device's busy and window seconds and a breakdown. Either way it says
whether the window's last replan matched the plain reference
(``correct``), and its last key, ``checks``, holds every compared number
beside its limit; the same lines end standard error.

It exits non-zero, and prints no result, where JAX finds no TPU or fewer
chips than the cell needs. The persistent compilation cache is kept in
``.jax_cache/perfbench`` inside the checkout, whatever
``JAX_COMPILATION_CACHE_DIR`` says, so that only a cell's first run in a
checkout compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]
CACHE_DIR = CHECKOUT / ".jax_cache" / "perfbench"


def _paths() -> None:
    """Import the benchmark package and the system under test from this
    checkout, and from nowhere else."""
    for p in (str(CHECKOUT / "src"), str(CHECKOUT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import repro.core
    where = Path(repro.core.__file__).resolve()
    if not where.is_relative_to(CHECKOUT / "src"):
        raise SystemExit(f"repro imported from {where}, "
                         f"not from {CHECKOUT / 'src'}")


def configure_jax() -> None:
    """The persistent compilation cache inside the checkout, with every
    program cached; libtpu's own log files off unless asked for."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    configure_jax()
    _paths()
    from perfbench import cell as celllib
    from perfbench import spec

    cell = spec.cell(args.workload)
    result = celllib.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_START)
    for line in result.notes:
        print(line, file=sys.stderr, flush=True)
    line = {"correct": result.correct, "attempted": result.attempted,
            "failed": result.failed, "metrics": result.metrics,
            "device": result.device}
    if result.breakdown is not None:
        line["breakdown"] = result.breakdown
    line["checks"] = result.checks
    celllib.print_checks(result)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
