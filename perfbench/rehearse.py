#!/usr/bin/env python3
"""CPU rehearsal of the benchmark: every path of a run, without the chip.

    JAX_PLATFORMS=cpu python3 perfbench/rehearse.py

It runs every cell of ``BENCHMARK.json``, cut by :func:`tiny` to 24 users,
3 APs and 8 subchannels with the kernels in the Pallas interpreter,
through the same set-up, window, trace reduction, metric readers and
reference check as ``run.py``, with and without the trace and with the
bfloat16 control. It prints whether each run was
correct, its compared numbers and which metric readers found something to
read, but no metric's value: a CPU run gives no device number.
"""
import time

T_START = time.perf_counter()

import copy  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]
TINY_DEPLOYMENT = {"n_users": 24, "n_aps": 3, "n_sub": 8}


def tiny(cell):
    """``cell`` (a ``spec.Cell``) cut to :data:`TINY_DEPLOYMENT`, with the
    Pallas kernels run by the interpreter; everything else as its files
    state it."""
    cfg = copy.deepcopy(cell.config)
    cfg["deployment"] = dict(TINY_DEPLOYMENT)
    if cfg["planner"]["sinr_backend"] == "pallas":
        cfg["planner"]["sinr_backend"] = "pallas_interpret"
    return cell._replace(config=cfg)


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    for p in (str(CHECKOUT / "src"), str(CHECKOUT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    from perfbench import cell as celllib
    from perfbench import spec

    bench = spec.load_json(spec.BENCHMARK)
    ok = True
    for w in bench["workloads"]:
        for trace in (0, 1):
            c = tiny(spec.cell(w["name"]))
            r = celllib.run(c, seed=2**31 + 11, seconds=0.5, trace=bool(trace),
                            t_start=time.perf_counter(), require_tpu=False,
                            control=not trace)
            print(f"rehearsal {w['name']} trace={trace} correct={r.correct} "
                  f"attempted={r.attempted} failed={r.failed} "
                  f"readers_with_a_value={sorted(r.metrics)}", flush=True)
            for name, chk in r.checks.items():
                print(f"  check {name} = {chk['value']!r} "
                      f"(limit {chk['limit']!r})", flush=True)
            ok &= r.correct
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
