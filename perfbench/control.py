#!/usr/bin/env python3
"""Readings for the limits of ``correct``: the program and the bfloat16
control on many seeds of one cell, on the chip, in one process.

    python3 perfbench/control.py --workload vgg16-einsum.replan10 \
        --seeds 11,12,13 --seconds 2

Each seed runs the cell as ``run.py`` does (a short window at the cell's
own load, then the comparison of its last replan), with the control's
numbers beside the program's. One JSON line per seed, then the largest
reading of the program (the lower reading of each limit) and the smallest
of the control (the upper one). The planner engine and its compiled
programs are built once and reused across seeds. The benchmark's own runs
never run the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from perfbench import run as runmod  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)

    runmod.configure_jax()
    runmod._paths()
    from perfbench import cell as celllib
    from perfbench import spec

    cell = spec.cell(args.workload)
    engine = celllib.make_loop(cell.module, cell.config, cell.traffic).engine
    low, high = {}, {}
    for seed in (int(s) for s in args.seeds.split(",")):
        r = celllib.run(cell, seed, args.seconds, False, time.perf_counter(),
                        control=True, engine=engine)
        prog = {k: v["value"] for k, v in r.checks.items()
                if not k.startswith("control.")}
        ctrl = {k[len("control."):]: v["value"] for k, v in r.checks.items()
                if k.startswith("control.")}
        print(json.dumps({"seed": seed, "correct": r.correct,
                          "attempted": r.attempted, "program": prog,
                          "control": ctrl, "notes": r.notes}), flush=True)
        for k, v in prog.items():
            low[k] = max(low.get(k, v), v)
        for k, v in ctrl.items():
            high[k] = min(high.get(k, v), v)
    print(json.dumps({"lower_readings": low, "upper_readings": high}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
