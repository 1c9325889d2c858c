"""Benchmark harness: one module per paper table/figure + roofline + kernels.
Prints ``name,label,value,derived`` CSV lines and writes a machine-readable
``BENCH_<n>.json`` artifact (per-benchmark rows + git SHA) so the perf
trajectory is tracked across PRs. Rows may carry extra metadata keys via
``paper_common.emit(..., meta=...)`` -- the noma kernel rows record the
kernel layout (gathered in BENCH_1, gather_free from BENCH_2 on) and the
block sizes, so artifacts stay comparable across kernel redesigns.

  PYTHONPATH=src python -m benchmarks.run [--only fig2_3,...] [--json PATH]
"""
import argparse
import json
import pathlib
import re
import subprocess
import sys
import time

_ROOT = pathlib.Path(__file__).resolve().parent.parent


def _git_sha() -> str:
    """HEAD short SHA, '-dirty'-suffixed when the tree has local changes --
    a clean SHA must be able to reproduce the recorded rows."""
    try:
        sha = subprocess.run(
            ["git", "-C", str(_ROOT), "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, check=True,
        ).stdout.strip()
        # Exclude the harness's own artifacts: a fresh BENCH_<n>.json from a
        # previous run must not mark a clean source tree dirty.
        dirty = subprocess.run(
            ["git", "-C", str(_ROOT), "status", "--porcelain", "--",
             ":!BENCH_*.json"],
            capture_output=True, text=True, check=True,
        ).stdout.strip()
        return f"{sha}-dirty" if dirty else sha
    except (OSError, subprocess.CalledProcessError):
        return "unknown"


def _next_bench_path() -> pathlib.Path:
    """Auto-number the artifact: BENCH_<n>.json with n = 1 + max existing."""
    taken = [int(m.group(1)) for p in _ROOT.glob("BENCH_*.json")
             if (m := re.fullmatch(r"BENCH_(\d+)\.json", p.name))]
    return _ROOT / f"BENCH_{max(taken, default=0) + 1}.json"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benchmark names")
    ap.add_argument("--json", default=None,
                    help="path for the JSON artifact (default: auto-numbered "
                         "BENCH_<n>.json in the repo root)")
    args = ap.parse_args()

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (
        chaos_serve,
        kernel_bench,
        ligd_properties,
        online_serve,
        paper_common,
        paper_fig2_3,
        paper_fig4_5,
        paper_fig6_11,
        recovery_serve,
        roofline_report,
    )

    paper_common.ROWS.clear()    # one artifact per invocation, never stale
    all_benches = {
        "fig2_3": paper_fig2_3.run,
        "fig4_5": paper_fig4_5.run,
        "fig6_11": paper_fig6_11.run,
        "ligd_properties": ligd_properties.run,
        "kernel_bench": kernel_bench.run,
        "roofline": roofline_report.run,
        "online_serve": online_serve.run,
        "chaos_serve": chaos_serve.run,
        "recovery_serve": recovery_serve.run,
    }
    chosen = (args.only.split(",") if args.only else list(all_benches))
    t0 = time.time()
    errors = []
    print("name,label,value,derived")
    for name in chosen:
        try:
            all_benches[name]()
        except Exception as e:  # run the rest, record it, exit non-zero
            print(f"{name},ERROR,0,{type(e).__name__}: {e}", file=sys.stderr)
            print(f"{name},error,0,{type(e).__name__}")
            errors.append({"bench": name, "error": f"{type(e).__name__}: {e}"})
    elapsed = time.time() - t0
    print(f"total,elapsed_s,{elapsed:.1f},all benchmarks")

    out = pathlib.Path(args.json) if args.json else _next_bench_path()
    out.write_text(json.dumps({
        "schema": 1,
        "git_sha": _git_sha(),
        "benches": chosen,
        "elapsed_s": round(elapsed, 1),
        "rows": paper_common.ROWS,
        "errors": errors,
    }, indent=1) + "\n")
    print(f"wrote {out}", file=sys.stderr)
    if errors:
        sys.exit(f"{len(errors)} benchmark(s) failed: "
                 + ", ".join(e["bench"] for e in errors))


if __name__ == "__main__":
    main()
