"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (and mirrors them into
results/bench.csv).  Usage: ``PYTHONPATH=src python -m benchmarks.run``
(optionally ``--only fig9``).

``--json PATH`` additionally APPENDS a machine-readable record — per-bench
medians, git sha, timestamp, smoke flag — to a JSON list at PATH, so runs
accumulate into a perf trajectory (e.g. ``BENCH_PR3.json`` checked in per
PR; regressions become a diff, not an anecdote).
"""
from __future__ import annotations

import argparse
import datetime
import json
import pathlib
import subprocess
import sys


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, cwd=pathlib.Path(__file__).parent,
        ).stdout.strip() or "unknown"
    except OSError:
        return "unknown"


def append_json_record(path: pathlib.Path, rows, smoke: bool) -> None:
    """Append one result record to the JSON list at ``path`` (created if
    missing; a corrupt/non-list file is replaced rather than crashing the
    bench run)."""
    record = {
        "git_sha": _git_sha(),
        "timestamp": datetime.datetime.now(datetime.timezone.utc).isoformat(),
        "smoke": smoke,
        "results": {n: {"us_per_call": round(u, 1), "derived": d} for n, u, d in rows},
    }
    history = []
    if path.exists():
        try:
            loaded = json.loads(path.read_text())
            if isinstance(loaded, list):
                history = loaded
        except (json.JSONDecodeError, OSError):
            pass
    history.append(record)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(history, indent=2) + "\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="substring filter on module.function (e.g. cache_ops)")
    ap.add_argument("--skip-scaling", action="store_true",
                    help="skip the multi-process scaling benchmark")
    ap.add_argument("--strict", action="store_true",
                    help="re-raise benchmark failures (CI smoke mode)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="append a machine-readable result record (per-bench "
                         "medians + git sha + timestamp) to a JSON list file")
    args = ap.parse_args()

    from benchmarks import bench_cache_ops, bench_drift, bench_figures, bench_scaling
    from benchmarks.common import SMOKE, Table

    fns = list(bench_figures.ALL) + list(bench_cache_ops.ALL) + list(bench_drift.ALL)
    if not args.skip_scaling:
        fns += list(bench_scaling.ALL)

    t = Table()
    print("name,us_per_call,derived")
    failed = []
    for fn in fns:
        if args.only and args.only not in f"{fn.__module__}.{fn.__name__}":
            continue
        try:
            fn(t)
        except Exception as e:  # finish the other benches, then exit non-zero
            if args.strict:
                raise
            failed.append(fn.__name__)
            t.add(f"{fn.__name__}/ERROR", 0.0, f"{type(e).__name__}: {e}")
    out = pathlib.Path(__file__).resolve().parents[1] / "results" / "bench.csv"
    out.parent.mkdir(exist_ok=True)
    out.write_text("name,us_per_call,derived\n" + "\n".join(
        f"{n},{u:.1f},{d}" for n, u, d in t.rows) + "\n")
    if args.json:
        append_json_record(pathlib.Path(args.json), t.rows, SMOKE)
    if failed:
        sys.exit(f"benchmarks failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
