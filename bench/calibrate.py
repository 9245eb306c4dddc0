"""Readings from which a configuration's correctness limits are set.

    python3 bench/calibrate.py --workload <name> --seeds 101-112 \
        --variants sound,control,half_batch [--control-seeds 3]

In one process, for each variant and seed, runs the cell's set-up and first
steps exactly as ``run.py`` does (no measured window: one window step) and
prints one JSON line with the compared numbers: ``sound`` is the program as
the configuration states it (the lower readings), the others are the control
and the planted faults of ``faults.py`` (the upper readings).  A variant
that crashes is recorded as failed and sets no reading.  Needs the chips the
cell asks for; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import faults, spec  # noqa: E402
from bench import run as bench_run  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


def _seeds(text: str):
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi) + 1)) if hi else [int(lo)]
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 101-112 or 5,9,11")
    ap.add_argument("--variants", default="sound,control,half_batch")
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="seeds (the first of --seeds) for each non-sound variant")
    args = ap.parse_args()
    import jax

    cell = spec.cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"calibrate: needs {cell.chips} TPU chip(s)", file=sys.stderr)
        return 2
    devices = devices[: cell.chips]
    enable_compile_cache()
    seeds = _seeds(args.seeds)
    for variant in args.variants.split(","):
        override, context = (None, None) if variant == "sound" else faults.OVERRIDES[variant]
        for seed in seeds if variant == "sound" else seeds[: args.control_seeds]:
            t = time.perf_counter()
            rec = {"workload": cell.name, "variant": variant, "seed": seed}
            try:
                if context is None:
                    out = bench_run.run(cell, seed, 0.0, False, devices, time.perf_counter())
                else:
                    with context():
                        out = bench_run.run(cell, seed, 0.0, False, devices,
                                            time.perf_counter(), model_override=override)
                rec.update(readings={k: v["value"] for k, v in out["compared"].items()},
                           correct=out["correct"], detail=out["detail"],
                           setup_s=out["metrics"]["setup_s"]["value"])
            except Exception as e:  # noqa: BLE001 - a crash is a failed variant
                rec.update(failed=f"{type(e).__name__}: {e}"[:2000])
                traceback.print_exc()
            rec["seconds"] = time.perf_counter() - t
            print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
