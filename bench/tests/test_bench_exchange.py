"""On four virtual CPU devices, a sharded tiny cell is correct, and with the
exchange between shards left out it is not."""
from __future__ import annotations

import json
import os
import subprocess
import sys

from bench import spec

SCRIPT = """
import json, time, jax
from bench import faults, run as bench_run
from bench.tests import tiny
out = {}
for name, ctx in (("sound", None), ("no_exchange", faults.no_exchange)):
    args = (tiny.cell(model_shards=4), 2**31 + 3, 0.2, False, jax.devices()[:4], time.perf_counter())
    if ctx is None:
        r = bench_run.run(*args)
    else:
        with ctx():
            r = bench_run.run(*args)
    out[name] = {"correct": r["correct"], "compared": r["compared"]}
print(json.dumps(out))
"""


def test_sharded_cell_needs_the_exchange():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(spec.ROOT / "src"), str(spec.ROOT)]))
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env, capture_output=True,
                          text=True, timeout=600, cwd=spec.ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sound"]["correct"], out
    assert not out["no_exchange"]["correct"], out
