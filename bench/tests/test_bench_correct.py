"""The comparison that decides ``correct``, driven through a whole run on the
CPU at a tiny size (the harness's look for a chip is skipped): a sound run
passes, and the control and each planted fault a one-chip cell can have
come out not correct."""
from __future__ import annotations

import time

import jax
import pytest

from bench import faults
from bench import run as bench_run
from bench.tests import tiny

SEED = 2**32 + 7


def _run(mix="zipf", override=None, share=1):
    return bench_run.run(tiny.cell(mix=mix, share=share), SEED, 0.2, False, jax.devices()[:1],
                         time.perf_counter(), model_override=override)


@pytest.mark.parametrize("mix", ["zipf", "drifting"])
def test_sound_run_is_correct(mix):
    out = _run(mix)
    assert out["correct"], out["compared"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "compared"
    assert set(out["metrics"]) == {"samples_per_s", "hbm_peak_gb", "setup_s"}


def test_a_vocabulary_share_runs_and_is_correct():
    out = _run(share=4)
    assert out["correct"], out["compared"]


@pytest.mark.parametrize("variant", ["control", "frozen", "half_batch"])
def test_control_and_faults_are_not_correct(variant):
    out = _run(override=faults.OVERRIDES[variant][0])
    assert not out["correct"], out["compared"]
