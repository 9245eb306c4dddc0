"""The ``move_rounds_per_step`` reader: the window delta of the trainer hub's
``cache_move_rounds`` counter over the window's steps."""
from __future__ import annotations

import jax
import pytest

from bench import run as bench_run
from bench import spec


def _record(window_steps, misses):
    return bench_run.RunRecord(
        cell=None, device_kind="cpu", chips=1, batch_size=1, flops_per_sample=1.0,
        window_steps=window_steps, window_s=1.0, samples_per_s=1.0, step_times_s=[1.0],
        hits=None, misses=misses, devices={})


def test_move_rounds_per_step_reads_the_trainers_window():
    """The reader gives the window delta of ``cache_move_rounds`` per window
    step; a record no live trainer's window matches, or one without window
    steps, gives nothing."""
    from repro.train.trainer import Trainer, TrainerConfig
    from bench.tests import tiny
    from bench.families import dlrm as fam

    cell = tiny.cell()
    system = fam.build(cell.config, cell.mix, 12, jax.devices()[:1])
    trainer = Trainer(TrainerConfig(max_steps=6), init_fn=system.init,
                      step_fn=system.step, make_batch=system.make_batch)
    trainer.run()
    first, last = trainer.history[2], trainer.history[-1]
    misses = last["cache_misses"] - first["cache_misses"]
    rounds = last["cache_move_rounds"] - first["cache_move_rounds"]
    assert rounds == 3  # one round a step: the tiny cell's lanes fit one buffer
    reader = spec.metric_reader("move_rounds_per_step")
    assert reader.read(_record(3, misses)) == pytest.approx(1.0)
    assert reader.read(_record(3, misses + 1)) is None
    assert reader.read(_record(0, misses)) is None
