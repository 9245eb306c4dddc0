"""The benchmark on a host-resident slow tier, on the CPU at a tiny size: a
run whose slow tier does not fit the device is correct, one whose host leg
drops its write-backs is not, and the two host-link readers read what the
host leg leaves."""
from __future__ import annotations

import dataclasses
import time
from unittest import mock

import jax
import pytest

from bench import host_link, layers, spec
from bench import run as bench_run
from bench import trace as tr
from bench.tests import tiny

SEED = 2**33 + 15
LIMIT = 20_000_000  # a device of 20 MB: the wide cell's 87 MB slow tier does not fit


def wide_cell():
    """The tiny cell with rows of 128 float32 values (512 B), the narrowest
    the host tier takes; listed in ``reduced`` as a configuration file
    lists such a change."""
    cell = tiny.cell()
    cfg = dict(cell.config, embed_dim=128, bottom_mlp=[64, 128],
               reduced=["embed_dim", "bottom_mlp"],
               published={"embed_dim": 32, "bottom_mlp": [64, 32]})
    return dataclasses.replace(cell, config=cfg)


def host_tier(model):
    """The same model, built where the device reports ``LIMIT`` bytes: its
    slow tier goes to host memory."""
    from repro.core import collection as col

    with mock.patch.object(col, "device_bytes_limit", lambda: LIMIT):
        return type(model)(model.cfg)


def _run(override):
    return bench_run.run(wide_cell(), SEED, 0.2, False, jax.devices()[:1],
                         time.perf_counter(), model_override=override)


def test_host_tier_run_is_correct():
    seen = []

    def override(model):
        model = host_tier(model)
        seen.append(model.collection.slow_tier_memory)
        return model

    out = _run(override)
    assert seen == ["pinned_host"]
    assert out["correct"], out["compared"]


def test_host_tier_run_without_write_back_is_not_correct(monkeypatch):
    from repro.store import HostStore

    monkeypatch.setattr(HostStore, "scatter_block", lambda self, *a, **kw: self)
    out = _run(host_tier)
    assert not out["correct"], out["compared"]


HLO = """HloModule jit_train_step, is_scheduled=true

%fused_pack (p0: s32[8]) -> s32[8] {
  %p0 = s32[8]{0} parameter(0)
  ROOT %clamp.1 = s32[8]{0} clamp(s32[8]{0} %p0, s32[8]{0} %p0, s32[8]{0} %p0), metadata={op_name="jit(train_step)/cache.movement/while/body/cache.host_link/jit(clip)/min"}
}

ENTRY %main (i: s32[8], t: f32[16,4]) -> f32[8] {
  %i = s32[8]{0} parameter(0)
  %t = f32[16,4]{1,0} parameter(1)
  %fusion.1 = s32[8]{0} fusion(s32[8]{0} %i), kind=kLoop, calls=%fused_pack
  %dynamic-slice-start.2 = f32[8,4]{1,0} dynamic-slice-start(f32[16,4]{1,0} %t, s32[8]{0} %fusion.1), dynamic_slice_sizes={8,4}, metadata={op_name="jit(train_step)/cache.movement/while/body/cache.host_link/while/body/dynamic_slice"}
  %dynamic-slice-done.3 = f32[8,4]{1,0} dynamic-slice-done(f32[8,4]{1,0} %dynamic-slice-start.2), metadata={op_name="jit(train_step)/cache.movement/while/body/cache.host_link/while/body/dynamic_slice"}
  %scatter.4 = f32[16,4]{1,0} scatter(f32[16,4]{1,0} %t, s32[8]{0} %i, f32[8,4]{1,0} %dynamic-slice-done.3), metadata={op_name="jit(train_step)/cache.movement/while/body/scatter"}
  ROOT %neg.5 = s32[8]{0} negate(s32[8]{0} %i), metadata={op_name="jit(train_step)/cache.plan/neg"}
}
"""


def _record(by_op, window_steps=2):
    busy = sum(by_op.values())
    return bench_run.RunRecord(
        cell=None, device_kind="cpu", chips=1, batch_size=1, flops_per_sample=1.0,
        window_steps=window_steps, window_s=1.0, samples_per_s=1.0, step_times_s=[1.0],
        hits=None, misses=None, devices={0: tr.DeviceWindow(busy, busy, {}, dict(by_op), [])})


def test_host_link_ops_are_the_ops_under_its_scope():
    assert host_link.link_ops(HLO) == {"clamp.1", "fusion.1", "dynamic-slice-start.2",
                                       "dynamic-slice-done.3"}
    # the movement keeps them too: the link nests in it
    assert layers.hlo_scopes(HLO)["dynamic-slice-done.3"] == "cache.movement"
    assert layers.scope_of("a/cache.movement/cache.host_link/b") == "cache.movement"


def test_host_link_time_is_the_scoped_ops_per_step():
    run = _record({"fusion.1": 1e6, "dynamic-slice-start.2": 2e6, "dynamic-slice-done.3": 5e6,
                   "scatter.4": 7e6, "neg.5": 3e6})
    assert host_link.host_link_ms_per_step(run, [HLO]) == pytest.approx(4.0)
    without = HLO.replace("cache.host_link", "cache.hostlink")
    assert host_link.host_link_ms_per_step(run, [without]) is None
    assert host_link.host_link_ms_per_step(_record({}, 0), [HLO]) is None


def test_host_link_readers_are_found_by_name():
    bench = spec.load_benchmark()
    cell = spec.cell("dlrm-criteo-host.zipf")
    names = {m["name"] for m in cell.per_layer}
    assert {"host_rows_per_step", "host_link_ms_per_step"} <= names
    for m in bench["per_layer"]:
        if m["name"] in ("host_rows_per_step", "host_link_ms_per_step"):
            assert m["workloads"] == ["dlrm-criteo-host.zipf"]
    for name in ("host_rows_per_step", "host_link_ms_per_step"):
        assert spec.metric_reader(name).read(_record({}, 0)) is None


def test_host_rows_per_step_reads_the_trainers_window():
    """The window delta of the hub's ``host_moved_rows`` (misses plus
    write-backs) per window step, from the live trainer whose window the
    record names."""
    from repro.train.trainer import Trainer, TrainerConfig
    from bench.families import dlrm as fam

    cell = wide_cell()
    system = fam.build(cell.config, cell.mix, 13, jax.devices()[:1], host_tier)
    trainer = Trainer(TrainerConfig(max_steps=6), init_fn=system.init,
                      step_fn=system.step, make_batch=system.make_batch)
    trainer.run()
    first, last = trainer.history[2], trainer.history[-1]
    misses = last["cache_misses"] - first["cache_misses"]
    moved = last["host_moved_rows"] - first["host_moved_rows"]
    assert moved >= misses > 0
    run = dataclasses.replace(_record({}, 3), misses=misses)
    assert spec.metric_reader("host_rows_per_step").read(run) == pytest.approx(moved / 3)
