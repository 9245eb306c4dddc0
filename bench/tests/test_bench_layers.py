"""Per-layer readings beyond the run record: ops put under the step's named
scopes from HLO metadata, scope times that add up to the busy time, and the
window delta of the trainer's row counter."""
from __future__ import annotations

import re

import jax
import pytest

from bench import layers, spec
from bench import run as bench_run
from bench import trace as tr

HLO = """HloModule jit_train_step, is_scheduled=true

%region_add (a: s32[], b: s32[]) -> s32[] {
  %a = s32[] parameter(0)
  %b = s32[] parameter(1)
  ROOT %add.0 = s32[] add(s32[] %a, s32[] %b), metadata={op_name="jit(train_step)/telemetry/reduce_sum"}
}

%fused_plan (p0: s32[8]) -> s32[8] {
  %p0 = s32[8]{0} parameter(0)
  %neg.1 = s32[8]{0} negate(s32[8]{0} %p0), metadata={op_name="jit(train_step)/cache.plan/neg"}
  ROOT %gather.1 = s32[8]{0} gather(s32[8]{0} %neg.1, s32[8]{0} %p0), metadata={op_name="jit(train_step)/cache.plan/telemetry/gather"}
}

%fused_grad (q0: f32[16,4], q1: s32[8,1], q2: f32[8,4]) -> f32[16,4] {
  %q0 = f32[16,4]{1,0} parameter(0)
  %q1 = s32[8,1]{1,0} parameter(1)
  %q2 = f32[8,4]{1,0} parameter(2)
  ROOT %scatter.2 = f32[16,4]{1,0} scatter(f32[16,4]{1,0} %q0, s32[8,1]{1,0} %q1, f32[8,4]{1,0} %q2), to_apply=%region_add, metadata={op_name="jit(train_step)/transpose(jvp(cache.lookup))/shard.exchange/scatter-add"}
}

%fused_multi (r0: f32[8,4]) -> (f32[8,4], f32[8,4]) {
  %r0 = f32[8,4]{1,0} parameter(0)
  %tanh.3 = f32[8,4]{1,0} tanh(f32[8,4]{1,0} %r0), metadata={op_name="jit(train_step)/jvp(dense)/tanh"}
  %mul.4 = f32[8,4]{1,0} multiply(f32[8,4]{1,0} %r0, f32[8,4]{1,0} %r0), metadata={op_name="jit(train_step)/transpose(jvp(dense))/mul"}
  %exp.5 = f32[8,4]{1,0} exponential(f32[8,4]{1,0} %r0), metadata={op_name="jit(train_step)/cache.row_update/exp"}
  ROOT %tuple.6 = (f32[8,4]{1,0}, f32[8,4]{1,0}, f32[8,4]{1,0}) tuple(f32[8,4]{1,0} %tanh.3, f32[8,4]{1,0} %mul.4, f32[8,4]{1,0} %exp.5)
}

%fused_bare (s0: s32[64]) -> s32[2] {
  %s0 = s32[64]{0} parameter(0)
  %c0 = s32[] constant(0)
  ROOT %reduce-window.7 = s32[2]{0} reduce-window(s32[64]{0} %s0, s32[] %c0), window={size=32 stride=32}, to_apply=%region_add
}

ENTRY %main (x: f32[8,4], i: s32[8], t: f32[16,4], j: s32[8,1]) -> f32[8] {
  %x = f32[8,4]{1,0} parameter(0), metadata={op_name="batch['dense']"}
  %i = s32[8]{0} parameter(1)
  %fusion.1 = s32[8]{0} fusion(s32[8]{0} %i), kind=kLoop, calls=%fused_plan, metadata={op_name="jit(train_step)/cache.plan/neg"}
  %fusion.2 = f32[16,4]{1,0} fusion(f32[16,4]{1,0} %t, s32[8,1]{1,0} %j, f32[8,4]{1,0} %x), kind=kLoop, calls=%fused_grad
  %fusion.3 = (f32[8,4]{1,0}, f32[8,4]{1,0}, f32[8,4]{1,0}) fusion(f32[8,4]{1,0} %x), kind=kLoop, calls=%fused_multi
  %fusion.4 = s32[2]{0} fusion(s32[64]{0} %k), kind=kLoop, calls=%fused_bare
  %copy.5 = f32[8,4]{1,0} copy(f32[8,4]{1,0} %x)
  %bitcast.6 = f32[64]{0} bitcast(f32[16,4]{1,0} %fusion.2)
  %dot.7 = f32[8,8]{1,0} dot(f32[8,4]{1,0} %x, f32[8,4]{1,0} %x), metadata={op_name="jit(train_step)/jit(dense)/dot_general"}
  ROOT %all-to-all.8 = f32[8,4]{1,0} all-to-all(f32[8,4]{1,0} %x), metadata={op_name="jit(train_step)/jvp(cache.lookup)/shard.exchange/all_to_all"}
}
"""


@pytest.mark.parametrize("op_name,want", [
    ("jit(train_step)/cache.plan/jit(_where)/select_n", "cache.plan"),
    ("jit(train_step)/cache.plan/vmap(telemetry)/gather", "telemetry"),
    ("jit(train_step)/transpose(jvp(cache.lookup))/jit(_take)/scatter-add", "cache.lookup"),
    ("jit(train_step)/jvp(cache.lookup)/shard.exchange/gather", "shard.exchange"),
    ("jit(train_step)/transpose(jvp(dense))/dot_general", "dense"),
    ("jit(train_step)/jit(dense)/dot_general", ""),  # a function's name, not a scope
    ("jit(train_step)/cache.planner/add", ""),  # whole components only
    ("batch['dense']", ""),
])
def test_scope_is_the_innermost_listed_path_component(op_name, want):
    assert layers.scope_of(op_name) == want


def test_hlo_ops_go_under_the_scope_of_their_root():
    sc = layers.hlo_scopes(HLO)
    assert sc["fusion.1"] == "telemetry"  # the root's metadata, not the fusion's
    assert sc["fusion.2"] == "shard.exchange"  # a transposed exchange scatter
    assert sc["fusion.3"] == "dense"  # a tuple root: its operands' most common
    assert sc["fusion.4"] == "telemetry"  # a bare root: what its body carries
    assert sc["bitcast.6"] == "shard.exchange"  # a copy-like op: its operand's
    assert sc["all-to-all.8"] == "shard.exchange"
    for op in ("copy.5", "dot.7", "x", "i"):  # no scope on the path
        assert sc[op] == "", op
    assert set(sc) == set(tr.hlo_categories(HLO))


def test_scope_times_add_up_to_the_busy_time():
    sc = layers.hlo_scopes(HLO)
    by_op = {"fusion.1": 5.0, "fusion.2": 7.0, "fusion.3": 11.0, "copy.5": 2.0,
             "other_module_op": 3.0}
    got = layers.scope_times(by_op, sc)
    assert got["telemetry"] == 5.0 and got["shard.exchange"] == 7.0 and got["dense"] == 11.0
    assert got[""] == 5.0
    assert sum(got.values()) == sum(by_op.values())


def _record(devices, window_steps=2, misses=None):
    return bench_run.RunRecord(
        cell=None, device_kind="cpu", chips=len(devices), batch_size=1, flops_per_sample=1.0,
        window_steps=window_steps, window_s=1.0, samples_per_s=1.0, step_times_s=[1.0],
        hits=None, misses=misses, devices=devices)


def _window(by_op):
    busy = sum(by_op.values())
    return tr.DeviceWindow(busy, busy, {}, dict(by_op), [])


def test_the_step_is_picked_by_the_window_time_its_scoped_ops_own():
    other = HLO.replace("cache.", "cachex.").replace("dense", "densex").replace(
        "telemetry", "tele").replace("shard.exchange", "shardx")
    run = _record({0: _window({"fusion.2": 4e6, "fusion.3": 2e6})})
    assert layers.step_scopes(run, [other]) is None  # a program without scopes
    assert layers.step_scopes(run, [other, HLO]) == layers.hlo_scopes(HLO)


SCOPE_READERS = {"plan_ms_per_step": "cache.plan", "movement_ms_per_step": "cache.movement",
                 "lookup_ms_per_step": "cache.lookup", "exchange_ms_per_step": "shard.exchange",
                 "dense_ms_per_step": "dense", "row_update_ms_per_step": "cache.row_update",
                 "telemetry_ms_per_step": "telemetry"}


def test_scope_readers_read_the_live_compiled_step():
    """The readers find the step among the process's live compiled programs and
    split the window's device time by its scopes, mean over chips, per step."""
    from bench.tests import tiny
    from bench.families import dlrm as fam

    cell = tiny.cell()
    system = fam.build(cell.config, cell.mix, 11, jax.devices()[:1])
    state = system.init()
    compiled = system.step.lower(state, system.make_batch(0)).compile()
    sc = layers.hlo_scopes(compiled.as_text())
    ops = [op for op, s in sc.items() if s]
    assert {sc[op] for op in ops} == set(layers.SCOPES) - {"shard.exchange"}
    by_op = {op: 1e6 * (k + 1) for k, op in enumerate(ops)}  # ns
    by_op["unscoped.op"] = 5e6
    run = _record({0: _window(by_op), 1: _window({op: 2 * ns for op, ns in by_op.items()})})
    total = 0.0
    for name, scope in SCOPE_READERS.items():
        got = spec.metric_reader(name).read(run)
        want = 1.5 * sum(ns for op, ns in by_op.items() if sc.get(op) == scope) / 1e6 / 2
        assert got == pytest.approx(want), name
        total += got
    assert total + 1.5 * 5e6 / 1e6 / 2 == pytest.approx(1.5 * sum(by_op.values()) / 1e6 / 2)
    del compiled


def test_row_hit_rate_reads_the_trainers_window():
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
    rows = last["cache_uniq_rows"] - first["cache_uniq_rows"]
    assert 0 < misses < rows
    reader = spec.metric_reader("row_hit_rate")
    assert reader.read(_record({}, window_steps=3, misses=misses)) == \
        pytest.approx(100.0 * (1 - misses / rows))
    # a record whose misses no live trainer's window gives: nothing to read
    assert reader.read(_record({}, window_steps=3, misses=misses + 1)) is None


def test_every_new_reader_reads_nothing_without_a_trace():
    run = _record({})
    for name in SCOPE_READERS:
        assert spec.metric_reader(name).read(run) is None


def test_recorded_tpu_trace_splits_busy_time_by_scope():
    """A trace recorded on a TPU v5e (``testdata/tpu_scopes.*``: three calls of
    a small jitted step whose stages run under the seven scope names, each in
    a ``bench.window_step`` span: argsorts, a scatter, a gather differentiated
    by ``value_and_grad`` with a roll under ``shard.exchange`` nested in its
    scope, matmuls and an update; the recording script's path in the
    metadata is renamed).  Every scope owns device time, the scope
    times plus the unscoped rest add up to the busy time, and the ops of the
    ``jvp(..)`` and ``transpose(..)`` of a scope land in it."""
    data = spec.BENCH / "testdata"
    t = tr.load(str(data / "tpu_scopes.xplane.pb"))
    hlo = (data / "tpu_scopes_hlo.txt").read_text()
    sc = layers.hlo_scopes(hlo)
    marks = [s for s in t.host if s.name == "bench.window_step"]
    assert list(t.devices) == [0] and len(marks) == 3
    thread = marks[0].module
    w0 = min(s.start for s in marks)
    w1 = max(s.end for s in t.host if s.name == "host-transfer" and s.module == thread)
    w = tr.reduce_device(t.devices[0], w0, w1, tr.hlo_categories(hlo), "jit_step")
    got = layers.scope_times(w.by_op, sc)
    assert sum(got.values()) == pytest.approx(w.busy_ns)
    for s in layers.SCOPES:
        assert got[s] > 0, s
    assert got[layers.UNSCOPED] < 0.05 * w.busy_ns
    own = dict(re.findall(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?op_name="([^"]*)"', hlo, re.M))
    ran = {op: own[op] for op, ns in w.by_op.items() if ns > 0 and op in own}
    for wrap, scope in (("jvp(cache.lookup)", "cache.lookup"),
                        ("transpose(jvp(cache.lookup))", "cache.lookup"),
                        ("transpose(jvp(cache.lookup))/shard.exchange", "shard.exchange"),
                        ("jvp(dense)", "dense"), ("transpose(jvp(dense))", "dense")):
        hit = [op for op, name in ran.items()
               if name.startswith(f"jit(step)/{wrap}/") and layers.scope_of(name) == scope]
        assert hit and all(sc[op] == scope for op in hit), wrap
