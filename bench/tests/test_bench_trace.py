"""The trace reduction: op classes from HLO text, and the sweep whose class
times plus ``other`` add up to the busy time."""
from __future__ import annotations

import pytest

from bench import spec
from bench import trace as tr

HLO = """HloModule jit_train_step, is_scheduled=true, entry_computation_layout={(f32[8,4]{1,0})->f32[8]{0}}

%cmp (a: s32[], b: s32[]) -> pred[] {
  %a = s32[] parameter(0)
  %b = s32[] parameter(1)
  ROOT %lt = pred[] compare(s32[] %a, s32[] %b), direction=LT
}

%fused_sort (param_0: s32[8]) -> (s32[8], s32[8]) {
  %param_0 = s32[8]{0} parameter(0)
  %iota.1 = s32[8]{0} iota(), iota_dimension=0
  ROOT %sort.1 = (s32[8]{0}, s32[8]{0}) sort(s32[8]{0} %param_0, s32[8]{0} %iota.1), dimensions={0}, to_apply=%cmp
}

%fused_scatter (p0: f32[16,4], p1: s32[8,1], p2: f32[8,4]) -> f32[16,4] {
  %p0 = f32[16,4]{1,0} parameter(0)
  %p1 = s32[8,1]{1,0} parameter(1)
  %p2 = f32[8,4]{1,0} parameter(2)
  ROOT %scatter.2 = f32[16,4]{1,0} scatter(f32[16,4]{1,0} %p0, s32[8,1]{1,0} %p1, f32[8,4]{1,0} %p2), update_window_dims={1}, to_apply=%add
}

%fused_dot (q0: f32[8,4], q1: f32[4,4], q2: f32[4]) -> f32[8,4] {
  %q0 = f32[8,4]{1,0} parameter(0)
  %q1 = f32[4,4]{1,0} parameter(1)
  %q2 = f32[4]{0} parameter(2)
  %convolution.3 = f32[8,4]{1,0} convolution(f32[8,4]{1,0} %q0, f32[4,4]{1,0} %q1), dim_labels=bf_io->bf
  %broadcast.4 = f32[8,4]{1,0} broadcast(f32[4]{0} %q2), dimensions={1}
  ROOT %add.5 = f32[8,4]{1,0} add(f32[8,4]{1,0} %convolution.3, f32[8,4]{1,0} %broadcast.4)
}

%fused_tuple (r0: s32[8], r1: f32[16,4]) -> (s32[8], f32[16,4]) {
  %r0 = s32[8]{0} parameter(0)
  %r1 = f32[16,4]{1,0} parameter(1)
  %ds = f32[8,4]{1,0} dynamic-slice(f32[16,4]{1,0} %r1, s32[] %c, s32[] %c), dynamic_slice_sizes={8,4}
  %neg = s32[8]{0} negate(s32[8]{0} %r0)
  ROOT %tuple.6 = (s32[8]{0}, f32[8,4]{1,0}) tuple(s32[8]{0} %neg, f32[8,4]{1,0} %ds)
}

ENTRY %main.20 (x: f32[8,4], w: f32[4,4]) -> f32[8] {
  %x = f32[8,4]{1,0} parameter(0)
  %w = f32[4,4]{1,0} parameter(1)
  %fusion.1 = (s32[8]{0}, s32[8]{0}) fusion(s32[8]{0} %i), kind=kLoop, calls=%fused_sort
  %fusion.2 = f32[16,4]{1,0} fusion(f32[16,4]{1,0} %t, s32[8,1]{1,0} %j, f32[8,4]{1,0} %x), kind=kLoop, calls=%fused_scatter
  %fusion.3 = f32[8,4]{1,0} fusion(f32[8,4]{1,0} %x, f32[4,4]{1,0} %w, f32[4]{0} %b), kind=kOutput, calls=%fused_dot
  %fusion.4 = (s32[8]{0}, f32[8,4]{1,0}) fusion(s32[8]{0} %i, f32[16,4]{1,0} %t), kind=kLoop, calls=%fused_tuple
  %all-reduce.7 = f32[4,4]{1,0} all-reduce(f32[4,4]{1,0} %w), replica_groups={}, to_apply=%add
  %all-gather-start.8 = (f32[4]{0}, f32[16]{0}) all-gather-start(f32[4]{0} %b), dimensions={0}
  %copy.9 = f32[8,4]{1,0} copy(f32[8,4]{1,0} %x)
  %bitcast.10 = f32[32]{0} bitcast(f32[16,4]{1,0} %fusion.2)
  ROOT %reduce.11 = f32[8]{0} reduce(f32[8,4]{1,0} %copy.9, f32[] %z), dimensions={1}, to_apply=%add
}
"""


def test_hlo_ops_are_classed_by_root():
    cats = tr.hlo_categories(HLO)
    assert cats["fusion.1"] == "sort"
    assert cats["fusion.2"] == "gather_scatter"
    assert cats["fusion.3"] == "matmul"  # output fusion: the op it fuses onto
    assert cats["fusion.4"] == "gather_scatter"  # tuple root: its heaviest operand
    assert cats["all-reduce.7"] == "collective"
    assert cats["all-gather-start.8"] == "collective"
    assert cats["copy.9"] == "other"
    assert cats["bitcast.10"] == "gather_scatter"  # passes its operand's class through
    assert cats["reduce.11"] == "other"


def _sum_check(w: tr.DeviceWindow):
    assert sum(w.by_category.values()) == pytest.approx(w.busy_ns, rel=1e-12, abs=1e-6)
    assert sum(w.by_op.values()) == pytest.approx(w.busy_ns, rel=1e-12, abs=1e-6)
    idle = sum(b - a for a, b in w.gaps)
    assert w.busy_ns + idle == pytest.approx(w.window_ns, rel=1e-12)


def test_sweep_gives_nested_time_to_the_innermost_op():
    cats = tr.hlo_categories(HLO)
    S = tr.Span
    ops = [S("while.0", 0, 100, "jit_train_step"), S("fusion.1", 10, 30, "jit_train_step"),
           S("fusion.3", 30, 50, "jit_train_step"), S("fusion.2", 120, 150, "jit_train_step"),
           S("fusion.2", 140, 160, "jit_other"),  # overlaps; another module's op
           S("all-reduce.7", 190, 260, "jit_train_step")]
    w = tr.reduce_device(ops, 5, 250, cats, "jit_train_step")
    _sum_check(w)
    assert w.by_category["sort"] == 20 and w.by_category["matmul"] == 20
    assert w.by_category["gather_scatter"] == 20  # 120..140; from 140 the later op owns
    assert w.by_category["collective"] == 60  # clipped to the window
    assert w.by_category["other"] == (95 - 40) + 20  # the while's own time + jit_other
    assert w.busy_ns == 95 + 40 + 60
    assert w.gaps == [(100, 120), (160, 190)]


def test_host_activity_names_the_innermost_span_of_the_thread():
    S = tr.Span
    host = [S("step", 0, 100, "main"), S("host-transfer", 40, 90, "main"),
            S("prefetch", 0, 200, "worker")]
    assert tr.host_activity(host, [50, 20, 150, 95], "main") == [
        "host-transfer", "step", "none", "step"]


def test_recorded_tpu_trace_reduces_to_its_busy_time():
    """A trace recorded on a TPU v5e (``testdata/tpu_small.*``: three calls of
    a small jitted sort + matmul + scatter + gather, each in a
    ``bench.window_step`` span): the device plane is found, its op names map
    onto the compiled module's instructions, and the classes plus ``other``
    add up to the busy time."""
    data = spec.BENCH / "testdata"
    t = tr.load(str(data / "tpu_small.xplane.pb"))
    hlo = (data / "tpu_small_hlo.txt").read_text()
    cats = tr.hlo_categories(hlo)
    assert list(t.devices) == [0]
    marks = [s for s in t.host if s.name == "bench.window_step"]
    assert len(marks) == 3
    thread = marks[0].module
    w0 = min(s.start for s in marks)
    w1 = max(s.end for s in t.host if s.name == "host-transfer" and s.module == thread)
    w = tr.reduce_device(t.devices[0], w0, w1, cats, "jit_step")
    _sum_check(w)
    assert w.busy_ns > 0
    # the step's ops map onto its instructions; the host-transfer's indexing
    # runs as a program of its own, whose ops count as other
    assert sum(ns for op, ns in w.by_op.items() if op in cats) > 0.5 * w.busy_ns
    for c in ("sort", "gather_scatter", "matmul"):
        assert w.by_category[c] > 0, c
    names = tr.host_activity(t.host, [(a + b) / 2 for a, b in w.gaps], thread)
    assert len(names) == len(w.gaps) and "host-transfer" in names
