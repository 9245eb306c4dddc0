"""Observability layer: wrap-safe exact counters, deterministic JSONL,
fixed-bucket histograms, span tracing, and the report CLI."""
import json
import threading

import jax
import jax.numpy as jnp
import pytest

from repro.obs import (
    NULL_TRACER,
    ExactCounter,
    FixedHistogram,
    MetricsHub,
    Tracer,
    log_bounds,
)
from repro.obs import report as report_mod

# --------------------------------------------------------------------------
# ExactCounter: wrap safety past 2^31 for every counter family
# --------------------------------------------------------------------------


def _wrapped(x: int) -> jnp.ndarray:
    """The int32 value a cumulative device counter holds after x events."""
    return jnp.asarray((x + 2**31) % 2**32 - 2**31, jnp.int32)


def test_exact_counter_survives_int32_wrap():
    c = ExactCounter()
    c.observe(_wrapped(2**31 - 100))
    c.observe(_wrapped(2**31 + 90))  # wrapped negative on device
    assert c.value == 2**31 + 90  # exact Python int, no wrap


def test_exact_counter_per_slab_and_idempotent():
    c = ExactCounter()
    c.observe({"a": _wrapped(5), "b": _wrapped(7)})
    c.observe({"a": _wrapped(5), "b": _wrapped(7)})  # summaries re-observe
    assert c.value == 12
    c.observe({"a": _wrapped(2**31 + 5), "b": _wrapped(7)})
    assert c.value == 2**31 + 12


def test_exact_counter_unit_weighted_bytes_are_wrap_safe():
    # bytes = rows x static row size must survive the ROW counter wrapping,
    # which a one-shot product of the raw counter would inherit.
    c = ExactCounter()
    c.observe({"s": _wrapped(2**31 - 10)}, unit={"s": jnp.asarray(128)})
    c.observe({"s": _wrapped(2**31 + 10)}, unit={"s": jnp.asarray(128)})
    assert c.value == (2**31 + 10) * 128


@pytest.mark.parametrize(
    "counts_key,unit_key,record_key",
    [
        ("slab_hits", None, "cache_hits"),
        ("slab_misses", None, "cache_misses"),
        ("slab_uniq_rows", None, "cache_uniq_rows"),
        ("host_moved_rows", "host_row_bytes", "host_wire_bytes"),
        ("exchange_routed_lanes", None, "exchange_routed_lanes"),
        ("exchange_routed_lanes", "exchange_lane_bytes", "exchange_bytes"),
        ("exchange_routed_lanes", "exchange_id_lane_bytes", "exchange_id_bytes"),
        ("exchange_routed_lanes", "exchange_row_lane_bytes", "exchange_row_bytes"),
        ("slab_refresh_swaps", None, "refresh_swaps_exact"),
        ("slab_refresh_rows", None, "refresh_rows_moved_exact"),
    ],
)
def test_every_hub_family_is_wrap_safe_past_2_31(counts_key, unit_key, record_key):
    """Each counter family routed through MetricsHub reconstructs exactly
    across an int32 wrap of its in-jit cumulative counter."""
    hub = MetricsHub()
    unit = 8
    m1 = {counts_key: {"s": _wrapped(2**31 - 3)}}
    m2 = {counts_key: {"s": _wrapped(2**31 + 3)}}
    if unit_key is not None:
        m1[unit_key] = {"s": jnp.asarray(unit, jnp.int32)}
        m2[unit_key] = {"s": jnp.asarray(unit, jnp.int32)}
    hub.observe_embedding_metrics(m1)
    out = hub.observe_embedding_metrics(m2)
    expect = (2**31 + 3) * (unit if unit_key is not None else 1)
    assert out[record_key] == expect
    assert isinstance(out[record_key], int)


def test_hub_derives_exact_hit_rate():
    hub = MetricsHub()
    out = hub.observe_embedding_metrics(
        {"slab_hits": {"s": _wrapped(30)}, "slab_misses": {"s": _wrapped(10)}}
    )
    assert out["hit_rate_exact"] == 0.75


# --------------------------------------------------------------------------
# FixedHistogram
# --------------------------------------------------------------------------


def test_log_bounds_cover_range_deterministically():
    b = log_bounds(1e-5, 100.0, per_decade=10)
    assert b[0] == 1e-5 and b[-1] >= 100.0
    assert b == log_bounds(1e-5, 100.0, per_decade=10)
    assert list(b) == sorted(b)


def test_histogram_quantiles_are_guaranteed_upper_bounds():
    h = FixedHistogram.latency()
    vals = [1e-3] * 900 + [1e-2] * 90 + [1e-1] * 9 + [1.0]
    for v in vals:
        h.observe(v)
    assert h.count == 1000
    s = sorted(vals)
    for q in (0.5, 0.95, 0.99, 0.999):
        true_q = s[max(0, int(q * len(s)) - 1)]
        assert h.quantile(q) >= true_q  # never under-reports
        assert h.quantile(q) <= true_q * 10 ** (1 / 10) + 1e-12  # bucket err
    assert h.quantile(1.0) == 1.0  # the max lands exactly on its sample


def test_histogram_order_independent_and_overflow_reports_max():
    vals = [5e-3, 2.0, 1e-4, 500.0, 5e-3]  # 500 s is past the last bound
    h1, h2 = FixedHistogram.latency(), FixedHistogram.latency()
    for v in vals:
        h1.observe(v)
    for v in reversed(vals):
        h2.observe(v)
    d1, d2 = h1.to_dict(), h2.to_dict()
    s1, s2 = d1.pop("sum"), d2.pop("sum")
    assert d1 == d2  # counts/extrema are exactly order-independent
    assert s1 == pytest.approx(s2)  # float sum only to addition re-ordering
    assert h1.quantile(1.0) == 500.0  # overflow bucket: exact max
    assert h1.counts[-1] == 1


def test_histogram_merge_and_roundtrip():
    a, b = FixedHistogram.latency(), FixedHistogram.latency()
    for v in (1e-3, 2e-3):
        a.observe(v)
    b.observe(0.5)
    m = a.merge(b)
    assert m.count == 3 and m.min == 1e-3 and m.max == 0.5
    assert FixedHistogram.from_dict(m.to_dict()).to_dict() == m.to_dict()
    with pytest.raises(ValueError):
        a.merge(FixedHistogram(bounds=(1.0, 2.0)))


# --------------------------------------------------------------------------
# Tracer
# --------------------------------------------------------------------------


def test_tracer_spans_aggregate_and_export_chrome_trace(tmp_path):
    tr = Tracer()
    for _ in range(3):
        with tr.span("plan"):
            pass
    with tr.span("compute", step=7):
        pass
    agg = tr.stage_summary()
    assert agg["plan"]["count"] == 3 and agg["compute"]["count"] == 1
    assert agg["plan"]["total_s"] >= 0
    path = tr.export_chrome_trace(str(tmp_path / "t.trace.json"))
    doc = json.loads((tmp_path / "t.trace.json").read_text())
    assert path.endswith("t.trace.json")
    assert len(doc["traceEvents"]) == 4
    ev = {e["name"] for e in doc["traceEvents"]}
    assert ev == {"plan", "compute"}
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in doc["traceEvents"])
    args = [e.get("args") for e in doc["traceEvents"] if e["name"] == "compute"]
    assert args == [{"step": 7}]


def test_tracer_event_cap_keeps_aggregates_exact():
    tr = Tracer(max_events=5)
    for _ in range(20):
        with tr.span("s"):
            pass
    assert tr.stage_summary()["s"]["count"] == 20  # exact past the cap
    assert tr.dropped_events == 15
    assert len(tr.chrome_trace()["traceEvents"]) == 5


def test_tracer_is_thread_safe():
    tr = Tracer()

    def work():
        for _ in range(50):
            with tr.span("w"):
                pass

    ts = [threading.Thread(target=work) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert tr.stage_summary()["w"]["count"] == 200


def test_null_tracer_records_nothing():
    with NULL_TRACER.span("x"):
        pass
    assert NULL_TRACER.stage_summary() == {}


# --------------------------------------------------------------------------
# MetricsHub sink: JSONL determinism
# --------------------------------------------------------------------------


def _strip_wall(line: str) -> dict:
    rec = json.loads(line)
    rec.pop("wall", None)
    return rec


def _run_hub(run_dir) -> str:
    hub = MetricsHub(run_dir=str(run_dir), run="r", timestamps=True)
    for step in range(3):
        out = hub.observe_embedding_metrics(
            {"slab_hits": {"s": _wrapped(10 * (step + 1))},
             "slab_misses": {"s": _wrapped(2 * (step + 1))}}
        )
        hub.histogram("step_time_s").observe(1e-3 * (step + 1))
        hub.log("step", {"step": step, **out}, wall={"time_s": 1e-3})
    tr = Tracer()
    with tr.span("compute"):
        pass
    hub.log_hist("step_time_s")
    hub.log_spans(tr)
    hub.close()
    return hub.jsonl_path


def test_jsonl_streams_are_byte_identical_modulo_wall(tmp_path):
    """Two identical runs emit byte-identical JSONL once the reserved `wall`
    subtree (timestamps, durations) is dropped — telemetry diffs become
    regression signals."""
    p1 = _run_hub(tmp_path / "a")
    p2 = _run_hub(tmp_path / "b")
    l1 = open(p1).read().splitlines()
    l2 = open(p2).read().splitlines()
    assert len(l1) == len(l2)
    for a, b in zip(l1, l2):
        assert _strip_wall(a) == _strip_wall(b)
    # every record's deterministic part serializes with sorted keys: the
    # stripped record re-serialized matches the on-disk prefix ordering
    for line in l1:
        rec = json.loads(line)
        assert json.dumps(rec, sort_keys=True) == line


def test_jsonl_without_timestamps_is_fully_byte_identical(tmp_path):
    def run(d):
        hub = MetricsHub(run_dir=str(d), run="r", timestamps=False)
        hub.log("step", {"step": 0, "loss": 0.5})
        hub.log_hist("h", FixedHistogram(bounds=(1.0, 2.0)))
        hub.close()
        return open(hub.jsonl_path).read()

    a = run(tmp_path / "a")
    b = run(tmp_path / "b")
    # histogram payloads sit under `wall`; with no observations and no
    # timestamps the full files match byte for byte
    assert a == b


def test_hub_sinkless_mode_accumulates_without_files(tmp_path):
    hub = MetricsHub()  # no run_dir
    hub.counter("c").add(3)
    hub.log("step", {"step": 0})
    assert hub.jsonl_path is None
    assert hub.snapshot()["counters"]["c"] == 3
    hub.close()
    assert list(tmp_path.iterdir()) == []


def test_hub_snapshot_delta():
    hub = MetricsHub()
    hub.counter("x").add(10)
    snap = hub.snapshot()
    hub.counter("x").add(5)
    assert hub.delta(snap) == {"x": 5}


# --------------------------------------------------------------------------
# report CLI
# --------------------------------------------------------------------------


def test_report_cli_renders_and_json(tmp_path, capsys):
    path = _run_hub(tmp_path)
    assert report_mod.main([path]) == 0
    text = capsys.readouterr().out
    assert "cache: 30 hits / 6 misses (exact)" in text
    assert "compute" in text and "step_time_s" in text
    assert report_mod.main([path, "--json"]) == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["train"]["n_steps"] == 3
    assert summary["train"]["cache_hits_total"] == 30
    assert summary["counters"]["cache_hits"] == 30
    assert summary["latency"]["step_time_s"]["count"] == 3


# --------------------------------------------------------------------------
# trainer integration: history bounding + step records
# --------------------------------------------------------------------------


def _toy_trainer(tmp_path=None, **cfg_kw):
    from repro.train.trainer import Trainer, TrainerConfig

    def step_fn(state, batch):
        s = state + 1
        return s, {"loss": jnp.asarray(0.5, jnp.float32)}

    return Trainer(
        TrainerConfig(max_steps=6, **cfg_kw),
        init_fn=lambda: jnp.zeros((), jnp.int32),
        step_fn=jax.jit(step_fn),
        make_batch=lambda s: {"x": s},
    )


def test_trainer_history_limit_bounds_memory(tmp_path):
    tr = _toy_trainer(obs_dir=str(tmp_path), history_limit=2)
    tr.run()
    assert len(tr.history) == 2  # only the tail stays in memory
    assert [r["step"] for r in tr.history] == [4, 5]
    # ...while the full stream is on disk
    records = report_mod.load_records(tr.hub.jsonl_path)
    steps = [r for r in records if r.get("kind") == "step"]
    assert [r["step"] for r in steps] == [0, 1, 2, 3, 4, 5]
    assert all("time_s" in r["wall"] for r in steps)  # wall-clock quarantined
    kinds = [r.get("kind") for r in records]
    assert kinds[0] == "meta" and "hist" in kinds and "spans" in kinds
    assert kinds[-1] == "summary"
    assert tr.trace_path and json.load(open(tr.trace_path))["traceEvents"]


def test_trainer_default_history_unbounded():
    tr = _toy_trainer()
    tr.run()
    assert [r["step"] for r in tr.history] == [0, 1, 2, 3, 4, 5]
    assert tr.hub.jsonl_path is None  # no obs dir -> no files
    assert tr.tracer is NULL_TRACER
