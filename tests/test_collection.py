"""Planner-driven EmbeddingCollection: placement plans, keyed-feature API,
mixed-plan exactness, and the end-to-end train/serve acceptance path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import collection as col
from repro.core.policies import Policy


def small_tables(dim=8, ids=16):
    return [
        col.TableConfig("hot", vocab=64, dim=dim, ids_per_step=ids),
        col.TableConfig("big", vocab=4096, dim=dim, ids_per_step=ids, cache_ratio=0.1),
        col.TableConfig("tiny_a", vocab=24, dim=dim, ids_per_step=ids),
        col.TableConfig("tiny_b", vocab=24, dim=dim, ids_per_step=ids),
    ]


def zipf_fb(tables, n, seed):
    rng = np.random.default_rng(seed)
    return col.FeatureBatch(ids={
        t.name: jnp.asarray((rng.zipf(1.3, n) % t.vocab).astype(np.int32))
        for t in tables
    })


# --------------------------------------------------------------------------
# planner
# --------------------------------------------------------------------------


def test_planner_respects_budget_and_mixes_placements():
    tables = small_tables()
    budget = 80_000  # holds the small tables, not the big one
    plan = col.PlacementPlanner(budget, group_below_rows=32).plan(tables)
    coll = col.EmbeddingCollection(tables, plan)
    placements = {n: p.placement for n, p in plan.placements.items()}
    assert placements["hot"] is col.Placement.DEVICE
    assert placements["big"] is col.Placement.CACHED
    assert placements["tiny_a"] is col.Placement.GROUPED
    assert placements["tiny_b"] is col.Placement.GROUPED
    assert coll.device_bytes()["device_total"] <= budget


def test_planner_prefers_hot_tables_with_counts():
    dim = 8
    tables = [
        col.TableConfig("a", vocab=256, dim=dim, ids_per_step=16),
        col.TableConfig("b", vocab=256, dim=dim, ids_per_step=16),
    ]
    # room for one DEVICE table plus the other table's cache floor (which
    # includes the online frequency tracker's vocab-sized counters), but NOT
    # for both tables resident
    budget = 256 * dim * 4 + col.PlacementPlanner(0)._fast_bytes(tables[0], 0.0) + 64
    counts = {"a": np.ones(256), "b": np.full(256, 1000)}
    plan = col.PlacementPlanner(budget).plan(tables, counts=counts)
    assert plan.placements["b"].placement is col.Placement.DEVICE
    assert plan.placements["a"].placement is col.Placement.CACHED


def test_planner_raises_when_budget_infeasible():
    tables = [col.TableConfig("t", vocab=1000, dim=64, ids_per_step=512)]
    with pytest.raises(ValueError):
        col.PlacementPlanner(100).plan(tables)


def test_floor_scaled_ratio_zero_is_honored():
    """A planner-assigned ratio of 0.0 (exactness floor) must not fall back
    to the table's own ratio — the built slab has floor capacity and the
    device footprint stays within the budget the planner enforced."""
    t = col.TableConfig("big", vocab=100_000, dim=32, ids_per_step=256, cache_ratio=0.05)
    floor_budget = col.PlacementPlanner(0)._fast_bytes(t, 0.0)
    plan = col.PlacementPlanner(floor_budget).plan([t])
    assert plan.placements["big"].cache_ratio == 0.0
    coll = col.EmbeddingCollection([t], plan)
    assert coll.cached_slabs["big"].capacity == t.unique_size()
    assert coll.device_bytes()["device_total"] <= floor_budget


def test_full_lookup_padding_is_zero_on_cached_tables():
    tables = [
        col.TableConfig("a", vocab=32, dim=4, ids_per_step=8),
        col.TableConfig("b", vocab=32, dim=4, ids_per_step=8),
    ]
    coll = col.EmbeddingCollection.create(tables, cache_ratio=0.5)  # shared arena
    state = coll.init(jax.random.PRNGKey(0))
    ids = jnp.asarray([3, -1, 7, -1], jnp.int32)
    rows = coll.full_lookup(state, "b", ids)
    assert bool((np.asarray(rows)[[1, 3]] == 0).all())
    assert bool((np.asarray(rows)[[0, 2]] != 0).any())


def test_dlrm_budget_mode_keeps_max_unique_bound():
    from repro.models.dlrm import DLRM, DLRMConfig

    cfg = DLRMConfig(vocab_sizes=(4096, 64), embed_dim=8, batch_size=16,
                     cache_ratio=0.25, max_unique_per_step=8,
                     bottom_mlp=(8,), top_mlp=(8,), device_budget_bytes=80_000)
    model = DLRM(cfg)
    cached = [s for s in model.collection.cached_slabs.values()]
    assert cached and all(s.max_unique_per_step == 8 for s in cached)


def test_explicit_placement_overrides_survive():
    tables = [
        col.TableConfig("pin_dev", vocab=32, dim=4, ids_per_step=8,
                        placement=col.Placement.DEVICE),
        col.TableConfig("pin_cache", vocab=32, dim=4, ids_per_step=8,
                        placement=col.Placement.CACHED, cache_ratio=0.5),
    ]
    plan = col.PlacementPlanner(10**9).plan(tables)
    assert plan.placements["pin_dev"].placement is col.Placement.DEVICE
    assert plan.placements["pin_cache"].placement is col.Placement.CACHED


# --------------------------------------------------------------------------
# mixed-plan exactness (THE paper property, generalized)
# --------------------------------------------------------------------------


def test_mixed_plan_matches_dense_reference_bitwise():
    tables = small_tables()
    plan = col.PlacementPlanner(80_000, group_below_rows=32).plan(tables)
    coll = col.EmbeddingCollection(tables, plan)
    assert coll.device_slabs and coll.cached_slabs, "want a genuinely mixed plan"
    state = coll.init(jax.random.PRNGKey(0))
    step = jax.jit(lambda s, fb: coll.lookup(s, fb))
    for i in range(20):
        fb = zipf_fb(tables, 16, seed=i)
        state, _, rows = step(state, fb)
        ref = coll.dense_reference(coll.flush(state), fb)
        for f in fb.features:
            np.testing.assert_array_equal(np.asarray(rows[f]), np.asarray(ref[f]))


def test_padding_lanes_give_zero_rows_everywhere():
    tables = small_tables()
    plan = col.PlacementPlanner(80_000, group_below_rows=32).plan(tables)
    coll = col.EmbeddingCollection(tables, plan)
    state = coll.init(jax.random.PRNGKey(0))
    fb = col.FeatureBatch(ids={t.name: jnp.full((16,), -1, jnp.int32) for t in tables})
    state, addr, rows = coll.lookup(state, fb)
    for f in fb.features:
        assert bool((np.asarray(addr[f]) == -1).all())
        assert bool((np.asarray(rows[f]) == 0).all())


def test_grads_reach_device_and_cached_tiers():
    tables = small_tables()
    plan = col.PlacementPlanner(80_000, group_below_rows=32).plan(tables)
    coll = col.EmbeddingCollection(tables, plan)
    state = coll.init(jax.random.PRNGKey(0))
    fb = zipf_fb(tables, 16, seed=0)
    state, addr = coll.prepare(state, fb)

    def loss_fn(w):
        rows = coll.gather(w, addr, fb)
        return sum(jnp.sum(r**2) for r in rows.values())

    grads = jax.grad(loss_fn)(coll.weights(state))
    assert any(float(jnp.abs(grads[s]).max()) > 0 for s in coll.device_slabs)
    assert any(float(jnp.abs(grads[s]).max()) > 0 for s in coll.cached_slabs)
    before = coll.weights(state)
    state2 = coll.apply_grads(state, grads, 0.1)
    after = coll.weights(state2)
    for s in before:
        assert not np.array_equal(np.asarray(before[s]), np.asarray(after[s]))


def test_uniq_overflow_counted_under_collection_api():
    tables = [col.TableConfig("t", vocab=100, dim=4, ids_per_step=16,
                              max_unique_per_step=4, cache_ratio=0.3,
                              placement=col.Placement.CACHED)]
    coll = col.EmbeddingCollection(tables, col.PlacementPlanner(10**9).plan(tables))
    state = coll.init(jax.random.PRNGKey(0))
    fb = col.FeatureBatch(ids={"t": jnp.arange(16, dtype=jnp.int32)})  # 16 distinct > 4
    state, _ = coll.prepare(state, fb)
    assert int(coll.metrics(state)["uniq_overflows"]) == 1
    fb2 = col.FeatureBatch(ids={"t": jnp.zeros(16, jnp.int32)})  # 1 distinct: fine
    state, _ = coll.prepare(state, fb2)
    assert int(coll.metrics(state)["uniq_overflows"]) == 1


# --------------------------------------------------------------------------
# FeatureBatch
# --------------------------------------------------------------------------


def test_feature_batch_from_onehot_and_shapes():
    m = jnp.asarray([[1, 2], [3, 4], [5, 6]], jnp.int32)
    fb = col.FeatureBatch.from_onehot(("a", "b"), m)
    np.testing.assert_array_equal(np.asarray(fb.ids["a"]), [1, 3, 5])
    np.testing.assert_array_equal(np.asarray(fb.ids["b"]), [2, 4, 6])


def test_feature_batch_bags_pool_matches_manual_segment_sum():
    tables = [col.TableConfig("t", vocab=50, dim=4, ids_per_step=12,
                              cache_ratio=0.5)]
    coll = col.EmbeddingCollection.create(tables, cache_ratio=0.5)
    state = coll.init(jax.random.PRNGKey(0))
    flat = jnp.asarray([1, 2, 3, -1, 4, 5, 6, 7, -1, -1, 8, 9], jnp.int32)
    seg = jnp.asarray([0, 0, 0, 0, 1, 1, 1, 1, 2, 2, 2, 2], jnp.int32)
    fb = col.FeatureBatch.from_bags({"t": (flat, seg)}, num_segments=3)
    state, _, rows = coll.lookup(state, fb)
    pooled = coll.pool(rows, fb)["t"]
    manual = np.zeros((3, 4), np.float32)
    r = np.asarray(rows["t"])
    for lane in range(12):
        manual[int(seg[lane])] += r[lane]
    np.testing.assert_allclose(np.asarray(pooled), manual, rtol=1e-6)
    # bag features keep exactness too
    ref = coll.dense_reference(coll.flush(state), fb)
    np.testing.assert_array_equal(np.asarray(rows["t"]), np.asarray(ref["t"]))


def test_unknown_feature_is_rejected():
    tables = [col.TableConfig("t", vocab=10, dim=2, ids_per_step=4)]
    coll = col.EmbeddingCollection.create(tables)
    state = coll.init(jax.random.PRNGKey(0))
    with pytest.raises(KeyError):
        coll.prepare(state, col.FeatureBatch(ids={"nope": jnp.zeros(4, jnp.int32)}))


def test_shard_specs_structure_matches_state():
    tables = small_tables()
    plan = col.PlacementPlanner(80_000, group_below_rows=32).plan(tables)
    coll = col.EmbeddingCollection(tables, plan)
    state = coll.init(jax.random.PRNGKey(0))
    specs = coll.shard_specs("column")
    a = jax.tree_util.tree_structure(state)
    b = jax.tree_util.tree_structure(specs)
    assert a == b


# --------------------------------------------------------------------------
# acceptance: mixed plan trains via Trainer and serves via ServeEngine
# --------------------------------------------------------------------------


def test_mixed_plan_trains_and_serves_end_to_end():
    from repro.data import synth
    from repro.models.dlrm import DLRM, DLRMConfig
    from repro.serve.engine import ServeEngine
    from repro.train.trainer import Trainer, TrainerConfig

    budget = 90_000  # promotes the small tables, caches the 4096-row one
    cfg = DLRMConfig(vocab_sizes=(4096, 256, 64), embed_dim=8, batch_size=16,
                     cache_ratio=0.25, lr=0.1, bottom_mlp=(16, 8), top_mlp=(16,),
                     device_budget_bytes=budget)
    model = DLRM(cfg)
    placements = {p.placement for p in model.collection.plan.placements.values()}
    assert col.Placement.DEVICE in placements and col.Placement.CACHED in placements
    assert model.collection.device_bytes()["device_total"] <= budget

    spec = synth.ZipfSparseSpec(vocab_sizes=cfg.vocab_sizes, n_dense=13)

    def make_batch(step):
        return {k: jnp.asarray(v) for k, v in synth.sparse_batch(spec, 16, 0, step).items()}

    trainer = Trainer(
        TrainerConfig(max_steps=5),
        init_fn=lambda: model.init(jax.random.PRNGKey(0)),
        step_fn=jax.jit(model.train_step),
        make_batch=make_batch,
        flush_fn=model.flush,
    )
    state = trainer.run()
    assert trainer.history and np.isfinite(trainer.history[-1]["loss"])

    # trained cached lookups still bit-match the dense reference
    fb = model.features(make_batch(99))
    emb_state, _, rows = model.collection.lookup(state["emb"], fb, writeback=False)
    ref = model.collection.dense_reference(model.collection.flush(emb_state), fb)
    for f in fb.features:
        np.testing.assert_array_equal(np.asarray(rows[f]), np.asarray(ref[f]))

    # ...and the same state serves through the engine
    pad = {"dense": np.zeros((13,), np.float32), "sparse": np.zeros((3,), np.int32),
           "label": np.zeros((), np.float32)}
    eng = ServeEngine(model.serve_step, state, batch_size=16, pad_example=pad)
    batch = synth.sparse_batch(spec, 7, 1, 0)
    scores = eng.score(batch)
    assert scores.shape == (7,) and np.isfinite(scores).all()
    assert eng.stats.summary()["requests"] == 7


def test_serve_stats_histogram_is_bounded_and_order_independent():
    """ServeStats latency telemetry is O(1) memory (fixed bucket counts, no
    sample list) and, unlike the reservoir it replaced, deterministic: the
    summary is a pure function of the latency POPULATION, not arrival order."""
    from repro.serve.engine import ServeStats

    lat = [1e-3 * (1 + (i % 7)) for i in range(10_000)]
    st = ServeStats()
    for v in lat:
        st.observe(v)
    assert st.batches == 10_000
    assert len(st.hist.counts) == len(st.hist.bounds) + 1  # fixed, not O(n)
    s = st.summary()
    assert s["p50_ms"] > 0 and s["p99_ms"] >= s["p50_ms"]
    assert s["p999_ms"] >= s["p95_ms"] >= s["p50_ms"]
    # quantile bounds never under-report: p99 covers the true 99th pct value
    assert s["p99_ms"] >= 1e3 * sorted(lat)[int(0.99 * len(lat)) - 1]

    st_rev = ServeStats()
    for v in reversed(lat):
        st_rev.observe(v)
    assert st_rev.summary() == s


def test_host_wire_bytes_exact_past_float32_resolution():
    """Satellite regression: cumulative wire traffic must accumulate as an
    exact Python int.  A float32 accumulator loses integer resolution past
    2^24, so at benchmark rates the old scalar drifted within ~25 steps; the
    per-slab (moved rows, row bytes) counters reconstruct the exact count."""
    import dataclasses

    tables = [col.TableConfig("t", vocab=64, dim=8, ids_per_step=8, cache_ratio=0.5,
                              placement=col.Placement.CACHED)]
    coll = col.EmbeddingCollection(tables, col.PlacementPlanner(10**9).plan(tables))
    state = coll.init(jax.random.PRNGKey(0))
    moved = 2**24 + 1  # row_bytes = 32 -> exact total 2^29 + 32, not a float32
    slab = state.slabs["t"]
    state = col.CollectionState(slabs={"t": dataclasses.replace(
        slab, cache=dataclasses.replace(
            slab.cache, misses=jnp.asarray(moved, jnp.int32)))})
    m = coll.metrics(state)
    expect = moved * 32
    from repro.obs.hub import ExactCounter

    got = ExactCounter().observe(m["host_moved_rows"], unit=m["host_row_bytes"])
    assert got == expect and isinstance(got, int)
    # ...and the in-jit float32 convenience scalar demonstrably drifts
    assert int(m["host_wire_bytes"]) != expect

    # the trainer records the exact int in its host-side history
    from repro.train.trainer import Trainer, TrainerConfig

    tr = Trainer(TrainerConfig(max_steps=1), init_fn=lambda: None,
                 step_fn=None, make_batch=None)
    metrics = dict(m, loss=jnp.asarray(0.0, jnp.float32))
    tr._post_step(0, state, metrics, t0=0.0)
    assert tr.history[-1]["host_wire_bytes"] == expect
    assert isinstance(tr.history[-1]["host_wire_bytes"], int)


def test_serve_summary_reports_exact_wire_bytes():
    """The serve engine's summary must survive (and exploit) the per-slab
    counter dicts in the metrics pytree."""
    from repro.serve.engine import ServeEngine

    tables = [col.TableConfig("t", vocab=64, dim=8, ids_per_step=8, cache_ratio=0.5)]
    coll = col.EmbeddingCollection.create(tables, cache_ratio=0.5)
    state = {"emb": coll.init(jax.random.PRNGKey(0))}
    eng = ServeEngine(lambda s, b: (jnp.zeros((1,)), None), state, batch_size=1,
                      pad_example={},
                      state_stats_fn=lambda s: coll.metrics(s["emb"], writeback=False))
    out = eng.summary()
    assert isinstance(out["host_wire_bytes"], int)
    # per-slab counter DICTS stay internal; the hub reconstructs each family
    # to a single exact int instead of leaking the pytree
    assert isinstance(out["host_moved_rows"], int)
    assert "slab_hits" not in out and "slab_misses" not in out


def test_single_arena_plan_is_paper_layout():
    """All-GROUPED = the paper's one concatenated freq-ordered table."""
    tables = small_tables()
    coll = col.EmbeddingCollection.create(tables, cache_ratio=0.1)
    assert not coll.device_slabs
    assert list(coll.cached_slabs) == [col.SHARED_ARENA]
    spec = coll.cached_slabs[col.SHARED_ARENA]
    assert spec.vocab == sum(t.vocab for t in tables)
