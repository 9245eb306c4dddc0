"""Adaptive frequency engine: online decayed counters, incremental
re-ranking refresh purity (the pure-reindexing property), sharded parity,
trainer/serve wiring, drift recovery, and the wrap-free exact counters."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import collection as col
from repro.core import freq as freq_lib
from repro.core.refresh import RefreshConfig, plan_swaps
from repro.core.sharded import ShardedEmbeddingCollection, flat_store


def _fb(tables, n, seed):
    rng = np.random.default_rng(seed)
    return col.FeatureBatch(ids={
        t.name: jnp.asarray(rng.integers(-1, t.vocab, n).astype(np.int32))
        for t in tables
    })


def _tables(dim=8, ids=16):
    return [
        col.TableConfig("big", vocab=512, dim=dim, ids_per_step=ids, cache_ratio=0.1),
        col.TableConfig("small", vocab=96, dim=dim, ids_per_step=ids, cache_ratio=0.3),
    ]


def _counts(tables, seed=1):
    rng = np.random.default_rng(seed)
    return {t.name: rng.integers(0, 50, t.vocab) for t in tables}


def _warm_state(coll, tables, steps=12, seed0=100):
    state = coll.init(jax.random.PRNGKey(0), counts=_counts(tables))
    step = jax.jit(lambda s, f: coll.lookup(s, f))
    for i in range(steps):
        state, _, _ = step(state, _fb(tables, 16, seed0 + i))
    return state


# --------------------------------------------------------------------------
# online tracker
# --------------------------------------------------------------------------


def test_tracker_matches_numpy_decay_oracle():
    """In-jit decayed counters == a numpy simulation of per-step decay."""
    tables = [col.TableConfig("t", vocab=32, dim=4, ids_per_step=6, cache_ratio=0.5)]
    coll = col.EmbeddingCollection.create(tables, cache_ratio=0.5)
    state = coll.init(jax.random.PRNGKey(0))
    half_life = 1024  # CacheConfig default
    d = 2.0 ** (-1.0 / half_life)
    oracle = np.zeros((32,))
    prep = jax.jit(lambda s, f: coll.prepare(s, f))
    rng = np.random.default_rng(0)
    for _ in range(10):
        ids = rng.integers(-1, 32, 6).astype(np.int32)
        state, _ = prep(state, col.FeatureBatch(ids={"t": jnp.asarray(ids)}))
        oracle *= d  # whole-vocab decay, one step
        # idx_map is identity (no counts): rank == raw id
        for r in np.unique(ids[ids >= 0]):
            oracle[r] += 1.0
    slab = state.slabs[col.SHARED_ARENA]
    tr = slab.cache.tracker
    got = freq_lib.decayed_scores(
        np.asarray(tr.score), np.asarray(tr.last_touch),
        int(slab.cache.step), half_life,
    )
    np.testing.assert_allclose(got, oracle, rtol=1e-5, atol=1e-7)
    # rolling window: hits+misses observed, rate in [0, 1]
    m = coll.metrics(state)
    assert 0.0 <= float(m["window_hit_rate"]) <= 1.0
    assert float(tr.win_hits + tr.win_misses) > 0


def test_plan_swaps_bounded_deterministic_and_boundary_only():
    scores = np.asarray([5.0, 1.0, 0.5, 9.0, 0.2, 7.0], np.float64)
    hot = np.asarray([True, True, True, False, False, False])
    a, b = plan_swaps(scores, hot, max_swaps=8)
    # pairs: coldest-hot vs hottest-cold while cold > hot: (2, 3), (1, 5)
    np.testing.assert_array_equal(a, [2, 1])
    np.testing.assert_array_equal(b, [3, 5])
    # bounded
    a1, b1 = plan_swaps(scores, hot, max_swaps=1)
    np.testing.assert_array_equal(a1, [2])
    np.testing.assert_array_equal(b1, [3])
    # ties never swap (strict comparison), identical inputs -> identical plan
    tied = np.ones((6,), np.float64)
    a2, b2 = plan_swaps(tied, hot, max_swaps=8)
    assert a2.size == 0 and b2.size == 0
    a3, b3 = plan_swaps(scores, hot, max_swaps=8)
    np.testing.assert_array_equal(a, a3)
    np.testing.assert_array_equal(b, b3)
    # min_gain hysteresis: 9-0.5=8.5 and 7-1=6 both clear 5.0; only the
    # first clears 7.0 (and the kept set stays a prefix)
    a4, _ = plan_swaps(scores, hot, max_swaps=8, min_gain=5.0)
    assert a4.tolist() == [2, 1]
    a5, _ = plan_swaps(scores, hot, max_swaps=8, min_gain=7.0)
    assert a5.tolist() == [2]


# --------------------------------------------------------------------------
# refresh purity: pure reindexing (THE acceptance property)
# --------------------------------------------------------------------------


def test_refresh_is_pure_reindexing_bitwise_fp32():
    """dense_reference / full_lookup / cached lookup are bitwise identical
    immediately before vs after a refresh (fp32), including with DIRTY
    resident rows (trained state): the dirty copy is written back before its
    rank moves."""
    tables = _tables()
    coll = col.EmbeddingCollection.create(tables, cache_ratio=0.1)
    state = _warm_state(coll, tables)
    # dirty the resident rows (synchronous row update)
    fb = _fb(tables, 16, 777)
    state, addr = coll.prepare(state, fb)
    grads = {k: jnp.ones_like(v) for k, v in coll.weights(state).items()}
    state = coll.apply_grads(state, grads, 0.1)

    probe = _fb(tables, 16, 999)
    ref_before = coll.dense_reference(coll.flush(state), probe)
    ids = jnp.arange(64, dtype=jnp.int32)
    fl_before = coll.full_lookup(coll.flush(state), "big", ids)
    state2, rep = coll.refresh(state, RefreshConfig(max_swaps=32))
    assert rep.total_swaps > 0  # the pass actually did something
    ref_after = coll.dense_reference(coll.flush(state2), probe)
    fl_after = coll.full_lookup(coll.flush(state2), "big", ids)
    for k in ref_before:
        np.testing.assert_array_equal(np.asarray(ref_before[k]), np.asarray(ref_after[k]))
    np.testing.assert_array_equal(np.asarray(fl_before), np.asarray(fl_after))
    # through-cache lookups read the identical values too
    s_a, _, rows_a = coll.lookup(state, probe)
    s_b, _, rows_b = coll.lookup(state2, probe)
    for k in rows_a:
        np.testing.assert_array_equal(np.asarray(rows_a[k]), np.asarray(rows_b[k]))
    # telemetry counters landed in metrics()
    m = coll.metrics(state2)
    assert int(m["refresh_swaps"]) == rep.total_swaps
    assert int(m["refresh_rows_moved"]) == rep.total_rows_moved


def test_refresh_index_maps_stay_consistent():
    """idx_map stays a permutation; row_to_slot/slot_to_row stay mutual
    inverses after surgery (invalidated rows excluded)."""
    tables = _tables()
    coll = col.EmbeddingCollection.create(tables, cache_ratio=0.1)
    state = _warm_state(coll, tables)
    state, _ = coll.refresh(state, RefreshConfig(max_swaps=16))
    slab = state.slabs[col.SHARED_ARENA]
    idx = np.asarray(slab.idx_map)
    assert sorted(idx.tolist()) == list(range(idx.shape[0]))
    s2r = np.asarray(slab.cache.slot_to_row)
    r2s = np.asarray(slab.cache.row_to_slot)
    for slot, row in enumerate(s2r):
        if row >= 0:
            assert r2s[row] == slot
    for row, slot in enumerate(r2s):
        if slot >= 0:
            assert s2r[slot] == row


def test_refresh_int8_host_store_is_codec_noise_bounded():
    """With an int8 host store a refresh's only numeric effect is the one
    quantize round trip of the swapped DIRTY rows; clean encoded rows move
    bit-stably (payload permutes encoded)."""
    tables = _tables()
    coll = col.EmbeddingCollection.create(tables, cache_ratio=0.1,
                                          host_precision="int8")
    state = _warm_state(coll, tables)
    probe = _fb(tables, 16, 999)
    # clean state (just flushed): refresh must be BIT-exact even for int8 —
    # flush wrote residents back, the extra writeback re-encodes the same
    # decoded values (stable projection), and the permute moves encoded rows.
    state = coll.flush(state)
    before = coll.dense_reference(state, probe)
    state2, rep = coll.refresh(state, RefreshConfig(max_swaps=32))
    assert rep.total_swaps > 0
    after = coll.dense_reference(coll.flush(state2), probe)
    for k in before:
        np.testing.assert_array_equal(np.asarray(before[k]), np.asarray(after[k]))


def test_refresh_noop_when_ranking_already_right():
    """A slab whose decayed ranking agrees with the static one emits no
    swaps — refresh converges instead of churning."""
    tables = [col.TableConfig("t", vocab=64, dim=4, ids_per_step=8, cache_ratio=0.25)]
    coll = col.EmbeddingCollection.create(tables, cache_ratio=0.25)
    state = coll.init(jax.random.PRNGKey(0))  # identity idx_map
    prep = jax.jit(lambda s, f: coll.prepare(s, f))
    for _ in range(6):  # traffic on the already-hot head ranks
        ids = jnp.asarray([0, 1, 2, 3, -1, -1, 0, 1], jnp.int32)
        state, _ = prep(state, col.FeatureBatch(ids={"t": ids}))
    state2, rep = coll.refresh(state)
    assert rep.total_swaps == 0
    # unchanged state (no-swap pass returns the slab as-is)
    np.testing.assert_array_equal(
        np.asarray(state.slabs[col.SHARED_ARENA].idx_map),
        np.asarray(state2.slabs[col.SHARED_ARENA].idx_map),
    )


# --------------------------------------------------------------------------
# sharded refresh
# --------------------------------------------------------------------------


def test_one_shard_refresh_bit_identical_to_unsharded():
    tables = _tables()
    un = col.EmbeddingCollection.create(tables, cache_ratio=0.1)
    sh = ShardedEmbeddingCollection.create(tables, num_shards=1, cache_ratio=0.1)
    st_un = _warm_state(un, tables)
    st_sh = _warm_state(sh, tables)
    st_un, rep_un = un.refresh(st_un, RefreshConfig(max_swaps=32))
    st_sh, rep_sh = sh.refresh(st_sh, RefreshConfig(max_swaps=32))
    assert rep_un.swaps == rep_sh.swaps
    for sname in un.cached_slabs:
        a, b = st_un.slabs[sname], st_sh.slabs[sname]
        np.testing.assert_array_equal(np.asarray(a.idx_map), np.asarray(b.idx_map))
        np.testing.assert_array_equal(
            np.asarray(a.full["weight"]),
            np.asarray(flat_store(b.full)["weight"]),
        )
        np.testing.assert_array_equal(
            np.asarray(a.cache.row_to_slot), np.asarray(b.cache.row_to_slot[0])
        )
        np.testing.assert_array_equal(
            np.asarray(a.cache.slot_to_row), np.asarray(b.cache.slot_to_row[0])
        )
        np.testing.assert_array_equal(
            np.asarray(a.cache.cached_rows["weight"]),
            np.asarray(b.cache.cached_rows["weight"][0]),
        )


@pytest.mark.parametrize("num_shards", [3, 4])
def test_sharded_refresh_is_pure_reindexing(num_shards):
    tables = _tables()
    coll = ShardedEmbeddingCollection.create(tables, num_shards=num_shards,
                                             cache_ratio=0.1)
    state = _warm_state(coll, tables)
    probe = _fb(tables, 16, 999)
    before = coll.dense_reference(coll.flush(state), probe)
    state2, rep = coll.refresh(state, RefreshConfig(max_swaps=32))
    after = coll.dense_reference(coll.flush(state2), probe)
    for k in before:
        np.testing.assert_array_equal(np.asarray(before[k]), np.asarray(after[k]))
    # lookups after refresh still match the dense reference (end-to-end)
    step = jax.jit(lambda s, f: coll.lookup(s, f))
    state2, _, rows = step(state2, probe)
    ref = coll.dense_reference(coll.flush(state2), probe)
    for k in rows:
        np.testing.assert_array_equal(np.asarray(rows[k]), np.asarray(ref[k]))


def test_sharded_refresh_exchange_budget_meters_cross_shard_rows():
    tables = _tables()
    coll = ShardedEmbeddingCollection.create(tables, num_shards=4, cache_ratio=0.1)
    state = _warm_state(coll, tables)
    unb, rep_unb = coll.refresh(state, RefreshConfig(max_swaps=32))
    state2, rep = coll.refresh(state, RefreshConfig(max_swaps=32, exchange_budget=4))
    for sname in rep.cross_shard_rows:
        assert rep.cross_shard_rows[sname] <= 4
        # deferral only ever reduces the applied set
        assert rep.swaps[sname] <= rep_unb.swaps[sname]
        assert (
            rep.swaps[sname] + rep.deferred_swaps[sname] == rep_unb.swaps[sname]
        )
    # budget 0 = same-shard swaps only
    _, rep0 = coll.refresh(state, RefreshConfig(max_swaps=32, exchange_budget=0))
    assert all(v == 0 for v in rep0.cross_shard_rows.values())


# --------------------------------------------------------------------------
# drift recovery (the mechanism the engine exists for)
# --------------------------------------------------------------------------


def _drift_hit_rate(with_refresh: bool):
    """Warm on phase-A stats, stream phase-B (shifted hot set), return the
    mean windowed hit rate over the final steps."""
    from repro.data import synth

    vocab, batch, steps = 2000, 128, 40
    tables = [col.TableConfig("t", vocab=vocab, dim=4, ids_per_step=batch,
                              cache_ratio=0.1, freq_half_life=10)]
    coll = col.EmbeddingCollection.create(tables, cache_ratio=0.1)
    phase_a = [synth._zipf_ids(np.random.default_rng(1000 + s), vocab, batch, 1.2)
               for s in range(20)]
    counts = np.zeros((vocab,), np.int64)
    for ids in phase_a:
        np.add.at(counts, ids, 1)
    state = coll.init(jax.random.PRNGKey(0), counts={"t": counts})
    prep = jax.jit(lambda s, f: coll.prepare(s, f))
    (sname,) = coll.cached_slabs
    rates, ph, pm = [], 0, 0
    for s in range(steps):
        ids = (synth._zipf_ids(np.random.default_rng(2000 + s), vocab, batch, 1.2)
               + 1000) % vocab  # hot set moved to a disjoint range
        state, _ = prep(state, col.FeatureBatch(ids={"t": jnp.asarray(ids.astype(np.int32))}))
        c = state.slabs[sname].cache
        h, m = int(jax.device_get(c.hits)), int(jax.device_get(c.misses))
        rates.append((h - ph) / max(h - ph + m - pm, 1))
        ph, pm = h, m
        if with_refresh and (s + 1) % 5 == 0:
            state, _ = coll.refresh(state, RefreshConfig(max_swaps=256))
    return float(np.mean(rates[-10:]))


def test_refresh_recovers_hit_rate_after_hot_set_shift():
    no = _drift_hit_rate(with_refresh=False)
    yes = _drift_hit_rate(with_refresh=True)
    assert yes > no + 0.05, (no, yes)


# --------------------------------------------------------------------------
# trainer / serve wiring
# --------------------------------------------------------------------------


def _dlrm_setup():
    from repro.data import synth
    from repro.models.dlrm import DLRM, DLRMConfig

    cfg = DLRMConfig(vocab_sizes=(4096, 256, 64), embed_dim=8, batch_size=16,
                     cache_ratio=0.25, lr=0.1, bottom_mlp=(16, 8), top_mlp=(16,))
    spec = synth.ZipfSparseSpec(vocab_sizes=cfg.vocab_sizes, n_dense=13)

    def make_batch(step):
        return {k: jnp.asarray(v) for k, v in synth.sparse_batch(spec, 16, 0, step).items()}

    return cfg, make_batch


def test_refresh_interval_fp32_losses_bit_identical_to_no_refresh():
    """Refresh is pure reindexing, so the SERIAL fp32 loss trajectory with
    refresh enabled is bit-identical to the run without it — which also
    proves refresh_interval=None is bit-identical to pre-refresh main."""
    from repro.models.dlrm import DLRM
    from repro.train.trainer import Trainer, TrainerConfig

    cfg, make_batch = _dlrm_setup()

    def losses(refresh_interval):
        model = DLRM(cfg)
        tr = Trainer(
            TrainerConfig(max_steps=8, refresh_interval=refresh_interval),
            init_fn=lambda: model.init(jax.random.PRNGKey(0)),
            step_fn=jax.jit(model.train_step),
            make_batch=make_batch, flush_fn=model.flush,
            refresh_fn=model.refresh,
        )
        tr.run()
        return [h["loss"] for h in tr.history], tr.history

    base, _ = losses(None)
    refreshed, hist = losses(3)
    assert base == refreshed
    # the refresh hook really ran (in-state counters surfaced via metrics)
    assert any(h.get("refresh_swaps", 0) > 0 for h in hist)


@pytest.mark.parametrize("depth", [1, 3])
def test_pipelined_trainer_with_refresh_matches_serial_losses(depth):
    """Group-boundary refreshes keep merged plans valid: the pipelined run
    with refresh stays loss-bit-identical to the serial no-refresh oracle."""
    from repro.models.dlrm import DLRM
    from repro.train.trainer import PipelinedTrainer, Trainer, TrainerConfig

    cfg, make_batch = _dlrm_setup()
    model = DLRM(cfg)
    serial = Trainer(TrainerConfig(max_steps=7),
                     init_fn=lambda: model.init(jax.random.PRNGKey(0)),
                     step_fn=jax.jit(model.train_step),
                     make_batch=make_batch, flush_fn=model.flush)
    serial.run()

    model2 = DLRM(cfg)
    piped = PipelinedTrainer(
        TrainerConfig(max_steps=7, pipeline_depth=depth, refresh_interval=2),
        init_fn=lambda: model2.init(jax.random.PRNGKey(0)),
        plan_fn=jax.jit(model2.plan_step),
        compute_fn=jax.jit(model2.compute_step),
        apply_fn=jax.jit(model2.apply_step),
        make_batch=make_batch, flush_fn=model2.flush,
        refresh_fn=model2.refresh)
    piped.run()
    assert [h["loss"] for h in serial.history] == [h["loss"] for h in piped.history]
    assert [h["step"] for h in serial.history] == [h["step"] for h in piped.history]
    assert any(h.get("refresh_swaps", 0) > 0 for h in piped.history)


def test_serve_engine_refresh_hook_scores_unchanged():
    from repro.models.dlrm import DLRM
    from repro.serve.engine import ServeEngine

    cfg, make_batch = _dlrm_setup()

    def build(refresh_every):
        model = DLRM(cfg)
        state = model.init(jax.random.PRNGKey(0))
        return model, ServeEngine(
            model.serve_step, state, batch_size=16,
            pad_example={"dense": np.zeros((13,), np.float32),
                         "sparse": np.zeros((3,), np.int32),
                         "label": np.zeros((), np.float32)},
            state_stats_fn=lambda s: model.collection.metrics(s["emb"], writeback=False),
            refresh_fn=(lambda s: model.refresh(s, writeback=False))
            if refresh_every else None,
            refresh_every=refresh_every,
        )

    _, plain = build(None)
    _, refreshing = build(2)
    for s in range(6):
        batch = {k: np.asarray(v) for k, v in make_batch(s).items()}
        a = plain.score(batch)
        b = refreshing.score(batch)
        np.testing.assert_array_equal(a, b)  # pure reindexing, serve-side
    summ = refreshing.summary()
    assert summ["refresh_swaps"] > 0
    assert summ["cache_hits"] >= 0 and summ["cache_misses"] >= 0


# --------------------------------------------------------------------------
# satellites: stream counts + exact wrap-free counters
# --------------------------------------------------------------------------


def test_collect_counts_stream_matches_materialized_counts():
    from repro.data.pipeline import Prefetcher

    tables = _tables()
    coll = col.EmbeddingCollection.create(tables, cache_ratio=0.2)
    fbs = [_fb(tables, 16, 300 + i) for i in range(5)]

    # oracle: materialized per-table counts
    want = {t.name: np.zeros((t.vocab,), np.int64) for t in tables}
    for fb in fbs:
        for f, ids in fb.ids.items():
            a = np.asarray(ids).reshape(-1).astype(np.int64)
            np.add.at(want[coll.feature_to_table[f]], a[a >= 0], 1)

    # plain iterator of FeatureBatches
    got = coll.collect_counts_stream(iter(fbs))
    for k in want:
        np.testing.assert_array_equal(got[k], want[k])

    # Prefetcher of (step, batch) pairs ending via the StopIteration contract
    def make(step):
        if step >= len(fbs):
            raise StopIteration
        return fbs[step]

    pf = Prefetcher(make, depth=2)
    try:
        got2 = coll.collect_counts_stream(pf)
    finally:
        pf.close()
    for k in want:
        np.testing.assert_array_equal(got2[k], want[k])

    # max_batches bounds an infinite stream
    def infinite(step):
        return fbs[step % len(fbs)]

    pf2 = Prefetcher(infinite, depth=2)
    try:
        got3 = coll.collect_counts_stream(pf2, max_batches=5)
    finally:
        pf2.close()
    for k in want:
        np.testing.assert_array_equal(got3[k], want[k])


def test_exact_counter_totals_survive_int32_wrap():
    """The satellite bugfix: cumulative int32 hit counters wrap past 2^31;
    the host-side accumulator recovers exact Python-int totals."""
    from repro.obs.hub import ExactCounter

    ec = ExactCounter()
    step = 1 << 28  # 268M events per observation
    seen = 0
    cur = np.int32(0)
    for _ in range(20):  # crosses the int32 wrap twice
        with np.errstate(over="ignore"):
            cur = np.int32(cur + np.int32(step))
        seen += step
        got = ec.observe({"slab": cur})
    assert got == seen  # 5.3B events, far past int32
    assert int(cur) != seen  # the raw counter really did wrap
    # idempotent re-observation
    assert ec.observe({"slab": cur}) == seen


def test_trainer_records_exact_hit_totals():
    from repro.models.dlrm import DLRM
    from repro.train.trainer import Trainer, TrainerConfig

    cfg, make_batch = _dlrm_setup()
    model = DLRM(cfg)
    tr = Trainer(TrainerConfig(max_steps=4),
                 init_fn=lambda: model.init(jax.random.PRNGKey(0)),
                 step_fn=jax.jit(model.train_step),
                 make_batch=make_batch, flush_fn=model.flush)
    tr.run()
    h = tr.history[-1]
    assert isinstance(h["cache_hits"], int) and isinstance(h["cache_misses"], int)
    assert 0.0 <= h["hit_rate_exact"] <= 1.0
    # cumulative: totals only grow along the run
    assert h["cache_hits"] >= tr.history[0]["cache_hits"]
