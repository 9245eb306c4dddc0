"""Cache core: Algorithm 1 invariants + exactness against a dense oracle."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cached_embedding as ce
from repro.core import cache as cache_lib
from repro.core.policies import Policy


def make_cfg(**kw):
    kw.setdefault("vocab_sizes", (50, 30))
    kw.setdefault("dim", 8)
    kw.setdefault("ids_per_step", 12)
    kw.setdefault("cache_ratio", 0.2)
    kw.setdefault("buffer_rows", 5)
    return ce.CachedEmbeddingConfig(**kw)


def zipf_counts(vocab, seed=0):
    z = np.random.default_rng(seed).zipf(1.5, size=100_000) % vocab
    return np.bincount(z, minlength=vocab)


@pytest.fixture(scope="module")
def state_and_cfg():
    cfg = make_cfg()
    st = ce.init_state(jax.random.PRNGKey(0), cfg, counts=zipf_counts(cfg.vocab))
    return cfg, st


def test_exactness_vs_oracle_stream(state_and_cfg):
    """THE paper property: cache = pure data movement, lookups exact."""
    cfg, st = state_and_cfg
    step = jax.jit(lambda s, i: ce.embed_onehot(cfg, s, i))
    key = jax.random.PRNGKey(1)
    for _ in range(25):
        key, k = jax.random.split(key)
        ids = jax.random.randint(k, (6, 2), 0, jnp.array([50, 30])).astype(jnp.int32)
        st, slots, emb = step(st, ids)
        ref = ce.dense_reference_lookup(ce.flush_state(cfg, st), ids)
        np.testing.assert_allclose(np.asarray(emb), np.asarray(ref), rtol=0, atol=0)


def test_all_requested_rows_resident(state_and_cfg):
    cfg, st = state_and_cfg
    ids = jax.random.randint(jax.random.PRNGKey(3), (12,), 0, 50).astype(jnp.int32)
    st2, slots = ce.prepare_ids(cfg, st, ids)
    assert bool((np.asarray(slots) >= 0).all())
    # slot/row maps are mutually inverse on resident rows
    s2r = np.asarray(st2.cache.slot_to_row)
    r2s = np.asarray(st2.cache.row_to_slot)
    for slot, row in enumerate(s2r):
        if row >= 0:
            assert r2s[row] == slot
    resident_rows = s2r[s2r >= 0]
    assert len(np.unique(resident_rows)) == len(resident_rows), "duplicate cached rows"


def test_padding_gives_zero_rows(state_and_cfg):
    cfg, st = state_and_cfg
    ids = jnp.full((12,), -1, jnp.int32)
    st2, slots = ce.prepare_ids(cfg, st, ids)
    assert bool((np.asarray(slots) == -1).all())
    rows = ce.gather_slots(st2, slots)
    assert bool((np.asarray(rows) == 0).all())


def test_freq_lfu_evicts_coldest():
    """With freq-ordered rows, victims must be the largest-rank resident rows."""
    cfg = make_cfg(vocab_sizes=(40,), ids_per_step=4, cache_ratio=0.25)  # capacity 10
    st = ce.init_state(jax.random.PRNGKey(0), cfg, warm=True)  # rows 0..9 resident
    # touch 4 cold rows -> must evict ranks 9,8,7,6 (the coldest), keep 0..5
    st2, _ = ce.prepare_ids(cfg, st, jnp.array([30, 31, 32, 33], jnp.int32))
    resident = set(np.asarray(st2.cache.slot_to_row).tolist())
    assert {0, 1, 2, 3, 4, 5} <= resident
    assert {6, 7, 8, 9}.isdisjoint(resident)


def test_protected_rows_never_evicted():
    """Algorithm 1 'backlist': rows needed now survive even if coldest."""
    cfg = make_cfg(vocab_sizes=(40,), ids_per_step=8, cache_ratio=0.25)
    st = ce.init_state(jax.random.PRNGKey(0), cfg, warm=True)
    # request the two coldest resident rows + 6 new ones; the two must stay
    ids = jnp.array([8, 9, 20, 21, 22, 23, 24, 25], jnp.int32)
    st2, slots = ce.prepare_ids(cfg, st, ids)
    resident = set(np.asarray(st2.cache.slot_to_row).tolist())
    assert {8, 9, 20, 21, 22, 23, 24, 25} <= resident


def test_hit_rate_improves_with_skew(state_and_cfg):
    cfg, _ = state_and_cfg
    st = ce.init_state(jax.random.PRNGKey(0), cfg, counts=zipf_counts(cfg.vocab))
    rng = np.random.default_rng(0)
    step = jax.jit(lambda s, i: ce.embed_onehot(cfg, s, i))
    for _ in range(30):
        # zipf-distributed raw ids favour hot (low-rank) rows
        ids = (rng.zipf(1.7, size=(6, 2)) % np.array([50, 30])).astype(np.int32)
        st, _, _ = step(st, jnp.asarray(ids))
    assert float(st.cache.hit_rate()) > 0.5


def test_policies_all_run(state_and_cfg):
    for pol in Policy:
        cfg = make_cfg(policy=pol)
        st = ce.init_state(jax.random.PRNGKey(0), cfg)
        st, _, emb = ce.embed_onehot(cfg, st, jnp.zeros((6, 2), jnp.int32))
        assert bool(jnp.isfinite(emb).all())


def test_update_then_flush_roundtrip(state_and_cfg):
    cfg, st = state_and_cfg
    ids = jax.random.randint(jax.random.PRNGKey(5), (6, 2), 0, 30).astype(jnp.int32)
    st, slots, emb = ce.embed_onehot(cfg, st, ids)
    g = jnp.ones_like(st.cache.cached_rows["weight"])
    st = ce.apply_row_grads(cfg, st, g, lr=0.5)
    st_f = ce.flush_state(cfg, st)
    ref = ce.dense_reference_lookup(st_f, ids)
    _, _, emb2 = ce.embed_onehot(cfg, st_f, ids)
    np.testing.assert_allclose(np.asarray(emb2), np.asarray(ref))


def test_rowwise_adagrad_rows_travel_with_cache():
    cfg = make_cfg(rowwise_adagrad=True)
    st = ce.init_state(jax.random.PRNGKey(0), cfg)
    ids = jnp.arange(12, dtype=jnp.int32)
    st, slots = ce.prepare_ids(cfg, st, ids)
    g = jnp.ones_like(st.cache.cached_rows["weight"])
    st = ce.apply_row_grads(cfg, st, g, lr=0.1)
    assert float(st.cache.cached_rows["accum"].max()) > 0
    st_f = ce.flush_state(cfg, st)
    assert float(st_f.full["accum"].max()) > 0  # accumulator written back


def test_unique_overflow_detected():
    cfg = make_cfg(vocab_sizes=(100,), ids_per_step=16, max_unique_per_step=4, cache_ratio=0.3)
    st = ce.init_state(jax.random.PRNGKey(0), cfg)
    ids = jnp.arange(16, dtype=jnp.int32)  # 16 distinct > bound of 4
    st2, _ = ce.prepare_ids(cfg, st, ids)
    assert int(st2.cache.uniq_overflows) == 1
    st3, _ = ce.prepare_ids(cfg, st2, jnp.zeros(16, jnp.int32))  # 1 distinct: fine
    assert int(st3.cache.uniq_overflows) == 1


def test_writeback_false_keeps_full_table():
    cfg = make_cfg(writeback=False)
    st = ce.init_state(jax.random.PRNGKey(0), cfg)
    before = np.asarray(st.full["weight"]).copy()
    st2, _ = ce.prepare_ids(cfg, st, jax.random.randint(jax.random.PRNGKey(1), (12,), 0, 80).astype(jnp.int32))
    np.testing.assert_array_equal(before, np.asarray(st2.full["weight"]))


# --------------------------------------------------------------------------
# eviction_key: every Policy variant against a numpy oracle + tie order
# --------------------------------------------------------------------------


def _key_oracle(policy, slot_to_row, last_used, use_count):
    """Independent numpy statement of each policy's eviction key."""
    if policy is Policy.FREQ_LFU:
        return slot_to_row.astype(np.int64)  # static rank: larger = colder
    if policy in (Policy.LRU, Policy.UVM_ROW):
        return -last_used.astype(np.int64)  # oldest access evicts first
    if policy is Policy.RUNTIME_LFU:
        return -use_count.astype(np.int64)  # fewest uses evicts first
    raise AssertionError(policy)


@pytest.mark.parametrize(
    "policy", [Policy.FREQ_LFU, Policy.LRU, Policy.RUNTIME_LFU, Policy.UVM_ROW]
)
def test_eviction_key_matches_numpy_oracle(policy):
    rng = np.random.default_rng(0)
    slot_to_row = rng.integers(-1, 40, 24).astype(np.int32)
    last_used = rng.integers(0, 9, 24).astype(np.int32)
    use_count = rng.integers(0, 5, 24).astype(np.int32)
    got = np.asarray(
        cache_lib.eviction_key(
            policy,
            jnp.asarray(slot_to_row),
            jnp.asarray(last_used),
            jnp.asarray(use_count),
        )
    )
    np.testing.assert_array_equal(got, _key_oracle(policy, slot_to_row, last_used, use_count))


@pytest.mark.parametrize(
    "policy", [Policy.FREQ_LFU, Policy.LRU, Policy.RUNTIME_LFU, Policy.UVM_ROW]
)
def test_victim_order_deterministic_under_ties(policy):
    """plan_prepare's victim order is a STABLE descending argsort of the key:
    tied slots evict in slot order, identically across calls — every data
    rank must pick the same victims (the determinism the paper's replicated
    bookkeeping relies on)."""
    cfg = cache_lib.CacheConfig(
        vocab=40, capacity=8, ids_per_step=4, policy=policy, buffer_rows=4
    )
    st = cache_lib.init_cache(cfg, {"weight": jnp.zeros((4,), jnp.float32)})
    # fill all 8 slots with rows 0..7; uniform recency/use -> all keys tie
    # (FREQ_LFU keys differ by construction; the others are fully tied)
    full = {"weight": jnp.arange(40 * 4, dtype=jnp.float32).reshape(40, 4)}
    full, st, _ = cache_lib.prepare(cfg, full, st, jnp.arange(8, dtype=jnp.int32)[:4])
    full, st, _ = cache_lib.prepare(cfg, full, st, jnp.arange(4, 8, dtype=jnp.int32))
    plan_a = cache_lib.plan_prepare(cfg, st, jnp.asarray([20, 21, 22, 23], jnp.int32))
    plan_b = cache_lib.plan_prepare(cfg, st, jnp.asarray([20, 21, 22, 23], jnp.int32))
    np.testing.assert_array_equal(
        np.asarray(plan_a.victim_slots), np.asarray(plan_b.victim_slots)
    )
    # numpy oracle of the same stable descending order over the key
    key = np.asarray(
        cache_lib.eviction_key(policy, st.slot_to_row, st.last_used, st.use_count)
    ).astype(np.int64)
    key[np.asarray(st.slot_to_row) < 0] = np.iinfo(np.int32).max // 2  # empty first
    protected = np.isin(np.asarray(st.slot_to_row), [20, 21, 22, 23])
    key[protected] = -(np.iinfo(np.int32).max // 2)
    # stable descending == lexsort on (slot asc) within equal -key
    order = np.lexsort((np.arange(8), -key))
    np.testing.assert_array_equal(np.asarray(plan_a.victim_slots), order[:4])


def test_uvm_row_key_is_recency_not_frequency():
    """UVM_ROW (the TorchRec-UVM stand-in) must key on recency: a slot with
    huge use_count but stale last_used evicts before a fresh slot."""
    slot_to_row = jnp.asarray([0, 1], jnp.int32)
    last_used = jnp.asarray([1, 9], jnp.int32)
    use_count = jnp.asarray([100, 1], jnp.int32)
    key = np.asarray(
        cache_lib.eviction_key(Policy.UVM_ROW, slot_to_row, last_used, use_count)
    )
    assert key[0] > key[1]  # stale slot carries the larger (evict-first) key


# --------------------------------------------------------------------------
# apply_plan runs only the transmitter rounds that reach the plan's loads
# --------------------------------------------------------------------------


def _static_apply(cfg, full, state, plan):
    """``apply_plan`` as it ran every round: both moves at the static round
    count, the plan's index image installed."""
    from repro.core import transmitter

    if cfg.writeback:
        full = transmitter.move_rows(
            state.cached_rows, full, plan.victim_slots, plan.victim_rows,
            plan.evict_active, buffer_rows=cfg.buffer_rows, dst_chunk_rows=cfg.chunk_rows)
    cached = transmitter.move_rows(
        full, state.cached_rows, plan.miss_rows, plan.victim_slots, plan.load_active,
        buffer_rows=cfg.buffer_rows, src_chunk_rows=cfg.chunk_rows)
    return full, cached


@pytest.mark.parametrize("writeback", [True, False])
@pytest.mark.parametrize("lookahead", [False, True])
@pytest.mark.parametrize("use_pallas_plan", [False, True])
def test_apply_plan_live_rounds_bit_identical_to_static_rounds(
    use_pallas_plan, lookahead, writeback
):
    """Over a stream whose miss counts span zero to several rounds, each
    ``apply_plan`` gives the arena, slow tier and index image the static
    round count gives, and ``move_rounds`` grows by ceil(loads / buffer)."""
    cfg = cache_lib.CacheConfig(vocab=80, capacity=24, ids_per_step=16, buffer_rows=3,
                                use_pallas_plan=use_pallas_plan, writeback=writeback)
    rng = np.random.default_rng(5)
    full = {"weight": jnp.asarray(rng.normal(size=(80, 8)), jnp.float32)}
    state = cache_lib.init_cache(cfg, {"weight": jnp.zeros((8,), jnp.float32)})
    plan_j = jax.jit(lambda s, r, f: cache_lib.plan_prepare(
        cfg, s, r, future_rows=f if lookahead else None))
    apply_j = jax.jit(lambda f, s, p: cache_lib.apply_plan(cfg, f, s, p))
    batches = [rng.integers(-1, 80, 16) for _ in range(3)]
    batches += [batches[-1], np.full(16, -1), rng.integers(0, 6, 16), rng.integers(-1, 80, 16)]
    rounds, seen = 0, set()
    for i, rows in enumerate(batches):
        fut = jnp.asarray(batches[(i + 1) % len(batches)][:8], jnp.int32)
        plan = plan_j(state, jnp.asarray(rows, jnp.int32), fut)
        loads = int(jnp.sum(plan.load_active))
        act = np.asarray(plan.load_active)
        assert not act[loads:].any()  # the loads sit at the front
        assert not (np.asarray(plan.evict_active) & ~act).any()
        want_full, want_rows = _static_apply(cfg, full, state, plan)
        full, new = apply_j(full, state, plan)
        for a, b in zip(jax.tree_util.tree_leaves((want_full, want_rows)),
                        jax.tree_util.tree_leaves((full, new.cached_rows))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for name in ("slot_to_row", "row_to_slot", "last_used", "use_count", "step",
                     "hits", "misses", "uniq_rows", "evictions"):
            np.testing.assert_array_equal(np.asarray(getattr(new, name)),
                                          np.asarray(getattr(plan, name)))
        rounds += -(-loads // cfg.buffer_rows)
        assert int(new.move_rounds) == rounds
        seen.add(-(-loads // cfg.buffer_rows))
        state = new
    assert 0 in seen and max(seen) >= 3, seen  # empty plans and multi-round ones
