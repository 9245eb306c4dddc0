"""kernels/cache_ops: bit-identity of the Pallas cache hot path.

Three layers of exactness, each against the historical route it replaces:

* reference ops (``ref.py``) vs the ``jnp.unique`` / full-capacity
  ``jnp.argsort`` oracles they displace — tie-heavy randomized trials;
* the fused ``use_pallas_plan`` planning route vs the oracle route, plan
  field by plan field, across every ``Policy`` variant, with and without
  lookahead pinning, unsharded and sharded (1 and 4 shards);
* the Pallas kernels (interpret mode, forced via
  ``REPRO_FORCE_PALLAS_CACHE_OPS``) vs the reference ops.

Plus chunk-granularity transmitter staging vs scattered-row moves.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cache as cache_lib
from repro.core import transmitter
from repro.core.collection import FeatureBatch, TableConfig
from repro.core.policies import Policy
from repro.core.sharded import ShardedEmbeddingCollection
from repro.kernels.cache_ops import kernel, ref
from repro.store.arena import ArenaStore
from repro.store.codec import get_codec
from repro.store.host_store import HostStore

_BIG = jnp.iinfo(jnp.int32).max // 2
INT_MAX = jnp.iinfo(jnp.int32).max

PLAN_FIELDS = (
    "miss_rows", "victim_slots", "victim_rows", "load_active", "evict_active",
    "slot_to_row", "row_to_slot", "last_used", "use_count", "slots",
    "hits", "misses", "evictions", "uniq_overflows",
)


# ---------------------------------------------------------------------------
# reference ops vs their oracles
# ---------------------------------------------------------------------------


def test_victim_topk_matches_argsort_under_ties():
    rng = np.random.default_rng(0)
    for trial in range(60):
        c = int(rng.integers(4, 400))
        kv = int(rng.integers(1, c + 1))
        # tie-heavy domain plus the planner's sentinel levels
        pool = np.concatenate([
            rng.integers(-4, 4, size=c),
            np.array([_BIG, -_BIG, -(_BIG // 2)]),
        ])
        key = jnp.asarray(rng.choice(pool, size=c), jnp.int32)
        want = jnp.argsort(key, descending=True)[:kv].astype(jnp.int32)
        got = ref.victim_topk(key, kv)
        assert jnp.array_equal(want, got), (trial, c, kv)


def test_victim_topk_all_equal_keys():
    # kv == capacity with every key tied: stable order = ascending index
    key = jnp.full((33,), 7, jnp.int32)
    got = ref.victim_topk(key, 33)
    assert jnp.array_equal(got, jnp.arange(33, dtype=jnp.int32))


def test_dedup_matches_unique_and_true_count():
    rng = np.random.default_rng(1)
    for trial in range(30):
        n = int(rng.integers(4, 120))
        k = int(rng.integers(1, n + 1))
        rows = rng.integers(0, 40, size=n).astype(np.int32)
        rows[rng.random(n) < 0.3] = INT_MAX  # sentinel padding lanes
        rows = jnp.asarray(rows)
        uniq, n_distinct = ref.dedup(rows, k, INT_MAX)
        want = jnp.unique(rows, size=k, fill_value=INT_MAX)
        assert jnp.array_equal(uniq, want), trial
        true = len(set(np.asarray(rows).tolist()) - {int(INT_MAX)})
        assert int(n_distinct) == true, trial


def test_compact_front_matches_stable_argsort():
    rng = np.random.default_rng(2)
    for _ in range(30):
        n = int(rng.integers(2, 64))
        mask = jnp.asarray(rng.random(n) < 0.5)
        vals = jnp.asarray(rng.integers(0, 100, size=n), jnp.int32)
        out_len = int(rng.integers(1, n + 1))
        perm = jnp.argsort(jnp.where(mask, 0, 1), stable=True)
        oracle = vals[perm][:out_len]
        got = ref.compact_front(mask, vals, out_len)
        m = min(int(jnp.sum(mask)), out_len)  # compacted prefix is the contract
        assert jnp.array_equal(got[:m], oracle[:m])
        assert jnp.all(got[m:] == -1)


# ---------------------------------------------------------------------------
# fused plan route vs oracle route
# ---------------------------------------------------------------------------


def _run_pair(policy, lookahead, steps=8, seed=3):
    rng = np.random.default_rng(seed)
    kw = dict(vocab=128, capacity=32, ids_per_step=16, buffer_rows=16,
              policy=policy)
    cfg_o = cache_lib.CacheConfig(**kw)
    cfg_p = cache_lib.CacheConfig(**kw, use_pallas_plan=True)
    ex = {"weight": jnp.zeros((8,), jnp.float32)}
    st_o = cache_lib.init_cache(cfg_o, ex)
    st_p = cache_lib.init_cache(cfg_p, ex)
    full_o = {"weight": jnp.asarray(rng.normal(size=(128, 8)), jnp.float32)}
    full_p = {"weight": full_o["weight"]}
    for step in range(steps):
        rows = jnp.asarray(rng.integers(-1, 128, size=16), jnp.int32)
        fut = None
        if lookahead:
            fut = jnp.asarray(rng.integers(-1, 128, size=16), jnp.int32)
        p_o = cache_lib.plan_prepare(cfg_o, st_o, rows, future_rows=fut)
        p_p = cache_lib.plan_prepare(cfg_p, st_p, rows, future_rows=fut)
        for f in PLAN_FIELDS:
            assert jnp.array_equal(getattr(p_o, f), getattr(p_p, f)), (
                policy, lookahead, step, f
            )
        full_o, st_o = cache_lib.apply_plan(cfg_o, full_o, st_o, p_o)
        full_p, st_p = cache_lib.apply_plan(cfg_p, full_p, st_p, p_p)
        assert jnp.array_equal(full_o["weight"], full_p["weight"])
        assert jnp.array_equal(
            st_o.cached_rows["weight"], st_p.cached_rows["weight"]
        )


@pytest.mark.parametrize("policy", list(Policy))
def test_fused_plan_bit_identical(policy):
    _run_pair(policy, lookahead=False)


@pytest.mark.parametrize("policy", list(Policy))
def test_fused_plan_bit_identical_with_lookahead(policy):
    _run_pair(policy, lookahead=True, seed=4)


def test_lookahead_pinning_identical_under_pressure():
    # capacity == unique buffer: future loads compete with pins — the branch
    # where merge order and the n_fut_load clip actually matter.
    rng = np.random.default_rng(5)
    kw = dict(vocab=64, capacity=16, ids_per_step=16, buffer_rows=8)
    cfg_o = cache_lib.CacheConfig(**kw)
    cfg_p = cache_lib.CacheConfig(**kw, use_pallas_plan=True)
    ex = {"weight": jnp.zeros((4,), jnp.float32)}
    st_o = cache_lib.init_cache(cfg_o, ex)
    st_p = cache_lib.init_cache(cfg_p, ex)
    full = {"weight": jnp.asarray(rng.normal(size=(64, 4)), jnp.float32)}
    full_o, full_p = dict(full), dict(full)
    for step in range(10):
        rows = jnp.asarray(rng.integers(-1, 64, size=16), jnp.int32)
        fut = jnp.asarray(rng.integers(-1, 64, size=16), jnp.int32)
        p_o = cache_lib.plan_prepare(cfg_o, st_o, rows, future_rows=fut)
        p_p = cache_lib.plan_prepare(cfg_p, st_p, rows, future_rows=fut)
        for f in PLAN_FIELDS:
            assert jnp.array_equal(getattr(p_o, f), getattr(p_p, f)), (step, f)
        full_o, st_o = cache_lib.apply_plan(cfg_o, full_o, st_o, p_o)
        full_p, st_p = cache_lib.apply_plan(cfg_p, full_p, st_p, p_p)
        assert jnp.array_equal(full_o["weight"], full_p["weight"])


@pytest.mark.parametrize("shards,rep_k", [(1, 0), (4, 8)])
def test_sharded_fused_plan_bit_identical(shards, rep_k):
    rng = np.random.default_rng(6)
    tables = [TableConfig("a", 192, 8, 32), TableConfig("b", 96, 8, 32)]
    c_o = ShardedEmbeddingCollection.create(
        tables, num_shards=shards, replicate_top_k=rep_k
    )
    c_p = ShardedEmbeddingCollection.create(
        tables, num_shards=shards, replicate_top_k=rep_k, use_pallas_plan=True
    )
    s_o = c_o.init(jax.random.PRNGKey(0))
    s_p = c_p.init(jax.random.PRNGKey(0))
    for step in range(4):
        fb = FeatureBatch(ids={
            "a": jnp.asarray(rng.integers(0, 192, size=32), jnp.int32),
            "b": jnp.asarray(rng.integers(0, 96, size=32), jnp.int32),
        })
        p_o = c_o.plan_prepare(s_o, fb)
        p_p = c_p.plan_prepare(s_p, fb)
        for x, y in zip(
            jax.tree_util.tree_leaves(p_o), jax.tree_util.tree_leaves(p_p)
        ):
            assert jnp.array_equal(x, y), (shards, step)
        s_o = c_o.apply_plan(s_o, p_o)
        s_p = c_p.apply_plan(s_p, p_p)
        for x, y in zip(
            jax.tree_util.tree_leaves(s_o), jax.tree_util.tree_leaves(s_p)
        ):
            assert jnp.array_equal(x, y), (shards, step)


# ---------------------------------------------------------------------------
# Pallas kernels (interpret mode) vs reference ops
# ---------------------------------------------------------------------------


def test_victim_threshold_kernel_matches_ref(monkeypatch):
    monkeypatch.setattr(kernel, "THRESHOLD_BLOCK_ROWS", 8)
    rng = np.random.default_rng(7)
    for trial in range(10):
        c = int(rng.integers(8, 3000))  # up to 3 tiles of 8 x 128 lanes
        kv = int(rng.integers(1, c + 1))
        key = jnp.asarray(rng.integers(-1000, 1000, size=c), jnp.int32)
        u = ref.ordered_u32(key)
        t, n_gt = kernel.victim_threshold_pallas(u, kv, interpret=True)
        srt = jnp.sort(u, descending=True)
        assert jnp.array_equal(t, srt[kv - 1]), trial
        assert int(n_gt) == int(jnp.sum(u > srt[kv - 1])), trial


def test_bucketize_kernel_matches_ref():
    rng = np.random.default_rng(8)
    owner = jnp.asarray(rng.integers(-1, 4, size=48), jnp.int32)
    local = jnp.asarray(
        np.where(rng.random(48) < 0.2, -1, rng.integers(0, 100, size=48)),
        jnp.int32,
    )
    want = ref.bucketize(owner, local, 4)
    got = kernel.bucketize_pallas(owner, local, 4, interpret=True)
    assert jnp.array_equal(want, got)


def test_bucketize_kernel_multi_tile_matches_ref(monkeypatch):
    monkeypatch.setattr(kernel, "BUCKETIZE_BLOCK_ROWS", 8)
    # 3000 lanes fold into three 8 x 128 tiles plus padding
    rng = np.random.default_rng(13)
    owner = jnp.asarray(rng.integers(-1, 4, size=3000), jnp.int32)
    local = jnp.asarray(rng.integers(-1, 900, size=3000), jnp.int32)
    want = ref.bucketize(owner, local, 4)
    got = kernel.bucketize_pallas(owner, local, 4, interpret=True)
    assert jnp.array_equal(want, got)


def test_f16_bits_decode_exact_for_every_half():
    """The kernel's float16 -> float32 widening from raw bits agrees with
    XLA's convert on all 65,536 half patterns (NaNs stay NaN)."""
    bits = jnp.arange(-(1 << 15), 1 << 15, dtype=jnp.int32).astype(jnp.int16)
    want = jax.lax.bitcast_convert_type(bits, jnp.float16).astype(jnp.float32)
    got = jax.jit(kernel._f16_bits_to_f32)(bits.astype(jnp.int32))
    nan = jnp.isnan(want)
    assert jnp.array_equal(jnp.isnan(got), nan)
    wb = jax.lax.bitcast_convert_type(want, jnp.int32)
    gb = jax.lax.bitcast_convert_type(got, jnp.int32)
    assert jnp.array_equal(jnp.where(nan, 0, wb), jnp.where(nan, 0, gb))


@pytest.mark.parametrize("codec", ["fp16", "int8"])
def test_gather_decode_kernel_matches_ref(codec):
    """Under ``GATHER_CHUNK`` < the lane count this exercises the
    SMEM-bounded device loop (``test_gather_decode_kernel_chunked_matches_ref``)."""
    rng = np.random.default_rng(9)
    ar = ArenaStore.create(
        {"w": jnp.asarray(rng.normal(size=(32, 8)), jnp.float32)}, 8, codec
    )
    slots = jnp.asarray([-1, 0, 5, 7, 8, 15, 31, 40, -3, 2], jnp.int32)
    c = get_codec(codec)
    # jit both: the production context (FMA selection agrees under jit)
    want = jax.jit(
        lambda h, t, s, sl: ref.arena_gather(h, t, s, sl, c.decode, jnp.float32)
    )(ar.head["w"], ar.tail["w"], ar.sideband.get("w"), slots)
    got = jax.jit(
        lambda h, t, s, sl: kernel.gather_decode_pallas(
            h, t, s, sl, codec, jnp.float32, interpret=True
        )
    )(ar.head["w"], ar.tail["w"], ar.sideband.get("w"), slots)
    assert jnp.array_equal(want, got)


@pytest.mark.parametrize("codec", ["fp16", "int8"])
def test_gather_decode_kernel_chunked_matches_ref(monkeypatch, codec):
    monkeypatch.setattr(kernel, "GATHER_CHUNK", 4)
    test_gather_decode_kernel_matches_ref(codec)


def test_forced_pallas_route_full_plan():
    """REPRO_FORCE_PALLAS_CACHE_OPS=1 (the CI interpret-mode smoke) must keep
    the whole fused plan + int8 arena gather bit-identical.  Run in a
    subprocess: the flag is read at trace time and this process has traces
    cached without it."""
    prog = textwrap.dedent("""
        import os
        os.environ["REPRO_FORCE_PALLAS_CACHE_OPS"] = "1"
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import cache as cache_lib
        from repro.kernels.cache_ops import ops
        assert ops.kernels_enabled()
        rng = np.random.default_rng(10)
        kw = dict(vocab=96, capacity=24, ids_per_step=12, buffer_rows=8,
                  arena_precision="int8")
        cfg_o = cache_lib.CacheConfig(**kw)
        cfg_p = cache_lib.CacheConfig(**kw, use_pallas_plan=True)
        ex = {"weight": jnp.zeros((8,), jnp.float32)}
        st_o = cache_lib.init_cache(cfg_o, ex)
        st_p = cache_lib.init_cache(cfg_p, ex)
        full_o = {"weight": jnp.asarray(rng.normal(size=(96, 8)), jnp.float32)}
        full_p = {"weight": full_o["weight"]}
        for step in range(4):
            rows = jnp.asarray(rng.integers(-1, 96, size=12), jnp.int32)
            p_o = cache_lib.plan_prepare(cfg_o, st_o, rows)
            p_p = cache_lib.plan_prepare(cfg_p, st_p, rows)
            assert jnp.array_equal(p_o.victim_slots, p_p.victim_slots), step
            assert jnp.array_equal(p_o.miss_rows, p_p.miss_rows), step
            full_o, st_o = cache_lib.apply_plan(cfg_o, full_o, st_o, p_o)
            full_p, st_p = cache_lib.apply_plan(cfg_p, full_p, st_p, p_p)
            assert jnp.array_equal(full_o["weight"], full_p["weight"]), step
            ga = st_o.cached_rows.gather_slots(jnp.arange(24, dtype=jnp.int32))
            gb = st_p.cached_rows.gather_slots(jnp.arange(24, dtype=jnp.int32))
            assert jnp.array_equal(ga["weight"], gb["weight"]), step
        print("OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout


# ---------------------------------------------------------------------------
# chunk-granularity staging
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scr,dcr", [(8, 0), (0, 8), (8, 8), (16, 4), (5, 3)])
def test_chunked_move_bit_identical(scr, dcr):
    rng = np.random.default_rng(11)
    src = {"w": jnp.asarray(rng.normal(size=(64, 4)), jnp.float32)}
    dst = {"w": jnp.asarray(rng.normal(size=(32, 4)), jnp.float32)}
    si = jnp.asarray(rng.integers(-1, 64, size=24), jnp.int32)
    di = jnp.asarray(rng.permutation(32)[:24], jnp.int32)
    ac = jnp.asarray(rng.integers(0, 2, size=24), bool)
    base = transmitter.move_rows(src, dict(dst), si, di, ac, buffer_rows=8)
    got = transmitter.move_rows(src, dict(dst), si, di, ac, buffer_rows=8,
                                src_chunk_rows=scr, dst_chunk_rows=dcr)
    assert jnp.array_equal(base["w"], got["w"])


@pytest.mark.parametrize("codec", ["fp32", "fp16", "int8"])
def test_chunked_move_hoststore_bit_identical(codec):
    rng = np.random.default_rng(12)
    hs = HostStore.create(
        {"w": jnp.asarray(rng.normal(size=(64, 4)), jnp.float32)}, codec
    )
    dst = {"w": jnp.asarray(rng.normal(size=(32, 4)), jnp.float32)}
    si = jnp.asarray(rng.integers(-1, 64, size=24), jnp.int32)
    di = jnp.asarray(rng.permutation(32)[:24], jnp.int32)
    ac = jnp.asarray(rng.integers(0, 2, size=24), bool)
    # encoded source chunked
    base = transmitter.move_rows(hs, dict(dst), si, di, ac, buffer_rows=8)
    got = transmitter.move_rows(hs, dict(dst), si, di, ac, buffer_rows=8,
                                src_chunk_rows=8)
    assert jnp.array_equal(base["w"], got["w"])
    # encoded destination chunked (RMW writeback)
    src = {"w": jnp.asarray(rng.normal(size=(32, 4)), jnp.float32)}
    di2 = jnp.asarray(rng.permutation(64)[:24], jnp.int32)
    a = transmitter.move_rows(src, hs, di, di2, ac, buffer_rows=8)
    b = transmitter.move_rows(src, hs, di, di2, ac, buffer_rows=8,
                              dst_chunk_rows=8)
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        assert jnp.array_equal(x, y)


def test_chunked_cache_pipeline_bit_identical():
    """chunk_rows threads through apply_plan/flush/warmup unchanged."""
    rng = np.random.default_rng(13)
    kw = dict(vocab=128, capacity=32, ids_per_step=16, buffer_rows=16)
    cfg_o = cache_lib.CacheConfig(**kw)
    cfg_c = cache_lib.CacheConfig(**kw, chunk_rows=8, use_pallas_plan=True)
    ex = {"weight": jnp.zeros((8,), jnp.float32)}
    st_o = cache_lib.init_cache(cfg_o, ex)
    st_c = cache_lib.init_cache(cfg_c, ex)
    full_o = {"weight": jnp.asarray(rng.normal(size=(128, 8)), jnp.float32)}
    full_c = {"weight": full_o["weight"]}
    full_o, st_o = cache_lib.warmup(cfg_o, full_o, st_o)
    full_c, st_c = cache_lib.warmup(cfg_c, full_c, st_c)
    for _ in range(4):
        rows = jnp.asarray(rng.integers(-1, 128, size=16), jnp.int32)
        full_o, st_o, sl_o = cache_lib.prepare(cfg_o, full_o, st_o, rows)
        full_c, st_c, sl_c = cache_lib.prepare(cfg_c, full_c, st_c, rows)
        assert jnp.array_equal(sl_o, sl_c)
        assert jnp.array_equal(full_o["weight"], full_c["weight"])
    full_o, st_o = cache_lib.flush(cfg_o, full_o, st_o)
    full_c, st_c = cache_lib.flush(cfg_c, full_c, st_c)
    assert jnp.array_equal(full_o["weight"], full_c["weight"])
    assert jnp.array_equal(
        st_o.cached_rows["weight"], st_c.cached_rows["weight"]
    )
