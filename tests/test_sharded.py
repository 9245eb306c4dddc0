"""Hybrid-parallel sharded EmbeddingCollection: the planner's device
assignment, sharded-vs-single-device exactness (the acceptance property),
host-precision interplay, checkpointing, and the forced-4-device mesh path."""
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import collection as col
from repro.core.sharded import ShardedEmbeddingCollection, flat_store

REPO = pathlib.Path(__file__).resolve().parents[1]


def small_tables(dim=8, ids=16):
    return [
        col.TableConfig("big", vocab=512, dim=dim, ids_per_step=ids, cache_ratio=0.2),
        col.TableConfig("small", vocab=96, dim=dim, ids_per_step=ids, cache_ratio=0.3),
    ]


def rand_fb(tables, n, seed):
    rng = np.random.default_rng(seed)
    return col.FeatureBatch(ids={
        t.name: jnp.asarray(rng.integers(-1, t.vocab, n).astype(np.int32))
        for t in tables
    })


# --------------------------------------------------------------------------
# planner device-assignment pass
# --------------------------------------------------------------------------


def test_assign_devices_balances_expected_traffic():
    # Zipf-ish skew whose hottest rank holds < 1/S of the mass, so a near-1.0
    # balance is achievable (when one rank dominates, its share is the floor)
    counts = 1e6 / (np.arange(1000, dtype=np.float64) + 1) ** 0.8
    a = col.PlacementPlanner.assign_devices(1000, 4, counts)
    assert a.owner.shape == (1000,) and a.local.shape == (1000,)
    # every shard holds at most ceil(vocab/S) rows; together they hold all
    assert a.shard_rows.max() <= a.rows_per_shard
    assert a.shard_rows.sum() == 1000
    # locals are dense per shard: 0..rows-1
    for s in range(4):
        got = np.sort(a.local[a.owner == s])
        np.testing.assert_array_equal(got, np.arange(a.shard_rows[s]))
    # greedy LPT balances the count mass well (max/mean close to 1)
    assert a.imbalance() < 1.05, a.shard_load
    # deterministic: same inputs, same assignment
    b = col.PlacementPlanner.assign_devices(1000, 4, counts)
    np.testing.assert_array_equal(a.owner, b.owner)


def test_assign_devices_round_robin_without_counts():
    a = col.PlacementPlanner.assign_devices(10, 3, None)
    np.testing.assert_array_equal(a.owner, np.arange(10) % 3)
    np.testing.assert_array_equal(a.local, np.arange(10) // 3)


def test_assign_devices_rejects_bad_shapes():
    with pytest.raises(ValueError):
        col.PlacementPlanner.assign_devices(10, 0)
    with pytest.raises(ValueError):
        col.PlacementPlanner.assign_devices(10, 2, np.ones(7))


# --------------------------------------------------------------------------
# exactness: sharded == dense reference, every step (the paper property)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("num_shards", [1, 3, 4])
def test_sharded_lookup_matches_dense_reference_bitwise(num_shards):
    tables = small_tables()
    coll = ShardedEmbeddingCollection.create(tables, num_shards=num_shards,
                                             cache_ratio=0.2)
    rng = np.random.default_rng(1)
    counts = {t.name: rng.integers(0, 50, t.vocab) for t in tables}
    state = coll.init(jax.random.PRNGKey(0), counts=counts)
    step = jax.jit(lambda s, fb: coll.lookup(s, fb))
    for i in range(10):
        fb = rand_fb(tables, 16, seed=100 + i)
        state, addr, rows = step(state, fb)
        ref = coll.dense_reference(coll.flush(state), fb)
        for f in fb.features:
            np.testing.assert_array_equal(np.asarray(rows[f]), np.asarray(ref[f]))
        pad = np.asarray(fb.ids[f]) < 0
        assert bool((np.asarray(addr[f])[pad] == -1).all())


def test_one_shard_is_bit_identical_to_unsharded_collection():
    """mesh=1 shard must be the unsharded collection, bit for bit: same init
    draws, same table contents, same addresses-modulo-layout gathers."""
    tables = small_tables()
    ref = col.EmbeddingCollection.create(tables, cache_ratio=0.2)
    sc = ShardedEmbeddingCollection.create(tables, num_shards=1, cache_ratio=0.2)
    rng = np.random.default_rng(2)
    counts = {t.name: rng.integers(0, 50, t.vocab) for t in tables}
    st_ref = ref.init(jax.random.PRNGKey(0), counts=counts)
    st_sh = sc.init(jax.random.PRNGKey(0), counts=counts)
    # identical slow tiers (1-shard layout is the identity permutation)
    for sname in ref.cached_slabs:
        np.testing.assert_array_equal(
            np.asarray(st_ref.slabs[sname].full["weight"]),
            np.asarray(flat_store(st_sh.slabs[sname].full)["weight"]),
        )
    for i in range(6):
        fb = rand_fb(tables, 16, seed=200 + i)
        st_ref, a_ref = ref.prepare(st_ref, fb)
        st_sh, a_sh = sc.prepare(st_sh, fb)
        r_ref = ref.gather(ref.weights(st_ref), a_ref, fb)
        r_sh = sc.gather(sc.weights(st_sh), a_sh, fb)
        for f in fb.features:
            np.testing.assert_array_equal(np.asarray(a_ref[f]), np.asarray(a_sh[f]))
            np.testing.assert_array_equal(np.asarray(r_ref[f]), np.asarray(r_sh[f]))


@pytest.mark.parametrize("num_shards", [1, 4])
def test_sharded_dlrm_loss_trajectory_matches_single_device(num_shards):
    """The acceptance property: the sharded collection reproduces the
    single-device loss trajectory (fp32: bit-exact — the cache is pure data
    movement per shard and gathers read identical values)."""
    from repro.data import synth
    from repro.models.dlrm import DLRM, DLRMConfig

    base = dict(vocab_sizes=(2048, 256, 64), embed_dim=8, batch_size=16,
                cache_ratio=0.15, lr=0.2, bottom_mlp=(16, 8), top_mlp=(16,))
    spec = synth.ZipfSparseSpec(vocab_sizes=base["vocab_sizes"], n_dense=13)

    def make(s):
        return {k: jnp.asarray(v) for k, v in synth.sparse_batch(spec, 16, 0, s).items()}

    def losses(shards):
        model = DLRM(DLRMConfig(**base, model_shards=shards))
        state = model.init(jax.random.PRNGKey(0))
        step = jax.jit(model.train_step)
        out = []
        for i in range(8):
            state, m = step(state, make(i))
            out.append(float(m["loss"]))
        return out

    assert losses(0) == losses(num_shards)


def test_sharded_pipelined_trainer_bit_identical_to_serial():
    """Pipelined groups plan per shard: the group guard and future addresses
    ride the sharded plan unchanged, so depth-k grouping stays loss-bit-
    identical on a sharded collection too."""
    from repro.data import synth
    from repro.models.dlrm import DLRM, DLRMConfig
    from repro.train.trainer import PipelinedTrainer, Trainer, TrainerConfig

    cfg = DLRMConfig(vocab_sizes=(1024, 128), embed_dim=8, batch_size=16,
                     cache_ratio=0.25, lr=0.1, bottom_mlp=(16, 8), top_mlp=(16,),
                     model_shards=2)
    spec = synth.ZipfSparseSpec(vocab_sizes=cfg.vocab_sizes, n_dense=13)

    def make_batch(step):
        return {k: jnp.asarray(v) for k, v in synth.sparse_batch(spec, 16, 0, step).items()}

    model = DLRM(cfg)
    serial = Trainer(TrainerConfig(max_steps=6),
                     init_fn=lambda: model.init(jax.random.PRNGKey(0)),
                     step_fn=jax.jit(model.train_step),
                     make_batch=make_batch, flush_fn=model.flush)
    serial.run()

    model2 = DLRM(cfg)
    piped = PipelinedTrainer(
        TrainerConfig(max_steps=6, pipeline_depth=2),
        init_fn=lambda: model2.init(jax.random.PRNGKey(0)),
        plan_fn=jax.jit(model2.plan_step),
        compute_fn=jax.jit(model2.compute_step),
        apply_fn=jax.jit(model2.apply_step),
        make_batch=make_batch, flush_fn=model2.flush)
    piped.run()
    assert [h["loss"] for h in serial.history] == [h["loss"] for h in piped.history]
    # exchange telemetry recorded as exact ints
    assert isinstance(serial.history[-1]["exchange_bytes"], int)


# --------------------------------------------------------------------------
# sharded state x host_precision (satellite)
# --------------------------------------------------------------------------


def test_sharded_int8_sideband_shards_with_payload():
    tables = small_tables()
    sc = ShardedEmbeddingCollection.create(tables, num_shards=4,
                                           cache_ratio=0.2, host_precision="int8")
    state = sc.init(jax.random.PRNGKey(0))
    for sname, spec in sc.cached_slabs.items():
        store = state.slabs[sname].full
        vs = sc.rows_per_shard(spec)
        assert store.data["weight"].shape == (4, vs, spec.dim)
        assert store.data["weight"].dtype == jnp.int8
        # per-row (scale, zp) sideband travels shard-for-shard with its rows
        assert store.sideband["weight"].shape == (4, vs, 2)
        # the sharded store is a permutation of the unsharded encoding: each
        # rank's (payload, sideband) pair is the row-wise encode of its row
        flat = flat_store(store)
        a = sc.assignments[sname]
        dest = a.owner.astype(np.int64) * vs + a.local.astype(np.int64)
        dec = np.asarray(flat.decode_rows(jnp.asarray(dest, jnp.int32))["weight"])
        assert np.isfinite(dec).all() and dec.shape == (spec.vocab, spec.dim)


@pytest.mark.parametrize("codec", ["fp16", "int8"])
def test_sharded_quantized_evict_reload_payload_stable(codec):
    """Evict/reload through per-shard transmitters keeps the store invariant
    (same contract as the unsharded store, tested in test_store): untouched
    rows keep a bit-stable encoded payload across arbitrary eviction cycles,
    and lookups track the slow tier to codec noise."""
    tables = [col.TableConfig("t", vocab=256, dim=8, ids_per_step=8, cache_ratio=0.05)]
    sc = ShardedEmbeddingCollection.create(tables, num_shards=2, cache_ratio=0.05,
                                           host_precision=codec)
    state = sc.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)

    def churn(state, n):
        for _ in range(n):  # tiny cache -> constant eviction traffic
            fb = col.FeatureBatch(ids={"t": jnp.asarray(
                rng.integers(0, 256, 8).astype(np.int32))})
            state, addr = sc.prepare(state, fb)
            rows = sc.gather(sc.weights(state), addr, fb)
            ref = sc.dense_reference(sc.flush(state), fb)
            np.testing.assert_allclose(np.asarray(rows["t"]), np.asarray(ref["t"]),
                                       atol=1e-6)
        return state

    state = churn(state, 6)
    state = sc.flush(state)
    store0 = state.slabs[col.SHARED_ARENA].full
    pay0 = np.asarray(store0.data["weight"])
    side0 = np.asarray(store0.sideband["weight"]) if store0.sideband else None
    state = churn(state, 6)  # more evict/reload cycles, no row updates
    state = sc.flush(state)
    store1 = state.slabs[col.SHARED_ARENA].full
    np.testing.assert_array_equal(pay0, np.asarray(store1.data["weight"]))
    if side0 is not None:
        # payload is bit-stable; the sideband recompute drifts by float ulps
        # only (the same contract test_store pins for the unsharded path)
        np.testing.assert_allclose(side0, np.asarray(store1.sideband["weight"]),
                                   atol=1e-6)
    m = sc.metrics(state)
    assert int(m["cache_evictions"]) > 0  # the round trips actually happened


def test_sharded_one_shard_int8_bit_identical_to_unsharded():
    """S=1 with a lossy codec still bit-matches the unsharded collection:
    row-wise quantization is layout-invariant and the 1-shard permutation is
    the identity."""
    from repro.data import synth
    from repro.models.dlrm import DLRM, DLRMConfig

    base = dict(vocab_sizes=(1024, 128), embed_dim=8, batch_size=16,
                cache_ratio=0.1, lr=0.2, bottom_mlp=(16, 8), top_mlp=(16,),
                host_precision="int8")
    spec = synth.ZipfSparseSpec(vocab_sizes=base["vocab_sizes"], n_dense=13)

    def losses(shards):
        model = DLRM(DLRMConfig(**base, model_shards=shards))
        state = model.init(jax.random.PRNGKey(0))
        step = jax.jit(model.train_step)
        out = []
        for i in range(6):
            batch = {k: jnp.asarray(v)
                     for k, v in synth.sparse_batch(spec, 16, 0, i).items()}
            state, m = step(state, batch)
            out.append(float(m["loss"]))
        return out

    assert losses(0) == losses(1)


def test_sharded_int8_losses_allclose_to_unsharded():
    """Sharded lossy codecs agree with the single-device run to codec noise
    (eviction schedules differ per shard, so quantize round trips differ)."""
    from repro.data import synth
    from repro.models.dlrm import DLRM, DLRMConfig

    base = dict(vocab_sizes=(1024, 128), embed_dim=8, batch_size=16,
                cache_ratio=0.1, lr=0.2, bottom_mlp=(16, 8), top_mlp=(16,),
                host_precision="int8")
    spec = synth.ZipfSparseSpec(vocab_sizes=base["vocab_sizes"], n_dense=13)

    def losses(shards):
        model = DLRM(DLRMConfig(**base, model_shards=shards))
        state = model.init(jax.random.PRNGKey(0))
        step = jax.jit(model.train_step)
        out = []
        for i in range(8):
            batch = {k: jnp.asarray(v)
                     for k, v in synth.sparse_batch(spec, 16, 0, i).items()}
            state, m = step(state, batch)
            out.append(float(m["loss"]))
        return out

    np.testing.assert_allclose(losses(0), losses(4), atol=5e-3)


def test_sharded_int8_checkpoint_roundtrip_exact(tmp_path):
    """The encoded sharded store (payload + sideband, stacked [S, ...])
    persists and restores exactly through the checkpointer."""
    from repro.train import checkpoint as ckpt

    tables = small_tables()
    sc = ShardedEmbeddingCollection.create(tables, num_shards=4,
                                           cache_ratio=0.2, host_precision="int8")
    state = sc.init(jax.random.PRNGKey(0))
    for i in range(4):
        fb = rand_fb(tables, 16, seed=300 + i)
        state, _ = sc.prepare(state, fb)
    state = sc.flush(state)
    ckpt.save(str(tmp_path), 7, {"emb": state})
    like = jax.eval_shape(
        lambda: {"emb": sc.init(jax.random.PRNGKey(0), warm=False)}
    )
    restored, step = ckpt.restore(str(tmp_path), like)
    assert step == 7
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        {"emb": state}, restored,
    )


# --------------------------------------------------------------------------
# structure + telemetry
# --------------------------------------------------------------------------


def test_sharded_shard_specs_structure_matches_state():
    tables = small_tables()
    for codec in ("fp32", "int8"):
        sc = ShardedEmbeddingCollection.create(tables, num_shards=4,
                                               cache_ratio=0.2, host_precision=codec)
        state = sc.init(jax.random.PRNGKey(0))
        specs = sc.shard_specs()
        assert jax.tree_util.tree_structure(state) == jax.tree_util.tree_structure(specs)


def test_exchange_telemetry_counts_valid_lanes():
    tables = [col.TableConfig("t", vocab=128, dim=8, ids_per_step=8, cache_ratio=0.3)]
    sc = ShardedEmbeddingCollection.create(tables, num_shards=2, cache_ratio=0.3)
    state = sc.init(jax.random.PRNGKey(0))
    fb = col.FeatureBatch(ids={"t": jnp.asarray([1, 2, 3, -1, -1, 5, 6, -1], jnp.int32)})
    state, _ = sc.prepare(state, fb)
    state, _ = sc.prepare(state, fb)
    m = sc.metrics(state)
    lanes = int(m["exchange_routed_lanes"][col.SHARED_ARENA])
    assert lanes == 2 * 5  # 5 valid lanes per step, cumulative
    per_lane = int(m["exchange_lane_bytes"][col.SHARED_ARENA])
    assert per_lane == 4 + 8 * 4  # id out + one dim-8 fp32 row back
    assert float(m["exchange_bytes"]) == lanes * per_lane
    from repro.obs.hub import ExactCounter
    assert ExactCounter().observe(m["exchange_routed_lanes"],
                                  unit=m["exchange_lane_bytes"]) == lanes * per_lane


def test_device_budget_mode_composes_with_sharding():
    """A budget plan (DEVICE + CACHED mix) shards only the cached slabs;
    DEVICE tables replicate and the whole thing stays exact."""
    tables = [
        col.TableConfig("big", vocab=4096, dim=8, ids_per_step=16, cache_ratio=0.1),
        col.TableConfig("hot", vocab=64, dim=8, ids_per_step=16),
    ]
    sc = ShardedEmbeddingCollection.create(tables, num_shards=2, budget_bytes=80_000)
    assert sc.device_slabs and sc.cached_slabs
    state = sc.init(jax.random.PRNGKey(0))
    from repro.core.sharded import ShardedSlab
    assert isinstance(state.slabs["big"], ShardedSlab)
    assert state.slabs["hot"].weight.shape == (64, 8)  # replicated DeviceSlab
    fb = rand_fb(tables, 16, seed=4)
    state, _, rows = sc.lookup(state, fb)
    ref = sc.dense_reference(sc.flush(state), fb)
    for f in fb.features:
        np.testing.assert_array_equal(np.asarray(rows[f]), np.asarray(ref[f]))


# --------------------------------------------------------------------------
# the real mesh: forced 4 host devices in a subprocess
# --------------------------------------------------------------------------


def run_sub(code: str, n_dev: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_sharded_collection_on_4_device_mesh_matches_reference():
    """Acceptance: a 4-shard collection jitted over a real (data=1, model=4)
    host mesh — state physically split one cache arena per device — produces
    the single-device reference loss trajectory."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        import repro.dist.partitioning as dist
        from repro.launch.mesh import make_hybrid_mesh
        from repro.data import synth
        from repro.models.dlrm import DLRM, DLRMConfig

        base = dict(vocab_sizes=(2048, 256), embed_dim=8, batch_size=16,
                    cache_ratio=0.15, lr=0.2, bottom_mlp=(16, 8), top_mlp=(16,))
        spec = synth.ZipfSparseSpec(vocab_sizes=base["vocab_sizes"], n_dense=13)
        make = lambda s: {k: jnp.asarray(v)
                          for k, v in synth.sparse_batch(spec, 16, 0, s).items()}

        ref = DLRM(DLRMConfig(**base))
        rs = ref.init(jax.random.PRNGKey(0))
        rstep = jax.jit(ref.train_step)
        ref_losses = []
        for i in range(6):
            rs, m = rstep(rs, make(i))
            ref_losses.append(float(m["loss"]))

        model = DLRM(DLRMConfig(**base, model_shards=4))
        state = model.init(jax.random.PRNGKey(0))
        mesh = make_hybrid_mesh(4)
        sh = lambda t: jax.tree_util.tree_map(
            lambda p: NamedSharding(mesh, p), t, is_leaf=lambda x: isinstance(x, P))
        sspecs = {"params": jax.tree_util.tree_map(lambda _: P(), state["params"]),
                  "opt": jax.tree_util.tree_map(lambda _: P(), state["opt"]),
                  "emb": model.collection.shard_specs(), "step": P()}
        bspecs = {"dense": P("data", None), "sparse": P("data", None),
                  "label": P("data")}
        state = jax.device_put(state, sh(sspecs))
        with dist.axis_rules(mesh, dist.hybrid_rules()):
            step = jax.jit(model.train_step, in_shardings=(sh(sspecs), sh(bspecs)))
            losses = []
            for i in range(6):
                state, m = step(state, make(i))
                losses.append(float(m["loss"]))
        np.testing.assert_allclose(losses, ref_losses, rtol=0, atol=0)
        w = state["emb"].slabs["__shared__"].cache.cached_rows["weight"]
        assert len(w.sharding.device_set) == 4, w.sharding
        assert float(m["exchange_bytes"]) > 0
        print("SHARDED_MESH_EXACT")
    """)
    assert "SHARDED_MESH_EXACT" in out


@pytest.mark.parametrize("dim", [128, 18, 7])
def test_uniform_rows_match_the_whole_table_draw(dim):
    """A shard's rows drawn alone by their threefry counters equal the same
    rows of the whole-table ``jax.random.uniform`` draw, bit for bit."""
    from repro.core.sharded import uniform_rows

    key = jax.random.split(jax.random.PRNGKey(3), 2)[1]
    s = 1.0 / np.sqrt(dim)
    full = jax.random.uniform(key, (999, dim), jnp.float32, -s, s)
    rows = jnp.asarray(np.random.default_rng(dim).permutation(999)[:240].reshape(4, 60),
                       jnp.int32)
    assert jnp.array_equal(uniform_rows(key, rows, dim, -s, s), full[rows])


@pytest.mark.parametrize("with_counts,top_k", [(False, 0), (True, 0), (False, 12),
                                                (True, 12)])
def test_sharded_init_draws_rows_like_the_whole_table(monkeypatch, with_counts, top_k):
    """Init draws each shard's rows (and the replicated head) by counter; the
    state equals the whole-table draw scattered to the same homes, bit for
    bit, with frequency counts, a replicated top-K, both or neither."""
    import repro.core.sharded as sharded

    tables = small_tables()
    coll = ShardedEmbeddingCollection.create(tables, num_shards=3, cache_ratio=0.2,
                                             replicate_top_k=top_k)
    rng = np.random.default_rng(5)
    counts = ({t.name: rng.integers(0, 50, t.vocab) for t in tables}
              if with_counts else None)
    got = coll.init(jax.random.PRNGKey(4), counts=counts)
    for sname, slab in got.slabs.items():
        assign = coll.assignments[sname]
        np.testing.assert_array_equal(np.asarray(slab.rank_owner), assign.owner)
        np.testing.assert_array_equal(np.asarray(slab.rank_local), assign.local)
    monkeypatch.setattr(sharded, "_draws_by_counter", lambda dtype: False)
    want = coll.init(jax.random.PRNGKey(4), counts=counts)
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("dim", [128, 18, 65535])
def test_row_counters_are_the_64_bit_flat_index(dim):
    from repro.core.sharded import row_counters

    r = np.array([0, 1, 2**16 - 1, 2**16, 33_762_576, 2**31 - 1], np.int64)
    hi, lo = row_counters(jnp.asarray(r, jnp.int32), dim)
    n = r[:, None].astype(np.uint64) * np.uint64(dim) + np.arange(dim, dtype=np.uint64)
    assert np.array_equal(np.asarray(hi, np.uint64), n >> np.uint64(32))
    assert np.array_equal(np.asarray(lo, np.uint64), n & np.uint64(0xFFFFFFFF))
