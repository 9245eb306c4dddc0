"""The slow tier in host memory: where the collection puts it, that a step
through the host leg trains exactly like one with the slow tier on the
device, and what each path the benchmark does not run does with it.

On the CPU the memory kinds are not kept (a jitted program returns device
arrays), so these tests check the decision, the program the host leg
builds and its values; the chip checks where the arrays live."""
import dataclasses
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import dlrm_criteo
from repro.core import collection as col
from repro.core import sharded as sharded_lib
from repro.data import synth
from repro.models.dlrm import DLRM, DLRMConfig
from repro.store import HostStore, get_codec
from repro.store import host_store
from repro.store.host_store import PINNED_HOST, HostPieces, draw_uniform
from repro.train.trainer import Trainer, TrainerConfig

BASE = dict(vocab_sizes=(3000, 500, 70), embed_dim=128, batch_size=32, cache_ratio=0.05,
            lr=0.2, bottom_mlp=(16, 128), top_mlp=(16,))
# a device of 1 MB: the 1.8 MB slow tier does not fit it
SMALL = 1 << 20
V5E_BYTES = 16 * 10**9  # a 16 GB chip
ONE_OF_FOUR = tuple(-(-v // 4) for v in dlrm_criteo.CONFIG.vocab_sizes)


def on_device_of(limit, build, *args, **kw):
    """``build(*args, **kw)`` where the device reports ``limit`` bytes."""
    with mock.patch.object(col, "device_bytes_limit", lambda: limit), \
            mock.patch.object(sharded_lib, "device_bytes_limit", lambda: limit):
        return build(*args, **kw)


def _pair(**kw):
    """The same model twice: its slow tier in host memory (the device is too
    small for it) and, as the model builds it on the CPU, on the device."""
    cfg = DLRMConfig(**BASE, **kw)
    return on_device_of(SMALL, DLRM, cfg), DLRM(cfg)


def _batches(n, seed=0):
    spec = synth.ZipfSparseSpec(vocab_sizes=BASE["vocab_sizes"], n_dense=13)
    return [{k: jnp.asarray(v) for k, v in
             synth.sparse_batch(spec, BASE["batch_size"], seed, s).items()} for s in range(n)]


def _read_rows(model, state, ids):
    return {t: np.asarray(jax.jit(lambda e, i, t=t: model.collection.full_lookup(e, t, i))(
        state["emb"], jnp.asarray(ids[t], jnp.int32))) for t in ids}


def _train(model, batches):
    state = jax.jit(model.init)(jax.random.PRNGKey(3))
    step = jax.jit(model.train_step, donate_argnums=(0,))
    losses = []
    for b in batches:
        state, m = step(state, b)
        losses.append(float(m["loss"]))
    return jax.jit(model.flush, donate_argnums=(0,))(state), losses


@pytest.mark.parametrize("pieces", [1, 3], ids=["one_piece", "pieces"])
@pytest.mark.parametrize("buffer_rows", [65536, 8], ids=["one_round", "rounds"])
def test_host_tier_trains_like_the_device_tier(buffer_rows, pieces, monkeypatch):
    # pieces of a third of the 1.8 MB slow tier: its rows split three ways
    monkeypatch.setattr(host_store, "PIECE_BYTES", 3570 * 512 // pieces)
    host, dev = _pair(buffer_rows=buffer_rows)
    assert host.collection.slow_tier_memory == PINNED_HOST
    assert dev.collection.slow_tier_memory == "device"
    assert host.collection.plan.placements == dev.collection.plan.placements
    batches = _batches(5)
    s_host, l_host = _train(host, batches)
    s_dev, l_dev = _train(dev, batches)
    assert l_host == l_dev
    full = s_host["emb"].slabs[col.SHARED_ARENA].full
    assert isinstance(full, HostStore) and full.memory_kind == PINNED_HOST
    assert len(full.data["weight"].pieces) == pieces
    touched = {f"f{i}": np.concatenate([[-1], np.unique(np.concatenate(
        [np.asarray(b["sparse"][:, i]) for b in batches]))]) for i in range(3)}
    got, want = _read_rows(host, s_host, touched), _read_rows(dev, s_dev, touched)
    for t in touched:
        np.testing.assert_array_equal(got[t], want[t])
    assert not got["f0"][0].any()  # a padding lane reads zeros
    # every row of the slow tier, read back
    rows = jnp.arange(-1, 3571, dtype=jnp.int32)
    read = jax.jit(lambda f: f.decode_rows(rows)["weight"])
    np.testing.assert_array_equal(
        np.asarray(read(full)),
        np.asarray(read(s_dev["emb"].slabs[col.SHARED_ARENA].full)))
    # the planner's own mixed layout (DEVICE tables beside a CACHED one)
    cfg = DLRMConfig(**BASE, device_budget_bytes=200_000, buffer_rows=buffer_rows)
    planned = on_device_of(SMALL, DLRM, cfg)
    assert planned.collection.slow_tier_memory == PINNED_HOST
    assert planned.collection.device_slabs and planned.collection.cached_slabs
    assert _train(planned, batches)[1] == _train(DLRM(cfg), batches)[1]


def test_a_budget_bounds_the_fast_tier_only():
    """The planner's device budget picks which tables are cached; where the
    slow tiers live follows the device's own memory alone."""
    planned = DLRM(DLRMConfig(**BASE, device_budget_bytes=200_000))
    assert planned.collection.cached_slabs
    assert planned.collection.slow_tier_memory == "device"


@pytest.mark.parametrize("dim,codec", [(8, "fp32"), (128, "int8"), (64, "fp16")])
def test_narrow_rows_refuse_the_host_tier(dim, codec):
    """A row moved to or from host memory must be 512 B or wider: a table whose
    rows (or int8 sideband rows) are narrower raises, naming the host tier,
    where its slow tier does not fit the device."""
    cfg = DLRMConfig(**dict(BASE, embed_dim=dim, bottom_mlp=(16, dim)), host_precision=codec)
    with pytest.raises(ValueError, match="host tier"):
        on_device_of(10_000, DLRM, cfg)  # a device of 10 KB
    assert DLRM(cfg).collection.slow_tier_memory == "device"


def test_host_leg_moves_rows_under_its_scope():
    host, dev = _pair(buffer_rows=8)
    state = jax.jit(host.init)(jax.random.PRNGKey(0))
    batch = _batches(1)[0]
    text = jax.jit(host.train_step).lower(state, batch).compile().as_text()
    link = [p.split("/") for p in re.findall(r'op_name="([^"]*)"', text)
            if "cache.host_link" in p.split("/")]
    assert link  # the legs are scoped, inside the movement
    assert all("cache.movement" in p[:p.index("cache.host_link")] for p in link)
    # rows cross by DMA inside the step: no host computation, no callback
    # (the memory kinds and the kernels' DMAs: tests/test_tpu_compile.py)
    lowered = jax.jit(host.train_step).lower(state, batch).as_text()
    assert "_xla_compute_type" not in lowered and "callback" not in lowered
    d_state = jax.jit(dev.init)(jax.random.PRNGKey(0))
    assert "cache.host_link" not in jax.jit(dev.train_step).lower(d_state, batch) \
        .compile().as_text()


def test_placement_follows_the_device_memory(monkeypatch):
    """With a 16 GB chip's limit, one chip's quarter of Criteo and each shard
    of the 4-shard layout keep the slow tier in HBM; the whole table on one
    chip (17.3 GB) goes to host memory.  Arithmetic only."""
    monkeypatch.setattr(col, "device_bytes_limit", lambda: V5E_BYTES)
    monkeypatch.setattr(sharded_lib, "device_bytes_limit", lambda: V5E_BYTES)
    quarter = DLRM(dataclasses.replace(dlrm_criteo.CONFIG, vocab_sizes=ONE_OF_FOUR))
    assert quarter.collection.slow_tier_memory == "device"
    assert quarter.collection.device_need() < V5E_BYTES / 2
    s4 = DLRM(dataclasses.replace(dlrm_criteo.CONFIG, model_shards=4))
    assert s4.collection.slow_tier_memory == "device"
    assert s4.collection.device_need() < V5E_BYTES / 2
    whole = DLRM(dlrm_criteo.CONFIG)
    assert whole.collection.slow_tier_memory == PINNED_HOST
    assert whole.collection.device_bytes()["slow_tier_bytes"] == 33_762_577 * 128 * 4
    assert whole.collection.device_need() > V5E_BYTES
    # the backend reports no limit: the slow tier stays on the device
    monkeypatch.setattr(col, "device_bytes_limit", lambda: None)
    assert DLRM(dlrm_criteo.CONFIG).collection.slow_tier_memory == "device"


def test_cpu_reports_no_device_limit():
    assert col.device_bytes_limit() is None


@pytest.mark.parametrize("pieces", [1, 3])
@pytest.mark.parametrize("codec", ["fp32", "int8"])
def test_block_draw_equals_the_whole_table_draw(codec, pieces, monkeypatch):
    vocab, dim, bound = 1001, 16, 0.25
    row = dim * jnp.dtype(get_codec(codec).payload_dtype(jnp.float32)).itemsize
    monkeypatch.setattr(host_store, "PIECE_BYTES", -(-vocab * row // pieces))
    key = jax.random.PRNGKey(11)
    want = jax.jit(lambda k: HostStore.create(
        {"weight": jax.random.uniform(k, (vocab, dim), jnp.float32, -bound, bound)},
        codec=codec))(key)
    got = jax.jit(lambda k: draw_uniform(k, vocab, dim, jnp.float32, -bound, bound, 300,
                                         codec))(key)
    assert got.memory_kind == PINNED_HOST and got.codec == codec
    leaf = got.data["weight"]
    assert isinstance(leaf, HostPieces) and len(leaf.pieces) == pieces
    assert leaf.shape == (vocab, dim) and leaf.rows >= -(-vocab // pieces)
    assert leaf.rows % leaf.per_chunk == 0  # a piece holds whole chunks
    for tree_got, tree_want in ((got.data, want.data), (got.sideband, want.sideband)):
        assert set(tree_got) == set(tree_want)
        for k in tree_want:
            t = tree_got[k]
            rows = np.concatenate([np.asarray(p).reshape((-1,) + t.row_shape)[:t.rows]
                                   for p in t.pieces])
            np.testing.assert_array_equal(rows[:vocab], np.asarray(tree_want[k]))


def test_pieces_stay_under_their_bound():
    """The whole Criteo table (17.3 GB) is held in 33 pieces of 0.52 GB,
    each a whole number of 4 KiB chunks of 8 rows."""
    n, rows = host_store.host_layout(33_762_577, [512])
    assert (n, rows) == (33, 1_023_112)
    assert rows % 8 == 0 and rows * 512 <= host_store.PIECE_BYTES
    assert n * rows >= 33_762_577


def test_refresh_names_the_host_tier():
    host, _ = _pair()
    state = jax.jit(host.init)(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="host tier"):
        host.refresh(state)


def test_sharded_collection_names_the_host_tier():
    tables = [col.TableConfig("big", vocab=4096, dim=8, ids_per_step=16, cache_ratio=0.1),
              col.TableConfig("hot", vocab=64, dim=8, ids_per_step=16)]
    with pytest.raises(ValueError, match="host tier"):
        on_device_of(80_000, sharded_lib.ShardedEmbeddingCollection.create, tables,
                     num_shards=2)


def test_serving_without_write_back_reads_the_host_tier():
    host, dev = _pair()
    states = [jax.jit(m.init)(jax.random.PRNGKey(5)) for m in (host, dev)]
    serve = [jax.jit(m.serve_step) for m in (host, dev)]
    for b in _batches(3, seed=4):
        out = [f(dict(s), b) for f, s in zip(serve, states)]
        np.testing.assert_array_equal(np.asarray(out[0][0]), np.asarray(out[1][0]))
        states = [dict(s, emb=o[1]) for s, o in zip(states, out)]


def test_checkpoint_restores_the_host_tier(tmp_path):
    """Save, then restore into a fresh trainer: each restored leaf goes where
    the fresh state holds it, and training resumes as if never stopped."""
    host, _ = _pair()
    batches = _batches(4)

    def trainer(steps, ckpt):
        cfg = TrainerConfig(max_steps=steps, ckpt_dir=str(ckpt) if ckpt else None,
                            ckpt_every=2, log_every=1 << 30)
        return Trainer(cfg, init_fn=lambda: jax.jit(host.init)(jax.random.PRNGKey(1)),
                       step_fn=jax.jit(host.train_step), make_batch=lambda s: batches[s % len(batches)],
                       flush_fn=jax.jit(host.flush))

    straight = trainer(4, None)
    straight.run()
    first = trainer(2, tmp_path)
    first.run()
    first.checkpointer.wait()
    resumed = trainer(4, tmp_path)
    state, start = resumed._bootstrap()
    assert start == 2
    fresh = jax.jit(host.init)(jax.random.PRNGKey(1))
    assert jax.tree_util.tree_structure(state) == jax.tree_util.tree_structure(fresh)
    for a, b in zip(jax.tree_util.tree_leaves(state), jax.tree_util.tree_leaves(fresh)):
        assert a.sharding == b.sharding
    assert state["emb"].slabs[col.SHARED_ARENA].full.memory_kind == PINNED_HOST
    resumed.run()
    assert [h["loss"] for h in resumed.history] == [h["loss"] for h in straight.history[2:]]
