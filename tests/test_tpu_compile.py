"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Interpret-mode parity (tests/test_cache_ops.py, tests/test_kernels.py) does
not see the TPU's tiling rule or its scoped-VMEM limit; the chip's own
compiler does.  It is installed with libtpu and compiles for a chip that is
described rather than attached, so each case here lowers one kernel with
``interpret=False`` for device 0 of a described ``v5e:2x2`` and compiles it.
Nothing runs.  Widths come from the registry configs.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import din as din_cfg
from repro.configs import dlrm_avazu, dlrm_criteo, fm as fm_cfg
from repro.core.collection import SHARED_ARENA
from repro.kernels.cache_ops import kernel as cache_kernel
from repro.kernels.embedding_bag.kernel import embedding_bag_chunked
from repro.kernels.fm_interaction.kernel import fm_interaction_pallas
from repro.models.dlrm import DLRM


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it is held by another process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _arena(config) -> int:
    """Arena slots of a DLRM registry config's shared cache slab."""
    return DLRM(config).collection.cached_slabs[SHARED_ARENA].capacity


@pytest.mark.parametrize("config", [dlrm_avazu.CONFIG, dlrm_criteo.CONFIG],
                         ids=["avazu", "criteo"])
def test_victim_threshold_compiles(one_chip, config):
    cap = _arena(config)
    kv = config.batch_size * len(config.vocab_sizes)  # unique-buffer floor
    fn = functools.partial(cache_kernel.victim_threshold_pallas, kv=min(kv, cap),
                           interpret=False)
    _compile(fn, one_chip, ((cap,), jnp.uint32))


def test_victim_threshold_avazu_capacity_is_the_unique_floor():
    # 13 fields x batch 65536 = 851,968 lanes outgrow 1.5% of 9.4M rows
    assert _arena(dlrm_avazu.CONFIG) == 851_968


def test_bucketize_compiles(one_chip):
    cfg = dlrm_criteo.CONFIG
    lanes = cfg.batch_size * len(cfg.vocab_sizes)  # Criteo plan width
    fn = functools.partial(cache_kernel.bucketize_pallas, num_shards=4, interpret=False)
    _compile(fn, one_chip, ((lanes,), jnp.int32), ((lanes,), jnp.int32))


@pytest.mark.parametrize("codec,tail_dtype", [("fp16", jnp.float16), ("int8", jnp.int8)])
@pytest.mark.parametrize("dim", [128, 18], ids=["dlrm", "din"])
def test_gather_decode_compiles(one_chip, codec, tail_dtype, dim):
    cap = _arena(dlrm_avazu.CONFIG)
    head = cap // 4  # arena_head_ratio 0.25
    shapes = [((head, dim), jnp.float32), ((cap - head, dim), tail_dtype),
              ((65536,), jnp.int32)]
    if codec == "int8":  # (scale, zero_point) sideband; fp16 has none
        shapes.insert(2, ((cap - head, 2), jnp.float32))

    def fn(head, tail, *rest):
        side, slots = rest if codec == "int8" else (None, rest[0])
        return cache_kernel.gather_decode_pallas(head, tail, side, slots, codec,
                                                 interpret=False)

    _compile(fn, one_chip, *shapes)


def test_embedding_bag_compiles(one_chip):
    cfg = din_cfg.CONFIG
    fn = functools.partial(embedding_bag_chunked, interpret=False)
    vocab = cfg.n_items + cfg.n_cates + cfg.n_users
    _compile(fn, one_chip, ((vocab, cfg.embed_dim), jnp.float32),
             ((cfg.batch_size, cfg.seq_len), jnp.int32))


def test_fm_interaction_compiles(one_chip):
    cfg = fm_cfg.CONFIG
    f, d = len(cfg.vocab_sizes), cfg.embed_dim
    fn = functools.partial(fm_interaction_pallas, interpret=False)
    compiled = _compile(fn, one_chip, ((cfg.batch_size, f, d), jnp.float32))
    assert compiled.memory_analysis() is not None


def test_host_tier_step_keeps_its_table_in_host_memory(topo, monkeypatch):
    """A DLRM step whose slow tier is in host memory, in two pieces moved by
    the host leg's Pallas kernels: the chip's compiler updates the 2 GB
    table in place in host memory and stages none of it in HBM; so do the
    flush and a read of a few rows."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import collection as col
    from repro.models.dlrm import DLRMConfig
    from repro.store import host_store

    monkeypatch.setattr(col, "device_bytes_limit", lambda: 1 << 30)  # a 1 GiB device
    monkeypatch.setattr(host_store, "PIECE_BYTES", 1 << 30)
    monkeypatch.setattr(host_store, "interpret_mode", lambda *a: False)  # the chip's kernels
    cfg = DLRMConfig(vocab_sizes=(4_000_000, 1000), embed_dim=128, batch_size=1024,
                     bottom_mlp=(128,), top_mlp=(64,), buffer_rows=512)
    model = DLRM(cfg)
    assert model.collection.slow_tier_memory == "pinned_host"
    mesh = Mesh(np.array(topo.devices[:1]), ("x",))
    host = NamedSharding(mesh, P(), memory_kind="pinned_host")
    dev = NamedSharding(mesh, P(), memory_kind="device")
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert len(shapes["emb"].slabs[SHARED_ARENA].full.data["weight"].pieces) == 2
    state = jax.tree_util.tree_map_with_path(
        lambda path, x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=host if ".full" in jax.tree_util.keystr(path) else dev),
        shapes)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=dev)
             for k, v in model.input_specs(cfg.batch_size).items()}
    table = 4_001_000 * 128 * 4
    for fn in (model.train_step, model.flush):
        args = (state, batch) if fn == model.train_step else (state,)
        ma = jax.jit(fn, donate_argnums=(0,)).lower(*args).compile().memory_analysis()
        assert ma.host_alias_size_in_bytes >= table
        # the legs' device blocks (160 MiB at least each), no copy of the table
        assert ma.temp_size_in_bytes < table // 4
    ids = jax.ShapeDtypeStruct((64,), jnp.int32, sharding=dev)
    read = jax.jit(lambda e, i: model.collection.full_lookup(e, "f0", i)).lower(
        state["emb"], ids).compile()
    assert read.memory_analysis().temp_size_in_bytes < table // 4
