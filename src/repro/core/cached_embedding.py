"""CachedEmbedding — the paper's one-big-table design as a thin adapter.

All per-field tables are concatenated into one big frequency-ordered table
(paper §5.1) and served through the two-tier software cache — i.e. exactly
the all-GROUPED special case of ``repro.core.collection``: one shared cache
arena over every table.  Since the collection refactor this module is a thin
single-arena adapter over the ``collection.cached_slab_*`` ops (one slab,
raw-global ids); it stays as the stable single-table API and the oracle for
the bit-exactness property tests.  New code should use
``collection.EmbeddingCollection``, which adds per-table placement plans.

The module is functional: a ``CachedEmbeddingState`` pytree is threaded
through the train step.

Training protocol (synchronous updates, paper §2.2.3):

    state, slots = prepare_ids(cfg, state, raw_ids)        # non-diff bookkeeping
    emb = gather(state.cache.cached_rows["weight"], slots) # diff wrt cached weight
    ... loss/backprop produces d(cached_weight) ...
    state = apply_row_grads(cfg, state, grad_cached, lr)   # update *cached* copy

Rows are authoritative while resident; eviction (inside ``prepare_ids``) and
``flush_state`` (checkpoint barrier) write them back to the full table.  The
cache is exact — a pure data-movement layer — so training curves match the
uncached baseline bit-for-bit up to float reordering (tested property).

Sharding (paper §4.4 hybrid parallel): column-wise 1-D tensor parallel — the
embedding dim of both tiers is sharded over the ``model`` mesh axis, index
arrays are replicated (every data rank derives identical bookkeeping), and the
lookup output is resharded batch-wise, which XLA SPMD realizes as the paper's
all-to-all.  ``shard_specs`` returns the PartitionSpec pytree.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cache as cache_lib
from repro.core import collection as coll_lib
from repro.core import freq as freq_lib
from repro.core.policies import Policy
from repro.store import HostStore, get_codec

__all__ = [
    "CachedEmbeddingConfig",
    "CachedEmbeddingState",
    "init_state",
    "prepare_ids",
    "gather_slots",
    "embed_onehot",
    "embed_bag",
    "apply_row_grads",
    "flush_state",
    "shard_specs",
    "device_bytes",
]


@dataclasses.dataclass(frozen=True)
class CachedEmbeddingConfig:
    vocab_sizes: Tuple[int, ...]  # per-field vocab sizes (concatenated)
    dim: int
    ids_per_step: int  # static flattened id count per prepare call
    cache_ratio: float = 0.015  # paper default 1.5 %
    buffer_rows: int = 65536
    policy: Policy = Policy.FREQ_LFU
    writeback: bool = True
    dtype: Any = jnp.float32
    rowwise_adagrad: bool = False  # carry per-row accumulator through the cache
    max_unique_per_step: int = 0  # 0 = worst case; see CacheConfig
    protect_via_inverse: bool = True  # see CacheConfig (paper isin = False)
    host_precision: str = "fp32"  # host-tier codec: fp32 (bit-exact) | fp16 | int8
    freq_half_life: int = 1024  # online frequency tracker decay (CacheConfig)
    use_pallas_plan: bool = False  # bounded-top-K fused planning (CacheConfig)
    chunk_rows: int = 0  # chunk-granularity host staging (CacheConfig)

    @property
    def vocab(self) -> int:
        return int(sum(self.vocab_sizes))

    @property
    def unique_size(self) -> int:
        k = min(self.ids_per_step, self.vocab)
        if self.max_unique_per_step:
            k = min(k, self.max_unique_per_step)
        return k

    @property
    def capacity(self) -> int:
        cap = max(int(self.cache_ratio * self.vocab), self.unique_size)
        return min(cap, self.vocab)

    def cache_config(self) -> cache_lib.CacheConfig:
        return cache_lib.CacheConfig(
            vocab=self.vocab,
            capacity=self.capacity,
            ids_per_step=self.ids_per_step,
            buffer_rows=self.buffer_rows,
            policy=self.policy,
            writeback=self.writeback,
            max_unique_per_step=self.max_unique_per_step,
            protect_via_inverse=self.protect_via_inverse,
            freq_half_life=self.freq_half_life,
            use_pallas_plan=self.use_pallas_plan,
            chunk_rows=self.chunk_rows,
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CachedEmbeddingState:
    # slow tier: a repro.store.HostStore of {"weight": [vocab, dim],
    # ("accum": [vocab])?} — fp32 codec = raw arrays (pre-store behavior)
    full: Any
    cache: cache_lib.CacheState
    idx_map: jnp.ndarray  # int32 [vocab] raw id -> freq-ranked row
    offsets: jnp.ndarray  # int32 [fields] per-field base offset

    def slab(self) -> coll_lib.CachedSlab:
        """View this state as the collection's single cached-arena slab."""
        return coll_lib.CachedSlab(full=self.full, cache=self.cache, idx_map=self.idx_map)

    def with_slab(self, slab: coll_lib.CachedSlab) -> "CachedEmbeddingState":
        return dataclasses.replace(
            self, full=slab.full, cache=slab.cache, idx_map=slab.idx_map
        )


def init_state(
    rng: jax.Array,
    cfg: CachedEmbeddingConfig,
    counts: Optional[np.ndarray] = None,
    warm: bool = True,
) -> CachedEmbeddingState:
    """Build the static module (freq-ordered full table + idx_map) and an
    empty (optionally warmed-up) cache."""
    vocab, dim = cfg.vocab, cfg.dim
    scale = 1.0 / np.sqrt(dim)
    weight = jax.random.uniform(rng, (vocab, dim), cfg.dtype, -scale, scale)
    if counts is not None:
        stats = freq_lib.build_freq_stats(counts)
        idx_map = jnp.asarray(stats.idx_map)
        # weight rows are freshly random; ordering is only logical, no permute needed,
        # but idx_map must still be a real permutation so lookups land right.
    else:
        idx_map = jnp.arange(vocab, dtype=jnp.int32)
    full = {"weight": weight}
    row_example = {"weight": jax.ShapeDtypeStruct((dim,), cfg.dtype)}
    if cfg.rowwise_adagrad:
        full["accum"] = jnp.zeros((vocab,), jnp.float32)
        row_example["accum"] = jax.ShapeDtypeStruct((), jnp.float32)
    row_example = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), row_example, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)
    )
    state = cache_lib.init_cache(cfg.cache_config(), row_example)
    offsets = jnp.asarray(freq_lib.concat_table_offsets(cfg.vocab_sizes).astype(np.int32))
    store = HostStore.create(full, codec=cfg.host_precision)
    st = CachedEmbeddingState(full=store, cache=state, idx_map=idx_map, offsets=offsets)
    if warm:
        st = st.with_slab(coll_lib.cached_slab_warmup(cfg.cache_config(), st.slab()))
    return st


def globalize(state: CachedEmbeddingState, field_ids: jnp.ndarray) -> jnp.ndarray:
    """[.., fields] local ids -> global concatenated-table ids."""
    return (field_ids.astype(jnp.int32) + state.offsets).astype(jnp.int32)


def prepare_ids(
    cfg: CachedEmbeddingConfig, state: CachedEmbeddingState, raw_ids: jnp.ndarray
) -> Tuple[CachedEmbeddingState, jnp.ndarray]:
    """Make all rows for ``raw_ids`` resident; return per-id cache slots.

    ``raw_ids``: int32 [ids_per_step] global ids, -1 = padding.  Non-
    differentiable bookkeeping (Algorithm 1) — call outside the grad closure.
    """
    slab, slots = coll_lib.cached_slab_prepare(cfg.cache_config(), state.slab(), raw_ids)
    return state.with_slab(slab), slots


def gather_slots(state: CachedEmbeddingState, slots: jnp.ndarray) -> jnp.ndarray:
    """Differentiable gather from the cached weight (padding -> zero rows)."""
    return coll_lib.cached_slab_gather(state.slab(), slots)


def embed_onehot(
    cfg: CachedEmbeddingConfig, state: CachedEmbeddingState, field_ids: jnp.ndarray
) -> Tuple[CachedEmbeddingState, jnp.ndarray, jnp.ndarray]:
    """One id per field (Criteo-style): [batch, fields] -> [batch, fields, dim].

    Returns (state', slots, embeddings); keep ``slots`` to scatter gradients.
    """
    b, f = field_ids.shape
    gids = globalize(state, field_ids).reshape(-1)
    state, slots = prepare_ids(cfg, state, gids)
    emb = gather_slots(state, slots).reshape(b, f, cfg.dim)
    return state, slots, emb


def embed_bag(
    cfg: CachedEmbeddingConfig,
    state: CachedEmbeddingState,
    flat_ids: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    combiner: str = "sum",
) -> Tuple[CachedEmbeddingState, jnp.ndarray, jnp.ndarray]:
    """EmbeddingBag over ragged multi-hot bags (padding ids < 0 contribute 0).

    JAX has no native EmbeddingBag; this is gather + ``jax.ops.segment_sum``
    through the cache tier.
    """
    state, slots = prepare_ids(cfg, state, flat_ids)
    rows = gather_slots(state, slots)
    pooled = jax.ops.segment_sum(rows, segment_ids, num_segments=num_segments)
    if combiner == "mean":
        cnt = jax.ops.segment_sum((flat_ids >= 0).astype(rows.dtype), segment_ids, num_segments)
        pooled = pooled / jnp.maximum(cnt, 1.0)[:, None]
    return state, slots, pooled


def apply_row_grads(
    cfg: CachedEmbeddingConfig,
    state: CachedEmbeddingState,
    grad_cached_weight: jnp.ndarray,
    lr: float | jnp.ndarray,
) -> CachedEmbeddingState:
    """Synchronous update of the *cached* rows (SGD or row-wise Adagrad).

    The full-table copy is updated lazily at eviction/flush — the paper's
    synchronous scheme: resident rows are authoritative.
    """
    cached = dict(state.cache.cached_rows)
    if cfg.rowwise_adagrad:
        g2 = jnp.mean(grad_cached_weight.astype(jnp.float32) ** 2, axis=-1)
        accum = cached["accum"] + g2
        scale = lr / (jnp.sqrt(accum) + 1e-10)
        cached["weight"] = cached["weight"] - (scale[:, None] * grad_cached_weight).astype(
            cached["weight"].dtype
        )
        cached["accum"] = accum
    else:
        cached["weight"] = cached["weight"] - (lr * grad_cached_weight).astype(
            cached["weight"].dtype
        )
    new_cache = dataclasses.replace(state.cache, cached_rows=cached)
    return dataclasses.replace(state, cache=new_cache)


def flush_state(cfg: CachedEmbeddingConfig, state: CachedEmbeddingState) -> CachedEmbeddingState:
    """Checkpoint barrier: write all resident rows back to the full table."""
    return state.with_slab(coll_lib.cached_slab_flush(cfg.cache_config(), state.slab()))


def dense_reference_lookup(state: CachedEmbeddingState, field_ids: jnp.ndarray) -> jnp.ndarray:
    """Oracle: bypass the cache, read the flushed full table (tests only;
    decoded when the slow tier is quantized)."""
    gids = globalize(state, field_ids)
    rows = state.idx_map[gids]
    return coll_lib._read_full_rows(state.full, rows)


def shard_specs(
    cfg: CachedEmbeddingConfig, mode: str = "column", model_axis: str = "model"
):
    """PartitionSpec pytree for the cache state.

    mode:
      * "column"     — the paper's column-wise 1-D TP: embedding dim of both
        tiers sharded over ``model_axis`` (requires dim % tp == 0).
      * "row"        — full (slow-tier) table row-sharded over ``model_axis``;
        cached tier replicated.  Used when dim is too small to split (DIN/FM,
        dims 10-18 — DESIGN.md §Arch-applicability).
      * "replicated" — everything replicated (tests / tiny tables).
    """
    from jax.sharding import PartitionSpec as P

    if mode == "column":
        full_w = cached_w = P(None, model_axis)
        side_w = P(None, None)  # per-row sideband cannot split the dim
    elif mode == "row":
        full_w, cached_w = P(model_axis, None), P(None, None)
        side_w = P(model_axis, None)
    else:
        full_w = cached_w = side_w = P(None, None)
    full_like = {"weight": jax.ShapeDtypeStruct((cfg.vocab, cfg.dim), cfg.dtype)}
    full = {"weight": full_w}
    cached = {"weight": cached_w}
    if cfg.rowwise_adagrad:
        full_like["accum"] = jax.ShapeDtypeStruct((cfg.vocab,), jnp.float32)
        full["accum"] = P(model_axis) if mode == "row" else P(None)
        cached["accum"] = P(None)
    return CachedEmbeddingState(
        full=HostStore.spec_like(full_like, full, side_w, codec=cfg.host_precision),
        cache=cache_lib.CacheState(
            cached_rows=cached,
            slot_to_row=P(None),
            row_to_slot=P(None),
            last_used=P(None),
            use_count=P(None),
            step=P(),
            hits=P(),
            misses=P(),
            uniq_rows=P(),
            move_rounds=P(),
            evictions=P(),
            uniq_overflows=P(),
            tier_promotions=P(),
            tier_demotions=P(),
            tracker=freq_lib.tracker_spec(P),
        ),
        idx_map=P(None),
        offsets=P(None),
    )


def device_bytes(cfg: CachedEmbeddingConfig) -> dict:
    """Fast-tier vs slow-tier footprint (paper Figs. 7/8 memory accounting;
    the slow tier is charged at its encoded, host-precision size)."""
    itemsize = jnp.dtype(cfg.dtype).itemsize
    fast = cfg.capacity * cfg.dim * itemsize  # cached weight
    fast += cfg.capacity * 4 * 3  # slot_to_row, last_used, use_count
    # row_to_slot + idx_map + frequency-tracker score/last_touch (on device)
    fast += cfg.vocab * 4 * 4
    slow = cfg.vocab * get_codec(cfg.host_precision).row_bytes((cfg.dim,), cfg.dtype)
    if cfg.rowwise_adagrad:
        fast += cfg.capacity * 4
        slow += cfg.vocab * 4  # accumulators stay raw fp32 (per-row scalars)
    return {"fast_tier_bytes": fast, "slow_tier_bytes": slow}
