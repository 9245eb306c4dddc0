"""Hybrid-parallel sharded ``EmbeddingCollection`` over a device mesh.

The paper scales its cache "to multiple GPUs in combination with the widely
used hybrid parallel training approaches": dense/MLP parameters replicate and
train data-parallel over the ``data`` mesh axis, while the cached embedding
slabs — too big to replicate — shard over a ``model`` axis, each shard owning
its own frequency-aware cache arena and its own slice of the host-tier
``HostStore``.  This module is that layer, built on the PR 1-3 stack:

  * ``PlacementPlanner.assign_devices`` (the RecShard-style pass in
    ``core.collection``) maps every frequency-ranked row of a cached slab to
    a shard, balancing expected hot-row traffic from the same ``FreqStats``
    counts that drive ``host_precision="auto"``.
  * ``ShardedSlab`` stacks the per-shard state along a leading ``[S, ...]``
    axis (uniform shapes; short shards pad with never-referenced zero rows).
    Sharding that axis over the mesh's ``model`` axis puts shard ``s``'s
    cache arena, index image and host-store slice on device ``s`` — the
    per-shard cache ops run under ``jax.vmap``, so XLA partitions them
    device-local with no cross-shard traffic.
  * ``plan_prepare`` bucketizes each batch's ids by owning shard (the
    id all-to-all: a ``[S, lanes]`` routed-id image, each row of which lands
    on its shard) and runs one cache plan per shard; ``gather`` reads the
    combined ``owner * capacity + slot`` address space off the stacked fast
    tier (the row all-to-all return path — on a sharded mesh XLA lowers the
    cross-shard gather to the collective).
  * DEVICE-placed tables stay replicated (they are dense-sized by
    definition), training data-parallel like the MLPs.

Exactness is unchanged: the cache remains pure data movement per shard, so a
sharded collection's lookups still bit-match the dense reference, and the
training loss trajectory matches the single-device collection (bit-exact for
fp32, codec-roundtrip-exact for lossy host codecs).  A 1-shard collection is
bit-identical to the unsharded one by construction (tested).

Worst-case sizing: a batch's lanes may all land on one shard, so each
per-shard cache keeps the full lane budget as its unique floor — capacity is
``max(ratio * vocab_s, min(ids_per_step, vocab_s))`` per shard.  Bound it
with ``TableConfig.max_unique_per_step`` exactly as on one device.

Scaling the exchange (the three fronts that keep throughput monotone in S):

  * **Hot-row replication** (``replicate_top_k``): the K hottest ranks live
    in a small :class:`RepArena` replicated on every shard.  Their lookups
    resolve to arena addresses (``S * cap + rank``) and never enter the
    id/row all-to-all; their summed-lane gradients reach the replicated leaf
    through GSPMD's automatic all-reduce (the data-axis sum, plus a
    model-axis ``psum`` whenever the compiler shards the lane dimension), so
    every shard applies the identical SGD update.  ``refresh`` promotes and
    demotes across the replicated boundary exactly like the capacity one.
  * **Exchange compression**: batch ids are deduplicated BEFORE the
    bucketize, so a shard never receives the same id twice per plan (and the
    vmapped per-shard unique sorts shrink to the dedup width); with
    ``exchange_codec`` the row-leg return path travels encoded (fp16/int8 +
    sideband, the PR3 wire codecs) and decodes at the consumer, with a
    straight-through gradient into the fp32 arenas.
  * **Traffic-aware re-balance** (``RefreshConfig.rebalance_threshold``):
    ``refresh`` re-runs ``assign_devices`` on the live ``FreqTracker``
    decayed scores when the observed routed imbalance drifts past the
    threshold, re-homing ranks so shard load tracks the live hot set.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.contracts import INT_COUNTERS, contract
from repro.core import cache as cache_lib
from repro.core import freq as freq_lib
from repro.core import refresh as refresh_lib
from repro.core import transmitter
from repro.core.collection import (
    METRICS_INT_COUNTERS,
    ArenaConfig,
    CollectionState,
    DeviceSlab,
    EmbeddingCollection,
    FeatureBatch,
    PlacementPlan,
    PlacementPlanner,
    ShardAssignment,
    TableConfig,
    _CachedSlabSpec,
    _read_full_rows,
    device_bytes_limit,
)
from repro.dist import partitioning as dist_part
from repro.kernels.cache_ops import ops as cache_ops
from repro.obs.tracing import SCOPE_EXCHANGE
from repro.store.host_store import row_counters, uniform_rows  # noqa: F401 (public here too)
from repro.store import (
    ArenaStore,
    HostStore,
    PrecisionPolicy,
    SlabGeometry,
    get_codec,
    tiered_arena_bytes,
)

__all__ = [
    "RepArena",
    "ShardedSlab",
    "ShardedCollectionPlan",
    "ShardedEmbeddingCollection",
    "flat_store",
    "row_counters",
    "uniform_rows",
]

# sentinel for invalid lanes in the dedup'd rank buffer: sorts after every
# real rank (vocab is far below int32 max), so ``jnp.unique`` packs real
# ranks first and padding last.
_PAD_RANK = jnp.iinfo(jnp.int32).max


def _draws_by_counter(dtype) -> bool:
    """``uniform_rows`` reproduces ``jax.random.uniform`` only for float32
    under JAX's partitionable threefry (its default)."""
    return bool(jax.config.jax_threefry_partitionable) and jnp.dtype(dtype) == jnp.float32


def flat_store(store: HostStore) -> HostStore:
    """View a shard-stacked store ([S, vocab_s, ...] leaves) as one flat
    [S * vocab_s, ...] store — flat row ``owner * vocab_s + local`` is the
    rank's slot, which is how oracles and checkpoint validators address it."""
    def rs(v):
        return v.reshape((-1,) + v.shape[2:])

    return HostStore(
        data={k: rs(v) for k, v in store.data.items()},
        sideband={k: rs(v) for k, v in store.sideband.items()},
        codec=store.codec,
        out_dtype=store.out_dtype,
    )


def _stack_store(store: HostStore, S: int, vs: int) -> HostStore:
    """Inverse of :func:`flat_store`: re-stack a flat [S*vs, ...] store into
    the [S, vs, ...] shard-stacked layout."""
    def rs(v):
        return v.reshape((S, vs) + v.shape[1:])

    return HostStore(
        data={k: rs(v) for k, v in store.data.items()},
        sideband={k: rs(v) for k, v in store.sideband.items()},
        codec=store.codec,
        out_dtype=store.out_dtype,
    )


def _shard_lane_idx(owner: jnp.ndarray, slot: jnp.ndarray, S: int, cap: int):
    """[L] per-lane (owner, slot) -> [S, L] per-shard take indices: shard s
    keeps its own lanes' slots and fills everyone else's with the
    out-of-range sentinel ``cap`` (-> zero row).  Each valid lane is owned by
    exactly ONE shard, so summing the per-shard takes is an exact select —
    and it is the form GSPMD partitions as the row all-to-all: every shard
    does an O(L) LOCAL take, instead of the all-gather of the whole stacked
    arena that a flat ``jnp.take`` on the [S*cap] view lowers to (that
    all-gather is what made the gather cost per shard scale with S)."""
    sids = jnp.arange(S, dtype=jnp.int32)[:, None]
    return jnp.where(owner[None, :] == sids, slot[None, :], cap)


def _partitioned_take(w: jnp.ndarray, owner: jnp.ndarray, slot: jnp.ndarray):
    """Raw (fp32) routed row-leg: [S, cap, dim] stacked arena + per-lane
    routing -> [L, dim] rows, as shard-local takes summed across the shard
    axis (see ``_shard_lane_idx``).  Lanes with ``owner`` outside [0, S)
    come back as exact zero rows — the padding-lane convention."""
    S, cap = w.shape[0], w.shape[1]
    idx = _shard_lane_idx(owner, slot, S, cap)
    part = jax.vmap(
        lambda w_, i_: jnp.take(w_, i_, axis=0, mode="fill", fill_value=0)
    )(w, idx)
    return jnp.sum(part, axis=0)


def _encoded_exchange(
    codec, w: jnp.ndarray, owner: jnp.ndarray, slot: jnp.ndarray
) -> jnp.ndarray:
    """The compressed row-leg of the exchange: each producer shard encodes
    ITS arena slice, per-lane payload + sideband cross the wire (that is the
    traffic ``metrics`` accounts), and the consumer decodes once.  Same
    partitioned shape as ``_partitioned_take`` — per-shard local takes of
    the ENCODED payload summed across shards (exact: one owner per lane,
    zero fill elsewhere, and every codec decodes zero payload + zero
    sideband to the zero row).  Straight-through gradient: the backward pass
    is the plain gather transpose (per-shard scatter-add into the fp32
    arena), identical to the uncompressed path, so training updates
    full-precision rows while only the forward value carries codec noise —
    the PR3 host-tier semantics, applied to the wire."""
    S, cap = w.shape[0], w.shape[1]
    shape = w.shape
    out_dtype = w.dtype

    @jax.custom_vjp
    def take_enc(w_):
        payload, side = jax.vmap(codec.encode)(w_)
        idx = _shard_lane_idx(owner, slot, S, cap)
        tk = jax.vmap(
            lambda x_, i_: jnp.take(x_, i_, axis=0, mode="fill", fill_value=0)
        )
        p = jnp.sum(tk(payload, idx), axis=0, dtype=payload.dtype)
        s_ = None
        if side is not None:
            s_ = jnp.sum(tk(side, idx), axis=0, dtype=side.dtype)
        return codec.decode(p, s_, out_dtype)

    def fwd(w_):
        return take_enc(w_), None

    def bwd(_, ct):
        own = jnp.where((owner >= 0) & (owner < S), owner, S)  # OOB -> drop
        return (
            jnp.zeros(shape, ct.dtype).at[own, slot].add(ct, mode="drop"),
        )

    take_enc.defvjp(fwd, bwd)
    return take_enc(w)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RepArena:
    """The replicated hot head of one sharded slab (``replicate_top_k``).

    ``rows[r]`` is the fp32-authoritative fast-tier row of frequency rank
    ``r < K`` — replicated on every shard (no leading [S] dim; its
    PartitionSpec replicates, and under jit GSPMD inserts the gradient
    all-reduce that keeps the copies identical, like the data-parallel
    MLPs).  Replicated lanes bypass the per-shard cache plans, so the arena
    keeps its own lazy-decay tracker slice (same formula and plan clock as
    ``FreqTracker``) — without it the hot head would go dark to ``refresh``
    and the re-balance trigger.  ``K = 0`` gives zero-length leaves and a
    behavior bit-identical to the pre-replication collection."""

    rows: jnp.ndarray  # [K, dim] replicated fast-tier rows
    score: jnp.ndarray  # float32 [K] decayed mass, exact at last_touch
    last_touch: jnp.ndarray  # int32 [K] plan step of the last touch
    step: jnp.ndarray  # int32 [] plan clock (ticks with ``apply_plan``)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ShardedSlab:
    """One cached slab sharded over the model axis (leading dim = shard)."""

    full: Any  # HostStore, leaves [S, rows_per_shard, ...] (encoded)
    cache: cache_lib.CacheState  # every leaf [S, ...] (per-shard arena)
    idx_map: jnp.ndarray  # int32 [vocab] raw id -> freq rank (replicated)
    rank_owner: jnp.ndarray  # int32 [vocab] rank -> owning shard (replicated)
    rank_local: jnp.ndarray  # int32 [vocab] rank -> local row (replicated)
    routed_lanes: jnp.ndarray  # int32 [S] cumulative id lanes routed per shard
    rep: RepArena  # replicated hot head (zero-length leaves when K = 0)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ShardedCollectionPlan:
    """``CollectionPlan`` analogue with per-shard cache plans.

    ``slab_plans`` leaves carry a leading [S] shard dim; ``addresses`` are
    COMBINED addresses (``owner * shard_capacity + slot``, -1 padding) into
    the flattened stacked fast tier, so the downstream gather/pool/grad path
    is shape-identical to the unsharded one.  ``routed`` counts this step's
    valid id lanes per shard (the id all-to-all payload).  Field names match
    ``CollectionPlan`` where the trainer reads them (``addresses``,
    ``future_addresses``, ``future_unresident`` — a scalar, summed over
    shards, so ``PipelinedTrainer`` needs no sharding awareness).
    """

    slab_plans: Dict[str, cache_lib.CachePlan]
    routed: Dict[str, jnp.ndarray]
    addresses: Dict[str, jnp.ndarray]
    # per-slab dedup'd rank buffer of this step's batch (int32, -1 padding) —
    # ``apply_plan`` folds the replicated head's touches into the arena
    # tracker from it (the per-shard plans never see replicated lanes).
    uniq_ranks: Dict[str, jnp.ndarray] = dataclasses.field(default_factory=dict)
    future_addresses: Tuple[Dict[str, jnp.ndarray], ...] = ()
    future_unresident: jnp.ndarray = dataclasses.field(
        default_factory=lambda: jnp.zeros((), jnp.int32)
    )
    writeback: bool = dataclasses.field(default=True, metadata=dict(static=True))


class ShardedEmbeddingCollection(EmbeddingCollection):
    """``EmbeddingCollection`` with cached slabs sharded over a model axis.

    Same keyed-feature surface (``init`` / ``plan_prepare`` / ``apply_plan``
    / ``prepare`` / ``weights`` / ``gather`` / ``pool`` / ``apply_grads`` /
    ``flush`` / ``metrics`` / ``device_bytes`` / ``shard_specs``), so models
    and both trainers consume it unchanged.  ``num_shards`` is the size of
    the mesh's ``model`` axis; on a single device the stacked state simply
    lives on that device (useful for tests — the math is mesh-agnostic).
    """

    def __init__(
        self,
        tables: Sequence[TableConfig],
        plan: PlacementPlan,
        num_shards: int,
        model_axis: str = "model",
        replicate_top_k: int = 0,
        exchange_codec: Optional[str] = None,
        max_routed_per_shard: int = 0,
    ):
        super().__init__(tables, plan)
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        self.num_shards = int(num_shards)
        self.model_axis = model_axis
        # static per-shard plan width bound: 0 (default) keeps the exact
        # full-width [S, U] bucketize image; > 0 compacts routed lanes to a
        # dense [S, W] image so the vmapped per-shard plans stop scaling with
        # the dedup buffer.  Lanes past the bound are counted into
        # ``uniq_overflows`` and trip the trainer's exactness guard.
        self.max_routed_per_shard = max(int(max_routed_per_shard), 0)
        # hot-row replication head size (per cached slab, clamped to vocab)
        self.replicate_top_k = max(int(replicate_top_k), 0)
        # wire codec of the row-leg exchange; None / "fp32" = raw rows (the
        # bit-exact default — fp32's encode/decode is identity, so it is
        # folded into the plain-gather path rather than paying the custom-vjp
        # detour for nothing).
        if exchange_codec in (None, "fp32"):
            self.exchange_codec: Optional[str] = None
        else:
            get_codec(exchange_codec)  # fail fast on typos
            self.exchange_codec = exchange_codec
        # per-slab frequency-driven device assignment; populated by ``init``
        # (it needs the counts), updated by re-balance passes, and mirrored
        # host-side for telemetry.
        self.assignments: Dict[str, ShardAssignment] = {}
        limit = device_bytes_limit()
        if self.cached_slabs and limit is not None and self.device_need() > limit:
            raise ValueError(
                f"the sharded collection needs {self.device_need()} B per device with "
                f"each shard's slow tier there, over the device's {limit} B; it has no "
                f"host tier (the slow tier in pinned_host memory serves the unsharded "
                f"collection only): use more model shards or devices with more memory")

    def _slow_tier_memory(self) -> str:
        return "device"  # the shard slow tiers stay on their devices (checked above)

    def device_need(self) -> int:
        """Bytes one mesh device needs with its shard of every slow tier: its
        share of the state (``device_per_shard``), its slow-tier shard and a
        step's buffers."""
        db = self.device_bytes()
        return db["device_per_shard"] + db["slow_tier_bytes"] // self.num_shards \
            + self.step_bytes()

    @classmethod
    def create(
        cls,
        tables: Sequence[TableConfig],
        num_shards: int = 1,
        budget_bytes: Optional[int] = None,
        counts: Optional[Mapping[str, np.ndarray]] = None,
        planner: Optional[PlacementPlanner] = None,
        model_axis: str = "model",
        replicate_top_k: int = 0,
        exchange_codec: Optional[str] = None,
        max_routed_per_shard: int = 0,
        **arena_kw,
    ) -> "ShardedEmbeddingCollection":
        """Plan + build, like ``EmbeddingCollection.create`` plus the shard
        count.  ``budget_bytes`` is the PER-DEVICE budget (each shard holds
        1/S of every cached slab plus the replicated DEVICE tables)."""
        if planner is None and budget_bytes is None:
            return cls(tables, PlacementPlan.single_arena(tables, **arena_kw),
                       num_shards, model_axis, replicate_top_k, exchange_codec,
                       max_routed_per_shard)
        planner = planner or PlacementPlanner(
            budget_bytes,
            arena=ArenaConfig(**arena_kw),
            host_precision=arena_kw.get("host_precision"),
            arena_precision=arena_kw.get("arena_precision"),
            arena_head_ratio=arena_kw.get("arena_head_ratio", 0.25),
        )
        return cls(tables, planner.plan(tables, counts=counts), num_shards,
                   model_axis, replicate_top_k, exchange_codec,
                   max_routed_per_shard)

    # ----- per-shard geometry ----------------------------------------------

    def rows_per_shard(self, spec: _CachedSlabSpec) -> int:
        return -(-spec.vocab // self.num_shards)

    def shard_capacity(self, spec: _CachedSlabSpec) -> int:
        """Per-shard cache capacity: the slab ratio applied to the local
        vocab, floored at one batch's unique rows (worst-case skew: every
        lane of a batch may land on one shard).  With a
        ``max_routed_per_shard`` bound the worst case is the bound itself
        (lanes past it trip the ``uniq_overflows`` guard), so the floor
        shrinks with it — this is what keeps per-shard plan cost (eviction
        sort, movement lists, index images) proportional to 1/S instead of
        pinning every shard at full-batch width.  Capacity never changes
        lookup VALUES (writeback keeps cached rows equal to the slow tier),
        so shrinking the floor preserves bit-exactness."""
        vs = self.rows_per_shard(spec)
        k = min(spec.ids_per_step, vs)
        if self.max_routed_per_shard:
            k = min(k, self.max_routed_per_shard)
        if spec.max_unique_per_step:
            k = min(k, spec.max_unique_per_step)
        return min(max(int(spec.cache_ratio * vs), k), vs)

    def shard_cache_config(
        self,
        spec: _CachedSlabSpec,
        ids_per_step: Optional[int] = None,
        writeback: bool = True,
    ) -> cache_lib.CacheConfig:
        ids = ids_per_step or spec.ids_per_step
        if self.max_routed_per_shard:
            # a shard never sees more than the routed-lane bound per step
            # (plan_prepare compacts to it and counts the excess into
            # ``uniq_overflows``), so the per-shard id width shrinks with it
            ids = min(ids, self.max_routed_per_shard)
        return cache_lib.CacheConfig(
            vocab=self.rows_per_shard(spec),
            capacity=self.shard_capacity(spec),
            ids_per_step=ids,
            buffer_rows=spec.buffer_rows,
            policy=spec.policy,
            writeback=writeback,
            max_unique_per_step=spec.max_unique_per_step,
            protect_via_inverse=spec.protect_via_inverse,
            freq_half_life=spec.freq_half_life,
            use_pallas_plan=spec.use_pallas_plan,
            chunk_rows=spec.chunk_rows,
            # each shard's arena tiers at the same head ratio; an unresolved
            # "auto" (config built before ``init``) budgets at the policy's
            # no-stats pick, exactly like the unsharded ``cache_config``.
            arena_precision=(
                PrecisionPolicy().no_stats
                if spec.arena_precision == "auto"
                else spec.arena_precision
            ),
            arena_head_ratio=spec.arena_head_ratio,
        )

    # ----- init -------------------------------------------------------------

    def init(
        self,
        rng: jax.Array,
        counts: Optional[Mapping[str, np.ndarray]] = None,
        warm: bool = True,
        host_precision: Optional[str] = None,
        arena_precision: Optional[str] = None,
    ) -> CollectionState:
        """Build the sharded state.  Weight draws mirror the unsharded
        ``init`` key-for-key, so the sharded collection starts from the exact
        same logical table as the single-device reference — the basis of the
        loss-trajectory parity property."""
        S = self.num_shards
        slabs: Dict[str, Any] = {}
        keys = jax.random.split(rng, len(self.device_slabs) + len(self.cached_slabs))
        kit = iter(keys)
        for name, t in self.device_slabs.items():
            scale = 1.0 / np.sqrt(t.dim)
            slabs[name] = DeviceSlab(
                weight=jax.random.uniform(next(kit), (t.vocab, t.dim), t.dtype, -scale, scale)
            )
        for sname, spec in self.cached_slabs.items():
            scale = 1.0 / np.sqrt(spec.dim)
            wkey = next(kit)  # the slab's draw, uniform(wkey, (vocab, dim))
            slab_counts = None
            counts_ranked = None
            if counts is not None:
                slab_counts = np.concatenate(
                    [
                        np.asarray(
                            counts.get(t.name, np.zeros((t.vocab,), np.int64)), np.int64
                        )
                        for t in spec.tables
                    ]
                )
                stats = freq_lib.build_freq_stats(slab_counts)
                idx_map = jnp.asarray(stats.idx_map)
                counts_ranked = stats.counts[stats.inv_map]  # descending
            else:
                idx_map = jnp.arange(spec.vocab, dtype=jnp.int32)
            K = min(self.replicate_top_k, spec.vocab)
            assign = PlacementPlanner.assign_devices(
                spec.vocab, S, counts_ranked, replicate_top_k=K
            )
            self.assignments[sname] = assign
            codec = host_precision or spec.host_precision
            if codec == "auto":
                codec = self.precision_policy.choose(
                    SlabGeometry(
                        name=sname,
                        vocab=spec.vocab,
                        dim=spec.dim,
                        capacity=S * self.shard_capacity(spec),
                        dtype_itemsize=jnp.dtype(spec.dtype).itemsize,
                    ),
                    counts=slab_counts,
                )
            else:
                get_codec(codec)  # fail fast on typos
            self.host_precision[sname] = codec
            # arena (fast-tier) precision mirrors the host resolution: "auto"
            # picks from the GLOBAL resident geometry (S * shard capacity /
            # head) — coverage is a property of the logical slab, not of one
            # shard's slice.  The resolved codec is written back into the
            # spec so every later ``shard_cache_config`` agrees with the
            # state structure built below.
            arena_codec = arena_precision or spec.arena_precision
            if arena_codec == "auto":
                cap_s = self.shard_capacity(spec)
                head_s = min(cap_s, max(1, int(round(spec.arena_head_ratio * cap_s))))
                arena_codec = self.precision_policy.choose_arena(
                    SlabGeometry(
                        name=sname,
                        vocab=spec.vocab,
                        dim=spec.dim,
                        capacity=S * cap_s,
                        dtype_itemsize=jnp.dtype(spec.dtype).itemsize,
                    ),
                    S * head_s,
                    counts=slab_counts,
                )
            else:
                get_codec(arena_codec)  # fail fast on typos
            if arena_codec != spec.arena_precision:
                spec = dataclasses.replace(spec, arena_precision=arena_codec)
                self.cached_slabs[sname] = spec
            self.arena_precision[sname] = arena_codec
            vs = self.rows_per_shard(spec)
            # scatter rank r's row to flat slot owner[r]*vs + local[r]; pad
            # rows (flat slots no rank maps to) stay zero and are never read.
            dest = assign.owner.astype(np.int64) * vs + assign.local.astype(np.int64)
            if counts is None:
                # round-robin over the sequence routed ranks K.. then the
                # replicated head: rank r sits at position p = (r - K) mod
                # vocab, on shard p % S, row p // S.  The rank maps are iotas
                # rather than vocab-sized constants in a jitted init.
                pos = (jnp.arange(spec.vocab, dtype=jnp.int32) + (spec.vocab - K)) % spec.vocab
                rank_owner, rank_local = pos % S, pos // S
            else:
                rank_owner = jnp.asarray(assign.owner)
                rank_local = jnp.asarray(assign.local)
            if _draws_by_counter(spec.dtype):
                # ``at[s, l]`` is the rank whose home is shard s's row l
                # (vocab on pad rows); each row is drawn where it lives, so
                # no device draws, or holds, the whole table
                if counts is None:
                    p = (jnp.arange(vs, dtype=jnp.int32)[None, :] * S
                         + jnp.arange(S, dtype=jnp.int32)[:, None])
                    at = jnp.where(p < spec.vocab, (p + K) % spec.vocab, spec.vocab)
                else:
                    inv = np.full((S * vs,), spec.vocab, np.int32)
                    inv[dest] = np.arange(spec.vocab, dtype=np.int32)
                    at = jnp.asarray(inv.reshape(S, vs))
                at = dist_part.constrain(at, "shard", None)
                rows = uniform_rows(wkey, at, spec.dim, -scale, scale)
                rows = jnp.where((at < spec.vocab)[..., None], rows, 0)
                flat = rows.reshape(S * vs, spec.dim)
                head = uniform_rows(wkey, jnp.arange(K, dtype=jnp.int32), spec.dim,
                                    -scale, scale)
            else:
                weight = jax.random.uniform(
                    wkey, (spec.vocab, spec.dim), spec.dtype, -scale, scale
                )
                flat = jnp.zeros((S * vs, spec.dim), spec.dtype).at[
                    jnp.asarray(dest, jnp.int32)].set(weight)
                head = weight[:K]
            store = HostStore.create({"weight": flat}, codec=codec)
            full = HostStore(
                data={k: v.reshape((S, vs) + v.shape[1:]) for k, v in store.data.items()},
                sideband={
                    k: v.reshape((S, vs) + v.shape[1:]) for k, v in store.sideband.items()
                },
                codec=store.codec,
                out_dtype=store.out_dtype,
            )
            ccfg = self.shard_cache_config(spec)
            cache0 = cache_lib.init_cache(
                ccfg, {"weight": jnp.zeros((spec.dim,), spec.dtype)}
            )
            cache = jax.tree_util.tree_map(
                lambda l: jnp.repeat(l[None], S, axis=0), cache0
            )
            if warm:
                full, cache = jax.vmap(
                    lambda f, c: cache_lib.warmup(ccfg, f, c)
                )(full, cache)
            # replicated hot head: rank r's content is row r of the slab's
            # draw (the same rank-content convention as the homes above), so
            # the arena starts bit-identical to the ranks' slow-tier homes.
            rep = RepArena(
                rows=head,
                score=jnp.zeros((K,), jnp.float32),
                last_touch=jnp.zeros((K,), jnp.int32),
                step=jnp.zeros((), jnp.int32),
            )
            slabs[sname] = ShardedSlab(
                full=full,
                cache=cache,
                idx_map=idx_map,
                rank_owner=rank_owner,
                rank_local=rank_local,
                routed_lanes=jnp.zeros((S,), jnp.int32),
                rep=rep,
            )
        return CollectionState(slabs=slabs)

    # ----- id routing (the bucketize / all-to-all image) --------------------

    def _rank_ids(self, slab: ShardedSlab, raw: jnp.ndarray) -> jnp.ndarray:
        """Slab-global raw ids (-1 pad) -> frequency ranks (-1 pad)."""
        valid = raw >= 0
        rank = slab.idx_map.at[jnp.where(valid, raw, 0)].get(mode="fill", fill_value=-1)
        return jnp.where(valid, rank, -1)

    def _route(
        self, slab: ShardedSlab, rank: jnp.ndarray
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Frequency ranks (-1 pad) -> (owning shard, local row), both -1 on
        padding lanes AND on replicated lanes (``rank < K``) — replicated
        ranks are served from the per-shard arena and never enter the id
        exchange, which is the whole point of the head."""
        K = slab.rep.rows.shape[0]
        ok = rank >= K
        owner = slab.rank_owner.at[jnp.where(ok, rank, 0)].get(mode="fill", fill_value=-1)
        local = slab.rank_local.at[jnp.where(ok, rank, 0)].get(mode="fill", fill_value=-1)
        return jnp.where(ok, owner, -1), jnp.where(ok, local, -1)

    @staticmethod
    def _dedup(
        rank: jnp.ndarray, vocab: int, fused: bool = False
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Dedup ranks ahead of the bucketize: [L] ranks (-1 pad) ->
        ``(uniq, pos)`` where ``uniq`` is the [U = min(L, vocab)] ascending
        unique buffer (``_PAD_RANK`` padding) and ``pos[i]`` locates lane
        i's rank in it.  A shard then receives each id at most ONCE per plan
        — duplicate lanes (within or across a slab's features) collapse to
        one exchange lane and one cache-plan lane.

        ``fused=True`` swaps ``jnp.unique`` for the one-sort dedup in
        ``kernels/cache_ops`` (bit-identical; ``_PAD_RANK`` is the max
        sentinel it collapses padding into)."""
        u = min(int(rank.shape[0]), int(vocab))
        key = jnp.where(rank >= 0, rank, _PAD_RANK)
        if fused:
            uniq, _ = cache_ops.dedup_impl(key, u, _PAD_RANK)
        else:
            uniq = jnp.unique(key, size=u, fill_value=_PAD_RANK)
        pos = jnp.minimum(jnp.searchsorted(uniq, key), u - 1).astype(jnp.int32)
        return uniq.astype(jnp.int32), pos

    def _bucketize(
        self, owner: jnp.ndarray, local: jnp.ndarray, fused: bool = False
    ) -> jnp.ndarray:
        """[lanes] routing -> [S, lanes] per-shard local-row image: shard s's
        row keeps only the lanes it owns (-1 elsewhere).  Sharding the
        leading axis over ``model`` makes this the id all-to-all payload.
        ``fused=True`` routes through ``kernels/cache_ops`` (a per-shard-row
        Pallas pass on accelerators; same where-image on CPU)."""
        if fused:
            return cache_ops.bucketize_impl(owner, local, self.num_shards)
        sids = jnp.arange(self.num_shards, dtype=jnp.int32)[:, None]
        return jnp.where(
            (owner[None, :] == sids) & (local[None, :] >= 0), local[None, :], -1
        ).astype(jnp.int32)

    def _compact_lanes(
        self, owner: jnp.ndarray, local: jnp.ndarray, width: int
    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """Dense [S, width] per-shard lane image (vs ``_bucketize``'s sparse
        [S, U] one): ONE stable argsort by owner groups every shard's lanes
        contiguously, so the vmapped per-shard plans chew ``width`` lanes
        instead of U — the term that made planning cost scale with S.

        Returns ``(rows, src, overflow)``: per-shard local rows (-1 pad),
        the source index of each compact lane in the dedup'd array (-1 pad;
        the scatter map that rebuilds combined addresses), and the per-shard
        count of lanes DROPPED because a shard drew more than ``width``
        unique rows.  Dropped lanes would silently read zero rows, so the
        caller must surface overflow through ``uniq_overflows`` (the trainer
        raises on it — same exactness contract as the unique-buffer bound)."""
        u = owner.shape[0]
        S = self.num_shards
        key = jnp.where(local >= 0, owner, S)  # pad/replicated -> sentinel S
        perm = jnp.argsort(key)  # stable: keeps dedup order within a shard
        sk = jnp.take(key, perm)
        starts = jnp.searchsorted(sk, jnp.arange(S + 1, dtype=sk.dtype))
        counts = (starts[1:] - starts[:-1]).astype(jnp.int32)
        j = jnp.arange(width, dtype=jnp.int32)[None, :]
        ok = j < jnp.minimum(counts, width)[:, None]
        pos = jnp.clip(starts[:S, None] + j, 0, u - 1)
        src = jnp.where(ok, jnp.take(perm, pos), -1).astype(jnp.int32)
        rows = jnp.where(
            ok, jnp.take(local, jnp.where(ok, src, 0)), -1
        ).astype(jnp.int32)
        overflow = jnp.maximum(counts - width, 0)
        return rows, src, overflow

    def _lane_width(self, u: int) -> Optional[int]:
        """Static compact-image width, or None for the full-width path (the
        historical, bound-free layout)."""
        w = self.max_routed_per_shard
        if w <= 0 or w >= u:
            return None
        return w

    @staticmethod
    def _combine_slots(per_shard_slots: jnp.ndarray, cap: int) -> jnp.ndarray:
        """[S, lanes] per-shard slots (-1 off-shard) -> [lanes] combined
        addresses ``owner * cap + slot`` (-1 pad).  Each valid lane is
        resident on exactly one shard, so an integer sum of the shifted
        one-hot encodings is exact — this is the return half of the
        exchange, folded into address arithmetic."""
        S = per_shard_slots.shape[0]
        enc = jnp.where(
            per_shard_slots >= 0,
            jnp.arange(S, dtype=jnp.int32)[:, None] * cap + per_shard_slots + 1,
            0,
        )
        return jnp.sum(enc, axis=0) - 1

    def _lookup_combined(
        self,
        row_to_slot: jnp.ndarray,  # [S, vocab_s] index image
        owner: jnp.ndarray,
        local: jnp.ndarray,
        cap: int,
    ) -> jnp.ndarray:
        """Combined address of each (owner, local) lane under an index image
        (-1 when not resident on its owner or a padding lane)."""
        enc = jnp.zeros(owner.shape, jnp.int32)
        for s in range(self.num_shards):  # S is small and static
            rs = row_to_slot[s]
            slot = rs.at[jnp.where(owner == s, local, 0)].get(mode="fill", fill_value=-1)
            enc = enc + jnp.where((owner == s) & (slot >= 0), s * cap + slot + 1, 0)
        return enc - 1

    # ----- the non-diff bookkeeping pass ------------------------------------

    # bounded-top-K declaration mirrors ``cache.plan_prepare``: with
    # ``use_pallas_plan`` the vmapped per-shard plans and the router dedup/
    # bucketize route through kernels/cache_ops (ROADMAP item 3), so no
    # capacity-sized sort survives; the oracle route keeps the historical
    # argsort and is covered by bit-identity tests instead.
    @contract(max_sort_size=64, int_counters=INT_COUNTERS)
    def plan_prepare(
        self,
        state: CollectionState,
        fb: FeatureBatch,
        fb_future: Sequence[FeatureBatch] = (),
        writeback: bool = True,
    ) -> ShardedCollectionPlan:
        """Sharded planning half: translate ids, bucketize them by owning
        shard, and run one weight-free cache plan per shard (vmapped over the
        stacked state — on a mesh each shard plans on its own device).
        Lookahead windows merge per shard exactly like the unsharded path;
        ``future_unresident`` sums over shards so the pipelined trainer's
        group guard is sharding-agnostic."""
        self._check_features(fb, *fb_future)
        addresses: Dict[str, jnp.ndarray] = {}
        future_addresses: List[Dict[str, jnp.ndarray]] = [{} for _ in fb_future]
        future_unresident = jnp.zeros((), jnp.int32)

        for j, b in enumerate((fb, *fb_future)):
            out = addresses if j == 0 else future_addresses[j - 1]
            for f in b.features:
                if self.feature_to_table[f] in self.device_slabs:
                    out[f] = b.ids[f].astype(jnp.int32)

        slab_plans: Dict[str, cache_lib.CachePlan] = {}
        routed: Dict[str, jnp.ndarray] = {}
        uniq_ranks: Dict[str, jnp.ndarray] = {}
        for sname, spec in self.cached_slabs.items():
            raw = self._slab_raw(fb, sname)
            slab = state.slabs[sname]
            fut_raws = [self._slab_raw(b, sname) for b in fb_future]
            if raw is None:
                # slab touched only by the window: not prefetched (see the
                # unsharded path) — surface its lanes in the guard instead.
                for raw_j in fut_raws:
                    if raw_j is not None:
                        future_unresident = future_unresident + jnp.sum(
                            raw_j >= 0
                        ).astype(jnp.int32)
                continue
            cap = self.shard_capacity(spec)
            K = slab.rep.rows.shape[0]
            ncomb = self.num_shards * cap  # arena addresses live past this
            rank = self._rank_ids(slab, raw)
            fused = spec.use_pallas_plan
            uniq, pos = self._dedup(rank, spec.vocab, fused=fused)  # [U], [lanes]
            owner_u, local_u = self._route(slab, uniq)
            width = self._lane_width(int(uniq.shape[0]))
            if width is None:
                rows_sh = self._bucketize(owner_u, local_u, fused=fused)  # [S, U]
                src_sh = lane_over = None
            else:
                # bounded dense image: the vmapped per-shard plans run at
                # ``width`` lanes instead of U — the term that made plan cost
                # scale with S.  Dropped lanes are counted loudly below.
                rows_sh, src_sh, lane_over = self._compact_lanes(
                    owner_u, local_u, width
                )
            # pin the per-shard image split over the shard axis: it is built
            # from REPLICATED dedup output, and without the constraint GSPMD
            # is free to keep the whole vmapped plan replicated — every
            # device then plans all S shards and plan cost scales with S.
            rows_sh = dist_part.constrain(rows_sh, "shard", None)
            fut_ranks = [
                None if p is None else self._rank_ids(slab, p) for p in fut_raws
            ]
            fut_parts = [r for r in fut_ranks if r is not None]
            if fut_parts:
                # the window merges into ONE dedup'd image (the per-shard
                # plan only needs the union of pinned rows)
                fuq, _ = self._dedup(
                    jnp.concatenate(fut_parts), spec.vocab, fused=fused
                )
                fo, fl = self._route(slab, fuq)
                if width is None:
                    fut_sh = self._bucketize(fo, fl, fused=fused)
                else:
                    # a dropped future lane only loses its prefetch pin; the
                    # pipelined group guard still counts it unresident, so
                    # the bound is safe (not silent) on the window leg.
                    fut_sh, _, _ = self._compact_lanes(fo, fl, width)
                fut_sh = dist_part.constrain(fut_sh, "shard", None)
            else:
                fut_sh = None
            ccfg = self.shard_cache_config(
                spec, ids_per_step=int(rows_sh.shape[1]), writeback=writeback
            )
            if fut_sh is None:
                plan = jax.vmap(
                    lambda st_, r_: cache_lib.plan_prepare(ccfg, st_, r_)
                )(slab.cache, rows_sh)
            else:
                plan = jax.vmap(
                    lambda st_, r_, f_: cache_lib.plan_prepare(
                        ccfg, st_, r_, future_rows=f_
                    )
                )(slab.cache, rows_sh, fut_sh)
            if width is not None:
                # a dropped lane would silently gather a zero row — count it
                # into the same exactness guard as the unique-buffer bound.
                plan = dataclasses.replace(
                    plan, uniq_overflows=plan.uniq_overflows + lane_over
                )
            slab_plans[sname] = plan
            routed[sname] = jnp.sum(rows_sh >= 0, axis=1).astype(jnp.int32)
            uniq_ranks[sname] = jnp.where(uniq < _PAD_RANK, uniq, -1)
            if width is None:
                combined_u = self._combine_slots(plan.slots, cap)  # [U]
            else:
                # scatter the compact [S, W] slots back to dedup'd lane
                # order: each uniq lane lives in at most one compact cell, so
                # a one-hot-shifted scatter-add is exact (see _combine_slots)
                u_n = int(uniq.shape[0])
                sids = jnp.arange(self.num_shards, dtype=jnp.int32)[:, None]
                enc = jnp.where(
                    (src_sh >= 0) & (plan.slots >= 0),
                    sids * cap + plan.slots + 1,
                    0,
                )
                dest = jnp.where(src_sh >= 0, src_sh, u_n).reshape(-1)
                combined_u = (
                    jnp.zeros((u_n,), jnp.int32)
                    .at[dest]
                    .add(enc.reshape(-1), mode="drop")
                    - 1
                )
            if K:
                # replicated lanes: always-resident arena addresses appended
                # after the routed combined space (the _PAD_RANK sentinel is
                # >= K, so padding lanes fall through untouched)
                combined_u = jnp.where(uniq < K, ncomb + uniq, combined_u)
            lane_addr = jnp.where(rank >= 0, jnp.take(combined_u, pos), -1)
            off = 0
            for f, n in self._slab_lanes(fb, sname):
                addresses[f] = lane_addr[off : off + n].reshape(fb.ids[f].shape)
                off += n
            for j, (b, rank_j) in enumerate(zip(fb_future, fut_ranks)):
                if rank_j is None:
                    continue
                o_j, l_j = self._route(slab, rank_j)
                slots_j = self._lookup_combined(plan.row_to_slot, o_j, l_j, cap)
                if K:
                    slots_j = jnp.where(
                        (rank_j >= 0) & (rank_j < K), ncomb + rank_j, slots_j
                    )
                # replicated lanes never count as unresident: their l_j is -1
                # and their addresses are arena-resident by construction.
                future_unresident = future_unresident + jnp.sum(
                    (l_j >= 0) & (slots_j < 0)
                ).astype(jnp.int32)
                off = 0
                for f, n in self._slab_lanes(b, sname):
                    future_addresses[j][f] = slots_j[off : off + n].reshape(
                        b.ids[f].shape
                    )
                    off += n
        return ShardedCollectionPlan(
            slab_plans=slab_plans,
            routed=routed,
            addresses=addresses,
            uniq_ranks=uniq_ranks,
            future_addresses=tuple(future_addresses),
            future_unresident=future_unresident,
            writeback=writeback,
        )

    @contract(donates=("state",), int_counters=INT_COUNTERS, max_sort_size=0)
    def apply_plan(
        self, state: CollectionState, plan: ShardedCollectionPlan
    ) -> CollectionState:
        """Execute every shard's planned row movement (vmapped: each shard
        moves rows between ITS host-store slice and ITS cache arena — no
        cross-shard traffic) and accumulate the exchange telemetry.

        All shards run the transmitter rounds that reach the busiest shard's
        loads.  That count is taken outside the ``vmap``: a batched trip
        count would lower the loop with a batched predicate, a ``select``
        over the whole carried slow tier in every round."""
        slabs = dict(state.slabs)
        for sname, p in plan.slab_plans.items():
            spec = self.cached_slabs[sname]
            ccfg = self.shard_cache_config(spec, writeback=plan.writeback)
            slab = slabs[sname]
            live = jnp.max(jnp.sum(p.load_active, axis=-1, dtype=jnp.int32))
            full, cache = jax.vmap(
                lambda f, c, pp, n: cache_lib.apply_plan(ccfg, f, c, pp, live_lanes=n),
                in_axes=(0, 0, 0, None),
            )(slab.full, slab.cache, p, live)
            rep = slab.rep
            step = rep.step + 1  # ticks with the per-shard plan clocks
            u = plan.uniq_ranks.get(sname)
            if rep.rows.shape[0] and u is not None:
                # fold the head's touches into the arena tracker (the
                # per-shard plans never see replicated lanes) — the same
                # lazy-decay bump as ``freq.tracker_touch``.  The dedup'd
                # rank buffer is ascending with -1 padding at the tail, so
                # every arena lane (rank < K) lives in its first K entries —
                # slice there instead of scanning the full lane width.
                K = rep.rows.shape[0]
                u = u[: min(K, u.shape[0])]
                m = (u >= 0) & (u < K)
                safe = jnp.where(m, u, 0)
                bumped = freq_lib.decay_to(
                    rep.score[safe], rep.last_touch[safe], step,
                    spec.freq_half_life,
                ) + 1.0
                dest = jnp.where(m, u, K)
                rep = dataclasses.replace(
                    rep,
                    # pinned replicated (see the apply_grads constraint)
                    score=dist_part.constrain(
                        rep.score.at[dest].set(bumped, mode="drop")
                    ),
                    last_touch=dist_part.constrain(
                        rep.last_touch.at[dest].set(step, mode="drop")
                    ),
                    step=step,
                )
            else:
                rep = dataclasses.replace(rep, step=step)
            slabs[sname] = dataclasses.replace(
                slab,
                full=full,
                cache=cache,
                routed_lanes=slab.routed_lanes + plan.routed[sname],
                rep=rep,
            )
        return CollectionState(slabs=slabs)

    # ----- differentiable read path -----------------------------------------

    # the exchange path: on a mesh this flatten + gather lowers to the row
    # all-to-all, so its contract covers the cross-shard wire too.
    @contract(max_sort_size=0)
    def gather(
        self,
        weights: Mapping[str, jnp.ndarray],
        addresses: Mapping[str, jnp.ndarray],
        fb: FeatureBatch,
    ) -> Dict[str, jnp.ndarray]:
        """Gather through the combined address space: each lane's combined
        address splits back into (owner, slot) and the routed leg is served
        as PER-SHARD LOCAL takes summed over the shard axis
        (:func:`_partitioned_take`) — the form GSPMD partitions as the row
        all-to-all; flattening the stacked arena and taking from the [S*cap]
        view instead lowers to an all-gather of the whole arena on every
        shard, which is what made gather cost scale with S.  Arena lanes
        (combined address >= S*cap) stay shard-local.  With
        ``exchange_codec`` the routed leg crosses ENCODED and decodes at the
        consumer (:func:`_encoded_exchange`); arena lanes never touch the
        wire, so they are always served raw.  Gradients flow back through
        the same maps, landing on the owning shard's slot / the replicated
        ``<slab>::rep`` leaf.  The routed leg runs under the
        ``shard.exchange`` named scope (its backward keeps the name), so a
        profiler trace tells the exchange from the rest of the lookup."""
        codec = get_codec(self.exchange_codec) if self.exchange_codec else None
        out = {}
        for f in fb.features:
            sname = self.table_slab[self.feature_to_table[f]][0]
            w = weights[sname]
            addr = addresses[f]
            flat = addr.reshape(-1)
            if sname not in self.cached_slabs:
                safe = jnp.where(flat >= 0, flat, w.shape[0])
                rows = jnp.take(w, safe, axis=0, mode="fill", fill_value=0)
            else:
                cap = w.shape[1]
                ncomb = w.shape[0] * cap
                rep = weights.get(sname + "::rep")
                K = rep.shape[0] if rep is not None else 0
                with jax.named_scope(SCOPE_EXCHANGE):
                    routed = (flat >= 0) & (flat < ncomb)
                    owner = jnp.where(routed, flat // cap, self.num_shards)
                    slot = jnp.where(routed, flat % cap, 0)
                    if codec is None:
                        rows = _partitioned_take(w, owner, slot)
                    else:
                        rows = _encoded_exchange(codec, w, owner, slot)
                if K:
                    # arena lanes stay shard-local and raw: overlay them on
                    # the routed leg (which returned zero rows for them)
                    loc = jnp.take(
                        rep, jnp.where(flat >= ncomb, flat - ncomb, K),
                        axis=0, mode="fill", fill_value=0,
                    )
                    rows = jnp.where((flat >= ncomb)[:, None], loc, rows)
            out[f] = rows.reshape(addr.shape + (rows.shape[-1],))
        return out

    def pool(self, rows, fb, combiner="sum", *, weights=None, addresses=None,
             use_pallas=False, max_bag=0):
        # the Pallas kernel reads the raw fp32 fast tier (arena concatenated
        # past the routed block); the exchange codec only shapes the
        # jnp.take route, which stays the exactness reference.
        if use_pallas and weights is not None:
            fused = {}
            for k, v in weights.items():
                if k.endswith("::rep"):
                    continue
                if k in self.cached_slabs:
                    v = v.reshape((-1,) + v.shape[2:])
                    rep = weights.get(k + "::rep")
                    if rep is not None and rep.shape[0]:
                        v = jnp.concatenate([v, rep], axis=0)
                fused[k] = v
            weights = fused
        return super().pool(rows, fb, combiner, weights=weights,
                            addresses=addresses, use_pallas=use_pallas,
                            max_bag=max_bag)

    def weights(self, state: CollectionState) -> Dict[str, jnp.ndarray]:
        """Parent surface plus one ``<slab>::rep`` leaf per replicated arena
        (omitted when K = 0, keeping the grads pytree — and with it the fp32
        trajectory — bit-identical to the pre-replication collection)."""
        out = super().weights(state)
        for sname in self.cached_slabs:
            rep = state.slabs[sname].rep
            if rep.rows.shape[0]:
                out[sname + "::rep"] = rep.rows
        return out

    @contract(donates=("state",), int_counters=INT_COUNTERS, max_sort_size=0)
    def apply_grads(
        self,
        state: CollectionState,
        grads: Mapping[str, jnp.ndarray],
        lr,
    ) -> CollectionState:
        """Parent SGD on the per-shard fast tiers, plus the replicated-slice
        update: a ``<slab>::rep`` grad is the SUM of its lanes' cotangents
        across the whole (data-parallel) batch — under jit on a mesh GSPMD
        materializes that sum as the all-reduce over the data axis plus a
        ``model``-axis ``psum`` wherever it sharded the lane dimension — so
        every shard applies the identical update and the arena copies never
        diverge (same mechanism that keeps the replicated MLPs in sync)."""
        state = super().apply_grads(state, grads, lr)
        slabs = dict(state.slabs)
        for sname in self.cached_slabs:
            g = grads.get(sname + "::rep")
            if g is None:
                continue
            slab = slabs[sname]
            rows = (slab.rep.rows - lr * g).astype(slab.rep.rows.dtype)
            # pin the arena replicated on the way out: without the constraint
            # GSPMD is free to shard the updated leaf over the mesh, and the
            # next step's in_shardings (replicated, see ``shard_specs``) then
            # reject the committed state.  Identity off-mesh.
            rows = dist_part.constrain(rows)
            slabs[sname] = dataclasses.replace(
                slab, rep=dataclasses.replace(slab.rep, rows=rows)
            )
        return CollectionState(slabs=slabs)

    def flush(self, state: CollectionState) -> CollectionState:
        slabs = dict(state.slabs)
        for sname, spec in self.cached_slabs.items():
            ccfg = self.shard_cache_config(spec)
            slab = slabs[sname]
            full, cache = jax.vmap(lambda f, c: cache_lib.flush(ccfg, f, c))(
                slab.full, slab.cache
            )
            K = slab.rep.rows.shape[0]
            if K:
                # the arena is authoritative for ranks < K: write it back to
                # the ranks' slow-tier homes AFTER the per-shard flush (a
                # never-planned warm copy of a replicated home may still sit
                # in some shard's arena; the rep row must win).
                vs = self.rows_per_shard(spec)
                homes = (
                    slab.rank_owner[:K] * vs + slab.rank_local[:K]
                ).astype(jnp.int32)
                flat = transmitter.write_rows(
                    {"weight": slab.rep.rows}, flat_store(full), homes,
                    jnp.ones((K,), bool), buffer_rows=spec.buffer_rows,
                )
                full = _stack_store(flat, self.num_shards, vs)
            slabs[sname] = dataclasses.replace(slab, full=full, cache=cache)
        return CollectionState(slabs=slabs)

    # ----- adaptive frequency refresh ---------------------------------------

    def refresh(
        self,
        state: CollectionState,
        cfg: Optional[refresh_lib.RefreshConfig] = None,
        writeback: bool = True,
    ) -> Tuple[CollectionState, refresh_lib.RefreshReport]:
        """Sharded re-ranking refresh (see ``EmbeddingCollection.refresh``).

        The incremental permutation is planned GLOBALLY from the merged
        per-shard decayed counters, then applied as content exchanges between
        the swapped ranks' fixed ``(owner, local)`` homes — the traffic
        balance ``assign_devices`` placed on the hot homes is inherited by
        the newly-hot rows.  Cross-shard exchanges are metered by
        ``cfg.exchange_budget`` (rows per refresh; excess pairs defer to the
        next pass).  With one shard the pass is bit-identical to the
        unsharded refresh."""
        cfg = cfg or refresh_lib.RefreshConfig()
        slabs = dict(state.slabs)
        report = refresh_lib.RefreshReport()
        for sname, spec in self.cached_slabs.items():
            slabs[sname], stats = refresh_lib.refresh_sharded_slab(
                self.shard_cache_config(spec, writeback=writeback),
                slabs[sname], cfg, writeback=writeback,
            )
            if cfg.rebalance_threshold is not None:
                slabs[sname], rstats = self._maybe_rebalance(
                    sname, spec, slabs[sname], cfg, writeback
                )
                stats = {**stats, **rstats}
            report.add(sname, stats)
        return CollectionState(slabs=slabs), report

    def _maybe_rebalance(
        self,
        sname: str,
        spec: _CachedSlabSpec,
        slab: ShardedSlab,
        cfg: refresh_lib.RefreshConfig,
        writeback: bool,
    ) -> Tuple[ShardedSlab, Dict[str, Any]]:
        """Traffic-aware re-homing (tentpole front c): measure the LIVE
        routed imbalance from the per-shard trackers' decayed scores; when it
        exceeds ``cfg.rebalance_threshold``, re-run ``assign_devices`` on the
        live scores and permute every rank's slow-tier home to its new
        ``(owner, local)`` — pure data movement (the encoded payload moves
        bit-exact), so the fp32 loss trajectory is unchanged while future
        exchange traffic follows the refreshed placement.  Planning is
        host-side numpy like init-time placement."""
        S = self.num_shards
        vs = self.rows_per_shard(spec)
        K = int(slab.rep.rows.shape[0])
        owner = np.asarray(jax.device_get(slab.rank_owner))
        local = np.asarray(jax.device_get(slab.rank_local))
        tr = slab.cache.tracker
        steps = np.asarray(jax.device_get(slab.cache.step), np.float64)
        local_scores = freq_lib.decayed_scores(
            np.asarray(jax.device_get(tr.score)),
            np.asarray(jax.device_get(tr.last_touch)),
            steps[:, None],
            spec.freq_half_life,
        )
        scores = local_scores[owner, local]
        scores[:K] = 0.0  # replicated ranks carry no routed traffic
        load = np.zeros((S,), np.float64)
        np.add.at(load, owner[K:], scores[K:])
        mean = float(load.mean())
        imb = float(load.max() / mean) if mean > 0 else 1.0
        stats: Dict[str, Any] = {"rebalance_moves": 0, "rebalance_imbalance": imb}
        if imb <= float(cfg.rebalance_threshold):
            return slab, stats
        assign = PlacementPlanner.assign_devices(
            spec.vocab, S, scores, replicate_top_k=K
        )
        new_flat = assign.owner.astype(np.int64) * vs + assign.local.astype(np.int64)
        old_flat = owner.astype(np.int64) * vs + local
        moved = int(np.sum(new_flat != old_flat))
        if not moved:
            return slab, stats
        # gather map: the leaf row that must land at each new flat home.
        src_for_dest = np.arange(S * vs, dtype=np.int64)
        src_for_dest[new_flat] = old_flat
        full, cache = refresh_lib._apply_rebalance(
            slab.full, slab.cache,
            jnp.asarray(src_for_dest, jnp.int32),
            buffer_rows=spec.buffer_rows, writeback=writeback,
        )
        ccfg = self.shard_cache_config(spec, writeback=writeback)
        full, cache = jax.vmap(lambda f, c: cache_lib.warmup(ccfg, f, c))(
            full, cache
        )
        self.assignments[sname] = assign
        stats["rebalance_moves"] = moved
        stats["rebalance_imbalance"] = imb
        return (
            dataclasses.replace(
                slab, full=full, cache=cache,
                rank_owner=jnp.asarray(assign.owner, jnp.int32),
                rank_local=jnp.asarray(assign.local, jnp.int32),
            ),
            stats,
        )

    # ----- oracles / bulk reads ---------------------------------------------

    def _rank_rows(self, slab: ShardedSlab, rank: jnp.ndarray) -> jnp.ndarray:
        """Decoded slow-tier rows for freq ranks (-1 lanes -> zero rows).
        Replicated ranks (< K) read the arena directly — it is authoritative
        (the slow-tier home only re-syncs at flush/refresh)."""
        vs = slab.full.data["weight"].shape[1]
        ok = rank >= 0
        owner = slab.rank_owner.at[jnp.where(ok, rank, 0)].get(mode="fill", fill_value=-1)
        local = slab.rank_local.at[jnp.where(ok, rank, 0)].get(mode="fill", fill_value=-1)
        flat = jnp.where(ok & (owner >= 0), owner * vs + local, -1)
        rows = _read_full_rows(flat_store(slab.full), flat)
        K = slab.rep.rows.shape[0]
        if K:
            in_rep = ok & (rank < K)
            rep_rows = jnp.take(
                slab.rep.rows, jnp.where(in_rep, rank, K),
                axis=0, mode="fill", fill_value=0,
            )
            rows = jnp.where(in_rep[:, None], rep_rows, rows)
        return rows

    def full_lookup(
        self, state: CollectionState, table: str, local_ids: jnp.ndarray
    ) -> jnp.ndarray:
        sname, off = self.table_slab[table]
        if sname in self.device_slabs:
            return super().full_lookup(state, table, local_ids)
        slab = state.slabs[sname]
        valid = local_ids >= 0
        rank = slab.idx_map.at[jnp.where(valid, local_ids + off, 0)].get(
            mode="fill", fill_value=-1
        )
        return self._rank_rows(slab, jnp.where(valid, rank, -1))

    def dense_reference(
        self, state: CollectionState, fb: FeatureBatch
    ) -> Dict[str, jnp.ndarray]:
        out = {}
        for f in fb.features:
            tname = self.feature_to_table[f]
            sname, off = self.table_slab[tname]
            ids = fb.ids[f]
            flat = ids.reshape(-1)
            if sname in self.device_slabs:
                w = state.slabs[sname].weight
                safe = jnp.where(flat >= 0, flat, w.shape[0])
                rows = jnp.take(w, safe, axis=0, mode="fill", fill_value=0)
            else:
                slab = state.slabs[sname]
                r = slab.idx_map.at[jnp.where(flat >= 0, flat + off, 0)].get(
                    mode="fill", fill_value=-1
                )
                rows = self._rank_rows(slab, jnp.where(flat >= 0, r, -1))
            out[f] = rows.reshape(ids.shape + (rows.shape[-1],))
        return out

    # ----- telemetry / accounting -------------------------------------------

    # jit-adjacent: traced inside every sharded compute_step — the int-counter
    # contract pins the exchange/refresh counter families the obs hub
    # reconstructs, and max_sort_size=0 asserts telemetry never adds a sort.
    @contract(int_counters=METRICS_INT_COUNTERS, max_sort_size=0)
    def metrics(
        self, state: CollectionState, writeback: bool = True
    ) -> Dict[str, jnp.ndarray]:
        """Unsharded telemetry (counters sum over shards) plus the exchange
        accounting: ``exchange_routed_lanes`` / ``exchange_lane_bytes`` are
        per-slab cumulative id lanes routed through the bucketize exchange
        and the per-lane payload (4 B id out + one fast-tier row back; the
        row leg prices at the exchange codec's encoded width) — exact bytes
        via ``repro.obs.hub.ExactCounter.observe(..., unit=...)``.  The two
        legs are also split out — ``exchange_id_lane_bytes`` /
        ``exchange_row_lane_bytes`` per slab and ``exchange_id_bytes`` /
        ``exchange_row_bytes`` float32 totals — and
        ``exchange_per_shard_lanes`` is the [S] routed-lane histogram summed
        over slabs.  Of the payload an expected (S-1)/S fraction crosses
        devices on an S-shard mesh.

        ``shard_imbalance`` is LIVE: max/mean of the per-shard decayed
        frequency mass (``freq.decay_to`` over the trackers at the current
        step), so it follows traffic drift instead of freezing at the
        init-time placement counts.  The cumulative-lane variant survives as
        ``shard_imbalance_routed``.

        Telemetry caveat (same as hits/misses): under pipelined group
        scheduling only group leaders run a plan, so routed lanes sample the
        leaders' batches."""
        out = super().metrics(state, writeback=writeback)
        lanes: Dict[str, jnp.ndarray] = {}
        lane_bytes: Dict[str, jnp.ndarray] = {}
        id_lane_bytes: Dict[str, jnp.ndarray] = {}
        row_lane_bytes: Dict[str, jnp.ndarray] = {}
        id_bytes = jnp.zeros((), jnp.float32)
        row_bytes = jnp.zeros((), jnp.float32)
        per_shard = jnp.zeros((self.num_shards,), jnp.int32)
        live = jnp.zeros((self.num_shards,), jnp.float32)
        for sname, spec in self.cached_slabs.items():
            slab = state.slabs[sname]
            n = jnp.sum(slab.routed_lanes)
            lanes[sname] = n.astype(jnp.int32)
            if self.exchange_codec:
                rb = int(get_codec(self.exchange_codec).row_bytes(
                    (spec.dim,), spec.dtype
                ))
            else:
                rb = spec.dim * jnp.dtype(spec.dtype).itemsize
            lane_bytes[sname] = jnp.asarray(4 + rb, jnp.int32)
            id_lane_bytes[sname] = jnp.asarray(4, jnp.int32)
            row_lane_bytes[sname] = jnp.asarray(rb, jnp.int32)
            id_bytes = id_bytes + n.astype(jnp.float32) * 4
            row_bytes = row_bytes + n.astype(jnp.float32) * rb
            per_shard = per_shard + slab.routed_lanes
            tr = slab.cache.tracker
            live = live + jnp.sum(
                freq_lib.decay_to(
                    tr.score, tr.last_touch, slab.cache.step[:, None],
                    spec.freq_half_life,
                ),
                axis=1,
            )
        tot = jnp.sum(per_shard)
        mean = tot.astype(jnp.float32) / self.num_shards
        tot_live = jnp.sum(live)
        out["exchange_routed_lanes"] = lanes
        out["exchange_lane_bytes"] = lane_bytes
        out["exchange_id_lane_bytes"] = id_lane_bytes
        out["exchange_row_lane_bytes"] = row_lane_bytes
        out["exchange_id_bytes"] = id_bytes
        out["exchange_row_bytes"] = row_bytes
        out["exchange_bytes"] = id_bytes + row_bytes
        out["exchange_per_shard_lanes"] = per_shard
        out["shard_imbalance"] = jnp.where(
            tot_live > 0,
            jnp.max(live) / jnp.maximum(tot_live / self.num_shards, 1e-9),
            1.0,
        )
        out["shard_imbalance_routed"] = jnp.where(
            tot > 0, jnp.max(per_shard).astype(jnp.float32) / jnp.maximum(mean, 1e-9), 1.0
        )
        return out

    def device_bytes(self) -> Dict[str, int]:
        """Footprint under the sharded layout.  ``device_total`` counts one
        REPLICA of the shared read-only arrays (DEVICE tables, id routing
        maps) plus the summed stacked arrays plus S copies of every
        replicated hot-row arena — each mesh device materializes its own
        ``ShardedSlab.rep``, so charging it once under-counted real HBM by
        ``(S-1) * rep_arena`` bytes.  ``device_per_shard`` is what one mesh
        device actually holds — the budget-relevant number.  Tiered arenas
        (``arena_precision`` != fp32) charge the encoded tail + sideband via
        :func:`tiered_arena_bytes`; ``arena_bytes_saved`` is the fast-tier
        HBM the tiering freed versus an all-fp32 arena."""
        S = self.num_shards
        per_slab: Dict[str, int] = {}
        replicated = 0
        stacked = 0
        rep_arenas = 0
        slow = slow_fp32 = 0
        fast_fp32 = fast_actual = 0
        for name, t in self.device_slabs.items():
            per_slab[name] = t.full_bytes
            replicated += t.full_bytes
        for sname, spec in self.cached_slabs.items():
            item = jnp.dtype(spec.dtype).itemsize
            vs = self.rows_per_shard(spec)
            cap = self.shard_capacity(spec)
            ccfg = self.shard_cache_config(spec)
            w = tiered_arena_bytes(
                cap, ccfg.head_capacity, spec.dim, spec.dtype,
                ccfg.arena_precision,
            )
            fast_fp32 += S * cap * spec.dim * item
            fast_actual += S * w
            # per shard: arena (+ sideband) + slot bookkeeping + row_to_slot
            # + tracker
            stack = S * (w + cap * 4 * 3 + vs * 4 * 3)
            rep = spec.vocab * 4 * 3  # idx_map + rank_owner + rank_local
            K = min(self.replicate_top_k, spec.vocab)
            # replicated arena: rows + its tracker (score, last_touch) + step
            # — PER DEVICE (every shard holds a full copy; fp32 by design)
            rep_arena = K * (spec.dim * item + 4 + 4) + 4
            per_slab[sname] = stack + rep + S * rep_arena
            stacked += stack
            replicated += rep
            rep_arenas += rep_arena
            codec = get_codec(self._slab_codec(sname))
            slow += S * vs * codec.row_bytes((spec.dim,), spec.dtype)
            slow_fp32 += S * vs * spec.dim * item
        return {
            "device_total": replicated + stacked + S * rep_arenas,
            "device_per_shard": replicated + rep_arenas + stacked // max(S, 1),
            "slow_tier_bytes": slow,
            "host_bytes_saved": slow_fp32 - slow,
            "arena_bytes_saved": fast_fp32 - fast_actual,
            "per_slab": per_slab,
            "budget_bytes": self.plan.budget_bytes,
        }

    # ----- sharding ----------------------------------------------------------

    def shard_specs(self, mode: str = "shard", model_axis: Optional[str] = None):
        """PartitionSpec pytree matching the sharded ``CollectionState``:
        every stacked leaf splits its leading shard dim over the mesh's
        ``model`` axis, the id-routing maps and DEVICE tables replicate
        (DEVICE tables train data-parallel with the MLPs).  ``mode`` is
        accepted for drop-in compatibility with the unsharded signature but
        the layout is fixed by the shard structure."""
        from jax.sharding import PartitionSpec as P

        axis = model_axis or self.model_axis
        slabs: Dict[str, Any] = {}
        for name in self.device_slabs:
            slabs[name] = DeviceSlab(weight=P(None, None))
        for sname, spec in self.cached_slabs.items():
            like = {"weight": jax.ShapeDtypeStruct((spec.vocab, spec.dim), spec.dtype)}
            arena_codec = self._slab_arena_codec(sname)
            if arena_codec == "fp32":
                cached_rows: Any = {"weight": P(axis, None, None)}
            else:
                # tiered arena: every tier's leaves carry the leading [S]
                # shard dim, sideband included (it is per-shard cache state,
                # unlike the host-store sideband which follows the row split)
                cap = self.shard_capacity(spec)
                cached_rows = ArenaStore.spec_like(
                    {"weight": jax.ShapeDtypeStruct((cap, spec.dim), spec.dtype)},
                    P(axis, None, None),
                    P(axis, None, None),
                    codec=arena_codec,
                )
            slabs[sname] = ShardedSlab(
                full=HostStore.spec_like(
                    like,
                    {"weight": P(axis, None, None)},
                    P(axis, None, None),
                    codec=self._slab_codec(sname),
                ),
                cache=cache_lib.CacheState(
                    cached_rows=cached_rows,
                    slot_to_row=P(axis, None),
                    row_to_slot=P(axis, None),
                    last_used=P(axis, None),
                    use_count=P(axis, None),
                    step=P(axis),
                    hits=P(axis),
                    misses=P(axis),
                    uniq_rows=P(axis),
                    move_rounds=P(axis),
                    evictions=P(axis),
                    uniq_overflows=P(axis),
                    tier_promotions=P(axis),
                    tier_demotions=P(axis),
                    tracker=freq_lib.tracker_spec(P, axis=axis),
                ),
                idx_map=P(None),
                rank_owner=P(None),
                rank_local=P(None),
                routed_lanes=P(axis),
                rep=RepArena(
                    rows=P(None, None),  # replicated on every shard
                    score=P(None),
                    last_touch=P(None),
                    step=P(),
                ),
            )
        return CollectionState(slabs=slabs)
