"""Planner-driven multi-table embeddings behind one keyed-feature API.

The paper manages ONE concatenated, frequency-ordered table through a single
software cache.  Production DLRMs hold dozens of tables whose size and skew
differ by orders of magnitude; per-table statistical placement across memory
tiers beats any one-size-fits-all policy (RecShard, arXiv 2201.10095), and
per-table tiering composes with cache-backed embeddings (arXiv 2010.11305).
This module generalizes the paper's design to that setting:

  * ``TableConfig``      — one logical table (vocab, dim, per-table cache
                           knobs, optional placement override).
  * ``FeatureBatch``     — keyed ids (feature name -> id array, -1 = padding),
                           the KJT analogue; ``from_onehot`` / ``from_bags``
                           constructors replace hand-flattened id vectors.
  * ``PlacementPlanner`` — takes the tables, optional frequency stats, and a
                           device-memory budget; assigns each table DEVICE
                           (fully resident, no cache bookkeeping), CACHED
                           (the paper's two-tier cache, per-table ratio and
                           policy), or GROUPED (many small tables share one
                           cache arena — the paper's original layout is the
                           all-GROUPED special case).
  * ``EmbeddingCollection`` — owns N tables under a plan and exposes the
                           collection-level surface shared by train and
                           serve: ``init`` / ``prepare`` / ``weights`` /
                           ``gather`` / ``pool`` / ``apply_grads`` /
                           ``flush`` / ``shard_specs`` / ``device_bytes``.

Everything rides on the existing machinery: ``core.cache`` (Algorithm 1),
``core.freq`` (static frequency module), ``core.transmitter``.  The cache
remains pure data movement, so a mixed-placement collection is bit-identical
to a dense reference lookup (tested property).

Training protocol (mirrors ``cached_embedding``, per collection):

    emb_state, slots = coll.prepare(emb_state, fb)       # non-diff bookkeeping
    def loss_fn(params, emb_w):                          # emb_w = coll.weights(state)
        rows = coll.gather(emb_w, slots, fb)             # diff wrt emb_w
        ...
    grads wrt (params, emb_w) ...
    emb_state = coll.apply_grads(emb_state, grads_emb, lr)
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.contracts import INT_COUNTERS, contract
from repro.core import cache as cache_lib
from repro.core import freq as freq_lib
from repro.core import refresh as refresh_lib
from repro.core.policies import Policy
from repro.store import (
    ArenaStore,
    HostStore,
    PrecisionPolicy,
    SlabGeometry,
    get_codec,
    tiered_arena_bytes,
)

__all__ = [
    "Placement",
    "TableConfig",
    "FeatureBatch",
    "TablePlacement",
    "PlacementPlan",
    "PlacementPlanner",
    "ShardAssignment",
    "EmbeddingCollection",
    "DeviceSlab",
    "CachedSlab",
    "CollectionState",
    "CollectionPlan",
]

SHARED_ARENA = "__shared__"

# The exact-counter contract of a ``metrics()`` dict: every per-slab
# cumulative counter (and its static per-unit byte size) that
# ``repro.obs.hub.MetricsHub.observe_embedding_metrics`` reconstructs
# host-side must leave jit as int32/uint32 — a float cast anywhere in between
# silently reintroduces the 2^24 resolution drift the pattern exists to kill.
METRICS_INT_COUNTERS: Tuple[str, ...] = (
    r"\['slab_(hits|misses|uniq_rows|refresh_swaps|refresh_rows"
    r"|tier_promotions|tier_demotions)'\]",
    r"\['host_(moved_rows|row_bytes)'\]",
    r"\['exchange_(routed_lanes|lane_bytes|id_lane_bytes|row_lane_bytes"
    r"|per_shard_lanes)'\]",
    r"\['(cache_misses|cache_evictions|uniq_overflows|refresh_swaps"
    r"|refresh_rows_moved)'\]$",
)


class Placement(enum.Enum):
    DEVICE = "device"  # full table resident on device, no cache bookkeeping
    CACHED = "cached"  # paper two-tier cache, table's own ratio/policy
    GROUPED = "grouped"  # shares the collection-wide cache arena


@dataclasses.dataclass(frozen=True)
class TableConfig:
    """One logical embedding table.

    ``feature_names`` lists the FeatureBatch keys served by this table
    (several features may share a table: e.g. ``hist_items`` and
    ``target_item`` both hit the items table); defaults to ``(name,)``.
    ``ids_per_step`` is the static number of id lanes the table's features
    contribute per step — it sizes the per-step unique buffer and the
    minimum cache capacity, exactly like the paper's strict buffer limit.
    """

    name: str
    vocab: int
    dim: int
    ids_per_step: int
    feature_names: Tuple[str, ...] = ()
    cache_ratio: float = 0.015  # paper default 1.5 %
    policy: Policy = Policy.FREQ_LFU
    buffer_rows: int = 65536
    max_unique_per_step: int = 0
    protect_via_inverse: bool = True
    dtype: Any = jnp.float32
    placement: Optional[Placement] = None  # planner override
    # host-tier storage codec for this table when CACHED: "fp32" (bit-exact
    # default), "fp16", "int8" (row-wise scale/zero-point), or "auto"
    # (PrecisionPolicy picks from frequency coverage at init).  None defers
    # to the planner / collection-wide setting.  DEVICE tables have no host
    # tier; GROUPED tables share the arena's codec.
    host_precision: Optional[str] = None
    # device-arena tail codec for this table when CACHED: "fp32" (raw arena,
    # bit-identical default), "fp16"/"int8" (frequency-tiered ArenaStore — an
    # fp32 head over the hottest slots, encoded tail for colder residents), or
    # "auto" (PrecisionPolicy.choose_arena picks from the head's share of
    # resident traffic at init).  None defers to the planner / collection-wide
    # setting.  DEVICE tables have no arena; GROUPED tables share the arena's.
    arena_precision: Optional[str] = None
    # decay half-life (steps) of the online frequency tracker — how fast the
    # adaptive engine forgets old traffic; match it to the expected drift
    # timescale (a refresh can only promote a newly-hot row once its fresh
    # mass outweighs the old hot set's decayed mass).  GROUPED tables use
    # the arena's value.
    freq_half_life: int = 1024
    # cache hot-path routing (see CacheConfig): bounded-top-K/fused planning
    # kernels and chunk-granularity host staging.  GROUPED tables use the
    # arena's values.
    use_pallas_plan: bool = False
    chunk_rows: int = 0

    @property
    def features(self) -> Tuple[str, ...]:
        return self.feature_names or (self.name,)

    @property
    def full_bytes(self) -> int:
        return self.vocab * self.dim * jnp.dtype(self.dtype).itemsize

    def unique_size(self, ids_per_step: Optional[int] = None) -> int:
        k = min(ids_per_step or self.ids_per_step, self.vocab)
        if self.max_unique_per_step:
            k = min(k, self.max_unique_per_step)
        return k


# ---------------------------------------------------------------------------
# FeatureBatch — the keyed-ids input type (KJT analogue)
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class FeatureBatch:
    """Keyed feature ids: name -> int32 id array (any shape, -1 = padding).

    For pooled ("bag") features, ``segments[name]`` assigns each flat lane to
    an output row (``num_segments`` rows total); ``EmbeddingCollection.pool``
    runs the segment reduction after the cached gather.
    """

    ids: Dict[str, jnp.ndarray]
    segments: Dict[str, jnp.ndarray] = dataclasses.field(default_factory=dict)
    num_segments: int = dataclasses.field(default=0, metadata=dict(static=True))

    @classmethod
    def from_onehot(cls, names: Sequence[str], id_matrix: jnp.ndarray) -> "FeatureBatch":
        """Criteo-style [batch, fields] matrix -> one [batch] feature per name."""
        assert id_matrix.ndim == 2 and id_matrix.shape[1] == len(names)
        return cls(ids={n: id_matrix[:, j].astype(jnp.int32) for j, n in enumerate(names)})

    @classmethod
    def from_bags(
        cls,
        bags: Mapping[str, Tuple[jnp.ndarray, jnp.ndarray]],
        num_segments: int,
        extra_onehot: Optional[Mapping[str, jnp.ndarray]] = None,
    ) -> "FeatureBatch":
        """Ragged multi-hot bags: name -> (flat_ids, segment_ids)."""
        ids = {n: flat.astype(jnp.int32) for n, (flat, _) in bags.items()}
        segments = {n: seg.astype(jnp.int32) for n, (_, seg) in bags.items()}
        if extra_onehot:
            ids.update({n: v.astype(jnp.int32) for n, v in extra_onehot.items()})
        return cls(ids=ids, segments=segments, num_segments=num_segments)

    @property
    def features(self) -> Tuple[str, ...]:
        return tuple(self.ids)


# ---------------------------------------------------------------------------
# placement plan + planner
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TablePlacement:
    placement: Placement
    # effective ratio for CACHED/GROUPED tables; None = use the table's own.
    # 0.0 is meaningful (planner shrunk to the exactness floor), hence Optional.
    cache_ratio: Optional[float] = None
    # host-tier codec ("fp32"/"fp16"/"int8"/"auto"); None = table's own / fp32
    host_precision: Optional[str] = None
    # device-arena tail codec ("fp32"/"fp16"/"int8"/"auto"); None = table's own
    arena_precision: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class ArenaConfig:
    """Knobs of the shared GROUPED cache arena."""

    cache_ratio: float = 0.015
    policy: Policy = Policy.FREQ_LFU
    buffer_rows: int = 65536
    max_unique_per_step: int = 0
    protect_via_inverse: bool = True
    host_precision: str = "fp32"  # the arena's host-tier codec (shared table)
    arena_precision: str = "fp32"  # the arena's device-tail codec (tiered arena)
    arena_head_ratio: float = 0.25  # fp32 head fraction when the arena is tiered
    freq_half_life: int = 1024  # online-tracker decay (see TableConfig)
    use_pallas_plan: bool = False  # bounded-top-K fused planning (CacheConfig)
    chunk_rows: int = 0  # chunk-granularity host staging (CacheConfig)


@dataclasses.dataclass(frozen=True)
class PlacementPlan:
    placements: Dict[str, TablePlacement]
    arena: ArenaConfig = ArenaConfig()
    budget_bytes: Optional[int] = None

    def placement(self, name: str) -> Placement:
        return self.placements[name].placement

    @classmethod
    def single_arena(
        cls,
        tables: Sequence[TableConfig],
        cache_ratio: float = 0.015,
        policy: Policy = Policy.FREQ_LFU,
        buffer_rows: int = 65536,
        max_unique_per_step: int = 0,
        protect_via_inverse: bool = True,
        host_precision: str = "fp32",
        arena_precision: str = "fp32",
        arena_head_ratio: float = 0.25,
        freq_half_life: int = 1024,
        use_pallas_plan: bool = False,
        chunk_rows: int = 0,
    ) -> "PlacementPlan":
        """The paper's layout: every table GROUPED into one shared cache."""
        return cls(
            placements={
                t.name: TablePlacement(
                    Placement.GROUPED,
                    cache_ratio,
                    host_precision=host_precision,
                    arena_precision=arena_precision,
                )
                for t in tables
            },
            arena=ArenaConfig(
                cache_ratio=cache_ratio,
                policy=policy,
                buffer_rows=buffer_rows,
                max_unique_per_step=max_unique_per_step,
                protect_via_inverse=protect_via_inverse,
                host_precision=host_precision,
                arena_precision=arena_precision,
                arena_head_ratio=arena_head_ratio,
                freq_half_life=freq_half_life,
                use_pallas_plan=use_pallas_plan,
                chunk_rows=chunk_rows,
            ),
            budget_bytes=None,
        )

    def summary(self) -> Dict[str, str]:
        out = {}
        for n, p in self.placements.items():
            s = f"{p.placement.value}"
            if p.placement is not Placement.DEVICE:
                s += f"@{p.cache_ratio:.4f}" if p.cache_ratio is not None else ""
                hp = p.host_precision or "fp32"
                if hp != "fp32":
                    s += f":{hp}"  # host-tier codec (bytes saved vs fp32)
                ap = p.arena_precision or "fp32"
                if ap != "fp32":
                    s += f"/arena:{ap}"  # device-arena tail codec (tiered)
            out[n] = s
        return out


@dataclasses.dataclass(frozen=True)
class ShardAssignment:
    """Frequency-driven device assignment of one cached slab's rows.

    Maps every frequency-ranked row of a slab to a ``model``-axis shard so
    the expected hot-row traffic is balanced across devices (RecShard,
    arXiv 2201.10095: the statistics a placement pass needs are exactly the
    frequency counts the planner already collects).  ``owner``/``local`` are
    host-side numpy; the sharded collection places them on device next to
    ``idx_map`` so id routing is one extra gather.
    """

    num_shards: int
    owner: np.ndarray  # int32 [vocab] freq rank -> owning shard
    local: np.ndarray  # int32 [vocab] freq rank -> row index on the owner
    shard_rows: np.ndarray  # int64 [S] real rows per shard (pads excluded)
    shard_load: np.ndarray  # float64 [S] expected ROUTED traffic per shard
    # hot-row replication head: ranks < replicate_top_k live in a small arena
    # replicated on every shard, so their lookups never enter the id/row
    # exchange.  They still get (owner, local) slow-tier homes — appended
    # AFTER the routed ranks, so they land at each shard's coldest local
    # positions and never occupy warm cache slots — and carry zero routed
    # load (``shard_load``/``imbalance`` meter only what actually routes).
    replicate_top_k: int = 0

    @property
    def rows_per_shard(self) -> int:
        """Uniform local vocab (stacked [S, rows_per_shard, ...] layout);
        shards with fewer real rows pad with never-referenced zero rows."""
        return -(-int(self.owner.shape[0]) // self.num_shards)

    def imbalance(self) -> float:
        """max/mean expected routed traffic across shards (1.0 = even)."""
        mean = float(np.mean(self.shard_load))
        return float(np.max(self.shard_load)) / mean if mean > 0 else 1.0


class PlacementPlanner:
    """Assign each table a memory tier under an explicit device-byte budget.

    Heuristic (RecShard-flavoured, deterministic):
      1. honor explicit ``TableConfig.placement`` overrides;
      2. greedily promote the remaining tables to DEVICE, hottest-per-byte
         first (access frequency per byte when counts are given, smallest
         table first otherwise), while the full table fits the remaining
         budget — small hot tables stop paying any cache bookkeeping;
      3. tables with vocab below ``group_below_rows`` share the GROUPED
         arena (one cache, one set of index arrays, amortized bookkeeping);
      4. everything else is CACHED with its own ratio/policy; if the summed
         fast tiers overflow the remaining budget, ratios are scaled down
         uniformly, floored at one batch's unique rows (exactness floor).

    Host precision: the planner also stamps each CACHED/GROUPED table's
    host-tier codec (``TablePlacement.host_precision``): the table's own
    ``TableConfig.host_precision`` wins, then the planner-wide
    ``host_precision`` default.  ``"auto"`` defers the choice to
    ``repro.store.PrecisionPolicy`` at ``EmbeddingCollection.init`` time,
    when frequency counts are available.
    """

    def __init__(
        self,
        budget_bytes: int,
        group_below_rows: int = 0,
        arena: Optional[ArenaConfig] = None,
        host_precision: Optional[str] = None,
        arena_precision: Optional[str] = None,
        arena_head_ratio: float = 0.25,
    ):
        self.budget_bytes = int(budget_bytes)
        self.group_below_rows = int(group_below_rows)
        self.arena = arena if arena is not None else ArenaConfig()
        self.host_precision = host_precision
        self.arena_precision = arena_precision
        self.arena_head_ratio = float(arena_head_ratio)

    @staticmethod
    def _tiered_weight_bytes(
        capacity: int, dim: int, dtype, arena_precision: Optional[str], head_ratio: float
    ) -> int:
        """Weight-leaf footprint of one arena at ``arena_precision`` — fp32
        head + encoded tail payload + tail sideband (the sideband bytes are
        part of the budget: they are device-resident like the payload).
        "auto" is budgeted at the policy's no-stats pick, matching what init
        resolves when no counts arrive."""
        ap = arena_precision or "fp32"
        if ap == "auto":
            ap = PrecisionPolicy().no_stats
        if ap == "fp32":
            head = capacity
        else:
            head = min(capacity, max(1, int(round(head_ratio * capacity))))
        return tiered_arena_bytes(capacity, head, dim, dtype, ap)

    def _table_arena_precision(self, t: TableConfig) -> Optional[str]:
        return t.arena_precision or self.arena_precision

    def _fast_bytes(self, t: TableConfig, ratio: float) -> int:
        """Device footprint of one CACHED table at ``ratio`` (weights + per-slot
        bookkeeping + the vocab-sized index arrays + the online frequency
        tracker's decayed counters)."""
        cap = min(max(int(ratio * t.vocab), t.unique_size()), t.vocab)
        w = self._tiered_weight_bytes(
            cap, t.dim, t.dtype, self._table_arena_precision(t), self.arena_head_ratio
        )
        # vocab-sized: row_to_slot + idx_map + tracker score + last_touch
        return w + cap * 4 * 3 + t.vocab * 4 * 4

    def _arena_bytes(self, grouped: Sequence[TableConfig]) -> int:
        if not grouped:
            return 0
        gvocab = sum(t.vocab for t in grouped)
        gids = sum(t.ids_per_step for t in grouped)
        gcap = min(max(int(self.arena.cache_ratio * gvocab), min(gids, gvocab)), gvocab)
        w = self._tiered_weight_bytes(
            gcap,
            grouped[0].dim,
            grouped[0].dtype,
            self.arena_precision or self.arena.arena_precision,
            self.arena.arena_head_ratio,
        )
        return w + gcap * 4 * 3 + gvocab * 4 * 4

    def plan(
        self,
        tables: Sequence[TableConfig],
        counts: Optional[Mapping[str, np.ndarray]] = None,
    ) -> PlacementPlan:
        placements: Dict[str, TablePlacement] = {}
        device_bytes = 0

        undecided: List[TableConfig] = []
        grouped: List[TableConfig] = []
        solo: List[TableConfig] = []
        for t in tables:
            if t.placement is Placement.DEVICE:
                placements[t.name] = TablePlacement(Placement.DEVICE)
                device_bytes += t.full_bytes
            elif t.placement is Placement.GROUPED:
                grouped.append(t)
            elif t.placement is Placement.CACHED:
                solo.append(t)
            elif t.vocab < self.group_below_rows:
                grouped.append(t)  # many tiny tables share the arena by policy
            else:
                undecided.append(t)

        def heat_per_byte(t: TableConfig) -> float:
            if counts is not None and t.name in counts:
                return float(np.sum(counts[t.name])) / max(t.full_bytes, 1)
            return 1.0 / max(t.full_bytes, 1)  # no stats: smallest first

        # greedy DEVICE promotion, hottest-per-byte first.  A promotion is
        # only taken if the rest of the plan stays feasible in the worst case
        # (every remaining cached table shrunk to its exactness floor).
        undecided.sort(key=lambda t: (-heat_per_byte(t), t.name))
        for i, t in enumerate(undecided):
            rest = undecided[i + 1 :] + solo
            floor_rest = sum(self._fast_bytes(r, 0.0) for r in rest)
            cost = device_bytes + t.full_bytes + floor_rest + self._arena_bytes(grouped)
            if cost <= self.budget_bytes:
                placements[t.name] = TablePlacement(Placement.DEVICE)
                device_bytes += t.full_bytes
            else:
                solo.append(t)

        def host_prec(t: TableConfig) -> Optional[str]:
            return t.host_precision or self.host_precision

        # the planner-wide defaults also govern the shared arena (the arena's
        # own fields keep their fp32 defaults otherwise); the returned plan's
        # ArenaConfig carries the resolved codecs so the collection's arena
        # slab agrees with the GROUPED placements.
        arena = dataclasses.replace(
            self.arena,
            host_precision=self.host_precision or self.arena.host_precision,
            arena_precision=self.arena_precision or self.arena.arena_precision,
        )
        for t in grouped:
            placements[t.name] = TablePlacement(
                Placement.GROUPED,
                arena.cache_ratio,
                host_precision=arena.host_precision,
                arena_precision=arena.arena_precision,
            )

        # fit solo cache ratios into what is left (index arrays included)
        remaining = self.budget_bytes - device_bytes - self._arena_bytes(grouped)
        want = sum(self._fast_bytes(t, t.cache_ratio) for t in solo)
        scale = 1.0
        if solo and want > remaining:
            floor = sum(self._fast_bytes(t, 0.0) for t in solo)
            if floor > remaining:
                raise ValueError(
                    f"budget {self.budget_bytes} cannot hold even one batch's unique "
                    f"rows per cached table (need >= {self.budget_bytes - remaining + floor})"
                )
            # weight bytes scale ~linearly with ratio; solve for the shrink
            scale = max(0.0, (remaining - floor) / max(want - floor, 1))
        for t in solo:
            placements[t.name] = TablePlacement(
                Placement.CACHED,
                t.cache_ratio * scale,
                host_precision=host_prec(t),
                arena_precision=self._table_arena_precision(t),
            )

        return PlacementPlan(
            placements=placements, arena=arena, budget_bytes=self.budget_bytes
        )

    @staticmethod
    def assign_devices(
        vocab: int,
        num_shards: int,
        counts_ranked: Optional[np.ndarray] = None,
        replicate_top_k: int = 0,
    ) -> ShardAssignment:
        """Device-assignment pass: spread a slab's frequency-ranked rows over
        ``num_shards`` model-axis shards, balancing expected hot-row traffic.

        ``counts_ranked`` is the slab's access counts in frequency-rank order
        (descending at init time — ``FreqStats.counts[inv_map]``; the live
        re-balance pass feeds ``FreqTracker`` decayed scores, which need not
        be monotone in rank).  Greedy longest-processing-time: routed ranks
        are taken hottest first and each goes to the least-loaded shard that
        still has room (every shard holds at most ``ceil(vocab/S)`` rows so
        the stacked state stays uniform).  Without counts the pass
        degenerates to round-robin over ranks — under a Zipfian ordering that
        is already near-optimal traffic balance.  Deterministic: ties break
        by (rows held, shard index), so every host derives the identical
        assignment (a requirement, like ``build_freq_stats`` stability).

        ``replicate_top_k`` marks ranks ``< K`` as replicated: they carry no
        routed load (their lookups are served from the per-shard replicated
        arena, never the exchange) and their slow-tier homes are appended
        *after* all routed ranks, onto the least-filled shards — i.e. at each
        shard's coldest local positions, outside the warm cache prefix.  With
        ``replicate_top_k=0`` the pass is bit-identical to the historical
        assignment.
        """
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        S = int(num_shards)
        vocab = int(vocab)
        K = min(max(int(replicate_top_k), 0), vocab)
        cap = -(-vocab // S)
        routed = np.arange(K, vocab, dtype=np.int64)
        c = None
        if counts_ranked is not None:
            c = np.asarray(counts_ranked, np.float64)
            if c.shape[0] != vocab:
                raise ValueError(f"counts_ranked has {c.shape[0]} entries, want {vocab}")
        owner = np.empty((vocab,), np.int32)
        local = np.empty((vocab,), np.int32)
        if c is None or S == 1:
            # round-robin over routed ranks, replicated homes appended last
            # (K=0 reduces to owner=rank%S, local=rank//S exactly).
            seq = np.concatenate([routed, np.arange(K, dtype=np.int64)])
            pos = np.arange(vocab, dtype=np.int64)
            owner[seq] = (pos % S).astype(np.int32)
            local[seq] = (pos // S).astype(np.int32)
        else:
            import heapq

            # LPT wants hottest-first; live re-balance scores are unsorted,
            # so order routed ranks by descending mass (stable -> identity
            # for the already-descending init-time counts).
            hot_first = routed[np.argsort(-c[routed], kind="stable")]
            sizes = np.zeros((S,), np.int64)
            heap = [(0.0, 0, s) for s in range(S)]  # (load, rows held, shard)
            for r in hot_first:
                ld, size, s = heapq.heappop(heap)
                owner[r] = s
                local[r] = size
                sizes[s] = size + 1
                if size + 1 < cap:  # full shards leave the heap for good
                    heapq.heappush(heap, (ld + c[r], size + 1, s))
            # replicated head: zero routed load, so placement only levels row
            # counts — append to the least-filled shards with room.
            rep_heap = [(int(sizes[s]), s) for s in range(S)]
            heapq.heapify(rep_heap)
            for r in range(K):
                size, s = heapq.heappop(rep_heap)
                owner[r] = s
                local[r] = size
                if size + 1 < cap:
                    heapq.heappush(rep_heap, (size + 1, s))
        load = np.zeros((S,), np.float64)
        if routed.size:
            if c is not None:
                np.add.at(load, owner[routed], c[routed])
            else:
                np.add.at(load, owner[routed], 1.0)
        shard_rows = np.bincount(owner, minlength=S).astype(np.int64)
        return ShardAssignment(
            num_shards=S, owner=owner, local=local, shard_rows=shard_rows,
            shard_load=load, replicate_top_k=K,
        )


# ---------------------------------------------------------------------------
# state pytrees
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DeviceSlab:
    """A fully-resident table: just the weight, no cache bookkeeping."""

    weight: jnp.ndarray  # [vocab, dim]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CachedSlab:
    """A two-tier cached arena (one table, or the shared GROUPED group)."""

    # slow tier: a repro.store.HostStore holding {"weight": [vocab, dim], ...}
    # encoded by the slab's host codec (fp32 = raw, bit-identical to the
    # pre-store pytree).  Raw dicts are still accepted anywhere the slab is
    # consumed (the transmitter handles both), but ``init`` always builds a
    # store.
    full: Any
    cache: cache_lib.CacheState
    idx_map: jnp.ndarray  # int32 [vocab] raw id -> freq-ranked row


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CollectionState:
    slabs: Dict[str, Any]  # name -> DeviceSlab | CachedSlab


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CollectionPlan:
    """The weight-free half of ``prepare`` for a whole collection.

    Built by ``EmbeddingCollection.plan_prepare`` from ids alone (per-slab
    ``cache.CachePlan``s plus the per-feature addresses); executed by
    ``EmbeddingCollection.apply_plan``.  Because planning never reads weights,
    the plan for step t+1 can be computed while step t's dense compute runs —
    the pipelined trainer's whole trick.

    When a lookahead window was merged, ``future_addresses[j]`` holds the
    planned addresses of ``fb_future[j]``'s lanes and ``future_unresident``
    counts future lanes whose row will NOT be resident after apply (loads
    dropped or pins reclaimed under capacity pressure).  A trainer that runs
    whole groups off one merged plan must see ``future_unresident == 0``;
    the current batch's addresses are unconditionally valid either way.
    """

    slab_plans: Dict[str, cache_lib.CachePlan]
    addresses: Dict[str, jnp.ndarray]  # feature -> slots / row ids (-1 pad)
    future_addresses: Tuple[Dict[str, jnp.ndarray], ...] = ()
    future_unresident: jnp.ndarray = dataclasses.field(
        default_factory=lambda: jnp.zeros((), jnp.int32)
    )
    writeback: bool = dataclasses.field(default=True, metadata=dict(static=True))


# --- slab-level ops (the single-arena core; ``cached_embedding`` adapts
#     its one-big-table API onto exactly these) ------------------------------


def _translate(slab: CachedSlab, raw_ids: jnp.ndarray) -> jnp.ndarray:
    """Slab-global raw ids (-1 pad) -> freq-ranked rows (-1 pad)."""
    valid = raw_ids >= 0
    rows = slab.idx_map.at[jnp.where(valid, raw_ids, 0)].get(mode="fill", fill_value=-1)
    return jnp.where(valid, rows, -1)


def _read_full_rows(full: Any, rows: jnp.ndarray) -> jnp.ndarray:
    """Gather weight rows from a slow tier — decoded when it is a HostStore,
    raw otherwise; negative lanes give zero rows (oracle/bulk read path)."""
    if isinstance(full, HostStore):
        return full.decode_rows(rows)["weight"]
    w = full["weight"]
    safe = jnp.where(rows >= 0, rows, w.shape[0])
    return jnp.take(w, safe, axis=0, mode="fill", fill_value=0)


def cached_slab_plan(
    ccfg: cache_lib.CacheConfig,
    slab: CachedSlab,
    raw_ids: jnp.ndarray,
    raw_future: Optional[jnp.ndarray] = None,
) -> cache_lib.CachePlan:
    """Planning half of ``cached_slab_prepare``: ids in, movement plan out —
    no weights touched (see ``cache.plan_prepare``)."""
    fut = None if raw_future is None else _translate(slab, raw_future)
    return cache_lib.plan_prepare(ccfg, slab.cache, _translate(slab, raw_ids), future_rows=fut)


def cached_slab_apply(
    ccfg: cache_lib.CacheConfig, slab: CachedSlab, plan: cache_lib.CachePlan
) -> CachedSlab:
    """Apply half: execute the planned row movement on this slab's weights."""
    full, cache_state = cache_lib.apply_plan(ccfg, slab.full, slab.cache, plan)
    return dataclasses.replace(slab, full=full, cache=cache_state)


def cached_slab_prepare(
    ccfg: cache_lib.CacheConfig, slab: CachedSlab, raw_ids: jnp.ndarray
) -> Tuple[CachedSlab, jnp.ndarray]:
    """Make all rows for ``raw_ids`` (slab-global, -1 pad) resident."""
    plan = cached_slab_plan(ccfg, slab, raw_ids)
    return cached_slab_apply(ccfg, slab, plan), plan.slots


def cached_slab_gather(slab: CachedSlab, slots: jnp.ndarray) -> jnp.ndarray:
    """Differentiable gather from the cached weight (padding -> zero rows)."""
    return cache_lib.lookup_slots(slab.cache, slots, leaf="weight")


def cached_slab_flush(ccfg: cache_lib.CacheConfig, slab: CachedSlab) -> CachedSlab:
    full, cache_state = cache_lib.flush(ccfg, slab.full, slab.cache)
    return dataclasses.replace(slab, full=full, cache=cache_state)


def cached_slab_warmup(ccfg: cache_lib.CacheConfig, slab: CachedSlab) -> CachedSlab:
    full, cache_state = cache_lib.warmup(ccfg, slab.full, slab.cache)
    return dataclasses.replace(slab, full=full, cache=cache_state)


# ---------------------------------------------------------------------------
# the collection
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _CachedSlabSpec:
    """Static geometry of one cached slab (solo table or shared arena)."""

    tables: Tuple[TableConfig, ...]
    cache_ratio: float
    policy: Policy
    buffer_rows: int
    max_unique_per_step: int
    protect_via_inverse: bool
    host_precision: str = "fp32"  # requested codec; "auto" resolves at init
    arena_precision: str = "fp32"  # device-arena tail codec; "auto" -> init
    arena_head_ratio: float = 0.25  # fp32 head fraction of a tiered arena
    freq_half_life: int = 1024  # online-tracker decay (adaptive engine)
    use_pallas_plan: bool = False  # bounded-top-K fused planning (CacheConfig)
    chunk_rows: int = 0  # chunk-granularity host staging (CacheConfig)

    @property
    def vocab(self) -> int:
        return sum(t.vocab for t in self.tables)

    @property
    def dim(self) -> int:
        return self.tables[0].dim

    @property
    def dtype(self):
        return self.tables[0].dtype

    @property
    def ids_per_step(self) -> int:
        return sum(t.ids_per_step for t in self.tables)

    @property
    def offsets(self) -> np.ndarray:
        return freq_lib.concat_table_offsets([t.vocab for t in self.tables])

    def unique_size(self, ids_per_step: Optional[int] = None) -> int:
        k = min(ids_per_step or self.ids_per_step, self.vocab)
        if self.max_unique_per_step:
            k = min(k, self.max_unique_per_step)
        return k

    @property
    def capacity(self) -> int:
        cap = max(int(self.cache_ratio * self.vocab), self.unique_size())
        return min(cap, self.vocab)

    @property
    def head_capacity(self) -> int:
        """fp32 slots of the (possibly tiered) arena — mirrors
        ``CacheConfig.head_capacity`` so planner/policy math agrees with the
        cache's own split."""
        if self.arena_precision == "fp32":
            return self.capacity
        return min(self.capacity, max(1, int(round(self.arena_head_ratio * self.capacity))))

    def cache_config(self, ids_per_step: Optional[int] = None, writeback: bool = True):
        # NB: capacity is fixed at construction; a batch whose unique buffer
        # exceeds it fails CacheConfig's own guard with an actionable error
        # (more uniques than slots cannot all be resident at once).  Serve
        # batches larger than ``ids_per_step`` are fine as long as
        # ``max_unique_per_step`` (or the vocab) bounds their uniques.
        return cache_lib.CacheConfig(
            vocab=self.vocab,
            capacity=self.capacity,
            ids_per_step=ids_per_step or self.ids_per_step,
            buffer_rows=self.buffer_rows,
            policy=self.policy,
            writeback=writeback,
            max_unique_per_step=self.max_unique_per_step,
            protect_via_inverse=self.protect_via_inverse,
            # a still-unresolved "auto" budgets/structures like the policy's
            # no-stats default; ``EmbeddingCollection.init`` replaces the spec
            # with the counts-resolved codec before any state exists.
            arena_precision=(
                PrecisionPolicy().no_stats
                if self.arena_precision == "auto"
                else self.arena_precision
            ),
            arena_head_ratio=self.arena_head_ratio,
            freq_half_life=self.freq_half_life,
            use_pallas_plan=self.use_pallas_plan,
            chunk_rows=self.chunk_rows,
        )


class EmbeddingCollection:
    """N tables under one placement plan, behind one keyed-feature surface."""

    def __init__(self, tables: Sequence[TableConfig], plan: PlacementPlan):
        names = [t.name for t in tables]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate table names: {names}")
        missing = [n for n in names if n not in plan.placements]
        if missing:
            raise ValueError(f"plan is missing placements for tables: {missing}")
        self.tables: Dict[str, TableConfig] = {t.name: t for t in tables}
        self.plan = plan

        # feature -> owning table
        self.feature_to_table: Dict[str, str] = {}
        for t in tables:
            for f in t.features:
                if f in self.feature_to_table:
                    raise ValueError(f"feature {f!r} claimed by two tables")
                self.feature_to_table[f] = t.name

        # slab layout: DEVICE/CACHED tables get their own slab; GROUPED share one
        self.device_slabs: Dict[str, TableConfig] = {}
        self.cached_slabs: Dict[str, _CachedSlabSpec] = {}
        grouped: List[TableConfig] = []
        for t in tables:
            p = plan.placements[t.name]
            if p.placement is Placement.DEVICE:
                self.device_slabs[t.name] = t
            elif p.placement is Placement.CACHED:
                self.cached_slabs[t.name] = _CachedSlabSpec(
                    tables=(t,),
                    cache_ratio=t.cache_ratio if p.cache_ratio is None else p.cache_ratio,
                    policy=t.policy,
                    buffer_rows=t.buffer_rows,
                    max_unique_per_step=t.max_unique_per_step,
                    protect_via_inverse=t.protect_via_inverse,
                    host_precision=p.host_precision or t.host_precision or "fp32",
                    arena_precision=p.arena_precision or t.arena_precision or "fp32",
                    freq_half_life=t.freq_half_life,
                    use_pallas_plan=t.use_pallas_plan,
                    chunk_rows=t.chunk_rows,
                )
            else:
                grouped.append(t)
        if grouped:
            dims = {(t.dim, jnp.dtype(t.dtype).name) for t in grouped}
            if len(dims) != 1:
                raise ValueError(f"GROUPED tables must share (dim, dtype); got {dims}")
            a = plan.arena
            self.cached_slabs[SHARED_ARENA] = _CachedSlabSpec(
                tables=tuple(grouped),
                cache_ratio=a.cache_ratio,
                policy=a.policy,
                buffer_rows=a.buffer_rows,
                max_unique_per_step=a.max_unique_per_step,
                protect_via_inverse=a.protect_via_inverse,
                host_precision=a.host_precision,
                arena_precision=a.arena_precision,
                arena_head_ratio=a.arena_head_ratio,
                freq_half_life=a.freq_half_life,
                use_pallas_plan=a.use_pallas_plan,
                chunk_rows=a.chunk_rows,
            )
        # resolved host codec per cached slab ("auto" is re-resolved by init,
        # which needs the frequency counts; shard_specs/device_bytes read this)
        self.host_precision: Dict[str, str] = {
            sname: spec.host_precision for sname, spec in self.cached_slabs.items()
        }
        # resolved device-arena tail codec per cached slab (same protocol:
        # "auto" re-resolves at init, when frequency counts are available)
        self.arena_precision: Dict[str, str] = {
            sname: spec.arena_precision for sname, spec in self.cached_slabs.items()
        }
        self.precision_policy = PrecisionPolicy()

        # table -> (slab, offset of the table inside the slab's concat vocab)
        self.table_slab: Dict[str, Tuple[str, int]] = {}
        for name in self.device_slabs:
            self.table_slab[name] = (name, 0)
        for sname, spec in self.cached_slabs.items():
            offs = spec.offsets
            for t, off in zip(spec.tables, offs):
                self.table_slab[t.name] = (sname, int(off))

    # ----- construction -----------------------------------------------------

    @classmethod
    def create(
        cls,
        tables: Sequence[TableConfig],
        budget_bytes: Optional[int] = None,
        counts: Optional[Mapping[str, np.ndarray]] = None,
        planner: Optional[PlacementPlanner] = None,
        **arena_kw,
    ) -> "EmbeddingCollection":
        """Plan + build.  Without a budget this is the paper's layout (one
        shared cache arena over all tables).  ``host_precision=`` (in
        ``arena_kw``) selects the host-tier codec collection-wide:
        "fp32"/"fp16"/"int8"/"auto"."""
        if planner is None and budget_bytes is None:
            return cls(tables, PlacementPlan.single_arena(tables, **arena_kw))
        planner = planner or PlacementPlanner(
            budget_bytes,
            arena=ArenaConfig(**arena_kw),
            host_precision=arena_kw.get("host_precision"),
            arena_precision=arena_kw.get("arena_precision"),
            arena_head_ratio=arena_kw.get("arena_head_ratio", 0.25),
        )
        return cls(tables, planner.plan(tables, counts=counts))

    # ----- init -------------------------------------------------------------

    def split_concat_counts(self, counts: np.ndarray) -> Dict[str, np.ndarray]:
        """Split a concatenated-vocab count vector (table declaration order)
        into the per-table dict ``init`` expects."""
        out, off = {}, 0
        for t in self.tables.values():
            out[t.name] = np.asarray(counts[off : off + t.vocab])
            off += t.vocab
        assert off == counts.shape[0], "counts length != total vocab"
        return out

    def init(
        self,
        rng: jax.Array,
        counts: Optional[Mapping[str, np.ndarray]] = None,
        warm: bool = True,
        host_precision: Optional[str] = None,
        arena_precision: Optional[str] = None,
    ) -> CollectionState:
        """Build the collection state.  ``host_precision`` overrides every
        cached slab's host-tier codec for this state ("fp32"/"fp16"/"int8"/
        "auto"); "auto" asks ``PrecisionPolicy`` to pick per slab from the
        frequency counts (fp16 when no counts are given).  The resolved
        choice is recorded in ``self.host_precision`` so ``shard_specs`` and
        ``device_bytes`` stay structurally consistent with the state.

        ``arena_precision`` does the same for the DEVICE arena's tail codec:
        "fp32" keeps the raw pre-tiering arena dict (bit-identical), "fp16"/
        "int8" build a frequency-tiered ``ArenaStore``, and "auto" asks
        ``PrecisionPolicy.choose_arena`` whether the fp32 head absorbs enough
        resident traffic to quantize the tail.  The resolved codec is written
        back into ``self.cached_slabs``/``self.arena_precision`` so every
        later ``cache_config()`` (prepare/refresh/flush/shard_specs) agrees
        with the state's arena container."""
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
            weight = jax.random.uniform(
                next(kit), (spec.vocab, spec.dim), spec.dtype, -scale, scale
            )
            slab_counts = None
            if counts is not None:
                slab_counts = np.concatenate(
                    [
                        np.asarray(
                            counts.get(t.name, np.zeros((t.vocab,), np.int64)), np.int64
                        )
                        for t in spec.tables
                    ]
                )
                idx_map = jnp.asarray(freq_lib.build_freq_stats(slab_counts).idx_map)
            else:
                idx_map = jnp.arange(spec.vocab, dtype=jnp.int32)
            geom = SlabGeometry(
                name=sname,
                vocab=spec.vocab,
                dim=spec.dim,
                capacity=spec.capacity,
                dtype_itemsize=jnp.dtype(spec.dtype).itemsize,
            )
            codec = host_precision or spec.host_precision
            if codec == "auto":
                codec = self.precision_policy.choose(geom, counts=slab_counts)
            else:
                get_codec(codec)  # fail fast on typos
            self.host_precision[sname] = codec
            arena_codec = arena_precision or spec.arena_precision
            if arena_codec == "auto":
                arena_codec = self.precision_policy.choose_arena(
                    geom, spec.head_capacity, counts=slab_counts
                )
            else:
                get_codec(arena_codec)  # fail fast on typos
            if arena_codec != spec.arena_precision:
                # write the resolution back so every later cache_config()
                # (prepare / refresh / flush / shard_specs) builds the same
                # arena container this state carries.
                spec = dataclasses.replace(spec, arena_precision=arena_codec)
                self.cached_slabs[sname] = spec
            self.arena_precision[sname] = arena_codec
            slab = CachedSlab(
                full=HostStore.create({"weight": weight}, codec=codec),
                cache=cache_lib.init_cache(
                    spec.cache_config(), {"weight": jnp.zeros((spec.dim,), spec.dtype)}
                ),
                idx_map=idx_map,
            )
            if warm:
                slab = cached_slab_warmup(spec.cache_config(), slab)
            slabs[sname] = slab
        return CollectionState(slabs=slabs)

    # ----- the non-diff bookkeeping pass ------------------------------------

    def _check_features(self, *fbs: FeatureBatch) -> None:
        for b in fbs:
            for f in b.features:
                if f not in self.feature_to_table:
                    raise KeyError(
                        f"unknown feature {f!r}; known: {sorted(self.feature_to_table)}"
                    )

    def _slab_lanes(self, fb: FeatureBatch, sname: str) -> List[Tuple[str, int]]:
        """Static (feature, flat lane count) list this slab serves, in a
        deterministic order (slab table order, then FeatureBatch order)."""
        spec = self.cached_slabs[sname]
        member = {t.name for t in spec.tables}
        out = []
        for f in fb.features:
            if self.feature_to_table.get(f) in member:
                out.append((f, int(np.prod(fb.ids[f].shape))))
        return out

    def _slab_raw(self, fb: FeatureBatch, sname: str) -> Optional[jnp.ndarray]:
        """Flat offset-translated id vector of this slab's lanes in ``fb``
        (slab-lane order); None when the batch has no lanes for the slab."""
        lanes = self._slab_lanes(fb, sname)
        if not lanes:
            return None
        parts = []
        for f, _ in lanes:
            ids = fb.ids[f].reshape(-1).astype(jnp.int32)
            off = self.table_slab[self.feature_to_table[f]][1]
            parts.append(jnp.where(ids >= 0, ids + off, -1))
        return jnp.concatenate(parts) if len(parts) > 1 else parts[0]

    def plan_prepare(
        self,
        state: CollectionState,
        fb: FeatureBatch,
        fb_future: Sequence[FeatureBatch] = (),
        writeback: bool = True,
    ) -> CollectionPlan:
        """Planning half of ``prepare``: dedup, slot assignment and the row
        movement plan, computed from ids and index state alone — no weights
        are read, so this can run while the previous step's dense compute is
        still in flight (the pipelined trainer dispatches it there).

        ``fb_future`` is a lookahead window of future batches: their ids are
        merged into the admission decision so rows needed at step t+k are
        scheduled for load now, and slots holding soon-needed rows are pinned
        against eviction (see ``cache.plan_prepare``).  The plan also carries
        each future batch's addresses (from the post-apply index image) plus a
        ``future_unresident`` count so a group-scheduled trainer can run the
        whole window off one merged plan — amortizing the bookkeeping k-fold —
        after checking that nothing was dropped under capacity pressure.
        """
        self._check_features(fb, *fb_future)
        addresses: Dict[str, jnp.ndarray] = {}
        future_addresses: List[Dict[str, jnp.ndarray]] = [{} for _ in fb_future]
        future_unresident = jnp.zeros((), jnp.int32)

        # DEVICE tables: the address IS the (local) row id.
        for j, b in enumerate((fb, *fb_future)):
            out = addresses if j == 0 else future_addresses[j - 1]
            for f in b.features:
                if self.feature_to_table[f] in self.device_slabs:
                    out[f] = b.ids[f].astype(jnp.int32)

        # cached slabs: concatenate this batch's lanes, one plan per slab.
        slab_plans: Dict[str, cache_lib.CachePlan] = {}
        for sname, spec in self.cached_slabs.items():
            raw = self._slab_raw(fb, sname)
            slab = state.slabs[sname]
            fut_raws = [self._slab_raw(b, sname) for b in fb_future]
            if raw is None:
                # a slab touched only by the window is not prefetched (every
                # batch of a homogeneous stream touches the same slabs; its
                # own step will fault the rows in exactly) — but its window
                # lanes are then NOT resident, so a group-scheduled trainer
                # must see them in the guard instead of a missing address.
                for raw_j in fut_raws:
                    if raw_j is not None:
                        future_unresident = future_unresident + jnp.sum(
                            raw_j >= 0
                        ).astype(jnp.int32)
                continue
            # translate once per future batch; the merged plan input and the
            # per-batch address lookups reuse the same translated rows
            rows_fut = [None if p is None else _translate(slab, p) for p in fut_raws]
            fut_parts = [r for r in rows_fut if r is not None]
            future_rows = jnp.concatenate(fut_parts) if fut_parts else None
            ccfg = spec.cache_config(ids_per_step=int(raw.shape[0]), writeback=writeback)
            plan = cache_lib.plan_prepare(
                ccfg, slab.cache, _translate(slab, raw), future_rows=future_rows
            )
            slab_plans[sname] = plan
            pos = 0
            for f, n in self._slab_lanes(fb, sname):
                addresses[f] = plan.slots[pos : pos + n].reshape(fb.ids[f].shape)
                pos += n
            # future lanes: addresses from the post-apply index image; count
            # lanes whose row will not be resident (dropped under pressure)
            for j, (b, rows_j) in enumerate(zip(fb_future, rows_fut)):
                if rows_j is None:
                    continue
                slots_j = plan.row_to_slot.at[jnp.where(rows_j >= 0, rows_j, 0)].get(
                    mode="fill", fill_value=-1
                )
                slots_j = jnp.where(rows_j >= 0, slots_j, -1)
                future_unresident = future_unresident + jnp.sum(
                    (rows_j >= 0) & (slots_j < 0)
                ).astype(jnp.int32)
                pos = 0
                for f, n in self._slab_lanes(b, sname):
                    future_addresses[j][f] = slots_j[pos : pos + n].reshape(b.ids[f].shape)
                    pos += n
        return CollectionPlan(
            slab_plans=slab_plans,
            addresses=addresses,
            future_addresses=tuple(future_addresses),
            future_unresident=future_unresident,
            writeback=writeback,
        )

    def apply_plan(self, state: CollectionState, plan: CollectionPlan) -> CollectionState:
        """Apply half of ``prepare``: execute each slab's planned row movement
        (the only part that touches weights — in the pipelined trainer it runs
        after the previous step's row update so evictions write back fresh
        values) and install the index images."""
        slabs = dict(state.slabs)
        for sname, p in plan.slab_plans.items():
            spec = self.cached_slabs[sname]
            ccfg = spec.cache_config(writeback=plan.writeback)
            slabs[sname] = cached_slab_apply(ccfg, slabs[sname], p)
        return CollectionState(slabs=slabs)

    def prepare(
        self, state: CollectionState, fb: FeatureBatch, writeback: bool = True
    ) -> Tuple[CollectionState, Dict[str, jnp.ndarray]]:
        """Make every requested row resident; return per-feature addresses.

        Addresses are cache slots for cached tables and plain row indices for
        DEVICE tables (-1 marks padding lanes in both).  Non-differentiable —
        call outside the grad closure (Algorithm 1 bookkeeping).  Equivalent
        to ``apply_plan(state, plan_prepare(state, fb))`` — bit-exact with the pre-split
        implementation.
        """
        p = self.plan_prepare(state, fb, writeback=writeback)
        return self.apply_plan(state, p), p.addresses

    def prepare_lookahead(
        self,
        state: CollectionState,
        fb_now: FeatureBatch,
        fb_future: Sequence[FeatureBatch],
        writeback: bool = True,
    ) -> Tuple[CollectionState, Dict[str, jnp.ndarray]]:
        """``prepare`` with a lookahead window: rows needed by ``fb_future``
        are fetched before they miss and pinned against eviction until their
        step comes up.  Exactness for ``fb_now`` is unconditional (future
        loads are dropped first under capacity pressure)."""
        p = self.plan_prepare(state, fb_now, fb_future=tuple(fb_future), writeback=writeback)
        return self.apply_plan(state, p), p.addresses

    # ----- differentiable read path -----------------------------------------

    def weights(self, state: CollectionState) -> Dict[str, jnp.ndarray]:
        """The trainable fast-tier weights, keyed by slab — differentiate the
        loss w.r.t. this dict and feed the grads to ``apply_grads``.

        A tiered arena returns its full DECODED [capacity, dim] view: the
        forward/backward run in fp32 against the dequantized rows, and
        ``apply_grads`` re-encodes the updated tail — the straight-through
        scheme of arXiv 2010.11305 (gradients flow as if the arena were
        full-precision; storage noise enters only through the decode)."""
        out = {}
        for name in self.device_slabs:
            out[name] = state.slabs[name].weight
        for sname in self.cached_slabs:
            cached = state.slabs[sname].cache.cached_rows
            if isinstance(cached, ArenaStore):
                out[sname] = cached.decode_leaf("weight")
            else:
                out[sname] = cached["weight"]
        return out

    @contract(max_sort_size=0)
    def gather(
        self,
        weights: Mapping[str, jnp.ndarray],
        addresses: Mapping[str, jnp.ndarray],
        fb: FeatureBatch,
    ) -> Dict[str, jnp.ndarray]:
        """Pure gather: feature -> rows of shape ``ids.shape + (dim,)``.

        A function of ``weights`` only, so gradients flow to the cached rows
        (or the DEVICE table) and nowhere else.
        """
        out = {}
        for f in fb.features:
            sname = self.table_slab[self.feature_to_table[f]][0]
            w = weights[sname]
            addr = addresses[f]
            flat = addr.reshape(-1)
            safe = jnp.where(flat >= 0, flat, w.shape[0])
            rows = jnp.take(w, safe, axis=0, mode="fill", fill_value=0)
            out[f] = rows.reshape(addr.shape + (w.shape[-1],))
        return out

    def pool(
        self,
        rows: Mapping[str, jnp.ndarray],
        fb: FeatureBatch,
        combiner: str = "sum",
        *,
        weights: Optional[Mapping[str, jnp.ndarray]] = None,
        addresses: Optional[Mapping[str, jnp.ndarray]] = None,
        use_pallas: bool = False,
        max_bag: int = 0,
    ) -> Dict[str, jnp.ndarray]:
        """Segment-reduce bag features ([lanes, dim] -> [num_segments, dim]);
        one-hot features pass through.

        With ``use_pallas`` (and ``weights`` + ``addresses`` from the same
        step), bag features skip the materialized per-lane ``rows`` entirely:
        the Pallas embedding-bag kernel runs a fused gather+segment-sum
        straight off the fast-tier slab, with the cache-slot addresses as its
        ids (-1 lanes are padding).  Differentiable w.r.t. ``weights`` via the
        kernel's custom VJP; the ``jnp.take``/``segment_sum`` route below
        stays as the bit-exactness reference.
        """
        out = dict(rows)
        if use_pallas and (weights is None or addresses is None):
            raise ValueError("use_pallas pooling needs weights= and addresses=")
        for f, seg in fb.segments.items():
            if use_pallas:
                from repro.kernels.embedding_bag import ops as eb_ops

                sname = self.table_slab[self.feature_to_table[f]][0]
                out[f] = eb_ops.embedding_bag(
                    weights[sname],
                    addresses[f].reshape(-1),
                    seg,
                    fb.num_segments,
                    combiner=combiner,
                    max_bag=max_bag,
                )
                continue
            pooled = jax.ops.segment_sum(rows[f], seg, num_segments=fb.num_segments)
            if combiner == "mean":
                cnt = jax.ops.segment_sum(
                    (fb.ids[f] >= 0).astype(pooled.dtype), seg, num_segments=fb.num_segments
                )
                pooled = pooled / jnp.maximum(cnt, 1.0)[:, None]
            out[f] = pooled
        return out

    def lookup(
        self, state: CollectionState, fb: FeatureBatch, writeback: bool = True
    ) -> Tuple[CollectionState, Dict[str, jnp.ndarray], Dict[str, jnp.ndarray]]:
        """Convenience prepare+gather: (state', addresses, feature -> rows)."""
        state, addresses = self.prepare(state, fb, writeback=writeback)
        rows = self.gather(self.weights(state), addresses, fb)
        return state, addresses, rows

    # ----- updates ----------------------------------------------------------

    @contract(donates=("state",), int_counters=INT_COUNTERS, max_sort_size=0)
    def apply_grads(
        self,
        state: CollectionState,
        grads: Mapping[str, jnp.ndarray],
        lr,
    ) -> CollectionState:
        """Synchronous SGD on the fast tier (the paper §2.2.3 scheme: resident
        rows are authoritative; the slow tier catches up at eviction/flush)."""
        slabs = dict(state.slabs)
        for name in self.device_slabs:
            slab = slabs[name]
            slabs[name] = dataclasses.replace(
                slab, weight=(slab.weight - lr * grads[name]).astype(slab.weight.dtype)
            )
        for sname in self.cached_slabs:
            slab = slabs[sname]
            cached = slab.cache.cached_rows
            if isinstance(cached, ArenaStore):
                # quantization-aware SGD: step on the decoded view, then store
                # head rows raw and re-encode the tail with a fresh per-row
                # master scale (the sideband).  Rows with zero gradient
                # re-encode to the identical payload (stable projection), so
                # untouched residents never drift.
                w = cached.decode_leaf("weight")
                w = (w - lr * grads[sname]).astype(w.dtype)
                cached = cached.replace_leaf("weight", w)
            else:
                cached = dict(cached)
                cached["weight"] = (cached["weight"] - lr * grads[sname]).astype(
                    cached["weight"].dtype
                )
            slabs[sname] = dataclasses.replace(
                slab, cache=dataclasses.replace(slab.cache, cached_rows=cached)
            )
        return CollectionState(slabs=slabs)

    def flush(self, state: CollectionState) -> CollectionState:
        """Checkpoint barrier: every cached slab writes residents back."""
        slabs = dict(state.slabs)
        for sname, spec in self.cached_slabs.items():
            slabs[sname] = cached_slab_flush(spec.cache_config(), slabs[sname])
        return CollectionState(slabs=slabs)

    # ----- adaptive frequency refresh ---------------------------------------

    def refresh(
        self,
        state: CollectionState,
        cfg: Optional[refresh_lib.RefreshConfig] = None,
        writeback: bool = True,
    ) -> Tuple[CollectionState, refresh_lib.RefreshReport]:
        """Re-rank every cached slab from its online decayed counters and
        apply the bounded incremental permutation (``core.refresh``).

        Host-side, OUTSIDE any jitted step; run it only when no planned
        addresses are outstanding (the trainers call it between steps /
        pipeline groups, the serve engine between batches).  Pure reindexing:
        ``full_lookup``/``dense_reference``/``lookup`` return bitwise the
        same values immediately before and after the call for fp32 host
        stores (codec-noise-bounded for fp16/int8, whose swapped dirty rows
        pay one quantize round trip on the write-back).  Pass
        ``writeback=False`` for read-only (serve) states, whose resident rows
        are clean.  Returns the refreshed state plus a ``RefreshReport``; the
        same counts accumulate in-state (``metrics()``: ``refresh_swaps`` /
        ``refresh_rows_moved``).
        """
        cfg = cfg or refresh_lib.RefreshConfig()
        slabs = dict(state.slabs)
        report = refresh_lib.RefreshReport()
        for sname, spec in self.cached_slabs.items():
            slabs[sname], stats = refresh_lib.refresh_cached_slab(
                spec.cache_config(writeback=writeback), slabs[sname], cfg,
                writeback=writeback,
            )
            report.add(sname, stats)
        return CollectionState(slabs=slabs), report

    def collect_counts_stream(
        self, stream, max_batches: Optional[int] = None
    ) -> Dict[str, np.ndarray]:
        """``freq.collect_counts_stream`` with this collection's feature ->
        table routing and vocab sizes filled in: per-table counts straight
        off a ``Prefetcher`` / ``FeatureBatch`` iterator, ready for
        ``init(counts=...)``."""
        return freq_lib.collect_counts_stream(
            stream,
            self.feature_to_table,
            {t.name: t.vocab for t in self.tables.values()},
            max_batches=max_batches,
        )

    # ----- oracles / bulk reads ---------------------------------------------

    def full_lookup(
        self, state: CollectionState, table: str, local_ids: jnp.ndarray
    ) -> jnp.ndarray:
        """Bulk read from the authoritative (slow) tier of one table —
        retrieval-style candidate scans bypass cache bookkeeping by design."""
        sname, off = self.table_slab[table]
        if sname in self.device_slabs:
            w = state.slabs[sname].weight
            safe = jnp.where(local_ids >= 0, local_ids, w.shape[0])
            return jnp.take(w, safe, axis=0, mode="fill", fill_value=0)
        slab = state.slabs[sname]
        valid = local_ids >= 0
        rows = slab.idx_map.at[jnp.where(valid, local_ids + off, 0)].get(
            mode="fill", fill_value=-1
        )
        return _read_full_rows(slab.full, jnp.where(valid, rows, -1))

    def dense_reference(
        self, state: CollectionState, fb: FeatureBatch
    ) -> Dict[str, jnp.ndarray]:
        """Oracle lookup reading only authoritative tiers (flush first so the
        slow tier is current) — the bit-exactness reference for tests (with a
        quantized host store the slow tier is codec-roundtrip-exact: what was
        flushed is what the oracle decodes)."""
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
                r = slab.idx_map.at[
                    jnp.where(flat >= 0, flat + off, 0)
                ].get(mode="fill", fill_value=-1)
                rows = _read_full_rows(slab.full, jnp.where(flat >= 0, r, -1))
            out[f] = rows.reshape(ids.shape + (rows.shape[-1],))
        return out

    # ----- telemetry / accounting -------------------------------------------

    # jit-adjacent: traced inside every compute_step — the int-counter
    # contract pins the per-slab counter families the obs hub reconstructs,
    # and max_sort_size=0 asserts metric collection never adds a sort.
    @contract(int_counters=METRICS_INT_COUNTERS, max_sort_size=0)
    def metrics(
        self, state: CollectionState, writeback: bool = True
    ) -> Dict[str, jnp.ndarray]:
        """Cache telemetry aggregated over cached slabs (DEVICE tables have
        no bookkeeping, hence no misses by construction).  ``host_wire_bytes``
        is the cumulative host<->device traffic estimate: demand misses
        (loads) plus — when the caller runs the cache with writeback —
        evictions (writebacks), each costing the slab's *encoded* row size,
        the quantity the mixed-precision store shrinks.  Pass
        ``writeback=False`` for read-only (serve) states, whose evicted rows
        are dropped and never cross the link.

        Two representations are returned: ``host_wire_bytes`` is a float32
        scalar (in-jit convenience — float32 loses integer resolution past
        2^24, so it DRIFTS on long runs), while ``host_moved_rows`` /
        ``host_row_bytes`` are per-slab int32 counters + static encoded row
        sizes from which ``repro.obs.hub.ExactCounter.observe(..., unit=...)``
        reconstructs the exact cumulative byte count host-side (what the
        trainer records through its ``MetricsHub``)."""
        hits = misses = evictions = overflows = 0
        win_h = win_m = jnp.zeros((), jnp.float32)
        ref_swaps = ref_rows = jnp.zeros((), jnp.int32)
        wire = jnp.zeros((), jnp.float32)
        moved_rows: Dict[str, jnp.ndarray] = {}
        row_bytes_map: Dict[str, jnp.ndarray] = {}
        slab_hits: Dict[str, jnp.ndarray] = {}
        slab_misses: Dict[str, jnp.ndarray] = {}
        slab_uniq_rows: Dict[str, jnp.ndarray] = {}
        slab_ref_swaps: Dict[str, jnp.ndarray] = {}
        slab_ref_rows: Dict[str, jnp.ndarray] = {}
        slab_tier_promotions: Dict[str, jnp.ndarray] = {}
        slab_tier_demotions: Dict[str, jnp.ndarray] = {}
        for sname, spec in self.cached_slabs.items():
            c = state.slabs[sname].cache
            hits = hits + jnp.sum(c.hits)
            misses = misses + jnp.sum(c.misses)
            evictions = evictions + jnp.sum(c.evictions)
            overflows = overflows + jnp.sum(c.uniq_overflows)
            slab_hits[sname] = jnp.sum(c.hits).astype(jnp.int32)
            slab_misses[sname] = jnp.sum(c.misses).astype(jnp.int32)
            slab_uniq_rows[sname] = jnp.sum(c.uniq_rows).astype(jnp.int32)
            win_h = win_h + jnp.sum(c.tracker.win_hits)
            win_m = win_m + jnp.sum(c.tracker.win_misses)
            ref_swaps = ref_swaps + jnp.sum(c.tracker.refresh_swaps)
            ref_rows = ref_rows + jnp.sum(c.tracker.refresh_rows)
            slab_ref_swaps[sname] = jnp.sum(c.tracker.refresh_swaps).astype(jnp.int32)
            slab_ref_rows[sname] = jnp.sum(c.tracker.refresh_rows).astype(jnp.int32)
            # precision-boundary crossings (jnp.sum folds the sharded [S]
            # per-shard counters into one cumulative int32, like hits/misses)
            slab_tier_promotions[sname] = jnp.sum(c.tier_promotions).astype(jnp.int32)
            slab_tier_demotions[sname] = jnp.sum(c.tier_demotions).astype(jnp.int32)
            full = state.slabs[sname].full
            row_bytes = (
                full.row_wire_bytes(batch_dims=full.data["weight"].ndim - 1)
                if isinstance(full, HostStore)
                else spec.dim * jnp.dtype(spec.dtype).itemsize
            )
            moved = c.misses + c.evictions if writeback else c.misses
            moved_rows[sname] = jnp.sum(moved).astype(jnp.int32)
            row_bytes_map[sname] = jnp.asarray(row_bytes, jnp.int32)
            wire = wire + jnp.sum(moved).astype(jnp.float32) * row_bytes
        tot = hits + misses
        win_tot = win_h + win_m
        return {
            "hit_rate": jnp.where(tot > 0, hits / jnp.maximum(tot, 1), 0.0),
            # drift telemetry: the exponentially-windowed hit rate reacts to a
            # hot-set shift within ~one half-life, long before the cumulative
            # rate moves; refresh_* count the adaptive engine's rank churn
            # (swapped pairs) and slow-tier rows it permuted.
            "window_hit_rate": jnp.where(
                win_tot > 0, win_h / jnp.maximum(win_tot, 1e-9), 0.0
            ),
            "refresh_swaps": ref_swaps,
            "refresh_rows_moved": ref_rows,
            "cache_misses": jnp.asarray(misses),
            "cache_evictions": jnp.asarray(evictions),
            "uniq_overflows": jnp.asarray(overflows),
            "host_wire_bytes": wire,
            "host_moved_rows": moved_rows,
            "host_row_bytes": row_bytes_map,
            # per-slab cumulative int32 counters: wrap-free exact totals are
            # reconstructed host-side (``repro.obs.hub``) — the int32 scalars
            # above wrap past 2^31 on long runs.
            "slab_hits": slab_hits,
            "slab_misses": slab_misses,
            # unique rows asked, the unit of ``slab_misses`` (``slab_hits``
            # counts lanes): 1 - misses / uniq_rows is the row hit rate
            "slab_uniq_rows": slab_uniq_rows,
            "slab_refresh_swaps": slab_ref_swaps,
            "slab_refresh_rows": slab_ref_rows,
            "slab_tier_promotions": slab_tier_promotions,
            "slab_tier_demotions": slab_tier_demotions,
        }

    def _slab_codec(self, sname: str) -> str:
        """Resolved host codec of one cached slab ("auto" before init falls
        back to the policy's no-stats default for accounting purposes)."""
        name = self.host_precision[sname]
        return self.precision_policy.no_stats if name == "auto" else name

    def _slab_arena_codec(self, sname: str) -> str:
        """Resolved device-arena tail codec (same "auto" fallback protocol
        as ``_slab_codec``)."""
        name = self.arena_precision[sname]
        return self.precision_policy.no_stats if name == "auto" else name

    def device_bytes(self) -> Dict[str, int]:
        """Device-resident vs host-tier footprint under the plan (per-slab
        breakdown included; the planner's budget bounds ``device_total``).
        The slow tier is accounted at its *encoded* size; ``host_bytes_saved``
        is what the host-precision codecs shaved off the fp32 layout, and
        ``arena_bytes_saved`` what the tiered arena shaved off the device
        side (a tiered slab's weight bytes = fp32 head + encoded tail payload
        + tail sideband, all device-resident)."""
        per_slab: Dict[str, int] = {}
        slow = slow_fp32 = 0
        fast_fp32 = fast_actual = 0
        for name, t in self.device_slabs.items():
            per_slab[name] = t.full_bytes
        for sname, spec in self.cached_slabs.items():
            item = jnp.dtype(spec.dtype).itemsize
            arena_codec = self._slab_arena_codec(sname)
            head = spec.capacity if arena_codec == "fp32" else spec.head_capacity
            w = tiered_arena_bytes(spec.capacity, head, spec.dim, spec.dtype, arena_codec)
            fast = w
            fast += spec.capacity * 4 * 3  # slot_to_row, last_used, use_count
            # row_to_slot + idx_map + tracker (score + last_touch)
            fast += spec.vocab * 4 * 4
            per_slab[sname] = fast
            fast_actual += w
            fast_fp32 += spec.capacity * spec.dim * item
            codec = get_codec(self._slab_codec(sname))
            slow += spec.vocab * codec.row_bytes((spec.dim,), spec.dtype)
            slow_fp32 += spec.vocab * spec.dim * item
        return {
            "device_total": sum(per_slab.values()),
            "slow_tier_bytes": slow,
            "host_bytes_saved": slow_fp32 - slow,
            "arena_bytes_saved": fast_fp32 - fast_actual,
            "per_slab": per_slab,
            "budget_bytes": self.plan.budget_bytes,
        }

    # ----- sharding ----------------------------------------------------------

    def shard_specs(self, mode: str = "column", model_axis: str = "model"):
        """PartitionSpec pytree matching ``CollectionState`` (see
        ``cached_embedding.shard_specs`` for the mode semantics).  The slow
        tier's specs mirror the slab's resolved ``HostStore`` layout — with
        an "auto" precision, call after ``init`` so the resolved codec (and
        hence the sideband structure) matches the state."""
        from jax.sharding import PartitionSpec as P

        if mode == "column":
            full_w = cached_w = dev_w = P(None, model_axis)
            side_w = P(None, None)  # per-row sideband cannot split the dim
        elif mode == "row":
            full_w, cached_w = P(model_axis, None), P(None, None)
            dev_w = P(model_axis, None)
            side_w = P(model_axis, None)  # sideband rows travel with the table
        else:
            full_w = cached_w = dev_w = side_w = P(None, None)

        slabs: Dict[str, Any] = {}
        for name in self.device_slabs:
            slabs[name] = DeviceSlab(weight=dev_w)
        for sname, spec in self.cached_slabs.items():
            like = {"weight": jax.ShapeDtypeStruct((spec.vocab, spec.dim), spec.dtype)}
            arena_codec = self._slab_arena_codec(sname)
            if arena_codec == "fp32":
                cached_rows: Any = {"weight": cached_w}
            else:
                # tiered arena: head/tail carry the cached-weight spec; the
                # [slots, 2] sideband rides with the CACHE (replicated in row
                # mode, and its (scale, zp) axis can never split the model
                # axis), hence P(None, None) rather than the host side_w.
                cached_rows = ArenaStore.spec_like(
                    {
                        "weight": jax.ShapeDtypeStruct(
                            (spec.capacity, spec.dim), spec.dtype
                        )
                    },
                    cached_w,
                    P(None, None),
                    codec=arena_codec,
                )
            slabs[sname] = CachedSlab(
                full=HostStore.spec_like(
                    like, {"weight": full_w}, side_w, codec=self._slab_codec(sname)
                ),
                cache=cache_lib.CacheState(
                    cached_rows=cached_rows,
                    slot_to_row=P(None),
                    row_to_slot=P(None),
                    last_used=P(None),
                    use_count=P(None),
                    step=P(),
                    hits=P(),
                    misses=P(),
                    uniq_rows=P(),
                    evictions=P(),
                    uniq_overflows=P(),
                    tier_promotions=P(),
                    tier_demotions=P(),
                    tracker=freq_lib.tracker_spec(P),
                ),
                idx_map=P(None),
            )
        return CollectionState(slabs=slabs)
