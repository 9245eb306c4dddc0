"""The dynamic module (paper §4.3, Algorithm 1) as a functional, jittable JAX op.

State layout (all device-resident, mirroring the paper's "green boxes"):

  cached_rows   pytree; each leaf [capacity, ...]   the CUDA-Cached-Weight analogue
                (leaf 0 is the weight; extra leaves carry per-row optimizer state)
  slot_to_row   int32 [capacity]   freq-ranked row held by each slot (-1 = empty)
                (the paper's ``cached_idx_map``)
  row_to_slot   int32 [vocab]      inverse map (-1 = not cached); 4 B/row ~= 0.8 %
                overhead of a dim-128 fp32 table, same trade the paper makes
  last_used / use_count  int32 [capacity]  only read by non-paper policies
  counters      hit/miss/transfer telemetry (int64 scalars)

Shapes are static: each ``prepare`` call ingests a fixed-size padded id vector,
takes a fixed-size ``unique``, and drives the bounded-buffer transmitter for a
fixed number of rounds — the compile-time promotion of the paper's "strictly
limit the buffer size / complete the transfer multiple times".

Invariant (tested property): after ``prepare``, every id of the batch maps to
a resident slot, and lookups through the cache are bit-identical to lookups
into an uncached table — the cache is pure data movement, which is why the
paper's accuracy matches the baseline.

Host tier: ``full_rows`` may be either a raw pytree (leaves [vocab, ...]) or
a :class:`repro.store.HostStore` — the mixed-precision host-side container.
``apply_plan`` / ``flush`` / ``warmup`` only ever touch it through the
transmitter, which is codec-aware: loads dequantize the staging block on
arrival, evictions/flushes quantize before the block crosses the link.  With
the fp32 codec the store path is bit-identical to the raw-pytree path; with
fp16/int8 the cache invariant weakens from bit-exact to codec-roundtrip-exact
(resident rows are still authoritative full-precision copies).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.contracts import INT_COUNTERS, contract
from repro.core import freq as freq_lib
from repro.core import transmitter
from repro.core.policies import Policy, eviction_key
from repro.kernels.cache_ops import ops as cache_ops
from repro.obs.tracing import SCOPE_TELEMETRY
from repro.store.arena import ArenaStore

__all__ = [
    "CacheConfig",
    "CacheState",
    "CachePlan",
    "init_cache",
    "plan_prepare",
    "apply_plan",
    "prepare",
    "lookup_slots",
    "flush",
    "warmup",
]

_EMPTY = jnp.array(-1, jnp.int32)
_BIG = jnp.iinfo(jnp.int32).max // 2


@dataclasses.dataclass(frozen=True)
class CacheConfig:
    vocab: int  # total rows of the (concatenated, freq-ordered) table
    capacity: int  # cached rows (= cache_ratio * vocab)
    ids_per_step: int  # static size of the flattened id vector per prepare()
    buffer_rows: int = 65536  # transmitter staging-block rows per round
    policy: Policy = Policy.FREQ_LFU
    writeback: bool = True  # False for inference (cache rows stay clean)
    protect_via_inverse: bool = True  # beyond-paper DEFAULT: O(K) scatter via
    # the inverse map instead of the paper's isin for the eviction "backlist"
    # (bit-identical; XLA lowers the isin as a [C x K] outer compare — the
    # entire memory roofline term of every recsys cell. False = paper-faithful
    # ablation. See EXPERIMENTS.md §Perf fm.)
    max_unique_per_step: int = 0  # 0 = worst case (= ids_per_step); smaller
    # values bound the per-step unique buffer (the same philosophy as the
    # paper's strict buffer limit).  Overflow — more distinct rows in a batch
    # than the bound — is counted in ``state.uniq_overflows`` and must stay 0
    # for exactness (the trainer asserts this; tests property-check it).
    arena_precision: str = "fp32"  # device-arena tail codec: "fp32" keeps the
    # raw pre-tiering dict (bit-identical); "fp16"/"int8" store the arena as a
    # frequency-tiered ``store.ArenaStore`` — fp32 head for the hottest slots,
    # encoded tail for the colder residents.  ("auto" is resolved to one of
    # these by the collection's PrecisionPolicy before a CacheConfig exists.)
    arena_head_ratio: float = 0.25  # fraction of capacity kept fp32 when tiered
    freq_half_life: int = 1024  # PLAN CALLS for a row's decayed access
    # counter (and the rolling hit-rate window) to halve — the adaptive
    # frequency engine's memory length.  The tracker clock is ``state.step``,
    # which advances once per ``plan_prepare``: in the serial trainer that is
    # one trainer step, but under group scheduling (pipeline_depth = k) only
    # group leaders plan, so the decay timescale stretches to k trainer steps
    # per tick — divide the half-life by the depth if you tune it to a drift
    # timescale measured in steps (same clock caveat as the hits/misses
    # sampling documented in ``plan_prepare``).  Tracking is always on (two
    # O(K) scatters per plan); the counters only influence behavior when a
    # ``core.refresh`` pass is invoked, so untouched runs stay bit-identical.
    use_pallas_plan: bool = False  # route planning through the bounded-top-K
    # + fused-dedup kernels (kernels/cache_ops): no capacity-sized sort
    # anywhere in plan_prepare.  Bit-identical to the default route (property
    # tested); False keeps the historical XLA route as the exactness oracle.
    chunk_rows: int = 0  # slow-tier staging granularity: 0 moves scattered
    # rows (historical path); > 0 groups each transmitter round's rows into
    # contiguous ``chunk_rows``-row chunks so host<->device traffic issues as
    # few large copies (the paper's chunk-based manager).  Bit-identical
    # either way; values that do not divide the vocab fall back to rows.

    def __post_init__(self):
        if self.capacity < self.unique_size:
            raise ValueError(
                f"cache capacity {self.capacity} must hold one batch's unique rows "
                f"(<= {self.unique_size})"
            )
        if self.arena_precision not in ("fp32", "fp16", "int8"):
            raise ValueError(
                f"arena_precision must be fp32/fp16/int8 at the cache level "
                f"(auto resolves above), got {self.arena_precision!r}"
            )
        if not (0.0 < self.arena_head_ratio <= 1.0):
            raise ValueError(f"arena_head_ratio must be in (0, 1], got {self.arena_head_ratio}")
        if self.chunk_rows < 0:
            raise ValueError(f"chunk_rows must be >= 0, got {self.chunk_rows}")

    @property
    def unique_size(self) -> int:
        # number of distinct rows a step may touch
        k = min(self.ids_per_step, self.vocab)
        if self.max_unique_per_step:
            k = min(k, self.max_unique_per_step)
        return k

    @property
    def head_capacity(self) -> int:
        """Slots kept fp32 when the arena is tiered (all of them for fp32)."""
        if self.arena_precision == "fp32":
            return self.capacity
        return min(self.capacity, max(1, int(round(self.arena_head_ratio * self.capacity))))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CacheState:
    cached_rows: Any  # pytree, leaves [capacity, ...]
    slot_to_row: jnp.ndarray  # int32 [capacity]
    row_to_slot: jnp.ndarray  # int32 [vocab]
    last_used: jnp.ndarray  # int32 [capacity]
    use_count: jnp.ndarray  # int32 [capacity]
    step: jnp.ndarray  # int32 []
    hits: jnp.ndarray  # int32 [] id-level hits (telemetry; x64 is off)
    misses: jnp.ndarray  # int32 [] unique-row misses (= rows moved host->device)
    evictions: jnp.ndarray  # int32 [] rows written back device->host
    uniq_overflows: jnp.ndarray  # int32 [] steps whose distinct rows > unique_size
    tier_promotions: jnp.ndarray  # int32 [] rows loaded INTO the fp32 head tier
    tier_demotions: jnp.ndarray  # int32 [] resident rows displaced OUT of it
    # (both always 0 for a raw fp32 arena — every slot is the head then)
    tracker: freq_lib.FreqTracker  # online decayed per-row counters (core.freq)
    # int32 [] unique rows the plans were asked for, the unit of ``misses``:
    # 1 - misses / uniq_rows is the row hit rate.  The last leaf on purpose:
    # inserted before ``tracker`` it moved the tracker's buffers in HBM, and
    # the plan's gather from ``tracker.last_touch`` ran 2 ms (28%) slower a
    # step on a TPU v5e (Criteo one-chip share, same HLO)
    uniq_rows: jnp.ndarray

    def hit_rate(self) -> jnp.ndarray:
        tot = self.hits + self.misses
        return jnp.where(tot > 0, self.hits / jnp.maximum(tot, 1), 0.0)


def init_cache(cfg: CacheConfig, row_tree_example: Any) -> CacheState:
    """Empty cache; ``row_tree_example`` gives per-row leaf shapes/dtypes.

    ``row_tree_example`` leaves have shape [..row dims..]; cached leaves get a
    leading ``capacity`` dim.
    """
    def z(leaf):
        return jnp.zeros((cfg.capacity,) + tuple(leaf.shape), leaf.dtype)

    cached_rows = jax.tree_util.tree_map(z, row_tree_example)
    if cfg.arena_precision != "fp32":
        # frequency-tiered arena: fp32 head + encoded tail.  Zeros encode to
        # zeros under both codecs, so the empty tiered arena decodes exactly
        # like the empty raw arena.
        cached_rows = ArenaStore.create(cached_rows, cfg.head_capacity, cfg.arena_precision)
    return CacheState(
        cached_rows=cached_rows,
        slot_to_row=jnp.full((cfg.capacity,), -1, jnp.int32),
        row_to_slot=jnp.full((cfg.vocab,), -1, jnp.int32),
        last_used=jnp.zeros((cfg.capacity,), jnp.int32),
        use_count=jnp.zeros((cfg.capacity,), jnp.int32),
        step=jnp.zeros((), jnp.int32),
        hits=jnp.zeros((), jnp.int32),
        misses=jnp.zeros((), jnp.int32),
        uniq_rows=jnp.zeros((), jnp.int32),
        evictions=jnp.zeros((), jnp.int32),
        uniq_overflows=jnp.zeros((), jnp.int32),
        tier_promotions=jnp.zeros((), jnp.int32),
        tier_demotions=jnp.zeros((), jnp.int32),
        tracker=freq_lib.init_tracker(cfg.vocab),
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CachePlan:
    """The weight-free half of Algorithm 1: a movement program plus the
    post-apply index image.

    ``plan_prepare`` computes it from (index state, ids) alone — no weights
    are touched, so a plan for step t+1 can be built while step t's dense
    compute is still running.  ``apply_plan`` executes the row movement and
    installs the index image; ``prepare`` composes the two and is bit-exact
    with the former fused implementation.
    """

    # movement program (static length = unique_size [+ lookahead uniques])
    miss_rows: jnp.ndarray  # int32 [kv] freq-ranked rows to load (-1 inactive)
    victim_slots: jnp.ndarray  # int32 [kv] destination slots
    victim_rows: jnp.ndarray  # int32 [kv] rows being displaced (-1 = empty)
    load_active: jnp.ndarray  # bool [kv]
    evict_active: jnp.ndarray  # bool [kv] displaced rows needing write-back
    # post-apply index image (everything in CacheState except cached_rows)
    slot_to_row: jnp.ndarray
    row_to_slot: jnp.ndarray
    last_used: jnp.ndarray
    use_count: jnp.ndarray
    step: jnp.ndarray
    hits: jnp.ndarray
    misses: jnp.ndarray
    uniq_rows: jnp.ndarray
    evictions: jnp.ndarray
    uniq_overflows: jnp.ndarray
    tier_promotions: jnp.ndarray
    tier_demotions: jnp.ndarray
    tracker: freq_lib.FreqTracker  # post-plan decayed-counter image
    # per-lane resident slot for the CURRENT batch (-1 padding)
    slots: jnp.ndarray


# max_sort_size quotes the analysis.smoke geometry (ids_per_step=16): planning
# declares bounded-top-K, so only O(unique)-sized sorts are admissible.  The
# smoke config routes through ``use_pallas_plan`` (ROADMAP item 3: bounded
# top-K victim selection + fused prepare, kernels/cache_ops), which holds the
# bound; the ``use_pallas_plan=False`` oracle route keeps the full-capacity
# eviction argsort and is covered by bit-identity property tests instead.
@contract(max_sort_size=64, int_counters=INT_COUNTERS)
def plan_prepare(
    cfg: CacheConfig,
    state: CacheState,
    rows: jnp.ndarray,
    future_rows: Optional[jnp.ndarray] = None,
) -> CachePlan:
    """Pure planning half of ``prepare``: dedup, victim selection, movement
    plan and index bookkeeping — callable on ids alone, no weights touched.

    ``future_rows`` (optional, int32 [F], -1 padding) merges a lookahead
    window of future-batch rows into the admission decision: rows needed at
    step t+k are scheduled for load *now* (before they miss) and slots
    holding soon-needed rows are pinned against eviction — the exact-lookahead
    analogue of the paper's frequency protection.  Current-batch rows always
    win: if capacity is short, future loads are dropped first and pinned
    future slots may be reclaimed, but rows of the current batch are never
    evicted (exactness is unconditional).

    Pin lifetime (audited invariant, tested in test_pipeline.py): a pin is
    PLAN-LOCAL.  Nothing in ``CacheState`` records it — the eviction-key
    demotion exists only inside this call, recomputed from the window the
    caller passes.  If a pipelined group is abandoned mid-group (early stop,
    producer error), the next ``plan_prepare`` with a fresh window simply
    does not re-pin the stale rows: they compete under the normal policy key
    (for LRU they age from their load step like any other resident row, for
    FREQ_LFU the pin never influenced the key beyond the planning call) and
    ``flush`` writes them back like any resident row.  No unpin step exists
    because no pin state persists.
    """
    k = cfg.unique_size
    # geometry comes from the STATE (a serve-time cfg may quote a smaller
    # capacity than the state it operates on — guards must use real sizes)
    capacity = state.slot_to_row.shape[0]
    vocab = state.row_to_slot.shape[0]
    valid = rows >= 0
    int_max = jnp.iinfo(jnp.int32).max

    # --- id-level hit telemetry (before any movement) ----------------------
    with jax.named_scope(SCOPE_TELEMETRY):
        pre_slots = state.row_to_slot.at[jnp.where(valid, rows, 0)].get(
            mode="fill", fill_value=-1
        )
        id_hits = jnp.sum((pre_slots >= 0) & valid)

    # --- unique needed rows (fixed size k, padded with -1 at the end) ------
    # jnp.unique sorts ascending; map padding to +inf-like sentinel then back.
    big_rows = jnp.where(valid, rows, int_max)
    if cfg.use_pallas_plan:
        # fused dedup -> residency probe -> miss compaction: ONE sort total
        # (the overflow count shares the dedup's sorted buffer instead of
        # paying a second full sort) — bit-identical to the route below.
        img = cache_ops.plan_image_impl(big_rows, state.row_to_slot, k)
        uniq_sorted = img.uniq_sorted
        uniq_valid = img.uniq_valid
        uniq = img.uniq
        n_distinct = img.n_distinct
        overflow = (n_distinct > k).astype(jnp.int32)
        uniq_slots = img.uniq_slots
        miss = img.miss
        n_miss = img.n_miss
    else:
        uniq = jnp.unique(big_rows, size=k, fill_value=int_max)
        uniq_valid = uniq != int_max
        uniq_sorted = uniq  # ascending, sentinel-padded — reused for membership
        uniq = jnp.where(uniq_valid, uniq, -1)

        # overflow detection: did the batch contain more distinct rows than k?
        # (jnp.unique(size=k) silently keeps the k smallest — count the truth.)
        srt = jnp.sort(big_rows)
        n_distinct = jnp.sum(
            (jnp.diff(srt) != 0) & (srt[1:] != int_max)
        ) + (srt[0] != int_max).astype(jnp.int32)
        overflow = (n_distinct > k).astype(jnp.int32)

        uniq_slots = state.row_to_slot.at[jnp.where(uniq_valid, uniq, 0)].get(mode="fill", fill_value=-1)
        miss = (uniq_slots < 0) & uniq_valid
        n_miss = jnp.sum(miss)
    # valid unique rows the plan was asked for (= sum(uniq_valid)), from the
    # count the overflow guard already takes: no second pass over the
    # unique buffer
    with jax.named_scope(SCOPE_TELEMETRY):
        n_uniq = jnp.minimum(n_distinct, k).astype(jnp.int32)

    # --- lookahead merge: unique FUTURE rows not already needed now --------
    if future_rows is not None and future_rows.shape[0] == 0:
        future_rows = None
    kf = 0
    if future_rows is not None:
        kf = min(int(future_rows.shape[0]), vocab)
        fbig = jnp.where(future_rows >= 0, future_rows, int_max)
        if cfg.use_pallas_plan:
            fut_uniq, _ = cache_ops.dedup_impl(fbig, kf, int_max)
        else:
            fut_uniq = jnp.unique(fbig, size=kf, fill_value=int_max)
        # membership in the current batch's unique set via the sorted buffer
        pos = jnp.clip(jnp.searchsorted(uniq_sorted, fut_uniq), 0, k - 1)
        in_now = uniq_sorted[pos] == fut_uniq
        fut_valid = (fut_uniq != int_max) & ~in_now
        fut_uniq = jnp.where(fut_valid, fut_uniq, -1)
        fut_slots = state.row_to_slot.at[jnp.where(fut_valid, fut_uniq, 0)].get(
            mode="fill", fill_value=-1
        )
        fut_miss = (fut_slots < 0) & fut_valid
        n_fut_miss = jnp.sum(fut_miss)

    # --- online frequency tracking (adaptive engine input) ------------------
    # The decayed counters ride the dedup this function already paid for:
    # current uniques count 1 touch, lookahead uniques count 1 touch (under
    # group scheduling each batch appears exactly once across the group's
    # merged plans, so per-batch mass is neither lost nor double-counted).
    # Purely additive state — no planning decision below reads it.
    step = state.step + 1
    tracker = freq_lib.tracker_touch(
        state.tracker, uniq, uniq_valid, step, cfg.freq_half_life
    )
    if kf:
        tracker = freq_lib.tracker_touch(
            tracker, fut_uniq, fut_valid, step, cfg.freq_half_life
        )
    tracker = freq_lib.tracker_observe(tracker, id_hits, n_miss, cfg.freq_half_life)

    # --- victim selection (Algorithm 1 lines 15-26) ------------------------
    # "backlist": rows needed now must not be evicted; rows needed in the
    # lookahead window are pinned one tier above (reclaimed only if the
    # current batch needs the space).
    if cfg.protect_via_inverse:
        # a slot needs protection iff it currently holds a needed (hit) row;
        # we already know those slots from the inverse map: O(K) scatter.
        hit_slots = jnp.where((uniq_slots >= 0) & uniq_valid, uniq_slots, capacity)
        protected = (
            jnp.zeros((capacity,), bool).at[hit_slots].set(True, mode="drop")
        )
    else:
        protected = jnp.isin(state.slot_to_row, jnp.where(uniq_valid, uniq, -7)) & (
            state.slot_to_row >= 0
        )
    key = eviction_key(cfg.policy, state.slot_to_row, state.last_used, state.use_count)
    key = jnp.where(state.slot_to_row < 0, _BIG, key)  # empty slots evict first
    if kf:
        if cfg.protect_via_inverse:
            fut_hit = jnp.where((fut_slots >= 0) & fut_valid, fut_slots, capacity)
            pinned = jnp.zeros((capacity,), bool).at[fut_hit].set(True, mode="drop")
        else:
            pinned = jnp.isin(
                state.slot_to_row, jnp.where(fut_valid, fut_uniq, -7)
            ) & (state.slot_to_row >= 0)
        key = jnp.where(pinned, -(_BIG // 2), key)  # soon-needed: evict late
    key = jnp.where(protected, -_BIG, key)  # needed-now slots evict last
    # a step can never load more rows than there are slots
    kv = min(k + kf, capacity)
    if cfg.use_pallas_plan:
        # bounded top-K: 32-round streaming threshold descent + kv-sized sort
        # (bit-identical to the full argsort slice, including tie order)
        victim_slots = cache_ops.victim_topk_impl(key, kv)
    else:
        order = jnp.argsort(key, descending=True)
        victim_slots = order[:kv].astype(jnp.int32)

    lane = jnp.arange(kv)
    if kf:
        # mandatory current-batch misses first, then as many future misses as
        # fit without reclaiming any pinned/protected slot.
        n_prot = jnp.sum(protected) + jnp.sum(pinned & ~protected)
        n_fut_load = jnp.clip(capacity - n_prot - n_miss, 0, n_fut_miss)
        n_loads = n_miss + n_fut_load
        active = lane < n_loads
        if cfg.use_pallas_plan:
            # cumsum-compact both miss runs and lane-select the merge — the
            # priority argsorts below, without sorting (lanes past the two
            # runs are never active, so the -1 padding never surfaces).
            now_c = img.miss_rows
            fut_c = cache_ops.compact_front_impl(fut_miss, fut_uniq, kf)
            cand = cache_ops.merge_candidates_impl(now_c, n_miss, fut_c, kv)
            miss_rows = jnp.where(active, cand, -1)
        else:
            perm_now = jnp.argsort(jnp.where(miss, 0, 1), stable=True)
            perm_fut = jnp.argsort(jnp.where(fut_miss, 0, 1), stable=True)
            cand_rows = jnp.concatenate([uniq[perm_now], fut_uniq[perm_fut]])
            cand_pri = jnp.concatenate(
                [
                    jnp.where(jnp.arange(k) < n_miss, 0, 2),
                    jnp.where(jnp.arange(kf) < n_fut_miss, 1, 2),
                ]
            )
            perm = jnp.argsort(cand_pri, stable=True)
            miss_rows = jnp.where(active, cand_rows[perm][:kv], -1)
    else:
        n_loads = n_miss
        active = lane < n_loads  # one victim per actual miss
        # --- compact miss rows to the front ---------------------------------
        if cfg.use_pallas_plan:
            miss_rows = jnp.where(active, img.miss_rows[:kv], -1)
        else:
            perm = jnp.argsort(jnp.where(miss, 0, 1), stable=True)
            miss_rows = jnp.where(active, uniq[perm][:kv], -1)

    victim_rows = state.slot_to_row[victim_slots]
    evict_active = active & (victim_rows >= 0)

    # --- precision-tier movement telemetry ---------------------------------
    # For a tiered arena, slots below head_capacity are the fp32 head: a load
    # landing there promotes the row to full precision; displacing a resident
    # row from there demotes it (it re-faults into whichever tier its new
    # rank's slot occupies).  The container type is static pytree metadata,
    # so this branch specializes at trace time (vmap included); raw fp32
    # arenas keep both counters pinned at zero.
    if isinstance(state.cached_rows, ArenaStore):
        head_cap = state.cached_rows.head_capacity
        in_head = victim_slots < head_cap
        n_promote = jnp.sum(active & in_head).astype(jnp.int32)
        n_demote = jnp.sum(evict_active & in_head).astype(jnp.int32)
    else:
        n_promote = jnp.zeros((), jnp.int32)
        n_demote = jnp.zeros((), jnp.int32)
    row_to_slot = state.row_to_slot.at[jnp.where(evict_active, victim_rows, vocab)].set(
        -1, mode="drop"
    )
    slot_to_row = state.slot_to_row.at[jnp.where(active, victim_slots, capacity)].set(
        jnp.where(active, miss_rows, -1), mode="drop"
    )
    row_to_slot = row_to_slot.at[jnp.where(active, miss_rows, vocab)].set(
        jnp.where(active, victim_slots, -1), mode="drop"
    )

    # --- recency / runtime-frequency bookkeeping ----------------------------
    touched_slots = row_to_slot.at[jnp.where(uniq_valid, uniq, 0)].get(mode="fill", fill_value=-1)
    touch = jnp.where(uniq_valid, touched_slots, capacity)
    last_used = state.last_used.at[touch].set(step, mode="drop")
    use_count = state.use_count.at[touch].add(1, mode="drop")
    # loaded rows start fresh
    fresh = jnp.where(active, victim_slots, capacity)
    use_count = use_count.at[fresh].set(1, mode="drop")
    if kf:
        # prefetched rows count as just-arrived so recency policies don't
        # evict them before their step comes up.
        last_used = last_used.at[jnp.where(active, victim_slots, capacity)].set(
            step, mode="drop"
        )

    # NB: negative indices WRAP in jax even with mode='fill'; mask explicitly.
    slots = jnp.where(
        valid, row_to_slot.at[jnp.where(valid, rows, 0)].get(mode="fill", fill_value=-1), -1
    )
    return CachePlan(
        miss_rows=miss_rows,
        victim_slots=victim_slots,
        victim_rows=victim_rows,
        load_active=active,
        evict_active=evict_active,
        slot_to_row=slot_to_row,
        row_to_slot=row_to_slot,
        last_used=last_used,
        use_count=use_count,
        step=step,
        # misses counts DEMAND misses only — a prefetched future row is not a
        # miss, so hit-rate telemetry keeps its meaning and shows the prefetch
        # benefit; transmitter traffic is visible via evictions + the movement
        # plan itself.  NB: hits/misses are recorded for the rows passed as
        # the CURRENT batch; under group scheduling (pipeline_depth > 1) only
        # group leaders run a plan, so telemetry samples 1/k of the traffic.
        hits=state.hits + id_hits.astype(jnp.int32),
        misses=state.misses + n_miss.astype(jnp.int32),
        uniq_rows=state.uniq_rows + n_uniq,
        evictions=state.evictions + jnp.sum(evict_active).astype(jnp.int32),
        uniq_overflows=state.uniq_overflows + overflow,
        tier_promotions=state.tier_promotions + n_promote,
        tier_demotions=state.tier_demotions + n_demote,
        tracker=tracker,
        slots=slots,
    )


@contract(donates=("full_rows", "state"), int_counters=INT_COUNTERS, max_sort_size=0)
def apply_plan(
    cfg: CacheConfig, full_rows: Any, state: CacheState, plan: CachePlan
) -> Tuple[Any, CacheState]:
    """Execute a ``CachePlan``: write back displaced rows, load missed rows,
    install the index image.  The only half that touches weights — in the
    pipelined trainer it runs after the previous step's row update so evicted
    rows carry their freshest values."""
    # chunk granularity applies to the SLOW-tier side only (the full table):
    # writebacks scatter into it, loads gather from it.  The cache side stays
    # row-granular — its slots are a permutation with no useful locality.
    if cfg.writeback:
        full_rows = transmitter.move_rows(
            state.cached_rows,
            full_rows,
            plan.victim_slots,
            plan.victim_rows,
            plan.evict_active,
            buffer_rows=cfg.buffer_rows,
            dst_chunk_rows=cfg.chunk_rows,
        )
    cached_rows = transmitter.move_rows(
        full_rows,
        state.cached_rows,
        plan.miss_rows,
        plan.victim_slots,
        plan.load_active,
        buffer_rows=cfg.buffer_rows,
        src_chunk_rows=cfg.chunk_rows,
    )
    new_state = CacheState(
        cached_rows=cached_rows,
        slot_to_row=plan.slot_to_row,
        row_to_slot=plan.row_to_slot,
        last_used=plan.last_used,
        use_count=plan.use_count,
        step=plan.step,
        hits=plan.hits,
        misses=plan.misses,
        uniq_rows=plan.uniq_rows,
        evictions=plan.evictions,
        uniq_overflows=plan.uniq_overflows,
        tier_promotions=plan.tier_promotions,
        tier_demotions=plan.tier_demotions,
        tracker=plan.tracker,
    )
    return full_rows, new_state


def prepare(
    cfg: CacheConfig,
    full_rows: Any,
    state: CacheState,
    rows: jnp.ndarray,
    future_rows: Optional[jnp.ndarray] = None,
) -> Tuple[Any, CacheState, jnp.ndarray]:
    """Algorithm 1 ``PrepareCache``: make every row of ``rows`` resident.

    Args:
      full_rows: the full (freq-ordered) table — a raw pytree with leaves
        [vocab, ...] or a ``repro.store.HostStore`` holding the same leaves
        encoded (misses are dequantized on load, evictions quantized on
        writeback, inside the transmitter rounds).
      rows: int32 [ids_per_step] freq-ranked row per id (-1 padding). Callers
        translate raw ids through ``idx_map`` first.
      future_rows: optional lookahead window of future-batch rows (see
        ``plan_prepare``) — prefetched alongside the current batch's misses.

    Returns (full_rows', state', slots) where ``slots`` maps each input lane to
    its resident cache slot (-1 for padding lanes).
    """
    plan = plan_prepare(cfg, state, rows, future_rows=future_rows)
    full_rows, new_state = apply_plan(cfg, full_rows, state, plan)
    return full_rows, new_state, plan.slots


def lookup_slots(state: CacheState, slots: jnp.ndarray, leaf: str | int = 0) -> jnp.ndarray:
    """Gather cached rows by slot; -1 (padding) lanes return zero rows.

    On a tiered arena the gather is decode-on-read: head lanes come back
    bit-exact, tail lanes dequantized — same zero-fill convention."""
    cached = state.cached_rows
    if isinstance(cached, ArenaStore):
        keys = sorted(set(cached.head) | set(cached.raw))
        key = keys[leaf] if isinstance(leaf, int) else leaf
        return cached.gather_slots(slots)[key]
    leaves = jax.tree_util.tree_leaves(cached)
    w = leaves[leaf] if isinstance(leaf, int) else cached[leaf]
    safe = jnp.where(slots >= 0, slots, w.shape[0])  # negatives would wrap
    return jnp.take(w, safe, axis=0, mode="fill", fill_value=0)


@contract(donates=("full_rows",), int_counters=INT_COUNTERS, max_sort_size=0)
def flush(cfg: CacheConfig, full_rows: Any, state: CacheState) -> Tuple[Any, CacheState]:
    """Write every resident row back to the full table (checkpoint barrier).

    After ``flush`` the full table is authoritative; the cache stays warm
    (rows remain resident and clean).
    """
    # geometry from the STATE, like ``prepare``: a serve-time cfg may quote a
    # different capacity/vocab than the state it operates on.
    capacity = state.slot_to_row.shape[0]
    slots = jnp.arange(capacity, dtype=jnp.int32)
    rows = state.slot_to_row
    active = rows >= 0
    full_rows = transmitter.move_rows(
        state.cached_rows,
        full_rows,
        slots,
        rows,
        active,
        buffer_rows=cfg.buffer_rows,
        dst_chunk_rows=cfg.chunk_rows,
    )
    return full_rows, state


@contract(donates=("state",), int_counters=INT_COUNTERS, max_sort_size=0)
def warmup(
    cfg: CacheConfig, full_rows: Any, state: CacheState
) -> Tuple[Any, CacheState]:
    """Paper §4.3 cache warm-up: pre-fill with the hottest (lowest-rank) rows."""
    # geometry from the STATE (see ``prepare``/``flush``): cfg capacity/vocab
    # may be stale relative to the arrays being warmed.
    capacity = state.slot_to_row.shape[0]
    vocab = state.row_to_slot.shape[0]
    n = min(capacity, vocab)
    rows = jnp.arange(capacity, dtype=jnp.int32)
    active = rows < n
    rows = jnp.where(active, rows, -1)
    slots = jnp.arange(capacity, dtype=jnp.int32)
    cached_rows = transmitter.move_rows(
        full_rows,
        state.cached_rows,
        rows,
        slots,
        active,
        buffer_rows=cfg.buffer_rows,
        src_chunk_rows=cfg.chunk_rows,
    )
    slot_to_row = jnp.where(active, rows, -1).astype(jnp.int32)
    row_to_slot = state.row_to_slot.at[jnp.where(active, rows, vocab)].set(
        jnp.where(active, slots, -1), mode="drop"
    )
    return full_rows, dataclasses.replace(
        state,
        cached_rows=cached_rows,
        slot_to_row=slot_to_row,
        row_to_slot=row_to_slot,
    )
