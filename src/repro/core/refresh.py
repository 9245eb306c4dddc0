"""Incremental re-ranking refresh: the adaptive half of the frequency module.

The paper's FREQ_LFU rank is frozen at init, so when the hot set drifts the
cache keeps protecting yesterday's hot rows (the regime runtime re-tiering
targets — Ren et al., ML-guided memory optimization for DLRM inference on
tiered memory).  This module closes the loop: every N steps a host-side pass
reads the online decayed counters (:class:`repro.core.freq.FreqTracker`,
updated in-jit by ``cache.plan_prepare``), re-ranks, and applies a BOUNDED
incremental permutation — at most ``max_swaps`` rank pairs, and only pairs
that cross the cache-capacity boundary (a swap that stays inside the hot or
the cold region cannot change any eviction outcome under FREQ_LFU, so it is
pure churn and never emitted).

A refresh is *pure reindexing*: ranks are names, not values.  Each swap

  1. writes the pair's resident rows (if any) back to the slow tier at their
     OLD rank positions (the dirty resident copy is authoritative — with a
     quantized host store this is the one codec round trip a refresh costs,
     which is why refresh purity is bitwise for fp32 and codec-noise-bounded
     for fp16/int8);
  2. invalidates their residency (``slot_to_row``/``row_to_slot`` -> -1; the
     rows simply re-fault on next use — empty slots evict first, so the freed
     slots are the next victims anyway);
  3. swaps the slow-tier payload+sideband rows and the tracker slices, and
     remaps ``idx_map`` through the rank permutation.

Model outputs are bitwise unchanged across the call (fp32): every raw id
still resolves — through the new ``idx_map`` and the permuted slow tier — to
exactly the value it resolved to before.  What changes is the FUTURE: the
promoted rows now live at hot ranks, so FREQ_LFU stops thrash-evicting them.

Sharded collections use the same plan; physical rows live at fixed
``(owner shard, local row)`` homes keyed by rank (``rank_owner``/
``rank_local`` never change), so a swap moves slow-tier row CONTENT between
the two ranks' homes — a cross-shard row exchange when the homes differ,
metered by ``RefreshConfig.exchange_budget`` (pairs beyond the budget are
deferred to the next refresh; same-shard pairs are always applied).  With one
shard the homes are the ranks themselves and the pass is bit-identical to
the unsharded one.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.contracts import INT_COUNTERS, contract
from repro.core import freq as freq_lib
from repro.core import transmitter
from repro.store import HostStore

__all__ = [
    "RefreshConfig",
    "RefreshReport",
    "plan_swaps",
    "refresh_cached_slab",
    "refresh_sharded_slab",
]


@dataclasses.dataclass(frozen=True)
class RefreshConfig:
    """Knobs of one refresh pass (per slab)."""

    max_swaps: int = 256  # bounded top-K rank pairs per slab per refresh
    min_gain: float = 0.0  # extra decayed mass a cold row must carry over the
    # hot row it displaces (hysteresis against boundary flapping; the
    # comparison is already strict, so 0.0 only suppresses exact ties)
    exchange_budget: Optional[int] = None  # sharded: max slow-tier rows moved
    # ACROSS shards per refresh (2 per cross-shard pair); None = unbounded,
    # 0 = same-shard swaps only.  Unsharded slabs ignore it.
    rebalance_threshold: Optional[float] = None  # sharded: when the LIVE
    # routed-traffic imbalance (max/mean of per-shard decayed tracker mass)
    # exceeds this after the swap pass, re-run ``assign_devices`` on the live
    # scores and re-home every rank (``_apply_rebalance``).  None = homes
    # stay where init placed them (the historical behavior).


@dataclasses.dataclass
class RefreshReport:
    """Host-side summary of one collection-wide refresh pass (per slab)."""

    swaps: Dict[str, int] = dataclasses.field(default_factory=dict)
    rows_moved: Dict[str, int] = dataclasses.field(default_factory=dict)
    cross_shard_rows: Dict[str, int] = dataclasses.field(default_factory=dict)
    deferred_swaps: Dict[str, int] = dataclasses.field(default_factory=dict)
    rebalance_moves: Dict[str, int] = dataclasses.field(default_factory=dict)
    rebalance_imbalance: Dict[str, float] = dataclasses.field(default_factory=dict)

    def add(self, slab: str, stats: Dict[str, int]) -> None:
        self.swaps[slab] = stats["swaps"]
        self.rows_moved[slab] = stats["rows_moved"]
        self.cross_shard_rows[slab] = stats.get("cross_shard_rows", 0)
        self.deferred_swaps[slab] = stats.get("deferred_swaps", 0)
        self.rebalance_moves[slab] = stats.get("rebalance_moves", 0)
        self.rebalance_imbalance[slab] = stats.get("rebalance_imbalance", 1.0)

    @property
    def total_swaps(self) -> int:
        return sum(self.swaps.values())

    @property
    def total_rows_moved(self) -> int:
        return sum(self.rows_moved.values())


def plan_swaps(
    scores: np.ndarray,
    hot: np.ndarray,
    max_swaps: int,
    min_gain: float = 0.0,
) -> Tuple[np.ndarray, np.ndarray]:
    """Pick the bounded set of capacity-boundary rank swaps.

    ``scores`` are the decayed access masses in CURRENT rank order and
    ``hot`` marks the ranks inside the cache-capacity (warm-set) boundary.
    Pairs the coldest hot ranks against the hottest cold ranks, hottest
    mismatch first, and keeps a pair only while the cold row's mass exceeds
    the hot row's by more than ``min_gain`` — gains are non-increasing along
    the pairing, so the kept set is a prefix.  Deterministic: stable sorts
    with rank tie-breaks (every host derives the identical plan, the same
    requirement ``build_freq_stats`` meets).

    Returns ``(a, b)``: demoted hot ranks and promoted cold ranks, pairwise.
    """
    hot = np.asarray(hot, bool)
    hot_idx = np.nonzero(hot)[0]
    cold_idx = np.nonzero(~hot)[0]
    k = min(int(max_swaps), hot_idx.size, cold_idx.size)
    if k <= 0:
        return np.empty((0,), np.int64), np.empty((0,), np.int64)
    s = np.asarray(scores, np.float64)
    # coldest hot ranks first; score ties -> larger rank first (the row the
    # old ranking already believed colder)
    order_h = np.lexsort((-hot_idx, s[hot_idx]))
    # hottest cold ranks first; score ties -> smaller rank first
    order_c = np.lexsort((cold_idx, -s[cold_idx]))
    a = hot_idx[order_h[:k]]
    b = cold_idx[order_c[:k]]
    keep = s[b] > s[a] + min_gain
    n = int(np.argmax(~keep)) if not keep.all() else k  # first rejected pair
    return a[:n].astype(np.int64), b[:n].astype(np.int64)


def _permute_rows(tree: Any, to: jnp.ndarray, frm: jnp.ndarray) -> Any:
    """Scatter-swap: row ``to[i]`` of every leaf takes row ``frm[i]``'s
    content (O(swaps) rows touched, not O(vocab)); OOB ``to`` lanes drop."""
    def perm(leaf):
        return leaf.at[to].set(leaf[frm], mode="drop")

    return jax.tree_util.tree_map(perm, tree)


def _permute_store(full: Any, to: jnp.ndarray, frm: jnp.ndarray) -> Any:
    """Permute slow-tier rows.  A ``HostStore`` permutes payload AND sideband
    ENCODED — no decode/re-encode, so the move itself is bit-exact for every
    codec; raw pytrees permute in place."""
    if isinstance(full, HostStore):
        return HostStore(
            data=_permute_rows(full.data, to, frm),
            sideband=_permute_rows(full.sideband, to, frm),
            codec=full.codec,
            out_dtype=full.out_dtype,
        )
    return _permute_rows(full, to, frm)


# ---------------------------------------------------------------------------
# unsharded slab surgery
# ---------------------------------------------------------------------------


@contract(int_counters=INT_COUNTERS)
@functools.partial(jax.jit, static_argnames=("buffer_rows", "writeback"))
def _apply_swaps(
    full: Any,
    cache: Any,
    idx_map: jnp.ndarray,
    a: jnp.ndarray,  # int32 [K] demoted hot ranks (-1 padding)
    b: jnp.ndarray,  # int32 [K] promoted cold ranks (-1 padding)
    valid: jnp.ndarray,  # bool [K]
    *,
    buffer_rows: int,
    writeback: bool,
):
    """Jitted state surgery for one swap set (padded to a static K so a slab
    compiles once): write back, invalidate, permute, remap.  Returns
    ``(full', cache', idx_map')``."""
    vocab = cache.row_to_slot.shape[0]
    capacity = cache.slot_to_row.shape[0]
    involved = jnp.concatenate([a, b])
    inv_valid = jnp.concatenate([valid, valid])
    # 1) write the pairs' dirty resident rows back at their OLD ranks
    slots = cache.row_to_slot.at[
        jnp.where(inv_valid, involved, 0)
    ].get(mode="fill", fill_value=-1)
    slots = jnp.where(inv_valid, slots, -1)
    active = slots >= 0
    if writeback:
        full = transmitter.move_rows(
            cache.cached_rows, full, slots, involved, active,
            buffer_rows=buffer_rows,
        )
    # 2) invalidate residency (the rows re-fault at their new ranks)
    slot_to_row = cache.slot_to_row.at[
        jnp.where(active, slots, capacity)
    ].set(-1, mode="drop")
    row_to_slot = cache.row_to_slot.at[
        jnp.where(inv_valid, involved, vocab)
    ].set(-1, mode="drop")
    # 3) swap slow-tier rows + tracker slices; remap idx_map through P
    to = jnp.where(inv_valid, involved, vocab)
    frm = jnp.where(inv_valid, jnp.concatenate([b, a]), 0)
    full = _permute_store(full, to, frm)
    tr = cache.tracker
    tr = dataclasses.replace(
        tr,
        score=_permute_rows(tr.score, to, frm),
        last_touch=_permute_rows(tr.last_touch, to, frm),
        refresh_swaps=tr.refresh_swaps + jnp.sum(valid).astype(jnp.int32),
        refresh_rows=tr.refresh_rows + jnp.sum(inv_valid).astype(jnp.int32),
    )
    perm = jnp.arange(vocab, dtype=jnp.int32)
    perm = perm.at[jnp.where(valid, a, vocab)].set(
        b.astype(jnp.int32), mode="drop"
    )
    perm = perm.at[jnp.where(valid, b, vocab)].set(
        a.astype(jnp.int32), mode="drop"
    )
    idx_map = perm[idx_map]
    cache = dataclasses.replace(
        cache, slot_to_row=slot_to_row, row_to_slot=row_to_slot, tracker=tr
    )
    return full, cache, idx_map


def _pad_pairs(a: np.ndarray, b: np.ndarray, k: int):
    """Pad a swap set to the static length ``k`` (-1 / False padding)."""
    valid = np.zeros((k,), bool)
    valid[: a.size] = True
    ap = np.full((k,), -1, np.int32)
    bp = np.full((k,), -1, np.int32)
    ap[: a.size] = a
    bp[: b.size] = b
    return jnp.asarray(ap), jnp.asarray(bp), jnp.asarray(valid)


def refresh_cached_slab(
    ccfg, slab, cfg: RefreshConfig, writeback: bool = True
) -> Tuple[Any, Dict[str, int]]:
    """One refresh pass over an unsharded ``collection.CachedSlab``.

    ``ccfg`` is the slab's ``cache.CacheConfig`` (half-life + buffer size;
    geometry comes from the STATE, as everywhere in ``core.cache``).  The
    swap planning runs host-side on device_get'd counters; the state surgery
    is one jitted call on swap arrays padded to ``cfg.max_swaps`` (compiled
    once per slab geometry).  With ``writeback=False`` (read-only serve
    states) resident rows are clean, so the write-back step is skipped and
    only the invalidate+permute runs.  Returns ``(slab', stats)``; a no-swap
    pass returns the slab unchanged.
    """
    if isinstance(slab.full, HostStore) and slab.full.on_host:
        # ``_apply_swaps`` returns a new slow tier (it donates nothing): a
        # host-resident table would be held twice in host memory
        raise ValueError("refresh swaps rows of a slow tier on the device; this "
                         "slab's slow tier is the host tier (pinned_host memory)")
    cache = slab.cache
    capacity = int(cache.slot_to_row.shape[0])
    vocab = int(cache.row_to_slot.shape[0])
    step = int(jax.device_get(cache.step))
    tr = cache.tracker
    scores = freq_lib.decayed_scores(
        jax.device_get(tr.score), jax.device_get(tr.last_touch), step,
        ccfg.freq_half_life,
    )
    hot = np.arange(vocab) < capacity
    a, b = plan_swaps(scores, hot, cfg.max_swaps, cfg.min_gain)
    if a.size == 0:
        return slab, {"swaps": 0, "rows_moved": 0}
    ap, bp, valid = _pad_pairs(a, b, int(cfg.max_swaps))
    full, new_cache, idx_map = _apply_swaps(
        slab.full, cache, slab.idx_map, ap, bp, valid,
        buffer_rows=ccfg.buffer_rows, writeback=writeback,
    )
    new_slab = dataclasses.replace(
        slab, full=full, cache=new_cache, idx_map=idx_map
    )
    return new_slab, {"swaps": int(a.size), "rows_moved": int(2 * a.size)}


# ---------------------------------------------------------------------------
# sharded slab surgery
# ---------------------------------------------------------------------------


def _flat_view(full: Any) -> Any:
    """Shard-stacked slow tier ([S, vs, ...] leaves) as a flat [S*vs, ...]
    tree/store, so flat home ``owner * vs + local`` addresses rows."""
    def rs(v):
        return v.reshape((-1,) + v.shape[2:])

    if isinstance(full, HostStore):
        return HostStore(
            data={k: rs(v) for k, v in full.data.items()},
            sideband={k: rs(v) for k, v in full.sideband.items()},
            codec=full.codec,
            out_dtype=full.out_dtype,
        )
    return jax.tree_util.tree_map(rs, full)


def _restack_like(flat: Any, like: Any) -> Any:
    """Inverse of :func:`_flat_view`: reshape a flat tree/store back to the
    shard-stacked leaf shapes of ``like``."""
    if isinstance(flat, HostStore):
        return HostStore(
            data={k: v.reshape(like.data[k].shape) for k, v in flat.data.items()},
            sideband={
                k: v.reshape(like.sideband[k].shape) for k, v in flat.sideband.items()
            },
            codec=flat.codec,
            out_dtype=flat.out_dtype,
        )
    return jax.tree_util.tree_map(lambda v, l: v.reshape(l.shape), flat, like)


def _read_flat_rows(full: Any, idx: jnp.ndarray) -> jnp.ndarray:
    """Decoded ``weight`` rows at flat homes ``idx`` (-1 lanes -> zero rows)
    of a stacked slow tier."""
    flat = _flat_view(full)
    if isinstance(flat, HostStore):
        return transmitter._gather_store_rows(flat, idx)["weight"]
    return transmitter.gather_rows(flat, idx)["weight"]


@contract(int_counters=INT_COUNTERS)
@functools.partial(jax.jit, static_argnames=("buffer_rows", "writeback"))
def _apply_swaps_sharded(
    full: Any,
    cache: Any,
    idx_map: jnp.ndarray,
    rep: Any,  # sharded.RepArena (or None): the slab's replicated hot head
    rows_img: jnp.ndarray,  # int32 [S, 2K] involved local rows (-1 off-shard)
    pa: jnp.ndarray,  # int32 [K] flat home of each demoted rank (-1 pad)
    pb: jnp.ndarray,  # int32 [K] flat home of each promoted rank (-1 pad)
    a: jnp.ndarray,  # int32 [K] demoted ranks (-1 pad)
    b: jnp.ndarray,  # int32 [K] promoted ranks (-1 pad)
    valid: jnp.ndarray,  # bool [K]
    swaps_ps: jnp.ndarray,  # int32 [S] per-shard swap shares (telemetry)
    rows_ps: jnp.ndarray,  # int32 [S] per-shard moved-row shares
    *,
    buffer_rows: int,
    writeback: bool,
):
    """Jitted sharded surgery (padded to static K; compiled once per slab):
    per-shard write-back + invalidate under ``vmap``, then the flat content
    exchange between the swapped ranks' fixed homes.

    Replicated boundary: a demoted rank ``a < K`` lives in the replicated
    arena, whose row (SGD-updated every step) and tracker slice are the
    authoritative copies — any per-shard cache copy of its home never
    diverges from init.  Before the home exchange the arena row + tracker
    slice are pushed into the rank's home (so the exchange carries them to
    the promoted rank's cold home); after it, the arena pulls the promoted
    content back from the now-swapped home.  The arena and per-shard plan
    clocks tick together, so raw (score, last_touch) interchange is exact."""
    S, vs = cache.row_to_slot.shape
    cap = cache.slot_to_row.shape[1]
    K = int(rep.rows.shape[0]) if rep is not None else 0
    vocab = idx_map.shape[0]

    def shard_surgery(full_s, cache_s, rows_s):
        slots = cache_s.row_to_slot.at[
            jnp.where(rows_s >= 0, rows_s, 0)
        ].get(mode="fill", fill_value=-1)
        slots = jnp.where(rows_s >= 0, slots, -1)
        act = slots >= 0
        if writeback:
            full_s = transmitter.move_rows(
                cache_s.cached_rows, full_s, slots, rows_s, act,
                buffer_rows=buffer_rows,
            )
        row_to_slot = cache_s.row_to_slot.at[
            jnp.where(rows_s >= 0, rows_s, vs)
        ].set(-1, mode="drop")
        slot_to_row = cache_s.slot_to_row.at[
            jnp.where(act, slots, cap)
        ].set(-1, mode="drop")
        return full_s, dataclasses.replace(
            cache_s, row_to_slot=row_to_slot, slot_to_row=slot_to_row
        )

    full, cache = jax.vmap(shard_surgery)(full, cache, rows_img)

    def fput(leaf2d, idx, vals):
        fl = leaf2d.reshape((-1,) + leaf2d.shape[2:])
        return fl.at[idx].set(vals, mode="drop").reshape(leaf2d.shape)

    if K:
        # demoted replicated ranks: push the arena's authoritative row +
        # tracker slice into the rank's home (overwrites any never-diverged
        # cache writeback above) so the generic exchange carries them.
        am = valid & (a >= 0) & (a < K)
        src = jnp.where(am, a, K)
        dst = jnp.where(am, pa, S * vs)
        if writeback:
            rows_push = jnp.take(rep.rows, src, axis=0, mode="fill", fill_value=0)
            flatf = transmitter.write_rows(
                {"weight": rows_push}, _flat_view(full), dst, am,
                buffer_rows=buffer_rows,
            )
            full = _restack_like(flatf, full)
        tr0 = cache.tracker
        cache = dataclasses.replace(
            cache,
            tracker=dataclasses.replace(
                tr0,
                score=fput(tr0.score, dst,
                           jnp.take(rep.score, src, mode="fill", fill_value=0)),
                last_touch=fput(tr0.last_touch, dst,
                                jnp.take(rep.last_touch, src, mode="fill",
                                         fill_value=0)),
            ),
        )

    # swap slow-tier content between the two ranks' flat homes
    vv = jnp.concatenate([valid, valid])
    to = jnp.where(vv, jnp.concatenate([pa, pb]), S * vs)
    frm = jnp.where(vv, jnp.concatenate([pb, pa]), 0)

    def flat_perm(leaf):
        flatl = leaf.reshape((-1,) + leaf.shape[2:])
        flatl = flatl.at[to].set(flatl[frm], mode="drop")
        return flatl.reshape(leaf.shape)

    if isinstance(full, HostStore):
        full = HostStore(
            data={k: flat_perm(v) for k, v in full.data.items()},
            sideband={k: flat_perm(v) for k, v in full.sideband.items()},
            codec=full.codec,
            out_dtype=full.out_dtype,
        )
    else:
        full = jax.tree_util.tree_map(flat_perm, full)
    tr = cache.tracker
    tr = dataclasses.replace(
        tr,
        score=flat_perm(tr.score),
        last_touch=flat_perm(tr.last_touch),
        refresh_swaps=tr.refresh_swaps + swaps_ps,
        refresh_rows=tr.refresh_rows + rows_ps,
    )
    cache = dataclasses.replace(cache, tracker=tr)

    if K:
        # pull the promoted content back into the arena: after the exchange,
        # home of rank a holds the promoted raw id's row + tracker slice.
        idxp = jnp.where(am, pa, -1)
        rows_new = _read_flat_rows(full, idxp)
        arena_dst = jnp.where(am, a, K)
        flsc = tr.score.reshape(-1)
        fllt = tr.last_touch.reshape(-1)
        safe = jnp.where(am, pa, 0)
        rep = dataclasses.replace(
            rep,
            rows=rep.rows.at[arena_dst].set(
                rows_new.astype(rep.rows.dtype), mode="drop"
            ),
            score=rep.score.at[arena_dst].set(flsc[safe], mode="drop"),
            last_touch=rep.last_touch.at[arena_dst].set(fllt[safe], mode="drop"),
        )

    perm = jnp.arange(vocab, dtype=jnp.int32)
    perm = perm.at[jnp.where(valid, a, vocab)].set(
        b.astype(jnp.int32), mode="drop"
    )
    perm = perm.at[jnp.where(valid, b, vocab)].set(
        a.astype(jnp.int32), mode="drop"
    )
    idx_map = perm[idx_map]
    return full, cache, idx_map, rep


def refresh_sharded_slab(
    ccfg, slab, cfg: RefreshConfig, writeback: bool = True
) -> Tuple[Any, Dict[str, int]]:
    """One refresh pass over a ``sharded.ShardedSlab``.

    Rank homes (``rank_owner``/``rank_local``) are FIXED — a swap exchanges
    slow-tier row content between the two ranks' physical homes, so the
    balance ``assign_devices`` computed for the hot positions is inherited by
    whichever rows are hot now.  Pairs whose homes sit on different shards
    are cross-shard row exchanges, metered by ``cfg.exchange_budget`` (kept
    pairs stay a prefix of the gain ordering among same-shard pairs plus the
    budget-affordable cross-shard ones).  With ``num_shards == 1`` every
    quantity reduces to the unsharded pass bit-for-bit.
    """
    cache = slab.cache
    rep = getattr(slab, "rep", None)
    K = int(rep.rows.shape[0]) if rep is not None else 0
    S, vs = cache.row_to_slot.shape
    cap = int(cache.slot_to_row.shape[1])
    steps = np.asarray(jax.device_get(cache.step))  # [S]; equal across shards
    tr = cache.tracker
    local_scores = freq_lib.decayed_scores(
        jax.device_get(tr.score), jax.device_get(tr.last_touch),
        steps[:, None], ccfg.freq_half_life,
    )  # [S, vs]
    owner = np.asarray(jax.device_get(slab.rank_owner), np.int64)
    local = np.asarray(jax.device_get(slab.rank_local), np.int64)
    vocab = owner.shape[0]
    scores = local_scores[owner, local]  # [vocab], rank order
    if K:
        # replicated ranks bypass the per-shard plans, so their signal lives
        # in the arena tracker (same plan clock as the per-shard caches).
        scores[:K] = freq_lib.decayed_scores(
            jax.device_get(rep.score), jax.device_get(rep.last_touch),
            float(jax.device_get(rep.step)), ccfg.freq_half_life,
        )
    # hot = inside the per-shard warm boundary OR in the replicated arena —
    # the swap set crosses the replicated boundary like the capacity one.
    hot = (local < cap) | (np.arange(vocab) < K)
    a, b = plan_swaps(scores, hot, cfg.max_swaps, cfg.min_gain)
    if a.size and cfg.exchange_budget is not None:
        cross = owner[a] != owner[b]
        keep = ~cross | (np.cumsum(cross) * 2 <= cfg.exchange_budget)
        deferred = int((~keep).sum())
        a, b = a[keep], b[keep]
    else:
        deferred = 0
    if a.size == 0:
        return slab, {"swaps": 0, "rows_moved": 0, "cross_shard_rows": 0,
                      "deferred_swaps": deferred}

    k = int(cfg.max_swaps)
    involved = np.concatenate([a, b])
    # per-shard image of the involved ranks' local rows (-1 off-shard/pad)
    rows_img = np.full((S, 2 * k), -1, np.int32)
    rows_img[owner[involved], np.arange(involved.size)] = local[involved]
    # flat homes, padded to the static K
    pa = np.full((k,), -1, np.int32)
    pb = np.full((k,), -1, np.int32)
    pa[: a.size] = owner[a] * vs + local[a]
    pb[: b.size] = owner[b] * vs + local[b]
    ap, bp, valid = _pad_pairs(a, b, k)
    # per-shard counter shares: swaps by the demoted (hot) rank's home, rows
    # by each changed home — both sum to the collection-wide totals.
    swaps_ps = np.bincount(owner[a], minlength=S).astype(np.int32)
    rows_ps = np.bincount(owner[involved], minlength=S).astype(np.int32)
    full, new_cache, idx_map, new_rep = _apply_swaps_sharded(
        slab.full, cache, slab.idx_map, rep, jnp.asarray(rows_img),
        jnp.asarray(pa), jnp.asarray(pb), ap, bp, valid,
        jnp.asarray(swaps_ps), jnp.asarray(rows_ps),
        buffer_rows=ccfg.buffer_rows, writeback=writeback,
    )
    kw = {"rep": new_rep} if rep is not None else {}
    new_slab = dataclasses.replace(
        slab, full=full, cache=new_cache, idx_map=idx_map, **kw
    )
    cross_rows = int(2 * np.sum(owner[a] != owner[b]))
    return new_slab, {
        "swaps": int(a.size),
        "rows_moved": int(involved.size),
        "cross_shard_rows": cross_rows,
        "deferred_swaps": deferred,
    }


# ---------------------------------------------------------------------------
# traffic-aware re-homing (sharded re-balance)
# ---------------------------------------------------------------------------


@contract(int_counters=INT_COUNTERS)
@functools.partial(jax.jit, static_argnames=("buffer_rows", "writeback"))
def _apply_rebalance(
    full: Any,
    cache: Any,
    src_for_dest: jnp.ndarray,  # int32 [S*vs] new flat home -> old flat home
    *,
    buffer_rows: int,
    writeback: bool,
):
    """Jitted re-home surgery for one sharded slab: write every resident row
    back (the dirty cache copy is authoritative), drop all residency, then
    permute the slow tier + tracker flat rows old-home -> new-home.

    ``src_for_dest`` is a full [S*vs] gather map (identity on positions that
    stay put or are padding).  Moving ENCODED payload + sideband keeps the
    move itself bit-exact for every codec; rank identities (``idx_map``) are
    untouched — this is re-homing, not re-ranking — so lookups through the
    caller-installed new ``rank_owner``/``rank_local`` resolve to exactly the
    pre-rebalance values (codec round trip for dirty rows, as everywhere).
    The caller re-warms the emptied per-shard caches afterwards."""
    cap = cache.slot_to_row.shape[1]

    def shard_flush(full_s, cache_s):
        slots = jnp.arange(cap, dtype=jnp.int32)
        rows = cache_s.slot_to_row
        act = rows >= 0
        if writeback:
            full_s = transmitter.move_rows(
                cache_s.cached_rows, full_s, slots, rows, act,
                buffer_rows=buffer_rows,
            )
        return full_s, dataclasses.replace(
            cache_s,
            slot_to_row=jnp.full_like(cache_s.slot_to_row, -1),
            row_to_slot=jnp.full_like(cache_s.row_to_slot, -1),
        )

    full, cache = jax.vmap(shard_flush)(full, cache)

    def flat_perm(leaf):
        flatl = leaf.reshape((-1,) + leaf.shape[2:])
        return flatl[src_for_dest].reshape(leaf.shape)

    if isinstance(full, HostStore):
        full = HostStore(
            data={k: flat_perm(v) for k, v in full.data.items()},
            sideband={k: flat_perm(v) for k, v in full.sideband.items()},
            codec=full.codec,
            out_dtype=full.out_dtype,
        )
    else:
        full = jax.tree_util.tree_map(flat_perm, full)
    tr = cache.tracker
    tr = dataclasses.replace(
        tr, score=flat_perm(tr.score), last_touch=flat_perm(tr.last_touch)
    )
    cache = dataclasses.replace(cache, tracker=tr)
    return full, cache
