"""XLA reference implementations of the cache hot-path ops.

Every function here is bit-identical to the historical ``jnp.unique`` /
full-capacity ``jnp.argsort`` route it replaces (property-tested in
``tests/test_cache_ops.py``), while staying O(K)-sorted instead of
O(capacity)-sorted:

* ``victim_topk`` — the K worst eviction keys via a 32-round bitwise
  threshold descent (count-based radix select) + a K-sized final sort.  The
  only sort is over ``kv`` lanes; the capacity-sized work is compare/sum
  passes, which is exactly what the Pallas tiled reducer streams on TPU.
* ``dedup`` — ``jnp.unique(size=k, fill_value=s)`` from ONE ``jnp.sort``
  (flag first occurrences, cumsum-compact), sharing the sorted buffer with
  the overflow count the caller previously paid a second sort for.
* ``compact_front`` / ``merge_candidates`` — the stable miss-compaction
  argsorts replaced by cumsum scatters and a lane select.
* ``arena_gather`` — the tiered-arena decode-on-read gather as one function
  of raw leaves (head + tail payload + sideband), so the transmitter and
  ``ArenaStore.gather_slots`` share a single fusable body.

These run as the CPU fast path; the Pallas kernels in ``kernel.py`` lower
the same math for the TPU and are verified bit-identical against this
module in interpret mode (tests) and compiled (``chip_smoke.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "PlanImage",
    "arena_gather",
    "bucketize",
    "compact_front",
    "dedup",
    "merge_candidates",
    "plan_image",
    "victim_topk",
]

_SIGN = jnp.uint32(0x80000000)


def ordered_u32(key: jnp.ndarray) -> jnp.ndarray:
    """Order-preserving int32 -> uint32 transform (flip the sign bit)."""
    return key.astype(jnp.uint32) ^ _SIGN


def victim_topk(key: jnp.ndarray, kv: int) -> jnp.ndarray:
    """Indices of the ``kv`` largest entries of ``key`` in stable descending
    order — bit-identical to ``jnp.argsort(key, descending=True)[:kv]``
    (ties broken by ascending index) without sorting all of ``key``.

    Three stages, mirroring the Pallas streaming reducer:
      1. threshold: 32-round bitwise descent finds ``t`` = the kv-th largest
         value (each round one masked count over the array);
      2. select: lanes with ``key > t`` plus the first ``kv - n_gt`` ties at
         ``t`` (exclusive cumsum rank), compacted index-ascending by binary
         search over the selection's inclusive cumsum (a gather — XLA CPU
         serializes scatters, and exactly ``kv`` lanes are selected, so
         every query hits);
      3. order: ONE ``kv``-sized stable descending argsort of the selected
         keys — index-ascending compaction makes it reproduce the full
         argsort's tie order exactly.
    """
    kv = int(kv)
    u = ordered_u32(key)

    def bit_step(i, t):
        cand = t | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        cnt = jnp.sum((u >= cand).astype(jnp.int32))
        return jnp.where(cnt >= kv, cand, t)

    t = jax.lax.fori_loop(0, 32, bit_step, jnp.uint32(0))
    n_gt = jnp.sum((u > t).astype(jnp.int32))
    return topk_select(u, t, n_gt, key, kv)


def topk_select(
    u: jnp.ndarray, t: jnp.ndarray, n_gt: jnp.ndarray, key: jnp.ndarray, kv: int
) -> jnp.ndarray:
    """Stages 2+3 of ``victim_topk`` given the threshold ``t`` and the
    strictly-greater count ``n_gt`` (also the epilogue of the Pallas
    threshold kernel)."""
    kv = int(kv)
    eq = (u == t).astype(jnp.int32)
    eq_rank = jnp.cumsum(eq) - eq  # exclusive rank among ties
    sel = (u > t) | ((eq == 1) & (eq_rank < kv - n_gt))
    csel = jnp.cumsum(sel.astype(jnp.int32))  # inclusive; csel[-1] == kv
    slots = jnp.searchsorted(
        csel, jnp.arange(1, kv + 1, dtype=jnp.int32)
    ).astype(jnp.int32)
    order = jnp.argsort(key[slots], descending=True)  # kv-sized, stable
    return slots[order]


def dedup(rows: jnp.ndarray, k: int, fill: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``jnp.unique(rows, size=k, fill_value=fill)`` plus the TRUE distinct
    count, from one sort.  ``fill`` must be the maximum sentinel the caller
    pads with (``int32 max`` in the cache; ``_PAD_RANK`` in the sharded
    router) — sentinel lanes are excluded from the count and collapse into
    the padding, exactly like the historical unique-then-count-again route.

    Returns ``(uniq, n_distinct)``: ``uniq`` ascending, ``fill``-padded.
    """
    k = int(k)
    srt = jnp.sort(rows)
    first = jnp.concatenate(
        [jnp.ones((1,), bool), jnp.diff(srt) != 0]
    ) & (srt != fill)
    n_distinct = jnp.sum(first.astype(jnp.int32))
    pos = jnp.cumsum(first.astype(jnp.int32)) - 1
    uniq = jnp.full((k,), fill, rows.dtype).at[
        jnp.where(first & (pos < k), pos, k)
    ].set(srt, mode="drop")
    return uniq, n_distinct


def compact_front(mask: jnp.ndarray, values: jnp.ndarray, out_len: int) -> jnp.ndarray:
    """``values[jnp.argsort(~mask, stable=True)][:out_len]`` on the masked
    lanes — i.e. masked values compacted to the front in original order —
    as a cumsum scatter (lanes past the masked count are -1; callers mask
    them with their own ``active`` select, like the argsort route did)."""
    out_len = int(out_len)
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
    return jnp.full((out_len,), -1, values.dtype).at[
        jnp.where(mask & (pos < out_len), pos, out_len)
    ].set(values, mode="drop")


def merge_candidates(
    now: jnp.ndarray, n_now: jnp.ndarray, fut: jnp.ndarray, kv: int
) -> jnp.ndarray:
    """Lane ``j`` of the merged candidate list: current-batch compacted
    misses first (``j < n_now``), then lookahead compacted misses — the
    select-form of the historical priority-argsort over the concatenated
    candidate arrays (bit-identical under the caller's ``active`` mask,
    which never exposes lanes past the two compacted runs)."""
    kv = int(kv)
    j = jnp.arange(kv, dtype=jnp.int32)
    now_v = jnp.take(now, jnp.clip(j, 0, now.shape[0] - 1))
    fut_v = jnp.take(fut, jnp.clip(j - n_now, 0, fut.shape[0] - 1))
    return jnp.where(j < n_now, now_v, fut_v)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PlanImage:
    """Fused dedup -> residency-probe output (one sort, no lane argsorts)."""

    uniq: jnp.ndarray  # int32 [k] ascending distinct rows, -1 padded
    uniq_sorted: jnp.ndarray  # int32 [k] same, sentinel-padded (membership)
    uniq_valid: jnp.ndarray  # bool [k]
    uniq_slots: jnp.ndarray  # int32 [k] resident slot per unique (-1 miss)
    miss: jnp.ndarray  # bool [k] valid + unresident
    miss_rows: jnp.ndarray  # int32 [k] miss rows compacted to the front (-1)
    n_miss: jnp.ndarray  # int32 []
    n_distinct: jnp.ndarray  # int32 [] TRUE distinct count (overflow guard)


def plan_image(rows: jnp.ndarray, row_to_slot: jnp.ndarray, k: int) -> PlanImage:
    """Dedup ``rows`` (sentinel-padded with int32 max) into a ``k``-lane
    unique buffer, probe residency through ``row_to_slot``, and compact the
    missed uniques to the front — the fused form of the cache planner's
    ``jnp.unique`` + second sort + stable miss argsort."""
    int_max = jnp.iinfo(jnp.int32).max
    uniq_sorted, n_distinct = dedup(rows, k, int_max)
    uniq_valid = uniq_sorted != int_max
    uniq = jnp.where(uniq_valid, uniq_sorted, -1)
    uniq_slots = row_to_slot.at[jnp.where(uniq_valid, uniq, 0)].get(
        mode="fill", fill_value=-1
    )
    miss = (uniq_slots < 0) & uniq_valid
    return PlanImage(
        uniq=uniq,
        uniq_sorted=uniq_sorted,
        uniq_valid=uniq_valid,
        uniq_slots=uniq_slots,
        miss=miss,
        miss_rows=compact_front(miss, uniq, k),
        n_miss=jnp.sum(miss.astype(jnp.int32)),
        n_distinct=n_distinct,
    )


def bucketize(owner: jnp.ndarray, local: jnp.ndarray, num_shards: int) -> jnp.ndarray:
    """[lanes] routing -> [S, lanes] per-shard local-row image (-1 off-shard)
    — the id all-to-all payload of the sharded collection."""
    sids = jnp.arange(int(num_shards), dtype=jnp.int32)[:, None]
    return jnp.where(
        (owner[None, :] == sids) & (local[None, :] >= 0), local[None, :], -1
    ).astype(jnp.int32)


def arena_gather(
    head: jnp.ndarray,
    tail: jnp.ndarray,
    sideband: Optional[jnp.ndarray],
    slots: jnp.ndarray,
    decode,
    out_dtype,
) -> jnp.ndarray:
    """Decode-on-read gather over one tiered leaf: head lanes bit-exact,
    tail lanes ``decode(payload, sideband)``, negative/OOB lanes zero rows.
    ``decode(payload, side, out_dtype)`` is the store codec's row decode.
    Bit-identical to ``ArenaStore.gather_slots`` on the same leaf."""
    h = head.shape[0]
    in_tail = slots >= h
    safe_h = jnp.where((slots >= 0) & ~in_tail, slots, h)
    head_rows = jnp.take(head, safe_h, axis=0, mode="fill", fill_value=0)
    safe_t = jnp.where(in_tail, slots - h, tail.shape[0])
    payload = jnp.take(tail, safe_t, axis=0, mode="fill", fill_value=0)
    side = None
    if sideband is not None:
        side = jnp.take(sideband, safe_t, axis=0, mode="fill", fill_value=0)
    tail_rows = decode(payload, side, out_dtype)
    mask = in_tail.reshape(in_tail.shape + (1,) * (head_rows.ndim - in_tail.ndim))
    return jnp.where(mask, tail_rows, head_rows)
