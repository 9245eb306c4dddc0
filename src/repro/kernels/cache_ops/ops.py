"""Dispatching entry points for the cache hot-path kernels.

Two layers:

* ``*_impl`` functions — UN-jitted, called inline from ``cache.plan_prepare``
  / ``sharded.plan_prepare`` / ``ArenaStore.gather_slots`` so they trace into
  the caller's jaxpr (the analyzer's sort-bound pass sees through them).  On
  CPU they run the XLA references from ``ref.py``; on TPU the
  capacity-streaming pieces compile through the Pallas kernels.  On CPU,
  ``REPRO_FORCE_PALLAS_CACHE_OPS=1`` (the interpret-mode CI smokes) routes
  them through the kernels in the Pallas interpreter.
* registered jit wrappers below — the analyzer/bench surface.  Each carries a
  ``@contract`` whose ``max_sort_size`` pins the bounded-top-K claim: at the
  smoke geometry nothing here may sort more than the unique buffer.

The dispatch decision is trace-time static (backend + env var), so a jitted
caller specializes per route exactly like the store-codec dispatch does.
"""
from __future__ import annotations

import functools
import os
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.contracts import contract
from repro.kernels.cache_ops import kernel as _kernel
from repro.kernels.cache_ops import ref as _ref
from repro.kernels.cache_ops.ref import PlanImage

__all__ = [
    "PlanImage",
    "arena_gather",
    "arena_gather_impl",
    "bucketize_impl",
    "chunked_move",
    "compact_front_impl",
    "dedup_impl",
    "kernels_enabled",
    "merge_candidates_impl",
    "plan_image",
    "plan_image_impl",
    "shard_bucketize",
    "victim_topk",
    "victim_topk_impl",
]


def kernels_enabled() -> bool:
    """Pallas route: on for the TPU (compiled), forceable on CPU via
    ``REPRO_FORCE_PALLAS_CACHE_OPS=1`` (interpret mode, for tests).  Whether
    a kernel compiles or interprets follows the backend alone
    (``repro.kernels.interpret_mode``), never this flag."""
    if jax.default_backend() == "tpu":
        return True
    return os.environ.get("REPRO_FORCE_PALLAS_CACHE_OPS") == "1"


# ---------------------------------------------------------------------------
# impl layer (inlined into callers)
# ---------------------------------------------------------------------------


def victim_topk_impl(key: jnp.ndarray, kv: int) -> jnp.ndarray:
    """Bounded top-K victim selection — bit-identical to
    ``jnp.argsort(key, descending=True)[:kv].astype(int32)``."""
    if kernels_enabled():
        u = _ref.ordered_u32(key)
        t, n_gt = _kernel.victim_threshold_pallas(u, kv)
        # select + order epilogue shared with the reference route
        return _ref.topk_select(u, t, n_gt, key, kv)
    return _ref.victim_topk(key, kv)


def dedup_impl(rows: jnp.ndarray, k: int, fill: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    return _ref.dedup(rows, k, fill)


def compact_front_impl(mask, values, out_len: int) -> jnp.ndarray:
    return _ref.compact_front(mask, values, out_len)


def merge_candidates_impl(now, n_now, fut, kv: int) -> jnp.ndarray:
    return _ref.merge_candidates(now, n_now, fut, kv)


def plan_image_impl(rows, row_to_slot, k: int) -> PlanImage:
    return _ref.plan_image(rows, row_to_slot, k)


def bucketize_impl(owner, local, num_shards: int) -> jnp.ndarray:
    if kernels_enabled():
        return _kernel.bucketize_pallas(owner, local, num_shards)
    return _ref.bucketize(owner, local, num_shards)


def arena_gather_impl(
    head: jnp.ndarray,
    tail: jnp.ndarray,
    sideband: Optional[jnp.ndarray],
    slots: jnp.ndarray,
    codec: str,
    decode,
    out_dtype,
) -> jnp.ndarray:
    """Fused tiered-arena gather+decode for one leaf.  ``decode`` is the
    store codec's row decode (used by the reference route and by codecs the
    kernel does not special-case)."""
    if kernels_enabled() and codec in ("fp16", "int8") and head.ndim == 2:
        return _kernel.gather_decode_pallas(
            head, tail, sideband, slots, codec, out_dtype
        )
    return _ref.arena_gather(head, tail, sideband, slots, decode, out_dtype)


# ---------------------------------------------------------------------------
# registered jit entry points (analyzer / bench / test surface)
# ---------------------------------------------------------------------------


@contract(max_sort_size=64)
@functools.partial(jax.jit, static_argnames=("kv",))
def victim_topk(key: jnp.ndarray, kv: int) -> jnp.ndarray:
    """The K worst eviction keys, stable-descending — no capacity-sized sort
    (the declared ``max_sort_size`` bounds the kv-sized epilogue sort at the
    smoke geometry)."""
    return victim_topk_impl(key, kv)


@contract(max_sort_size=64)
@functools.partial(jax.jit, static_argnames=("k",))
def plan_image(rows: jnp.ndarray, row_to_slot: jnp.ndarray, k: int) -> PlanImage:
    """Fused dedup -> residency probe -> miss compaction (one k-ish sort)."""
    return plan_image_impl(rows, row_to_slot, k)


@contract(max_sort_size=64)
@functools.partial(jax.jit, static_argnames=("num_shards", "u"))
def shard_bucketize(
    rank: jnp.ndarray,
    rank_owner: jnp.ndarray,
    rank_local: jnp.ndarray,
    rep_k: int,
    num_shards: int,
    u: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused sharded-router front end: dedup ranks, route, and build the
    [S, U] bucketize image in one pass.  Returns ``(uniq, pos, owner_u,
    local_u, rows_sh)`` — bit-identical to the historical ``_dedup`` /
    ``_route`` / ``_bucketize`` composition."""
    pad = jnp.iinfo(jnp.int32).max
    key = jnp.where(rank >= 0, rank, pad)
    uniq, _ = dedup_impl(key, u, pad)
    uniq = uniq.astype(jnp.int32)
    pos = jnp.minimum(jnp.searchsorted(uniq, key), u - 1).astype(jnp.int32)
    ok = uniq >= rep_k  # replicated head lanes never enter the exchange
    owner_u = jnp.where(
        ok,
        rank_owner.at[jnp.where(ok, uniq, 0)].get(mode="fill", fill_value=-1),
        -1,
    )
    local_u = jnp.where(
        ok,
        rank_local.at[jnp.where(ok, uniq, 0)].get(mode="fill", fill_value=-1),
        -1,
    )
    rows_sh = bucketize_impl(owner_u, local_u, num_shards)
    return uniq, pos, owner_u, local_u, rows_sh


@contract(max_sort_size=0)
@functools.partial(jax.jit, static_argnames=("codec", "out_dtype"))
def arena_gather(
    head: jnp.ndarray,
    tail: jnp.ndarray,
    sideband: Optional[jnp.ndarray],
    slots: jnp.ndarray,
    codec: str = "fp16",
    out_dtype: str = "float32",
) -> jnp.ndarray:
    """Fused tiered-arena gather+decode over one leaf (bench/test surface;
    the cache calls ``arena_gather_impl`` inline via ``ArenaStore``)."""
    from repro.store.codec import get_codec

    c = get_codec(codec)
    return arena_gather_impl(
        head, tail, sideband, slots, codec, c.decode, jnp.dtype(out_dtype)
    )


@contract(max_sort_size=64)
@functools.partial(
    jax.jit, static_argnames=("buffer_rows", "src_chunk_rows", "dst_chunk_rows")
)
def chunked_move(
    src_tree: Any,
    dst_tree: Any,
    src_idx: jnp.ndarray,
    dst_idx: jnp.ndarray,
    active: jnp.ndarray,
    buffer_rows: int,
    src_chunk_rows: int = 0,
    dst_chunk_rows: int = 0,
) -> Any:
    """Chunk-granularity transmitter round (registered surface for the
    analyzer: the per-round chunk dedup sorts ``buffer_rows`` lanes, never
    the table).  Thin wrapper over ``transmitter.move_rows``."""
    from repro.core import transmitter

    return transmitter.move_rows(
        src_tree,
        dst_tree,
        src_idx,
        dst_idx,
        active,
        buffer_rows=buffer_rows,
        src_chunk_rows=src_chunk_rows,
        dst_chunk_rows=dst_chunk_rows,
    )
