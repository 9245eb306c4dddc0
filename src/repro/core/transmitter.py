"""The data transmitter (paper §4.3): bounded-buffer, blocked row movement.

The paper packs scattered embedding rows into contiguous blocks on the source
device, ships the block across the slow link (PCI-e there; host<->HBM DMA on a
TPU host), and scatters on the target — with a strictly limited buffer, so a
big transfer completes in multiple rounds.

JAX/XLA adaptation: shapes must be static, so the transmitter has a fixed
per-round budget ``buffer_rows`` and lays the (padded) index arrays out as
``ceil(K / buffer_rows)`` rounds.  Inactive lanes use out-of-bounds indices
with ``mode='drop'`` / ``mode='fill'`` so they are no-ops, but a round costs
its ``buffer_rows`` lanes whether they carry rows or not.  A caller whose
active lanes sit at the front (a cache plan compacts its loads there) passes
``live_lanes``, the count of leading lanes that may be active, and the loop
runs only the ``ceil(live_lanes / buffer_rows)`` rounds that reach them: the
trip count is traced, the staging block keeps its static size.
The pack -> move -> scatter structure is kept explicit (``pack`` is a gather
into a contiguous [buffer_rows, ...] staging block — exactly the paper's
buffer) so that on TPU the staging block is what crosses the host/device
boundary.

Rows are pytrees: every leaf has a leading "row" dimension; auxiliary per-row
state (e.g. row-wise Adagrad accumulators) moves together with the weights.

Codec-aware movement: either side of ``move_rows`` may be a
:class:`repro.store.HostStore` (the mixed-precision host tier).  The pack
stage then gathers the *encoded* payload + sideband into the staging block —
that is what crosses the slow link, so an int8 store moves ~4x fewer bytes
per round — and the decode (load) / encode (writeback) runs on the block at
the device end of the link.  With the fp32 codec the store is raw arrays and
the path is bit-identical to the plain-pytree one.  A host-resident store
(``memory_kind == "pinned_host"``) moves a round's rows in 4 KiB chunks by
DMA (``HostStore.gather_block`` / ``scatter_block``), over the lanes that
may be active only (``live_lanes``), and chunk staging is off for it.

The DEVICE side may likewise be a :class:`repro.store.ArenaStore` (the
frequency-tiered arena): gathers decode-on-read (head slots bit-exact, tail
slots dequantized) and scatters encode tail lanes on arrival.  When a host
store loads into an arena of the SAME codec, the tail lanes take the host
payload + sideband verbatim — the encoded block that crossed the link lands
in the tail tier without a decode/re-encode round trip (the head lanes still
decode, since the head stores fp32).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.store.arena import ArenaStore
from repro.store.host_store import HostStore

__all__ = ["move_rows", "write_rows", "gather_rows", "scatter_rows", "num_rounds",
           "live_rounds"]


def num_rounds(k: int, buffer_rows: int) -> int:
    return -(-k // buffer_rows)


def live_rounds(k: int, buffer_rows: int, live_lanes: jnp.ndarray) -> jnp.ndarray:
    """Rounds of a ``K = k``-lane move that reach its first ``live_lanes``
    lanes: ``ceil(live_lanes / buffer_rows)``, at most the static round count
    (int32 scalar, traced)."""
    buffer_rows = min(buffer_rows, k)
    live = jnp.asarray(live_lanes, jnp.int32)
    return jnp.minimum(-(-live // buffer_rows), num_rounds(k, buffer_rows))


# ---------------------------------------------------------------------------
# chunk-granularity staging (paper's chunk-based manager, arXiv 2208.05321)
# ---------------------------------------------------------------------------
#
# Instead of gathering/scattering K scattered rows on the slow tier, the
# chunked path groups the round's rows by their CONTIGUOUS chunk of
# ``chunk_rows`` rows, dedups the chunk ids (one buffer-sized sort per
# round — never a table-sized one), and moves whole chunks: loads gather at
# most ``buffer_rows`` unique chunks and pick rows out of the staged block;
# writebacks read-modify-write the touched chunks.  On a host<->device link
# this turns K row-sized DMAs into a few large contiguous ones; values are
# bit-identical to the row-granular path (tested).


def _chunk_plan(
    idx: jnp.ndarray, chunk: int, n_chunks: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-round chunk schedule: dedup'd chunk ids (``n_chunks`` = OOB pad)
    plus each lane's flat position ``pos_in_dedup * chunk + offset`` into
    the staged [B, chunk, ...] block (-1 for inactive lanes)."""
    big = jnp.iinfo(jnp.int32).max
    b = idx.shape[0]
    cid = jnp.where(idx >= 0, idx // chunk, big)
    srt = jnp.sort(cid)  # buffer-sized, bounded by the round
    first = jnp.concatenate([jnp.ones((1,), bool), jnp.diff(srt) != 0]) & (srt != big)
    pos = jnp.cumsum(first.astype(jnp.int32)) - 1
    uniq_c = jnp.full((b,), n_chunks, jnp.int32).at[
        jnp.where(first, pos, b)
    ].set(srt.astype(jnp.int32), mode="drop")
    lane_pos = jnp.clip(
        jnp.searchsorted(uniq_c, jnp.where(idx >= 0, cid, 0).astype(jnp.int32)),
        0,
        b - 1,
    ).astype(jnp.int32)
    flat = jnp.where(idx >= 0, lane_pos * chunk + idx % chunk, -1)
    return uniq_c, flat


def _chunkable(tree: Any, chunk: int) -> bool:
    """Chunking needs every leaf's row count to divide evenly (the reshaped
    [rows/chunk, chunk, ...] view); otherwise fall back to row granularity."""
    if chunk <= 0:
        return False
    leaves = jax.tree_util.tree_leaves(tree)
    return bool(leaves) and all(leaf.shape[0] % chunk == 0 for leaf in leaves)


def _gather_rows_chunked(tree: Any, idx: jnp.ndarray, chunk: int) -> Any:
    """Chunked pack: gather the round's unique chunks, then pick each lane's
    row out of the staged block.  Inactive lanes (-1) produce zero rows —
    same convention as :func:`gather_rows`."""
    b = idx.shape[0]

    def g(leaf):
        nc = leaf.shape[0] // chunk
        uniq_c, flat = _chunk_plan(idx, chunk, nc)
        view = leaf.reshape((nc, chunk) + leaf.shape[1:])
        staged = jnp.take(view, uniq_c, axis=0, mode="fill", fill_value=0)
        rows = staged.reshape((b * chunk,) + leaf.shape[1:])
        safe = jnp.where(flat >= 0, flat, b * chunk)
        return jnp.take(rows, safe, axis=0, mode="fill", fill_value=0)

    return jax.tree_util.tree_map(g, tree)


def _scatter_rows_chunked(
    tree: Any, idx: jnp.ndarray, block: Any, active: jnp.ndarray, chunk: int
) -> Any:
    """Chunked unpack: read-modify-write the touched chunks — gather them,
    overwrite the block's rows at their in-chunk offsets, scatter the chunks
    back.  Untouched rows of a touched chunk keep their gathered values, so
    the result is bit-identical to the row-granular scatter."""
    b = idx.shape[0]
    idx_eff = jnp.where(active, idx, -1)

    def s(leaf, blk):
        nc = leaf.shape[0] // chunk
        uniq_c, flat = _chunk_plan(idx_eff, chunk, nc)
        view = leaf.reshape((nc, chunk) + leaf.shape[1:])
        staged = jnp.take(view, uniq_c, axis=0, mode="fill", fill_value=0)
        rows = staged.reshape((b * chunk,) + leaf.shape[1:])
        rows = rows.at[jnp.where(flat >= 0, flat, b * chunk)].set(blk, mode="drop")
        staged = rows.reshape((b, chunk) + leaf.shape[1:])
        new_view = view.at[uniq_c].set(staged, mode="drop")  # pad = OOB, dropped
        return new_view.reshape(leaf.shape)

    return jax.tree_util.tree_map(s, tree, block)


def _gather_store_rows_chunked(store: HostStore, idx: jnp.ndarray, chunk: int) -> Any:
    """Chunked pack from a host store: the chunks that cross the link are the
    ENCODED payload + sideband (chunking composes with the wire codec)."""
    block = _gather_rows_chunked(store.data, idx, chunk)
    side = _gather_rows_chunked(store.sideband, idx, chunk)
    return store.decode_block(block, side)


def _scatter_store_rows_chunked(
    store: HostStore, idx: jnp.ndarray, block: Any, active: jnp.ndarray, chunk: int
) -> HostStore:
    """Chunked unpack into a host store: encode on the device side, then RMW
    whole payload/sideband chunks on the host side."""
    data_blk, side_blk = store.encode_block(block)
    data = _scatter_rows_chunked(store.data, idx, data_blk, active, chunk)
    sideband = (
        _scatter_rows_chunked(store.sideband, idx, side_blk, active, chunk)
        if store.sideband
        else store.sideband
    )
    return dataclasses.replace(store, data=data, sideband=sideband)


def _on_host(tree: Any) -> bool:
    return isinstance(tree, HostStore) and tree.on_host


def gather_rows(tree: Any, idx: jnp.ndarray) -> Any:
    """Pack: gather rows ``idx`` of every leaf into a contiguous block.

    Out-of-bounds / negative indices produce zero rows (``mode='fill'``).
    """
    def g(leaf):
        safe = jnp.where(idx >= 0, idx, leaf.shape[0])  # negatives would wrap
        return jnp.take(leaf, safe, axis=0, mode="fill", fill_value=0)

    return jax.tree_util.tree_map(g, tree)


def scatter_rows(tree: Any, idx: jnp.ndarray, block: Any, active: jnp.ndarray) -> Any:
    """Unpack: scatter ``block`` rows into ``tree`` at ``idx`` where ``active``.

    Inactive lanes are redirected out of bounds and dropped.
    """
    n = jax.tree_util.tree_leaves(tree)[0].shape[0]
    safe_idx = jnp.where(active, idx, n)  # n == OOB -> dropped

    def s(leaf, blk):
        return leaf.at[safe_idx].set(blk, mode="drop")

    return jax.tree_util.tree_map(s, tree, block)


def _gather_store_rows(store: HostStore, idx: jnp.ndarray) -> Any:
    """Pack from a host store: the staging block is the ENCODED payload +
    sideband (this is what crosses the link), decoded only on arrival."""
    block = gather_rows(store.data, idx)
    side = gather_rows(store.sideband, idx)
    return store.decode_block(block, side)


def _pack_store_rows(store: HostStore, idx: jnp.ndarray, count) -> Any:
    """Pack from a host store: the ENCODED payload + sideband staging block
    (this is what crosses the link).  A host-resident store moves only the
    round's first ``count`` lanes."""
    if store.on_host:
        return store.gather_block(idx, count)
    return gather_rows(store.data, idx), gather_rows(store.sideband, idx)


def _scatter_store_rows(
    store: HostStore, idx: jnp.ndarray, block: Any, active: jnp.ndarray, count=None
) -> HostStore:
    """Unpack into a host store: quantize-on-writeback — the block is encoded
    on the device side, then payload + sideband cross the link and scatter.
    A host-resident store moves only the round's first ``count`` lanes."""
    data_blk, side_blk = store.encode_block(block)
    if store.on_host:
        return store.scatter_block(idx, data_blk, side_blk, active, count)
    data = scatter_rows(store.data, idx, data_blk, active)
    sideband = (  # sideband-free codecs (fp32/fp16) carry an empty dict
        scatter_rows(store.sideband, idx, side_blk, active) if store.sideband else store.sideband
    )
    return dataclasses.replace(store, data=data, sideband=sideband)


def move_rows(
    src_tree: Any,
    dst_tree: Any,
    src_idx: jnp.ndarray,
    dst_idx: jnp.ndarray,
    active: jnp.ndarray,
    *,
    buffer_rows: int,
    src_chunk_rows: int = 0,
    dst_chunk_rows: int = 0,
    live_lanes: Optional[jnp.ndarray] = None,
) -> Any:
    """Move rows ``src_idx`` of ``src_tree`` to positions ``dst_idx`` of ``dst_tree``.

    ``active`` masks real lanes; all arrays have static length K, laid out as
    ``ceil(K/buffer_rows)`` rounds through a [buffer_rows, ...] staging
    block.  Returns the updated ``dst_tree``.  Designed to be called
    from inside a jitted step (it is pure; no own jit so the caller fuses it).

    ``live_lanes`` (a scalar, may be traced) promises that every lane at or
    past it is inactive; the loop then stops after the rounds that reach it
    (:func:`live_rounds`), which changes no output.  ``None`` runs every
    round.  A single-round move has no loop and ignores it.

    Either side may be a ``HostStore``: loads gather the encoded staging
    block and decode it at the device end; writebacks encode the block
    before it crosses, then scatter payload + sideband into the store.  The
    device side may be an ``ArenaStore`` (tiered arena) — see module
    docstring for the encoded host->tail fast path.

    ``src_chunk_rows`` / ``dst_chunk_rows`` (0 = off) switch the named side
    to chunk-granularity staging: the round's rows are grouped into
    contiguous ``chunk_rows``-row chunks and whole chunks cross the link
    (loads pick rows out of the staged chunks; writebacks read-modify-write
    them).  Callers set the knob on their SLOW-TIER side only.  Values are
    bit-identical to the row-granular path; chunking silently falls back to
    rows when a leaf's row count does not divide the chunk size.  The
    host->tail verbatim fast path is row-granular (the staged chunks are
    decoded at the device end), so it is bypassed under a chunked source.
    """
    k = src_idx.shape[0]
    buffer_rows = min(buffer_rows, k)
    rounds = num_rounds(k, buffer_rows)
    pad = rounds * buffer_rows - k
    if pad:
        src_idx = jnp.concatenate([src_idx, jnp.full((pad,), -1, src_idx.dtype)])
        dst_idx = jnp.concatenate([dst_idx, jnp.full((pad,), -1, dst_idx.dtype)])
        active = jnp.concatenate([active, jnp.zeros((pad,), bool)])
    src_store = src_tree.data if isinstance(src_tree, HostStore) else src_tree
    chunk_src = (
        src_chunk_rows
        if not isinstance(src_tree, ArenaStore) and not _on_host(src_tree)
        and _chunkable(src_store, src_chunk_rows)
        else 0
    )
    dst_store = dst_tree.data if isinstance(dst_tree, HostStore) else dst_tree
    chunk_dst = (
        dst_chunk_rows
        if not isinstance(dst_tree, ArenaStore) and not _on_host(dst_tree)
        and _chunkable(dst_store, dst_chunk_rows)
        else 0
    )
    if _on_host(src_tree):
        src_tree = src_tree.placed()
    if _on_host(dst_tree):
        dst_tree = dst_tree.placed()  # the loop carries it host-typed

    def lanes(r):  # of round r: its lanes a host-resident side moves
        if live_lanes is None:
            return buffer_rows
        return jnp.clip(live_lanes - r * buffer_rows, 0, buffer_rows)

    def body(r, dst):
        s = r * buffer_rows
        si = jax.lax.dynamic_slice_in_dim(src_idx, s, buffer_rows)
        di = jax.lax.dynamic_slice_in_dim(dst_idx, s, buffer_rows)
        ac = jax.lax.dynamic_slice_in_dim(active, s, buffer_rows)
        si = jnp.where(ac, si, -1)
        enc_payload: Optional[Any] = None
        enc_side: Optional[Any] = None
        if isinstance(src_tree, HostStore):  # pack + decode-on-load
            if chunk_src:
                block = _gather_store_rows_chunked(src_tree, si, chunk_src)
            else:
                # keep the encoded block around: if the destination is a
                # tiered arena of the same codec, tail lanes take it
                # verbatim below.
                enc_payload, enc_side = _pack_store_rows(src_tree, si, lanes(r))
                block = src_tree.decode_block(enc_payload, enc_side)
        elif isinstance(src_tree, ArenaStore):  # pack + decode-on-read
            block = src_tree.gather_slots(si)
        elif chunk_src:
            block = _gather_rows_chunked(src_tree, si, chunk_src)
        else:
            block = gather_rows(src_tree, si)  # pack (staging buffer)
        if isinstance(dst, HostStore):  # encode-on-writeback + unpack
            if chunk_dst:
                return _scatter_store_rows_chunked(dst, di, block, ac, chunk_dst)
            return _scatter_store_rows(dst, di, block, ac, lanes(r))
        if isinstance(dst, ArenaStore):  # tiered unpack (tail encodes)
            payload_blk = side_blk = None
            if enc_payload is not None and isinstance(src_tree, HostStore) \
                    and src_tree.codec == dst.codec:
                payload_blk = {
                    k_: enc_payload[k_]
                    for k_ in dst.tail
                    if k_ in enc_payload and src_tree.is_encoded(k_)
                }
                side_blk = {
                    k_: enc_side[k_] for k_ in dst.sideband if k_ in enc_side
                }
            return dst.scatter_slots(
                di, block, ac, payload_block=payload_blk, side_block=side_blk
            )
        if chunk_dst:
            return _scatter_rows_chunked(dst, di, block, ac, chunk_dst)
        return scatter_rows(dst, di, block, ac)  # move + unpack

    if rounds == 1:
        return body(0, dst_tree)
    if live_lanes is not None:
        rounds = live_rounds(k, buffer_rows, live_lanes)  # traced: a `while`
    return jax.lax.fori_loop(0, rounds, body, dst_tree)


def write_rows(
    rows: Any,
    dst_tree: Any,
    dst_idx: jnp.ndarray,
    active: jnp.ndarray,
    *,
    buffer_rows: int,
    dst_chunk_rows: int = 0,
) -> Any:
    """Scatter an explicit block of ``rows`` (row i -> ``dst_idx[i]``) into
    ``dst_tree`` through the same bounded staging buffer as :func:`move_rows`
    — encode-on-writeback applies when the destination is a ``HostStore``.
    Used by the sharded collection to push its replicated arena back to the
    rows' slow-tier homes (flush, refresh demotions)."""
    k = dst_idx.shape[0]
    src_idx = jnp.arange(k, dtype=dst_idx.dtype)
    return move_rows(
        rows, dst_tree, src_idx, dst_idx, active,
        buffer_rows=buffer_rows, dst_chunk_rows=dst_chunk_rows,
    )
