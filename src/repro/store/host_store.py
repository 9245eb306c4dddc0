"""``HostStore`` — the host-tier (slow, full-table) representation.

A ``HostStore`` replaces the raw fp32 ``full_rows`` pytree under the cache:
it holds each leaf *encoded* by one :mod:`repro.store.codec` codec (payload
dict + per-row sideband dict) and exposes row-block ``encode_rows`` /
``decode_rows`` so the transmitter can quantize-on-writeback and
dequantize-on-load inside its pack -> move -> scatter rounds.  The staging
block crosses the host<->device link *encoded* — for int8 that is ~4x fewer
bytes per cache miss — while the cached working set stays full precision
(the mixed-precision-cache design of arXiv 2010.11305).

``data`` must be a flat ``Dict[str, jnp.ndarray]`` (the shape every slab's
``full`` tree already has: ``{"weight": [vocab, dim], ("accum": [vocab])?}``).
Leaves the codec does not transform (per-row scalars like optimizer
accumulators, integer leaves) are stored raw and pass through untouched.

The fp32 codec stores raw arrays, so a ``HostStore("fp32")`` is bit-identical
to the pre-store pytree in every operation — existing callers migrate with
zero numeric risk.  ``store[key]`` / ``store[key] = v`` index straight into
``data`` (for fp32 that is the old raw-leaf access; quantized readers must
use ``decode_rows`` / ``decode_leaf``).

The codec name rides on the pytree as static metadata, so jit specializes
per codec and checkpoint restore can validate it (leaf dtype/shape mismatch
= codec mismatch).

Where the slow tier lives rides along the same way: ``memory_kind`` is
``"device"`` (the leaves are ordinary device arrays, HBM on a TPU) or
``"pinned_host"`` (host DRAM).  A host-resident leaf is a ``HostPieces``:
1-D pieces of about ``PIECE_BYTES`` in host memory.  Inside the jitted
step rows cross in 4 KiB chunks, by DMAs that Pallas kernels issue from a
list of chunks: ``gather_block`` brings each row's chunk to the device and
picks the row out; ``scatter_block`` reads each chunk its rows fall in,
sets the rows on the device and writes the chunk back.  (Packing rows with
a host computation, ``jax.experimental.compute_on``, moved the whole table
through each call on a TPU v5e; one XLA DMA per row is right but gives the
profiler several ops per row.)  Inside a jitted step every leaf it returns
is stated to be in host memory, so a donated store stays where it was.
The legs run under the ``cache.host_link`` named scope.  Off the TPU the
kernels run in the Pallas interpreter and the leaves keep no memory kind.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.random import threefry2x32_p

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode
from repro.obs.tracing import SCOPE_HOST_LINK
from repro.store.codec import Codec, get_codec

__all__ = ["CHUNK_BYTES", "HostPieces", "HostStore", "MIN_ROW_BYTES", "PIECE_BYTES",
           "PINNED_HOST", "draw_uniform", "host_layout", "host_row_ok", "row_counters",
           "uniform_rows"]

PINNED_HOST = "pinned_host"

# A host-resident leaf is held as row pieces of about this many bytes each.
# No host buffer, and no offset inside one, may pass 16 GiB (on a TPU v5e a
# 17.3 GB table held as one host buffer trained wrong past its first 16 GiB;
# held as 5.8 GB pieces it trains right), and the draw starts each piece as
# zeros on the device, so a piece is also the most HBM the draw holds.
PIECE_BYTES = 1 << 29

# Rows cross the link in whole chunks of this many bytes: a DMA from or to
# host memory on a TPU v5e takes only 4 KiB-aligned slices of a 1-D array.
# A load moves the chunk that holds its row; a write-back reads each chunk
# it touches, sets its rows on the device and writes the chunk back.
CHUNK_BYTES = 4096

# the narrowest row the host tier takes: rows of 64 B moved one per DMA
# halted a TPU v5e's TensorCore; rows of 512 B and up, in 4 KiB chunks, are
# what the chip has run
MIN_ROW_BYTES = 512

INFLIGHT = 64  # chunk DMAs a kernel keeps in flight

# the least a device block that a kernel fills from host memory, or empties
# into it, holds: larger than a TPU v5e's 128 MiB of VMEM, which the
# compiler may give a smaller one and which host DMAs cannot reach
STAGE_MIN_BYTES = 160 << 20


def _to_host(x):
    """State ``x`` to live in host memory (a no-op for a host array, and on
    a backend that runs the Pallas kernels in the interpreter, which keeps
    no memory kinds)."""
    if interpret_mode():
        return x
    return jax.device_put(x, jax.memory.Space.Host)


def host_row_ok(row_bytes: int) -> bool:
    """Whether rows of ``row_bytes`` bytes can live in the host tier: at
    least ``MIN_ROW_BYTES``, a whole number of them to a chunk."""
    return row_bytes >= MIN_ROW_BYTES and CHUNK_BYTES % row_bytes == 0


def host_layout(vocab: int, widths) -> Tuple[int, int]:
    """``(pieces, rows)``: the row pieces the host-resident leaves of one
    store, ``vocab`` rows of ``widths`` bytes each, are held in, and the
    rows each piece holds (a whole number of every leaf's chunks)."""
    n = max(1, -(-vocab * max(widths) // PIECE_BYTES))
    per_chunk = max(max(1, CHUNK_BYTES // w) for w in widths)
    return n, -(-(-(-vocab // n)) // per_chunk) * per_chunk


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class HostPieces:
    """One host-resident leaf of ``vocab`` rows held as 1-D row pieces: row
    ``r`` is row ``r % rows`` of piece ``r // rows``, whose values start at
    element ``(r % rows) * row_size`` of the piece.  A piece may hold more
    rows than ``rows`` (the draw's padding, never read)."""

    pieces: Tuple[jnp.ndarray, ...]
    rows: int = dataclasses.field(metadata=dict(static=True))
    row_shape: Tuple[int, ...] = dataclasses.field(metadata=dict(static=True))
    vocab: int = dataclasses.field(metadata=dict(static=True))

    @property
    def dtype(self):
        return self.pieces[0].dtype

    @property
    def row_size(self) -> int:
        return int(np.prod(self.row_shape, dtype=np.int64))

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.vocab,) + self.row_shape

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def per_chunk(self) -> int:
        return CHUNK_BYTES // (self.row_size * self.dtype.itemsize)


def _chunk_copies(n_pieces: int, chunk: int, start_ref, cnt_ref, chunk_ref, make):
    """For each piece, one DMA per position of its run ``[start, start +
    cnt)``, ``INFLIGHT`` in flight: ``make(piece, piece offset, block
    offset, semaphore)`` builds the copy of chunk ``chunk_ref[c]``."""
    for p in range(n_pieces):
        s, n = start_ref[p], cnt_ref[p]

        def copy(c, p=p):
            return make(p, pl.multiple_of(chunk_ref[c] * chunk, chunk),
                        pl.multiple_of(c * chunk, chunk), c % INFLIGHT)

        def issue(i, carry, s=s, copy=copy):
            @pl.when(i >= INFLIGHT)
            def _():
                copy(s + i - INFLIGHT).wait()

            copy(s + i).start()
            return carry

        def drain(i, carry, s=s, copy=copy):
            copy(s + i).wait()
            return carry

        jax.lax.fori_loop(0, n, issue, 0)
        jax.lax.fori_loop(jnp.maximum(n - INFLIGHT, 0), n, drain, 0)


def _take_kernel(start_ref, cnt_ref, chunk_ref, *refs, n_pieces, chunk):
    pieces, out_ref, sems = refs[:n_pieces], refs[n_pieces], refs[n_pieces + 1]
    _chunk_copies(n_pieces, chunk, start_ref, cnt_ref, chunk_ref,
                  lambda p, src, dst, k: pltpu.make_async_copy(
                      pieces[p].at[pl.ds(src, chunk)], out_ref.at[pl.ds(dst, chunk)],
                      sems.at[k]))


def _put_kernel(start_ref, cnt_ref, chunk_ref, blk_ref, piece_ref, out_ref, sems, *, chunk):
    del piece_ref  # aliased to out_ref
    _chunk_copies(1, chunk, start_ref, cnt_ref, chunk_ref,
                  lambda p, dst, src, k: pltpu.make_async_copy(
                      blk_ref.at[pl.ds(src, chunk)], out_ref.at[pl.ds(dst, chunk)],
                      sems.at[k]))


def _host_space(interpret: bool):
    return pl.ANY if interpret else pltpu.HOST


def _device_space(interpret: bool):
    # HBM stated, else a small block may be given VMEM, which host DMAs
    # cannot reach
    return pl.ANY if interpret else pltpu.HBM


def _staged(lanes: int) -> int:
    """Chunks of a device block for ``lanes`` chunks (``STAGE_MIN_BYTES``
    at least; off the TPU just ``lanes``)."""
    return lanes if interpret_mode() else max(lanes, STAGE_MIN_BYTES // CHUNK_BYTES)


def _take_chunks(leaf: HostPieces, start, cnt, chunks) -> jnp.ndarray:
    """``[len(chunks), chunk]`` on the device: at position ``c`` of piece
    ``p``'s run, chunk ``chunks[c]`` of piece ``p``."""
    interpret = interpret_mode()
    chunk = CHUNK_BYTES // leaf.dtype.itemsize
    n, lanes = len(leaf.pieces), chunks.shape[0]
    out = pl.pallas_call(
        functools.partial(_take_kernel, n_pieces=n, chunk=chunk),
        out_shape=jax.ShapeDtypeStruct((_staged(lanes) * chunk,), leaf.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=_host_space(interpret))] * n,
            out_specs=pl.BlockSpec(memory_space=_device_space(interpret)),
            scratch_shapes=[pltpu.SemaphoreType.DMA((INFLIGHT,))]),
        interpret=interpret,
    )(start, cnt, chunks, *leaf.pieces)
    return out.reshape(-1, chunk)[:lanes]


def _put_chunks(leaf: HostPieces, start, cnt, chunks, blk: jnp.ndarray) -> HostPieces:
    """``leaf`` with chunk ``chunks[c]`` of the piece whose run holds
    position ``c`` set to row ``c`` of ``blk`` (``[len(chunks), chunk]``),
    in place: one call a piece (several aliased host outputs of one call do
    not compile)."""
    interpret = interpret_mode()
    chunk = CHUNK_BYTES // leaf.dtype.itemsize
    call = pl.pallas_call(
        functools.partial(_put_kernel, chunk=chunk),
        out_shape=jax.ShapeDtypeStruct(leaf.pieces[0].shape, leaf.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=_device_space(interpret)),
                      pl.BlockSpec(memory_space=_host_space(interpret))],
            out_specs=pl.BlockSpec(memory_space=_host_space(interpret)),
            scratch_shapes=[pltpu.SemaphoreType.DMA((INFLIGHT,))]),
        input_output_aliases={4: 0},
        interpret=interpret,
    )
    flat = jnp.pad(blk, ((0, _staged(blk.shape[0]) - blk.shape[0]), (0, 0))).reshape(-1)
    return dataclasses.replace(leaf, pieces=tuple(
        call(start[p:p + 1], cnt[p:p + 1], chunks, flat, _to_host(piece))
        for p, piece in enumerate(leaf.pieces)))


def _runs(piece: jnp.ndarray, n_pieces: int):
    """Start and length of each piece's run in ``piece`` (ascending, with
    ``n_pieces`` past the runs)."""
    cnt = jnp.sum(piece[:, None] == jnp.arange(n_pieces)[None, :], axis=0, dtype=jnp.int32)
    return jnp.cumsum(cnt) - cnt, cnt


def _by_row(idx: jnp.ndarray, valid: jnp.ndarray):
    """The lanes in row order, the lanes that are not ``valid`` last:
    ``(order, rows, live)``."""
    order = jnp.argsort(jnp.where(valid, idx, jnp.iinfo(jnp.int32).max)).astype(jnp.int32)
    return order, idx[order], valid[order]


def _host_take(leaf: HostPieces, idx: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Rows ``idx`` of a host-resident leaf on the device, zero rows where
    not ``valid``: each row's chunk crosses the link."""
    lanes, n = idx.shape[0], len(leaf.pieces)
    order, rows, live = _by_row(idx, valid)
    piece = jnp.where(live, rows // leaf.rows, n)
    local = rows - jnp.minimum(piece, n - 1) * leaf.rows
    start, cnt = _runs(piece, n)
    got = _take_chunks(leaf, start, cnt, jnp.where(live, local // leaf.per_chunk, 0))
    got = got.reshape((lanes, leaf.per_chunk) + leaf.row_shape)[
        jnp.arange(lanes), local % leaf.per_chunk]
    back = jnp.zeros((lanes,), jnp.int32).at[order].set(jnp.arange(lanes, dtype=jnp.int32))
    return jnp.where(valid.reshape((-1,) + (1,) * len(leaf.row_shape)), got[back],
                     jnp.zeros((), got.dtype))


def _host_put(leaf: HostPieces, idx: jnp.ndarray, block: jnp.ndarray,
              valid: jnp.ndarray) -> HostPieces:
    """A host-resident leaf with rows ``idx`` set to ``block`` where
    ``valid`` (distinct rows): each chunk they touch is read once, its rows
    set on the device, and written back once."""
    lanes, n = idx.shape[0], len(leaf.pieces)
    per = leaf.per_chunk
    order, rows, live = _by_row(idx, valid)
    g = rows // per  # the row's chunk, counted over the pieces
    first = live & jnp.concatenate([jnp.ones((1,), bool), g[1:] != g[:-1]])
    u = jnp.cumsum(first, dtype=jnp.int32) - 1
    uniq = jnp.zeros((lanes,), jnp.int32).at[jnp.where(first, u, lanes)].set(g, mode="drop")
    ulive = jnp.arange(lanes) < jnp.sum(first, dtype=jnp.int32)
    piece = jnp.where(ulive, uniq // (leaf.rows // per), n)
    local = jnp.where(ulive, uniq - jnp.minimum(piece, n - 1) * (leaf.rows // per), 0)
    start, cnt = _runs(piece, n)
    staged = _take_chunks(leaf, start, cnt, local).reshape((lanes, per) + leaf.row_shape)
    staged = staged.at[jnp.where(live, u, lanes), rows % per].set(block[order], mode="drop")
    return _put_chunks(leaf, start, cnt, local, staged.reshape(lanes, -1))


def _write_kernel(off_ref, blk_ref, piece_ref, out_ref, sem, *, chunk):
    del piece_ref  # aliased to out_ref
    copy = pltpu.make_async_copy(
        blk_ref, out_ref.at[pl.ds(pl.multiple_of(off_ref[0], chunk), blk_ref.shape[0])],
        sem.at[0])
    copy.start()
    copy.wait()


def _write_block(piece: jnp.ndarray, off, blk: jnp.ndarray) -> jnp.ndarray:
    """``piece`` (1-D, host) with ``blk`` (1-D) written from element ``off``
    (a whole number of chunks), in place."""
    interpret = interpret_mode()
    return pl.pallas_call(
        functools.partial(_write_kernel, chunk=CHUNK_BYTES // piece.dtype.itemsize),
        out_shape=jax.ShapeDtypeStruct(piece.shape, piece.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=_device_space(interpret)),
                      pl.BlockSpec(memory_space=_host_space(interpret))],
            out_specs=pl.BlockSpec(memory_space=_host_space(interpret)),
            scratch_shapes=[pltpu.SemaphoreType.DMA((1,))]),
        input_output_aliases={2: 0},
        interpret=interpret,
    )(jnp.reshape(off, (1,)).astype(jnp.int32), blk, piece)


def row_counters(rows: jnp.ndarray, dim: int):
    """``(hi, lo)`` uint32 words of the 64-bit counters ``r * dim + j`` of
    rows ``rows`` (shape ``rows.shape + (dim,)``), from 16-bit limbs of r."""
    if not 0 < dim < 1 << 16:
        raise ValueError(f"dim {dim} outside the 16-bit counter split")
    r = rows.astype(jnp.uint32)[..., None]
    j = jnp.arange(dim, dtype=jnp.uint32)
    d = jnp.uint32(dim)
    p_lo = (r & 0xFFFF) * d
    p_hi = (r >> 16) * d
    t = (p_hi & 0xFFFF) << 16
    s1 = t + p_lo
    lo = s1 + j
    hi = (p_hi >> 16) + (s1 < t).astype(jnp.uint32) + (lo < s1).astype(jnp.uint32)
    return hi, lo


@functools.partial(jax.jit, static_argnames=("dim",))
def uniform_rows(key, rows: jnp.ndarray, dim: int, minval: float, maxval: float):
    """Rows ``rows`` (int32, any shape) of ``jax.random.uniform(key, (vocab,
    dim), jnp.float32, minval, maxval)``, bit for bit, without drawing the
    others.  Partitionable threefry draws element ``(r, j)`` from the 64-bit
    counter ``r * dim + j``, so each device can draw just the rows it holds.
    Jitted like ``jax.random.uniform``, so both compile the same arithmetic."""
    data = jax.random.key_data(key) if jax.dtypes.issubdtype(
        key.dtype, jax.dtypes.prng_key) else key
    hi, lo = row_counters(rows, dim)
    b1, b2 = threefry2x32_p.bind(data[0], data[1], hi, lo)
    # jax.random.uniform's float32 transform of the bits
    one = jnp.asarray(np.array(1.0, np.float32).view(np.uint32))
    floats = jax.lax.bitcast_convert_type(((b1 ^ b2) >> 9) | one, jnp.float32) - 1.0
    lo_v, hi_v = jnp.float32(minval), jnp.float32(maxval)
    return jnp.maximum(lo_v, floats * (hi_v - lo_v) + lo_v)


def draw_uniform(key, vocab: int, dim: int, dtype, minval: float, maxval: float,
                 block_rows: int, codec: str = "fp32") -> "HostStore":
    """A host-resident ``HostStore`` whose ``weight`` holds, row for row, what
    ``jax.random.uniform(key, (vocab, dim), dtype, minval, maxval)`` gives,
    drawn and encoded on the device at most about ``block_rows`` rows at a
    time and written block by block into host memory, so no device holds
    the table.

    Each leaf is held in ``host_layout`` pieces (``HostPieces``), drawn one
    after another in equal blocks of whole chunks, so a piece holds a few
    rows past its own (drawn like the rest, never read).  Each piece starts
    as zeros on the device, moved to host memory (one piece of device
    memory at a time)."""
    if not jax.config.jax_threefry_partitionable or jnp.dtype(dtype) != jnp.float32:
        raise ValueError("a host-resident slow tier is drawn block by block, which "
                         "equals the whole-table draw only for float32 tables under "
                         "jax_threefry_partitionable")
    c = get_codec(codec)
    like = jax.eval_shape(lambda: c.encode(jnp.zeros((1, dim), dtype)) if c.encodes(
        jnp.zeros((1, dim), dtype)) else (jnp.zeros((1, dim), dtype), None))
    leaves = {"data": like[0]} if like[1] is None else {"data": like[0], "side": like[1]}
    row_bytes = {k: int(np.prod(v.shape[1:], dtype=np.int64)) * v.dtype.itemsize
                 for k, v in leaves.items()}
    n_pieces, stride = host_layout(vocab, list(row_bytes.values()))
    per_chunk = max(max(1, CHUNK_BYTES // b) for b in row_bytes.values())
    n_blocks = -(-stride // block_rows)
    rows = -(-(-(-stride // n_blocks)) // per_chunk) * per_chunk  # equal blocks of chunks

    def encode(w):
        if c.encodes(w):
            payload, side = c.encode(w)
            return {"data": payload} if side is None else {"data": payload, "side": side}
        return {"data": w}

    out = {k: [] for k in leaves}
    dep = jnp.zeros((), jnp.float32)
    for p in range(n_pieces):
        # the zeros wait for the piece before: one piece on the device at a time
        pieces = {k: _to_host(jnp.full((n_blocks * rows * row_bytes[k] // v.dtype.itemsize,),
                                       dep, v.dtype))
                  for k, v in leaves.items()}

        def block(b, pieces, p=p):
            w = encode(uniform_rows(key, p * stride + b * rows + jnp.arange(rows, dtype=jnp.int32),
                                    dim, minval, maxval))
            return {k: _write_block(v, b * rows * (row_bytes[k] // leaves[k].dtype.itemsize),
                                    w[k].reshape(-1)) for k, v in pieces.items()}

        pieces = jax.lax.fori_loop(0, n_blocks, block, pieces)
        pieces, dep = jax.lax.optimization_barrier((pieces, dep))
        for k in leaves:
            out[k].append(pieces[k])
    leaf = lambda k: HostPieces(tuple(out[k]), stride, tuple(leaves[k].shape[1:]),  # noqa: E731
                                vocab)
    return HostStore(data={"weight": leaf("data")},
                     sideband={"weight": leaf("side")} if "side" in leaves else {},
                     codec=c.name, out_dtype=str(jnp.dtype(dtype)), memory_kind=PINNED_HOST)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class HostStore:
    """Encoded full-table container: ``data`` payload leaves [vocab, ...] in
    the codec's storage dtype, ``sideband`` per-row codec metadata (e.g.
    int8's [vocab, 2] (scale, zero_point)), empty for sideband-free codecs."""

    data: Dict[str, jnp.ndarray]
    sideband: Dict[str, jnp.ndarray]
    codec: str = dataclasses.field(default="fp32", metadata=dict(static=True))
    out_dtype: str = dataclasses.field(default="float32", metadata=dict(static=True))
    memory_kind: str = dataclasses.field(default="device", metadata=dict(static=True))

    @property
    def on_host(self) -> bool:
        return self.memory_kind == PINNED_HOST

    # ----- construction -----------------------------------------------------

    @staticmethod
    def _out_dtype(codec: "Codec", full_tree: Dict[str, Any]) -> str:
        """The single decode-target dtype of the tree's encoded leaves.

        One store decodes to ONE dtype, so all codec-eligible leaves must
        share their source dtype — reject mixed trees instead of silently
        decoding the minority leaf to the wrong type."""
        dts = {str(jnp.dtype(v.dtype)) for v in full_tree.values() if codec.encodes(v)}
        if len(dts) > 1:
            raise ValueError(
                f"HostStore encodes all leaves to one decode dtype, but the "
                f"tree mixes {sorted(dts)} — split the table into one store "
                f"per dtype"
            )
        return dts.pop() if dts else "float32"

    @classmethod
    def create(cls, full_tree: Dict[str, jnp.ndarray], codec: str = "fp32") -> "HostStore":
        """Encode a raw full-table dict into a store (one codec per store)."""
        c = get_codec(codec)
        data: Dict[str, jnp.ndarray] = {}
        sideband: Dict[str, jnp.ndarray] = {}
        out_dtype = cls._out_dtype(c, full_tree)
        for k, leaf in full_tree.items():
            if c.encodes(leaf):
                payload, side = c.encode(leaf)
                data[k] = payload
                if side is not None:
                    sideband[k] = side
            else:
                data[k] = leaf
        return cls(data=data, sideband=sideband, codec=codec, out_dtype=out_dtype)

    @classmethod
    def like(cls, full_like: Dict[str, Any], codec: str = "fp32") -> "HostStore":
        """Structure-only store from shape/dtype examples (specs, eval_shape)."""
        c = get_codec(codec)
        data: Dict[str, Any] = {}
        sideband: Dict[str, Any] = {}
        out_dtype = cls._out_dtype(c, full_like)
        for k, leaf in full_like.items():
            if c.encodes(leaf):
                data[k] = jax.ShapeDtypeStruct(leaf.shape, c.payload_dtype(leaf.dtype))
                srow = c.sideband_row_shape()
                if srow is not None:
                    sideband[k] = jax.ShapeDtypeStruct((leaf.shape[0],) + srow, jnp.float32)
            else:
                data[k] = jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
        return cls(data=data, sideband=sideband, codec=codec, out_dtype=out_dtype)

    @classmethod
    def spec_like(
        cls,
        full_like: Dict[str, Any],
        leaf_specs: Dict[str, Any],
        side_spec: Any,
        codec: str = "fp32",
        memory_kind: str = "device",
    ) -> "HostStore":
        """PartitionSpec mirror of ``create(full_like, codec)``: a store whose
        ``data`` holds ``leaf_specs`` and whose sideband entries (``side_spec``
        per quantized leaf) appear exactly where ``create`` would put arrays —
        the single source of truth for shard-spec trees that must match a
        real store's structure.  A host-resident store (``memory_kind``
        ``"pinned_host"``) gives each leaf's spec to each of its pieces."""
        c = get_codec(codec)
        out_dtype = cls._out_dtype(c, full_like)
        data = dict(leaf_specs)
        sideband = {
            k: side_spec
            for k, leaf in full_like.items()
            if c.encodes(leaf) and c.sideband_row_shape() is not None
        }
        if memory_kind == PINNED_HOST:
            leaf = next(iter(full_like.values()))
            like = cls.like(full_like, codec)
            n, stride = host_layout(leaf.shape[0], [
                int(np.prod(v.shape[1:])) * jnp.dtype(v.dtype).itemsize
                for v in list(like.data.values()) + list(like.sideband.values())])
            flat = jax.sharding.PartitionSpec(None)
            data, sideband = ({k: HostPieces((flat,) * n, stride, tuple(like_t[k].shape[1:]),
                                             leaf.shape[0])
                               for k in t} for t, like_t in ((data, like.data),
                                                             (sideband, like.sideband)))
        return cls(
            data=data, sideband=sideband, codec=c.name, out_dtype=out_dtype,
            memory_kind=memory_kind,
        )

    # ----- raw-leaf access (fp32 compatibility surface) ---------------------

    def __getitem__(self, key: str) -> jnp.ndarray:
        """The stored payload leaf — for fp32 stores this is the raw array
        (the pre-store access idiom); quantized readers want ``decode_leaf``."""
        return self.data[key]

    def __setitem__(self, key: str, value) -> None:
        self.data[key] = value

    def __contains__(self, key: str) -> bool:
        return key in self.data

    # ----- codec plumbing ---------------------------------------------------

    @property
    def _codec(self) -> Codec:
        return get_codec(self.codec)

    @property
    def _out(self):
        return jnp.dtype(self.out_dtype)

    def is_encoded(self, key: str) -> bool:
        """True when ``data[key]`` is stored in the codec's low-precision
        form (self-describing: payload dtype differs from the decode target,
        or a sideband entry exists)."""
        if self.codec == "fp32":
            return False
        if key in self.sideband:
            return True
        return jnp.dtype(self.data[key].dtype) != self._out and jnp.issubdtype(
            self._out, jnp.floating
        )

    # ----- block transforms (what the transmitter calls per round) ----------

    def decode_block(
        self, block: Dict[str, jnp.ndarray], side: Dict[str, jnp.ndarray]
    ) -> Dict[str, jnp.ndarray]:
        """Decode a gathered staging block back to full precision."""
        c = self._codec
        return {
            k: c.decode(v, side.get(k), self._out) if self.is_encoded(k) else v
            for k, v in block.items()
        }

    def encode_block(
        self, block: Dict[str, jnp.ndarray]
    ) -> Tuple[Dict[str, jnp.ndarray], Dict[str, jnp.ndarray]]:
        """Encode a full-precision staging block for the trip to host."""
        c = self._codec
        data: Dict[str, jnp.ndarray] = {}
        side: Dict[str, jnp.ndarray] = {}
        for k, v in block.items():
            if self.is_encoded(k):
                payload, s = c.encode(v)
                data[k] = payload
                if s is not None:
                    side[k] = s
            else:
                data[k] = v
        return data, side

    # ----- row reads (oracles / bulk scans) ---------------------------------

    def decode_rows(self, idx: jnp.ndarray) -> Dict[str, jnp.ndarray]:
        """Gather rows ``idx`` of every leaf, decoded; negative/OOB lanes
        give zero rows (the ``transmitter.gather_rows`` convention)."""
        if self.on_host:
            return self.decode_block(*self.gather_block(idx))
        block: Dict[str, jnp.ndarray] = {}
        side: Dict[str, jnp.ndarray] = {}
        for k, leaf in self.data.items():
            safe = jnp.where(idx >= 0, idx, leaf.shape[0])
            block[k] = jnp.take(leaf, safe, axis=0, mode="fill", fill_value=0)
            if k in self.sideband:
                side[k] = jnp.take(
                    self.sideband[k], safe, axis=0, mode="fill", fill_value=0
                )
        return self.decode_block(block, side)

    def decode_leaf(self, key: str) -> jnp.ndarray:
        """The whole leaf, decoded (oracle/bulk use; fp32 = zero-cost)."""
        if not self.is_encoded(key):
            return self.data[key]
        return self._codec.decode(self.data[key], self.sideband.get(key), self._out)

    # ----- the host leg (``memory_kind == "pinned_host"``) -------------------

    def placed(self) -> "HostStore":
        """This store with every leaf stated to be in its memory: inside a jit
        a host-resident store's leaves become host-typed (a no-op where they
        already are), so a loop that carries the store keeps one type."""
        if not self.on_host:
            return self
        return dataclasses.replace(self, data=jax.tree_util.tree_map(_to_host, self.data),
                                   sideband=jax.tree_util.tree_map(_to_host, self.sideband))

    def gather_block(self, idx: jnp.ndarray, count=None
                     ) -> Tuple[Dict[str, jnp.ndarray], Dict[str, jnp.ndarray]]:
        """The ENCODED staging block (payload, sideband) of rows ``idx`` on the
        device, still encoded; negative lanes, and lanes past ``count`` (all
        lanes when None), give zero rows.  Host-resident only."""
        count = idx.shape[0] if count is None else count
        with jax.named_scope(SCOPE_HOST_LINK):
            take = lambda t: {k: _host_take(v, idx, self._valid(v, idx, count))  # noqa: E731
                              for k, v in t.items()}
            return take(self.data), take(self.sideband)

    def scatter_block(self, idx: jnp.ndarray, data_blk: Dict[str, jnp.ndarray],
                      side_blk: Dict[str, jnp.ndarray], active: jnp.ndarray,
                      count=None) -> "HostStore":
        """Write an encoded block into rows ``idx`` where ``active``, over the
        first ``count`` lanes (all when None).  Host-resident only."""
        count = idx.shape[0] if count is None else count
        with jax.named_scope(SCOPE_HOST_LINK):
            put = lambda t, blk: {k: _host_put(v, idx, blk[k],  # noqa: E731
                                               active & self._valid(v, idx, count))
                                  for k, v in t.items()}
            return dataclasses.replace(self, data=put(self.data, data_blk),
                                       sideband=put(self.sideband, side_blk))

    @staticmethod
    def _valid(leaf: HostPieces, idx: jnp.ndarray, count) -> jnp.ndarray:
        return (idx >= 0) & (idx < leaf.vocab) & (jnp.arange(idx.shape[0]) < count)

    # ----- accounting -------------------------------------------------------

    def row_wire_bytes(self, batch_dims: int = 1) -> int:
        """Encoded bytes per row across all leaves — what one transmitter
        lane moves over the host link (load or writeback).  ``batch_dims``
        counts the leading non-row dims: 1 for a plain [vocab, ...] store,
        2 for a shard-stacked [S, vocab_s, ...] one."""
        total = 0
        for k, leaf in self.data.items():
            if self.is_encoded(k):
                total += self._codec.row_bytes(tuple(leaf.shape[batch_dims:]), self._out)
            else:
                total += int(
                    np.prod(leaf.shape[batch_dims:], dtype=np.int64)
                ) * jnp.dtype(leaf.dtype).itemsize
        return total

    def host_bytes(self) -> int:
        """Total host-tier footprint (payload + sideband)."""
        n = 0
        for leaf in list(self.data.values()) + list(self.sideband.values()):
            n += int(np.prod(leaf.shape, dtype=np.int64)) * jnp.dtype(leaf.dtype).itemsize
        return n

    def fp32_equiv_bytes(self) -> int:
        """What the same table would cost stored raw (the pre-store layout)."""
        n = 0
        for k, leaf in self.data.items():
            item = jnp.dtype(self._out if self.is_encoded(k) else leaf.dtype).itemsize
            n += int(np.prod(leaf.shape, dtype=np.int64)) * item
        return n

    def bytes_saved(self) -> int:
        return self.fp32_equiv_bytes() - self.host_bytes()
