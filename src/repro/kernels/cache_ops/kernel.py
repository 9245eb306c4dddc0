"""Pallas TPU kernels for the cache hot path (ROADMAP item 3).

Three kernels, each the accelerator lowering of a ``ref.py`` function and
verified bit-identical against it, in interpret mode by the tests and
compiled on the chip by ``chip_smoke.py``:

* ``victim_threshold_pallas`` — the tiled streaming reducer behind bounded
  top-K victim selection.  The eviction-key array streams HBM -> VMEM one
  tile at a time; SMEM carries the running radix threshold and per-round
  count across grid steps (grid iteration is sequential on TPU).  32 bit
  rounds + one greater-than round produce ``(t, n_gt)`` — the kv-th largest
  key and the count strictly above it — after which the O(kv) select/sort
  epilogue runs in XLA (shared verbatim with the reference route).
* ``bucketize_pallas`` — the [S, lanes] per-shard routing image, one
  (shard, lane tile) pair per grid step (the id all-to-all payload of the
  sharded collection).
* ``gather_decode_pallas`` — the tiered-arena fused gather+decode: slot ids
  are scalar-prefetched so the BlockSpec index maps pick the head tile OR
  tail payload tile holding each lane's row, and the kernel picks that row
  and decodes tail lanes in-register (fp16 upcast / int8 scale+zero-point)
  instead of decoding a full gathered block and selecting afterwards.

Blocks follow the TPU's tiling rule: the last two block dims are multiples
of the dtype's native tile (8/16/32 sublanes x 128 lanes) or span the whole
array.  Flat arrays are folded into lane-dense ``[rows, 128]`` tiles; row
gathers fetch the native tile that holds the row and slice it in VMEM.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode, map_chunks, sublane_tile

__all__ = [
    "bucketize_pallas",
    "gather_decode_pallas",
    "victim_threshold_pallas",
]

_LANES = 128
# rows of 128 lanes per grid step of the streaming kernels, and slot lanes
# scalar-prefetched into SMEM per gather call (1 MiB of SMEM holds 256K)
THRESHOLD_BLOCK_ROWS = 1024
BUCKETIZE_BLOCK_ROWS = 512
GATHER_CHUNK = 1 << 15


# ---------------------------------------------------------------------------
# bounded top-K: the threshold reducer
# ---------------------------------------------------------------------------


def _threshold_kernel(u_ref, t_ref, ngt_ref, cur_ref, cnt_ref, *, kv: int):
    b, j = pl.program_id(0), pl.program_id(1)
    tiles = pl.num_programs(1)

    @pl.when((b == 0) & (j == 0))
    def _init():
        cur_ref[0, 0] = jnp.uint32(0)
        cnt_ref[0, 0] = jnp.int32(0)

    @pl.when((b > 0) & (j == 0))
    def _commit():
        # close bit round b-1: keep its candidate iff >= kv keys reach it
        prev_bit = jnp.uint32(1) << (jnp.uint32(32) - b.astype(jnp.uint32))
        cand = cur_ref[0, 0] | prev_bit
        cur_ref[0, 0] = jnp.where(cnt_ref[0, 0] >= kv, cand, cur_ref[0, 0])
        cnt_ref[0, 0] = jnp.int32(0)

    tile = u_ref[...]

    @pl.when(b < 32)
    def _count_ge():
        cand = cur_ref[0, 0] | (
            jnp.uint32(1) << (jnp.uint32(31) - b.astype(jnp.uint32))
        )
        cnt_ref[0, 0] += jnp.sum((tile >= cand).astype(jnp.int32))

    @pl.when(b == 32)
    def _count_gt():  # final round: count keys strictly above the threshold
        cnt_ref[0, 0] += jnp.sum((tile > cur_ref[0, 0]).astype(jnp.int32))

    @pl.when((b == 32) & (j == tiles - 1))
    def _finalize():
        t_ref[0, 0] = cur_ref[0, 0]
        ngt_ref[0, 0] = cnt_ref[0, 0]


def _fold_lanes(x: jnp.ndarray, fill, block_rows: int) -> Tuple[jnp.ndarray, int]:
    """Pad a flat array with ``fill`` and fold it into ``[tiles * rows, 128]``
    lane-dense tiles of ``rows`` (a multiple of 8) sublanes each — the
    (8, 128)-aligned layout the TPU's block rule asks for."""
    n = x.shape[0]
    rows = -(-n // _LANES)
    block_rows = min(block_rows, -(-rows // 8) * 8)
    tiles = -(-rows // block_rows)
    pad = tiles * block_rows * _LANES - n
    if pad:
        x = jnp.concatenate([x, jnp.full((pad,), fill, x.dtype)])
    return x.reshape(tiles * block_rows, _LANES), block_rows


def victim_threshold_pallas(
    u: jnp.ndarray,  # uint32 [C] order-transformed eviction keys
    kv: int,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(t, n_gt): the kv-th largest of ``u`` and the count strictly above it.

    Padding note: ``u`` is padded with 0 (the minimum of the transformed
    domain).  Every bit-round candidate has at least one bit set (> 0) and
    the final round compares strictly, so pad lanes never count.
    """
    u2, block_rows = _fold_lanes(u, 0, THRESHOLD_BLOCK_ROWS)
    tiles = u2.shape[0] // block_rows
    t, ngt = pl.pallas_call(
        functools.partial(_threshold_kernel, kv=int(kv)),
        grid=(33, tiles),
        in_specs=[pl.BlockSpec((block_rows, _LANES), lambda b, j: (j, 0))],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.uint32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.SMEM((1, 1), jnp.uint32),  # running threshold
            pltpu.SMEM((1, 1), jnp.int32),  # per-round count
        ],
        interpret=interpret_mode(interpret),
    )(u2)
    return t[0, 0], ngt[0, 0]


# ---------------------------------------------------------------------------
# [S, lanes] bucketize
# ---------------------------------------------------------------------------


def _bucketize_kernel(owner_ref, local_ref, out_ref):
    s = pl.program_id(0)
    local = local_ref[...]
    mine = (owner_ref[...] == s) & (local >= 0)
    out_ref[...] = jnp.where(mine, local, -1)


def bucketize_pallas(
    owner: jnp.ndarray,  # int32 [U] owning shard (-1 pad/replicated)
    local: jnp.ndarray,  # int32 [U] shard-local row (-1 pad/replicated)
    num_shards: int,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    u = owner.shape[0]
    s = int(num_shards)
    owner2, block_rows = _fold_lanes(owner, -1, BUCKETIZE_BLOCK_ROWS)
    local2, _ = _fold_lanes(local, -1, block_rows)
    rows = owner2.shape[0]
    lane_spec = pl.BlockSpec((block_rows, _LANES), lambda s_, j: (j, 0))
    out = pl.pallas_call(
        _bucketize_kernel,
        grid=(s, rows // block_rows),
        in_specs=[lane_spec, lane_spec],
        out_specs=pl.BlockSpec((None, block_rows, _LANES), lambda s_, j: (s_, j, 0)),
        out_shape=jax.ShapeDtypeStruct((s, rows, _LANES), jnp.int32),
        interpret=interpret_mode(interpret),
    )(owner2, local2)
    return out.reshape(s, rows * _LANES)[:, :u]


# ---------------------------------------------------------------------------
# tiered-arena fused gather + decode
# ---------------------------------------------------------------------------


def _row_tile(n_rows: int, dtype) -> int:
    """Rows per gather block: the dtype's native tile, or the whole array
    when it holds fewer rows than one tile."""
    return min(sublane_tile(dtype), n_rows)


def _f16_bits_to_f32(bits: jnp.ndarray) -> jnp.ndarray:
    """Exact float16 -> float32 from the half's bit pattern held in int32
    (the TPU loads no f16 vectors; int16 loads and 32-bit math it has)."""
    h = bits & 0xFFFF
    sign = (h & 0x8000) << 16
    exp = (h >> 10) & 0x1F
    man = h & 0x3FF
    normal = sign | ((exp + 112) << 23) | (man << 13)
    special = sign | 0x7F800000 | (man << 13)  # inf / nan
    tiny = man.astype(jnp.float32) * jnp.float32(2.0**-24)  # zero/subnormal
    tiny = jax.lax.bitcast_convert_type(tiny, jnp.int32) | sign
    out = jnp.where(exp == 0, tiny, jnp.where(exp == 31, special, normal))
    return jax.lax.bitcast_convert_type(out, jnp.float32)


def _gather_decode_kernel(
    slots_ref, head_ref, tail_ref, side_ref, out_ref, wide_ref,
    *, h: int, t: int, codec: str, th: int, tt: int, ts: int, to: int,
):
    i = pl.program_id(0)
    slot = slots_ref[i]
    in_tail = slot >= h
    valid = (slot >= 0) & (slot < h + t)  # OOB slots give zero rows, like the
    # reference route's fill-gather (whose zero payload decodes to zero)
    hs = jnp.where((slot >= 0) & (slot < h), slot, 0)  # same clamps as the
    tr = jnp.where(in_tail & valid, slot - h, 0)  # index maps below
    head_row = head_ref[pl.ds(hs % th, 1), :].astype(out_ref.dtype)
    # the packed (16/32-row) payload tile widens to int32 in VMEM first: the
    # TPU slices single rows out of 32-bit tiles only (the widening is exact)
    wide_ref[...] = tail_ref[...].astype(jnp.int32)
    payload = wide_ref[pl.ds(tr % tt, 1), :]
    if codec == "int8":
        side = side_ref[pl.ds(tr % ts, 1), :]
        # f32 accumulate then cast — the exact codec decode order
        tail_row = (
            payload.astype(jnp.float32) * side[:, 0:1] + side[:, 1:2]
        ).astype(out_ref.dtype)
    else:  # fp16 bits: plain upcast
        tail_row = _f16_bits_to_f32(payload).astype(out_ref.dtype)
    row = jnp.where(in_tail, tail_row, head_row)
    out_ref[pl.ds(i % to, 1), :] = jnp.where(valid, row, jnp.zeros_like(row))


def gather_decode_pallas(
    head: jnp.ndarray,  # [H, D] fp32 head rows
    tail: jnp.ndarray,  # [T, D] encoded tail payload
    sideband: Optional[jnp.ndarray],  # [T, 2] (scale, zero_point) or None
    slots: jnp.ndarray,  # int32 [K] arena slots (-1 padding)
    codec: str,
    out_dtype=jnp.float32,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused decode-on-read gather: one [K, D] pass, each lane streaming
    the native tile that holds either its head row or its tail payload
    (+ sideband) through VMEM and decoding in-register.  Bit-identical to
    ``ref.arena_gather`` with the store codecs (fp16 upcast; int8
    ``payload * scale + zero_point``).  Output lanes revisit one
    ``[to, D]`` block for ``to`` consecutive grid steps, so the [K, D]
    result is written back one native tile at a time.  Slots are
    scalar-prefetched into SMEM ``GATHER_CHUNK`` lanes per kernel call."""
    if codec not in ("fp16", "int8"):
        raise ValueError(f"gather_decode_pallas supports fp16/int8, got {codec!r}")
    h, d = head.shape
    t = tail.shape[0]
    side = sideband
    if side is None:  # fp16: dummy sideband keeps the spec list static
        side = jnp.zeros((max(t, 1), 2), jnp.float32)
    if codec == "fp16":
        tail = jax.lax.bitcast_convert_type(tail, jnp.int16)
    out_dtype = jnp.dtype(out_dtype)
    th, tt = _row_tile(h, head.dtype), _row_tile(t, tail.dtype)
    ts = _row_tile(side.shape[0], side.dtype)

    def head_index(i, slots_pf):
        s = slots_pf[i]
        return jnp.where((s >= 0) & (s < h), s, 0) // th, 0

    def tail_index(tile):
        def index(i, slots_pf):
            s = slots_pf[i]
            return jnp.where((s >= h) & (s < h + t), s - h, 0) // tile, 0

        return index

    def call(lane_slots):
        k = lane_slots.shape[0]
        to = _row_tile(k, out_dtype)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # slots
            grid=(k,),
            in_specs=[
                pl.BlockSpec((th, d), head_index),
                pl.BlockSpec((tt, d), tail_index(tt)),
                pl.BlockSpec((ts, 2), tail_index(ts)),
            ],
            out_specs=pl.BlockSpec((to, d), lambda i, slots_pf: (i // to, 0)),
            scratch_shapes=[pltpu.VMEM((tt, d), jnp.int32)],  # widened payload
        )
        return pl.pallas_call(
            functools.partial(
                _gather_decode_kernel, h=h, t=t, codec=codec, th=th, tt=tt, ts=ts, to=to
            ),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((k, d), out_dtype),
            interpret=interpret_mode(interpret),
        )(lane_slots, head, tail, side)

    return map_chunks(call, slots, GATHER_CHUNK, -1)

