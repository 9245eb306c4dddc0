"""Pallas TPU FM interaction: fused sum-square-trick pooling.

One grid step per batch block: loads a [block_b, F, D] tile into VMEM,
computes 0.5 * ((sum_f v)^2 - sum_f v^2) . sum_d entirely in registers, and
writes a [block_b, 1] partial.  F*D per sample is tiny (recsys: 40 x 10), so
the block_b dimension is what keeps the VPU busy; the fusion avoids
materializing the [B, D] sum and [B, F, D] square in HBM, which is what the
XLA path does (3 HBM round-trips -> 1).

In VMEM each sample's [F, D] slab pads to native (8, 128) f32 tiles, so a
40 x 10 sample occupies 40 x 128 words; ``block_rows`` sizes the batch block
to that padded footprint so the double-buffered input and its f32
temporaries stay inside the 16 MiB scoped VMEM.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

_BLOCK_BYTES = 2 << 20  # padded f32 bytes of one input block


def block_rows(f: int, d: int) -> int:
    """Batch rows per block: a power of two >= 8 whose tile-padded f32
    [block, F, D] slab fits ``_BLOCK_BYTES``."""
    per_row = (-(-f // 8) * 8) * (-(-d // 128) * 128) * 4
    rows = 8
    while rows * 2 * per_row <= _BLOCK_BYTES:
        rows *= 2
    return rows


def _kernel(v_ref, out_ref):
    v = v_ref[...].astype(jnp.float32)  # [bb, F, D]
    s = v.sum(axis=1)  # [bb, D]
    sq = (v * v).sum(axis=1)
    out_ref[...] = (0.5 * (s * s - sq).sum(axis=-1, keepdims=True)).astype(out_ref.dtype)


def fm_interaction_pallas(
    v: jnp.ndarray,  # [B, F, D]
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """[B] FM second-order term; the batch pads with zero rows to a whole
    number of ``block_rows(F, D)`` blocks."""
    b, f, d = v.shape
    block_b = min(block_rows(f, d), b)
    pad = (-b) % block_b
    if pad:
        v = jnp.concatenate([v, jnp.zeros((pad, f, d), v.dtype)], axis=0)
    out = pl.pallas_call(
        _kernel,
        grid=((b + pad) // block_b,),
        in_specs=[pl.BlockSpec((block_b, f, d), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b + pad, 1), v.dtype),
        interpret=interpret_mode(interpret),
    )(v)
    return out[:b, 0]
