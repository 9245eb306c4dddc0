"""Pallas TPU embedding-bag: fused gather + segment-sum.

This is the paper's hot op (torch ``EmbeddingBag``, Fig. 1) as a TPU kernel.
Bags are presented DENSE: ``ids_dense`` [num_segments, max_bag] with -1
padding (the jit wrapper densifies CSR-style sorted segment ids).  The grid
is (dim_blocks, segments, max_bag); the id matrix is scalar-prefetched (SMEM)
so the table BlockSpec ``index_map`` picks, per step, the native tile
(8/16/32 rows by dtype) that holds the bag's next row, and the kernel slices
that row out in VMEM.  The output block holds one native tile of segments
and is revisited on consecutive steps (init at t == 0, accumulate after),
the canonical Pallas reduction pattern.  Padding lanes multiply by 0.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode, map_chunks, sublane_tile

MAX_IDS = 1 << 15  # scalar-prefetched ids per kernel call


def _kernel(ids_ref, row_ref, out_ref, *, tv: int, to: int):
    b, t = pl.program_id(1), pl.program_id(2)
    max_bag = pl.num_programs(2)
    rid = ids_ref[b * max_bag + t]
    valid = (rid >= 0).astype(row_ref.dtype)
    row = row_ref[pl.ds(jnp.maximum(rid, 0) % tv, 1), :] * valid
    seg = pl.ds(b % to, 1)

    @pl.when(t == 0)
    def _init():
        out_ref[seg, :] = row

    @pl.when(t > 0)
    def _acc():
        out_ref[seg, :] += row


def embedding_bag_pallas(
    table: jnp.ndarray,  # [V, D]
    ids_dense: jnp.ndarray,  # [num_segments, max_bag] int32, -1 padding
    block_d: int = 512,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    v, d = table.shape
    s, max_bag = ids_dense.shape
    block_d = min(block_d, d)
    assert d % block_d == 0, "dim must divide block_d"
    nd = d // block_d
    tv = min(sublane_tile(table.dtype), v)
    to = min(sublane_tile(table.dtype), s)

    def row_index(j, b, t, ids):
        return jnp.maximum(ids[b * max_bag + t], 0) // tv, j

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # flattened ids_dense
        grid=(nd, s, max_bag),
        in_specs=[pl.BlockSpec((tv, block_d), row_index)],
        out_specs=pl.BlockSpec((to, block_d), lambda j, b, t, ids: (b // to, j)),
    )
    fn = pl.pallas_call(
        functools.partial(_kernel, tv=tv, to=to),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, d), table.dtype),
        interpret=interpret_mode(interpret),
    )
    return fn(ids_dense.reshape(-1), table)


def embedding_bag_chunked(
    table: jnp.ndarray,
    ids_dense: jnp.ndarray,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """``embedding_bag_pallas`` over bag chunks of at most ``MAX_IDS`` ids,
    so the scalar-prefetched id matrix never outgrows SMEM (1 MiB)."""
    segs = max(1, MAX_IDS // ids_dense.shape[1])
    return map_chunks(
        lambda ids: embedding_bag_pallas(table, ids, interpret=interpret),
        ids_dense, segs, -1,
    )
