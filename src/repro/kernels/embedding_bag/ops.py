"""Jit'd wrapper for the embedding-bag kernel.

Densifies (sorted) CSR-style segment ids into [num_segments, max_bag] and
invokes the Pallas kernel; handles the mean combiner and empty bags.

Differentiable: the fused gather+pool has a custom VJP (the standard
embedding-bag backward — scatter-add of the pooled cotangent into the touched
rows), so the cached-embedding pooled path can run the kernel inside the loss
closure and still deliver gradients to the fast-tier weights.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.contracts import contract
from repro.kernels.embedding_bag.kernel import embedding_bag_chunked


def densify(flat_ids: jnp.ndarray, segment_ids: jnp.ndarray, num_segments: int, max_bag: int):
    """[N] (sorted segments) -> [num_segments, max_bag] id matrix, -1 padded."""
    n = flat_ids.shape[0]
    starts = jnp.searchsorted(segment_ids, jnp.arange(num_segments), side="left")
    pos = jnp.arange(n) - starts[segment_ids]
    slot = jnp.where(pos < max_bag, segment_ids * max_bag + pos, num_segments * max_bag)
    dense = jnp.full((num_segments * max_bag,), -1, jnp.int32)
    dense = dense.at[slot].set(flat_ids, mode="drop")
    return dense.reshape(num_segments, max_bag)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _embedding_bag(table, flat_ids, segment_ids, num_segments, combiner, max_bag):
    dense = densify(flat_ids, segment_ids, num_segments, max_bag)
    out = embedding_bag_chunked(table, dense)
    if combiner == "mean":
        valid = jnp.sum((dense >= 0).astype(jnp.float32), axis=1)
        out = out / jnp.maximum(valid, 1)[:, None].astype(out.dtype)
    return out


def _fwd(table, flat_ids, segment_ids, num_segments, combiner, max_bag):
    out = _embedding_bag(table, flat_ids, segment_ids, num_segments, combiner, max_bag)
    proto = jnp.zeros((0,) + table.shape[1:], table.dtype)  # shape/dtype carrier
    return out, (table.shape[0], proto, flat_ids, segment_ids)


def _bwd(num_segments, combiner, max_bag, res, g):
    vocab, proto, flat_ids, segment_ids = res
    dtype = proto.dtype
    # the forward pools only the lanes densify kept — a bag overflowing
    # max_bag is truncated — so the backward must use the SAME lane mask
    # (and the same per-bag count for the mean combiner)
    starts = jnp.searchsorted(segment_ids, jnp.arange(num_segments), side="left")
    pos = jnp.arange(flat_ids.shape[0]) - starts[segment_ids]
    valid = (flat_ids >= 0) & (pos < max_bag)
    g_rows = jnp.take(g, segment_ids, axis=0)  # [N, D] pooled cotangent per lane
    if combiner == "mean":
        cnt = jax.ops.segment_sum(
            valid.astype(g.dtype), segment_ids, num_segments=num_segments
        )
        g_rows = g_rows / jnp.maximum(cnt, 1.0)[segment_ids][:, None]
    g_rows = g_rows * valid[:, None].astype(g.dtype)
    safe = jnp.where(valid, flat_ids, vocab)  # padding lanes dropped OOB
    d_table = (
        jnp.zeros((vocab, g.shape[-1]), dtype).at[safe].add(g_rows.astype(dtype), mode="drop")
    )
    f0 = lambda x: np.zeros(x.shape, jax.dtypes.float0)  # int inputs: zero cotangent
    return d_table, f0(flat_ids), f0(segment_ids)


_embedding_bag.defvjp(_fwd, _bwd)


@contract(max_sort_size=0)
@functools.partial(jax.jit, static_argnames=("num_segments", "combiner", "max_bag"))
def embedding_bag(
    table: jnp.ndarray,
    flat_ids: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    combiner: str = "sum",
    max_bag: int = 0,
) -> jnp.ndarray:
    if max_bag <= 0:
        max_bag = int(flat_ids.shape[0])  # worst case (one hot bag)
    return _embedding_bag(table, flat_ids, segment_ids, num_segments, combiner, max_bag)
