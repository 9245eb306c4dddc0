"""Pallas kernels for the cache hot path and the recsys interaction ops.

Every kernel compiles for the TPU and runs in the Pallas interpreter
everywhere else (the CPU test suite); ``interpret_mode`` is the one place
that decides, from the backend.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["interpret_mode", "map_chunks", "sublane_tile"]


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """``interpret`` if given, else True off the TPU: a TPU never runs a
    kernel in the interpreter unless a caller asks for it explicitly."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"


def sublane_tile(dtype) -> int:
    """Rows of one native TPU tile for ``dtype``: 8 for 32-bit, 16 for
    16-bit, 32 for 8-bit types (the lane width is always 128)."""
    return 32 // jnp.dtype(dtype).itemsize


def map_chunks(call, lanes: jnp.ndarray, chunk: int, fill) -> jnp.ndarray:
    """``call(lanes)`` over ``chunk``-lane pieces (``fill``-padded) in one
    device loop, so a scalar-prefetched lane array never outgrows SMEM;
    results concatenate along their leading dim."""
    n = lanes.shape[0]
    if n <= chunk:
        return call(lanes)
    pieces = -(-n // chunk)
    pad = pieces * chunk - n
    lanes = jnp.concatenate([lanes, jnp.full((pad,) + lanes.shape[1:], fill, lanes.dtype)])
    out = jax.lax.map(call, lanes.reshape((pieces, chunk) + lanes.shape[1:]))
    return out.reshape((pieces * chunk,) + out.shape[2:])[:n]
