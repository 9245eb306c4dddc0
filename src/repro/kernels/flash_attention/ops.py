"""Jit'd wrapper: [B,S,H,hd] layout glue + custom_vjp (bwd recomputes via the
jnp oracle — standard recompute-in-backward; a dedicated bwd kernel is the
real-TPU follow-up)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.analysis.contracts import contract
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attn(q, k, v, causal, window):
    return flash_attention_pallas(q, k, v, causal=causal, window=window)


def _attn_fwd(q, k, v, causal, window):
    return _attn(q, k, v, causal, window), (q, k, v)


def _attn_bwd(causal, window, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda q_, k_, v_: attention_ref(q_, k_, v_, causal, window), q, k, v)
    return vjp(g)


_attn.defvjp(_attn_fwd, _attn_bwd)


@contract(max_sort_size=0)
def flash_attention(
    q: jnp.ndarray,  # [B, S, Hq, hd] (model layout)
    k: jnp.ndarray,  # [B, S, Hkv, hd]
    v: jnp.ndarray,
    causal: bool = True,
    window: Optional[int] = None,
) -> jnp.ndarray:
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = _attn(qt, kt, vt, causal, window)
    return o.transpose(0, 2, 1, 3)
