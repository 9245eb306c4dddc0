"""Pallas TPU flash attention (forward): blocked online-softmax, causal and
sliding-window masks, GQA via head-index mapping (no KV replication in HBM).

Grid: (B, Hq, nq, nk) with the KV loop innermost; running max / sum / output
accumulator live in VMEM scratch and the output tile is written on the last
KV step (the canonical FlashAttention schedule on TPU: q tile stays resident,
K/V tiles stream through VMEM).  Fully-masked KV blocks are skipped by a
block-level predicate (for causal this halves work; for sliding-window it
makes cost O(S * W)).

Used for the LM archs when ``config.use_pallas`` (real TPU); XLA's chunked
attention (nn.layers.gqa_attention) is the CPU/dry-run path.  The backward
pass recomputes through the jnp reference via ``jax.custom_vjp`` in ops.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, scale, causal, window, block_q, block_k, nk):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window

    # block-level skip: any overlap at all?
    q_lo, q_hi = iq * block_q, (iq + 1) * block_q - 1
    k_lo, k_hi = ik * block_k, (ik + 1) * block_k - 1
    live = jnp.bool_(True)
    if causal:
        live &= q_hi >= k_lo
    if window is not None:
        live &= (q_lo - k_hi) < window

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, d]
        s = q @ k.T  # [bq, bk]
        s = jnp.where(mask, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + p.sum(-1)
        m_scr[...] = m_new
        vv = v_ref[0, 0].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * corr[:, None] + p @ vv

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jnp.ndarray,  # [B, Hq, Sq, D]
    k: jnp.ndarray,  # [B, Hkv, Sk, D]
    v: jnp.ndarray,  # [B, Hkv, Sk, D]
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 256,
    block_k: int = 256,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    g = hq // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0
    nq, nk = sq // block_q, sk // block_k
    scale = 1.0 / np.sqrt(d)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, nk=nk,
    )
    return pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, iq, ik: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h, iq, ik: (b_, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h, iq, ik: (b_, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda b_, h, iq, ik: (b_, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(q, k, v)
