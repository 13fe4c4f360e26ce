"""Pallas TPU flash attention (prefill): causal / sliding-window / full.

Grid: (batch*heads, num_q_blocks, num_kv_blocks); the kv-block axis is the
innermost, sequential ("arbitrary") dimension, carrying the online-softmax
state (m, l, acc) in VMEM scratch. Blocks are MXU-aligned (q/kv block
lengths multiples of 128 on TPU; head_dim padded to 128 by the wrapper).

Fully-masked (q-block, kv-block) pairs — future blocks under causality,
expired blocks under SWA — are skipped with @pl.when, so causal attention
does ~half the work and SWA does O(S * window).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int, bq: int, bk: int,
                  nk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo = qi * bq
    k_lo = ki * bk
    # block-level visibility: any (q,k) pair in range?
    live = True
    if causal:
        live = jnp.logical_and(live, k_lo <= q_lo + bq - 1)
    if window:
        live = jnp.logical_and(live, k_lo + bk - 1 > q_lo - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                  # (bq, D)
        k = k_ref[0].astype(jnp.float32)                  # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), dtype=bool)
        if causal:
            mask &= q_pos >= k_pos
        if window:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                               # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)                  # (bk, D)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool = True, window: int = 0,
                         scale: float = 1.0, block_q: int = 128,
                         block_k: int = 128, interpret: bool = False):
    """q,k,v (BH, S, D) — same head count (GQA repeated by caller)."""
    BH, S, D = q.shape
    T = k.shape[1]
    bq = min(block_q, S)
    bk = min(block_k, T)
    assert S % bq == 0 and T % bk == 0, (S, T, bq, bk)
    nq, nk = S // bq, T // bk

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               window=window, bq=bq, bk=bk, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
