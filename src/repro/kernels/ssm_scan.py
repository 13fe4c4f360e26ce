"""Pallas TPU chunked SSD scan (Mamba2 / mLSTM linear-attention core).

Computes  state_t = exp(log_a_t) * state_{t-1} + k_t v_t^T ;  y_t = q_t state_t
in chunked form: intra-chunk work is two (L x L)/(L x Dk) MXU matmuls; the
inter-chunk recurrence is carried across the sequential chunk grid axis in a
(Dk, Dv) f32 VMEM scratch. Emits both y and the final state (for decode
cache handoff).

Grid: (B*H, num_chunks) with num_chunks sequential.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_kernel(q_ref, k_ref, v_ref, la_ref, y_ref, state_out_ref, state_scr,
                *, L: int, nc: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    q = q_ref[0].astype(jnp.float32)                      # (L, Dk)
    k = k_ref[0].astype(jnp.float32)                      # (L, Dk)
    v = v_ref[0].astype(jnp.float32)                      # (L, Dv)
    la = la_ref[0].astype(jnp.float32)                    # (L, 1)
    lcum = jnp.cumsum(la, axis=0)                         # inclusive
    total = lcum[L - 1, 0]

    # intra-chunk: scores[s,t] = (q_s . k_t) * exp(lcum_s - lcum_t) * (s>=t)
    s_mat = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    rel = lcum - lcum.reshape(1, L)                       # (L,L) via bcast
    row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    decay = jnp.where(row >= col, jnp.exp(rel), 0.0)
    y_intra = jax.lax.dot((s_mat * decay).astype(v.dtype), v,
                          preferred_element_type=jnp.float32)

    # inter-chunk: y_inter = exp(lcum) * q @ state_prev
    state_prev = state_scr[...]                           # (Dk, Dv)
    y_inter = jax.lax.dot((q * jnp.exp(lcum)).astype(jnp.float32),
                          state_prev, preferred_element_type=jnp.float32)
    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: state = exp(total) * state + sum_t exp(total - lcum_t) k_t v_t^T
    w = jnp.exp(total - lcum)                             # (L, 1)
    s_chunk = jax.lax.dot_general(k * w, v, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    state_scr[...] = state_prev * jnp.exp(total) + s_chunk

    @pl.when(ci == nc - 1)
    def _emit_state():
        state_out_ref[0] = state_scr[...]


def ssd_scan_bhs(q, k, v, log_a, *, chunk: int = 128,
                 interpret: bool = False):
    """q,k (BH, S, Dk); v (BH, S, Dv); log_a (BH, S, 1).

    Returns (y (BH, S, Dv), final_state (BH, Dk, Dv) f32)."""
    BH, S, Dk = q.shape
    Dv = v.shape[-1]
    L = min(chunk, S)
    assert S % L == 0, (S, L)
    nc = S // L

    kernel = functools.partial(_ssd_kernel, L=L, nc=nc)
    y, state = pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, L, Dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, L, Dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, L, Dv), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, L, 1), lambda b, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, L, Dv), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, Dk, Dv), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, Dv), q.dtype),
            jax.ShapeDtypeStruct((BH, Dk, Dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((Dk, Dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, log_a)
    return y, state
