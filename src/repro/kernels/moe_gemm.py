"""Pallas TPU grouped expert GEMM: (E, C, d) x (E, d, f) -> (E, C, f).

The batched per-expert matmul at the heart of the replicated-dispatch EP
path (repro.models.moe). Classic tiled matmul with a sequential K-loop
accumulating into VMEM scratch; expert index is an outer parallel grid axis,
so one kernel launch covers all local experts.

Block sizes default to MXU-aligned (128) tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _gemm_kernel(x_ref, w_ref, o_ref, acc_scr, *, nk: int):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot(x_ref[0], w_ref[0],
                                preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


def grouped_gemm(x, w, *, block_c: int = 128, block_f: int = 128,
                 block_d: int = 512, interpret: bool = False):
    """x (E, C, d); w (E, d, f) -> (E, C, f)."""
    E, C, d = x.shape
    f = w.shape[-1]
    bc, bf, bd = min(block_c, C), min(block_f, f), min(block_d, d)
    assert C % bc == 0 and f % bf == 0 and d % bd == 0, (C, f, d, bc, bf, bd)
    grid = (E, C // bc, f // bf, d // bd)

    kernel = functools.partial(_gemm_kernel, nk=grid[3])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, bd, bf), lambda e, i, j, k: (e, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, C, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, w)
