"""Pallas TPU flash-decode: one query token per sequence against a KV cache.

Head layout: the KV cache keeps its ``(..., Hkv, D)`` minor axes, which
the TPU cannot tile one head at a time (a block whose second-minor dim is
1 out of Hkv is refused), and which cannot be merged into one lane axis
outside the kernel without XLA copying the whole cache into the new
tiling. So each block carries EVERY KV head as it lies in HBM, and the
kernel folds it to ``(block * Hkv, D)`` rows ordered (token, kv head).
One ``(H, D) x (D, block * Hkv)`` MXU matmul then scores every query head
against every row, and a mask keeps only the rows of the head's own KV
head: masked scores get zero softmax weight, so the value matmul
``(H, block * Hkv) x (block * Hkv, D)`` sums exactly over the head's own
tokens. The matmuls, and the softmax work on the ``(H, block * Hkv)``
scores, are ``Hkv`` times the minimum. Whether decode stays memory-bound
under that has not been timed on a chip; blocking fewer KV heads per grid
step would cut the waste.

Grid: (batch, num_kv_blocks); the kv-block axis is sequential and carries
(m, l, acc) scratch. Per-sequence valid lengths arrive via SMEM.

Paged variants (``paged_flash_decode``, ``paged_mla_decode``) decode
straight out of a block/page-table cache (see ``repro.serving.paged``):
the per-slot page table and valid lengths ride in as scalar-prefetch
operands, so each KV block's *physical* page index is computed before the
DMA is issued — gather-by-page-table without ever materializing a
contiguous view. Block size equals the page size; pages whose first token
is at/past the slot's valid length are skipped entirely, so per-slot work
scales with live pages. The MLA variant attends over paged compressed
latents ``c_kv`` plus the shared rope keys and accumulates output in
latent space (absorbed-matrix decode: the caller applies ``w_uv``/``wo``).

Copy-on-write prefix sharing (``repro.serving.paged.PrefixCache``) needs
NO kernel change: sharing is pure page-table aliasing — two slots whose
table rows name the same physical page read the same KV through the same
scalar-prefetched gather, and the engine guarantees a shared page is
never written (first write copies it and repoints the row, so by the time
this kernel runs every writable page is exclusively owned).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_MAX_ROWS = 4096    # folded (token, kv head) rows per contiguous block


def _row_layout(H: int, Hkv: int, block: int):
    """Int32 maps of the folded block: the KV head of each query head
    (H, 1), and the KV head and token offset of each row (1, block*Hkv)."""
    rows = np.arange(block * Hkv)
    return (jnp.asarray((np.arange(H) // (H // Hkv))[:, None], jnp.int32),
            jnp.asarray((rows % Hkv)[None, :], jnp.int32),
            jnp.asarray((rows // Hkv)[None, :], jnp.int32))


def _init_state(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _attend_block(q_ref, qkv_ref, rkv_ref, rtok_ref, k_ref, v_ref, m_scr,
                  l_scr, acc_scr, *, k_lo, length, scale: float):
    """Online-softmax update of every head against one (block, Hkv, D) KV
    block."""
    q = q_ref[...]                                        # (H, D)
    blk, hkv, d = k_ref.shape
    k = k_ref[...].reshape(blk * hkv, d)                  # rows (tok, head)
    v = v_ref[...].reshape(blk * hkv, d)
    if k.dtype != q.dtype:
        k, v = k.astype(q.dtype), v.astype(q.dtype)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    own = ((qkv_ref[...] == rkv_ref[...])
           & (k_lo + rtok_ref[...] < length))             # (H, blk * Hkv)
    s = jnp.where(own, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new


def _finish(o_ref, l_scr, acc_scr):
    o_ref[...] = (acc_scr[...] /
                  jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def _decode_kernel(len_ref, q_ref, qkv_ref, rkv_ref, rtok_ref, k_ref, v_ref,
                   o_ref, m_scr, l_scr, acc_scr, *, scale: float, bk: int,
                   nk: int):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        _init_state(m_scr, l_scr, acc_scr)

    length = len_ref[pl.program_id(0)]                    # valid kv count

    @pl.when(ki * bk < length)
    def _compute():
        _attend_block(q_ref, qkv_ref, rkv_ref, rtok_ref, k_ref, v_ref,
                      m_scr, l_scr, acc_scr, k_lo=ki * bk, length=length,
                      scale=scale)

    @pl.when(ki == nk - 1)
    def _done():
        _finish(o_ref, l_scr, acc_scr)


def _decode_scratch(H: int, D: int):
    return [pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32)]


def _block_tokens(Skv: int, Hkv: int, block_k: int) -> int:
    """Tokens per contiguous-cache block: the largest divisor of ``Skv``
    within ``block_k`` whose folded block stays within ``_MAX_ROWS`` rows
    (the VMEM budget of the double-buffered K/V blocks and scores). A
    cache length with no such divisor of at least 8 tokens is refused:
    blocks of a few tokens would run one grid step per token."""
    cap = max(1, min(block_k, _MAX_ROWS // Hkv, Skv))
    bk = next(t for t in range(cap, 0, -1) if Skv % t == 0)
    if bk < min(8, Skv):
        raise ValueError(
            f"flash_decode: cache length {Skv} has no divisor of 8 to {cap} "
            f"tokens to block by; pad the cache to a multiple of 8")
    return bk


def flash_decode(q, cache_k, cache_v, lengths, *, scale: float = 1.0,
                 block_k: int = 512, active=None, interpret: bool = False):
    """q (B, H, D); cache_k/v (B, Skv, Hkv, D); lengths (B,) valid counts.

    Returns (B, H, D). SWA ring buffers pass ``min(lengths, Skv)``: the
    softmax is permutation-invariant over the valid set, so the same
    length mask covers them.

    ``active`` (B,) bool, optional: convenience for callers that carry a
    per-slot mask instead of pre-zeroed lengths. Inactive slots get their
    valid length forced to 0, so every KV block's ``k_lo < length`` guard
    fails and the kernel does NO attention work for them (their output
    rows are meaningless zeros the caller discards). The serving megastep
    achieves the same effect by zeroing freed slots' lengths, so per-slot
    work is always proportional to the live context either way.
    """
    B, H, D = q.shape
    if active is not None:
        lengths = jnp.where(active, lengths, 0)
    Skv, Hkv = cache_k.shape[1], cache_k.shape[2]
    bk = _block_tokens(Skv, Hkv, block_k)
    nk = Skv // bk

    kernel = functools.partial(_decode_kernel, scale=scale, bk=bk, nk=nk)
    const = lambda b, j: (0, 0)                           # noqa: E731
    return pl.pallas_call(
        kernel,
        grid=(B, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),        # lengths (B,)
            pl.BlockSpec((None, H, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((H, 1), const),
            pl.BlockSpec((1, bk * Hkv), const),
            pl.BlockSpec((1, bk * Hkv), const),
            pl.BlockSpec((None, bk, Hkv, D), lambda b, j: (b, j, 0, 0)),
            pl.BlockSpec((None, bk, Hkv, D), lambda b, j: (b, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, H, D), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        scratch_shapes=_decode_scratch(H, D),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, *_row_layout(H, Hkv, bk), cache_k,
      cache_v)


# ------------------------------------------------------------ paged decode --
def _paged_decode_kernel(pt_ref, len_ref, q_ref, qkv_ref, rkv_ref, rtok_ref,
                         k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                         scale: float, page: int, npages: int):
    b, ji = pl.program_id(0), pl.program_id(1)

    @pl.when(ji == 0)
    def _init():
        _init_state(m_scr, l_scr, acc_scr)

    length = len_ref[b]                       # valid kv count for this slot

    @pl.when(ji * page < length)              # dead pages: no work at all
    def _compute():
        _attend_block(q_ref, qkv_ref, rkv_ref, rtok_ref, k_ref, v_ref,
                      m_scr, l_scr, acc_scr, k_lo=ji * page, length=length,
                      scale=scale)

    @pl.when(ji == npages - 1)
    def _done():
        _finish(o_ref, l_scr, acc_scr)


def paged_flash_decode(q, k_pages, v_pages, page_table, lengths, *,
                       scale: float = 1.0, interpret: bool = False):
    """q (B, H, D); k/v_pages (NP+1, page, Hkv, D); page_table (B, n) int32
    (physical page of each slot's j-th logical block — unreserved columns
    must point at a valid index, conventionally the trash page NP);
    lengths (B,) valid counts. Returns (B, H, D).

    The page table and lengths are scalar-prefetch operands: the k/v
    BlockSpec index maps read ``pt[b, j]`` to aim each block's DMA at the
    right physical page. A slot with ``lengths[b] == 0`` (inactive) skips
    every page; its output row is meaningless zeros the caller discards.
    """
    B, H, D = q.shape
    page, Hkv = k_pages.shape[1], k_pages.shape[2]
    npages = page_table.shape[1]

    kernel = functools.partial(_paged_decode_kernel, scale=scale, page=page,
                               npages=npages)
    const = lambda b, j, pt, ln: (0, 0)                   # noqa: E731
    kv_page = lambda b, j, pt, ln: (pt[b, j], 0, 0, 0)    # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, npages),
        in_specs=[
            pl.BlockSpec((None, H, D), lambda b, j, pt, ln: (b, 0, 0)),
            pl.BlockSpec((H, 1), const),
            pl.BlockSpec((1, page * Hkv), const),
            pl.BlockSpec((1, page * Hkv), const),
            pl.BlockSpec((None, page, Hkv, D), kv_page),
            pl.BlockSpec((None, page, Hkv, D), kv_page),
        ],
        out_specs=pl.BlockSpec((None, H, D),
                               lambda b, j, pt, ln: (b, 0, 0)),
        scratch_shapes=_decode_scratch(H, D),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32), q,
      *_row_layout(H, Hkv, page), k_pages, v_pages)


def _paged_mla_kernel(pt_ref, len_ref, ql_ref, qr_ref, ckv_ref, kr_ref,
                      o_ref, m_scr, l_scr, acc_scr, *, scale: float,
                      page: int, npages: int):
    b, ji = pl.program_id(0), pl.program_id(1)

    @pl.when(ji == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    live = ji * page < length

    @pl.when(live)
    def _compute():
        ql = ql_ref[0].astype(jnp.float32)                # (H, R)
        qr = qr_ref[0].astype(jnp.float32)                # (H, Dr)
        ckv = ckv_ref[0].astype(jnp.float32)              # (page, R)
        kr = kr_ref[0].astype(jnp.float32)                # (page, Dr)
        # scores in latent space: absorbed q against compressed latents,
        # plus the shared (per-token, head-broadcast) rope key term
        s = (jax.lax.dot_general(ql, ckv, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr, kr, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
             ) * scale                                    # (H, page)
        k_pos = ji * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < length, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        # value IS the latent: output accumulated in latent space (H, R)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
            p, ckv, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ji == npages - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_mla_decode(q_lat, q_rope, ckv_pages, krope_pages, page_table,
                     lengths, *, scale: float = 1.0,
                     interpret: bool = False):
    """Absorbed-matrix MLA decode over paged compressed latents.

    q_lat (B, H, R) — q_nope already absorbed through w_uk; q_rope
    (B, H, Dr); ckv_pages (NP+1, page, R); krope_pages (NP+1, page, Dr);
    page_table (B, n); lengths (B,) valid counts. Returns out_lat
    (B, H, R) — the caller applies w_uv then wo.
    """
    B, H, R = q_lat.shape
    page = ckv_pages.shape[1]
    Dr = krope_pages.shape[2]
    npages = page_table.shape[1]

    kernel = functools.partial(_paged_mla_kernel, scale=scale, page=page,
                               npages=npages)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, npages),
        in_specs=[
            pl.BlockSpec((1, H, R), lambda b, j, pt, ln: (b, 0, 0)),
            pl.BlockSpec((1, H, Dr), lambda b, j, pt, ln: (b, 0, 0)),
            pl.BlockSpec((1, page, R), lambda b, j, pt, ln: (pt[b, j], 0, 0)),
            pl.BlockSpec((1, page, Dr),
                         lambda b, j, pt, ln: (pt[b, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, R), lambda b, j, pt, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, R), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, R), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      q_lat, q_rope, ckv_pages, krope_pages)
    return out
