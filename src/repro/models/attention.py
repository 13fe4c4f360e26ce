"""Attention: GQA/MHA/SWA self-attention, cross-attention, and DeepSeek MLA.

Prefill uses a blockwise online-softmax path (lax.scan over KV chunks) so the
S x S score matrix is never materialized — mandatory for the 32k prefill
cells to fit HBM, and the XLA-native analogue of the Pallas flash kernel in
``repro.kernels.flash_attention`` (used when ``cfg.use_kernels``).

Decode computes one new token against a cache:
  * full attention: cache length = seq_len
  * sliding window:  ring buffer of ``cfg.sliding_window`` slots
  * MLA:             compressed latent cache (kv_lora_rank + rope_dim)
                     with the absorbed-matrix decode trick (no k/v
                     decompression on the hot path).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import (apply_rope, cdt, init_linear, normal_init,
                                 pdt, rms_norm_heads, rope_cos_sin)
from repro.models.sharding import shard

NEG_INF = -1e30

_FULL_CHUNK = False


def set_full_chunk(on: bool) -> None:
    """Dry-run analysis mode: single-chunk blockwise attention so HLO cost
    analysis sees the full S x T work (chunk loops are while-loops that
    HloCostAnalysis counts once). FLOP-neutral vs production chunking."""
    global _FULL_CHUNK
    _FULL_CHUNK = on


# ------------------------------------------------------------------ init ---
def init_attention(key, cfg, cross: bool = False) -> dict:
    """Standard (non-MLA) attention parameters."""
    hd = cfg.resolved_head_dim
    keys = jax.random.split(key, 6)
    d = cfg.d_model
    n_kv = cfg.n_heads if cross and cfg.family == "audio" else cfg.n_kv_heads
    kv_in = cfg.vision_dim if (cross and cfg.vision_dim) else d
    p = {
        "wq": normal_init(keys[0], (d, cfg.n_heads, hd), d, pdt(cfg)),
        "wk": normal_init(keys[1], (kv_in, n_kv, hd), kv_in, pdt(cfg)),
        "wv": normal_init(keys[2], (kv_in, n_kv, hd), kv_in, pdt(cfg)),
        "wo": normal_init(keys[3], (cfg.n_heads, hd, d), cfg.n_heads * hd,
                          pdt(cfg)),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype=pdt(cfg))
        p["k_norm"] = jnp.ones((hd,), dtype=pdt(cfg))
    return p


def init_mla(key, cfg) -> dict:
    m = cfg.mla
    d = cfg.d_model
    keys = jax.random.split(key, 6)
    q_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    p = {
        "wq": normal_init(keys[0], (d, cfg.n_heads, q_dim), d, pdt(cfg)),
        # joint down-projection: [latent | shared rope key]
        "w_dkv": normal_init(keys[1], (d, m.kv_lora_rank + m.qk_rope_head_dim),
                             d, pdt(cfg)),
        "w_uk": normal_init(keys[2], (m.kv_lora_rank, cfg.n_heads,
                                      m.qk_nope_head_dim), m.kv_lora_rank,
                            pdt(cfg)),
        "w_uv": normal_init(keys[3], (m.kv_lora_rank, cfg.n_heads,
                                      m.v_head_dim), m.kv_lora_rank, pdt(cfg)),
        "wo": normal_init(keys[4], (cfg.n_heads, m.v_head_dim, d),
                          cfg.n_heads * m.v_head_dim, pdt(cfg)),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dtype=pdt(cfg)),
    }
    if m.q_lora_rank:
        p["w_dq"] = normal_init(keys[5], (d, m.q_lora_rank), d, pdt(cfg))
        p["w_uq"] = normal_init(keys[5], (m.q_lora_rank, cfg.n_heads, q_dim),
                                m.q_lora_rank, pdt(cfg))
        del p["wq"]
    return p


# ------------------------------------------------------- qkv projections ---
def _project_qkv(p, x, cfg, positions, memory=None, rope: bool = True):
    """Returns q (B,S,H,D) and k,v (B,T,Hkv,D); rope applied for self-attn."""
    c = cdt(cfg)
    q = jnp.einsum("bsd,dhk->bshk", x.astype(c), p["wq"].astype(c))
    src = x if memory is None else memory
    k = jnp.einsum("btd,dhk->bthk", src.astype(c), p["wk"].astype(c))
    v = jnp.einsum("btd,dhk->bthk", src.astype(c), p["wv"].astype(c))
    if cfg.qk_norm and "q_norm" in p:
        q = rms_norm_heads(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm_heads(k, p["k_norm"], cfg.norm_eps)
    if rope and memory is None:
        cos, sin = rope_cos_sin(positions, q.shape[-1], cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def _repeat_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(B,T,Hkv,D) -> (B,T,H,D). Under GSPMD this is a local gather of a
    replicated tensor into a head-sharded one (no collective)."""
    hkv = k.shape[2]
    if hkv == n_heads:
        return k
    return jnp.repeat(k, n_heads // hkv, axis=2)


def _layer_of(cache: jax.Array, layer: Optional[jax.Array]) -> jax.Array:
    """Layer ``layer`` of a stacked cache; the cache itself without one."""
    if layer is None:
        return cache
    return jax.lax.dynamic_index_in_dim(cache, layer, keepdims=False)


def write_cache_row(cache: jax.Array, new_row: jax.Array, slot: jax.Array,
                    mode: str, layer: Optional[jax.Array] = None
                    ) -> jax.Array:
    """Write one token per sequence into a (B, S, ...) cache at ``slot``.

    With ``layer``, ``cache`` is stacked over layers, (L, B, S, ...), and
    the rows land at ``[layer, b, slot_b]`` of it in place: nothing of the
    other layers is read or written.

    mode="scatter": indexed .at[].set — one-row write, but on a TP mesh with
    a seq-sharded cache GSPMD resolves the scatter through an involuntary
    full rematerialization (replicate + repartition the whole per-layer
    cache: ~GBs of collective per layer per token; see EXPERIMENTS.md §Perf).

    mode="mask": one-hot select — elementwise, shard-local under any
    (batch, kv_seq) sharding; the broadcast of the tiny new row is the only
    cross-shard traffic. XLA fuses the select into the cache's donated
    buffer, so HBM traffic stays O(cache) read + masked write.
    """
    if layer is not None:
        if mode == "mask":
            slab = write_cache_row(_layer_of(cache, layer), new_row, slot,
                                   mode)
            return jax.lax.dynamic_update_index_in_dim(cache, slab, layer, 0)
        B = cache.shape[1]
        return cache.at[layer, jnp.arange(B), slot].set(
            new_row.astype(cache.dtype))
    B = cache.shape[0]
    if mode == "mask":
        S = cache.shape[1]
        onehot = jnp.arange(S, dtype=jnp.int32)[None, :] == slot[:, None]
        mask = onehot.reshape((B, S) + (1,) * (cache.ndim - 2))
        return jnp.where(mask, new_row[:, None].astype(cache.dtype), cache)
    return cache.at[jnp.arange(B), slot].set(new_row.astype(cache.dtype))


# ------------------------------------------------- blockwise prefill core --
def blockwise_attention(q, k, v, *, scale: float, causal: bool,
                        window: int = 0, q_offset=0,
                        kv_len: Optional[jax.Array] = None,
                        chunk: int = 1024) -> jax.Array:
    """Online-softmax attention; never materializes (S, T) for the full T.

    q (B,S,H,D); k,v (B,T,H,D) — same head count (callers repeat GQA KV).
    ``q_offset`` shifts query positions (chunked prefill continuation); a
    (B,) array gives every row its own offset (shared-prefix tail prefill).
    ``kv_len`` (B,) masks out padding keys.
    """
    B, S, H, D = q.shape
    T = k.shape[1]
    if _FULL_CHUNK:
        chunk = T
    chunk = min(chunk, T)
    if T % chunk:
        pad = chunk - T % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_len = jnp.full((B,), T, jnp.int32) if kv_len is None else kv_len
        T = T + pad
    nc = T // chunk
    kc = k.reshape(B, nc, chunk, H, D).swapaxes(0, 1)  # (nc,B,C,H,D)
    vc = v.reshape(B, nc, chunk, H, D).swapaxes(0, 1)

    per_row = isinstance(q_offset, jax.Array) and q_offset.ndim == 1
    if per_row:
        q_pos = q_offset[:, None] + jnp.arange(S, dtype=jnp.int32)  # (B,S)
    else:
        q_pos = jnp.arange(S, dtype=jnp.int32) + q_offset           # (S,)
    qf = q.astype(jnp.float32) * scale

    def step(carry, inp):
        acc, m, l = carry
        ci, k_i, v_i = inp
        s = jnp.einsum("bshd,bchd->bshc", qf, k_i.astype(jnp.float32))
        k_pos = ci * chunk + jnp.arange(chunk, dtype=jnp.int32)  # (C,)
        if per_row:
            mask = jnp.ones((B, S, chunk), dtype=bool)
            if causal:
                mask &= q_pos[:, :, None] >= k_pos[None, None, :]
            if window:
                mask &= (q_pos[:, :, None] - k_pos[None, None, :]) < window
            s = jnp.where(mask[:, :, None, :], s, NEG_INF)
        else:
            mask = jnp.ones((S, chunk), dtype=bool)
            if causal:
                mask &= q_pos[:, None] >= k_pos[None, :]
            if window:
                mask &= (q_pos[:, None] - k_pos[None, :]) < window
            s = jnp.where(mask[None, :, None, :], s, NEG_INF)
        if kv_len is not None:
            valid = k_pos[None, :] < kv_len[:, None]             # (B,C)
            s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bshc,bchd->bshd", p, v_i.astype(jnp.float32))
        return (acc, m_new, l), None

    init = (jnp.zeros((B, S, H, D), jnp.float32),
            jnp.full((B, S, H), NEG_INF, jnp.float32),
            jnp.zeros((B, S, H), jnp.float32))
    # checkpoint the chunk body: backward recomputes per-chunk probs instead
    # of saving every (B,S,H,chunk) score tensor (flash-style memory)
    (acc, m, l), _ = jax.lax.scan(
        jax.checkpoint(step), init,
        (jnp.arange(nc, dtype=jnp.int32), kc, vc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


# -------------------------------------------------------------- prefill ----
@jax.named_scope("attention")
def attend_prefill(p, x, cfg, *, positions, layer_window: int = 0,
                   memory=None, causal: bool = True,
                   kv_len: Optional[jax.Array] = None,
                   return_kv: bool = False):
    """Full-sequence attention. Returns (out, (k, v) narrow-head or None)."""
    q, k, v = _project_qkv(p, x, cfg, positions, memory=memory)
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", None, None)
    v = shard(v, "batch", "seq", None, None)
    scale = 1.0 / math.sqrt(q.shape[-1])
    kf = _repeat_kv(k, cfg.n_heads)
    vf = _repeat_kv(v, cfg.n_heads)
    if cfg.use_kernels and memory is None and kv_len is None:
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, kf, vf, causal=causal,
                                   window=layer_window, scale=scale)
    else:
        out = blockwise_attention(q, kf, vf, scale=scale,
                                  causal=causal and memory is None,
                                  window=layer_window, kv_len=kv_len)
    out = shard(out, "batch", "seq", "heads", None)
    y = jnp.einsum("bshk,hkd->bsd", out.astype(cdt(cfg)),
                   p["wo"].astype(cdt(cfg)))
    y = shard(y, "batch", "seq", None)
    return (y, (k, v)) if return_kv else (y, None)


@jax.named_scope("merge_rows")
def _merge_rows(view: jax.Array, tail: jax.Array,
                starts: jax.Array) -> jax.Array:
    """Overlay freshly computed tail rows onto a gathered cache view.

    view (B, L, ...) holds per-row cache content (shared prefix pages plus
    whatever the row's private pages currently contain); tail (B, Tb, ...)
    holds new values for logical positions [start, start + Tb). Row b of
    the result equals view outside that span and tail inside it — prefix
    positions pass through untouched (bitwise), which is what keeps the
    shared-prefill path exact. starts (B,) lie in [0, L]; a span that runs
    past L is cut there."""
    L, Tb = view.shape[1], tail.shape[1]
    # one contiguous write per row into a view padded by Tb positions, so a
    # tail that runs past L is cut at L rather than shifted back by the
    # start clamp of dynamic_update_slice
    pad = [(0, 0), (0, Tb)] + [(0, 0)] * (view.ndim - 2)
    padded = jnp.pad(view, pad)

    def write(row, t, s):
        return jax.lax.dynamic_update_slice_in_dim(row, t, s, axis=0)

    merged = jax.vmap(write)(padded, tail.astype(view.dtype), starts)
    return merged[:, :L]


@jax.named_scope("attention")
def attend_prefill_shared(p, x, cfg, *, positions, starts, kv_len,
                          view_k, view_v):
    """Tail-only prefill attention for page-level prefix sharing.

    x (B,Tb,d) embeds ONLY the unshared tail tokens of each row;
    ``positions`` (B,Tb) are their absolute positions (starts[b] + i);
    view_k/view_v (B,L,Hkv,D) are the rows' cache views gathered through
    the page table, already holding the shared prefix KV. Computes q/k/v
    for the tail, merges tail KV into the view at each row's offset, and
    runs causal attention with per-row query offsets over the merged KV —
    masked garbage beyond ``kv_len`` contributes exact zeros, so outputs
    are bit-identical to a full-prompt prefill of the same row.

    Returns (y (B,Tb,d), merged narrow (k, v)) — the merged KV is what the
    caller scatters back into the row's pages (columns >= the shared-page
    count only; shared pages are never written)."""
    q, k, v = _project_qkv(p, x, cfg, positions)
    q = shard(q, "batch", "seq", "heads", None)
    mk = _merge_rows(view_k, k, starts)
    mv = _merge_rows(view_v, v, starts)
    mk = shard(mk, "batch", "seq", None, None)
    mv = shard(mv, "batch", "seq", None, None)
    scale = 1.0 / math.sqrt(q.shape[-1])
    out = blockwise_attention(q, _repeat_kv(mk, cfg.n_heads),
                              _repeat_kv(mv, cfg.n_heads), scale=scale,
                              causal=True, q_offset=starts, kv_len=kv_len)
    out = shard(out, "batch", "seq", "heads", None)
    y = jnp.einsum("bshk,hkd->bsd", out.astype(cdt(cfg)),
                   p["wo"].astype(cdt(cfg)))
    y = shard(y, "batch", "seq", None)
    return y, (mk, mv)


# --------------------------------------------------------------- decode ----
@jax.named_scope("attention")
def attend_decode(p, x, cfg, *, cache_k, cache_v, lengths,
                  layer_window: int = 0, memory_kv=None, layer=None):
    """One-token decode. x (B,1,d); cache_k/v (B,Scache,Hkv,D); lengths (B,).

    Returns (y (B,1,d), new_cache_k, new_cache_v). SWA caches are ring
    buffers (Scache == window); full caches write at ``lengths``. With
    ``layer``, cache_k/v are stacked over layers (L,B,Scache,Hkv,D): the
    new row is written into layer ``layer`` in place, attention reads that
    layer, and the whole stack is returned.
    """
    c = cdt(cfg)
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x.astype(c), p["wq"].astype(c))
    k_new = jnp.einsum("bsd,dhk->bshk", x.astype(c), p["wk"].astype(c))
    v_new = jnp.einsum("bsd,dhk->bshk", x.astype(c), p["wv"].astype(c))
    if cfg.qk_norm and "q_norm" in p:
        q = rms_norm_heads(q, p["q_norm"], cfg.norm_eps)
        k_new = rms_norm_heads(k_new, p["k_norm"], cfg.norm_eps)
    cos, sin = rope_cos_sin(lengths[:, None], q.shape[-1], cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k_new = apply_rope(k_new, cos, sin)

    s_cache = cache_k.shape[-3]
    slot = lengths % s_cache if layer_window else jnp.minimum(
        lengths, s_cache - 1)
    stack_k = write_cache_row(cache_k, k_new[:, 0], slot, cfg.kv_update,
                              layer)
    stack_v = write_cache_row(cache_v, v_new[:, 0], slot, cfg.kv_update,
                              layer)
    cache_k, cache_v = _layer_of(stack_k, layer), _layer_of(stack_v, layer)

    pos = jnp.arange(s_cache, dtype=jnp.int32)
    n_valid = jnp.minimum(lengths + 1, s_cache)
    if layer_window:
        valid = pos[None, :] < n_valid[:, None]       # ring: all slots once full
    else:
        valid = pos[None, :] <= lengths[:, None]
    scale = 1.0 / math.sqrt(hd)
    if cfg.use_kernels and getattr(cfg, "gqa_decode", "grouped") != "repeat":
        # length-masked Pallas flash-decode: per-slot work is proportional
        # to that slot's valid KV length, so the engine megastep's free
        # slots (length 0/1) skip essentially every KV block. The softmax
        # is permutation-invariant over the valid KV set, so the same call
        # covers SWA ring buffers (n_valid caps at the window). The kernel
        # takes every cache length (its block divides the cache).
        from repro.kernels import ops as kops
        out = kops.flash_decode(q[:, 0], cache_k, cache_v, n_valid,
                                scale=scale)[:, None]
    elif getattr(cfg, "gqa_decode", "grouped") == "repeat":
        # baseline path: repeat cache to full heads (GSPMD all-gathers the
        # sharded cache across the model axis — kept for §Perf A/B)
        kf = _repeat_kv(cache_k, cfg.n_heads)
        vf = _repeat_kv(cache_v, cfg.n_heads)
        s = jnp.einsum("bshk,bthk->bhst", q.astype(jnp.float32) * scale,
                       kf.astype(jnp.float32))
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhst,bthk->bshk", w, vf.astype(jnp.float32))
    else:
        out = grouped_attention_narrow(q * scale, cache_k, cache_v,
                                       valid)[:, :1]
    y = jnp.einsum("bshk,hkd->bsd", out.astype(c), p["wo"].astype(c))
    return y, stack_k, stack_v


def _paged_write_row(pages: jax.Array, new_row: jax.Array,
                     page_table: jax.Array, lengths: jax.Array,
                     active: jax.Array) -> jax.Array:
    """Write one token per slot into a paged cache at logical position
    ``lengths``. pages (NP+1, P, ...); page_table (B, n); new_row (B, ...).

    Inactive slots write into the TRASH page (index NP) — their stale page
    table may point at pages now owned by another slot, so they must never
    write through it. The clamp mirrors ``write_cache_row``'s
    ``min(lengths, cache-1)`` so an at-capacity slot overwrites its last
    position instead of escaping its reservation."""
    B, n = page_table.shape
    P = pages.shape[1]
    trash = pages.shape[0] - 1
    wpos = jnp.minimum(lengths, n * P - 1)
    rows = jnp.arange(B)
    dest = jnp.where(active, page_table[rows, wpos // P], trash)
    return pages.at[dest, wpos % P].set(new_row.astype(pages.dtype))


def _paged_gather(pages: jax.Array, page_table: jax.Array) -> jax.Array:
    """(NP+1, P, ...) + (B, n) -> contiguous view (B, n*P, ...)."""
    B, n = page_table.shape
    P = pages.shape[1]
    return pages[page_table.reshape(-1)].reshape((B, n * P) +
                                                 pages.shape[2:])


@jax.named_scope("attention")
def paged_attend_decode(p, x, cfg, *, k_pages, v_pages, page_table, lengths,
                        active):
    """One-token GQA decode against a paged KV cache.

    x (B,1,d); k/v_pages (NP+1, P, Hkv, D); page_table (B, n) int32;
    lengths (B,); active (B,) bool (inactive slots do no cache writes and
    their outputs are garbage the caller discards).

    With ``cfg.use_kernels`` attention runs in the Pallas paged kernel
    (gather-by-page-table, per-slot work proportional to live pages);
    otherwise the pages are gathered into a contiguous view and scored by
    the same ``grouped_attention_narrow`` math as the slot cache — greedy
    outputs stay bit-identical to the contiguous path.
    """
    c = cdt(cfg)
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x.astype(c), p["wq"].astype(c))
    k_new = jnp.einsum("bsd,dhk->bshk", x.astype(c), p["wk"].astype(c))
    v_new = jnp.einsum("bsd,dhk->bshk", x.astype(c), p["wv"].astype(c))
    if cfg.qk_norm and "q_norm" in p:
        q = rms_norm_heads(q, p["q_norm"], cfg.norm_eps)
        k_new = rms_norm_heads(k_new, p["k_norm"], cfg.norm_eps)
    cos, sin = rope_cos_sin(lengths[:, None], q.shape[-1], cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k_new = apply_rope(k_new, cos, sin)

    k_pages = _paged_write_row(k_pages, k_new[:, 0], page_table, lengths,
                               active)
    v_pages = _paged_write_row(v_pages, v_new[:, 0], page_table, lengths,
                               active)
    scale = 1.0 / math.sqrt(hd)
    cap = page_table.shape[1] * k_pages.shape[1]
    if cfg.use_kernels:
        from repro.kernels import ops as kops
        n_valid = jnp.where(active, jnp.minimum(lengths + 1, cap), 0)
        out = kops.paged_flash_decode(q[:, 0], k_pages, v_pages, page_table,
                                      n_valid, scale=scale)[:, None]
    else:
        kv = _paged_gather(k_pages, page_table)
        vv = _paged_gather(v_pages, page_table)
        pos = jnp.arange(cap, dtype=jnp.int32)
        valid = pos[None, :] <= lengths[:, None]
        out = grouped_attention_narrow(q * scale, kv, vv, valid)[:, :1]
    y = jnp.einsum("bshk,hkd->bsd", out.astype(c), p["wo"].astype(c))
    return y, k_pages, v_pages


@jax.named_scope("attention")
def paged_mla_decode(p, x, cfg, *, ckv_pages, krope_pages, page_table,
                     lengths, active):
    """Absorbed-matrix MLA decode against paged compressed latents.

    ckv_pages (NP+1, P, r); krope_pages (NP+1, P, dr); the per-session
    resident footprint is the latent pages — never decompressed k/v — so
    DeepSeek-style models keep their compressed footprint end-to-end."""
    m = cfg.mla
    c = cdt(cfg)
    q = _mla_q(p, x, cfg)
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    cos, sin = rope_cos_sin(lengths[:, None], m.qk_rope_head_dim,
                            cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)

    ckv_new, krope_new = _mla_latent(p, x, cfg)
    krope_new = apply_rope(krope_new[:, :, None, :], cos, sin)[:, :, 0, :]
    ckv_pages = _paged_write_row(ckv_pages, ckv_new[:, 0], page_table,
                                 lengths, active)
    krope_pages = _paged_write_row(krope_pages, krope_new[:, 0], page_table,
                                   lengths, active)

    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope.astype(c),
                       p["w_uk"].astype(c))
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    cap = page_table.shape[1] * ckv_pages.shape[1]
    if cfg.use_kernels:
        from repro.kernels import ops as kops
        n_valid = jnp.where(active, jnp.minimum(lengths + 1, cap), 0)
        out_lat = kops.paged_mla_decode(
            q_lat[:, 0], q_rope[:, 0], ckv_pages, krope_pages, page_table,
            n_valid, scale=scale)[:, None].astype(jnp.float32)
    else:
        ckv = _paged_gather(ckv_pages, page_table)
        kr = _paged_gather(krope_pages, page_table)
        s = jnp.einsum("bshr,btr->bhst", q_lat.astype(jnp.float32) * scale,
                       ckv.astype(jnp.float32))
        s += jnp.einsum("bshd,btd->bhst", q_rope.astype(jnp.float32) * scale,
                        kr.astype(jnp.float32))
        pos = jnp.arange(cap, dtype=jnp.int32)
        valid = pos[None, :] <= lengths[:, None]
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        out_lat = jnp.einsum("bhst,btr->bshr", w, ckv.astype(jnp.float32))
    out = jnp.einsum("bshr,rhd->bshd", out_lat.astype(c), p["w_uv"].astype(c))
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(c))
    return y, ckv_pages, krope_pages


def grouped_attention_narrow(q, cache_k, cache_v, valid):
    """GQA scoring on NARROW KV — no head-repeat of the cache.

    q (B,S,H,D) pre-scaled; cache_k/v (B,T,Hkv,D); valid (B,T) bool.
    Returns (B,S,H,D). No causal structure (callers mask via ``valid``).

    Repeating a (batch, kv_seq)-sharded cache to full heads makes GSPMD
    all-gather the whole per-layer cache across the model axis every token
    (measured: ~0.5 GB/layer on granite decode_32k — EXPERIMENTS.md §Perf).
    The grouped einsum keeps the cache's contraction partner narrow: scores
    and the attn*V contraction stay seq-sharded, and only O(B*H) softmax
    stats and outputs cross shards.
    """
    B, S, H, D = q.shape
    hkv = cache_k.shape[2]
    G = H // hkv
    qg = q.reshape(B, S, hkv, G, D)
    s = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32),
                   cache_k.astype(jnp.float32))       # (B,Hkv,G,S,T)
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", w, cache_v.astype(jnp.float32))
    return out.reshape(B, S, H, D)


def project_memory_kv(p, memory, cfg):
    """Compute cross-attention K/V once from an encoder/vision memory."""
    c = cdt(cfg)
    k = jnp.einsum("btd,dhk->bthk", memory.astype(c), p["wk"].astype(c))
    v = jnp.einsum("btd,dhk->bthk", memory.astype(c), p["wv"].astype(c))
    return k, v


def attend_cached_memory(p, x, cfg, mem_k, mem_v,
                         mem_len: Optional[jax.Array] = None):
    """Cross-attention against precomputed memory K/V (no rope, no cache
    update). x (B,S,d); mem_k/v (B,T,Hkv,D). Used by whisper decode and
    VLM image layers."""
    c = cdt(cfg)
    q = jnp.einsum("bsd,dhk->bshk", x.astype(c), p["wq"].astype(c))
    if cfg.qk_norm and "q_norm" in p:
        q = rms_norm_heads(q, p["q_norm"], cfg.norm_eps)
    scale = 1.0 / math.sqrt(q.shape[-1])
    if x.shape[1] > 256:
        kf = _repeat_kv(mem_k, cfg.n_heads)   # fresh activations: repeat is
        vf = _repeat_kv(mem_v, cfg.n_heads)   # a local slice, no collective
        out = blockwise_attention(q, kf, vf, scale=scale, causal=False,
                                  kv_len=mem_len)
        y = jnp.einsum("bshk,hkd->bsd", out.astype(c), p["wo"].astype(c))
        return y
    # decode path: grouped-query scoring on the narrow cached memory KV
    # (repeating a sharded cache would all-gather it — see
    # grouped_attention_narrow)
    B, S, H, D = q.shape
    if mem_len is not None:
        pos = jnp.arange(mem_k.shape[1], dtype=jnp.int32)
        valid = pos[None, :] < mem_len[:, None]
    else:
        valid = jnp.ones((B, mem_k.shape[1]), bool)
    out = grouped_attention_narrow(q * scale, mem_k, mem_v, valid)
    y = jnp.einsum("bshk,hkd->bsd", out.astype(c), p["wo"].astype(c))
    return y


# -------------------------------------------------------------- MLA --------
def _mla_q(p, x, cfg):
    c = cdt(cfg)
    if "w_dq" in p:
        ql = jnp.einsum("bsd,dr->bsr", x.astype(c), p["w_dq"].astype(c))
        return jnp.einsum("bsr,rhk->bshk", ql, p["w_uq"].astype(c))
    return jnp.einsum("bsd,dhk->bshk", x.astype(c), p["wq"].astype(c))


def _mla_latent(p, x, cfg):
    """Down-project to (latent c_kv (B,S,r), shared rope key (B,S,dr))."""
    m = cfg.mla
    c = cdt(cfg)
    dkv = jnp.einsum("bsd,dr->bsr", x.astype(c), p["w_dkv"].astype(c))
    ckv, k_rope = dkv[..., :m.kv_lora_rank], dkv[..., m.kv_lora_rank:]
    # latent is RMS-normed (DeepSeek), rope key gets positional rotation
    ckv = rms_norm_heads(ckv, p["kv_norm"], cfg.norm_eps)
    return ckv, k_rope


@jax.named_scope("attention")
def mla_prefill(p, x, cfg, *, positions, kv_len=None, return_kv: bool = False,
                chunk: int = 1024):
    """Blockwise MLA prefill with per-chunk KV decompression (FlashMLA-style)."""
    m = cfg.mla
    c = cdt(cfg)
    B, S, _ = x.shape
    q = _mla_q(p, x, cfg)                                   # (B,S,H,dn+dr)
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    cos, sin = rope_cos_sin(positions, m.qk_rope_head_dim, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    ckv, k_rope = _mla_latent(p, x, cfg)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0, :]  # (B,S,dr)

    # decompress per KV chunk inside the online-softmax scan
    T = S
    if _FULL_CHUNK:
        chunk = T
    chunk = min(chunk, T)
    assert T % chunk == 0, "MLA prefill expects chunk-divisible seq"
    nc = T // chunk
    ckv_c = ckv.reshape(B, nc, chunk, -1).swapaxes(0, 1)
    kr_c = k_rope.reshape(B, nc, chunk, -1).swapaxes(0, 1)
    q_pos = jnp.arange(S, dtype=jnp.int32)
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    qn = q_nope.astype(jnp.float32) * scale
    qr = q_rope.astype(jnp.float32) * scale
    H = cfg.n_heads

    def step(carry, inp):
        acc, mx, l = carry
        ci, ckv_i, kr_i = inp
        k_i = jnp.einsum("bcr,rhk->bchk", ckv_i.astype(c), p["w_uk"].astype(c))
        v_i = jnp.einsum("bcr,rhk->bchk", ckv_i.astype(c), p["w_uv"].astype(c))
        s = jnp.einsum("bshd,bchd->bshc", qn, k_i.astype(jnp.float32))
        s += jnp.einsum("bshd,bcd->bshc", qr, kr_i.astype(jnp.float32))
        k_pos = ci * chunk + jnp.arange(chunk, dtype=jnp.int32)
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask[None, :, None, :], s, NEG_INF)
        if kv_len is not None:
            valid = k_pos[None, :] < kv_len[:, None]
            s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(mx, jnp.max(s, axis=-1))
        pr = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(mx - m_new)
        l = l * corr + jnp.sum(pr, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bshc,bchd->bshd", pr, v_i.astype(jnp.float32))
        return (acc, m_new, l), None

    init = (jnp.zeros((B, S, H, m.v_head_dim), jnp.float32),
            jnp.full((B, S, H), NEG_INF, jnp.float32),
            jnp.zeros((B, S, H), jnp.float32))
    (acc, _, l), _ = jax.lax.scan(
        step, init, (jnp.arange(nc, dtype=jnp.int32), ckv_c, kr_c))
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(c)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(c))
    y = shard(y, "batch", "seq", None)
    return (y, (ckv, k_rope)) if return_kv else (y, None)


@jax.named_scope("attention")
def mla_decode(p, x, cfg, *, cache_ckv, cache_krope, lengths, layer=None):
    """Absorbed-matrix MLA decode: attention runs in the latent space.

    cache_ckv (B,Sc,r); cache_krope (B,Sc,dr); x (B,1,d). With ``layer``,
    the caches are stacked over layers, as in ``attend_decode``.
    """
    m = cfg.mla
    c = cdt(cfg)
    B = x.shape[0]
    q = _mla_q(p, x, cfg)
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    cos, sin = rope_cos_sin(lengths[:, None], m.qk_rope_head_dim,
                            cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)

    ckv_new, krope_new = _mla_latent(p, x, cfg)
    krope_new = apply_rope(krope_new[:, :, None, :], cos, sin)[:, :, 0, :]
    slot = jnp.minimum(lengths, cache_ckv.shape[-2] - 1)
    stack_ckv = write_cache_row(cache_ckv, ckv_new[:, 0], slot,
                                cfg.kv_update, layer)
    stack_krope = write_cache_row(cache_krope, krope_new[:, 0], slot,
                                  cfg.kv_update, layer)
    cache_ckv = _layer_of(stack_ckv, layer)
    cache_krope = _layer_of(stack_krope, layer)

    # absorb W_uk into q: q_lat (B,1,H,r)
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope.astype(c), p["w_uk"].astype(c))
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    s = jnp.einsum("bshr,btr->bhst", q_lat.astype(jnp.float32) * scale,
                   cache_ckv.astype(jnp.float32))
    s += jnp.einsum("bshd,btd->bhst", q_rope.astype(jnp.float32) * scale,
                    cache_krope.astype(jnp.float32))
    pos = jnp.arange(cache_ckv.shape[1], dtype=jnp.int32)
    valid = pos[None, :] <= lengths[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out_lat = jnp.einsum("bhst,btr->bshr", w, cache_ckv.astype(jnp.float32))
    out = jnp.einsum("bshr,rhd->bshd", out_lat.astype(c), p["w_uv"].astype(c))
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(c))
    return y, stack_ckv, stack_krope
