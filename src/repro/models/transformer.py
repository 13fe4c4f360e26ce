"""Decoder-only transformer assembly (dense + MoE families).

Layers are *stacked* (leading layer axis on every param leaf) and driven by
``lax.scan`` so HLO size and compile memory are O(1) in depth — required for
the 94-layer MoE dry-run on a 512-device mesh and the production-correct
choice generally.

The public surface is a ``Model`` record of pure functions:

  init(rng) -> params
  forward_hidden(params, batch) -> (hidden (B,S,d), aux)     # pre-unembed
  forward(params, batch) -> (logits (B,S,V), aux)            # tests / small
  init_cache(batch, cache_len, dtype) -> cache
  prefill(params, tokens, lengths, cache) -> (last_logits (B,V), cache)
  decode_step(params, tokens (B,1), lengths, cache) -> (logits (B,V), cache)

KV caches are stacked over layers. Prefill threads them through the layer
scan as ``xs``/``ys`` (scan stacking re-assembles the written cache); decode
carries the stacked cache through the scan and writes each layer's new row
into it in place, so a decode step moves one row per layer, not the cache.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models.layers import (apply_mlp, apply_norm, cdt, embed,
                                 init_embedding, init_mlp, init_norm,
                                 layer_slice, pdt, stack_params, unembed)
from repro.models.sharding import layer_scan, shard


@dataclass
class Model:
    cfg: Any
    init: Callable
    forward_hidden: Callable
    forward: Callable
    init_cache: Callable
    prefill: Callable
    decode_step: Callable
    # paged decode (block/page-table KV cache, see repro.serving.paged):
    # decode_paged(params, tokens (B,1), lengths, pages, page_table (B,n),
    # active (B,) bool) -> (logits (B,V), pages). The physical page pool is
    # built with init_cache(num_pages + 1, page_size, dtype). None for
    # families whose state does not page (SSM/xLSTM/SWA/audio/vlm) — the
    # engine keeps the contiguous slot path for them.
    decode_paged: Optional[Callable] = None
    # tail-only prefill for page-level prefix sharing:
    # prefill_shared(params, tail_tokens (B,Tb), lengths (B,), starts (B,),
    # view_cache) -> (last_logits (B,V), merged_view_cache). ``view_cache``
    # is the rows' paged KV gathered into a contiguous view (shared prefix
    # already resident); only positions [starts, lengths) are computed.
    # None when tail-only compute could diverge from a full prefill: MLA
    # (latents recompress), MoE (capacity dropping is sequence-dependent),
    # or sliding-window ring buffers (not paged anyway).
    prefill_shared: Optional[Callable] = None


# ---------------------------------------------------------- block pieces ---
def init_dense_block(key, cfg, use_moe: bool, d_ff_override: int = 0) -> dict:
    k1, k2 = jax.random.split(key)
    is_mla = cfg.attention == "mla"
    p = {
        "ln1": init_norm(cfg),
        "attn": attn.init_mla(k1, cfg) if is_mla else attn.init_attention(
            k1, cfg),
        "ln2": init_norm(cfg),
    }
    if use_moe:
        p["moe"] = moe_lib.init_moe(k2, cfg)
    else:
        p["mlp"] = init_mlp(k2, cfg, d_ff=d_ff_override or cfg.d_ff)
    return p


def dense_block_prefill(p, x, cfg, *, positions, kv_len, window,
                        capacity_factor=None):
    """Returns (x, aux, kv) — kv is the narrow (k, v) pair or MLA latents."""
    h = apply_norm(p["ln1"], x, cfg)
    if cfg.attention == "mla":
        a, kv = attn.mla_prefill(p["attn"], h, cfg, positions=positions,
                                 kv_len=kv_len, return_kv=True)
    else:
        a, kv = attn.attend_prefill(p["attn"], h, cfg, positions=positions,
                                    layer_window=window, kv_len=kv_len,
                                    return_kv=True)
    x = x + a
    h = apply_norm(p["ln2"], x, cfg)
    if "moe" in p:
        m, aux = moe_lib.apply_moe(p["moe"], h, cfg,
                                   capacity_factor=capacity_factor)
    else:
        m, aux = apply_mlp(p["mlp"], h, cfg), jnp.float32(0.0)
    return x + m, aux, kv


def dense_block_prefill_shared(p, x, cfg, *, positions, starts, kv_len,
                               view_kv):
    """``dense_block_prefill`` over tail tokens only: attention merges the
    freshly computed tail KV into the row's gathered page view at each
    row's offset. Returns (x, merged narrow kv) — the merged view is the
    layer's new cache content. Non-MoE, non-MLA only (see Model)."""
    h = apply_norm(p["ln1"], x, cfg)
    a, kv = attn.attend_prefill_shared(p["attn"], h, cfg, positions=positions,
                                       starts=starts, kv_len=kv_len,
                                       view_k=view_kv[0], view_v=view_kv[1])
    x = x + a
    h = apply_norm(p["ln2"], x, cfg)
    return x + apply_mlp(p["mlp"], h, cfg), kv


def dense_block_decode(p, x, cfg, *, lengths, window, cache_kv, layer=None):
    """One-token block. With ``layer``, ``cache_kv`` is the stack over the
    scanned layers and this block writes and reads its layer of it."""
    h = apply_norm(p["ln1"], x, cfg)
    if cfg.attention == "mla":
        a, ck, kr = attn.mla_decode(p["attn"], h, cfg, cache_ckv=cache_kv[0],
                                    cache_krope=cache_kv[1], lengths=lengths,
                                    layer=layer)
        new_kv = (ck, kr)
    else:
        a, ck, cv = attn.attend_decode(p["attn"], h, cfg, cache_k=cache_kv[0],
                                       cache_v=cache_kv[1], lengths=lengths,
                                       layer_window=window, layer=layer)
        new_kv = (ck, cv)
    x = x + a
    h = apply_norm(p["ln2"], x, cfg)
    if "moe" in p:
        m, _ = moe_lib.apply_moe(p["moe"], h, cfg, capacity_factor=2.0)
    else:
        m = apply_mlp(p["mlp"], h, cfg)
    return x + m, new_kv


def dense_block_decode_paged(p, x, cfg, *, lengths, page_table, active,
                             pages_kv):
    """``dense_block_decode`` against paged KV storage: same residual
    structure, attention reads/writes through the per-slot page table."""
    h = apply_norm(p["ln1"], x, cfg)
    if cfg.attention == "mla":
        a, ck, kr = attn.paged_mla_decode(
            p["attn"], h, cfg, ckv_pages=pages_kv[0],
            krope_pages=pages_kv[1], page_table=page_table, lengths=lengths,
            active=active)
        new_kv = (ck, kr)
    else:
        a, ck, cv = attn.paged_attend_decode(
            p["attn"], h, cfg, k_pages=pages_kv[0], v_pages=pages_kv[1],
            page_table=page_table, lengths=lengths, active=active)
        new_kv = (ck, cv)
    x = x + a
    h = apply_norm(p["ln2"], x, cfg)
    if "moe" in p:
        m, _ = moe_lib.apply_moe(p["moe"], h, cfg, capacity_factor=2.0)
    else:
        m = apply_mlp(p["mlp"], h, cfg)
    return x + m, new_kv


def _window(cfg) -> int:
    return cfg.sliding_window if cfg.attention == "sliding_window" else 0


def _kv_cache_shapes(cfg, batch: int, cache_len: int, dtype):
    """Per-layer KV cache arrays (no layer axis)."""
    if cfg.attention == "mla":
        m = cfg.mla
        return (jnp.zeros((batch, cache_len, m.kv_lora_rank), dtype),
                jnp.zeros((batch, cache_len, m.qk_rope_head_dim), dtype))
    hd = cfg.resolved_head_dim
    s = min(cache_len, _window(cfg)) if _window(cfg) else cache_len
    return (jnp.zeros((batch, s, cfg.n_kv_heads, hd), dtype),
            jnp.zeros((batch, s, cfg.n_kv_heads, hd), dtype))


def shard_kv_cache(kv, stacked: bool = False):
    """Decode KV caches: batch over data axes, cache-seq over model axis
    (uniform across archs — independent of head-count divisibility).
    ``stacked`` caches carry a leading, unsharded layer axis."""
    lead = (None,) if stacked else ()
    return tuple(shard(c, *lead, "batch", "kv_seq",
                       *[None] * (c.ndim - len(lead) - 2)) for c in kv)


def _write_prefill_kv(cache_kv, new_kv, window: int):
    """Write prefill K/V (narrow heads or MLA latents) into a cache slice."""
    out = []
    for dst, src in zip(cache_kv, new_kv):
        S = src.shape[1]
        if window and S > dst.shape[1]:
            src = src[:, -dst.shape[1]:]      # keep the last `window` tokens
            S = src.shape[1]
        pad = [(0, 0), (0, dst.shape[1] - S)] + [(0, 0)] * (src.ndim - 2)
        upd = jnp.pad(src.astype(dst.dtype), pad)
        mask = (jnp.arange(dst.shape[1]) < S)
        mask = mask.reshape((1, -1) + (1,) * (src.ndim - 2))
        out.append(jnp.where(mask, upd, dst))
    return tuple(out)


# --------------------------------------------------------------- builder ---
def build_decoder(cfg) -> Model:
    """Dense + MoE decoder-only families (stablelm, nemotron, granite,
    danube, smollm2, qwen3-moe, deepseek-v2)."""
    n_scan = cfg.n_layers - cfg.moe.first_dense_layers
    window = _window(cfg)

    def init(rng):
        keys = jax.random.split(rng, cfg.n_layers + 2)
        layers = [init_dense_block(keys[i], cfg, use_moe=cfg.moe.enabled)
                  for i in range(n_scan)]
        p = {"embed": init_embedding(keys[-1], cfg),
             "final_norm": init_norm(cfg),
             "layers": stack_params(layers)}
        if cfg.moe.first_dense_layers:
            p["dense0"] = [init_dense_block(keys[n_scan + 0], cfg,
                                            use_moe=False,
                                            d_ff_override=cfg.moe.dense_d_ff)
                           for _ in range(cfg.moe.first_dense_layers)]
        return p

    def _maybe_remat(fn, train):
        if train and cfg.remat in ("block", "full"):
            policy = (None if cfg.remat == "full"
                      else jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
            return jax.checkpoint(fn, policy=policy)
        return fn

    def forward_hidden(params, batch, train: bool = False,
                       capacity_factor=None):
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = embed(params["embed"], tokens, cfg)
        positions = jnp.arange(S, dtype=jnp.int32)
        kv_len = batch.get("lengths")
        aux0 = jnp.float32(0.0)

        for blk in params.get("dense0", []):
            x, a, _ = dense_block_prefill(blk, x, cfg, positions=positions,
                                          kv_len=kv_len, window=window)
            aux0 = aux0 + a

        def body(carry, layer_params):
            x, aux = carry
            x, a, _ = dense_block_prefill(
                layer_params, x, cfg, positions=positions, kv_len=kv_len,
                window=window, capacity_factor=capacity_factor)
            return (x, aux + a), None

        (x, aux), _ = layer_scan(_maybe_remat(body, train), (x, aux0),
                                 params["layers"])
        x = apply_norm(params["final_norm"], x, cfg)
        return x, aux

    def forward(params, batch, train: bool = False):
        x, aux = forward_hidden(params, batch, train)
        return unembed(params["embed"], x, cfg), aux

    def init_cache(batch: int, cache_len: int, dtype=None):
        dtype = dtype or cdt(cfg)
        per_layer = _kv_cache_shapes(cfg, batch, cache_len, dtype)
        layers_kv = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (n_scan,) + a.shape).copy(),
            per_layer)
        cache = {"layers": layers_kv}
        if cfg.moe.first_dense_layers:
            cache["dense0"] = [_kv_cache_shapes(cfg, batch, cache_len, dtype)
                               for _ in range(cfg.moe.first_dense_layers)]
        return cache

    def prefill(params, tokens, lengths, cache, extra=None):
        B, S = tokens.shape
        x = embed(params["embed"], tokens, cfg)
        positions = jnp.arange(S, dtype=jnp.int32)

        new_dense0 = []
        for blk, ckv in zip(params.get("dense0", []),
                            cache.get("dense0", [])):
            x, _, kv = dense_block_prefill(blk, x, cfg, positions=positions,
                                           kv_len=lengths, window=window)
            new_dense0.append(_write_prefill_kv(ckv, kv, window))

        def body(x, xs):
            layer_params, ckv = xs
            x, _, kv = dense_block_prefill(
                layer_params, x, cfg, positions=positions, kv_len=lengths,
                window=window, capacity_factor=2.0)
            return x, _write_prefill_kv(ckv, kv, window)

        x, layers_kv = layer_scan(body, x, (params["layers"],
                                            cache["layers"]))
        x = apply_norm(params["final_norm"], x, cfg)
        last = jnp.take_along_axis(
            x, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)[:, 0]
        logits = unembed(params["embed"], last[:, None], cfg)[:, 0]
        new_cache = {"layers": layers_kv}
        if new_dense0:
            new_cache["dense0"] = new_dense0
        return logits, new_cache

    def prefill_shared(params, tokens, lengths, starts, view, extra=None):
        """Tail-only prefill: ``tokens`` (B,Tb) holds prompt[starts:] per
        row, ``view`` is the row's paged KV gathered contiguous (prefix
        positions already populated). Logits come from logical position
        ``lengths - 1`` = tail index ``lengths - starts - 1``."""
        B, Tb = tokens.shape
        x = embed(params["embed"], tokens, cfg)
        positions = starts[:, None] + jnp.arange(Tb, dtype=jnp.int32)[None, :]

        new_dense0 = []
        for blk, vkv in zip(params.get("dense0", []),
                            view.get("dense0", [])):
            x, kv = dense_block_prefill_shared(
                blk, x, cfg, positions=positions, starts=starts,
                kv_len=lengths, view_kv=vkv)
            new_dense0.append(kv)

        def body(x, xs):
            layer_params, vkv = xs
            x, kv = dense_block_prefill_shared(
                layer_params, x, cfg, positions=positions, starts=starts,
                kv_len=lengths, view_kv=vkv)
            return x, kv

        x, layers_kv = layer_scan(body, x, (params["layers"],
                                            view["layers"]))
        x = apply_norm(params["final_norm"], x, cfg)
        last = jnp.take_along_axis(
            x, jnp.maximum(lengths - starts - 1, 0)[:, None, None],
            axis=1)[:, 0]
        logits = unembed(params["embed"], last[:, None], cfg)[:, 0]
        new_view = {"layers": layers_kv}
        if new_dense0:
            new_view["dense0"] = new_dense0
        return logits, new_view

    def decode_step(params, tokens, lengths, cache, extra=None):
        B = tokens.shape[0]
        x = embed(params["embed"], tokens, cfg)

        new_dense0 = []
        for blk, ckv in zip(params.get("dense0", []),
                            cache.get("dense0", [])):
            x, kv = dense_block_decode(blk, x, cfg, lengths=lengths,
                                       window=window, cache_kv=ckv)
            new_dense0.append(kv)

        def body(carry, xs):
            x, kv = carry
            layer_params, i = xs
            x, kv = dense_block_decode(
                layer_params, x, cfg, lengths=lengths, window=window,
                cache_kv=shard_kv_cache(kv, stacked=True), layer=i)
            return (x, shard_kv_cache(kv, stacked=True)), None

        (x, layers_kv), _ = layer_scan(
            body, (x, cache["layers"]),
            (params["layers"], jnp.arange(n_scan, dtype=jnp.int32)))
        x = apply_norm(params["final_norm"], x, cfg)
        logits = unembed(params["embed"], x, cfg)[:, 0]
        new_cache = {"layers": layers_kv}
        if new_dense0:
            new_cache["dense0"] = new_dense0
        return logits, new_cache

    def decode_paged(params, tokens, lengths, pages, page_table, active,
                     extra=None):
        """One-token decode against the paged pool. ``pages`` mirrors the
        ``init_cache`` pytree built at (num_pages + 1, page_size); the page
        table is shared by every layer (all layers grow in lockstep)."""
        x = embed(params["embed"], tokens, cfg)

        new_dense0 = []
        for blk, pkv in zip(params.get("dense0", []),
                            pages.get("dense0", [])):
            x, kv = dense_block_decode_paged(blk, x, cfg, lengths=lengths,
                                             page_table=page_table,
                                             active=active, pages_kv=pkv)
            new_dense0.append(kv)

        def body(x, xs):
            layer_params, pkv = xs
            x, new_kv = dense_block_decode_paged(
                layer_params, x, cfg, lengths=lengths,
                page_table=page_table, active=active, pages_kv=pkv)
            return x, new_kv

        x, layers_kv = layer_scan(body, x, (params["layers"],
                                            pages["layers"]))
        x = apply_norm(params["final_norm"], x, cfg)
        logits = unembed(params["embed"], x, cfg)[:, 0]
        new_pages = {"layers": layers_kv}
        if new_dense0:
            new_pages["dense0"] = new_dense0
        return logits, new_pages

    shareable = not (window or cfg.moe.enabled or cfg.attention == "mla")
    return Model(cfg=cfg, init=init, forward_hidden=forward_hidden,
                 forward=forward, init_cache=init_cache, prefill=prefill,
                 decode_step=decode_step,
                 decode_paged=None if window else decode_paged,
                 prefill_shared=prefill_shared if shareable else None)
