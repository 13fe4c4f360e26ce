"""Shared pure-JAX building blocks: norms, linears, embeddings, RoPE, MLPs.

Parameters are plain nested dicts of jnp arrays; every block is an
``init_*(key, ...) -> params`` / ``apply(params, x, ...) -> y`` pair so the
whole model is a pytree transformable by jit/grad/scan/shard_map.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.sharding import shard


def dt(name: str):
    return jnp.dtype(name)


def pdt(cfg):
    return dt(cfg.param_dtype)


def cdt(cfg):
    return dt(cfg.compute_dtype)


def normal_init(key, shape, fan_in: int, dtype) -> jax.Array:
    scale = fan_in ** -0.5
    return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------- norms ----
def init_norm(cfg, d: Optional[int] = None) -> dict:
    d = d or cfg.d_model
    p = {"scale": jnp.ones((d,), dtype=pdt(cfg))}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype=pdt(cfg))
    return p


def apply_norm(p: dict, x: jax.Array, cfg) -> jax.Array:
    # Norms run in f32 for stability regardless of compute dtype.
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm" and "bias" in p:
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + cfg.norm_eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def rms_norm_heads(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """Qwen3-style per-head q/k RMSNorm over the head_dim axis."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)).astype(x.dtype)


# -------------------------------------------------------------- linears ----
def init_linear(key, d_in: int, d_out: int, cfg, bias: bool = False) -> dict:
    p = {"w": normal_init(key, (d_in, d_out), d_in, pdt(cfg))}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype=pdt(cfg))
    return p


def linear(p: dict, x: jax.Array, cfg) -> jax.Array:
    y = jnp.einsum("...i,io->...o", x.astype(cdt(cfg)), p["w"].astype(cdt(cfg)))
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


# ----------------------------------------------------------- embeddings ----
def init_embedding(key, cfg) -> dict:
    k1, k2 = jax.random.split(key)
    p = {"tok": normal_init(k1, (cfg.padded_vocab, cfg.d_model), cfg.d_model,
                            pdt(cfg))}
    if not cfg.tie_embeddings:
        p["unembed"] = normal_init(k2, (cfg.d_model, cfg.padded_vocab),
                                   cfg.d_model, pdt(cfg))
    return p


def embed(p: dict, tokens: jax.Array, cfg) -> jax.Array:
    x = jnp.take(p["tok"].astype(cdt(cfg)), tokens, axis=0)
    return shard(x, "batch", "seq", None)


@jax.named_scope("logits")
def unembed(p: dict, x: jax.Array, cfg) -> jax.Array:
    w = p["tok"].T if "unembed" not in p else p["unembed"]
    logits = jnp.einsum("...d,dv->...v", x.astype(cdt(cfg)), w.astype(cdt(cfg)))
    logits = logits.astype(dt(cfg.logit_dtype))
    return shard(logits, "batch", "seq", "vocab")


# --------------------------------------------------------------- rope ------
def rope_cos_sin(positions: jax.Array, dim: int, theta: float):
    """positions (...,) int -> cos,sin of shape (..., dim//2), f32."""
    half = dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x (..., S, H, D); cos/sin (..., S, D/2) broadcast over heads."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :].astype(x1.dtype)
    s = sin[..., None, :].astype(x1.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


# --------------------------------------------------------------- MLPs ------
def init_mlp(key, cfg, d_ff: Optional[int] = None, activation: Optional[str] = None,
             d_model: Optional[int] = None) -> dict:
    d_ff = d_ff or cfg.d_ff
    act = activation or cfg.activation
    d = d_model or cfg.d_model
    keys = jax.random.split(key, 3)
    p = {"up": normal_init(keys[0], (d, d_ff), d, pdt(cfg)),
         "down": normal_init(keys[1], (d_ff, d), d_ff, pdt(cfg))}
    if act == "swiglu":
        p["gate"] = normal_init(keys[2], (d, d_ff), d, pdt(cfg))
    return p


@jax.named_scope("mlp")
def apply_mlp(p: dict, x: jax.Array, cfg, activation: Optional[str] = None,
              sharded: bool = True) -> jax.Array:
    act = activation or cfg.activation
    xc = x.astype(cdt(cfg))
    up = jnp.einsum("...d,df->...f", xc, p["up"].astype(cdt(cfg)))
    if act == "swiglu":
        gate = jnp.einsum("...d,df->...f", xc, p["gate"].astype(cdt(cfg)))
        h = jax.nn.silu(gate) * up
    elif act == "squared_relu":
        r = jax.nn.relu(up)
        h = r * r
    else:  # gelu
        h = jax.nn.gelu(up)
    if sharded and h.ndim == 3:
        h = shard(h, "batch", "seq", "d_ff")
    y = jnp.einsum("...f,fd->...d", h, p["down"].astype(cdt(cfg)))
    if sharded and y.ndim == 3:
        y = shard(y, "batch", "seq", None)
    return y


# ------------------------------------------------------------ stacking -----
def stack_params(param_list):
    """[pytree, pytree, ...] -> pytree with a leading layer axis (for scan)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=0), *param_list)


def layer_slice(stacked, i: int):
    return jax.tree_util.tree_map(lambda x: x[i], stacked)
