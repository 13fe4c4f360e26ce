"""Plain float32 reference of the dense decoder, and its lower-precision
control.

It imports nothing of the program and takes nothing the program made: the
weights come from ``bench.weights`` and the seed. The equations are those
the configuration states for a Llama-style decoder: token embedding;
per layer an RMSNorm, grouped-query attention with rotary positions
(half-split rotation, base ``rope_theta``, scores scaled by
``head_dim ** -0.5``, causal), a residual add, an RMSNorm, a SwiGLU MLP
and a residual add; a final RMSNorm and logits through the tied
embedding. It runs layer by layer, in blocks of rows, at the highest
matmul precision, so it fits beside nothing else on the chip.

The control (``precision="control"``) is the same computation with every
linear layer's weights and inputs rounded to the next precision below the
configuration's: float8 e4m3 (scaled per output channel and per token)
for a bfloat16 configuration, bfloat16 for a float32 one.
"""

from __future__ import annotations

from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as bench_weights

ROW_BLOCK = 16
E4M3_MAX = 448.0


def lower_precision(served_dtype: str):
    """Round-trip ``x`` through the precision below ``served_dtype``,
    scaled over ``axes`` (the contracted axes of the matmul it feeds)."""
    if served_dtype == "bfloat16":
        def q(x, axes):
            amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
            scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
            return ((x / scale).astype(jnp.float8_e4m3fn)
                    .astype(jnp.float32) * scale)
        return q
    if served_dtype == "float32":
        return lambda x, axes: x.astype(jnp.bfloat16).astype(jnp.float32)
    raise ValueError(f"no control precision below {served_dtype}")


def _exact(x, axes):
    return x


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[..., None] * freqs          # (B,S,half)
    c, s = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _layer(x, lengths, w, c, q):
    B, S, d = x.shape
    H, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // H
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    h = q(_rms(x, w["ln1"], eps), (-1,))
    qh = jnp.einsum("bsd,dhk->bshk", h, q(w["wq"], (0,)))
    kh = jnp.einsum("bsd,dhk->bshk", h, q(w["wk"], (0,)))
    vh = jnp.einsum("bsd,dhk->bshk", h, q(w["wv"], (0,)))
    qh, kh = _rope(qh, pos, theta), _rope(kh, pos, theta)
    group = H // Hkv
    kh = jnp.repeat(kh, group, axis=2)      # query head i reads kv head i // group
    vh = jnp.repeat(vh, group, axis=2)
    s = jnp.einsum("bshk,bthk->bhst", qh, kh) * (hd ** -0.5)
    t = jnp.arange(S)
    ok = (t[None, :] <= t[:, None])[None] & (t[None, None, :]
                                             < lengths[:, None, None])
    s = jnp.where(ok[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhst,bthk->bshk", p, vh)
    x = x + jnp.einsum("bshk,hkd->bsd", q(o, (-2, -1)), q(w["wo"], (0, 1)))
    h = q(_rms(x, w["ln2"], eps), (-1,))
    g = jnp.einsum("bsd,df->bsf", h, q(w["gate"], (0,)))
    u = jnp.einsum("bsd,df->bsf", h, q(w["up"], (0,)))
    m = q(jax.nn.silu(g) * u, (-1,))
    return x + jnp.einsum("bsf,fd->bsd", m, q(w["down"], (0,)))


def _head(x, rows, out, c, q):
    """Logits at the gathered positions ``rows`` (B,R) of x (B,S,d), over
    the configuration's vocabulary."""
    h = jnp.take_along_axis(x, rows[:, :, None], axis=1)
    h = q(_rms(h, out["final_norm"], c["rms_norm_eps"]), (-1,))
    emb = q(out["embed"], (1,))
    return jnp.einsum("brd,vd->brv", h, emb)[..., :c["vocab_size"]]


def logits_at(c: Dict, seed: int, tokens: np.ndarray, lengths: np.ndarray,
              rows: np.ndarray, precision: str = "reference") -> np.ndarray:
    """Logits (N, R, vocab_size) at positions ``rows`` (N, R) of the
    sequences ``tokens`` (N, S), each valid up to ``lengths``.
    ``precision`` is "reference" (float32) or "control"."""
    q = (_exact if precision == "reference"
         else lower_precision(c["torch_dtype"]))
    make = bench_weights.layer_maker(bench_weights.dense_layout(c), seed,
                                     jnp.dtype(c["torch_dtype"]))
    N = tokens.shape[0]
    pad = -N % ROW_BLOCK
    tok = np.pad(tokens, ((0, pad), (0, 0)))
    lens = np.pad(lengths, (0, pad), constant_values=1)
    rws = np.pad(rows, ((0, pad), (0, 0)))
    with jax.default_matmul_precision("highest"):
        out = make(-1)
        embed = jax.jit(lambda e, t: jnp.take(e, t, axis=0))
        layer = jax.jit(lambda x, l, w: _layer(x, l, w, c, q))
        head = jax.jit(lambda x, r, o: _head(x, r, o, c, q))
        xs = [embed(out["embed"], jnp.asarray(tok[i:i + ROW_BLOCK]))
              for i in range(0, len(tok), ROW_BLOCK)]
        ls = [jnp.asarray(lens[i:i + ROW_BLOCK])
              for i in range(0, len(tok), ROW_BLOCK)]
        for li in range(c["num_hidden_layers"]):
            w = make(li)
            xs = [layer(x, l, w) for x, l in zip(xs, ls)]
            del w
        got = [np.asarray(head(x, jnp.asarray(rws[i * ROW_BLOCK:
                                                  (i + 1) * ROW_BLOCK]), out))
               for i, x in enumerate(xs)]
    return np.concatenate(got)[:N]


def sequences(answers: Sequence) -> tuple:
    """Pack served answers ``(prompt, served tokens)`` for ``logits_at``:
    each sequence is the prompt and all served tokens but the last, and
    ``rows`` are the positions whose next-token logits chose each served
    token."""
    lens = [len(p) + len(g) - 1 for p, g in answers]
    S = -(-max(lens) // 32) * 32
    R = max(len(g) for _, g in answers)
    tokens = np.zeros((len(answers), S), np.int32)
    rows = np.zeros((len(answers), R), np.int32)
    for i, (p, g) in enumerate(answers):
        seq = list(p) + list(g[:-1])
        tokens[i, :len(seq)] = seq
        rows[i, :len(g)] = np.arange(len(p) - 1, len(p) - 1 + len(g))
        rows[i, len(g):] = len(p) - 1
    return tokens, np.asarray(lens, np.int32), rows
