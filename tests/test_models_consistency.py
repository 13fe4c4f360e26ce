"""Cross-path numerical consistency: prefill+decode == full forward,
ragged batches, SWA ring-buffer wraparound, kernel-vs-jnp paths."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ALL_ARCHS, get_reduced_config
from repro.models import build_model
from repro.models.layers import apply_norm, embed, unembed
from repro.models.transformer import _window, dense_block_decode

TOL = 2e-3


def extras(cfg, B, key=9):
    k = jax.random.PRNGKey(key)
    e = {}
    if cfg.family == "audio":
        e["frames"] = jax.random.normal(k, (B, cfg.encoder_seq_len,
                                            cfg.d_model))
    if cfg.family == "vlm":
        e["patches"] = jax.random.normal(k, (B, cfg.vision_tokens,
                                             cfg.vision_dim))
    return e


@pytest.mark.parametrize("arch", sorted(ALL_ARCHS))
def test_prefill_decode_matches_forward(arch):
    cfg = get_reduced_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    ex = extras(cfg, B)
    full, _ = model.forward(params, dict(tokens=toks, **ex))
    cache = model.init_cache(B, 64, jnp.float32)
    lengths = jnp.array([10, 16], jnp.int32) - 1
    lg, cache = model.prefill(params, toks, lengths, cache, extra=ex or None)
    assert float(jnp.max(jnp.abs(lg[0] - full[0, 8]))) < TOL
    assert float(jnp.max(jnp.abs(lg[1] - full[1, 14]))) < TOL
    nxt = jnp.stack([toks[0, 9], toks[1, 15]])[:, None]
    lg, cache = model.decode_step(params, nxt, lengths, cache)
    assert float(jnp.max(jnp.abs(lg[0] - full[0, 9]))) < TOL
    assert float(jnp.max(jnp.abs(lg[1] - full[1, 15]))) < TOL


def test_swa_ring_buffer_wraparound():
    """Decode far past the window: ring cache must equal a fresh prefill
    over the same (window-truncated) history."""
    cfg = get_reduced_config("h2o-danube-1.8b", sliding_window=8,
                             max_seq_len=128)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 1, 40
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                              cfg.vocab_size)
    # path A: prefill 8, decode 31 steps
    cache = model.init_cache(B, 64, jnp.float32)
    lengths = jnp.array([8], jnp.int32)
    _, cache = model.prefill(params, toks[:, :8], lengths, cache)
    logits = None
    for t in range(8, S - 1):
        logits, cache = model.decode_step(params, toks[:, t:t + 1], lengths,
                                          cache)
        lengths = lengths + 1
    # path B: full forward; SWA makes position S-1 depend on the last
    # `window` tokens only, so logits must agree despite ring wrap
    full, _ = model.forward(params, {"tokens": toks})
    err = float(jnp.max(jnp.abs(logits - full[:, S - 2])))
    assert err < TOL, err


def test_kernel_path_matches_jnp():
    for arch in ("smollm2-1.7b", "zamba2-7b"):
        cfg = get_reduced_config(arch)
        m0 = build_model(cfg)
        m1 = build_model(dataclasses.replace(cfg, use_kernels=True))
        p = m0.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0,
                                  cfg.vocab_size)
        l0, _ = m0.forward(p, {"tokens": toks})
        l1, _ = m1.forward(p, {"tokens": toks})
        assert float(jnp.max(jnp.abs(l0 - l1))) < 5e-3


def test_unrolled_layers_match_scanned():
    from repro.models.sharding import set_layer_unroll
    cfg = get_reduced_config("zamba2-7b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    a, _ = model.forward(params, {"tokens": toks})
    set_layer_unroll(True)
    try:
        b, _ = model.forward(params, {"tokens": toks})
    finally:
        set_layer_unroll(False)
    assert float(jnp.max(jnp.abs(a - b))) < 1e-5


def test_mla_decode_absorbed_matches_prefill_math():
    """Absorbed-latent decode must agree with the blockwise MLA prefill."""
    cfg = get_reduced_config("deepseek-v2-lite-16b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0,
                              cfg.vocab_size)
    full, _ = model.forward(params, {"tokens": toks})
    cache = model.init_cache(B, 32, jnp.float32)
    lengths = jnp.full((B,), S - 1, jnp.int32)
    _, cache = model.prefill(params, toks[:, :S - 1], lengths, cache)
    lg, _ = model.decode_step(params, toks[:, S - 1:], lengths, cache)
    assert float(jnp.max(jnp.abs(lg - full[:, S - 1]))) < TOL


def _slab_decode(cfg, params, tokens, lengths, cache):
    """``decode_step`` with one cache slab per layer: the layer scan takes
    each layer's K/V as ``xs``, ``write_cache_row`` writes the new row into
    that slab, attention reads it, and the slab comes back as ``ys``."""
    window = _window(cfg)
    x = embed(params["embed"], tokens, cfg)
    dense0 = []
    for blk, ckv in zip(params.get("dense0", []), cache.get("dense0", [])):
        x, kv = dense_block_decode(blk, x, cfg, lengths=lengths,
                                   window=window, cache_kv=ckv)
        dense0.append(kv)

    def body(x, xs):
        layer_params, ckv = xs
        return dense_block_decode(layer_params, x, cfg, lengths=lengths,
                                  window=window, cache_kv=ckv)

    x, layers = jax.lax.scan(body, x, (params["layers"], cache["layers"]))
    x = apply_norm(params["final_norm"], x, cfg)
    new_cache = {"layers": layers}
    if dense0:
        new_cache["dense0"] = dense0
    return unembed(params["embed"], x, cfg)[:, 0], new_cache


@pytest.mark.parametrize("kv_update", ["scatter", "mask"])
@pytest.mark.parametrize("arch,overrides,cache_len,lengths", [
    ("smollm2-1.7b", {}, 16, [3, 15, 20]),                  # dense
    ("granite-3-2b", {"n_kv_heads": 2}, 16, [0, 9, 15]),    # GQA
    ("qwen3-moe-235b-a22b", {}, 16, [5, 14, 15]),           # MoE
    ("deepseek-v2-lite-16b", {}, 16, [1, 15, 17]),          # MLA
    ("h2o-danube-1.8b", {"sliding_window": 8}, 64, [2, 13, 23]),  # SWA ring
], ids=["dense", "gqa", "moe", "mla", "swa"])
def test_decode_writes_stacked_cache_like_slabs(arch, overrides, cache_len,
                                                lengths, kv_update):
    """The stacked in-place row write gives logits and caches bit-identical
    to per-layer slabs over two steps, with random cache contents: rows at
    and past ``cache_len - 1`` (clamped), and SWA ring slots that wrap
    (13 % 8) and land on the last slot (23 % 8)."""
    cfg = get_reduced_config(arch, kv_update=kv_update, **overrides)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B = len(lengths)
    cache = model.init_cache(B, cache_len, jnp.float32)
    leaves, tree = jax.tree_util.tree_flatten(cache)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    cache = jax.tree_util.tree_unflatten(tree, [
        jax.random.normal(k, a.shape, a.dtype) for k, a in zip(keys, leaves)])
    tokens = jax.random.randint(jax.random.PRNGKey(5), (B, 1), 0,
                                cfg.vocab_size)
    lengths = jnp.array(lengths, jnp.int32)
    stacked = jax.jit(model.decode_step)
    slabs = jax.jit(lambda p, t, n, c: _slab_decode(cfg, p, t, n, c))
    got = want = cache
    for step in range(2):
        lg_got, got = stacked(params, tokens, lengths + step, got)
        lg_want, want = slabs(params, tokens, lengths + step, want)
        assert jnp.array_equal(lg_got, lg_want), step
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert a.shape == b.shape and jnp.array_equal(a, b), step
