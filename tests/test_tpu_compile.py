"""The main-path Pallas kernels compile for a TPU v5e at real widths.

No chip is needed: the TPU compiler compiles against a described ``v5e:2x2``
topology, which refuses what interpret mode accepts (block shapes that do
not tile, kernels that exceed the scoped VMEM). Every kernel compile must
keep the kernel, so each asserts a ``tpu_custom_call`` in the compiled module;
the shared-prefill merge, plain XLA, is held to per-row writes instead, and
the decode loop to in-place row writes into the cache it carries.

The topology is described inside a fixture: only one process at a time may
load the TPU library, so nothing here touches it at import or collection.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.models import build_model
from repro.kernels.decode_attention import (flash_decode, paged_flash_decode,
                                            paged_mla_decode)
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.models.attention import _merge_rows

SLOTS, PAGE, NUM_PAGES, MAX_PAGES = 16, 64, 256, 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _gqa_widths():
    cfg = get_config("smollm2-1.7b")
    return (cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim,
            jnp.dtype(cfg.compute_dtype))


def test_paged_flash_decode_compiles(one_chip):
    H, Hkv, D, dt = _gqa_widths()
    pool = (NUM_PAGES + 1, PAGE, Hkv, D)
    _compile(lambda q, k, v, pt, n: paged_flash_decode(q, k, v, pt, n,
                                                       scale=D ** -0.5),
             one_chip, ((SLOTS, H, D), dt), (pool, dt), (pool, dt),
             ((SLOTS, MAX_PAGES), jnp.int32), ((SLOTS,), jnp.int32))


@pytest.mark.parametrize("cache_len", [512, 1024, 600])
def test_flash_decode_compiles(one_chip, cache_len):
    H, Hkv, D, dt = _gqa_widths()
    cache = (SLOTS, cache_len, Hkv, D)
    _compile(lambda q, k, v, n: flash_decode(q, k, v, n, scale=D ** -0.5),
             one_chip, ((SLOTS, H, D), dt), (cache, dt), (cache, dt),
             ((SLOTS,), jnp.int32))


def test_flash_attention_prefill_compiles(one_chip):
    H, _, D, dt = _gqa_widths()
    x = (4 * H, 512, D)                 # (batch * heads, bucket, head_dim)
    _compile(lambda q, k, v: flash_attention_bhsd(q, k, v, causal=True,
                                                  scale=D ** -0.5),
             one_chip, (x, dt), (x, dt), (x, dt))


def test_paged_mla_decode_compiles(one_chip):
    cfg = get_config("deepseek-v2-lite-16b")
    H, R, Dr = cfg.n_heads, cfg.mla.kv_lora_rank, cfg.mla.qk_rope_head_dim
    dt = jnp.dtype(cfg.compute_dtype)
    _compile(lambda ql, qr, c, kr, pt, n: paged_mla_decode(
                 ql, qr, c, kr, pt, n, scale=(R + Dr) ** -0.5),
             one_chip, ((SLOTS, H, R), dt), ((SLOTS, H, Dr), dt),
             ((NUM_PAGES + 1, PAGE, R), dt), ((NUM_PAGES + 1, PAGE, Dr), dt),
             ((SLOTS, MAX_PAGES), jnp.int32), ((SLOTS,), jnp.int32))


def test_merge_rows_writes_rows_without_gather(one_chip):
    """The shared-prefill merge at the smollm2-1.7b cell's shapes (32 slots,
    a 256-position view, a 32-token tail bucket) compiles to per-row slice
    writes. They access about 6x the bytes of view, tail and output (the
    padded copy, the row loop, the slice back); an element-wise gather,
    which reads one index per view element, accesses about 1100x."""
    _, Hkv, D, dt = _gqa_widths()
    view, tail = (32, 256, Hkv, D), (32, 32, Hkv, D)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in ((view, dt), (tail, dt), ((32,), jnp.int32))]
    compiled = jax.jit(_merge_rows).lower(*args).compile()
    assert not re.search(r"\bgather\(", compiled.as_text())
    cost = compiled.cost_analysis()
    moved = (2 * math.prod(view) + math.prod(tail)) * jnp.dtype(dt).itemsize
    assert cost["bytes accessed"] < 8 * moved


def _while_bodies(hlo: str) -> str:
    """The text of every computation a ``while`` body runs, fusions and
    nested loops included."""
    comps = dict(re.findall(r"^(?:ENTRY )?%(\S+) .*?\{\n(.*?)^\}", hlo,
                            re.M | re.S))
    todo = re.findall(r"\bwhile\(.*?\bbody=%([\w.\-]+)", hlo)
    seen = set()
    while todo:
        name = todo.pop()
        if name not in seen:
            seen.add(name)
            todo += re.findall(r"(?:calls|body|condition|to_apply)=%([\w.\-]+)",
                               comps[name])
    return "\n".join(comps[n] for n in seen)


def test_decode_loop_writes_rows_into_the_carried_cache(one_chip):
    """Three decode steps of smollm2-1.7b over the cell's cache (32 slots,
    256 positions), in a ``while_loop`` as the megastep runs them. Each
    layer writes its new K/V rows into the carried stack in place: no loop
    body copies the stack, rewrites it whole, or cuts out a layer's slab.
    The device keeps the carry (its 64-wide head dim padded to the 128-lane
    tile) and little else: a per-step copy of the carry would double the
    temporary bytes, and re-stacking the cache through the layer scan took
    three times them."""
    cfg = get_config("smollm2-1.7b")
    model = build_model(cfg)
    dt = jnp.dtype(cfg.compute_dtype)
    B, S, L = 32, 256, cfg.n_layers
    Hkv, D = cfg.n_kv_heads, cfg.resolved_head_dim

    def loop(params, tokens, lengths):
        cache = model.init_cache(B, S, dt)

        def body(c):
            step, tokens, lengths, cache = c
            logits, cache = model.decode_step(params, tokens, lengths, cache)
            tokens = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            return step + 1, tokens, lengths + 1, cache
        return jax.lax.while_loop(lambda c: c[0] < 3, body,
                                  (jnp.int32(0), tokens, lengths, cache))

    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    compiled = jax.jit(loop).lower(
        params, jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)).compile()
    bodies = _while_bodies(compiled.as_text())
    stack, slab = f"bf16[{L},{B},{S},{Hkv},{D}]", f"bf16[{B},{S},{Hkv},{D}]"
    for line in bodies.splitlines():
        out = re.match(r"\s*(?:ROOT )?%\S+ = (\S+?)\{.*?\} ([\w\-]+)\(",
                       line)
        if out is None:
            continue
        shape, op = out.groups()
        assert not (shape == stack and op in ("copy", "dynamic-update-slice")
                    ), line
        assert not (shape == slab and op != "parameter"), line
    carry = 2 * L * B * S * Hkv * (-(-D // 128) * 128) * dt.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * carry
