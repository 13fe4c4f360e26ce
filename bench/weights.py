"""Random weights from the run's seed, made by the benchmark.

The program serves these weights and the plain reference regenerates
them, leaf by leaf and layer by layer, from the same seed: the reference
takes nothing the program has made. Every value depends only on the seed,
the leaf's name and the layer, so the whole tree (made on the device in
one jitted call, in the type it is served in) and one layer made alone
hold the same numbers.

A layout (``layout(c)`` of the configuration's architecture, e.g.
``dense_layout``) names each leaf of the parameter tree (paths as
``/``-joined dict keys; ``layers/...`` leaves carry a leading layer axis)
with the shape it has per layer and how it is drawn. A tree with a leaf
that is not in the layout, or without one that is, is refused.
"""

from __future__ import annotations

import zlib
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NORM_STD = 0.1      # norm scales are 1 + N(0, NORM_STD)


Layout = Dict[str, Tuple[Tuple[int, ...], int]]


def dense_layout(c: Dict) -> Layout:
    """path -> (per-layer shape, fan_in); fan_in 0 marks a norm scale."""
    d, f = c["hidden_size"], c["intermediate_size"]
    H, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // H
    return {
        "embed/tok": ((c["padded_vocab"], d), d),
        "final_norm/scale": ((d,), 0),
        "layers/ln1/scale": ((d,), 0),
        "layers/ln2/scale": ((d,), 0),
        "layers/attn/wq": ((d, H, hd), d),
        "layers/attn/wk": ((d, Hkv, hd), d),
        "layers/attn/wv": ((d, Hkv, hd), d),
        "layers/attn/wo": ((H, hd, d), H * hd),
        "layers/mlp/gate": ((d, f), d),
        "layers/mlp/up": ((d, f), d),
        "layers/mlp/down": ((f, d), f),
    }


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole number below 2**64."""
    key = jax.random.PRNGKey(0)
    for shift in (0, 16, 32, 48):
        key = jax.random.fold_in(key, (int(seed) >> shift) & 0xFFFF)
    return key


def _leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _draw(key, shape, fan_in: int, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    x = 1.0 + NORM_STD * x if fan_in == 0 else x * (fan_in ** -0.5)
    return x.astype(dtype)


def _path(kp) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)


def make_params(shapes, layout: Layout, layers: int, seed: int,
                dtype) -> Dict:
    """The program's parameter tree (``shapes``, e.g. from
    ``jax.eval_shape(model.init, ...)``) of ``layers`` layers, filled
    from the seed by ``layout`` on the default device in one jitted
    call."""
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [_path(kp) for kp, _ in flat]
    if sorted(paths) != sorted(layout):
        raise ValueError(f"parameter tree {sorted(paths)} does not match "
                         f"the configuration's layout {sorted(layout)}")
    for p, (_, leaf) in zip(paths, flat):
        shape, _ = layout[p]
        want = ((layers,) + shape) if p.startswith("layers/") else shape
        if tuple(leaf.shape) != want:
            raise ValueError(f"{p}: program shape {leaf.shape}, "
                             f"configuration shape {want}")

    def build(key):
        leaves = []
        for p in paths:
            shape, fan_in = layout[p]
            k = _leaf_key(key, p)
            if p.startswith("layers/"):
                leaves.append(jax.vmap(
                    lambda l, k=k, s=shape, fi=fan_in: _draw(
                        jax.random.fold_in(k, l), s, fi, dtype))(
                    jnp.arange(layers, dtype=jnp.uint32)))
            else:
                leaves.append(_draw(k, shape, fan_in, dtype))
        return jax.tree_util.tree_unflatten(tree, leaves)

    return jax.jit(build)(seed_key(seed))


def layer_maker(layout: Layout, seed: int,
                dtype) -> Callable[[int], Dict]:
    """``f(layer) -> {name: float32 array}`` for one layer of ``layout``,
    each value rounded through the served ``dtype`` first; ``f(-1)``
    gives the leaves outside the layers (the embedding table and the
    final norm)."""
    key = seed_key(seed)
    names = [p for p in layout if p.startswith("layers/")]
    top = [p for p in layout if not p.startswith("layers/")]

    @jax.jit
    def one_layer(l):
        return {p.split("/")[-1] if "/mlp/" in p or "/attn/" in p
                else p.split("/")[1]: _draw(
                    jax.random.fold_in(_leaf_key(key, p), l), layout[p][0],
                    layout[p][1], dtype).astype(jnp.float32)
                for p in names}

    @jax.jit
    def outer():
        return {p.split("/")[0]: _draw(_leaf_key(key, p), layout[p][0],
                                       layout[p][1], dtype
                                       ).astype(jnp.float32)
                for p in top}

    def make(layer: int) -> Dict:
        if layer < 0:
            return outer()
        return one_layer(np.uint32(layer))

    return make
