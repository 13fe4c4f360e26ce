"""A configuration file (``bench/configs/<name>.json``) and the context the
program serves it from.

The file states the model as it is run: its published sizes under their
``config.json`` names, and under ``serving`` the engine's geometry. The
program's own config for ``program_arch`` is taken for what the file does
not state, and every size the file states is written over it, so the file
is what runs. ``build_context`` is the PCM recipe's builder: the program's
model and paged engine around weights that the benchmark makes from the
seed.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict

import jax
import jax.numpy as jnp


def load_config(path: str) -> Dict:
    with open(path) as f:
        c = json.load(f)
    pad = c.get("vocab_pad_to", 256)
    c["padded_vocab"] = -(-c["vocab_size"] // pad) * pad
    c["file"] = os.path.abspath(path)
    if c.get("architecture") != "dense_decoder":
        raise ValueError(f"{path}: no reference for architecture "
                         f"{c.get('architecture')!r}")
    if c["hidden_size"] % c["num_attention_heads"]:
        raise ValueError(f"{path}: hidden_size is not a multiple of the "
                         f"head count")
    return c


def program_config(c: Dict):
    """The program's ModelConfig for configuration ``c``."""
    from repro.configs import get_config
    dt = c["torch_dtype"]
    cfg = dataclasses.replace(
        get_config(c["program_arch"]),
        d_model=c["hidden_size"], d_ff=c["intermediate_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        vocab_size=c["vocab_size"], vocab_pad_to=c.get("vocab_pad_to", 256),
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        param_dtype=dt, compute_dtype=dt,
        kv_cache_dtype=c["serving"]["kv_cache_dtype"])
    want = {"family": "dense", "activation": "swiglu", "norm": "rmsnorm",
            "qk_norm": False, "use_kernels": False, "sliding_window": 0}
    got = {k: getattr(cfg, k) for k in want}
    if got != want or cfg.attention != "full" or cfg.moe.enabled \
            or cfg.padded_vocab != c["padded_vocab"]:
        raise ValueError(f"{c['program_arch']}: the program's model "
                         f"({got}, attention {cfg.attention!r}, padded vocab "
                         f"{cfg.padded_vocab}) is not the dense decoder that "
                         f"{c['file']} states")
    return cfg


def build_context(config_file: str, seed: int) -> Dict:
    """PCM builder: runs once on the worker, on its device."""
    from repro.models import build_model
    from repro.serving import InferenceEngine
    from bench import weights
    c = load_config(config_file)
    cfg = program_config(c)
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = weights.make_params(shapes, c, seed, jnp.dtype(cfg.param_dtype))
    s = c["serving"]
    engine = InferenceEngine(
        model, params, slots=s["slots"], cache_len=s["cache_len"],
        prefill_buckets=tuple(s["prefill_buckets"]), megastep=s["megastep"],
        paged=True, page_size=s["page_size"], prefix_sharing=True)
    if engine.paged_fallback or engine.prefix_fallback:
        raise RuntimeError(f"engine left the paged prefix-sharing path: "
                           f"{engine.paged_fallback or engine.prefix_fallback}")
    return {"engine": engine, "cfg": cfg}


def footprint(c: Dict) -> Dict[str, int]:
    """Bytes the recipe declares: a cold worker makes the weights, so
    there is nothing to fetch; the host snapshot holds the weights, and
    the device the weights and the KV pool."""
    from bench import counts
    s = c["serving"]
    w = counts.weight_bytes(c)
    kv = s["slots"] * s["cache_len"] * counts.kv_bytes_per_token(c)
    return {"artifact_bytes": 0, "env_bytes": 0, "host_bytes": w,
            "device_bytes": w + kv}
