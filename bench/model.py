"""A configuration file (``bench/configs/<name>.json``) and the context the
program serves it from.

The file states the model as it is run: its ``architecture``, which names
the module in ``bench/architectures/`` that knows it, its published sizes
under their ``config.json`` names, and under ``serving`` the engine's
geometry. The architecture's ``program_config`` takes the program's own
config for ``program_arch`` for what the file does not state, and writes
every size the file states over it, so the file is what runs.
``build_context`` is the PCM recipe's builder: the program's model and
paged engine around weights that the benchmark makes from the seed.
"""

from __future__ import annotations

import json
import os
from typing import Dict

import jax
import jax.numpy as jnp

from bench import architectures


def load_config(path: str, root: str) -> Dict:
    """The configuration at ``path``, checked by the architecture it
    names among the modules of the checkout at ``root``."""
    with open(path) as f:
        c = json.load(f)
    pad = c.get("vocab_pad_to", 256)
    c["padded_vocab"] = -(-c["vocab_size"] // pad) * pad
    c["file"] = os.path.abspath(path)
    c["root"] = os.path.abspath(root)
    architectures.of(c).check(c)
    return c


def build_context(config_file: str, seed: int, root: str) -> Dict:
    """PCM builder: runs once on the worker, on its device."""
    from repro.models import build_model
    from repro.serving import InferenceEngine
    from bench import weights
    c = load_config(config_file, root)
    arch = architectures.of(c)
    cfg = arch.program_config(c)
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = weights.make_params(shapes, arch.layout(c),
                                 c["num_hidden_layers"], seed,
                                 jnp.dtype(cfg.param_dtype))
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
    arch = architectures.of(c)
    s = c["serving"]
    w = arch.weight_bytes(c)
    kv = s["slots"] * s["cache_len"] * arch.kv_bytes_per_token(c)
    return {"artifact_bytes": 0, "env_bytes": 0, "host_bytes": w,
            "device_bytes": w + kv}
