"""The dense decoder: token embedding; per layer an RMSNorm, grouped-query
attention with rotary positions, a residual add, an RMSNorm, a SwiGLU MLP
and a residual add; a final RMSNorm and logits through the tied
embedding. Its reference is ``bench.reference``, its weight leaves
``bench.weights.dense_layout``, its work counts ``bench.counts``."""

from __future__ import annotations

import dataclasses
from typing import Dict

from bench import counts, reference, weights

layout = weights.dense_layout
logits_at = reference.logits_at
weight_bytes = counts.weight_bytes
kv_bytes_per_token = counts.kv_bytes_per_token
prefill_cost = counts.prefill_cost
decode_cost = counts.decode_cost


def check(c: Dict) -> None:
    if c["hidden_size"] % c["num_attention_heads"]:
        raise ValueError(f"{c['file']}: hidden_size is not a multiple of "
                         f"the head count")


def program_config(c: Dict):
    """The program's ModelConfig for configuration ``c``: the program's
    own config for ``program_arch``, with every size the file states
    written over it."""
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
