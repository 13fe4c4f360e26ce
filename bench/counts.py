"""Operations and bytes of the dense decoder's work, from its shapes.

These count the work that produced something: the tokens a prefill
computed (not the padding of its bucket, not the prefix served from the
cache), and the decode steps that produced tokens. A share computed from
them can only rise when the program drops wasted work, and cannot pass
100% unless the time leaves work out. Bytes are at the served dtype's own
width, without the padding of a KV head to the 128-lane tile.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def layer_params(c: Dict) -> int:
    """Weights of one decoder layer's matmuls."""
    d, f = c["hidden_size"], c["intermediate_size"]
    H, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // H
    return d * H * hd + 2 * d * Hkv * hd + H * hd * d + 3 * d * f


def head_params(c: Dict) -> int:
    """The logits matmul over the padded vocabulary (tied embedding)."""
    return c["padded_vocab"] * c["hidden_size"]


def weight_bytes(c: Dict) -> int:
    """Every parameter the program holds: layers, norms, embedding."""
    d = c["hidden_size"]
    n = (c["num_hidden_layers"] * (layer_params(c) + 2 * d)
         + head_params(c) + d)
    return n * DTYPE_BYTES[c["torch_dtype"]]


def kv_bytes_per_token(c: Dict) -> int:
    hd = c["hidden_size"] // c["num_attention_heads"]
    return (c["num_hidden_layers"] * 2 * c["num_key_value_heads"] * hd
            * DTYPE_BYTES[c["torch_dtype"]])


def attention_flops(c: Dict, keys: int) -> int:
    """Scores and weighted values of one query token over ``keys`` keys,
    in every layer."""
    hd = c["hidden_size"] // c["num_attention_heads"]
    return c["num_hidden_layers"] * 4 * c["num_attention_heads"] * hd * keys


def token_flops(c: Dict, keys: int, logits: bool) -> int:
    """One token through every layer, attending over ``keys`` keys; with
    ``logits`` its logits too."""
    n = 2 * c["num_hidden_layers"] * layer_params(c) + attention_flops(c, keys)
    return n + (2 * head_params(c) if logits else 0)


def prefill_cost(c: Dict, rows: Iterable[Tuple[int, int]],
                 calls: int) -> Dict[str, int]:
    """``rows``: (start, length) of each prompt a prefill computed, tokens
    ``start..length-1`` (``start`` tokens came from the prefix cache);
    ``calls``: prefill dispatches. Each call reads every weight once;
    each row reads its cached prefix's KV and writes its new KV, and
    computes logits at its last position."""
    kvb = kv_bytes_per_token(c)
    flops = 0
    kv = 0
    for start, length in rows:
        n = length - start
        keys = (length * (length + 1) - start * (start + 1)) // 2
        flops += (n * token_flops(c, 0, False) + attention_flops(c, keys)
                  + 2 * head_params(c))
        kv += length * kvb
    return {"flops": flops, "bytes": calls * weight_bytes(c) + kv}


def decode_cost(c: Dict, rows: Iterable[Tuple[int, int]],
                steps: int) -> Dict[str, int]:
    """``rows``: (prompt length, tokens served) of each request; its decode
    steps make tokens 1.. of the answer (token 0 comes from the prefill).
    The step making token ``j`` reads the KV of ``length + j - 1`` earlier
    positions, writes one position's KV and computes logits. ``steps``
    decode steps read every weight once each."""
    kvb = kv_bytes_per_token(c)
    flops = 0
    kv = 0
    for length, served in rows:
        n = served - 1
        keys = n * length + n * (n + 1) // 2       # sum of length + j
        flops += n * token_flops(c, 0, True) + attention_flops(c, keys)
        kv += keys * kvb                           # keys - 1 read, 1 written
    return {"flops": flops, "bytes": steps * weight_bytes(c) + kv}


def roofline_seconds(cost: Dict[str, int], peaks: Dict) -> Tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_flops = cost["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
