"""HLO text analysis: collective-byte accounting.

Parses compiled (post-GSPMD) HLO and sums the operand bytes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute.
Collectives inside while-loop bodies are counted once by this parse, not
once per trip.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"=\s*(?P<result>\([^)]*\)|[a-z0-9]+\[[0-9,]*\]\S*)\s+"
    r"(?P<kind>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<suffix>-start|-done)?\(")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind bytes (plus 'total').

    Post-optimization HLO prints operands without shapes, so we size each
    collective by its RESULT shape: exact for all-reduce / all-to-all /
    collective-permute, received-bytes for all-gather, and sent-bytes/shards
    for reduce-scatter (conservative)."""
    out: Dict[str, int] = defaultdict(int)
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        if m.group("suffix") == "-done":
            continue  # async pairs: counted at -start
        kind = m.group("kind")
        nbytes = sum(_shape_bytes(d, s)
                     for d, s in _SHAPE_RE.findall(m.group("result")))
        out[kind] += nbytes
        out["total"] += nbytes
    return dict(out)

