"""How ``correct`` is decided.

After the window has closed and the program's state is freed, a sample
of the answered claims, drawn from the seed and holding the longest
prompt, is run once through the plain reference of the configuration's
architecture (``bench/architectures/``) with the tokens the program
served. For each served token the gap is the reference's best
logit at that position minus the reference's logit of the served token:
0 where the program chose the reference's own greedy token, small where
it chose a near-tie that rounding can flip, large where the served token
is wrong. The run compares the numbers its cell's limits name
(``bench/limits/<cell>.json``: the mean gap over the served tokens, the
widest gap), beside counts that must be 0.

``control_<name>`` reads the same number for the tokens that the
lower-precision control puts first at the same positions; the limits were
set between the two readings (PERF.md), and the control in the program's
place is judged by the same ``verdict``.
"""

from __future__ import annotations

import json
import os
import random
from typing import Dict, List, Sequence, Tuple

import numpy as np

from bench import architectures, reference

Answer = Tuple[List[int], List[int]]          # (prompt, served tokens)

# reference top-2 margin under which a position counts as a near-tie, for
# the diagnostic near_tie_share (how tie-prone a seed's model is)
NEAR_TIE = 0.05


def sample(answers: Sequence[Answer], n: int, seed: int) -> List[Answer]:
    """``n`` answers drawn from the seed, the one with the longest prompt
    among them."""
    idx = list(range(len(answers)))
    longest = max(idx, key=lambda i: len(answers[i][0]))
    rest = [i for i in idx if i != longest]
    random.Random(int(seed) ^ 0x5EED).shuffle(rest)
    return [answers[i] for i in [longest] + rest[:max(0, n - 1)]]


def served_gaps(ref: np.ndarray, answers: Sequence[Answer]) -> np.ndarray:
    """Per served token: reference best logit minus the reference logit of
    the served token (``ref`` is (N, R, V) from the architecture's
    ``logits_at``)."""
    out = []
    for i, (_, g) in enumerate(answers):
        for j, t in enumerate(g):
            out.append(float(ref[i, j].max() - ref[i, j, t]))
    return np.asarray(out)


def chosen_gaps(ref: np.ndarray, other: np.ndarray,
                answers: Sequence[Answer]) -> np.ndarray:
    """Per position: the reference's gap for the token ``other`` puts
    first."""
    out = []
    for i, (_, g) in enumerate(answers):
        for j in range(len(g)):
            t = int(np.argmax(other[i, j]))
            out.append(float(ref[i, j].max() - ref[i, j, t]))
    return np.asarray(out)


def logit_gap(c: Dict, seed: int, picked: Sequence[Answer],
              control: bool = False) -> Dict[str, float]:
    arch = architectures.of(c)
    tokens, lens, rows = reference.sequences(picked)
    ref = arch.logits_at(c, seed, tokens, lens, rows)
    gaps = served_gaps(ref, picked)
    top2 = np.sort(ref, axis=-1)[..., -2:]
    margins = np.concatenate([top2[i, :len(g), 1] - top2[i, :len(g), 0]
                              for i, (_, g) in enumerate(picked)])
    got = {"logit_gap": float(gaps.max()), "mean_gap": float(gaps.mean()),
           "flip_share": float(np.mean(gaps > 0)),
           "near_tie_share": float(np.mean(margins < NEAR_TIE)),
           "tokens_compared": int(gaps.size)}
    if control:
        low = arch.logits_at(c, seed, tokens, lens, rows,
                             precision="control")
        cg = chosen_gaps(ref, low, picked)
        got.update(control_logit_gap=float(cg.max()),
                   control_mean_gap=float(cg.mean()),
                   control_flip_share=float(np.mean(cg > 0)))
    return got


def load_limits(root: str, workload: str) -> Dict:
    path = os.path.join(root, "bench", "limits", f"{workload}.json")
    with open(path) as f:
        return json.load(f)


def verdict(compared: Dict[str, Dict]) -> bool:
    return all(v["value"] <= v["limit"] for v in compared.values())
