"""Claim traffic: FEVER-style claims, the few-shot prompts around them, and
their token ids, all drawn from the run's seed.

A copy of the program's synthetic claim world (``repro.data.fever``) and
of its word-hash tokenizer (``repro.data.tokenizer``), so that the inputs
of a run do not change when the program's data modules do. Claim ``i`` of
seed ``s`` is the same on every machine: it hashes ``"s:i"``.

Every seed gets the same sizes: claim ``i``'s domain, and so its number of
words, is fixed by ``i``, and the shots' labels are one fixed set in the
seed's order, so the template has one length. The seed draws the facts,
the labels of the claims and the order of the shots' labels.
"""

from __future__ import annotations

import hashlib
import random
from typing import Dict, List, Optional

LABELS = ("SUPPORTED", "REFUTED", "NOT ENOUGH INFO")
BOS = 2
N_SPECIAL = 8

_WORLD = {
    "capital": [("paris", "france"), ("tokyo", "japan"), ("lima", "peru"),
                ("oslo", "norway"), ("cairo", "egypt"), ("rome", "italy"),
                ("madrid", "spain"), ("ottawa", "canada"),
                ("canberra", "australia"), ("nairobi", "kenya")],
    "author": [("orwell", "1984"), ("austen", "emma"), ("kafka", "trial"),
               ("melville", "mobydick"), ("joyce", "ulysses"),
               ("woolf", "orlando"), ("tolstoy", "war"),
               ("dante", "inferno")],
    "element": [("hydrogen", "1"), ("helium", "2"), ("carbon", "6"),
                ("oxygen", "8"), ("iron", "26"), ("gold", "79"),
                ("neon", "10"), ("silicon", "14")],
}
_TEMPLATES = {
    "capital": "{a} is the capital of {b}",
    "author": "{a} wrote {b}",
    "element": "{a} has atomic number {b}",
}
_UNKNOWN_SUBJECTS = ["zorblax", "quixel", "vantor", "mirelle", "koppen",
                     "drayune", "selvath", "ombrix"]

_DOMAINS = sorted(_WORLD)

# shots and claims come from disjoint index ranges of the seed's stream
SHOT_BASE = 1 << 40
# the shots' labels, 40/40/20 as the claims' are drawn, in the seed's order
SHOT_LABELS = ("SUPPORTED",) * 3 + ("REFUTED",) * 3 + ("NOT ENOUGH INFO",) * 2


def _rng(seed: int, key) -> random.Random:
    return random.Random(int.from_bytes(
        hashlib.md5(f"{seed}:{key}".encode()).digest()[:8], "little"))


def make_claim(index: int, seed: int,
               label: Optional[str] = None) -> Dict[str, str]:
    """Claim ``index`` of ``seed``; with ``label``, one that has it."""
    rng = _rng(seed, index)
    domain = _DOMAINS[index % len(_DOMAINS)]
    facts = _WORLD[domain]
    a, b = rng.choice(facts)
    roll = rng.random()
    if label is None:
        label = ("SUPPORTED" if roll < 0.4 else
                 "REFUTED" if roll < 0.8 else "NOT ENOUGH INFO")
    if label == "REFUTED":
        b = rng.choice([x for _, x in facts if x != b])
    elif label == "NOT ENOUGH INFO":
        a = rng.choice(_UNKNOWN_SUBJECTS)
    return {"text": _TEMPLATES[domain].format(a=a, b=b), "label": label}


def token(word: str, vocab_size: int) -> int:
    h = int.from_bytes(hashlib.md5(word.lower().encode()).digest()[:8],
                       "little")
    return N_SPECIAL + h % (vocab_size - N_SPECIAL)


def encode(text: str, vocab_size: int) -> List[int]:
    return [BOS] + [token(w, vocab_size) for w in text.split()]


class ClaimStream:
    """Prompts for claims 0, 1, 2, ... of one seed under one traffic mix.

    Every prompt starts with the same ``shots`` labelled claims (the
    Prompt-for-Fact shape: one instruction prefix per sweep), then asks
    about its own claim."""

    def __init__(self, traffic: Dict, seed: int, vocab_size: int):
        self.seed = int(seed)
        self.vocab_size = int(vocab_size)
        self.prompt = traffic["prompt"]
        shots = int(traffic["shots"])
        labels = [SHOT_LABELS[j % len(SHOT_LABELS)] for j in range(shots)]
        _rng(self.seed, "shots").shuffle(labels)
        self._template = " ".join(
            f"{self._ask(SHOT_BASE + j, label)} {label.lower()} ."
            for j, label in enumerate(labels))
        # the template's ids once: a prompt's words are the template's and
        # then its claim's, so only the claim is hashed per prompt
        self._template_ids = encode(self._template, self.vocab_size)

    def _ask(self, index: int, label: Optional[str] = None) -> str:
        """The prompt for claim ``index``; ``{id}`` in it is the claim's
        row number, as a batch job labels its rows."""
        claim = make_claim(index, self.seed, label)["text"]
        return self.prompt.format(claim=claim, id=index)

    def text(self, index: int) -> str:
        return f"{self._template} {self._ask(index)}"

    def tokens(self, index: int) -> List[int]:
        """``encode(self.text(index))``, hashing only the claim's words."""
        return self._template_ids + [token(w, self.vocab_size)
                                     for w in self._ask(index).split()]

    def batch(self, start: int, n: int) -> List[List[int]]:
        return [self.tokens(i) for i in range(start, start + n)]
