"""Claim traffic: FEVER-style claims, the few-shot prompts around them, and
their token ids, all drawn from the run's seed.

A copy of the program's synthetic claim world (``repro.data.fever``) and
of its word-hash tokenizer (``repro.data.tokenizer``), so that the inputs
of a run do not change when the program's data modules do. Claim ``i`` of
seed ``s`` is the same on every machine: it hashes ``"s:i"``.
"""

from __future__ import annotations

import hashlib
import random
from typing import Dict, List

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

# shots and claims come from disjoint index ranges of the seed's stream
SHOT_BASE = 1 << 40


def make_claim(index: int, seed: int) -> Dict[str, str]:
    rng = random.Random(int.from_bytes(
        hashlib.md5(f"{seed}:{index}".encode()).digest()[:8], "little"))
    domain = rng.choice(sorted(_WORLD))
    facts = _WORLD[domain]
    a, b = rng.choice(facts)
    roll = rng.random()
    if roll < 0.4:
        label = "SUPPORTED"
    elif roll < 0.8:
        label = "REFUTED"
        b = rng.choice([x for _, x in facts if x != b])
    else:
        label = "NOT ENOUGH INFO"
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
        self._template = " ".join(
            f"{self._ask(i)} {make_claim(i, self.seed)['label'].lower()} ."
            for i in range(SHOT_BASE, SHOT_BASE + int(traffic["shots"])))

    def _ask(self, index: int) -> str:
        """The prompt for claim ``index``; ``{id}`` in it is the claim's
        row number, as a batch job labels its rows."""
        return self.prompt.format(claim=make_claim(index, self.seed)["text"],
                                  id=index)

    def text(self, index: int) -> str:
        return f"{self._template} {self._ask(index)}"

    def tokens(self, index: int) -> List[int]:
        return encode(self.text(index), self.vocab_size)

    def batch(self, start: int, n: int) -> List[List[int]]:
        return [self.tokens(i) for i in range(start, start + n)]
