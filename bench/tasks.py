"""Task bodies the benchmark submits through ``PCMClient.submit``.

They run on the PCM worker, against the context the recipe built, and
hold the benchmark's own spans: ``bench.task_body`` around a whole body
and ``bench.generate`` around the engine's work.
"""

from __future__ import annotations

import time
from typing import Dict, List, Sequence

import jax


def _answer(eng, prompts: Sequence[Sequence[int]], max_new: int) -> Dict:
    from repro.serving.request import Request
    with jax.profiler.TraceAnnotation("bench.generate"):
        reqs = [eng.submit(Request(prompt=list(p), max_new_tokens=max_new,
                                   temperature=0.0, stop_tokens=()))
                for p in prompts]
        eng.run_to_completion()
    return {"generated": [list(r.generated) for r in reqs],
            "prefix": [int(r.prefix_tokens) for r in reqs]}


def answer_claims(prompts: List[List[int]], max_new: int) -> Dict:
    """Greedy answers for a batch of claim prompts through the paged
    engine, with the body's start and end on the host clock."""
    from repro.core import load_context
    t0 = time.monotonic()
    with jax.profiler.TraceAnnotation("bench.task_body"):
        out = _answer(load_context("engine"), prompts, max_new)
    out.update(t0=t0, t1=time.monotonic())
    return out


def engine_stats() -> Dict:
    from repro.core import load_context
    return load_context("engine").stats.as_dict()
