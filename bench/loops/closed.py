"""Closed loop of claim batches: the offline Prompt-for-Fact sweep.

Each task carries one wave of ``slots`` claims; ``outstanding`` tasks
are in flight at once, so the runtime always has the next batch
queued and never starves the worker. Submission stops when the window's
seconds are up; the window closes when the last batch's answers are back,
so the rate covers all the work and all the time of the window.
"""

from __future__ import annotations

import collections
import time
from typing import Dict

import jax

# claim ids of the set-up batch; the window's claims start at 0
SETUP_FIRST = 1 << 30


def prepare(run) -> None:
    """One task: it builds the context, whose engine compiles or loads
    every executable, and prefills one wave from an empty prefix cache,
    which caches the shared template's pages (a sweep runs for hours
    behind one template)."""
    _, fut = run.submit(SETUP_FIRST, run.slots)
    fut.result()


def window(run, seconds: float) -> Dict:
    n, k = run.slots, int(run.traffic["outstanding"])
    futs: collections.deque = collections.deque()
    answers, tasks = [], []
    attempted, nxt = 0, 0
    open_ = time.monotonic()
    deadline = open_ + seconds
    while True:
        while len(futs) < k and time.monotonic() < deadline:
            with jax.profiler.TraceAnnotation("bench.submit"):
                futs.append(run.submit(nxt, n))
            nxt += n
            attempted += n
        if not futs:
            break
        prompts, fut = futs.popleft()
        with jax.profiler.TraceAnnotation("bench.await_answers"):
            res = fut.result()
        tasks.append((res["t0"], res["t1"], len(prompts)))
        answers.extend(zip(prompts, res["generated"], res["prefix"]))
    close = time.monotonic()
    return {"open": open_, "close": close, "attempted": attempted,
            "answers": answers, "tasks": tasks}


def end_to_end(run, win: Dict) -> Dict[str, float]:
    done = sum(1 for _, g, _ in win["answers"] if g)
    return {"claims_per_s": done / (win["close"] - win["open"])}
