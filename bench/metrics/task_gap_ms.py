"""Runtime: mean gap on the worker between one task body's end and the
next body's start, over every pair of successive bodies in the window
(the benchmark's ``bench.task_body`` span, host clock). Time the runtime
spends between batches, when the engine holds no work."""


def read(r):
    spans = sorted(r.window["tasks"])
    gaps = [b[0] - a[1] for a, b in zip(spans, spans[1:])]
    if not gaps:
        return None
    return 1000.0 * sum(gaps) / len(gaps)
