"""Kernels: the roofline time of the decode work over the device time of
the engine's paged megastep executable, ``_paged_megastep_impl``, in the
trace. Work counts only steps that produced tokens: every weight read
once per step, plus each token's KV reads and write and its FLOPs (the
architecture's ``decode_cost``). A task's steps are at least its decode
tokens over the slots, which is the count taken."""

from bench import architectures, counts, tracing

MEGASTEP_MODULE = "_paged_megastep_impl"


def read(r):
    if r.trace is None:
        return None
    dev_s, runs = tracing.module_seconds(r.trace, MEGASTEP_MODULE)
    if runs == 0 or dev_s <= 0:
        return None
    c = r.config
    slots = int(c["serving"]["slots"])
    rows, steps, i = [], 0, 0
    for _, _, n in r.window["tasks"]:
        chunk = r.window["answers"][i:i + n]
        i += n
        tokens = sum(max(0, len(g) - 1) for _, g, _ in chunk)
        steps += -(-tokens // slots)
        rows.extend((len(p), len(g)) for p, g, _ in chunk)
    cost = architectures.of(c).decode_cost(c, rows, steps)
    t, _ = counts.roofline_seconds(cost, r.peaks)
    return 100.0 * t / dev_s
