"""Model step: the model FLOPs of the tokens computed in the traced
window, over the window and the chip's bf16 peak. Computed tokens are the
prompt tokens not served from the prefix cache and the decode tokens
produced; each costs two FLOPs per weight of the layers (and of the
logits, where they are computed) plus attention over its context (the
architecture's ``prefill_cost`` and ``decode_cost``)."""

from bench import architectures


def read(r):
    if r.trace is None or not r.window["answers"]:
        return None
    c = r.config
    arch = architectures.of(c)
    pre = arch.prefill_cost(
        c, [(s, len(p)) for p, _, s in r.window["answers"]], 0)
    dec = arch.decode_cost(
        c, [(len(p), len(g)) for p, g, _ in r.window["answers"]], 0)
    flops = pre["flops"] + dec["flops"]
    return 100.0 * flops / (r.trace["window_s"]
                            * r.peaks["bf16_flops_per_s"])
