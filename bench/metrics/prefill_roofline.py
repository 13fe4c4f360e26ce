"""Kernels: the roofline time of the prefill work over the device time of
the engine's prefill executables (``_paged_prefill_impl``,
``_shared_prefill_impl``) in the trace. Work counts the prompt tokens each
prefill computed (not its bucket's padding, not the prefix the cache
served), their KV, and one read of every weight per prefill dispatch
(``EngineStats.prefill_batches``), by the architecture's
``prefill_cost``."""

from bench import architectures, counts, tracing


def read(r):
    if r.trace is None:
        return None
    dev_s = 0.0
    for name in ("_paged_prefill_impl", "_shared_prefill_impl"):
        dev_s += tracing.module_seconds(r.trace, name)[0]
    calls = r.stats.get("prefill_batches", 0)
    if dev_s <= 0 or calls <= 0:
        return None
    cost = architectures.of(r.config).prefill_cost(
        r.config, [(s, len(p)) for p, _, s in r.window["answers"]], calls)
    t, _ = counts.roofline_seconds(cost, r.peaks)
    return 100.0 * t / dev_s
