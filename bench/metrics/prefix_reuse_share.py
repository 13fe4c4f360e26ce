"""Engine: share of the window's prompt tokens whose KV came from the
prefix cache, from the engine's own counters (``EngineStats``)."""


def read(r):
    reused = r.stats.get("prefix_tokens_reused", 0)
    total = reused + r.stats.get("prefill_tokens", 0)
    if total <= 0:
        return None
    return 100.0 * reused / total
