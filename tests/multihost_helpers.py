"""Module-level builders/tasks for multi-host tests.

Everything a recipe or task carries across the process boundary must be
picklable by reference — lambdas and closures die at the socket. The
worker node process imports this module by name (``spawn_node_process``
extends the child's PYTHONPATH with this directory), so these functions
are the shared vocabulary of every cross-process test.
"""

from __future__ import annotations

from repro.core.context import ContextRecipe

SMALL = {"artifact_bytes": 1 << 20, "env_bytes": 1 << 20,
         "host_bytes": 1 << 20, "device_bytes": 1 << 20}


def build_tiny_engine(slots: int = 2, cache_len: int = 64):
    """Deterministic tiny-engine context: params from a fixed PRNG seed,
    so every process that builds this recipe holds bit-identical weights
    (the greedy-parity assertions depend on it)."""
    import jax
    from repro.configs import get_reduced_config
    from repro.models import build_model
    from repro.serving import InferenceEngine
    cfg = get_reduced_config("smollm2-1.7b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return {"engine": InferenceEngine(model, params, slots=slots,
                                      cache_len=cache_len,
                                      prefill_buckets=(16,))}


def tiny_engine_recipe(name: str = "mh-engine", **kw) -> ContextRecipe:
    return ContextRecipe(name=name, **SMALL).with_builder(
        build_tiny_engine, **kw)


def tiny_prompts(n: int, seed: int = 7, lo: int = 3, hi: int = 12):
    import numpy as np
    from repro.configs import get_reduced_config
    cfg = get_reduced_config("smollm2-1.7b")
    rng = np.random.RandomState(seed)
    return [list(map(int, rng.randint(8, cfg.vocab_size,
                                      size=rng.randint(lo, hi))))
            for _ in range(n)]


def generate_task(prompts, max_new_tokens: int = 6):
    """Greedy-decode ``prompts`` against the installed engine context and
    return (outputs, engine-stat scalars) — the cross-process probe for
    both bit-parity and the compile/cache-hit split."""
    from repro.core.library import current_context
    eng = current_context()["engine"]
    out = eng.generate(prompts, max_new_tokens=max_new_tokens)
    st = eng.stats
    return out, {"compiles": st.compiles,
                 "aot_cache_hits": st.aot_cache_hits,
                 "builder": False}


class MHSplitEngine:
    """Pure-numpy engine duck-type with the split template hooks —
    module-level (picklable) twin of test_transfer_stream's SplitEngine,
    so striped transfers can cross process boundaries without paying a
    JAX build on every node."""

    def __init__(self, n_rows: int = 64, n_cols: int = 1024, seed: int = 0):
        import numpy as np
        rng = np.random.default_rng(seed)
        self.params = {"w": rng.standard_normal((n_rows, n_cols))}
        self.rng_key = np.zeros(2, dtype=np.uint32)
        self.state = {"steps": np.zeros(4, dtype=np.int32)}
        self.exe_cache = {"megastep": "exe"}

    def offload_device_state(self):
        st = {"params": self.params, "_rng": self.rng_key,
              "state": self.state}
        self.params = self.state = self.rng_key = None
        return st

    def restore_device_state(self, host_state):
        self.params = host_state["params"]
        self.rng_key = host_state["_rng"]
        self.state = host_state["state"]

    def export_template(self):
        import numpy as np
        out = dict(self.export_template_host())
        out.update({"params": {k: np.array(v)
                               for k, v in self.params.items()},
                    "_rng": np.array(self.rng_key)})
        return out

    def export_template_device(self):
        return {"params": self.params, "_rng": self.rng_key}

    def export_template_host(self):
        import numpy as np
        return {"state": {"steps": np.zeros(4, dtype=np.int32)}}

    def clone_offloaded(self):
        import copy
        clone = copy.copy(self)
        clone.exe_cache = dict(self.exe_cache)
        clone.params = clone.state = clone.rng_key = None
        return clone

    def checksum(self) -> float:
        return float(self.params["w"].sum())


def split_build(seed: int = 0, rows: int = 64):
    return {"engine": MHSplitEngine(n_rows=rows, seed=seed), "v": 21}


def split_recipe(name: str = "mh-split", seed: int = 0,
                 rows: int = 64) -> ContextRecipe:
    """Footprints sized like test_transfer_stream's live recipes: big
    enough that the planner prices PEER under the FS/BUILD rungs at the
    modest KB-scale rates live calibration measures. ``rows`` scales the
    params leaf (rows x 1024 float64) — crank it up when a test needs a
    LONG stripe it can interrupt mid-flight."""
    return ContextRecipe(
        name=name, artifact_bytes=48 << 20, env_bytes=16 << 20,
        host_bytes=64 << 20, device_bytes=64 << 20,
    ).with_builder(split_build, seed=seed, rows=rows)


def checksum_task():
    from repro.core.library import load_variable_from_context
    return load_variable_from_context("engine").checksum()


def slow_checksum_task(seconds: float = 0.3):
    import time
    time.sleep(seconds)
    return checksum_task()
