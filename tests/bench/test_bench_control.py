"""The comparison that decides ``correct``: the lower-precision control
fails it, and so does a run whose timed path is broken underneath. Tiny
float32 sizes on the CPU, where the control is bfloat16; the chip readings
at the cells' own sizes are in PERF.md."""

import json
import time

import pytest

from conftest import make_root


def test_control_reads_above_the_limit_and_the_program_below(
        tmp_path, on_cpu, monkeypatch):
    """At a tiny bfloat16 size, as the chip's cells are served: the
    program's gaps on three seeds against the float8 control's, and the
    control in the program's place judged not correct by the harness's
    own verdict on every seed."""
    import conftest
    from bench import calibrate
    for c in (conftest.TINY, conftest.TINY_GQA):
        monkeypatch.setitem(c, "torch_dtype", "bfloat16")
        monkeypatch.setitem(c, "serving", dict(c["serving"],
                                               kv_cache_dtype="bfloat16"))
    root = make_root(tmp_path, limit=0.05)
    lines = []
    s = calibrate.calibrate(root, "tiny.factcheck", [1, 2, 3], 3, 0.3,
                            on_cpu.measure, emit=lines.append)
    assert len(lines) == 4
    assert all(json.loads(x)["correct"] for x in lines[:3])
    assert s["control_correct"] == [False, False, False], s
    g = s["logit_gap"]
    assert g["lower"] < 0.05 < g["upper"], s
    assert g["upper"] >= 3 * g["lower"], s
    m = s["mean_gap"]
    assert m["upper"] >= 3 * m["lower"], s


def _altered_sample(real):
    def sample(logits, *a, **kw):
        toks = real(logits, *a, **kw)
        return (toks + 1) % 509
    return sample


def _half_left_out(real):
    def submit(self, req):
        self._bench_n = getattr(self, "_bench_n", 0) + 1
        return req if self._bench_n % 2 else real(self, req)
    return submit


def _unchanged(real):
    def scatter(pages, *a, **kw):
        return pages
    return scatter


FAULTS = {
    "token_altered": ("repro.serving.engine.sample", _altered_sample),
    "half_left_out": ("repro.serving.engine.InferenceEngine.submit",
                      _half_left_out),
    "kv_write_dropped": ("repro.serving.paged.scatter_view", _unchanged),
}


@pytest.mark.parametrize("fault,workload", [
    ("token_altered", "tiny.factcheck"),
    ("half_left_out", "tiny.factcheck"),
    ("kv_write_dropped", "tiny-gqa.factcheck"),
])
def test_a_broken_timed_path_is_not_correct(tmp_path, on_cpu, monkeypatch,
                                            fault, workload):
    import importlib
    target, make = FAULTS[fault]
    mod_name, _, attr = target.rpartition(".")
    try:
        owner = importlib.import_module(mod_name)
    except ModuleNotFoundError:
        mod_name, _, cls = mod_name.rpartition(".")
        owner = getattr(importlib.import_module(mod_name), cls)
    monkeypatch.setattr(owner, attr, make(getattr(owner, attr)))
    root = make_root(tmp_path, limit=1e-3)
    res = on_cpu.measure(root, workload, 11, 0.3, False, time.monotonic())
    assert res["correct"] is False
    failed = [k for k, v in res["compared"].items() if v["value"] > v["limit"]]
    assert failed, res["compared"]
