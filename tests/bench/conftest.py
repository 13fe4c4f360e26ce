"""Fixtures of the benchmark's CPU tests: a checkout-like root whose
BENCHMARK.json names tiny float32 configurations, with the device check
steered to the CPU inside the test."""

import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TINY = {
    "source": "tiny float32 shape of the dense decoder, for CPU tests",
    "architecture": "dense_decoder",
    "program_arch": "smollm2-1.7b",
    "hidden_act": "silu",
    "hidden_size": 64,
    "intermediate_size": 128,
    "num_hidden_layers": 2,
    "num_attention_heads": 4,
    "num_key_value_heads": 4,
    "rms_norm_eps": 1e-05,
    "rope_theta": 130000,
    "tie_word_embeddings": True,
    "vocab_size": 512,
    "vocab_pad_to": 256,
    "torch_dtype": "float32",
    "serving": {"slots": 4, "cache_len": 256, "megastep": 8,
                "prefill_buckets": [32, 256], "page_size": 64,
                "kv_cache_dtype": "float32"},
}
TINY_GQA = dict(TINY, program_arch="granite-3-2b", num_key_value_heads=2,
                vocab_size=515, rope_theta=10000.0,
                num_hidden_layers=3)
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
             "hbm_bytes": 16e9}
CELLS = [("tiny.factcheck", "tiny", "factcheck"),
         ("tiny-gqa.factcheck", "tiny-gqa", "factcheck")]


def make_root(tmp, limit: float = 1e-3) -> str:
    """A root holding a copy of bench/ and a BENCHMARK.json over tiny
    configurations, with the real spec's metrics."""
    root = str(tmp)
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for name, c in (("tiny", TINY), ("tiny-gqa", TINY_GQA)):
        with open(os.path.join(root, "bench", "configs", f"{name}.json"),
                  "w") as f:
            json.dump(c, f)
    spec["configs"] = [{"name": n, "source": "tests", "reduced": [],
                        "file": f"bench/configs/{n}.json", "why": "tests"}
                       for n in ("tiny", "tiny-gqa")]
    spec["workloads"] = [{"name": w, "config": c, "traffic": t, "chips": 1,
                          "why": "tests"} for w, c, t in CELLS]
    # the tiny GQA cell rehearses the chip cell's traffic on a GQA shape
    rename = {"smollm2-1.7b.factcheck": ["tiny.factcheck",
                                         "tiny-gqa.factcheck"]}
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [t for w in m["workloads"] for t in rename[w]]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    for w, _, _ in CELLS:
        with open(os.path.join(root, "bench", "limits", f"{w}.json"),
                  "w") as f:
            json.dump({"logit_gap": {"limit": limit}}, f)
    return root


@pytest.fixture
def on_cpu(monkeypatch):
    """The harness's look for a chip, steered to the CPU."""
    from bench import harness
    monkeypatch.setattr(harness, "require_device", lambda chips: dict(CPU))
    monkeypatch.setattr(harness, "peaks_for", lambda root, kind: CPU_PEAKS)
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda chips: 0)
    return harness


def with_fake_device(read_events):
    """``bench.tracing.read_events`` for a CPU trace, which has no device
    plane: the real host spans, and on one made-up device plane a prefill
    and a megastep executable in each ``bench.generate`` span, each with
    one operation."""
    def read(path):
        ev = read_events(path)
        ops, mods = [], []
        for name, s, e in ev["spans"]:
            if name != "bench.generate":
                continue
            third = (e - s) // 3
            mods.append(("jit__shared_prefill_impl(1)", s, s + third))
            mods.append(("jit__paged_megastep_impl(2)", s + third,
                         s + 2 * third))
            ops.append(("fusion.1", s, s + third))
            ops.append(("while.2", s + third, s + 2 * third))
        ev["device_ops"] = {"/device:TPU:0": ops}
        ev["device_modules"] = {"/device:TPU:0": mods}
        return ev
    return read
