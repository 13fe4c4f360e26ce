"""The harness finds a configuration, a traffic mix and a per-layer
metric by name: adding one takes new files and new entries in
BENCHMARK.json, and no edit of a file that is already there."""

import hashlib
import json
import os
import time

from conftest import TINY, make_root, with_fake_device

READER = '''"""Claims answered in the window (a made-up per-layer metric)."""


def read(r):
    return float(len(r.window["answers"])) or None
'''


def _digests(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[p] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_config_traffic_and_metric_need_only_new_files(
        tmp_path, on_cpu, monkeypatch):
    from bench import tracing
    monkeypatch.setattr(tracing, "read_events",
                        with_fake_device(tracing.read_events))
    root = make_root(tmp_path)
    before = _digests(root)
    spec_path = os.path.join(root, "BENCHMARK.json")
    spec = json.load(open(spec_path))

    # new files only
    cfg = dict(TINY, num_hidden_layers=1, hidden_size=32,
               intermediate_size=64, num_attention_heads=2,
               num_key_value_heads=1)
    json.dump(cfg, open(os.path.join(root, "bench", "configs",
                                     "tiny-mqa.json"), "w"))
    traffic = json.load(open(os.path.join(root, "bench", "traffic",
                                          "factcheck.json")))
    traffic.update(shots=2, max_new_tokens=2)
    json.dump(traffic, open(os.path.join(root, "bench", "traffic",
                                         "factcheck-short.json"), "w"))
    with open(os.path.join(root, "bench", "metrics",
                           "claims_answered.py"), "w") as f:
        f.write(READER)
    json.dump({"logit_gap": {"limit": 1e-3}},
              open(os.path.join(root, "bench", "limits",
                                "tiny-mqa.factcheck-short.json"), "w"))

    # new entries only
    spec["configs"].append({"name": "tiny-mqa", "source": "tests",
                            "file": "bench/configs/tiny-mqa.json",
                            "reduced": [], "why": "tests"})
    spec["workloads"].append({"name": "tiny-mqa.factcheck-short",
                              "config": "tiny-mqa",
                              "traffic": "factcheck-short", "chips": 1,
                              "why": "tests"})
    for m in spec["end_to_end"]:
        if m["name"] == "claims_per_s":
            m["workloads"].append("tiny-mqa.factcheck-short")
    spec["per_layer"].append({"name": "claims_answered", "unit": "claims",
                              "better": "higher", "source": "host_clock",
                              "layer": "runtime", "moves": "claims_per_s",
                              "workloads": ["tiny-mqa.factcheck-short"]})
    json.dump(spec, open(spec_path, "w"))

    w = "tiny-mqa.factcheck-short"
    plain = on_cpu.measure(root, w, 9, 0.3, False, time.monotonic())
    traced = on_cpu.measure(root, w, 9, 0.3, True, time.monotonic())
    assert plain["correct"] and traced["correct"]
    assert set(plain["metrics"]) == {"claims_per_s", "setup_s"}
    assert traced["metrics"]["claims_answered"]["value"] == traced["attempted"]
    assert traced["info"]["tokens_compared"] % 2 == 0
    after = _digests(root)
    assert {p: after[p] for p in before} == before
