"""The harness finds a configuration, its architecture, a traffic mix and
a per-layer metric by name: adding one takes new files and new entries in
BENCHMARK.json, and no edit of a file that is already there."""

import hashlib
import json
import os
import time

from conftest import TINY, TINY_GQA, make_root, with_fake_device

READER = '''"""Claims answered in the window (a made-up per-layer metric)."""


def read(r):
    return float(len(r.window["answers"])) or None
'''

ARCHITECTURE = '''"""The dense decoder under another name (a made-up architecture),
which refuses a file that does not say it is aliased."""

from bench.architectures.dense_decoder import (  # noqa: F401
    decode_cost, kv_bytes_per_token, layout, logits_at, prefill_cost,
    program_config, weight_bytes)
from bench.architectures import dense_decoder


def check(c):
    if not c.get("aliased"):
        raise ValueError("not aliased")
    dense_decoder.check(c)
'''


def _digests(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[p] = hashlib.sha256(fh.read()).hexdigest()
    return out


def _add_cell(root, spec, config, cfg, traffic):
    """New files and new entries only: a configuration file, a limits
    file, the configuration and cell in the spec, and the cell in each
    metric's list of cells."""
    workload = f"{config}.{traffic}"
    json.dump(cfg, open(os.path.join(root, "bench", "configs",
                                     f"{config}.json"), "w"))
    json.dump({"logit_gap": {"limit": 1e-3}},
              open(os.path.join(root, "bench", "limits",
                                f"{workload}.json"), "w"))
    spec["configs"].append({"name": config, "source": "tests",
                            "file": f"bench/configs/{config}.json",
                            "reduced": [], "why": "tests"})
    spec["workloads"].append({"name": workload, "config": config,
                              "traffic": traffic, "chips": 1,
                              "why": "tests"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(workload)
    return workload


def _runs_correct(harness, root, workload):
    plain = harness.measure(root, workload, 9, 0.3, False, time.monotonic())
    traced = harness.measure(root, workload, 9, 0.3, True, time.monotonic())
    assert plain["correct"] and traced["correct"]
    assert set(plain["metrics"]) == {"claims_per_s", "setup_s"}
    return traced


def test_new_config_traffic_and_metric_need_only_new_files(
        tmp_path, on_cpu, monkeypatch):
    from bench import tracing
    monkeypatch.setattr(tracing, "read_events",
                        with_fake_device(tracing.read_events))
    root = make_root(tmp_path)
    before = _digests(root)
    spec_path = os.path.join(root, "BENCHMARK.json")
    spec = json.load(open(spec_path))

    cfg = dict(TINY, num_hidden_layers=1, hidden_size=32,
               intermediate_size=64, num_attention_heads=2,
               num_key_value_heads=1)
    traffic = json.load(open(os.path.join(root, "bench", "traffic",
                                          "factcheck.json")))
    traffic.update(shots=2, max_new_tokens=2)
    json.dump(traffic, open(os.path.join(root, "bench", "traffic",
                                         "factcheck-short.json"), "w"))
    with open(os.path.join(root, "bench", "metrics",
                           "claims_answered.py"), "w") as f:
        f.write(READER)
    w = _add_cell(root, spec, "tiny-mqa", cfg, "factcheck-short")
    spec["per_layer"].append({"name": "claims_answered", "unit": "claims",
                              "better": "higher", "source": "host_clock",
                              "layer": "runtime", "moves": "claims_per_s",
                              "workloads": [w]})
    json.dump(spec, open(spec_path, "w"))

    traced = _runs_correct(on_cpu, root, w)
    assert traced["metrics"]["claims_answered"]["value"] == traced["attempted"]
    assert traced["info"]["tokens_compared"] % 2 == 0
    after = _digests(root)
    assert {p: after[p] for p in before} == before


def test_new_architecture_needs_only_new_files(tmp_path, on_cpu,
                                               monkeypatch):
    """A configuration of an architecture the benchmark did not know: a
    module in bench/architectures/ (here the dense decoder under another
    name, with its own check), its configuration file, a limits file and
    the entries. The cell runs correct, plain and traced, with every
    per-layer metric that reads the architecture's counts."""
    from bench import tracing
    monkeypatch.setattr(tracing, "read_events",
                        with_fake_device(tracing.read_events))
    root = make_root(tmp_path)
    before = _digests(root)
    spec_path = os.path.join(root, "BENCHMARK.json")
    spec = json.load(open(spec_path))
    with open(os.path.join(root, "bench", "architectures",
                           "aliased_decoder.py"), "w") as f:
        f.write(ARCHITECTURE)
    w = _add_cell(root, spec, "tiny-aliased",
                  dict(TINY_GQA, architecture="aliased_decoder",
                       aliased=True), "factcheck")
    json.dump(spec, open(spec_path, "w"))

    traced = _runs_correct(on_cpu, root, w)
    wanted = {m["name"] for m in spec["per_layer"]}
    assert set(traced["metrics"]) == wanted
    after = _digests(root)
    assert {p: after[p] for p in before} == before
