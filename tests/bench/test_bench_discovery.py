"""The harness finds a configuration, its architecture, a traffic mix and
a per-layer metric by name: adding one takes new files and new entries in
BENCHMARK.json, and no edit of a file that is already there."""

import hashlib
import json
import os
import shutil
import time

from conftest import (CELLS, REPO, TINY, TINY_GQA, make_root,
                      with_fake_device)

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
    """sha256 of every file under ``root``'s bench/ and tests/bench/."""
    out = {}
    for d, _, files in (w for top in ("bench", os.path.join("tests", "bench"))
                        for w in os.walk(os.path.join(root, top))):
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
    return _add_workload(spec, config, traffic)


def _add_workload(spec, config, traffic):
    """A cell of a configuration the spec has, named in each metric's
    list of cells."""
    workload = f"{config}.{traffic}"
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


def test_a_new_architecture_is_rehearsed_from_new_files_of_the_repo(
        tmp_path, on_cpu, monkeypatch):
    """From a checkout of the repo's own BENCHMARK.json, bench/ and
    tests/bench/: a configuration of a new architecture with a cell of the
    factcheck traffic and one of a traffic no tiny file rehearses, each
    named in every metric's list, and one tiny file for the architecture.
    The CPU fixtures rehearse the first cell on the tiny file, correct
    plain and traced with every per-layer metric listed for it, drop the
    second, and need no file that was there changed."""
    from bench import tracing
    monkeypatch.setattr(tracing, "read_events",
                        with_fake_device(tracing.read_events))
    src = str(tmp_path / "checkout")
    for d in ("bench", os.path.join("tests", "bench")):
        shutil.copytree(os.path.join(REPO, d), os.path.join(src, d),
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), src)
    before = _digests(src)
    spec_path = os.path.join(src, "BENCHMARK.json")
    spec = json.load(open(spec_path))

    with open(os.path.join(src, "bench", "architectures",
                           "aliased_decoder.py"), "w") as f:
        f.write(ARCHITECTURE)
    real = json.load(open(os.path.join(src, "bench", "configs",
                                       "smollm2-1.7b.json")))
    rehearsed = _add_cell(src, spec, "aliased-1.7b",
                          dict(real, architecture="aliased_decoder",
                               aliased=True), "factcheck")
    _add_workload(spec, "aliased-1.7b", "resume")
    json.dump(spec, open(spec_path, "w"))
    json.dump(dict(TINY_GQA, architecture="aliased_decoder", aliased=True,
                   rehearses=["factcheck"]),
              open(os.path.join(src, "tests", "bench", "tiny",
                                "tiny-aliased.json"), "w"))

    root = make_root(tmp_path / "root", source=src)
    out = json.load(open(os.path.join(root, "BENCHMARK.json")))
    w = "tiny-aliased.factcheck"
    cells = {c for c, _, _ in CELLS} | {w}
    assert {c["name"] for c in out["workloads"]} == cells
    for m in out["end_to_end"] + out["per_layer"]:
        if "workloads" in m:
            assert set(m["workloads"]) == cells, m
    assert all(c["traffic"] == "factcheck" for c in out["workloads"])

    traced = _runs_correct(on_cpu, root, w)
    wanted = {m["name"] for m in out["per_layer"] if on_cpu.applies(m, w)}
    assert set(traced["metrics"]) == wanted
    assert wanted == {m["name"] for m in spec["per_layer"]
                      if on_cpu.applies(m, rehearsed)}
    after = _digests(src)
    assert {p: after[p] for p in before} == before
