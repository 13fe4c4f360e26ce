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

TINY_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
             "hbm_bytes": 16e9}


def load_tinies(directory: str):
    """The tiny configurations in ``directory`` (``<name>.json``, each a
    configuration as ``bench.model.load_config`` reads it plus the
    traffic mixes it ``rehearses``), by name: the configurations, and the
    mixes each rehearses."""
    configs, rehearses = {}, {}
    for name in sorted(f[:-5] for f in os.listdir(directory)
                       if f.endswith(".json")):
        with open(os.path.join(directory, f"{name}.json")) as f:
            configs[name] = json.load(f)
        rehearses[name] = configs[name].pop("rehearses")
    return configs, rehearses


def tiny_cells(rehearses):
    """(workload, configuration, traffic) of every tiny cell."""
    return [(f"{n}.{t}", n, t) for n, ts in rehearses.items() for t in ts]


TINIES, REHEARSES = load_tinies(TINY_DIR)
TINY, TINY_GQA = TINIES["tiny"], TINIES["tiny-gqa"]
CELLS = tiny_cells(REHEARSES)


def _architecture(source: str, config: dict):
    """The architecture a configuration's file names; None where the file
    is not there, so that no tiny cell rehearses its cells."""
    path = os.path.join(source, config["file"])
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        return json.load(f).get("architecture")


def make_root(tmp, limit: float = 1e-3, source: str = REPO) -> str:
    """A root holding a copy of ``source``'s bench/ and a BENCHMARK.json
    over the tiny configurations of its tests/bench/tiny/, with its spec's
    metrics. A cell of the spec stands for its configuration's
    architecture and its traffic, and is rehearsed by every tiny cell of
    the same pair; a metric's list of cells becomes the tiny cells that
    rehearse a cell on it, and a cell that none rehearses drops out."""
    root = str(tmp)
    shutil.copytree(os.path.join(source, "bench"),
                    os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(source, "BENCHMARK.json")) as f:
        spec = json.load(f)
    # from the repo, the loaded configurations, which a test may patch
    tinies, rehearses = (TINIES, REHEARSES) if source == REPO else \
        load_tinies(os.path.join(source, "tests", "bench", "tiny"))
    cells = tiny_cells(rehearses)
    for name, c in tinies.items():
        with open(os.path.join(root, "bench", "configs", f"{name}.json"),
                  "w") as f:
            json.dump(c, f)
    by_pair = {}
    for w, n, t in cells:
        by_pair.setdefault((tinies[n]["architecture"], t), set()).add(w)
    configs = {c["name"]: c for c in spec["configs"]}
    rehearsed_by = {
        w["name"]: by_pair.get((_architecture(source, configs[w["config"]]),
                                w["traffic"]), set())
        for w in spec["workloads"]}
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            named = set().union(*(rehearsed_by.get(w, ())
                                  for w in m["workloads"]))
            m["workloads"] = [w for w, _, _ in cells if w in named]
    spec["configs"] = [{"name": n, "source": "tests", "reduced": [],
                        "file": f"bench/configs/{n}.json", "why": "tests"}
                       for n in tinies]
    spec["workloads"] = [{"name": w, "config": n, "traffic": t, "chips": 1,
                          "why": "tests"} for w, n, t in cells]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    for w, _, _ in cells:
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
