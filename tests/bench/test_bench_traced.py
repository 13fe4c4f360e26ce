"""CPU rehearsal of every cell's traced run: per-layer readers, the
device's busy and window seconds and the breakdown. The CPU has no
device plane, so one is made up from the benchmark's own host spans."""

import json
import os
import time

import pytest

from conftest import CELLS, make_root, with_fake_device


@pytest.mark.parametrize("workload", [w for w, _, _ in CELLS])
def test_traced_cell_reports_its_per_layer_metrics(tmp_path, on_cpu,
                                                   monkeypatch, workload):
    from bench import tracing
    monkeypatch.setattr(tracing, "read_events",
                        with_fake_device(tracing.read_events))
    root = make_root(tmp_path)
    res = on_cpu.measure(root, workload, 3, 0.5, True, time.monotonic())
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    wanted = {m["name"] for m in spec["per_layer"]
              if on_cpu.applies(m, workload)}
    assert set(res["metrics"]) == wanted
    assert res["correct"], res["compared"]
    dev = res["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    b = res["breakdown"]
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(isinstance(n, str) and s > 0 for n, s in b["idle_gaps"])
    for name in ("idle_share", "prefix_reuse_share"):
        if name in res["metrics"]:
            assert 0 <= res["metrics"][name]["value"] <= 100
    assert not os.path.exists(os.path.join(root, ".bench_trace"))
