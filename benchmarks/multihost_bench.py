"""Multi-host PCM benchmark (``--only multihost``): real worker
processes over the loopback socket transport.

Two sections, written to ``BENCH_multihost.json``:

``bootstrap``
    A 2-process joiner storm: node A cold-builds the reduced engine
    (model init + true XLA compiles), then node B joins cold and
    bootstraps entirely over the wire — serialized snapshot/template via
    ``repro.core.wire`` (chunked, sha256-verified), executables resolved
    through the shared on-disk AOTRecipe cache instead of recompiling.
    Metric: node A's cold cost (builder + true-compile seconds) vs node
    B's wire bootstrap (install + its own compile seconds, which must be
    ~0). Strict: >= 50x, zero builder calls and zero true XLA recompiles
    on the joiner (AOT cache hits only), greedy outputs bit-identical
    across the two processes.

``calibration``
    The planner's per-transport-kind EWMA after the live run: the
    socket namespace holds a real observed loopback rate while the
    memcpy namespace stays untouched (no in-process transfers happened),
    demonstrating that wire lanes price from NIC calibration, never from
    memcpy history. Strict: socket observed, memcpy None.

The whole benchmark doubles as a hang canary for the transport threads
(per-connection reader/writer, heartbeat monitor, node frame loop) when
CI runs it under a hard wall-clock timeout.

It is a CPU check: every process runs with ``JAX_PLATFORMS=cpu`` (node
processes never take a chip), so its seconds are CPU seconds.
"""

from __future__ import annotations

import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TESTS = os.path.join(_REPO, "tests")
if _TESTS not in sys.path:
    # the cross-process task/recipe vocabulary lives with the multihost
    # tests: both sides of the socket must import it by module name
    sys.path.insert(0, _TESTS)

N_TASKS = 8


def _wait(cond, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.05)
    return cond()


def bench_multihost(quick: bool = False, strict: bool = False) -> dict:
    import multihost_helpers as H
    from repro.core import ContextMode, PCMManager
    from repro.cluster.node import spawn_node_process

    from repro.launch.compile_cache import AOT_SUBDIR, configure_compile_cache

    aot_dir = os.path.join(configure_compile_cache(), AOT_SUBDIR)
    mgr = PCMManager(mode=ContextMode.FULL, n_workers=0,
                     chunk_bytes=1 << 20)
    procs = {}
    try:
        addr = mgr.listen()
        spawn = lambda wid: spawn_node_process(  # noqa: E731
            addr, wid, aot_cache=aot_dir, extra_path=(_TESTS,))

        # ---- cold build on node A (publishes into the shared AOT cache)
        procs["nodeA"] = spawn("nodeA")
        mgr.wait_for_workers(["nodeA"], timeout=180)
        recipe = H.tiny_engine_recipe()
        prompts = H.tiny_prompts(4)
        mgr.warm_up(recipe, worker_ids=["nodeA"])
        pidA, outA, stA = mgr.submit(
            H.probe_task, args=(prompts,), recipe=recipe).result(timeout=600)
        mirA = mgr.workers["nodeA"].library
        cold_seconds = mirA.build_seconds_total + stA["compile_seconds"]

        # ---- joiner storm: node B bootstraps over the wire
        procs["nodeB"] = spawn("nodeB")
        mgr.wait_for_workers(["nodeB"], timeout=180)
        futs = [mgr.submit(H.slow_probe_task, args=(prompts, 0.4),
                           recipe=recipe) for _ in range(N_TASKS)]
        results = [f.result(timeout=600) for f in futs]
        mgr.run_until_idle(timeout=120)
        _wait(lambda: not mgr._stripes and mgr.fetch_history(recipe))

        mirB = mgr.workers["nodeB"].library
        pid_to_node = {p.pid: wid for wid, p in procs.items()}
        joiner_stats = [st for pid, _out, st in results
                        if pid_to_node.get(pid) == "nodeB"]
        parity = all(out == outA for _pid, out, _st in results)
        bootstrap_seconds = (mirB.peer_install_seconds
                             + mirB.restore_seconds_total)
        joiner_compile_seconds = max(
            [st["compile_seconds"] for st in joiner_stats], default=0.0)
        warm_seconds = bootstrap_seconds + joiner_compile_seconds
        speedup = cold_seconds / max(warm_seconds, 1e-9)
        hist = mgr.fetch_history(recipe)
        record = {
            "bootstrap": {
                "n_tasks": N_TASKS,
                "cold_build_seconds": cold_seconds,
                "cold_builder_seconds": mirA.build_seconds_total,
                "cold_compile_seconds": stA["compile_seconds"],
                "warm_bootstrap_seconds": warm_seconds,
                "joiner_install_seconds": bootstrap_seconds,
                "joiner_compile_seconds": joiner_compile_seconds,
                "speedup_serialized_vs_cold_build": speedup,
                "joiner_builder_calls": mirB.builder_calls,
                "joiner_true_compiles": max(
                    [st["compiles"] for st in joiner_stats], default=0),
                "joiner_aot_cache_hits": max(
                    [st["aot_cache_hits"] for st in joiner_stats],
                    default=0),
                "joiner_tasks": len(joiner_stats),
                "greedy_parity": parity,
                "fetch_sources": sorted({d.source.name for d in hist}),
                "stripe_stats": dict(mgr._stripe_stats),
            },
        }

        cal = mgr.planner.calibration()
        record["calibration"] = {
            "socket_bytes_per_s": cal["p2p:socket"],
            "memcpy_bytes_per_s": cal["p2p:memcpy"],
            "nic_default_bytes_per_s": mgr.planner.nic_bytes_per_s,
            "socket_lane_observed": cal["p2p:socket"] is not None,
        }

        if strict:
            b = record["bootstrap"]
            assert b["greedy_parity"], \
                "greedy outputs diverged across processes"
            assert b["joiner_tasks"] >= 1, \
                "the joiner never ran a task — storm did not spill over"
            assert b["joiner_builder_calls"] == 0, \
                f"joiner rebuilt: {b['joiner_builder_calls']} builder calls"
            assert b["joiner_true_compiles"] == 0, \
                f"joiner recompiled: {b['joiner_true_compiles']}"
            assert b["joiner_aot_cache_hits"] > 0, \
                "joiner resolved no executables through the AOT cache"
            assert b["joiner_install_seconds"] > 0, \
                "no wire install was measured on the joiner"
            assert b["speedup_serialized_vs_cold_build"] >= 50.0, \
                (f"serialized bootstrap only "
                 f"x{b['speedup_serialized_vs_cold_build']:.1f} vs cold "
                 f"build (cold {b['cold_build_seconds']:.2f}s, warm "
                 f"{b['warm_bootstrap_seconds']:.3f}s)")
            c = record["calibration"]
            assert c["socket_lane_observed"], \
                "no socket-lane calibration was recorded"
            assert c["memcpy_bytes_per_s"] is None, \
                "memcpy namespace contaminated by wire observations"
        return record
    finally:
        mgr.shutdown(timeout=60)
        for p in procs.values():
            try:
                p.terminate()
                p.wait(timeout=10)
            except Exception:
                pass


if __name__ == "__main__":
    import json
    print(json.dumps(bench_multihost(quick=True, strict=True), indent=2))
