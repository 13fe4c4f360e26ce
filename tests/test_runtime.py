"""Concurrent PCM runtime tests: actor workers, physical tier movement
(DEVICE -> HOST_RAM -> LOCAL_DISK -> DEVICE), preemption mid-flight, and
the one-clock-source contract."""

import threading
import time

import numpy as np
import pytest

from repro.core import (ContextAwareScheduler, ContextMode, ContextRecipe,
                        ContextStore, Library, PCMClient, PCMManager,
                        SimulatorBackend, SnapshotPool, Task, Tier,
                        TierFullError, load_context, make_recipe)
from repro.core.context import GB
from repro.core.store import DEFAULT_DEVICE_BYTES, device_tier_bytes


# ---------------------------------------------------------- store admit ----
class TestAdmitRefusal:
    def test_pinned_blockage_refused_not_overcommitted(self):
        s = ContextStore(device_bytes=10 * GB)
        s.pin("a")
        s.admit("a", Tier.DEVICE, 8 * GB)
        with pytest.raises(TierFullError):
            s.admit("b", Tier.DEVICE, 6 * GB)
        assert not s.has("b", Tier.DEVICE)
        assert s.used(Tier.DEVICE) == 8 * GB      # never exceeded capacity

    def test_pinned_bytes_surfaced_in_stats(self):
        s = ContextStore(device_bytes=10 * GB)
        s.pin("a")
        s.admit("a", Tier.DEVICE, 8 * GB)
        s.admit("b", Tier.HOST_RAM, 1 * GB)
        st = s.stats()
        assert st["tiers"]["DEVICE"]["pinned_bytes"] == 8 * GB
        assert st["tiers"]["DEVICE"]["used_bytes"] == 8 * GB
        assert st["tiers"]["HOST_RAM"]["pinned_bytes"] == 0
        assert st["tiers"]["HOST_RAM"]["entries"] == 1

    def test_unpinned_victims_still_evicted(self):
        s = ContextStore(device_bytes=10 * GB)
        s.pin("a")
        s.admit("a", Tier.DEVICE, 4 * GB, now=1.0)
        s.admit("b", Tier.DEVICE, 4 * GB, now=2.0)
        evicted = s.admit("c", Tier.DEVICE, 4 * GB, now=3.0)
        assert evicted == ["b"]                   # pinned "a" survived
        assert s.has("a", Tier.DEVICE) and s.has("c", Tier.DEVICE)

    def test_readmission_replaces_not_double_counts(self):
        s = ContextStore(device_bytes=10 * GB)
        s.admit("a", Tier.DEVICE, 8 * GB, now=1.0)
        # re-admitting the resident key must not evict anything or raise
        assert s.admit("a", Tier.DEVICE, 8 * GB, now=2.0) == []
        assert s.used(Tier.DEVICE) == 8 * GB

    def test_oversized_is_tier_full(self):
        s = ContextStore(device_bytes=1 * GB)
        with pytest.raises(TierFullError):
            s.admit("big", Tier.DEVICE, 2 * GB)


class _FakeDevice:
    def __init__(self, platform, stats):
        self.platform, self.device_kind, self._stats = platform, "fake", stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("platform,stats,want", [
    ("cpu", None, DEFAULT_DEVICE_BYTES),
    ("tpu", {"bytes_limit": 15 * GB}, 15 * GB),
    ("tpu", None, RuntimeError),
    ("tpu", {"bytes_in_use": 0}, RuntimeError),
])
def test_device_tier_sized_from_the_device(platform, stats, want):
    """A live worker's DEVICE tier holds what its device holds; only the
    CPU keeps the default, and an unreadable accelerator is refused."""
    dev = _FakeDevice(platform, stats)
    if want is RuntimeError:
        with pytest.raises(RuntimeError):
            device_tier_bytes(dev)
    else:
        assert device_tier_bytes(dev) == want


def test_a_chip_holds_one_live_worker(monkeypatch):
    """Live workers take one accelerator each, and each DEVICE tier is its
    whole chip; a worker beyond the chips is refused instead of admitting
    a chip's memory twice."""
    import types
    from repro.core import manager as manager_mod
    chips = [_FakeDevice("tpu", {"bytes_limit": 16 * GB}) for _ in range(2)]
    for i, chip in enumerate(chips):
        chip.id = i
    mgr = PCMManager(n_workers=0)
    monkeypatch.setattr(manager_mod, "jax",
                        types.SimpleNamespace(local_devices=lambda: chips))
    try:
        for chip in chips:
            assert mgr._free_device() is chip
            w = manager_mod.LiveWorker(f"w{chip.id}", mgr, device=chip)
            assert w.store.capacity[Tier.DEVICE] == 16 * GB
            mgr.workers[w.worker_id] = w
        with pytest.raises(RuntimeError, match="a chip holds one"):
            mgr._free_device()
    finally:
        mgr.workers.clear()
        mgr.shutdown()


# ----------------------------------------------------------- one clock -----
class TestClockSource:
    def test_live_event_timestamps_use_backend_clock(self):
        """All scheduler events must carry manager-relative time (seconds
        since start), never raw time.monotonic()."""
        mgr = PCMManager(mode=ContextMode.FULL, n_workers=1)
        try:
            assert mgr.submit(lambda: 1).result(timeout=30) == 1
            c = mgr.scheduler.completions[0]
            assert 0.0 <= c.t <= mgr.now + 0.5
            assert 0.0 <= c.duration < 30.0
            info = next(iter(mgr.scheduler.workers.values()))
            assert 0.0 <= info.joined_at <= mgr.now
        finally:
            mgr.shutdown()

    def test_preemption_timestamp_on_backend_clock(self):
        mgr = PCMManager(mode=ContextMode.FULL, n_workers=1)
        try:
            fut = mgr.submit(lambda: time.sleep(0.05) or 1)
            mgr.preempt_worker(next(iter(mgr.workers)))
            mgr.add_worker()
            assert fut.result(timeout=30) == 1
            task = mgr.lookup_task(fut.task_id)
            assert task.attempts >= 1
            # submitted_at and the completion both live on the same clock
            assert task.submitted_at <= mgr.scheduler.completions[-1].t
        finally:
            mgr.shutdown()

    def test_sim_clock_is_modeled_time(self):
        backend = SimulatorBackend(n_workers=1)
        sim = PCMClient(backend=backend)
        res = sim.submit(lambda: None,
                         context=sim.context(ContextRecipe(name="m"))
                         ).result()
        assert res.finished_at == pytest.approx(backend.now)
        assert backend.scheduler.completions[0].t == res.finished_at


# --------------------------------------------------- concurrent runtime ----
class TestConcurrentRuntime:
    def test_workers_execute_in_parallel(self):
        """Four 0.25s sleeps across four actor threads must overlap."""
        mgr = PCMManager(mode=ContextMode.FULL, n_workers=4)
        try:
            t0 = time.monotonic()
            futs = [mgr.submit(lambda: time.sleep(0.25) or 1)
                    for _ in range(4)]
            assert [f.result(timeout=30) for f in futs] == [1] * 4
            assert time.monotonic() - t0 < 0.85   # serial would be >= 1.0
        finally:
            mgr.shutdown()

    def test_preemption_during_inflight_task(self):
        """A task preempted mid-execution reruns elsewhere; the zombie
        copy's result is discarded at the revalidation barrier."""
        mgr = PCMManager(mode=ContextMode.FULL, n_workers=1)
        try:
            started = threading.Event()
            release = threading.Event()

            def slow(x):
                started.set()
                release.wait(10)
                return x * 2

            fut = mgr.submit(slow, (21,))
            assert started.wait(10)
            victim = next(iter(mgr.workers))
            mgr.preempt_worker(victim)            # no-warning, mid-flight
            mgr.add_worker()
            release.set()
            assert fut.result(timeout=30) == 42
            assert mgr.lookup_task(fut.task_id).attempts >= 1
            assert len([c for c in mgr.scheduler.completions
                        if c.task_id == fut.task_id]) == 1
        finally:
            release.set()
            mgr.shutdown()

    def test_preemption_during_materialize(self):
        """Preempting a worker while its builder runs must not wedge the
        pool or lose the task."""
        mgr = PCMManager(mode=ContextMode.FULL, n_workers=1)
        try:
            building = threading.Event()

            def slow_build():
                building.set()
                time.sleep(0.2)
                return {"v": 7}

            rec = make_recipe("slowctx", slow_build)
            fut = mgr.submit(lambda: load_context("v") + 1, recipe=rec)
            assert building.wait(10)
            mgr.preempt_worker(next(iter(mgr.workers)))
            mgr.add_worker()
            assert fut.result(timeout=30) == 8
        finally:
            mgr.shutdown()

    def test_map_over_four_workers_survives_midrun_preemption(self):
        """Acceptance: client.map across >=4 concurrent workers completes
        every future through a mid-run preemption."""
        mgr = PCMManager(mode=ContextMode.FULL, n_workers=4)
        client = PCMClient(backend=mgr)
        try:
            ctx = client.context(lambda: {"m": 100}, name="ctx")

            def f(x):
                time.sleep(0.02)
                return load_context("m") + x

            batch = client.map(f, list(range(24)), context=ctx, timeout=60)
            time.sleep(0.1)                       # mid-run
            mgr.preempt_worker(next(iter(mgr.workers)))
            mgr.add_worker()
            assert batch.gather() == [100 + i for i in range(24)]
        finally:
            mgr.shutdown()

    def test_as_completed_concurrent_backend(self):
        mgr = PCMManager(mode=ContextMode.FULL, n_workers=2)
        client = PCMClient(backend=mgr)
        try:
            batch = client.map(lambda x: x * 2, [1, 2, 3, 4])
            seen = sorted(f.result(timeout=10)
                          for f in batch.as_completed(timeout=30))
            assert seen == [2, 4, 6, 8]
        finally:
            mgr.shutdown()

    def test_run_until_idle_counts_completions(self):
        mgr = PCMManager(mode=ContextMode.FULL, n_workers=2)
        try:
            futs = [mgr.submit(lambda: 1) for _ in range(6)]
            done = mgr.run_until_idle(timeout=30)
            assert all(f.done for f in futs)
            assert done == 6
        finally:
            mgr.shutdown()


# -------------------------------------------- snapshot pool (host tiers) ---
class FakeEngine:
    """Minimal offloadable component (the serving engine's duck-type)."""

    def __init__(self, n=1000):
        self.weights = np.arange(n, dtype=np.float64)
        self.exe_cache = {"megastep": object()}   # survives the round trip

    def offload_device_state(self):
        state = {"weights": self.weights}
        self.weights = None
        return state

    def restore_device_state(self, host_state):
        self.weights = host_state["weights"]


class TestSnapshotPool:
    def test_demote_restore_roundtrip_plain_value(self):
        pool = SnapshotPool()
        builds = []
        rec = make_recipe("plain", lambda: builds.append(1) or {"v": 5})
        lib = Library("w0", snapshots=pool)
        lib.ensure(rec)
        assert lib.demote(rec.key()) is not None
        assert not lib.has(rec.key())
        assert pool.tier(rec.key()) == Tier.HOST_RAM
        ctx = lib.ensure(rec)                     # promotes, no rebuild
        assert ctx.value == {"v": 5} and ctx.restored
        assert builds == [1]
        assert lib.restores == 1 and lib.builder_calls == 1

    def test_host_capacity_spills_lru_to_disk(self, tmp_path):
        pool = SnapshotPool(host_bytes=10_000, spill_dir=str(tmp_path))
        lib = Library("w0", snapshots=pool)
        r1 = make_recipe("e1", FakeEngine, host_bytes=0)
        r2 = make_recipe("e2", FakeEngine, host_bytes=0)
        lib.ensure(r1)
        lib.ensure(r2)
        lib.demote(r1.key())                      # 8000 B in host
        lib.demote(r2.key())                      # over 10k: r1 spills
        assert pool.tier(r1.key()) == Tier.LOCAL_DISK
        assert pool.tier(r2.key()) == Tier.HOST_RAM
        assert pool.stats()["spills"] == 1
        # restore from DISK: unspill + reattach, bit-identical arrays
        eng = lib.ensure(r1).value
        assert isinstance(eng, FakeEngine)
        np.testing.assert_array_equal(eng.weights,
                                      np.arange(1000, dtype=np.float64))
        assert "megastep" in eng.exe_cache        # metadata never left

    def test_explicit_spill_and_restore(self, tmp_path):
        pool = SnapshotPool(spill_dir=str(tmp_path))
        lib = Library("w0", snapshots=pool)
        rec = make_recipe("e", FakeEngine)
        lib.ensure(rec)
        lib.demote(rec.key())
        assert pool.spill(rec.key())
        assert pool.tier(rec.key()) == Tier.LOCAL_DISK
        eng = lib.ensure(rec).value
        np.testing.assert_array_equal(eng.weights,
                                      np.arange(1000, dtype=np.float64))

    def test_restore_seconds_waits_for_the_restored_state(self):
        """``restore_seconds`` ends at a barrier on the state the
        components hand back, not when the copies were only issued."""
        class Pending:                          # a copy still in flight
            def block_until_ready(self):
                time.sleep(0.05)
                return self

        class SlowEngine(FakeEngine):
            def restore_device_state(self, host_state):
                super().restore_device_state(host_state)
                return {"weights": Pending()}

        pool = SnapshotPool()
        lib = Library("w0", snapshots=pool)
        rec = make_recipe("slow", SlowEngine, host_bytes=0)
        lib.ensure(rec)
        lib.demote(rec.key())
        ctx = lib.ensure(rec)
        assert ctx.restored and ctx.restore_seconds >= 0.05
        assert lib.restore_seconds_total == ctx.restore_seconds

    def test_demote_without_pool_refuses_not_destroys(self):
        lib = Library("w0")                       # no snapshot pool
        builds = []
        rec = make_recipe("nopool", lambda: builds.append(1) or {"v": 1})
        lib.ensure(rec)
        assert lib.demote(rec.key()) is None      # nowhere to put it
        assert lib.has(rec.key())                 # so it must NOT evict
        lib.ensure(rec)
        assert builds == [1]

    def test_pinned_context_requires_force_demote(self):
        pool = SnapshotPool()
        lib = Library("w0", snapshots=pool)
        rec = make_recipe("pinned", lambda: {"v": 1})
        lib.ensure(rec)
        lib.pin(rec.key())
        assert lib.demote(rec.key()) is None      # pin = device promise
        assert lib.has(rec.key())
        assert lib.demote(rec.key(), force=True) is not None


class TestPreemptRejoinRestore:
    def test_preempt_then_rejoin_restores_from_pool(self):
        """The tentpole acceptance path: preempt_worker -> add_worker
        round-trips the context at restore cost (no builder rerun)."""
        builds = []
        mgr = PCMManager(mode=ContextMode.FULL, n_workers=1)
        try:
            rec = make_recipe("ctx", lambda: builds.append(1) or {"v": 3})
            mgr.warm_up(rec)
            assert builds == [1]
            mgr.preempt_worker(next(iter(mgr.workers)))
            deadline = time.monotonic() + 10
            while rec.key() not in mgr.snapshots.keys():
                assert time.monotonic() < deadline, "retirement demotion " \
                    "never reached the snapshot pool"
                time.sleep(0.01)
            assert mgr.snapshots.tier(rec.key()) == Tier.HOST_RAM
            mgr.add_worker()
            fut = mgr.submit(lambda: load_context("v"), recipe=rec)
            assert fut.result(timeout=30) == 3
            assert builds == [1]                  # restored, never rebuilt
            st = mgr.stats()
            assert st["context_restores"] == 1
            assert st["snapshot_pool"]["demotions"] >= 1
        finally:
            mgr.shutdown()

    def test_phantom_host_residency_invalidated_on_restore(self):
        """Two workers demote into the node pool (one surviving snapshot);
        once something consumes it, every worker's HOST_RAM claim is a
        phantom and must be invalidated so placement stays honest."""
        mgr = PCMManager(mode=ContextMode.FULL, n_workers=2)
        try:
            rec = make_recipe("ph", lambda: {"v": 1})
            mgr.warm_up(rec)
            mgr.demote_context(rec)
            assert all(t == Tier.HOST_RAM
                       for t in mgr.residency(rec).values())
            # consume the snapshot the way a restoring worker would
            assert mgr.snapshots.take(rec.key()) is not None
            assert all(t < Tier.HOST_RAM
                       for t in mgr.residency(rec).values())
            # and the runtime still completes work (cold rebuild)
            assert mgr.submit(lambda: load_context("v"),
                              recipe=rec).result(timeout=60) == 1
        finally:
            mgr.shutdown()

    def test_shutdown_fails_outstanding_futures(self):
        gate = threading.Event()
        mgr = PCMManager(mode=ContextMode.FULL, n_workers=1)
        fut = mgr.submit(lambda: gate.wait(10))
        fut2 = mgr.submit(lambda: 2)              # queued behind the gate
        mgr.shutdown(timeout=0.1)
        gate.set()
        with pytest.raises(RuntimeError, match="shut down"):
            fut2.result()
        with pytest.raises(RuntimeError, match="shut down"):
            fut.result()

    def test_sim_demotion_respects_pins_like_live(self):
        backend = SimulatorBackend(n_workers=1)
        sim = PCMClient(backend=backend)
        h = sim.context(ContextRecipe(name="m"))
        h.warm_up()
        h.pin()
        assert backend.demote_context(h.recipe) == []
        h.release()
        assert len(backend.demote_context(h.recipe)) == 1

    def test_demote_context_api_and_residency(self):
        mgr = PCMManager(mode=ContextMode.FULL, n_workers=1)
        client = PCMClient(backend=mgr)
        try:
            builds = []
            ctx = client.context(lambda: builds.append(1) or {"m": 9},
                                 name="d")
            ctx.warm_up()
            assert ctx.demote(Tier.HOST_RAM)
            assert ctx.snapshot_tier() == Tier.HOST_RAM
            assert all(t == Tier.HOST_RAM
                       for t in ctx.residency().values())
            assert client.submit(lambda: load_context("m"),
                                 context=ctx).result(timeout=30) == 9
            assert builds == [1]
        finally:
            mgr.shutdown()


# ------------------------------------------------- scheduler host tier -----
class TestHostTierPlacement:
    def test_prefers_host_resident_worker_over_cold(self):
        R = ContextRecipe(name="m")
        s = ContextAwareScheduler(mode=ContextMode.FULL)
        s.on_worker_join("cold", 0.0)
        s.on_worker_join("warmish", 0.0)
        st = s.workers["warmish"].store
        st.admit(R.key(), Tier.LOCAL_DISK, R.transfer_bytes)
        st.admit(R.key(), Tier.HOST_RAM, R.host_bytes)
        acts = s.submit(Task(task_id="t0", recipe=R), 1.0)
        starts = [a for a in acts if a.kind == "start"]
        assert starts[0].worker_id == "warmish"
        assert not starts[0].warm
        assert starts[0].host_resident == (True,)

    def test_sim_models_restore_cheaper_than_cold(self):
        backend = SimulatorBackend(n_workers=1)
        sim = PCMClient(backend=backend)
        h = sim.context(ContextRecipe(name="m"))
        cold = sim.submit(lambda: None, context=h).result()
        backend.demote_context(h.recipe, Tier.HOST_RAM)
        restored = sim.submit(lambda: None, context=h).result()
        warm = sim.submit(lambda: None, context=h).result()
        assert not cold.warm and not restored.warm and warm.warm
        assert cold.duration > 3 * restored.duration
        assert restored.duration > warm.duration


# ------------------------------------------------ real engine round trip ---
@pytest.fixture(scope="module")
def smol():
    import jax
    from repro.configs import get_reduced_config
    from repro.models import build_model
    cfg = get_reduced_config("smollm2-1.7b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompts(cfg, n, seed=0):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(8, cfg.vocab_size,
                             size=rng.randint(3, 14))) for _ in range(n)]


def _engine_recipe(name, model, params, builds=None):
    from repro.serving import InferenceEngine

    def build():
        if builds is not None:
            builds.append(1)
        eng = InferenceEngine(model, params, slots=2, cache_len=64,
                              prefill_buckets=(16,), megastep=4)
        return {"engine": eng}

    return make_recipe(name, build, host_bytes=0)


def _paged_engine_recipe(name, model, params, builds=None):
    from repro.serving import InferenceEngine

    def build():
        if builds is not None:
            builds.append(1)
        eng = InferenceEngine(model, params, slots=4, cache_len=64,
                              prefill_buckets=(16,), megastep=4,
                              paged=True, page_size=8)
        return {"engine": eng}

    return make_recipe(name, build, host_bytes=0)


class TestEngineTierRoundTrip:
    def test_device_host_disk_device_parity(self, smol, tmp_path):
        """Acceptance: DEVICE -> HOST_RAM -> LOCAL_DISK -> DEVICE round
        trip restores with zero builder calls, zero XLA compiles, and
        bit-identical greedy outputs vs the never-demoted context."""
        cfg, model, params = smol
        ps = _prompts(cfg, 5)
        builds = []
        pool = SnapshotPool(spill_dir=str(tmp_path))
        lib = Library("w0", snapshots=pool)
        rec = _engine_recipe("rt", model, params, builds)

        ctx = lib.ensure(rec)
        eng = ctx.value["engine"]
        baseline = eng.generate(ps, max_new_tokens=6)   # greedy (temp=0)
        # reference: a separate never-demoted engine gives the same greedy
        reference = _engine_recipe("ref", model, params).builder()["engine"]
        assert reference.generate(ps, max_new_tokens=6) == baseline
        compiles_before = eng.stats.compiles

        lib.demote(rec.key())                     # DEVICE -> HOST_RAM
        assert eng.offloaded and eng.params is None
        with pytest.raises(RuntimeError, match="offloaded"):
            eng.generate(ps, max_new_tokens=1)
        assert pool.spill(rec.key())              # HOST_RAM -> LOCAL_DISK
        assert pool.tier(rec.key()) == Tier.LOCAL_DISK

        ctx2 = lib.ensure(rec)                    # LOCAL_DISK -> DEVICE
        eng2 = ctx2.value["engine"]
        assert eng2 is eng and not eng2.offloaded
        assert builds == [1]                      # ZERO builder calls
        out = eng2.generate(ps, max_new_tokens=6)
        assert out == baseline                    # bit-identical greedy
        assert eng2.stats.compiles == compiles_before   # ZERO compiles
        assert lib.restores == 1 and ctx2.restored
        assert ctx2.restore_seconds > 0

    def test_restore_seconds_covers_the_device_copy(self, smol):
        """The restored engine's cache is ready when ``restore_seconds``
        is taken: a later barrier on it waits for nothing the restore
        left out."""
        import jax
        cfg, model, params = smol
        pool = SnapshotPool()
        lib = Library("w0", snapshots=pool)
        rec = _paged_engine_recipe("barrier", model, params)
        eng = lib.ensure(rec).value["engine"]
        eng.generate(_prompts(cfg, 2), max_new_tokens=3)
        lib.demote(rec.key())
        ctx = lib.ensure(rec)
        t0 = time.monotonic()
        jax.block_until_ready(ctx.value["engine"].cache)
        assert ctx.restore_seconds >= time.monotonic() - t0

    def test_restore_preserves_midstream_state(self, smol):
        """Demoting between megasteps and restoring must continue decoding
        exactly where the never-demoted engine would."""
        cfg, model, params = smol
        from repro.serving import InferenceEngine, Request

        def mk():
            return InferenceEngine(model, params, slots=2, cache_len=64,
                                   prefill_buckets=(16,), megastep=4)

        ps = _prompts(cfg, 2, seed=7)
        ref = mk()
        for p in ps:
            ref.submit(Request(prompt=list(p), max_new_tokens=12))
        want = [r.generated for r in ref.run_to_completion()]

        eng = mk()
        reqs = [eng.submit(Request(prompt=list(p), max_new_tokens=12))
                for p in ps]
        eng.step()                                # prefill + first megastep
        host = eng.offload_device_state()         # demote mid-stream
        assert eng.offloaded
        eng.restore_device_state(host)            # promote
        while eng.has_work():
            eng.step()
        got = sorted(r.generated for r in reqs)
        assert got == sorted(want)

    def test_preemption_during_inflight_megastep(self, smol):
        """Preempting the worker while a generate() is mid-megastep must
        rerun the task elsewhere and produce the same greedy output."""
        cfg, model, params = smol
        ps = _prompts(cfg, 3, seed=1)
        expected = _engine_recipe("exp", model, params).builder()[
            "engine"].generate(ps, max_new_tokens=8)

        mgr = PCMManager(mode=ContextMode.FULL, n_workers=1)
        try:
            rec = _engine_recipe("live", model, params)
            decoding = threading.Event()

            def task():
                eng = load_context("engine")
                decoding.set()
                return eng.generate(ps, max_new_tokens=8)

            fut = mgr.submit(task, recipe=rec)
            assert decoding.wait(120)             # engine built, decoding
            mgr.preempt_worker(next(iter(mgr.workers)))
            mgr.add_worker()
            assert fut.result(timeout=300) == expected
            assert mgr.lookup_task(fut.task_id).attempts >= 1
        finally:
            mgr.shutdown()


class TestPagedEngineUnderPCM:
    def test_midstream_snapshot_ships_live_pages_only(self, smol, tmp_path):
        """A paged engine demoted mid-stream snapshots only its live pages:
        pool occupancy shrinks with actual context (far below the full page
        pool), and the HOST_RAM -> LOCAL_DISK -> DEVICE round trip restores
        with zero builder calls, zero compiles, and a bit-identical
        continuation of the in-flight decodes."""
        cfg, model, params = smol
        from repro.serving import Request

        ps = _prompts(cfg, 2, seed=3)
        ref = _paged_engine_recipe("pref", model, params).builder()["engine"]
        for p in ps:
            ref.submit(Request(prompt=list(p), max_new_tokens=12))
        want = sorted(r.generated for r in ref.run_to_completion())

        builds = []
        pool = SnapshotPool(spill_dir=str(tmp_path))
        lib = Library("w0", snapshots=pool)
        rec = _paged_engine_recipe("paged-rt", model, params, builds)
        ctx = lib.ensure(rec)
        eng = ctx.value["engine"]
        eng.warm_executables()                    # all page/prefill buckets
        reqs = [eng.submit(Request(prompt=list(p), max_new_tokens=12))
                for p in ps]
        eng.step()                                # mid-stream: pages live
        live1 = eng._alloc.live_pages
        assert 0 < live1 < eng.num_pages
        snap = eng.snapshot()
        live_b, cap_b = snap["live_bytes"], snap["capacity_bytes"]
        compiles_before = eng.stats.compiles

        lib.demote(rec.key())                     # DEVICE -> HOST_RAM
        assert eng.offloaded
        nbytes_mid = pool.stats()["host_used_bytes"]
        assert pool.spill(rec.key())              # HOST_RAM -> LOCAL_DISK
        assert pool.tier(rec.key()) == Tier.LOCAL_DISK

        ctx2 = lib.ensure(rec)                    # LOCAL_DISK -> DEVICE
        eng2 = ctx2.value["engine"]
        assert eng2 is eng and not eng2.offloaded
        assert builds == [1]                      # ZERO builder calls
        while eng2.has_work():
            eng2.step()
        assert sorted(r.generated for r in reqs) == want
        assert eng2.stats.compiles == compiles_before   # ZERO compiles

        # all pages released at completion (the prefix cache keeps holds
        # past request finish by design — drop it so a second demote
        # isolates the live-page contribution of the mid-stream snapshot)
        eng2.drop_prefix_cache()
        assert eng2._alloc.live_pages == 0
        lib.demote(rec.key())
        nbytes_idle = pool.stats()["host_used_bytes"]
        delta = nbytes_mid - nbytes_idle
        # delta = live pages + their int32 ids + int32 refcounts; never
        # the full pool
        assert live_b <= delta <= live_b + 8 * live1
        assert nbytes_mid < nbytes_idle + cap_b
