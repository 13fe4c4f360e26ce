"""Streamed context movement: chunk plans, stripe lanes, corruption
degrade, non-blocking donor export, and pipelined cost accounting.

Covers the chunk-granular transfer machinery end to end: deterministic
ChunkPlans shared by every movement path, receiver-side StripeBuffer
verification/reassembly, sha256-failed chunks surfacing as typed errors
and degrading a single LANE (reassign) or the whole stripe (ladder
fallback, logged as ``degraded_from``), the SnapshotPool as a stripe
lane for immutable params, streamed DISK restores, and the planner's
failed-flow bookkeeping + bounded decision logs.
"""

import copy
import os
import shutil
import threading
import time

import numpy as np
import pytest

from repro.checkpoint.io import (ChunkCorruptionError, iter_entries,
                                 load_chunks, plan_chunk_rows, read_manifest,
                                 save_pytree)
from repro.checkpoint.manager import SpillStore
from repro.core import (ContextAwareScheduler, ContextMode, FetchSource,
                        PCMManager, Tier, TransferPlanner, export_context,
                        load_context, make_recipe, materialize,
                        restore_context)
from repro.core.context import (snapshot_context, stripe_export_state,
                                stripe_export_template)
from repro.core.library import Library
from repro.core.streaming import (ChunkPlan, ChunkRef, StripeBuffer,
                                  assign_lanes, chunk_digest, pool_eligible)

GB = 1 << 30


class SplitEngine:
    """Engine duck-type WITH the split template hooks: immutable params
    ship straight from device (``export_template_device``) while decode
    state is synthesized pristine (``export_template_host``) — the shape
    the streamed stripe path exercises."""

    def __init__(self, n_rows=64, n_cols=1024, seed=0):
        rng = np.random.default_rng(seed)
        self.params = {"w": rng.standard_normal((n_rows, n_cols))}
        self.rng_key = np.zeros(2, dtype=np.uint32)
        self.state = {"steps": np.zeros(4, dtype=np.int32)}
        self.exe_cache = {"megastep": object()}

    def offload_device_state(self):
        st = {"params": self.params, "_rng": self.rng_key,
              "state": self.state}
        self.params = None
        self.state = None
        self.rng_key = None
        return st

    def restore_device_state(self, host_state):
        self.params = host_state["params"]
        self.rng_key = host_state["_rng"]
        self.state = host_state["state"]

    def export_template(self):
        out = dict(self.export_template_host())
        out.update({"params": {k: np.array(v)
                               for k, v in self.params.items()},
                    "_rng": np.array(self.rng_key)})
        return out

    def export_template_device(self):
        return {"params": self.params, "_rng": self.rng_key}

    def export_template_host(self):
        return {"state": {"steps": np.zeros(4, dtype=np.int32)}}

    def clone_offloaded(self):
        clone = copy.copy(self)
        clone.exe_cache = dict(self.exe_cache)
        clone.params = None
        clone.state = None
        clone.rng_key = None
        return clone

    def checksum(self):
        return float(self.params["w"].sum())


def split_builder(seed=0):
    return {"engine": SplitEngine(seed=seed), "v": 21}


# ------------------------------------------------------------ chunk plans --
class TestChunkPlan:
    def test_large_leaf_splits_cover_and_roundtrip(self):
        arr = np.arange(2048 * 64, dtype=np.float64).reshape(2048, 64)
        tree = {"a": arr, "tiny": np.float64(3.5)}
        plan = ChunkPlan(tree, chunk_bytes=128 << 10)
        a_refs = [r for r in plan.refs if r.key == "a"]
        assert len(a_refs) > 1
        assert a_refs[0].start == 0 and a_refs[-1].stop == 2048
        for prev, nxt in zip(a_refs, a_refs[1:]):
            assert prev.stop == nxt.start          # contiguous, disjoint
        tiny = next(r for r in plan.refs if r.key == "tiny")
        assert tiny.axis < 0 and tiny.count == 1   # rides whole
        flat = ChunkPlan.flat_map(tree)
        back = np.concatenate([np.asarray(plan.extract(flat, r))
                               for r in a_refs], axis=0)
        np.testing.assert_array_equal(back, arr)
        assert plan.total_bytes == arr.nbytes + np.float64(3.5).nbytes

    def test_deterministic_across_independent_holders(self):
        t1 = {"p": np.zeros((512, 32)), "s": np.ones(3)}
        t2 = {"p": np.full((512, 32), 7.0), "s": np.zeros(3)}
        p1 = ChunkPlan(t1, chunk_bytes=32 << 10)
        p2 = ChunkPlan(t2, chunk_bytes=32 << 10)
        assert p1.refs == p2.refs                 # shapes alone decide
        assert p1.leaf_keys == p2.leaf_keys

    def test_axes_override_chunks_page_axis(self):
        pages = np.arange(4 * 256 * 32, dtype=np.float64).reshape(4, 256, 32)
        plan = ChunkPlan({"kv": {"pages": pages}}, chunk_bytes=64 << 10,
                         axes={"kv/pages": 1})
        refs = [r for r in plan.refs if r.key == "kv/pages"]
        assert len(refs) > 1 and all(r.axis == 1 for r in refs)
        flat = ChunkPlan.flat_map({"kv": {"pages": pages}})
        back = np.concatenate([np.asarray(plan.extract(flat, r))
                               for r in refs], axis=1)
        np.testing.assert_array_equal(back, pages)


class TestAssignLanes:
    def _refs(self):
        mk = lambda key, i, n: ChunkRef(key=key, index=i, count=n, axis=0,
                                        start=i, stop=i + 1)
        return ([mk("c0/params/w", i, 8) for i in range(8)]
                + [mk("c0/_rng", 0, 1), mk("c0/state/steps", 0, 1)])

    def test_pool_lane_gets_only_params(self):
        lanes = assign_lanes(self._refs(), n_donor_lanes=2, n_pool_lanes=1)
        assert len(lanes) == 3
        assert lanes[2] and all(pool_eligible(r.key) for r in lanes[2])
        non_params = [r for lane in lanes for r in lane
                      if not pool_eligible(r.key)]
        assert non_params                          # present, and only on
        for r in non_params:                       # donor lanes
            assert r in lanes[0] or r in lanes[1]
        flat = [r for lane in lanes for r in lane]
        assert sorted(r.id for r in flat) == \
            sorted(r.id for r in self._refs())     # partition, no loss

    def test_requires_a_donor_lane(self):
        with pytest.raises(ValueError):
            assign_lanes(self._refs(), n_donor_lanes=0, n_pool_lanes=2)

    def test_pool_eligibility_is_path_component_exact(self):
        assert pool_eligible("c0/params/w")
        assert not pool_eligible("c0/paramsx/w")
        assert not pool_eligible("c0/_rng")


# ---------------------------------------------------------- stripe buffer --
class TestStripeBuffer:
    def _template(self, chunk_bytes=16 << 10):
        rng = np.random.default_rng(7)
        device = {"c0": {"params": {"w": rng.standard_normal((256, 64))},
                         "_rng": np.arange(2, dtype=np.uint32)}}
        host = {"c0": {"state": {"steps": np.zeros(4, dtype=np.int32)}}}
        plan = ChunkPlan(device, chunk_bytes=chunk_bytes)
        return device, host, plan

    def test_out_of_order_delivery_reassembles_bit_identical(self):
        device, host, plan = self._template()
        assert len(plan.refs) > 4                  # actually striped
        buf = StripeBuffer()
        buf.set_template(plan, clone=None, host_halves=host,
                         nbytes=plan.total_bytes, build_seconds=1.0,
                         aot_seconds=2.0)
        flat = ChunkPlan.flat_map(device)
        order = list(plan.refs)[::-1]              # reversed = out of order
        for lane, ref in enumerate(order):
            piece = np.asarray(plan.extract(flat, ref))
            buf.deliver(ref, piece, chunk_digest(piece), lane=lane % 3)
        # duplicate redelivery is idempotent
        ref0 = plan.refs[0]
        piece0 = np.asarray(plan.extract(flat, ref0))
        n = buf.chunks_delivered
        buf.deliver(ref0, piece0, chunk_digest(piece0))
        assert buf.chunks_delivered == n
        assert buf.complete()
        out = buf.assemble()
        np.testing.assert_array_equal(out["c0"]["params"]["w"],
                                      device["c0"]["params"]["w"])
        np.testing.assert_array_equal(out["c0"]["_rng"], device["c0"]["_rng"])
        np.testing.assert_array_equal(out["c0"]["state"]["steps"],
                                      host["c0"]["state"]["steps"])

    def test_corrupt_chunk_raises_typed_error(self):
        device, host, plan = self._template()
        buf = StripeBuffer()
        buf.set_template(plan, None, host, plan.total_bytes, 0.0, 0.0)
        flat = ChunkPlan.flat_map(device)
        ref = plan.refs[0]
        piece = np.asarray(plan.extract(flat, ref))
        with pytest.raises(ChunkCorruptionError):
            buf.deliver(ref, piece, "0" * 64, lane=1)
        assert isinstance(ChunkCorruptionError("x"), ValueError)
        assert not buf.complete()                  # nothing accepted

    def test_missing_refs_tracks_undelivered_subset(self):
        device, host, plan = self._template()
        buf = StripeBuffer()
        buf.set_template(plan, None, host, plan.total_bytes, 0.0, 0.0)
        flat = ChunkPlan.flat_map(device)
        lane = assign_lanes(plan.refs, 2, 0)[0]
        assert len(lane) >= 2
        done, rest = lane[: len(lane) // 2], lane[len(lane) // 2:]
        for ref in done:
            piece = np.asarray(plan.extract(flat, ref))
            buf.deliver(ref, piece, chunk_digest(piece))
        missing = buf.missing_refs(lane)
        assert [r.id for r in missing] == [r.id for r in rest]


# --------------------------------------------- chunked export bit parity --
class TestStripeExport:
    def test_chunked_export_equals_monolithic_export(self):
        rec = make_recipe("stripe-parity", split_builder)
        ctx = materialize(rec, "donor")
        mono = export_context(ctx)
        clone, host_halves, host_nbytes = stripe_export_template(ctx)
        device = stripe_export_state(ctx)
        plan = ChunkPlan(device, chunk_bytes=16 << 10)
        assert len(plan.refs) > 4
        buf = StripeBuffer()
        buf.set_template(plan, clone, host_halves,
                         host_nbytes + plan.total_bytes, ctx.build_seconds,
                         ctx.aot_seconds)
        flat = ChunkPlan.flat_map(device)
        for ref in plan.refs:
            piece = np.asarray(plan.extract(flat, ref))
            buf.deliver(ref, piece, chunk_digest(piece))
        host_state = buf.assemble()
        # bit-for-bit the same template the monolithic path ships
        for name, half in mono.host_state.items():
            np.testing.assert_array_equal(host_state[name]["params"]["w"],
                                          half["params"]["w"])
            np.testing.assert_array_equal(host_state[name]["_rng"],
                                          half["_rng"])
            np.testing.assert_array_equal(host_state[name]["state"]["steps"],
                                          half["state"]["steps"])
        # donor untouched: export_template_device never materialized host
        assert ctx.value["engine"].params is not None
        # and the shipped clone shares the donor's AOT executables
        eng_clone = clone["engine"]
        assert eng_clone.exe_cache["megastep"] is \
            ctx.value["engine"].exe_cache["megastep"]


# ------------------------------------------------- checkpoint corruption --
class TestCheckpointCorruption:
    def _save(self, tmp_path, tree, chunk_bytes=8 << 10):
        d = os.path.join(str(tmp_path), "ckpt")
        save_pytree(tree, d, chunk_rows=plan_chunk_rows(
            tree, chunk_bytes=chunk_bytes))
        return d

    @staticmethod
    def _corrupt_npz_entry(directory, entry_name):
        """Rewrite one npz entry's payload in place and re-stamp the
        container digest — silent corruption the whole-file sha cannot
        see, exactly what the per-chunk/per-entry digests exist for."""
        import json
        from repro.checkpoint.io import _sha256_file
        npz = os.path.join(directory, "arrays.npz")
        with np.load(npz) as z:
            entries = {k: np.array(z[k]) for k in z.files}
        assert entry_name in entries, sorted(entries)
        entries[entry_name] = entries[entry_name] + 1
        os.remove(npz)
        np.savez(npz, **entries)
        man_path = os.path.join(directory, "manifest.json")
        with open(man_path) as f:
            man = json.load(f)
        man["sha256"] = _sha256_file(npz)
        with open(man_path, "w") as f:
            json.dump(man, f)
        return npz

    def test_corrupt_chunk_raises_clean_typed_error(self, tmp_path):
        big = np.arange(4096 * 8, dtype=np.float64).reshape(4096, 8)
        d = self._save(tmp_path, {"w": big})
        man = read_manifest(d)
        assert man["chunks"].get("w", {}).get("count", 0) > 1
        self._corrupt_npz_entry(d, "w#chunk00000")
        with pytest.raises(ChunkCorruptionError):
            load_chunks(d, "w")
        with pytest.raises(ChunkCorruptionError):
            list(iter_entries(d))

    def test_corrupt_unchunked_entry_caught_by_entry_digest(self, tmp_path):
        d = self._save(tmp_path, {"small": np.arange(16.0)},
                       chunk_bytes=1 << 20)
        self._corrupt_npz_entry(d, "small")
        with pytest.raises(ChunkCorruptionError):
            list(iter_entries(d))

    def test_iter_entries_streams_bit_identical_with_key_filter(
            self, tmp_path):
        rng = np.random.default_rng(3)
        tree = {"w": rng.standard_normal((2048, 8)),
                "b": rng.standard_normal(32)}
        d = self._save(tmp_path, tree)
        got = dict(iter_entries(d))
        assert sorted(got) == ["b", "w"]
        np.testing.assert_array_equal(got["w"], tree["w"])
        np.testing.assert_array_equal(got["b"], tree["b"])
        only_w = dict(iter_entries(d, keys={"w"}))
        assert sorted(only_w) == ["w"]


# ---------------------------------------------------- streamed DISK path --
class TestStreamedRestore:
    def _spilled(self, tmp_path, name, seed=0):
        rec = make_recipe(name, lambda: split_builder(seed))
        ctx = materialize(rec, "w0")
        snap = snapshot_context(ctx)
        store = SpillStore(os.path.join(str(tmp_path), name))
        snap.spill(store, chunk_bytes=16 << 10)
        assert snap.spilled
        return snap, store

    def test_streamed_equals_whole_snapshot_restore(self, tmp_path):
        snap_s, store_s = self._spilled(tmp_path, "stream-a")
        snap_w, store_w = self._spilled(tmp_path, "whole-a")
        ctx_s = restore_context(snap_s, "r0", spill_store=store_s,
                                streamed=True)
        ctx_w = restore_context(snap_w, "r1", spill_store=store_w,
                                streamed=False)
        es, ew = ctx_s.value["engine"], ctx_w.value["engine"]
        np.testing.assert_array_equal(es.params["w"], ew.params["w"])
        np.testing.assert_array_equal(es.state["steps"], ew.state["steps"])
        assert ctx_s.value["v"] == ctx_w.value["v"] == 21
        # streamed restores report per-stage timings for calibration
        assert "disk" in (ctx_s.stage_seconds or {})
        disk_bytes, disk_secs = ctx_s.stage_seconds["disk"]
        assert disk_bytes > 0 and disk_secs >= 0

    def test_streamed_restore_surfaces_spill_corruption(self, tmp_path):
        snap, store = self._spilled(tmp_path, "corrupt-a")
        d = store.path(snap.spill_key)
        man = read_manifest(d)
        key, spec = next(iter(man["chunks"].items()))
        assert spec["count"] > 1
        TestCheckpointCorruption._corrupt_npz_entry(d, f"{key}#chunk00000")
        with pytest.raises(ChunkCorruptionError):
            restore_context(snap, "r0", spill_store=store, streamed=True)


# ------------------------------------------------------- modeled storm --
class TestModeledStorm:
    """The same joiner storm through the simulator's production scheduler
    under two planners: chunk-streamed, striped transfers against one
    monolithic single-donor transfer (chunk >= payload, width 1, which the
    pipeline formula degenerates to the sum of stages)."""
    DONORS, JOINERS = 2, 4

    def _storm(self, streamed: bool):
        from repro.cluster.simulator import modeled_fetch_seconds
        from repro.core.backend import SimulatorBackend

        class FetchProbe(SimulatorBackend):
            """Records the modeled seconds each bootstrap fetch takes."""

            def __init__(self, *a, **kw):
                super().__init__(*a, **kw)
                self.fetch_seconds = {}

            def _start_fetch(self, a):
                self.fetch_seconds.setdefault(a.worker_id, []).append(
                    modeled_fetch_seconds(a, self.profiles[a.worker_id],
                                          self.cost, dict(self._stats)))
                super()._start_fetch(a)

        if streamed:
            be = FetchProbe(n_workers=self.DONORS,
                            planner=TransferPlanner(), donor_wait=True)
        else:
            be = FetchProbe(n_workers=self.DONORS,
                            planner=TransferPlanner(chunk_bytes=1 << 62),
                            donor_wait=True, stripe_width=1)
        rec = make_recipe("storm.model", lambda: None)  # paper footprints
        be.warm_up(rec)
        futs = [be.submit(lambda: None, recipe=rec, n_items=4)
                for _ in range(10 * (self.DONORS + self.JOINERS))]
        joiners = [be.add_worker() for _ in range(self.JOINERS)]
        for f in futs:
            be.wait(f, timeout=300)
        boots = {w: sum(v) for w, v in be.fetch_seconds.items()
                 if w in joiners}
        return boots, [d.source for d in be.fetch_history(rec)]

    def test_streamed_striped_bootstrap_beats_monolithic(self):
        mono, mono_src = self._storm(streamed=False)
        streamed, streamed_src = self._storm(streamed=True)
        assert len(mono) == len(streamed) == self.JOINERS
        assert set(mono_src) == set(streamed_src) == {FetchSource.PEER}
        assert sum(mono.values()) >= 1.5 * sum(streamed.values())


# -------------------------------------------------- planner flow hygiene --
class TestPlannerFailedFlows:
    NB = 10 * GB

    def test_failed_flow_freed_counted_and_never_calibrates(self):
        p = TransferPlanner(donor_fanout=1)
        plan = p.peer_plan(self.NB, {"d0"}, now=0.0)
        assert p.peer_plan(self.NB, {"d0"}, now=0.01) is None   # saturated
        p.complete(plan, now=0.02, measured_seconds=0.02, failed=True)
        st = p.stats(now=0.03)
        assert st["failed_flows"] == 1
        assert st["completed_flows"] == 0
        assert st["donors_active"] == {}            # freed immediately
        assert p.calibration()["p2p"] is None       # no EWMA pollution
        assert p.peer_plan(self.NB, {"d0"}, now=0.03) is not None

    def test_striped_plan_registers_and_frees_every_lane(self):
        p = TransferPlanner(donor_fanout=1)
        plan = p.peer_plan(self.NB, {"d0", "d1"}, now=0.0, width=2)
        assert len(plan.stripes) == 2
        assert p.donor_load("d0", now=0.01) == 1
        assert p.donor_load("d1", now=0.01) == 1
        p.complete(plan, now=0.02, measured_seconds=0.02)
        assert p.donor_load("d0", now=0.03) == 0
        assert p.donor_load("d1", now=0.03) == 0
        assert p.stats()["completed_flows"] == 1

    def test_pipeline_seconds_degenerates_correctly(self):
        p = TransferPlanner(chunk_bytes=64 << 20)
        stages = [2.0, 5.0, 1.0]
        one_chunk = p.pipeline_seconds(stages, 64 << 20)
        assert one_chunk == pytest.approx(sum(stages))   # no overlap
        many = p.pipeline_seconds(stages, 64 << 30)      # 1024 chunks
        assert many < sum(stages)
        assert many == pytest.approx(max(stages), rel=0.01)

    def test_stage_observation_feeds_pipeline_costs(self):
        p = TransferPlanner()
        before = p.d2h_seconds(1 * GB)
        p.observe_stage("d2h", 1 * GB, 10.0)        # measured: 0.1 GB/s
        assert p.calibration()["d2h"] == pytest.approx(GB / 10.0)
        assert p.d2h_seconds(1 * GB) > before       # cost model updated


class TestBoundedLogs:
    def test_fetch_log_is_a_ring_buffer(self):
        s = ContextAwareScheduler(fetch_log_limit=5)
        rec = make_recipe("ring", lambda: {"v": 1})
        for i in range(20):
            s.record_degrade(f"w{i}", rec.key(), FetchSource.BUILD,
                             float(i), degraded_from=FetchSource.PEER)
        assert len(s.fetch_log) == 5
        assert s.fetch_log[0].worker_id == "w15"    # oldest trimmed

    def test_library_fetch_sources_bounded(self):
        lib = Library("w0", fetch_source_limit=3)
        for _ in range(10):
            lib._record_source(FetchSource.BUILD)
        assert lib.fetch_sources == [FetchSource.BUILD] * 3
        assert isinstance(lib.fetch_sources, list)  # slicing call sites


# --------------------------------------------------------- live striping --
class TestLiveStreamedMovement:
    def _mgr(self, n_workers=2, **kw):
        kw.setdefault("chunk_bytes", 32 << 10)
        return PCMManager(mode=ContextMode.FULL, n_workers=n_workers,
                          donor_wait=True, **kw)

    @staticmethod
    def _recipe(name, builds):
        """Declared footprints sized to the tiny test payload: live stage
        calibration (sha256 + numpy copies over KB-scale chunks) reports
        modest bytes/s, and pricing 15GB paper-scale defaults at those
        measured rates would push PEER above the FS/BUILD rungs."""
        return make_recipe(name,
                           lambda: builds.append(1) or split_builder(),
                           artifact_bytes=48 << 20, env_bytes=16 << 20,
                           host_bytes=64 << 20, device_bytes=64 << 20)

    @staticmethod
    def _wait(cond, timeout=20.0):
        """Tasks complete on warm donors while a joiner's stripe is still
        in flight — stripe outcomes must be awaited, not assumed done."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if cond():
                return True
            time.sleep(0.05)
        return cond()

    def test_striped_storm_bit_identical_zero_builds(self):
        builds = []
        mgr = self._mgr(n_workers=2)
        try:
            rec = self._recipe("stream-storm", builds)
            mgr.warm_up(rec)
            assert len(builds) == 2
            expect = SplitEngine(seed=0).checksum()
            futs = [mgr.submit(
                lambda: load_context("engine").checksum(), recipe=rec)
                for _ in range(12)]
            for _ in range(4):
                mgr.add_worker()
            assert all(f.result(timeout=60) == expect for f in futs)
            mgr.run_until_idle(timeout=30)
            assert self._wait(lambda: mgr.fetch_history(rec)
                              and not mgr._stripes
                              and mgr.stats()["peer_installs"] ==
                              len(mgr.fetch_history(rec)))
            decisions = mgr.fetch_history(rec)
            assert len(builds) == 2                  # zero joiner builds
            assert decisions and all(d.source == FetchSource.PEER
                                     for d in decisions)
            assert all(d.degraded_from is None for d in decisions)
            st = mgr.stats()
            assert st["striping"]["stripes"] >= 1
            assert st["striping"]["chunks"] > len(decisions)  # chunked
            assert st["striping"]["degrades"] == 0
            assert st["peer_installs"] == len(decisions)
        finally:
            mgr.shutdown()

    def test_corrupt_stripe_single_donor_degrades_down_ladder(self):
        builds = []
        mgr = self._mgr(n_workers=1)
        try:
            rec = self._recipe("stream-corrupt", builds)
            mgr.warm_up(rec)
            hits = []

            def fault(stripe_id, ref, lane):
                if not hits:
                    hits.append(ref.key)
                    return True
                return False

            mgr._chunk_fault = fault
            fut = mgr.submit(lambda: load_context("engine").checksum(),
                             recipe=rec)
            mgr.add_worker()
            assert fut.result(timeout=60) == SplitEngine(seed=0).checksum()
            mgr.run_until_idle(timeout=30)
            assert self._wait(lambda: any(
                d.degraded_from is not None for d in mgr.fetch_history(rec)))
            assert hits                               # fault actually fired
            st = mgr.stats()
            assert st["striping"]["lane_failures"] >= 1
            assert st["striping"]["degrades"] >= 1
            degraded = [d for d in mgr.fetch_history(rec)
                        if d.degraded_from == FetchSource.PEER]
            assert degraded                           # logged, not silent
            assert degraded[0].source != FetchSource.PEER
            assert st["transfer"]["failed_flows"] >= 1
        finally:
            mgr.shutdown()

    def test_corrupt_lane_with_survivor_reassigns_no_degrade(self):
        builds = []
        mgr = self._mgr(n_workers=2)
        try:
            rec = self._recipe("stream-reassign", builds)
            mgr.warm_up(rec)
            hits = []

            def fault(stripe_id, ref, lane):
                if lane == 1 and not hits:
                    hits.append(ref.key)
                    return True
                return False

            mgr._chunk_fault = fault
            fut = mgr.submit(lambda: load_context("engine").checksum(),
                             recipe=rec)
            mgr.add_worker()
            assert fut.result(timeout=60) == SplitEngine(seed=0).checksum()
            mgr.run_until_idle(timeout=30)
            assert self._wait(
                lambda: mgr.stats()["peer_installs"] >= 1)
            st = mgr.stats()
            if hits:                     # stripe was 2-wide and lane 1 hit
                assert st["striping"]["lane_failures"] >= 1
            assert st["striping"]["degrades"] == 0
            assert len(builds) == 2                   # still zero rebuilds
            assert st["peer_installs"] >= 1
            assert all(d.degraded_from is None
                       for d in mgr.fetch_history(rec))
        finally:
            mgr.shutdown()

    def test_donor_preempted_mid_stripe_survivor_finishes(self):
        builds = []
        mgr = self._mgr(n_workers=2, chunk_bytes=8 << 10,
                        export_chunk_budget=1)
        try:
            gate = threading.Event()
            rec = self._recipe("stream-preempt", builds)
            mgr.warm_up(rec)
            donors = list(mgr.workers)
            # keep both donors' mailboxes busy so exports are budgeted to
            # a chunk per turn and the stripe is in flight when we preempt
            slow = [mgr.submit(lambda: gate.wait(10)) for _ in range(2)]
            fut = mgr.submit(lambda: load_context("engine").checksum(),
                             recipe=rec)
            mgr.add_worker()
            time.sleep(0.15)
            mgr.preempt_worker(donors[0])
            gate.set()
            assert fut.result(timeout=60) == SplitEngine(seed=0).checksum()
        finally:
            gate.set()
            mgr.shutdown()

    def test_pool_serves_params_as_a_stripe_lane(self):
        builds = []
        mgr = self._mgr(n_workers=2, chunk_bytes=8 << 10)
        try:
            # footprints that price striped PEER under a DISK promotion
            # (small wire payload, big host snapshot): the spilled pool
            # copy then rides as a stripe LANE instead of winning the rung
            rec = make_recipe("stream-pool",
                              lambda: builds.append(1) or split_builder(),
                              artifact_bytes=1 * GB, env_bytes=0,
                              host_bytes=8 * GB, device_bytes=1 * GB)
            mgr.warm_up(rec)
            cold = next(iter(mgr.workers))
            assert mgr.demote_context(rec, tier=Tier.LOCAL_DISK,
                                      worker_ids=[cold]) == [cold]
            mgr.preempt_worker(cold)     # nothing left to reclaim the copy
            fut = mgr.submit(lambda: load_context("engine").checksum(),
                             recipe=rec)
            mgr.add_worker()
            assert fut.result(timeout=60) == SplitEngine(seed=0).checksum()
            mgr.run_until_idle(timeout=30)
            assert self._wait(lambda: mgr.snapshots.stripe_reads > 0)
            assert len(builds) == 2
            assert mgr.snapshots.peek(rec.key()) is not None  # non-consuming
            assert mgr.stats()["snapshot_pool"]["stripe_reads"] > 0
        finally:
            mgr.shutdown()

    def test_budgeted_export_interleaves_with_serving(self):
        builds = []
        mgr = self._mgr(n_workers=1, chunk_bytes=4 << 10,
                        export_chunk_budget=1)
        try:
            rec = self._recipe("stream-budget", builds)
            mgr.warm_up(rec)
            # serving load on the donor while the export streams out
            serving = [mgr.submit(lambda i=i: i * i, recipe=rec)
                       for i in range(16)]
            fut = mgr.submit(lambda: load_context("engine").checksum(),
                             recipe=rec)
            mgr.add_worker()
            assert [f.result(timeout=60) for f in serving] == \
                [i * i for i in range(16)]
            assert fut.result(timeout=60) == SplitEngine(seed=0).checksum()
            mgr.run_until_idle(timeout=30)
            assert self._wait(
                lambda: mgr.stats()["peer_installs"] >= 1)
            st = mgr.stats()
            assert len(builds) == 1
            assert st["striping"]["chunks"] >= 8     # many budgeted turns
            assert st["peer_installs"] >= 1
        finally:
            mgr.shutdown()

    def test_streamed_disk_promotion_live_and_calibrated(self):
        builds = []
        mgr = self._mgr(n_workers=1, chunk_bytes=16 << 10)
        try:
            rec = self._recipe("stream-disk", builds)
            mgr.warm_up(rec)
            assert mgr.demote_context(rec, tier=Tier.LOCAL_DISK)
            assert Tier.DEVICE not in mgr.residency(rec).values()
            expect = SplitEngine(seed=0).checksum()
            assert mgr.submit(lambda: load_context("engine").checksum(),
                              recipe=rec).result(timeout=60) == expect
            # a second task drains the stage observations into the planner
            assert mgr.submit(lambda: 5, recipe=rec).result(timeout=60) == 5
            mgr.run_until_idle(timeout=30)
            assert len(builds) == 1                  # promotion, not build
            cal = mgr.stats()["transfer"]["measured_bytes_per_s"]
            assert cal["disk"] is not None           # streamed stages fed
        finally:
            mgr.shutdown()
