"""Multi-host PCM: wire-format snapshots, the socket transport, and real
subprocess worker nodes under the existing mailbox runtime.

Three layers, bottom up:

*  the **wire format** (``repro.core.wire``): versioned blobs whose array
   payloads ride checkpoint/io's chunked-sha256 path, with engines
   replaced by AOTRecipes so executables never cross the wire;
*  the **transport** (``repro.core.transport``): length-prefixed frames,
   per-connection IO threads, heartbeats, and the two-layer loss story
   (socket EOF instant, heartbeat monitor for wedged links) feeding the
   manager's normal preemption path;
*  **whole-node processes** (``repro.cluster.node``): spawn real worker
   processes over loopback and assert the acceptance bar — wire
   bootstrap with zero builder calls, bit-identical greedy continuation,
   striped PEER fetches across process boundaries, and kill -9 of a
   donor mid-stripe surviving via lane failover.

The cross-process vocabulary (recipes, tasks) lives in
``multihost_helpers`` — everything that crosses the socket must be
picklable by reference.
"""

import os
import signal
import socket
import struct
import time

import numpy as np
import pytest

import multihost_helpers as H
from repro.core import (ContextMode, ElasticRunner, FetchSource, PCMManager,
                        TransferPlanner)
from repro.core.context import ContextRecipe, materialize, snapshot_context
from repro.core.transport import (Connection, Router, TransportError,
                                  read_frame, write_frame)
from repro.core.wire import (WireError, decode_snapshot, decode_template,
                             decode_template_specs, encode_snapshot,
                             encode_template)
from repro.cluster.node import spawn_node_process

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


def _wait(cond, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.05)
    return cond()


# ---------------------------------------------------------------------------
# wire format
# ---------------------------------------------------------------------------
class TestWireFormat:
    def _snap(self, rows=64):
        rec = H.split_recipe("wire-rt", rows=rows)
        ctx = materialize(rec, worker_id="w0")
        return rec, snapshot_context(ctx)

    def test_snapshot_roundtrip_bit_identical(self):
        rec, snap = self._snap()
        blob = encode_snapshot(snap, chunk_bytes=32 << 10)
        assert bytes(blob[:4]) == b"PCMW"
        out = decode_snapshot(blob)
        assert out.recipe.key() == rec.key()
        assert out.nbytes == snap.nbytes
        a = snap.host_state["c0"]["params"]["w"]
        b = out.host_state["c0"]["params"]["w"]
        assert np.array_equal(np.asarray(a), np.asarray(b))
        # decode state survives with exact dtypes
        assert out.host_state["c0"]["state"]["steps"].dtype == np.int32

    def test_corrupt_payload_detected_at_chunk_granularity(self):
        from repro.checkpoint.io import ChunkCorruptionError
        _, snap = self._snap()
        blob = bytearray(encode_snapshot(snap, chunk_bytes=32 << 10))
        blob[-8] ^= 0xFF                      # flip a bit in the params
        with pytest.raises((ChunkCorruptionError, WireError)):
            decode_snapshot(bytes(blob))

    def test_bad_magic_and_truncation_rejected(self):
        _, snap = self._snap()
        blob = encode_snapshot(snap)
        with pytest.raises(WireError):
            decode_snapshot(b"NOPE" + blob[4:])
        with pytest.raises(WireError):
            decode_snapshot(blob[:len(blob) // 2])

    def test_spilled_snapshot_refuses_the_wire(self):
        _, snap = self._snap()
        snap.spilled = True
        with pytest.raises(WireError):
            encode_snapshot(snap)

    def test_template_specs_peek_matches_full_decode(self):
        """The manager's cheap forwarding peek and the receiver's full
        decode must agree on the chunk-plan inputs — that is what lets a
        remote donor's blob pass through the manager verbatim."""
        from repro.core.context import stripe_export_state
        rec = H.split_recipe("wire-tpl")
        ctx = materialize(rec, worker_id="w0")
        eng = ctx.value["engine"]
        device_tree = stripe_export_state(ctx)
        blob = encode_template(rec, eng.clone_offloaded(),
                               {"host": eng.export_template_host()},
                               device_tree, nbytes=123, build_seconds=1.5,
                               aot_seconds=0.5, chunk_bytes=32 << 10)
        specs, meta = decode_template_specs(blob)
        full = decode_template(blob)
        assert meta["nbytes"] == full["nbytes"] == 123
        assert meta["chunk_bytes"] == full["chunk_bytes"] == 32 << 10
        import jax
        flat_a = jax.tree_util.tree_leaves(specs)
        flat_b = jax.tree_util.tree_leaves(full["spec_tree"])
        assert [(s.shape, s.dtype) for s in flat_a] == \
            [(s.shape, s.dtype) for s in flat_b]
        assert full["recipe"].key() == rec.key()


# ---------------------------------------------------------------------------
# transport
# ---------------------------------------------------------------------------
class TestTransport:
    def test_frame_roundtrip(self):
        a, b = socket.socketpair()
        try:
            write_frame(a, "task", {"token": 3}, b"payload")
            kind, meta, payload = read_frame(b)
            assert (kind, meta["token"], payload) == ("task", 3, b"payload")
        finally:
            a.close()
            b.close()

    def test_garbage_length_prefix_fails_fast(self):
        a, b = socket.socketpair()
        try:
            a.sendall(struct.pack("<IQ", 1 << 30, 0))
            with pytest.raises(TransportError):
                read_frame(b)
        finally:
            a.close()
            b.close()

    def test_connection_ordering_heartbeats_and_eof(self):
        a, b = socket.socketpair()
        got, lost = [], []
        conn = Connection(b, "peer",
                          on_frame=lambda c, k, m, p: got.append((k, m["i"],
                                                                  p)),
                          on_lost=lambda c, r: lost.append(r),
                          heartbeat=0.05)
        conn.start()
        try:
            for i in range(5):
                write_frame(a, "task", {"i": i}, str(i).encode())
            assert _wait(lambda: len(got) == 5, timeout=5.0)
            assert [g[1] for g in got] == list(range(5))   # strict order
            # idle writer emits heartbeats the peer can read
            kind, _, _ = read_frame(a)
            assert kind == "hb"
            # EOF fires on_lost exactly once (the reader also sees the
            # close(), which must stay behind the once-only gate)
            a.close()
            assert _wait(lambda: lost, timeout=5.0)
            time.sleep(0.2)
            assert len(lost) == 1
        finally:
            conn.close()
            try:
                a.close()
            except OSError:
                pass

    def test_router_declares_silent_peer_lost(self):
        """Heartbeat-layer loss: a peer whose link is open but silent
        (network partition, wedged process) is declared lost after
        ``lost_after`` seconds without any inbound frame."""
        a, b = socket.socketpair()
        lost = []
        conn = Connection(b, "w",
                          on_frame=lambda c, k, m, p: None,
                          on_lost=lambda c, r: lost.append(r),
                          heartbeat=0.05)
        conn.start()
        router = Router(lost_after=0.4)
        router.register("w", conn)
        try:
            assert _wait(lambda: lost, timeout=5.0)
            assert "declared lost" in lost[0]
            assert conn.closed
            assert len(lost) == 1
        finally:
            router.close()
            conn.close()
            a.close()

    def test_heartbeat_loss_feeds_manager_preemption(self):
        """A fake node that HELLOs then goes silent must be removed from
        the pool through the SAME preemption path a reclaimed GPU takes —
        no special-case teardown."""
        mgr = PCMManager(mode=ContextMode.FULL, n_workers=1)
        s = None
        try:
            addr = mgr.listen(heartbeat=0.1, lost_after=0.6)
            s = socket.create_connection(addr, timeout=5)
            write_frame(s, "hello", {"worker_id": "ghost"})
            kind, meta, _ = read_frame(s)
            assert kind == "hello_ack"
            assert meta["mode"] == ContextMode.FULL.value
            mgr.wait_for_workers(["ghost"], timeout=10)
            assert "ghost" in mgr.workers
            # stay silent: no heartbeats, no frames -> declared lost
            assert _wait(lambda: "ghost" not in mgr.workers, timeout=10.0)
        finally:
            if s is not None:
                s.close()
            mgr.shutdown(timeout=30)


# ---------------------------------------------------------------------------
# per-transport-kind calibration (the planner satellite)
# ---------------------------------------------------------------------------
class TestTransportKindCalibration:
    def test_cold_socket_lane_prices_from_nic_defaults(self):
        """Regression: a blazing in-process memcpy history must NOT make
        the first wire transfer look free. The socket namespace prices
        from the conservative NIC default until its own observations
        arrive."""
        pl = TransferPlanner()
        nbytes = 1 << 30
        # calibrate memcpy ludicrously fast (thread handoff measures GB/ms)
        plan = pl.peer_plan(nbytes, {"a"}, now=0.0)
        assert plan is not None and plan.kind == "memcpy"
        pl.complete(plan, now=0.0, measured_seconds=1e-3)
        assert pl.calibration()["p2p:memcpy"] == pytest.approx(nbytes / 1e-3)
        # the socket namespace is untouched: still the NIC default
        assert pl.calibration()["p2p:socket"] is None
        assert pl.peer_rate_seconds(nbytes, kind="socket") == \
            pytest.approx(nbytes / pl.nic_bytes_per_s)
        got = pl.peer_seconds(nbytes, {"b"}, now=100.0,
                              kinds={"b": "socket"})
        assert got is not None
        assert got[1] == pytest.approx(nbytes / pl.nic_bytes_per_s)

    def test_socket_observations_stay_in_their_namespace(self):
        pl = TransferPlanner()
        nbytes = 64 << 20
        plan = pl.peer_plan(nbytes, {"remote"}, now=0.0,
                            kinds={"remote": "socket"})
        assert plan is not None and plan.kind == "socket"
        pl.complete(plan, now=0.0, measured_seconds=2.0)
        cal = pl.calibration()
        assert cal["p2p:socket"] == pytest.approx(nbytes / 2.0)
        assert cal["p2p:memcpy"] is None            # no contamination
        # subsequent socket pricing uses the measured wire rate
        assert pl.peer_rate_seconds(nbytes, kind="socket") == \
            pytest.approx(2.0)
        # memcpy pricing still uses its own (modeled) rate
        assert pl.peer_rate_seconds(nbytes, kind="memcpy") == \
            pytest.approx(nbytes / min(pl.p2p_bytes_per_s,
                                       pl.nic_bytes_per_s))

    def test_mixed_stripe_calibrates_as_socket(self):
        """One remote lane makes the whole stripe a wire transfer for
        calibration purposes — the slowest lane is the one that matters."""
        pl = TransferPlanner()
        plan = pl.peer_plan(64 << 20, {"local", "remote"}, now=0.0, width=2,
                            kinds={"remote": "socket"})
        assert plan is not None
        assert set(plan.stripes) == {"local", "remote"}
        assert plan.kind == "socket"


# ---------------------------------------------------------------------------
# whole-node subprocesses over loopback
# ---------------------------------------------------------------------------
def test_node_process_needs_a_cpu_environment():
    """A node child must never reach for a chip its parent (or a sibling)
    holds: without JAX_PLATFORMS=cpu the spawn is refused."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
        spawn_node_process(("127.0.0.1", 1), "w", env=env)
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
        spawn_node_process(("127.0.0.1", 1), "w",
                           env=dict(env, JAX_PLATFORMS="tpu"))


def test_node_rejects_an_unknown_profile():
    from repro.cluster.node import run
    with pytest.raises(ValueError, match="unknown --profile"):
        run(["--connect", "127.0.0.1:1", "--worker-id", "w",
             "--profile", "no-such-gpu"])


class TestNodeProcesses:
    @staticmethod
    def _spawn(addr, wid, **kw):
        return spawn_node_process(addr, wid, extra_path=(TESTS_DIR,), **kw)

    @staticmethod
    def _teardown(mgr, procs):
        mgr.shutdown(timeout=30)
        for p in procs.values():
            try:
                p.terminate()
            except Exception:
                pass
        for p in procs.values():
            try:
                p.wait(timeout=10)
            except Exception:
                pass

    def test_node_lifecycle_parity_and_wire_pool_promotion(self):
        """The full acceptance arc on ONE remote node: join via HELLO,
        warm (builds once, on the node), greedy decode bit-identical to
        an in-process engine, demote shipping the snapshot INTO the
        manager pool over the wire, then a task-time POOL promotion back
        over the wire — restored engine decodes identically with zero
        true recompiles (AOTRecipe cache hits only)."""
        recipe = H.tiny_engine_recipe()
        prompts = H.tiny_prompts(2)
        ref = H.build_tiny_engine()["engine"].generate(prompts,
                                                       max_new_tokens=6)
        mgr = PCMManager(mode=ContextMode.FULL, n_workers=0)
        procs = {}
        try:
            addr = mgr.listen()
            procs["nodeA"] = self._spawn(addr, "nodeA")
            mgr.wait_for_workers(["nodeA"], timeout=120)
            mgr.warm_up(recipe, worker_ids=["nodeA"])

            out1, st1 = mgr.submit(H.generate_task, args=(prompts,),
                                   recipe=recipe).result(timeout=300)
            assert out1 == ref                 # bit-identical over the wire
            assert st1["compiles"] > 0         # cold build truly compiled

            # demote: the snapshot crosses the wire into the MANAGER pool
            assert mgr.demote_context(recipe)
            key = recipe.key()
            assert _wait(lambda: key in mgr.snapshots.keys(), timeout=60.0)

            # next task promotes over the wire (POOL rung, no rebuild)
            out2, st2 = mgr.submit(H.generate_task, args=(prompts,),
                                   recipe=recipe).result(timeout=300)
            assert out2 == ref
            # the wire-restored shell re-lowers into AOTRecipe cache hits,
            # never a true XLA recompile — the assertable split
            assert st2["compiles"] == 0
            assert st2["aot_cache_hits"] > 0

            mir = mgr.workers["nodeA"].library
            assert mir.builder_calls == 1
            assert mir.restores == 1
            srcs = [s.name for s in mir.fetch_sources]
            assert "POOL" in srcs              # live FetchSource vocabulary
        finally:
            self._teardown(mgr, procs)

    def test_striped_peer_bootstrap_across_processes(self):
        """A cold joiner process bootstraps entirely over the socket
        transport from two remote donors: chunked, sha256-verified,
        striped — zero builder calls on the receiver, PEER in the fetch
        history, checksums bit-identical everywhere."""
        rec = H.split_recipe("mh-stripe")
        expect = H.MHSplitEngine(seed=0).checksum()
        mgr = PCMManager(mode=ContextMode.FULL, n_workers=0,
                         chunk_bytes=32 << 10)
        procs = {}
        try:
            addr = mgr.listen()
            for wid in ("nodeA", "nodeB"):
                procs[wid] = self._spawn(addr, wid)
            mgr.wait_for_workers(["nodeA", "nodeB"], timeout=120)
            mgr.warm_up(rec)

            procs["nodeC"] = self._spawn(addr, "nodeC")
            mgr.wait_for_workers(["nodeC"], timeout=120)
            futs = [mgr.submit(H.slow_checksum_task, args=(0.15,),
                               recipe=rec) for _ in range(8)]
            res = [f.result(timeout=180) for f in futs]
            assert all(r == expect for r in res), res

            mgr.run_until_idle(timeout=60)
            assert _wait(lambda: not mgr._stripes
                         and mgr.fetch_history(rec), timeout=30.0)
            hist = mgr.fetch_history(rec)
            assert all(d.source == FetchSource.PEER for d in hist), hist
            assert mgr._stripe_stats["stripes"] >= 1
            assert mgr._stripe_stats["chunks"] > 0
            mirC = mgr.workers["nodeC"].library
            assert mirC.builder_calls == 0     # never built: wire bootstrap
            assert mirC.peer_installs >= 1
            out = mgr.submit(H.checksum_task, recipe=rec).result(timeout=60)
            assert out == expect
        finally:
            self._teardown(mgr, procs)

    def test_elastic_runner_drives_node_processes(self):
        """The opportunistic-pool arc with WHOLE PROCESSES: a capacity
        rise spawns a real node, reclaim retires it through the normal
        preemption path (its context demotes over the wire into the
        manager pool, the process exits on BYE), and the next capacity
        rise bootstraps a fresh process from that pooled snapshot with
        zero rebuilds."""
        rec = H.split_recipe("mh-elastic")
        expect = H.MHSplitEngine(seed=0).checksum()
        mgr = PCMManager(mode=ContextMode.FULL, n_workers=0,
                         chunk_bytes=1 << 20)
        capacity = {"n": 1}
        runner = ElasticRunner(
            mgr, lambda t: ["gpu"] * capacity["n"], profiles={},
            spawn_remote=True, name_prefix="en",
            node_kwargs={"extra_path": (TESTS_DIR,)})
        try:
            mgr.listen()
            runner.step()
            assert len(runner.procs) == 1
            wid1 = next(iter(runner.procs))
            proc1 = runner.procs[wid1]
            mgr.wait_for_workers([wid1], timeout=120)
            out = mgr.submit(H.checksum_task,
                             recipe=rec).result(timeout=120)
            assert out == expect

            # capacity reclaimed: retire over the wire, context survives
            capacity["n"] = 0
            runner.step()
            assert _wait(lambda: wid1 not in mgr.workers, timeout=30.0)
            assert _wait(lambda: rec.key() in mgr.snapshots.keys(),
                         timeout=60.0)
            assert _wait(lambda: proc1.poll() is not None, timeout=30.0)

            # capacity returns: a FRESH process restores from the pool
            capacity["n"] = 1
            runner.step()
            wid2 = next(iter(runner.procs))
            assert wid2 != wid1
            mgr.wait_for_workers([wid2], timeout=120)
            out = mgr.submit(H.checksum_task,
                             recipe=rec).result(timeout=120)
            assert out == expect
            mir = mgr.workers[wid2].library
            assert mir.builder_calls == 0
            assert mir.restores >= 1
            assert runner.stats()["preemptions"] == 1
        finally:
            runner.stop()
            procs = dict(runner.procs)
            self._teardown(mgr, procs)

    def test_donor_kill9_mid_stripe_lane_failover(self):
        """kill -9 a donor process while its stripe lanes are in flight:
        socket EOF feeds the normal preemption path (victim leaves the
        pool), the surviving donor re-exports the undelivered refs, and
        every task still completes with the correct result."""
        rec = H.split_recipe("mh-kill", rows=4096)   # ~1024 chunks @ 32KB
        expect = H.MHSplitEngine(n_rows=4096, seed=0).checksum()
        mgr = PCMManager(mode=ContextMode.FULL, n_workers=0,
                         chunk_bytes=32 << 10)
        procs = {}
        try:
            addr = mgr.listen(heartbeat=0.2, lost_after=3.0)
            for wid in ("nodeA", "nodeB"):
                procs[wid] = self._spawn(addr, wid, heartbeat=0.2)
            mgr.wait_for_workers(["nodeA", "nodeB"], timeout=120)
            mgr.warm_up(rec)

            procs["nodeC"] = self._spawn(addr, "nodeC", heartbeat=0.2)
            mgr.wait_for_workers(["nodeC"], timeout=120)
            futs = [mgr.submit(H.slow_checksum_task, args=(0.1,),
                               recipe=rec) for _ in range(6)]

            # wait until the stripe to nodeC is mid-flight, then SIGKILL
            # one of its donors
            sid = donors = None
            deadline = time.monotonic() + 90
            while time.monotonic() < deadline:
                with mgr._lock:
                    for s, sf in mgr._stripes.items():
                        if sf.receiver_id == "nodeC" and \
                                sf.buffer.chunks_delivered:
                            sid, donors = s, list(sf.donor_ids)
                            break
                if sid is not None:
                    break
                time.sleep(0.005)
            assert sid is not None, "stripe to the joiner never started"
            victim = donors[0]
            os.kill(procs[victim].pid, signal.SIGKILL)

            res = [f.result(timeout=240) for f in futs]
            assert all(r == expect for r in res), res
            mgr.run_until_idle(timeout=60)
            assert _wait(lambda: not mgr._stripes
                         and mgr.fetch_history(rec), timeout=30.0)
            assert victim not in mgr.workers   # EOF -> preemption path
            hist = mgr.fetch_history(rec)
            assert any(d.worker_id == "nodeC" for d in hist), hist
            mirC = mgr.workers["nodeC"].library
            # the context LANDED without a builder call: surviving-lane
            # stripe completion or a ladder fallback to POOL/DISK — any
            # rung but BUILD
            assert mirC.builder_calls == 0
            assert mirC.peer_installs + mirC.restores >= 1
            out = mgr.submit(H.checksum_task, recipe=rec).result(timeout=60)
            assert out == expect
        finally:
            self._teardown(mgr, procs)
