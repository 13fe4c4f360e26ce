"""Copy-on-write page-level prefix sharing: radix cache, refcounted
allocator invariants, shared-prefill exactness, COW under preemption,
prefix-aware routing/placement."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.core import ContextMode, PCMClient, PCMManager, load_context, \
    make_recipe
from repro.core.scheduler import ContextAwareScheduler, Task
from repro.models import build_model
from repro.models.attention import _merge_rows
from repro.serving import InferenceEngine, Request, RequestState, \
    SessionRouter
from repro.serving.paged import PageAllocator, PrefixCache, pages_for


@pytest.fixture(scope="module")
def smol():
    cfg = get_reduced_config("smollm2-1.7b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def shared_prompts(cfg, n, prefix_len=18, seed=0):
    """n prompts sharing an (unaligned, for page_size 8) token prefix."""
    rng = np.random.RandomState(seed)
    prefix = list(rng.randint(8, cfg.vocab_size, size=prefix_len))
    return [prefix + list(rng.randint(8, cfg.vocab_size,
                                      size=3 + (i % 5)))
            for i in range(n)]


def paged_engine(model, params, *, sharing=True, slots=2, cache_len=64,
                 page_size=8, num_pages=None, megastep=4):
    return InferenceEngine(model, params, slots=slots, cache_len=cache_len,
                           prefill_buckets=(16,), megastep=megastep,
                           paged=True, page_size=page_size,
                           num_pages=num_pages, prefix_sharing=sharing)


# ----------------------------------------------------------- radix cache --
class TestPrefixCache:
    def test_match_walks_full_chunks_then_partial(self):
        alloc = PageAllocator(8, 4)
        c = PrefixCache(4)
        prompt = list(range(100, 110))          # 2 full chunks + 2 partial
        pages = alloc.reserve(0, pages_for(len(prompt), 4))
        assert c.insert(prompt, pages, alloc) == 3
        # same 10 tokens + new tail: full 10-token hit (capped below len)
        got = c.match(prompt + [7, 8])
        assert got == (10, pages)
        # diverges inside chunk 2: only the full chunks match
        got = c.match(prompt[:8] + [1, 2, 3])
        assert got == (8, pages[:2])
        # identical prompt: start is capped at len - 1 (one tail token
        # is always computed so admission yields a logit)
        start, ps = c.match(list(prompt))
        assert start == 9 and ps == pages
        assert c.match([1, 2, 3]) is None

    def test_partial_lcp_inside_one_page(self):
        alloc = PageAllocator(4, 8)
        c = PrefixCache(8)
        prompt = [5, 6, 7, 8, 9]                # one partial page only
        pages = alloc.reserve(0, 1)
        c.insert(prompt, pages, alloc)
        start, ps = c.match([5, 6, 7, 1, 2, 3])
        assert start == 3 and ps == pages       # LCP within the partial

    def test_evict_lru_leaf_never_live(self):
        alloc = PageAllocator(8, 2)
        c = PrefixCache(2)
        pa = alloc.reserve(0, 2)
        pb = alloc.reserve(1, 2)
        c.insert([1, 2, 3, 4], pa, alloc)
        c.insert([1, 2, 9, 9], pb, alloc)
        c.match([1, 2, 9, 9, 5])                # touch b: a becomes LRU
        alloc.release(0)
        alloc.release(1)
        # both cached; a's leaf is the LRU candidate
        assert c.evict(1, alloc) == 1
        assert c.match([1, 2, 3, 4, 5])[0] == 2    # a's leaf gone, root kept
        # pin b's leaf page as if a slot mapped it: evict must skip it
        alloc2_holds = c.pages()
        assert pb[1] in alloc2_holds
        alloc.reserve_shared(3, [pb[1]], 0)
        freed = c.evict(99, alloc)
        assert pb[1] in c.pages()               # live page survived
        alloc.release(3)
        assert c.evict(99, alloc) >= 1          # now reclaimable
        alloc.check(c.pages())

    def test_forget_page_partials_only(self):
        alloc = PageAllocator(4, 4)
        c = PrefixCache(4)
        pages = alloc.reserve(0, 2)
        c.insert([1, 2, 3, 4, 5, 6], pages, alloc)
        assert c.forget_page(pages[1], alloc)       # the partial's page
        assert not c.forget_page(pages[0], alloc)   # full chunks never
        alloc.release(0)
        alloc.check(c.pages())


# ------------------------------------------- refcount invariant property --
class TestRefcountInvariant:
    def test_random_admit_cow_close_evict(self):
        """Property: after every operation, free list + refcounted pages
        partition the pool exactly, and each refcount equals slot
        mappings + cache holds (PageAllocator.check) — under a random
        interleaving of shared admission, COW, release, and eviction."""
        rng = np.random.RandomState(7)
        P, POOL = 4, 32
        alloc = PageAllocator(POOL, P)
        cache = PrefixCache(P)
        templates = [list(rng.randint(0, 50, size=rng.randint(6, 20)))
                     for _ in range(4)]
        live = {}                                # slot -> prompt
        next_slot = 0
        for _ in range(300):
            op = rng.randint(4)
            if op == 0:                          # admit (shared when hit)
                t = templates[rng.randint(len(templates))]
                prompt = list(t) + list(rng.randint(0, 50,
                                                    size=rng.randint(1, 6)))
                n_total = pages_for(len(prompt), P)
                hit = cache.match(prompt)
                start, shared = (0, []) if hit is None else hit
                n_keep = start // P
                shared = shared[:n_keep]
                if alloc.free_pages < n_total - n_keep:
                    continue
                alloc.reserve_shared(next_slot, shared, n_total - n_keep)
                cache.insert(prompt, alloc.owned(next_slot), alloc)
                live[next_slot] = prompt
                next_slot += 1
            elif op == 1 and live:               # COW a shared column
                s = list(live)[rng.randint(len(live))]
                owned = alloc.owned(s)
                col = rng.randint(len(owned))
                if alloc.refcount(owned[col]) > 1 and alloc.free_pages:
                    alloc.cow(s, col)
            elif op == 2 and live:               # close a session
                s = list(live)[rng.randint(len(live))]
                del live[s]
                alloc.release(s)
            else:                                # memory pressure
                cache.evict(rng.randint(1, 4), alloc)
            alloc.check(cache.pages())
            assert alloc.free_pages + len(alloc.live_ids()) == POOL
        for s in list(live):
            alloc.release(s)
        cache.evict(POOL, alloc)
        alloc.check(cache.pages())
        assert alloc.free_pages == POOL


# -------------------------------------------------------- tail merge --
def _overlay(view, tail, starts):
    """Plain per-position overlay: the reference for ``_merge_rows``."""
    out = view.copy()
    L, Tb = view.shape[1], tail.shape[1]
    for b, s in enumerate(starts):
        for pos in range(s, min(s + Tb, L)):
            out[b, pos] = tail[b, pos - s].astype(view.dtype)
    return out


@pytest.mark.parametrize("view_dt,tail_dt", [
    ("bfloat16", "bfloat16"), ("float32", "float32"),
    ("bfloat16", "float32")])
@pytest.mark.parametrize("kv_heads", [32, 8])
@pytest.mark.parametrize("tb", [1, 32, 256])
def test_merge_rows_matches_plain_overlay(tb, kv_heads, view_dt, tail_dt):
    """The tail lands at each row's start and stops at L; every other
    position, the shared prefix and -0.0 included, passes through bitwise."""
    L, D = 256, 4
    # start 0, mid-page, L - Tb, and spans that run past L
    starts = [0, 100, L - tb, L - tb + 1, L - 1, L - tb // 2 - 1]
    starts = [min(max(s, 0), L - 1) for s in starts]
    rng = np.random.RandomState(tb * kv_heads)
    view = rng.randn(len(starts), L, kv_heads, D).astype(np.float32)
    view[rng.rand(*view.shape) < 0.25] = -0.0
    tail = rng.randn(len(starts), tb, kv_heads, D).astype(np.float32)
    tail[rng.rand(*tail.shape) < 0.25] = -0.0
    view_j = jnp.asarray(view, view_dt)
    tail_j = jnp.asarray(tail, tail_dt)
    got = np.asarray(jax.jit(_merge_rows)(
        view_j, tail_j, jnp.asarray(starts, jnp.int32)))
    want = _overlay(np.asarray(view_j), np.asarray(tail_j), starts)
    assert got.dtype == want.dtype
    bits = np.uint16 if got.dtype.itemsize == 2 else np.uint32
    np.testing.assert_array_equal(got.view(bits), want.view(bits))
    assert np.signbit(got[got == 0]).any()


# ------------------------------------------------------ engine exactness --
class TestSharedPrefillExactness:
    def test_sequential_sessions_bit_identical(self, smol):
        """One prefill per shared prompt: later sessions hit the cache,
        prefill only their tail, and still produce exactly the unshared
        engine's greedy tokens."""
        cfg, model, params = smol
        ps = shared_prompts(cfg, 6)
        base = paged_engine(model, params, sharing=False)
        eng = paged_engine(model, params, sharing=True)
        assert eng.prefix_fallback is None, eng.prefix_fallback
        want = base.generate(ps, max_new_tokens=12)
        got = eng.generate(ps, max_new_tokens=12)
        assert got == want
        assert eng.stats.prefix_hits >= 4
        assert eng.stats.prefix_tokens_reused >= 4 * 16
        # the 18-token prefix is unaligned for page_size 8: every hit
        # shares the boundary page and pays a copy-on-write
        assert eng.stats.cow_copies >= 1
        assert eng.stats.prefill_tokens < base.stats.prefill_tokens / 2
        s = eng.snapshot()
        assert s["prefix_cache"]["hits"] == eng.stats.prefix_hits
        assert "prefix_hits" in eng.stats.as_dict()
        eng._alloc.check(eng._prefix_cache.pages())

    def test_mixed_wave_cold_and_hit_rows(self, smol):
        """A wave mixing a cold seed with cache hits rides one shared
        executable and stays bit-identical."""
        cfg, model, params = smol
        ps = shared_prompts(cfg, 5, seed=3)
        base = paged_engine(model, params, sharing=False, slots=4)
        want = base.generate(ps, max_new_tokens=10)
        eng = paged_engine(model, params, sharing=True, slots=4)
        # seed the cache, then submit the rest at once: the next wave
        # holds up to 4 hitting rows admitted together
        first = eng.submit(Request(prompt=list(ps[0]), max_new_tokens=10))
        eng.run_to_completion()
        rest = [eng.submit(Request(prompt=list(p), max_new_tokens=10))
                for p in ps[1:]]
        eng.run_to_completion()
        assert [first.generated] + [r.generated for r in rest] == want
        assert eng.stats.prefix_hits == 4

    def test_sharing_admits_more_sessions_at_fixed_pool(self, smol):
        """At the same page pool, hitters reserve only their unshared
        pages, so the sharing engine runs >= 1.5x the concurrent sessions
        of the unshared one, with the same greedy tokens."""
        cfg, model, params = smol
        ps = shared_prompts(cfg, 6, prefix_len=40, seed=4)
        peak, outs = {}, {}
        for sharing in (False, True):
            eng = paged_engine(model, params, sharing=sharing, slots=6,
                               num_pages=14)
            reqs = [eng.submit(Request(prompt=list(p), max_new_tokens=6))
                    for p in ps]
            peak[sharing] = 0
            while eng.has_work():
                eng.step()
                peak[sharing] = max(peak[sharing], len(eng.active))
            outs[sharing] = [r.generated for r in reqs]
        assert outs[True] == outs[False]
        assert peak[False] >= 1
        assert peak[True] >= 1.5 * peak[False], peak

    def test_zero_warm_compiles(self, smol):
        cfg, model, params = smol
        ps = shared_prompts(cfg, 4, seed=5)
        eng = paged_engine(model, params, sharing=True)
        eng.warm_executables()
        warm = eng.stats.compiles
        eng.generate(ps, max_new_tokens=9)
        assert eng.stats.compiles == warm
        assert eng.stats.prefix_hits >= 2

    def test_offload_restore_carries_sharing(self, smol):
        """Mid-stream offload of a sharing engine serializes each shared
        page ONCE plus its refcount; restore resumes bit-identically and
        the prefix cache keeps serving hits."""
        cfg, model, params = smol
        ps = shared_prompts(cfg, 4, seed=8)
        ref = paged_engine(model, params, sharing=True)
        want = ref.generate(ps, max_new_tokens=12)

        eng = paged_engine(model, params, sharing=True)
        reqs = [eng.submit(Request(prompt=list(p), max_new_tokens=12))
                for p in ps[:2]]
        eng.step()                              # shared pages live
        host = eng.offload_device_state()
        live = host["_paged_live_ids"]
        refs = host["_paged_refcounts"]
        assert len(set(int(p) for p in live)) == len(live)
        assert any(int(r) > 1 for r in refs)    # sharing visible on host
        eng.restore_device_state(host)
        while eng.has_work():
            eng.step()
        later = [eng.submit(Request(prompt=list(p), max_new_tokens=12))
                 for p in ps[2:]]
        eng.run_to_completion()
        assert ([r.generated for r in reqs]
                + [r.generated for r in later]) == want
        assert eng.stats.prefix_hits >= 2
        eng._alloc.check(eng._prefix_cache.pages())


# ----------------------------------------------- reservation-leak regress --
class TestReservationLeak:
    def test_cancel_releases_pages_and_pool_recovers(self, smol):
        """Regression: shedding/cancelling requests — queued AND active —
        returns every reserved page; the pool can be driven to exhaustion
        and recovers to fully free."""
        cfg, model, params = smol
        # 10 pages of 8 tokens: each ~22-token + 12-new request needs 5
        eng = paged_engine(model, params, sharing=False, slots=2,
                           num_pages=10)
        ps = shared_prompts(cfg, 4, seed=11)
        reqs = [eng.submit(Request(prompt=list(p), max_new_tokens=12))
                for p in ps]
        eng.step()                               # 2 active, 2 queued
        assert len(eng.active) == 2 and len(eng.queue) == 2
        assert eng._alloc.free_pages == 0        # pool exhausted
        queued = next(iter(eng.queue))
        assert eng.cancel(queued)
        assert queued.state is RequestState.CANCELLED
        active_req = next(iter(eng.active.values()))
        pages_held = eng._alloc.live_pages
        assert eng.cancel(active_req)
        assert eng._alloc.live_pages < pages_held
        eng.run_to_completion()
        assert eng._alloc.free_pages == 10       # no leaked reservations
        assert eng._alloc.live_pages == 0
        # pool is reusable after the churn
        out = eng.generate([ps[0]], max_new_tokens=12)
        assert len(out[0]) >= 1
        assert eng._alloc.free_pages == 10

    def test_cancel_with_sharing_keeps_cache_consistent(self, smol):
        cfg, model, params = smol
        eng = paged_engine(model, params, sharing=True, slots=2,
                           num_pages=16)
        ps = shared_prompts(cfg, 3, seed=13)
        eng.generate([ps[0]], max_new_tokens=8)      # seed the cache
        r = eng.submit(Request(prompt=list(ps[1]), max_new_tokens=8))
        eng.step()
        assert eng.cancel(r)                         # mid-flight hit
        eng._alloc.check(eng._prefix_cache.pages())
        assert eng.drop_prefix_cache() > 0
        eng._alloc.check(eng._prefix_cache.pages())
        assert eng._alloc.free_pages == 16
        # identical output after the teardown path
        base = paged_engine(model, params, sharing=False)
        assert eng.generate([ps[2]], max_new_tokens=8) == \
            base.generate([ps[2]], max_new_tokens=8)


# ----------------------------------------------- session-close withdrawal --
class TestCancelSession:
    def test_withdraws_unclaimed_turns_only(self):
        """Closing a session with ``cancel_pending=True`` pulls its
        admitted-but-unclaimed turns out of every queue (no leaked
        admission depth); other sessions' turns stay claimable."""
        from repro.serving import AdmissionController, SLOClass, \
            TokenStream, Turn

        def turn(sid, slo=SLOClass.BATCH):
            return Turn(session_id=sid, tenant="t", slo=slo, ctx_key="c",
                        lane=0, prompt=[2] * 4, max_new_tokens=4,
                        stream=TokenStream(0))
        ac = AdmissionController()
        for t in (turn("s1"), turn("s1", SLOClass.INTERACTIVE),
                  turn("s2")):
            ac.admit(t, now=0.0)
        claimed = ac.claim(("c", 0), now=0.0)     # s1's interactive turn
        assert claimed.session_id == "s1" and claimed.claimed
        gone = ac.cancel_session("s1")
        assert [t.session_id for t in gone] == ["s1"]
        assert not any(t.claimed for t in gone)   # in-flight untouched
        nxt = ac.claim(("c", 0), now=0.0)
        assert nxt.session_id == "s2"             # others unaffected
        assert ac.claim(("c", 0), now=0.0) is None


# ------------------------------------------------- routing and placement --
class TestPrefixRouting:
    def test_lane_for_colocates_template_mates(self):
        r = SessionRouter(None, lanes=8)
        lanes = {r.lane_for(f"session-{i}", prefix_key="tmpl-A")
                 for i in range(20)}
        assert len(lanes) == 1                   # all template-mates
        free = {r.lane_for(f"session-{i}") for i in range(40)}
        assert len(free) > 1                     # undeclared still spread

    def test_scheduler_prefers_prefix_holding_worker(self):
        rec = make_recipe("pfx.ctx", lambda: {"v": 1})
        s = ContextAwareScheduler(mode=ContextMode.FULL)
        s.on_worker_join("w0", 0.0)
        s.on_worker_join("w1", 0.0)
        from repro.core.store import Tier
        for w in ("w0", "w1"):                   # both warm
            s.workers[w].store.admit_recipe(rec, Tier.DEVICE)
        # w1 holds the task's shared prompt prefix
        s.prefix_hit = lambda task, worker_id: worker_id == "w1"
        acts = s.submit(Task(task_id="t0", recipe=rec, n_items=4), 1.0)
        start = next(a for a in acts if a.kind == "start")
        assert start.worker_id == "w1" and start.warm
        # without the oracle, compute rank decides (w0 on id tie-break)
        s2 = ContextAwareScheduler(mode=ContextMode.FULL)
        s2.on_worker_join("w0", 0.0)
        s2.on_worker_join("w1", 0.0)
        for w in ("w0", "w1"):
            s2.workers[w].store.admit_recipe(rec, Tier.DEVICE)
        acts = s2.submit(Task(task_id="t0", recipe=rec, n_items=4), 1.0)
        start = next(a for a in acts if a.kind == "start")
        assert start.worker_id == "w0"


# -------------------------------------------------- COW under preemption --
def _sharing_recipe(model, params, builds, name="pfx.engine"):
    def build():
        builds.append(1)
        return {"engine": paged_engine(model, params, sharing=True,
                                       num_pages=16)}
    return make_recipe(name, build)


class TestCowUnderPreemption:
    def test_shared_pages_survive_preemption(self, smol):
        """Sessions sharing a template keep streaming across a worker
        preemption: the context recovers through POOL/DISK (zero
        rebuilds), shared pages and their refcounts ride the snapshot,
        and the continuation is bit-identical to an undisturbed engine."""
        cfg, model, params = smol
        ps = shared_prompts(cfg, 3, seed=21)
        ref = paged_engine(model, params, sharing=True,
                           num_pages=16).generate(ps, max_new_tokens=24)
        builds = []
        mgr = PCMManager(mode=ContextMode.FULL, n_workers=1)
        try:
            client = PCMClient(backend=mgr)
            rec = _sharing_recipe(model, params, builds)
            ctx = client.context(rec)
            ctx.warm_up()
            assert len(builds) == 1
            sess = client.session(ctx, tenant="tmpl",
                                  prefix_key="fact-verify-v1")
            assert sess.prefix_key == "fact-verify-v1"
            # seed the template's pages, then stream the two hitters and
            # yank the device while their tokens are flowing
            streams = [sess.submit(list(ps[0]), max_new_tokens=24)]
            assert streams[0].result(timeout=120) == ref[0]
            streams += [sess.submit(list(p), max_new_tokens=24)
                        for p in ps[1:]]
            it = iter(streams[1])
            assert next(it) == ref[1][0]         # mid-stream now
            victim = next(iter(mgr.workers))
            mgr.preempt_worker(victim)
            deadline = time.monotonic() + 60
            while (mgr.snapshots.tier(rec.key()) is None
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            assert mgr.snapshots.tier(rec.key()) is not None
            mgr.add_worker()
            outs = [s.result(timeout=120) for s in streams]
            assert outs == ref                   # bit-identical continuation
            assert len(builds) == 1              # restore, never rebuild
            from repro.core import FetchSource
            mgr.run_until_idle(timeout=60)
            assert any(d.source in (FetchSource.POOL, FetchSource.DISK)
                       for d in mgr.fetch_history(rec))
            hits, cows = client.submit(
                lambda: (load_context("engine").stats.prefix_hits,
                         load_context("engine").stats.cow_copies),
                context=ctx).result(timeout=120)
            assert hits >= 2 and cows >= 1
            fd = client.frontdoor().stats()
            assert fd["prefix"]["hits"] >= 2
            assert fd["prefix"]["tokens_reused"] >= 2 * 16
        finally:
            mgr.shutdown()


# ------------------------------------------------- page-granular spill ----
class TestPageGranularSpill:
    def test_paged_snapshot_spills_in_page_chunks(self, smol, tmp_path):
        """HOST_RAM -> LOCAL_DISK of a paged engine context streams the
        gathered cache leaves through checkpoint/io in page-aligned
        chunks (per-chunk sha256), and the round trip stays exact."""
        import glob
        import json
        import os

        from repro.core import Library, SnapshotPool
        cfg, model, params = smol
        ps = shared_prompts(cfg, 2, seed=30)
        pool = SnapshotPool(spill_dir=str(tmp_path))
        lib = Library("w0", snapshots=pool)
        rec = _sharing_recipe(model, params, [], name="pfx.spill")
        ctx = lib.ensure(rec)
        eng = ctx.value["engine"]
        eng.warm_executables()
        reqs = [eng.submit(Request(prompt=list(p), max_new_tokens=12))
                for p in ps]
        eng.step()
        want_live = len(eng._alloc.live_ids())
        lib.demote(rec.key())                    # DEVICE -> HOST_RAM
        assert pool.spill(rec.key())             # HOST_RAM -> LOCAL_DISK
        manifests = glob.glob(str(tmp_path) + "/**/manifest.json",
                              recursive=True)
        assert manifests
        chunked = {}
        for m in manifests:
            with open(m) as f:
                chunked.update(json.load(f).get("chunks", {}))
        assert chunked                           # cache leaves ARE chunked
        for key, spec in chunked.items():
            assert "/cache" in key
            assert spec["count"] == -(-want_live // spec["rows"])
            assert len(spec["sha256"]) == spec["count"]
        # chunks split the PAGE axis: a partial read returns whole pages
        from repro.checkpoint import load_chunks
        ckdir = os.path.dirname(manifests[0])
        key = sorted(chunked)[0]
        parts, spec = load_chunks(ckdir, key, indices=[spec["count"] - 1])
        tail_pages = want_live - (spec["count"] - 1) * spec["rows"]
        assert parts[0].shape[spec["axis"]] == tail_pages
        ctx2 = lib.ensure(rec)                   # LOCAL_DISK -> DEVICE
        assert ctx2.value["engine"] is eng
        while eng.has_work():
            eng.step()
        base = paged_engine(model, params, sharing=True, num_pages=16)
        assert [r.generated for r in reqs] == \
            base.generate(ps, max_new_tokens=12)
