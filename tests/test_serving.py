"""Continuous-batching inference engine tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.models import build_model
from repro.serving import InferenceEngine, Request
from repro.serving.kvcache import batch_axes, gather_slots, merge_slots


@pytest.fixture(scope="module")
def smol():
    cfg = get_reduced_config("smollm2-1.7b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def prompts(cfg, n, seed=0):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(8, cfg.vocab_size,
                             size=rng.randint(3, 14))) for _ in range(n)]


def test_generate_and_determinism(smol):
    cfg, model, params = smol
    ps = prompts(cfg, 9)
    e1 = InferenceEngine(model, params, slots=4, cache_len=64,
                         prefill_buckets=(16, 32))
    o1 = e1.generate(ps, max_new_tokens=6)
    e2 = InferenceEngine(model, params, slots=4, cache_len=64,
                         prefill_buckets=(16, 32))
    o2 = e2.generate(ps, max_new_tokens=6)
    assert o1 == o2
    assert len(o1) == 9 and all(1 <= len(o) <= 6 for o in o1)


def test_batching_invariance(smol):
    """Result of a request must not depend on what shares its batch."""
    cfg, model, params = smol
    ps = prompts(cfg, 6, seed=3)
    multi = InferenceEngine(model, params, slots=3, cache_len=64,
                            prefill_buckets=(16,)).generate(
        ps, max_new_tokens=5)
    solo = [InferenceEngine(model, params, slots=1, cache_len=64,
                            prefill_buckets=(16,)).generate(
        [p], max_new_tokens=5)[0] for p in ps]
    assert multi == solo


def test_slot_reuse_and_stats(smol):
    cfg, model, params = smol
    eng = InferenceEngine(model, params, slots=2, cache_len=64,
                          prefill_buckets=(16,))
    outs = eng.generate(prompts(cfg, 7), max_new_tokens=3)
    assert len(outs) == 7
    st = eng.snapshot()
    assert st["stats"]["completed"] == 7
    assert st["free_slots"] == 2 and st["active"] == 0


def test_prompt_too_long_rejected(smol):
    cfg, model, params = smol
    eng = InferenceEngine(model, params, slots=1, cache_len=16)
    with pytest.raises(ValueError):
        eng.submit(Request(prompt=list(range(99))))


def test_cache_slot_merge_gather(smol):
    cfg, model, params = smol
    axes = batch_axes(model.init_cache, 32, jnp.float32)
    big = model.init_cache(4, 32, jnp.float32)
    small = jax.tree_util.tree_map(
        lambda a: jnp.ones_like(a),
        model.init_cache(2, 32, jnp.float32))
    merged = merge_slots(big, small, jnp.array([1, 3]), axes)
    back = gather_slots(merged, jnp.array([1, 3]), axes)
    for leaf in jax.tree_util.tree_leaves(back):
        assert float(jnp.min(leaf)) == 1.0
    untouched = gather_slots(merged, jnp.array([0, 2]), axes)
    for leaf in jax.tree_util.tree_leaves(untouched):
        assert float(jnp.max(jnp.abs(leaf))) == 0.0


def _generate_with_stops(model, params, ps, stop_tokens, K,
                         max_new_tokens=12):
    eng = InferenceEngine(model, params, slots=4, cache_len=64,
                          prefill_buckets=(16, 32), megastep=K)
    reqs = [eng.submit(Request(prompt=list(p), max_new_tokens=max_new_tokens,
                               stop_tokens=stop_tokens)) for p in ps]
    eng.run_to_completion()
    return [r.generated for r in reqs], eng


def test_megastep_parity_greedy(smol):
    """Greedy outputs must be bit-identical for K in {1, 8, 32}, including
    mid-megastep stop-token exits on mixed-length prompts."""
    cfg, model, params = smol
    ps = prompts(cfg, 9, seed=7)
    base, _ = _generate_with_stops(model, params, ps, (1,), 1)
    # force real mid-stream stops: stop on a token the model actually emits
    stop = next(t for out in base for t in out[1:])
    outs = {}
    for K in (1, 8, 32):
        outs[K], eng = _generate_with_stops(model, params, ps, (1, stop), K)
        assert eng.stats.decode_tokens == sum(
            len(o) - 1 for o in outs[K])    # derived block accounting
    assert outs[1] == outs[8] == outs[32]
    assert any(o[-1] == stop and len(o) < 12 for o in outs[1]), \
        "stop token never fired — test is vacuous"


def test_megastep_fuses_many_tokens_per_dispatch(smol):
    """A K=32 megastep must amortize its dispatch over many decode tokens:
    the device loop, not the host, carries the decode."""
    cfg, model, params = smol
    eng = InferenceEngine(model, params, slots=4, cache_len=64,
                          prefill_buckets=(16,), megastep=32)
    eng.generate(prompts(cfg, 8, seed=41), max_new_tokens=32)
    st = eng.stats
    assert st.megasteps > 0
    assert st.decode_tokens / st.megasteps >= 8, \
        (st.decode_tokens, st.megasteps)


def test_masked_slots_cache_unchanged(smol):
    """Free slots' cache rows must be bit-for-bit unchanged by megasteps."""
    cfg, model, params = smol
    eng = InferenceEngine(model, params, slots=4, cache_len=64,
                          prefill_buckets=(16,), megastep=8)
    # poison the free slots' rows so "unchanged" is distinguishable from
    # "zeroed"
    marker = jax.tree_util.tree_map(lambda a: jnp.ones_like(a) * 3.25,
                                    model.init_cache(2, 64, jnp.float32))
    eng.cache = merge_slots(eng.cache, marker, jnp.array([2, 3]), eng._axes)
    ps = prompts(cfg, 2, seed=11)
    eng.generate(ps, max_new_tokens=6)          # occupies slots 0 and 1
    kept = gather_slots(eng.cache, jnp.array([2, 3]), eng._axes)
    for leaf in jax.tree_util.tree_leaves(kept):
        assert float(jnp.min(leaf)) == 3.25 and float(jnp.max(leaf)) == 3.25


def test_long_prompt_not_truncated(smol):
    """Prompts longer than the largest configured bucket must prefill whole
    (buckets auto-extend to cache_len) — never silently truncate."""
    cfg, model, params = smol
    rng = np.random.RandomState(2)
    long_p = list(rng.randint(8, cfg.vocab_size, size=40))
    small = InferenceEngine(model, params, slots=1, cache_len=64,
                            prefill_buckets=(16,))
    assert small.prefill_buckets == (16, 64)
    big = InferenceEngine(model, params, slots=1, cache_len=64,
                          prefill_buckets=(64,))
    assert (small.generate([long_p], max_new_tokens=4) ==
            big.generate([long_p], max_new_tokens=4))
    from repro.serving.engine import _bucket
    with pytest.raises(ValueError):
        _bucket(99, (16, 64))


def test_engine_under_pcm_zero_compiles(smol):
    """Materializing an engine inside a PCM context AOT-compiles its
    executables; tasks on the warm context perform zero compiles."""
    from repro.core import Library, load_context, make_recipe
    cfg, model, params = smol

    def build():
        eng = InferenceEngine(model, params, slots=2, cache_len=32,
                              prefill_buckets=(16,), megastep=8)
        return {"engine": eng}

    def task(ps):
        return load_context("engine").generate(ps, max_new_tokens=4)

    recipe = make_recipe("warm.engine", build)
    lib = Library("w0")
    ps = prompts(cfg, 3, seed=13)
    ctx = lib.ensure(recipe)                # materialize: AOT warm happens
    eng = ctx.value["engine"]
    assert ctx.aot_seconds > 0 and lib.aot_seconds_total > 0
    warm_compiles = eng.stats.compiles
    assert warm_compiles > 0
    lib.invoke(task, (ps,), recipe=recipe, task_id="t1")
    assert eng.stats.compiles == warm_compiles, \
        "first task on a warm context must not compile"
    lib.invoke(task, (ps,), recipe=recipe, task_id="t2")
    assert eng.stats.compiles == warm_compiles, \
        "second task on a warm context must not compile"


def test_megastep_prefix_buckets_parity(smol):
    """Length-bounded decode (bucketed cache prefix) must not change
    outputs vs full-cache decode."""
    cfg, model, params = smol
    ps = prompts(cfg, 6, seed=17)
    bucketed = InferenceEngine(model, params, slots=3, cache_len=256,
                               prefill_buckets=(16,), megastep=8)
    assert len(bucketed.decode_buckets) > 1
    full = InferenceEngine(model, params, slots=3, cache_len=256,
                           prefill_buckets=(16,), megastep=8,
                           decode_buckets=(256,))
    assert (bucketed.generate(ps, max_new_tokens=8) ==
            full.generate(ps, max_new_tokens=8))
    assert ("megastep", 8, 64, True) in bucketed._exe or \
           ("megastep", 8, 64, False) in bucketed._exe


def test_midstream_admission_greedy_parity(smol):
    """Admitting prefills into a live wave must not change any request's
    greedy output: each equals what its prompt generates alone on a fresh
    engine. Batching invariance extended to mid-stream admission."""
    cfg, model, params = smol
    ps = prompts(cfg, 7, seed=23)
    kw = dict(slots=2, cache_len=64, prefill_buckets=(16,), megastep=4)
    eng = InferenceEngine(model, params, **kw)
    eng.warm_executables()
    warm = eng.stats.compiles
    # two-phase arrival: the second batch lands while the first is
    # mid-decode, so it is admitted into a live wave
    reqs = [eng.submit(Request(prompt=list(p), max_new_tokens=6))
            for p in ps[:3]]
    eng.step()
    reqs += [eng.submit(Request(prompt=list(p), max_new_tokens=6))
             for p in ps[3:]]
    eng.run_to_completion()
    assert eng.stats.compiles == warm
    fresh = InferenceEngine(model, params, **kw)
    alone = [fresh.generate([p], max_new_tokens=6)[0] for p in ps]
    assert [r.generated for r in reqs] == alone


def test_continuous_admits_on_slot_free(smol):
    """A queued prefill must be admitted the megastep after a slot frees —
    not after the whole wave finishes."""
    cfg, model, params = smol
    ps = prompts(cfg, 3, seed=29)
    eng = InferenceEngine(model, params, slots=2, cache_len=64,
                          prefill_buckets=(16,), megastep=2)
    eng.submit(Request(prompt=list(ps[0]), max_new_tokens=2))   # short
    eng.submit(Request(prompt=list(ps[1]), max_new_tokens=16))  # long
    eng.submit(Request(prompt=list(ps[2]), max_new_tokens=4))   # queued
    overlapped = False
    while eng.has_work():
        eng.step()
        snap = eng.snapshot()
        if snap["queued"] == 0 and snap["active"] == 2:
            overlapped = True       # 3rd admitted while long one runs
    assert overlapped, \
        "continuous admission never overlapped the queued request"


def test_streaming_token_callbacks(smol):
    """on_token must fire once per generated token, in order, with
    contiguous indices, and the callback sequence must equal generated."""
    cfg, model, params = smol
    eng = InferenceEngine(model, params, slots=2, cache_len=64,
                          prefill_buckets=(16,), megastep=4)
    seen = {}
    reqs = []
    for p in prompts(cfg, 5, seed=31):
        r = Request(prompt=list(p), max_new_tokens=7,
                    on_token=lambda req, tok, i: seen.setdefault(
                        id(req), []).append((i, tok)))
        reqs.append(eng.submit(r))
    eng.run_to_completion()
    for r in reqs:
        pairs = seen[id(r)]
        assert [i for i, _ in pairs] == list(range(len(r.generated)))
        assert [t for _, t in pairs] == r.generated


def test_streaming_callback_error_does_not_break_engine(smol):
    cfg, model, params = smol
    eng = InferenceEngine(model, params, slots=1, cache_len=64,
                          prefill_buckets=(16,))
    ps = prompts(cfg, 2, seed=37)

    def boom(req, tok, i):
        raise RuntimeError("stream consumer crashed")

    r1 = eng.submit(Request(prompt=list(ps[0]), max_new_tokens=4,
                            on_token=boom))
    r2 = eng.submit(Request(prompt=list(ps[1]), max_new_tokens=4))
    eng.run_to_completion()
    assert len(r1.generated) >= 1 and len(r2.generated) >= 1


def test_priority_jumps_admission_queue(smol):
    """priority>0 (interactive) requests are admitted ahead of queued
    batch requests but never preempt running decodes."""
    cfg, model, params = smol
    eng = InferenceEngine(model, params, slots=1, cache_len=64,
                          prefill_buckets=(16,), megastep=2)
    ps = prompts(cfg, 4, seed=41)
    running = eng.submit(Request(prompt=list(ps[0]), max_new_tokens=6))
    eng.step()                                  # occupy the only slot
    batch1 = eng.submit(Request(prompt=list(ps[1]), max_new_tokens=2))
    batch2 = eng.submit(Request(prompt=list(ps[2]), max_new_tokens=2))
    inter = eng.submit(Request(prompt=list(ps[3]), max_new_tokens=2,
                               priority=1))
    assert list(eng.queue) == [inter, batch1, batch2]
    eng.run_to_completion()
    # the running decode was never preempted, and the interactive request
    # got its first token before either batch request
    assert running.first_token_time < inter.first_token_time
    assert inter.first_token_time < batch1.first_token_time
    assert inter.first_token_time < batch2.first_token_time


def test_request_metric_split(smol):
    """tokens_per_second is decode-only (first_token-relative);
    end_to_end_tokens_per_second includes queueing+prefill; ttft_seconds
    is the gap between them."""
    from repro.serving.request import Request as Req
    r = Req(prompt=[1, 2, 3])
    r.arrival_time = 100.0
    r.first_token_time = 102.0
    r.finished_time = 104.0
    r.generated = [5, 6, 7, 8]
    assert r.ttft_seconds == pytest.approx(2.0)
    assert r.decode_seconds == pytest.approx(2.0)
    # 3 decode steps after the first token over 2s — prefill excluded
    assert r.tokens_per_second == pytest.approx(3 / 2.0)
    # all 4 tokens over the 4s the client actually waited
    assert r.end_to_end_tokens_per_second == pytest.approx(4 / 4.0)


def test_temperature_sampling_differs(smol):
    cfg, model, params = smol
    ps = prompts(cfg, 2, seed=5)
    eng = InferenceEngine(model, params, slots=2, cache_len=64,
                          prefill_buckets=(16,), rng_seed=0)
    hot = eng.generate(ps, max_new_tokens=8, temperature=5.0)
    eng2 = InferenceEngine(model, params, slots=2, cache_len=64,
                           prefill_buckets=(16,), rng_seed=0)
    cold = eng2.generate(ps, max_new_tokens=8, temperature=0.0)
    assert hot != cold


# --------------------------------------------------------------- paged KV --
@pytest.fixture(scope="module")
def deepseek():
    cfg = get_reduced_config("deepseek-v2-lite-16b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    return cfg, model, params


def _paged_engine(model, params, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("cache_len", 64)
    kw.setdefault("prefill_buckets", (16, 32))
    kw.setdefault("megastep", 4)
    kw.setdefault("paged", True)
    kw.setdefault("page_size", 8)
    # These tests measure UNSHARED paged semantics (every page returns to
    # the free list at completion, only slot-owned pages ever written);
    # the prefix cache deliberately retains pages past request finish, so
    # sharing is off here — prefix-sharing coverage lives in test_prefix.py.
    kw.setdefault("prefix_sharing", False)
    return InferenceEngine(model, params, **kw)


def test_paged_vs_slot_greedy_parity(smol):
    """The paged cache must generate bit-identical greedy outputs to the
    contiguous slot cache — including mid-stream stop-token exits — across
    megastep sizes."""
    cfg, model, params = smol
    ps = prompts(cfg, 9, seed=7)
    base, _ = _generate_with_stops(model, params, ps, (1,), 1)
    stop = next(t for out in base for t in out[1:])
    for K in (1, 8):
        slot_eng = InferenceEngine(model, params, slots=4, cache_len=64,
                                   prefill_buckets=(16, 32), megastep=K)
        pg = _paged_engine(model, params, megastep=K)
        assert pg._paged and pg.paged_fallback is None
        rs = [slot_eng.submit(Request(prompt=list(p), max_new_tokens=12,
                                      stop_tokens=(1, stop))) for p in ps]
        rp = [pg.submit(Request(prompt=list(p), max_new_tokens=12,
                                stop_tokens=(1, stop))) for p in ps]
        slot_eng.run_to_completion()
        pg.run_to_completion()
        assert [r.generated for r in rs] == [r.generated for r in rp]
        assert any(r.generated[-1] == stop and len(r.generated) < 12
                   for r in rp), "stop never fired — test is vacuous"
        assert pg.stats.decode_path == "paged"
        # slot reuse after free: 9 requests through 4 slots, and every
        # page returned to the pool at the end
        assert pg._alloc.free_pages == pg.num_pages
        assert pg._alloc.live_pages == 0


def test_paged_mla_greedy_parity(deepseek):
    """DeepSeek-style MLA runs compressed end-to-end on pages: the paged
    latent cache must match the contiguous latent cache bit-for-bit."""
    cfg, model, params = deepseek
    assert model.decode_paged is not None
    ps = prompts(cfg, 5, seed=3)
    slot_eng = InferenceEngine(model, params, slots=3, cache_len=32,
                               prefill_buckets=(16,), megastep=4)
    pg = _paged_engine(model, params, slots=3, cache_len=32,
                       prefill_buckets=(16,))
    assert pg._paged, pg.paged_fallback
    assert (slot_eng.generate(ps, max_new_tokens=5) ==
            pg.generate(ps, max_new_tokens=5))


def test_paged_unsupported_family_falls_back(smol):
    """paged=True on a non-pageable family (xLSTM matrix memories) keeps
    the slot cache silently, records why, and still generates correctly."""
    cfg = get_reduced_config("xlstm-350m")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ps = prompts(cfg, 3, seed=5)
    fb = InferenceEngine(model, params, slots=2, cache_len=32,
                         prefill_buckets=(16,), paged=True)
    assert not fb._paged and fb.paged_fallback is not None
    assert fb.snapshot()["decode_path"] != "paged"
    ref = InferenceEngine(model, params, slots=2, cache_len=32,
                          prefill_buckets=(16,))
    assert (fb.generate(ps, max_new_tokens=3) ==
            ref.generate(ps, max_new_tokens=3))


def test_paged_free_pages_untouched(smol):
    """Pages owned by nobody (and pages owned by OTHER slots) must be
    bit-for-bit untouched by prefill and decode: masked writes land in
    TRASH, never through a stale or foreign page table."""
    cfg, model, params = smol
    pg = _paged_engine(model, params, slots=2, cache_len=32,
                       prefill_buckets=(16,), megastep=2)
    marker = 3.25
    pg.cache = jax.tree_util.tree_map(
        lambda a: jnp.full_like(a, marker), pg.cache)
    ps = prompts(cfg, 1, seed=11)
    req = pg.submit(Request(prompt=list(ps[0]), max_new_tokens=12))
    pg.step()                  # prefill + first megastep: still mid-stream
    owned = set(pg._alloc.owned(req.slot))
    assert owned, "request should hold pages mid-stream"
    pg.run_to_completion()
    untouched = np.array(sorted(set(range(pg.num_pages)) - owned), np.int64)
    from repro.serving.paged import gather_live
    kept = gather_live(pg.cache, jnp.asarray(untouched, jnp.int32),
                       pg._axes)
    for leaf in jax.tree_util.tree_leaves(kept):
        assert float(jnp.min(leaf)) == marker
        assert float(jnp.max(leaf)) == marker


def test_paged_pool_exhaustion_serializes_admission(smol):
    """When the pool can't hold another whole-lifetime reservation, the
    queue head WAITS (no bypass) and admission resumes on release — every
    request still completes with the unconstrained output."""
    cfg, model, params = smol
    ps = prompts(cfg, 4, seed=9)
    want = _paged_engine(model, params).generate(ps, max_new_tokens=8)
    # room for ~one request at a time: lifetime <= 13 + 8 = 21 tokens = 3
    # pages of 8 -> num_pages=4 fits one, never two
    tight = _paged_engine(model, params, num_pages=4)
    reqs = [tight.submit(Request(prompt=list(p), max_new_tokens=8))
            for p in ps]
    seen_concurrent = 0
    while tight.has_work():
        tight.step()
        seen_concurrent = max(seen_concurrent, len(tight.active))
    assert [r.generated for r in reqs] == want
    assert seen_concurrent == 1, "4-page pool must serialize admission"
    with pytest.raises(ValueError, match="pages"):
        tight.submit(Request(prompt=list(range(8, 48)), max_new_tokens=8))


def test_paged_capacity_vs_live_bytes(smol):
    """snapshot() splits allocation from live context; live_bytes tracks
    page reservations up and back down to zero."""
    cfg, model, params = smol
    pg = _paged_engine(model, params)
    s0 = pg.snapshot()
    assert s0["decode_path"] == "paged" and s0["live_bytes"] == 0
    assert s0["capacity_bytes"] == s0["cache_bytes"] > 0
    reqs = [pg.submit(Request(prompt=list(p), max_new_tokens=8))
            for p in prompts(cfg, 2, seed=13)]
    pg.step()
    s1 = pg.snapshot()
    assert 0 < s1["live_bytes"] < s1["capacity_bytes"]
    assert s1["live_pages"] == pg._alloc.live_pages > 0
    assert pg.stats.live_pages > 0      # per-megastep occupancy
    pg.run_to_completion()
    assert pg.snapshot()["live_bytes"] == 0

    # contiguous engines estimate live bytes from sequence-scaling leaves
    slot_eng = InferenceEngine(model, params, slots=4, cache_len=64,
                               prefill_buckets=(16, 32), megastep=4)
    slot_eng.submit(Request(prompt=list(range(8, 20)), max_new_tokens=8))
    slot_eng.step()
    ss = slot_eng.snapshot()
    assert 0 < ss["live_bytes"] < ss["capacity_bytes"]


def test_paged_offload_restore_midstream(smol):
    """Mid-stream demote/restore on the paged engine: the snapshot carries
    only live pages, restore performs zero compiles, and decode continues
    bit-identically."""
    cfg, model, params = smol
    ps = prompts(cfg, 6, seed=19)
    want = _paged_engine(model, params, slots=3).generate(
        ps, max_new_tokens=12)
    eng = _paged_engine(model, params, slots=3)
    eng.warm_executables()
    c0 = eng.stats.compiles
    reqs = [eng.submit(Request(prompt=list(p), max_new_tokens=12))
            for p in ps]
    done = list(eng.step()) + list(eng.step())
    assert eng.active, "offload must happen mid-stream"
    live_b = eng.snapshot()["live_bytes"]
    host = eng.offload_device_state()
    live_nbytes = sum(np.asarray(x).nbytes for x in
                      jax.tree_util.tree_leaves(host["cache"]))
    assert live_nbytes == live_b
    cap = eng.num_pages * sum(
        np.asarray(x).nbytes for x in jax.tree_util.tree_leaves(
            model.init_cache(1, eng.page_size, jnp.float32)))
    assert 0 < live_nbytes < cap, \
        "snapshot must ship live pages only, not the whole pool"
    assert host["_paged_live_ids"].size == eng._alloc.live_pages
    eng.restore_device_state(host)
    done += eng.run_to_completion()
    got = [r.generated for r in sorted(done, key=lambda r: r.request_id)]
    assert got == want
    assert eng.stats.compiles == c0, "restore must not compile"


def test_paged_template_export_is_empty_and_clone_parity(smol):
    """export_template on a paged donor ships ZERO cache pages (nbytes ~
    weights only); the restored clone generates bit-identically with zero
    builder calls and zero compiles."""
    cfg, model, params = smol
    ps = prompts(cfg, 5, seed=23)
    donor = _paged_engine(model, params)
    donor.warm_executables()
    want = donor.generate(ps, max_new_tokens=6)
    tpl = donor.export_template()
    tpl_cache = sum(np.asarray(x).nbytes for x in
                    jax.tree_util.tree_leaves(tpl["cache"]))
    assert tpl_cache == 0
    assert tpl["_paged_live_ids"].size == 0
    assert (tpl["page_table"] == donor.trash).all()
    clone = donor.clone_offloaded()
    clone.restore_device_state(tpl)
    assert clone.generate(ps, max_new_tokens=6) == want
    assert clone.stats.compiles == 0
    assert clone._alloc.live_pages == 0


def test_paged_more_sessions_than_slot_capacity(smol):
    """The capacity pitch: at the SAME pool bytes as a 2-slot contiguous
    cache, the paged engine runs far more concurrent short sessions."""
    cfg, model, params = smol
    slot_eng = InferenceEngine(model, params, slots=2, cache_len=64,
                               prefill_buckets=(16,), megastep=4)
    cap = slot_eng.snapshot()["capacity_bytes"]
    pg = _paged_engine(model, params, slots=8, cache_len=64,
                       prefill_buckets=(16,), num_pages=16)  # 16*8=128 toks
    assert pg.snapshot()["capacity_bytes"] == cap
    ps = prompts(cfg, 8, seed=29)
    reqs = [pg.submit(Request(prompt=list(p), max_new_tokens=2))
            for p in ps]
    # each lifetime is <= 13 + 2 = 15 tokens = 2 pages: all 8 sessions fit
    # the 16-page pool at once. stats.live_pages records occupancy as of
    # the megastep, so a >= 8-page reading proves >= 4 concurrent sessions
    # — double the 2 slots the same bytes buy contiguously.
    peak_pages = 0
    while pg.has_work():
        pg.step()
        peak_pages = max(peak_pages, pg.stats.live_pages)
    assert all(len(r.generated) >= 1 for r in reqs)
    assert pg.stats.completed == 8
    assert peak_pages >= 8, \
        f"expected >=8 live pages (>=4 sessions) at 2-slot bytes, " \
        f"saw {peak_pages}"
