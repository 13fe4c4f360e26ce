"""Quickstart: the PCMClient session API in ~100 lines.

The paper's Fig. 5 transformation, session-style: an expensive
``load_model`` context builder is declared ONCE as a first-class
ContextHandle, decoupled from cheap ``infer_model`` tasks submitted in
bulk. The context (weights + AOT-compiled prefill/megastep executables +
KV pools + per-slot decode state) is built once per worker and reused by
every subsequent task — including after a no-warning preemption. Inference
inside the context runs as fused decode *megasteps*: one device dispatch
generates up to K tokens across all slots before the host syncs (see the
``load_model`` docstring for the latency/throughput trade).

The SAME workload function runs against two backends:

  1. the LIVE backend (PCMManager): real JAX inference on this host,
     executed by a CONCURRENT actor runtime — every worker is a thread
     with a mailbox owning its Library/ContextStore, the scheduler runs
     behind one lock fed by runtime events, and Futures resolve on
     condition variables (``result(timeout=...)`` just waits, nothing
     busy-polls);
  2. the SIMULATOR backend: a dry run against the paper's calibrated
     device cost models — no model is built, Futures resolve to modeled
     placement/timing records. This is how cluster-scale figures are
     explored before burning GPU hours.

Context tier movement is PHYSICAL on the live backend. Preempting a
worker (or calling ``ctx.demote()``) snapshots the context off the
device — params + engine state via ``jax.device_get`` into the node
snapshot pool, spilling LRU snapshots to local disk through
``checkpoint/io`` — and the next task that needs it RESTORES instead of
rebuilding: zero builder calls, zero XLA compiles, bit-identical greedy
outputs, at transfer cost instead of minutes of startup. That delta is
the paper's headline number. ``tests/test_runtime.py`` checks the round
trip (``TestEngineTierRoundTrip``); ``python3 bench/run.py`` measures
speed on the chip.

Migrating from the PR-0 decorator API:

    @context_app(context=(load_model, ("smollm2-1.7b",)))   # old
    def infer_model(texts): ...
    fut = infer_model(texts); fut.result()

becomes

    client = PCMClient(n_workers=2)
    ctx = client.context(load_model, "smollm2-1.7b")        # new: handle
    @client.task(context=ctx)
    def infer_model(texts): ...
    fut = infer_model(texts); fut.result(timeout=120)

``context_app``/``load_context`` still work as shims, but the client adds
context pinning/warm-up/residency, multi-context tasks
(``contexts={"a": h1, "b": h2}`` + ``load_context("a.var")``), bulk
``client.map(...) -> FutureBatch`` with ``as_completed()``/``gather()``,
priorities, and backend swapping.

Streaming sessions (the front door). Bulk ``map`` is the wrong shape for
interactive traffic, so the client also speaks sessions::

    sess = client.session(ctx, tenant="acme", slo=SLOClass.INTERACTIVE)
    stream = sess.submit(prompt_tokens, max_new_tokens=64)
    for token in stream:          # tokens arrive as megasteps complete
        ...
    stream.ttft_seconds           # time to first token

Sessions are sticky (a session's turns keep hitting the lane whose
context is warm for them), survive worker preemption mid-stream via the
PEER/POOL/DISK/FS/BUILD ladder, and pass through a front door that
enforces per-tenant token-bucket quotas and bounded queues — an
over-budget tenant gets an explicit ``ShedError`` (backpressure, with
``retry_after_seconds``) instead of silently degrading everyone else.
INTERACTIVE turns jump ahead of queued BATCH turns (never preempting a
running decode); BATCH tenants share capacity by deficit round-robin.
The engine underneath admits new prefills continuously as slots free —
an arrival waits at most one megastep for admission, not a whole wave
drain. ``tests/test_frontdoor.py`` and ``tests/test_serving.py`` check
sheds, streams and mid-stream admission.

Paged KV cache (``paged=True``). The contiguous slot cache pays
``slots x cache_len`` positions per leaf whether a slot holds 12 tokens
or 512, so sessions-per-GPU is capped by allocated capacity. With
``InferenceEngine(..., paged=True, page_size=...)`` the cache becomes a
shared pool of fixed-size pages behind a per-slot page table: a request
reserves ``ceil(tokens / page)`` pages at admission and frees them at
finish, so concurrency is bounded by LIVE tokens — at the exact same HBM
bytes the engine holds several times the sessions. Greedy outputs stay
bit-identical to the slot cache, warm paths still compile nothing, and
demotion/peer transfer ships only the live pages (``snapshot()`` splits
``capacity_bytes`` from ``live_bytes``). Attention families page (dense
GQA and MLA latents, routed through Pallas paged-decode kernels when
``cfg.use_kernels``); SSM/xLSTM state and sliding-window ring buffers
silently keep the slot path (``engine.paged_fallback`` says why).
The ``test_paged_*`` tests of ``tests/test_serving.py`` check the session
multiplier, decode parity and live-page snapshots.

Copy-on-write prefix sharing (paged + ``prefix_sharing=True``, the
default). High-throughput lightweight-LLM applications send the SAME
prompt template to every request — a fact-verification app prefixes each
claim with one instructions/few-shot block. With sharing on, the engine
keeps a radix prefix cache over the page pool
(``repro.serving.paged.PrefixCache``): the first request prefills the
template once; every later admission radix-matches its prompt, maps its
page-table row onto the already-resident pages (refcount++), and
prefills ONLY its unshared tail. A partially-shared boundary page is
copied on first write (copy-on-write, fused into the prefill dispatch;
decode appends into a cache-held page copy before the megastep), and
cache-only pages are evicted LRU behind live reservations — sharing
never blocks admission. Greedy outputs stay bit-identical to unshared
prefill and warm paths still compile nothing. Above the engine,
``open_session(..., prefix_key=...)`` lanes template-mates onto the same
engine and the scheduler's placement prefers a prefix-warm worker over
an equally-warm cold one. ``tests/test_prefix.py`` checks the parity,
the prefill shrink and the session multiplier.

Multi-host PCM (the socket transport). A LiveWorker can be a PROCESS on
another node: the manager opens the transport (``manager.listen()``) and
worker processes (``python -m repro.cluster.node --connect HOST:PORT``,
or :func:`repro.cluster.node.spawn_node_process`, or an
``ElasticRunner(spawn_remote=True)`` reconciling a capacity trace into
real processes) join the SAME pool the in-process actor threads live
in — same scheduler, same fetch ladder, same preemption semantics.
What changes is purely the medium: context movement crosses the wire as
versioned ``repro.core.wire`` blobs — every array chunk sha256-verified
through checkpoint/io's manifest path, executables replaced by
AOTRecipes so a receiver re-lowers into compile-cache HITS (a shared
``--aot-cache`` dir makes that hold across OS processes) instead of
receiving unpicklable executable objects. Striped peer bootstraps work
donor-process -> receiver-process (the manager forwards chunk frames and
reconciles lane failures), a ``kill -9``'d node is detected by socket
EOF (or, for wedged links, a heartbeat monitor) and fed to the SAME
preemption path as a reclaimed GPU, and the planner prices wire lanes
in their own ``p2p:socket`` calibration namespace so a cold socket lane
never inherits in-process memcpy history. ``tests/test_multihost.py``
drives it with real worker processes: greedy parity, zero builder calls
and zero true recompiles on a joiner.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import time

import jax

from repro.configs import get_reduced_config
from repro.core import ContextMode, PCMClient, SimulatorBackend, load_context
from repro.data.tokenizer import HashTokenizer
from repro.models import build_model
from repro.serving import InferenceEngine


# ---- 1. the context builder (the paper's `load_model`) --------------------
def load_model(arch: str):
    """What is RESIDENT in this context: the weights, the slot KV cache,
    the per-slot decode state, and — because PCM materialization calls
    ``engine.warm_executables()`` — the AOT-compiled prefill + decode
    megastep executables. Tasks against a warm context perform zero
    compiles and zero allocations on the hot path.

    ``megastep=8``: each engine step launches ONE fused device loop that
    generates up to 8 tokens per active slot; the host syncs once per
    megastep (a (slots, 8) token block) instead of once per token. Larger
    K amortizes more dispatch/sync overhead (throughput) but admits queued
    requests at coarser boundaries (latency); K=1 is bit-exact with the
    classic per-token loop, and greedy outputs are identical for every K.
    """
    print(f"  [context] building {arch} (the expensive one-time startup)...")
    cfg = get_reduced_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = InferenceEngine(model, params, slots=4, cache_len=64,
                             prefill_buckets=(16, 32), megastep=8)
    return {"engine": engine, "tokenizer": HashTokenizer(cfg.vocab_size)}


# ---- 2. the inference task (the paper's `infer_model`) --------------------
def infer_model(texts):
    engine = load_context("engine")
    tok = load_context("tokenizer")
    prompts = [tok.encode(t) for t in texts]
    return engine.generate(prompts, max_new_tokens=4)


# ---- 3. one workload, any backend -----------------------------------------
def run_workload(client: PCMClient, claims, batch_size=4):
    """Declare the context, warm it, sweep the claims. Identical code for
    the live runtime and the dry-run simulator."""
    ctx = client.context(load_model, "smollm2-1.7b",
                         name="smollm2.verifier")
    ctx.warm_up()            # materialize off the task critical path
    with ctx:                # pinned for the block: survives mode eviction
        batch = client.map(infer_model, claims, batch_size=batch_size,
                           context=ctx)
        results = batch.gather(timeout=600)
    tiers = {w: t.name for w, t in ctx.residency().items()}
    return results, tiers


def main():
    claims = [f"claim number {i} about the capital of somewhere"
              for i in range(12)]

    print("== live backend: real JAX inference ==")
    client = PCMClient(mode=ContextMode.FULL, n_workers=2)
    t0 = time.monotonic()
    results, tiers = run_workload(client, claims)
    st = client.stats()
    print(f"verified {sum(len(r) for r in results)} claims in "
          f"{time.monotonic() - t0:.2f}s")
    print(f"context prewarmed on {len(tiers)} workers "
          f"({st['cold_invocations']} cold invocations, "
          f"{st['warm_invocations']} warm); residency: {tiers}")

    # no-warning preemption: the warm worker dies, tasks requeue elsewhere
    victim = client.workers[0]
    print(f"preempting worker {victim} (no warning)...")
    client.backend.preempt_worker(victim)
    ctx = client.context(load_model, "smollm2-1.7b", name="smollm2.verifier")
    more = client.map(infer_model, claims[:4], batch_size=2, context=ctx)
    for fut in more.as_completed(timeout=600):
        assert fut.result() is not None
    print("requeued tasks completed on the surviving warm worker.")

    # physical demotion/restore: the context leaves the device (host-RAM
    # snapshot in the node pool) and comes back at restore cost — no
    # builder rerun, no recompiles
    demoted = ctx.demote()                       # DEVICE -> HOST_RAM
    print(f"demoted context off {len(demoted)} worker(s); snapshot tier: "
          f"{ctx.snapshot_tier().name}")
    t0 = time.monotonic()
    fut = client.submit(infer_model, claims[:2], context=ctx)
    assert fut.result(timeout=600) is not None
    st = client.stats()
    print(f"restored + ran in {time.monotonic() - t0:.2f}s "
          f"({st['context_restores']} restore(s), builder ran "
          f"{st['builder_calls']}x total — cold build took "
          f"{st['context_build_seconds']:.1f}s)")

    # streamed restores: a cold joiner bootstraps the same context by
    # striping verified chunks from warm donors (and the node snapshot
    # pool) instead of waiting on one monolithic export — and each donor
    # ships only a budgeted few chunks per mailbox turn, so its own
    # decode never stalls behind a big device_get
    print("== streamed restores: striped peer bootstrap ==")
    joiner = client.backend.add_worker()
    deadline = time.monotonic() + 120
    while not client.backend.fetch_history():       # keep demand pending
        batch = client.map(infer_model, claims[:6], batch_size=2,
                           context=ctx)
        for fut in batch.as_completed(timeout=600):
            assert fut.result() is not None
        if time.monotonic() > deadline:
            break
    st = client.stats()
    stripes = st["striping"]
    hist = client.backend.fetch_history()
    how = hist[-1].source.value if hist else "warm"
    print(f"worker {joiner} joined cold and fetched the context via "
          f"{how}: {stripes['stripes']} stripe(s), {stripes['chunks']} "
          f"verified chunks, {stripes['lane_failures']} lane failures, "
          f"{stripes['degrades']} degrades — builder still ran "
          f"{st['builder_calls']}x total, serving never paused")

    # streaming sessions: the front door over the same live pool. An
    # interactive tenant streams token-by-token; a rate-limited tenant
    # hits explicit backpressure instead of degrading everyone else.
    print("== streaming sessions: the front door ==")
    from repro.serving import ShedError, SLOClass, TenantQuota
    tok = HashTokenizer(get_reduced_config("smollm2-1.7b").vocab_size)
    client.frontdoor(quotas={"freeloader": TenantQuota(
        tokens_per_second=0.1, burst_tokens=24.0, max_queued_turns=4)})
    with client.session(ctx, tenant="acme",
                        slo=SLOClass.INTERACTIVE) as sess:
        stream = sess.submit(tok.encode("what is the capital of nowhere"),
                             max_new_tokens=8)
        toks = [t for t in stream]               # arrives per megastep
        print(f"streamed {len(toks)} tokens, ttft "
              f"{stream.ttft_seconds * 1e3:.1f}ms")
    with client.session(ctx, tenant="freeloader") as cheap:
        cheap.submit(tok.encode("one is fine"), max_new_tokens=8).result(
            timeout=600)
        try:
            cheap.submit(tok.encode("two is too many"), max_new_tokens=8)
        except ShedError as e:
            print(f"over-budget tenant shed: {e.reason} "
                  f"(retry after {e.retry_after_seconds:.0f}s)")

    # paged KV cache: the same engine API, sessions bounded by live
    # tokens instead of slots x cache_len — and snapshots that ship only
    # the pages requests actually own
    print("== paged KV cache: more sessions per GPU, live-byte snapshots ==")
    from repro.serving import Request
    cfg = get_reduced_config("smollm2-1.7b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # pool sized to TWO contiguous slots' bytes, shared by eight slots
    paged = InferenceEngine(model, params, slots=8, cache_len=64,
                            prefill_buckets=(16,), megastep=8, paged=True,
                            page_size=8, num_pages=2 * (64 // 8))
    for i in range(8):
        paged.submit(Request(prompt=tok.encode(f"short question {i}"),
                             max_new_tokens=8))
    peak = 0
    while paged.has_work():
        paged.step()
        peak = max(peak, paged.stats.live_pages)
    snap = paged.snapshot()
    print(f"{paged.stats.completed} sessions through a "
          f"{snap['capacity_bytes']} byte pool (2 contiguous slots' "
          f"worth), peak {peak} live pages; snapshots ship live bytes "
          f"only ({snap['live_bytes']} idle vs {snap['capacity_bytes']} "
          "allocated)")

    # copy-on-write prefix sharing: one prefill per shared template — the
    # fact-verification shape (same instructions block, per-claim tail)
    print("== prefix sharing: one prefill per shared prompt template ==")
    template = tok.encode(
        "you are a fact checker given a claim answer supported or refuted "
        "with a short justification here is the claim to verify")
    shared = InferenceEngine(model, params, slots=8, cache_len=64,
                             prefill_buckets=(16,), megastep=8, paged=True,
                             page_size=8, num_pages=2 * (64 // 8))
    for i in range(8):
        shared.submit(Request(prompt=template + tok.encode(f"claim {i}"),
                              max_new_tokens=8))
    shared.run_to_completion()
    stp = shared.stats
    print(f"{stp.completed} sessions over a {len(template)}-token shared "
          f"template: {stp.prefix_hits} prefix hits, "
          f"{stp.prefix_tokens_reused} prompt tokens served from shared "
          f"pages, {stp.cow_copies} copy-on-write page copies, only "
          f"{stp.prefill_tokens} tokens actually prefilled")

    # multi-host PCM: a worker that is a PROCESS on another node joins
    # the pool over the socket transport. The context builder must be
    # importable BY NAME in the node process (pickle-by-reference), so
    # the demo imports this file as a module and hands the node our
    # directory; contexts then cross the wire as chunked-sha256 blobs
    # with executables as AOTRecipes (cache hits, never recompiles).
    print("== multi-host: a worker process over the socket transport ==")
    import os
    from repro.core import PCMManager, make_recipe
    from repro.cluster.node import spawn_node_process
    import quickstart as qs          # our own module, importable by name
    here = os.path.dirname(os.path.abspath(__file__))
    mh = PCMManager(mode=ContextMode.FULL, n_workers=0)
    node_proc = None
    try:
        addr = mh.listen()
        node_proc = spawn_node_process(addr, "node-1", extra_path=(here,))
        mh.wait_for_workers(["node-1"], timeout=180)
        recipe = make_recipe("smollm2.verifier.mh", qs.load_model,
                             ("smollm2-1.7b",))
        mh.warm_up(recipe)           # builds IN the node process
        out = mh.submit(qs.infer_model, args=(claims[:2],),
                        recipe=recipe).result(timeout=600)
        assert out is not None
        mh.demote_context(recipe)    # snapshot crosses the wire -> pool
        t0 = time.monotonic()
        out = mh.submit(qs.infer_model, args=(claims[:2],),
                        recipe=recipe).result(timeout=600)
        mir = mh.workers["node-1"].library
        print(f"node-1 (pid {node_proc.pid}) built once "
              f"({mir.builder_calls}x), demoted over the wire, then "
              f"restored + ran in {time.monotonic() - t0:.2f}s "
              f"({mir.restores} restore(s), sources "
              f"{[s.name for s in mir.fetch_sources]})")
    finally:
        mh.shutdown(timeout=60)
        if node_proc is not None:
            node_proc.terminate()

    print("== simulator backend: same workload, modeled cluster time ==")
    sim = PCMClient(backend=SimulatorBackend(n_workers=8, profile="a10",
                                             mode=ContextMode.FULL))
    sim_claims = [f"claim {i}" for i in range(800)]
    results, tiers = run_workload(sim, sim_claims, batch_size=50)
    st = sim.stats()
    print(f"modeled {sum(r.n_items for r in results)} inferences on 8xA10 "
          f"in {st['now']:.0f} simulated seconds "
          f"({st['warm_starts']} warm / {st['cold_starts']} cold starts, "
          f"{st['p2p_transfers']} P2P bootstraps)")


if __name__ == "__main__":
    main()
