"""Bring-up check: the PCM fact-verification path on one TPU.

Drives the paper's own workload through the normal entry points — a
``PCMClient`` over a live ``PCMManager``, a ``ContextRecipe`` whose builder
is ``repro.launch.serve.build_context``, and a paged ``InferenceEngine``
with prefix sharing — at smollm2-1.7b's published width (24 layers,
d_model 2048, 32 query / 32 KV heads, vocab 49,152, bf16), with random
weights drawn from ``--seed``. Phases, in order:

  (a) device     platform, kind and count; anything but a TPU exits non-zero
  (b) serve      cold-build the context on one live worker, then verify 64
                 FEVER claims (one shared few-shot template) in batches of
                 16, 4 new tokens each, megastep K=8: paged decode, prefix
                 hits, no fallback, valid token ids
  (c) logits     first-token logits of the engine's prefill against a
                 float32 forward at highest matmul precision
  (d) lifecycle  warm rerun, then HOST_RAM and LOCAL_DISK demote + restore:
                 bit-identical outputs, zero builder calls, zero compiles,
                 the expected fetch source; start seconds are printed
  (e) kernels    paged_flash_decode against its reference, and a short
                 generate through an engine with use_kernels=True

Every phase raises on failure. The last line of stdout is the JSON object
``{"ok": true, "device": {...}}``, printed only when every phase passed.

  python chip_smoke.py                 # one chip: phases (a)-(e)
  python chip_smoke.py --four-chips    # four live workers, one per chip

``--four-chips`` runs only its own phase: the donor cold-builds on chip 0
and serves the claims, three joiners bootstrap over PEER, and each of the
four serves the same claims with bit-identical outputs, its weights,
cache and executables on its own chip.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config, get_reduced_config  # noqa: E402
from repro.core import (ContextMode, PCMClient, PCMManager,  # noqa: E402
                        load_context, make_recipe)
from repro.core.store import Tier  # noqa: E402
from repro.core.transfer import FetchSource  # noqa: E402
from repro.data import fever  # noqa: E402
from repro.data.tokenizer import HashTokenizer  # noqa: E402
from repro.launch.compile_cache import configure_compile_cache  # noqa: E402
from repro.launch.serve import build_context  # noqa: E402

ARCH = "smollm2-1.7b"
SLOTS, CACHE_LEN, MEGASTEP = 16, 256, 8
PREFILL_BUCKETS = (32, 256)
N_CLAIMS, BATCH, MAX_NEW, SHOTS = 64, 16, 4, 8
N_LOGIT_PROMPTS = 4
N_CHIPS = 4           # live workers of --four-chips, one per chip
LOGIT_TOL = 5e-2      # max |engine - float32 reference| / max |reference|
KERNEL_TOL = 3e-2     # bf16 kernel output against its float32 reference
BOOTSTRAP_TIMEOUT = 600.0     # seconds for the joiners' PEER bootstraps
SPILL_DIR = os.path.join(REPO, ".pcm_spill")


def say(phase: str, **fields):
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def recipe_for(full_config: bool, seed: int):
    """The serving context, with its true footprint: a cold worker makes
    the weights, so there is no artifact or software environment to fetch
    and the fetch ladder offers no FS rung (a cold worker builds); the
    host snapshot holds the weights, and the device the weights and the
    KV page pool."""
    cfg = get_config(ARCH) if full_config else get_reduced_config(ARCH)
    weights = cfg.param_count() * jnp.dtype(cfg.param_dtype).itemsize
    kv = SLOTS * CACHE_LEN * cfg.kv_bytes_per_token(
        jnp.dtype(cfg.kv_cache_dtype).itemsize)
    return make_recipe(f"{ARCH}.chip-smoke", build_context,
                       (ARCH, SLOTS, CACHE_LEN, MEGASTEP, full_config, seed,
                        PREFILL_BUCKETS),
                       artifact_bytes=0, env_bytes=0,
                       host_bytes=weights, device_bytes=weights + kv)


def claim_prompts(vocab_size: int):
    """64 claims behind one shared few-shot template (the Prompt-for-Fact
    shape: a fixed instruction prefix, a short per-claim tail)."""
    tok = HashTokenizer(vocab_size)
    shots = fever.claim_batch(range(fever.FEVER_SIZE - SHOTS,
                                    fever.FEVER_SIZE))
    preamble = " ".join(f"{fever.render_prompt(c)} {c.label.lower()} ."
                        for c in shots)
    return [tok.encode(f"{preamble} {fever.render_prompt(c)}")
            for c in fever.claim_batch(range(N_CLAIMS))]


# ------------------------------------------------------------ task bodies --
def _generate(prompts):
    return load_context("engine").generate(prompts, max_new_tokens=MAX_NEW)


def _on_device(tree, device) -> bool:
    return all(leaf.devices() == {device}
               for leaf in jax.tree_util.tree_leaves(tree))


def _engine_report():
    eng = load_context("engine")
    exe_shardings = [exe.input_shardings for exe in eng._exe.values()]
    return {"decode_path": eng.stats.decode_path,
            "paged_fallback": eng.paged_fallback,
            "prefix_fallback": eng.prefix_fallback,
            "prefix_hits": eng.stats.prefix_hits,
            "compiles": eng.stats.compiles,
            "aot_cache_hits": eng.stats.aot_cache_hits,
            "device": eng.device.id,
            "state_on_device": _on_device(
                (eng.params, eng.cache, eng.page_table), eng.device),
            "executables": len(exe_shardings),
            "executables_on_device": all(
                s.device_set == {eng.device}
                for s in jax.tree_util.tree_leaves(exe_shardings))}


def _replay(prompts, hold_seconds: float = 0.0):
    """Serve the claims in batches from an empty prefix cache — the exact
    admission path of a fresh engine — and report where it ran."""
    eng = load_context("engine")
    eng.drop_prefix_cache()
    outs = []
    for i in range(0, len(prompts), BATCH):
        outs.extend(eng.generate(prompts[i:i + BATCH],
                                 max_new_tokens=MAX_NEW))
    time.sleep(hold_seconds)
    return {"outputs": outs, **_engine_report()}


def _logit_check(prompts):
    """First-token logits of the engine's prefill computation (its model,
    weights and cache dtype) against the same weights run through the full
    forward in float32 at highest matmul precision. The float32 model
    converts each weight where it is used, so the check holds no second
    copy of the weights."""
    from repro.models import build_model
    eng = load_context("engine")
    model, cfg = eng.model, eng.cfg
    lens = np.array([len(p) for p in prompts], np.int32)
    S = -(-int(lens.max()) // 8) * 8
    toks = np.zeros((len(prompts), S), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    cache = model.init_cache(len(prompts), S, jnp.dtype(cfg.kv_cache_dtype))
    got, _ = jax.jit(model.prefill)(eng.params, toks, lens, cache)
    model32 = build_model(dataclasses.replace(cfg, compute_dtype="float32",
                                              logit_dtype="float32"))
    with jax.default_matmul_precision("highest"):
        full, _ = jax.jit(model32.forward)(eng.params, {"tokens": toks})
    want = full[np.arange(len(prompts)), lens - 1]
    V = cfg.vocab_size
    got = np.asarray(got[:, :V], np.float32)
    want = np.asarray(want[:, :V], np.float32)
    err = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    return {"max_abs_err": err, "max_abs_ref": scale,
            "rel_err": err / scale,
            "top1_agree": int(np.sum(got.argmax(1) == want.argmax(1))),
            "finite": bool(np.isfinite(got).all())}


def _kernel_generate(prompts):
    """A short generate through a use_kernels=True engine on the same
    weights: every decode step runs the Pallas paged-decode kernel."""
    from repro.models import build_model
    from repro.serving import InferenceEngine
    eng = load_context("engine")
    model = build_model(dataclasses.replace(eng.cfg, use_kernels=True))
    keng = InferenceEngine(model, eng.params, slots=len(prompts),
                           cache_len=CACHE_LEN, prefill_buckets=(CACHE_LEN,),
                           megastep=MEGASTEP, paged=True,
                           prefix_sharing=False)
    outs = keng.generate(prompts, max_new_tokens=MAX_NEW)
    return {"outputs": outs, "decode_path": keng.stats.decode_path,
            "paged_fallback": keng.paged_fallback}


# ------------------------------------------------------------ phases ------
def check_device(want_platform: str = "tpu", min_count: int = 1):
    devices = jax.devices()
    dev = devices[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
    say("a", **info)
    if dev.platform != want_platform:
        raise SystemExit(f"no {want_platform} found: JAX reports "
                         f"{dev.platform} ({dev.device_kind}); the smoke "
                         f"check runs on the chip only")
    if len(devices) < min_count:
        raise SystemExit(f"{min_count} devices needed, {len(devices)} found")
    return info


def outputs_digest(outs) -> str:
    """Short digest of greedy outputs, comparable across runs."""
    import hashlib
    return hashlib.sha256(json.dumps(outs).encode()).hexdigest()[:16]


def _check_outputs(outs, vocab_size: int):
    assert len(outs) == N_CLAIMS, len(outs)
    for o in outs:
        assert 1 <= len(o) <= MAX_NEW, o
        assert all(0 <= t < vocab_size for t in o), o


def _worker(mgr):
    (wid,) = mgr.workers
    return mgr.workers[wid]


def _start(client, handle):
    """Context start latency in whatever state the context is in: wall
    seconds of a task that only reads the context. Returns it with the
    model config the context was built from."""
    t0 = time.monotonic()
    cfg = client.submit(lambda: load_context("cfg"), context=handle).result()
    return time.monotonic() - t0, cfg


def serve_phase(client, handle, prompts, vocab_size: int, cold: float):
    """(b): after the cold build on one live worker, the claims in
    batches."""
    lib = _worker(client.backend).library
    assert lib.builder_calls == 1, lib.builder_calls
    built = client.submit(_engine_report, context=handle).result()
    outs = []
    for i in range(0, len(prompts), BATCH):
        outs.extend(client.submit(_generate, prompts[i:i + BATCH],
                                  context=handle).result())
    rep = client.submit(_engine_report, context=handle).result()
    assert rep["decode_path"] == "paged", rep
    assert rep["paged_fallback"] is None, rep["paged_fallback"]
    assert rep["prefix_fallback"] is None, rep["prefix_fallback"]
    assert rep["prefix_hits"] > 0, rep
    assert rep["state_on_device"] and rep["executables_on_device"], rep
    assert rep["compiles"] == built["compiles"], (built, rep)
    _check_outputs(outs, vocab_size)
    say("b", claims=len(outs), batches=len(prompts) // BATCH,
        prefix_hits=rep["prefix_hits"], compiles=rep["compiles"],
        decode_path=rep["decode_path"], cold_start_s=cold,
        outputs_sha=outputs_digest(outs))
    return outs, rep["compiles"]


def logit_phase(client, handle, prompts):
    """(c): engine prefill logits against the float32 reference."""
    r = client.submit(_logit_check, prompts[:N_LOGIT_PROMPTS],
                      context=handle).result()
    say("c", prompts=N_LOGIT_PROMPTS, max_abs_err=r["max_abs_err"],
        max_abs_ref=r["max_abs_ref"], rel_err=r["rel_err"], tol=LOGIT_TOL,
        top1_agree=f"{r['top1_agree']}/{N_LOGIT_PROMPTS}")
    assert r["finite"], r
    assert r["rel_err"] <= LOGIT_TOL, r
    return r


def lifecycle_phase(client, handle, prompts, base, compiles: int,
                    cold: float):
    """(d): warm rerun, HOST_RAM and LOCAL_DISK demote + restore."""
    mgr = client.backend
    lib = _worker(mgr).library
    starts = {"cold": cold}
    for label, tier, source in (("warm", None, None),
                                ("host_ram", Tier.HOST_RAM, FetchSource.POOL),
                                ("disk", Tier.LOCAL_DISK, FetchSource.DISK)):
        if tier is not None:
            assert handle.demote(tier), f"nothing demoted to {tier.name}"
            assert handle.snapshot_tier() == tier, handle.snapshot_tier()
        restores = lib.restores
        starts[label], _ = _start(client, handle)
        assert lib.restores == restores + (source is not None), label
        rep = client.submit(_replay, prompts, context=handle).result()
        assert rep["outputs"] == base, f"{label}: outputs differ from (b)"
        assert lib.builder_calls == 1, (label, lib.builder_calls)
        assert rep["compiles"] == compiles, (label, rep["compiles"])
        assert rep["state_on_device"], rep
        if source is not None:
            assert lib.fetch_sources[-1] == source, (label,
                                                     lib.fetch_sources[-1])
    say("d", **{f"{k}_start_s": v for k, v in starts.items()},
        builder_calls=lib.builder_calls, restores=lib.restores,
        compiles=compiles, outputs="bit-identical")
    return starts


def kernel_phase(client, handle, prompts, cfg):
    """(e): paged_flash_decode against its reference at the config's
    widths and the served engine's page size, then a short generate
    through the kernel path."""
    from repro.kernels import ops, ref
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    dt = jnp.dtype(cfg.compute_dtype)
    page = client.submit(lambda: load_context("engine").page_size,
                         context=handle).result()
    n_pages, n_cols = SLOTS * (CACHE_LEN // page), CACHE_LEN // page
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(keys[0], (SLOTS, H, D), jnp.float32).astype(dt)
    kp = jax.random.normal(keys[1], (n_pages + 1, page, Hkv, D),
                           jnp.float32).astype(dt)
    vp = jax.random.normal(keys[2], (n_pages + 1, page, Hkv, D),
                           jnp.float32).astype(dt)
    table = np.random.RandomState(0).permutation(n_pages)[
        :SLOTS * n_cols].reshape(SLOTS, n_cols).astype(np.int32)
    lengths = np.array([1 + (37 * i) % CACHE_LEN for i in range(SLOTS)],
                       np.int32)
    lengths[0] = 0                                   # an inactive slot
    out = jax.jit(lambda *a: ops.paged_flash_decode(*a, scale=D ** -0.5))(
        q, kp, vp, table, lengths)
    want = ref.paged_decode_ref(q, kp, vp, table, lengths, scale=D ** -0.5)
    live = lengths > 0
    err = float(jnp.max(jnp.abs(out[live].astype(jnp.float32)
                                - want[live].astype(jnp.float32))))
    assert bool(jnp.all(jnp.isfinite(out))), "non-finite kernel output"
    assert err <= KERNEL_TOL, err
    r = client.submit(_kernel_generate, prompts[:4],
                      context=handle).result()
    assert r["decode_path"] == "paged" and r["paged_fallback"] is None, r
    assert all(1 <= len(o) <= MAX_NEW and
               all(0 <= t < cfg.vocab_size for t in o)
               for o in r["outputs"]), r["outputs"]
    say("e", paged_flash_decode_max_abs_err=err, tol=KERNEL_TOL,
        kernel_generate=len(r["outputs"]))


def one_chip(full_config: bool = True, seed: int = 0,
             spill_dir: str = SPILL_DIR):
    """Phases (b)-(e) on one live worker."""
    mgr = PCMManager(mode=ContextMode.FULL, n_workers=1,
                     spill_dir=spill_dir)
    client = PCMClient(backend=mgr)
    try:
        handle = client.context(recipe_for(full_config, seed))
        cold, cfg = _start(client, handle)
        prompts = claim_prompts(cfg.vocab_size)
        base, compiles = serve_phase(client, handle, prompts,
                                     cfg.vocab_size, cold)
        logit_phase(client, handle, prompts)
        lifecycle_phase(client, handle, prompts, base, compiles, cold)
        kernel_phase(client, handle, prompts, cfg)
        return base
    finally:
        client.shutdown()


def add_joiners(client, handle, n: int):
    """Add ``n`` live workers one at a time, the warm ones idle: short
    tasks keep the context in demand, and the scheduler bootstraps each
    cold joiner from a warm peer. Returns the joiners' ids and their
    bootstrap seconds."""
    mgr = client.backend
    joiners, bootstrap_s = [], []
    deadline = time.monotonic() + BOOTSTRAP_TIMEOUT
    for _ in range(n):
        t0 = time.monotonic()
        joiners.append(mgr.add_worker())
        while set(handle.residency().values()) != {Tier.DEVICE}:
            assert time.monotonic() < deadline, handle.residency()
            _start(client, handle)
        bootstrap_s.append(time.monotonic() - t0)
    return joiners, bootstrap_s


def four_chips(full_config: bool = True, seed: int = 0,
               spill_dir: str = SPILL_DIR):
    """Four live workers in this process, worker i on device i: the donor
    cold-builds on chip 0 and serves the claims (the one-chip reference);
    three joiners bootstrap over PEER and serve the same claims."""
    devices = jax.devices()[:N_CHIPS]
    mgr = PCMManager(mode=ContextMode.FULL, n_workers=1,
                     spill_dir=spill_dir)
    client = PCMClient(backend=mgr)
    try:
        handle = client.context(recipe_for(full_config, seed))
        (donor,) = mgr.workers
        handle.warm_up(worker_ids=[donor])
        _, cfg = _start(client, handle)
        prompts = claim_prompts(cfg.vocab_size)
        ref = client.submit(_replay, prompts, context=handle).result()
        _check_outputs(ref["outputs"], cfg.vocab_size)
        assert ref["device"] == devices[0].id, ref

        joiners, bootstrap_s = add_joiners(client, handle, N_CHIPS - 1)
        # where each joiner's context came from, and the planner's measured
        # rates that priced the choice: printed before the checks below
        say("4chips-fetch", bootstrap_s=bootstrap_s,
            decisions=[(d.worker_id, d.source.name, d.donor,
                        d.degraded_from and d.degraded_from.name)
                       for d in handle.fetch_history()],
            sources={w: [s.name for s in mgr.workers[w].library.fetch_sources]
                     for w in joiners},
            calibration=mgr.scheduler.planner.calibration())
        # four replays at once land one per warm idle worker; each holds
        # its worker briefly so none of them takes a second one
        results = client.map(lambda _: _replay(prompts, 1.0), range(N_CHIPS),
                             context=handle).gather()
        served = {r["device"] for r in results}
        assert served == {d.id for d in devices}, served

        held = {w: mgr.workers[w].device for w in [donor] + joiners}
        assert sorted(d.id for d in held.values()) == \
            sorted(d.id for d in devices), held
        for r in results:
            assert r["outputs"] == ref["outputs"], \
                f"device {r['device']}: outputs differ from the donor's"
            assert r["state_on_device"] and r["executables_on_device"], r
        joiner_reports = [r for r in results if r["device"] != ref["device"]]
        assert all(r["compiles"] == 0 and r["aot_cache_hits"] > 0
                   for r in joiner_reports), joiner_reports
        libs = {w: mgr.workers[w].library for w in joiners}
        assert all(lib.builder_calls == 0 for lib in libs.values()), \
            {w: lib.builder_calls for w, lib in libs.items()}
        assert all(lib.fetch_sources and
                   set(lib.fetch_sources) == {FetchSource.PEER}
                   for lib in libs.values()), \
            {w: lib.fetch_sources for w, lib in libs.items()}
        say("4chips", workers=len(held),
            devices=sorted(d.id for d in held.values()),
            replays=len(results), outputs_sha=outputs_digest(
                ref["outputs"]), joiner_builder_calls=0,
            joiner_compiles=0,
            joiner_aot_cache_hits=[r["aot_cache_hits"]
                                   for r in joiner_reports],
            outputs="bit-identical")
    finally:
        client.shutdown()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights")
    args = ap.parse_args(argv)
    info = check_device(min_count=N_CHIPS if args.four_chips else 1)
    configure_compile_cache()
    try:
        if args.four_chips:
            four_chips(seed=args.seed)
        else:
            one_chip(seed=args.seed)
    finally:
        shutil.rmtree(SPILL_DIR, ignore_errors=True)
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
