"""Serving launcher: PCM-managed fact-verification inference.

``python -m repro.launch.serve --arch smollm2-1.7b --claims 64 --mode full``

Builds the model context via a PCM ContextRecipe (weights + paged engine +
compiled executables), submits claim-verification tasks through the
context-aware scheduler, and reports throughput + context amortization —
the live (real-JAX-execution) counterpart of the cluster simulation.
``--full-config`` serves the published architecture (random weights from
``--seed``) instead of the reduced smoke-test one. On an accelerator
there is one live worker per device by default.
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.configs import get_config, get_reduced_config
from repro.core import (ContextMode, PCMManager, context_app, load_context,
                        make_recipe)
from repro.data import fever
from repro.data.tokenizer import LABEL_TOKENS, HashTokenizer
from repro.launch.compile_cache import configure_compile_cache
from repro.models import build_model
from repro.serving import InferenceEngine


def build_context(arch: str, slots: int, cache_len: int, megastep: int = 8,
                  full_config: bool = False, seed: int = 0,
                  prefill_buckets=(32, 64)):
    """The paper's ``load_model``: expensive, runs once per worker.

    Random weights from ``seed``, in the config's own dtypes; the paged KV
    cache (with prefix sharing) is kept in the config's
    ``kv_cache_dtype``. Materialization AOT-compiles the engine's megastep
    + prefill executables (``warm_executables``), so the compile cost
    lands here — in the context build — and never on the task hot
    path."""
    cfg = get_config(arch) if full_config else get_reduced_config(arch)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    engine = InferenceEngine(model, params, slots=slots,
                             cache_len=cache_len,
                             prefill_buckets=prefill_buckets,
                             megastep=megastep, paged=True)
    tok = HashTokenizer(cfg.vocab_size)
    return {"engine": engine, "tokenizer": tok, "cfg": cfg}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm2-1.7b")
    ap.add_argument("--claims", type=int, default=64)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--workers", type=int, default=None,
                    help="live workers (default: one per device on an "
                         "accelerator, 2 on the CPU)")
    ap.add_argument("--mode", choices=("agnostic", "partial", "full"),
                    default="full")
    ap.add_argument("--prompt", type=int, default=0,
                    help="prompt template index (Prompt-for-Fact sweep)")
    ap.add_argument("--preempt-after", type=int, default=0,
                    help="preempt a worker after N tasks (demo)")
    ap.add_argument("--megastep", type=int, default=8,
                    help="tokens generated per fused decode dispatch "
                         "(K=1 matches the classic per-token loop)")
    ap.add_argument("--full-config", action="store_true",
                    help="serve the published (not reduced) architecture")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights")
    args = ap.parse_args()

    configure_compile_cache()
    workers = args.workers
    if workers is None:
        workers = (2 if jax.default_backend() == "cpu"
                   else jax.local_device_count())
    mode = ContextMode(args.mode)
    mgr = PCMManager(mode=mode, n_workers=workers)
    recipe = make_recipe(f"{args.arch}.ctx", build_context,
                         (args.arch, 4, 128, args.megastep, args.full_config,
                          args.seed))
    template = fever.PROMPT_CANDIDATES[args.prompt]

    @context_app(recipe=recipe, manager=mgr, n_items=args.batch_size)
    def verify_batch(indices):
        ctx_engine = load_context("engine")
        tok = load_context("tokenizer")
        claims = fever.claim_batch(indices)
        prompts = [tok.encode(fever.render_prompt(c, template))
                   for c in claims]
        outs = ctx_engine.generate(prompts, max_new_tokens=2)
        preds = [o[0] if o else -1 for o in outs]
        golds = [LABEL_TOKENS[c.label] for c in claims]
        return [int(p == g) for p, g in zip(preds, golds)]

    t0 = time.monotonic()
    futs = []
    n_batches = (args.claims + args.batch_size - 1) // args.batch_size
    for b in range(n_batches):
        idx = list(range(b * args.batch_size,
                         min((b + 1) * args.batch_size, args.claims)))
        futs.append(verify_batch(idx))
        if args.preempt_after and b == args.preempt_after:
            victim = next(iter(mgr.workers))
            print(f"[serve] preempting {victim}")
            mgr.preempt_worker(victim)
            mgr.add_worker()

    correct = sum(sum(f.result()) for f in futs)
    dt = time.monotonic() - t0
    st = mgr.stats()
    print(f"[serve] mode={args.mode} claims={args.claims} "
          f"accuracy={correct / max(1, args.claims):.3f} "
          f"wall={dt:.1f}s cold={st['cold_invocations']} "
          f"warm={st['warm_invocations']} "
          f"context_build={st['context_build_seconds']:.1f}s")


if __name__ == "__main__":
    main()
