"""Hypothesis property tests on system invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.core import (ContextAwareScheduler, ContextMode, ContextRecipe,
                        ContextStore, Task, Tier)
from repro.core.context import GB
from repro.data import HashTokenizer
from repro.models.attention import blockwise_attention
from repro.serving.sampler import sample

SETTINGS = dict(deadline=None, max_examples=25,
                suppress_health_check=[HealthCheck.too_slow])


# ------------------------------------------------------------ store --------
@given(st.lists(st.tuples(st.integers(0, 9), st.integers(1, 8)),
                min_size=1, max_size=40))
@settings(**SETTINGS)
def test_store_capacity_invariant(ops):
    """No tier ever exceeds capacity, whatever the admit sequence."""
    s = ContextStore(device_bytes=10 * GB)
    for i, (key_id, size_gb) in enumerate(ops):
        s.admit(f"k{key_id}", Tier.DEVICE, size_gb * GB, now=float(i))
        assert s.used(Tier.DEVICE) <= 10 * GB


@given(st.lists(st.integers(0, 5), min_size=1, max_size=30))
@settings(**SETTINGS)
def test_store_admitted_resident_until_evicted(keys):
    s = ContextStore(device_bytes=100 * GB)
    for i, k in enumerate(keys):
        s.admit(f"k{k}", Tier.DEVICE, 1 * GB, now=float(i))
        assert s.has(f"k{k}", Tier.DEVICE)


# --------------------------------------------------------- scheduler -------
@given(st.lists(st.sampled_from(["join", "leave", "submit", "done"]),
                min_size=5, max_size=60),
       st.integers(0, 2 ** 31 - 1))
@settings(**SETTINGS)
def test_scheduler_liveness_under_random_events(events, seed):
    """Whatever the event order: no task is lost, no worker runs two tasks,
    and draining the system completes everything submitted."""
    rng = np.random.RandomState(seed)
    s = ContextAwareScheduler(mode=ContextMode.FULL)
    recipe = ContextRecipe(name="r")
    t = 0.0
    n_sub = 0
    for ev in events:
        t += 1.0
        if ev == "join":
            s.on_worker_join(f"w{rng.randint(100)}", t)
        elif ev == "leave" and s.workers:
            s.on_worker_leave(rng.choice(sorted(s.workers)), t)
        elif ev == "submit":
            s.submit(Task(task_id=f"t{n_sub}", recipe=recipe), t)
            n_sub += 1
        elif ev == "done" and s.running:
            tid = sorted(s.running)[0]
            wid = s.running[tid][0]
            s.on_task_done(wid, tid, t)
        # invariant: a worker runs at most one task
        workers_running = [w for w, _ in s.running.values()]
        assert len(workers_running) == len(set(workers_running))
    # drain: add a worker and finish everything
    s.on_worker_join("closer", t + 1)
    guard = 0
    while not s.all_done():
        guard += 1
        assert guard < 10 * n_sub + 50, "scheduler failed to drain"
        if s.running:
            tid = sorted(s.running)[0]
            wid = s.running[tid][0]
            t += 1.0
            s.on_task_done(wid, tid, t)
        else:
            break
    assert s.all_done()
    done_primaries = {c.task_id for c in s.completions}
    assert done_primaries == {f"t{i}" for i in range(n_sub)}


# --------------------------------------------------------- attention -------
@given(st.integers(1, 3), st.integers(1, 4).map(lambda x: 16 * x),
       st.integers(1, 2), st.sampled_from([16, 32]),
       st.sampled_from([0, 8]), st.integers(8, 32))
@settings(**SETTINGS)
def test_blockwise_attention_matches_naive(B, S, H, D, window, chunk):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(B * S + H), 3)
    q = jax.random.normal(k1, (B, S, H, D))
    k = jax.random.normal(k2, (B, S, H, D))
    v = jax.random.normal(k3, (B, S, H, D))
    scale = D ** -0.5
    out = blockwise_attention(q, k, v, scale=scale, causal=True,
                              window=window, chunk=chunk)
    from repro.kernels.ref import flash_attention_ref
    exp = flash_attention_ref(q.swapaxes(1, 2).reshape(B * H, S, D),
                              k.swapaxes(1, 2).reshape(B * H, S, D),
                              v.swapaxes(1, 2).reshape(B * H, S, D),
                              causal=True, window=window, scale=scale)
    exp = exp.reshape(B, H, S, D).swapaxes(1, 2)
    assert float(jnp.max(jnp.abs(out - exp))) < 1e-4


# ----------------------------------------------------------- sampler -------
@given(st.integers(2, 6), st.integers(4, 64), st.integers(0, 2 ** 31 - 1))
@settings(**SETTINGS)
def test_greedy_sampling_is_argmax(B, V, seed):
    logits = jax.random.normal(jax.random.PRNGKey(seed), (B, V))
    toks = sample(logits, jax.random.PRNGKey(0),
                  jnp.zeros((B,)), vocab_size=V)
    assert (np.asarray(toks) == np.asarray(jnp.argmax(logits, -1))).all()


@given(st.integers(2, 6), st.integers(8, 64))
@settings(**SETTINGS)
def test_vocab_padding_never_sampled(B, V):
    logits = jnp.zeros((B, V + 16))
    logits = logits.at[:, V:].set(100.0)  # tempting padded logits
    toks = sample(logits, jax.random.PRNGKey(1),
                  jnp.full((B,), 2.0), vocab_size=V)
    assert (np.asarray(toks) < V).all()


# --------------------------------------------------------- tokenizer -------
@given(st.lists(st.sampled_from("abcdefgh xyz"), min_size=1, max_size=40))
@settings(**SETTINGS)
def test_tokenizer_ids_in_range(chars):
    text = "".join(chars)
    tok = HashTokenizer(512)
    for t in tok.encode(text):
        assert 0 <= t < 512
