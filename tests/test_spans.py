"""The program's own profiler spans and executable names: a CPU trace of
a tiny paged engine driven through ``PCMClient`` holds the runtime's and
the engine's spans, nested on the worker's thread, with their stats; the
megastep executables carry their implementation's name."""

import collections

import jax
import pytest

from repro.core import (ContextMode, PCMClient, PCMManager, load_context,
                        make_recipe)


@pytest.fixture(scope="module")
def smol():
    from repro.configs import get_reduced_config
    from repro.models import build_model
    cfg = get_reduced_config("smollm2-1.7b")
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _engine(model, params, paged=True):
    from repro.serving import InferenceEngine
    kw = dict(paged=True, page_size=8) if paged else {}
    return InferenceEngine(model, params, slots=4, cache_len=64,
                           prefill_buckets=(16,), megastep=4, **kw)


def _answer(prompts):
    return load_context("engine").generate(prompts, max_new_tokens=5)


def _program_events(trace_dir):
    """``(plane, line) -> [(name, start_ns, end_ns, stats)]`` of the
    program's spans (``pcm.*`` and ``engine.*``) in a recorded trace."""
    import glob
    import os
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    lines = collections.defaultdict(list)
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(("pcm.", "engine.")):
                    lines[(plane.name, i)].append(
                        (e.name, int(e.start_ns),
                         int(e.start_ns + e.duration_ns), dict(e.stats)))
    return lines


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_worker_spans_nest_with_their_stats(smol, tmp_path):
    cfg, model, params = smol
    prompts = [[5 + i, 9, 11 + i, 3] for i in range(3)]
    mgr = PCMManager(mode=ContextMode.FULL, n_workers=1)
    client = PCMClient(backend=mgr)
    try:
        handle = client.context(make_recipe(
            "spans", lambda: {"engine": _engine(model, params)},
            host_bytes=0))
        client.submit(_answer, prompts, context=handle).result()  # warm
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        fut = client.submit(_answer, prompts, context=handle)
        out = fut.result()
        jax.profiler.stop_trace()
    finally:
        client.shutdown()
    assert len(out) == 3 and all(len(g) == 5 for g in out)

    lines = _program_events(str(tmp_path))
    (submit,) = [e for evs in lines.values() for e in evs
                 if e[0] == "pcm.submit"]
    (worker,) = [k for k, evs in lines.items()
                 if any(e[0] == "pcm.task" for e in evs)]
    evs = lines[worker]
    by = collections.defaultdict(list)
    for e in evs:
        by[e[0]].append(e)
    (task,) = by["pcm.task"]
    (fn,) = by["pcm.fn"]
    assert submit[3] == {"task_id": fut.task_id} == task[3]
    assert _inside(fn, task) and _inside(by["pcm.context"][0], task)
    steps = by["engine.step"]
    assert steps and all(_inside(s, fn) for s in steps)
    assert sorted(s[3]["step"] for s in steps) == list(
        range(steps[0][3]["step"], steps[0][3]["step"] + len(steps)))
    syncs = by["engine.sync"]
    assert {s[3]["of"] for s in syncs} == {"prefill", "megastep"}
    assert all(any(_inside(y, s) for s in steps) for y in syncs)
    (admit,) = by["engine.admit"]
    (prefill,) = by["engine.prefill"]
    assert _inside(prefill, admit)
    assert prefill[3]["bucket"] == 16 and prefill[3]["n"] == 3
    assert all(any(_inside(m, d) for d in by["engine.decode"])
               for m in by["engine.megastep"])
    assert all("npages" in m[3] for m in by["engine.megastep"])
    # warm: the traced task compiled nothing and built nothing
    assert "engine.compile" not in by and "pcm.build" not in by


def test_compile_span_marks_a_true_compile(smol, tmp_path):
    cfg, model, params = smol
    eng = _engine(model, params)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    eng.generate([[5, 9, 11]], max_new_tokens=2)
    jax.profiler.stop_trace()
    compiles = [e for evs in _program_events(str(tmp_path)).values()
                for e in evs if e[0] == "engine.compile"]
    assert len(compiles) == eng.stats.compiles > 0
    assert {e[3]["key"] for e in compiles} == {repr(k) for k in eng._exe}


@pytest.mark.parametrize("paged,name", [(True, "_paged_megastep_impl"),
                                        (False, "_megastep_impl")])
def test_megastep_executable_is_named(smol, paged, name):
    cfg, model, params = smol
    eng = _engine(model, params, paged=paged)
    exe = (eng._paged_megastep_exe(1) if paged
           else eng._megastep_exe(eng.cache_len, False))
    head = exe.as_text().splitlines()[0]
    assert head.startswith(f"HloModule jit_{name}"), head
    assert "unknown" not in head
