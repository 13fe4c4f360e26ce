"""What the program's own spans read (``bench/spans.py``): the engine's
host time per step, the runtime's gap between task functions, and idle
gaps named by the innermost span, on hand-made events with spans on two
thread lines, and the spans of a trace recorded here."""

import threading

import jax
import pytest

MS = 1_000_000
W, C = "/host:CPU#0", "/host:CPU#1"     # the worker's line, another line


def _bench_events():
    # the window 0..100 ms; device busy 20..25, 38..44 and 80..100
    return {
        "spans": [("bench.window", 0, 100 * MS),
                  ("bench.task_body", 10 * MS, 60 * MS),
                  ("bench.generate", 20 * MS, 50 * MS),
                  ("bench.task_body", 70 * MS, 100 * MS),
                  ("bench.await_answers", 0, 100 * MS)],
        "device_modules": {"/device:TPU:0": [
            ("jit__shared_prefill_impl(7)", 20 * MS, 44 * MS)]},
        "device_ops": {"/device:TPU:0": [
            ("%fusion.1 = bf16[8]{0} fusion(x), kind=kCustom", 20 * MS,
             25 * MS),
            ("%fusion.2 = bf16[8]{0} fusion(x), kind=kLoop", 38 * MS,
             44 * MS),
            ("%fusion.1 = bf16[8]{0} fusion(x), kind=kCustom", 80 * MS,
             100 * MS)]},
    }


def _program_spans():
    ms = lambda n, line, s, e: (n, line, s * MS, e * MS)
    return [
        ms("pcm.submit", C, 1, 2), ms("pcm.submit", C, 62, 63),
        ms("pcm.task", W, 10, 60), ms("pcm.fn", W, 11, 59),
        ms("engine.step", W, 20, 50),
        ms("engine.admit", W, 20, 42), ms("engine.prefill", W, 21, 22),
        ms("engine.sync", W, 22, 40),
        ms("engine.decode", W, 42, 50), ms("engine.megastep", W, 43, 44),
        ms("engine.sync", W, 44, 48),
        ms("pcm.task", W, 70, 100), ms("pcm.fn", W, 72, 99),
        ms("engine.step", W, 75, 95), ms("engine.sync", W, 80, 90),
        # another thread's spans: a sync inside the worker's first step
        # and a task function between the worker's two
        ms("engine.sync", C, 40, 42), ms("pcm.fn", C, 61, 62),
        # a step that ends after the window closes is not counted
        ms("engine.step", W, 99, 130),
    ]


def test_engine_host_ms_subtracts_its_own_lines_syncs():
    from bench import spans
    # step 20..50 less syncs 22..40 and 44..48 = 8; step 75..95 less
    # 80..90 = 10; C's sync 40..42 is not the worker's
    assert spans.engine_host_ms(_program_spans(), 0, 100 * MS) == 9.0


def test_runtime_gap_ms_reads_the_workers_task_functions():
    from bench import spans
    # the worker's pcm.fn 11..59 then 72..99; C's lone pcm.fn 61..62
    # makes no gap of its own and splits none of the worker's
    assert spans.runtime_gap_ms(_program_spans(), 0, 100 * MS) == 13.0
    assert spans.runtime_gap_ms(_program_spans(), 0, 60 * MS) is None


def test_idle_gap_inside_a_sync_is_named_by_it():
    from bench import spans
    gaps = spans.idle_gaps(_bench_events(), _program_spans(), 0, 100 * MS)
    # 44..80 (mid 62: C's pcm.submit), 0..20 (mid 10: the first task
    # body, the worker's pcm.task being as long), 25..38 (mid 31: the
    # worker's prefill sync, inside bench.generate)
    assert [n for n, _ in gaps] == ["pcm.submit", "bench.task_body",
                                    "engine.sync"]
    assert [s for _, s in gaps] == pytest.approx([0.036, 0.02, 0.013])


def test_without_program_spans_the_gaps_read_as_the_benchmarks():
    from bench import spans, tracing
    ev = _bench_events()
    assert (spans.idle_gaps(ev, [], 0, 100 * MS)
            == tracing.reduce(ev)["idle_gaps"])
    assert spans.engine_host_ms([], 0, 100 * MS) is None
    assert spans.runtime_gap_ms([], 0, 100 * MS) is None


def test_summary_of_the_window():
    from bench import spans
    out = spans.summarize(_bench_events(), _program_spans())
    assert out["engine_host_ms"] == 9.0 and out["runtime_gap_ms"] == 13.0
    assert out["span_s"]["engine.step"] == [3, pytest.approx(0.051)]
    ev = _bench_events()
    ev["spans"] = ev["spans"][1:]
    assert spans.summarize(ev, _program_spans()) is None


def test_program_spans_of_a_recorded_trace(tmp_path):
    from bench import spans, tracing
    jax.profiler.start_trace(str(tmp_path), profiler_options=tracing.options())
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        with jax.profiler.TraceAnnotation("pcm.submit", task_id="t00001"):
            pass

        def worker():
            for step in range(2):
                with jax.profiler.TraceAnnotation("pcm.fn"):
                    with jax.profiler.TraceAnnotation("engine.step",
                                                      step=step):
                        with jax.profiler.TraceAnnotation("engine.sync"):
                            jax.device_get(jax.numpy.ones(4) + step)
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    jax.profiler.stop_trace()
    path = tracing.find_xplane(str(tmp_path))
    got = spans.read_program_spans(path)
    assert sorted(n for n, _, _, _ in got) == sorted(
        ["pcm.submit"] + ["pcm.fn", "engine.step", "engine.sync"] * 2)
    (client,) = {line for n, line, _, _ in got if n == "pcm.submit"}
    (worker_line,) = {line for n, line, _, _ in got if n == "pcm.fn"}
    assert client != worker_line
    out = spans.summarize(tracing.read_events(path), got)
    assert out["engine_host_ms"] >= 0 and out["runtime_gap_ms"] >= 0
    assert out["span_s"]["pcm.fn"][0] == 2
