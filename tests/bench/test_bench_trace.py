"""The reduction from a profiler trace to busy time, executable time and
named idle gaps: on hand-made device events (the CPU has no device plane)
and on the host spans of a trace recorded here."""

import threading

import jax
import jax.numpy as jnp

MS = 1_000_000


def _events():
    # window 0..100 ms; two executables with ops; the host in a task body
    # 10..60 and 70..100, in generate 20..50
    return {
        "spans": [("bench.window", 0, 100 * MS),
                  ("bench.task_body", 10 * MS, 60 * MS),
                  ("bench.generate", 20 * MS, 50 * MS),
                  ("bench.task_body", 70 * MS, 100 * MS),
                  ("bench.await_answers", 0, 100 * MS)],
        "device_modules": {"/device:TPU:0": [
            ("jit__shared_prefill_impl(7)", 20 * MS, 40 * MS),
            ("jit__unknown(9)", 45 * MS, 50 * MS),
            ("jit__shared_prefill_impl(7)", 80 * MS, 110 * MS)]},
        "device_ops": {"/device:TPU:0": [
            ("%fusion.1 = bf16[8]{0} fusion(x), kind=kCustom", 20 * MS,
             30 * MS),
            ("%fusion.2 = bf16[8]{0} fusion(x), kind=kLoop", 30 * MS,
             40 * MS),
            ("%while.3 = (s32[], bf16[2]) while(x)", 45 * MS, 50 * MS),
            ("%fusion.1 = bf16[8]{0} fusion(x), kind=kCustom", 80 * MS,
             110 * MS)]},
    }


def test_busy_idle_and_executable_time_by_hand():
    from bench import tracing
    red = tracing.reduce(_events())
    assert red["window_s"] == 0.1
    # union of ops inside the window: 20..40, 45..50, 80..100
    assert abs(red["busy_s"] - 0.045) < 1e-12
    assert abs(red["module_s"]["_shared_prefill_impl"] - 0.040) < 1e-12
    assert red["module_n"] == {"_shared_prefill_impl": 2, "_unknown": 1}
    assert tracing.module_seconds(red, "_unknown") == (0.005, 1)
    ops = dict((n, s) for n, s in red["device_ops"])
    assert abs(ops["_shared_prefill_impl: %fusion.1 bf16[8] kCustom"]
               - 0.030) < 1e-12
    assert "_unknown: %while.3 tuple" in ops
    gaps = red["idle_gaps"]
    # 0..20 (await only), 50..80 (mid 65: await only), 40..45 (generate)
    assert [round(s, 9) for _, s in gaps] == [0.03, 0.02, 0.005]
    assert gaps[2][0] == "bench.generate"
    assert gaps[0][0] == "bench.await_answers"
    assert abs(sum(s for _, s in gaps) - 0.055) < 1e-12


def test_no_window_or_no_device_gives_nothing():
    from bench import tracing
    ev = _events()
    assert tracing.reduce(dict(ev, device_ops={})) is None
    assert tracing.reduce(dict(ev, spans=ev["spans"][1:])) is None


def test_breakdown_keeps_ten():
    from bench import tracing
    ev = _events()
    ev["device_ops"]["/device:TPU:0"] = [
        (f"%op.{i} = f32[1]{{0}} add(x)", i * MS, i * MS + MS // 2)
        for i in range(40)]
    red = tracing.reduce(ev)
    assert len(red["device_ops"]) == 10 and len(red["idle_gaps"]) == 10


def test_host_spans_of_a_recorded_cpu_trace(tmp_path):
    from bench import tracing
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path), profiler_options=tracing.options())
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        def body():
            with jax.profiler.TraceAnnotation("bench.task_body"):
                f(x).block_until_ready()
        t = threading.Thread(target=body)
        t.start()
        t.join()
    jax.profiler.stop_trace()
    ev = tracing.read_events(tracing.find_xplane(str(tmp_path)))
    names = [n for n, _, _ in ev["spans"]]
    assert names.count(tracing.WINDOW) == 1 and "bench.task_body" in names
    (w,) = [(s, e) for n, s, e in ev["spans"] if n == tracing.WINDOW]
    (b,) = [(s, e) for n, s, e in ev["spans"] if n == "bench.task_body"]
    assert w[0] <= b[0] < b[1] <= w[1]
    assert ev["device_ops"] == {}
    assert tracing.reduce(ev) is None


def test_self_time_of_nested_ops():
    from bench import tracing
    got = tracing.self_times([("while", 0, 100), ("a", 10, 30),
                              ("b", 40, 60), ("c", 45, 50), ("d", 120, 130)])
    assert {n: t for n, _, t in got} == {"while": 60, "a": 20, "b": 15,
                                        "c": 5, "d": 10}
