"""Runs one cell of ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by its name:

  configuration   the ``file`` its ``configs`` entry names; its
                  ``architecture`` names the module in
                  ``bench/architectures/<architecture>.py`` that holds
                  the reference, weight layout and work counts
  traffic mix     ``bench/traffic/<traffic>.json``; its ``loop`` names the
                  task loop in ``bench/loops/<loop>.py``
  per-layer metric ``bench/metrics/<name>.py``, whose ``read(reading)``
                  returns the value or None when it finds nothing to read
  limits          ``bench/limits/<workload>.json``

A run: check the device; set-up (the PCM context is built by its recipe,
the loop warms every shape the window uses); the window, traced with
``--trace 1``; the program's state is freed; the comparison with the
plain reference; the result line.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, Optional

from bench.modules import load_module

SPEC = "BENCHMARK.json"


# ------------------------------------------------------------- the spec --
def load_spec(root: str) -> Dict:
    with open(os.path.join(root, SPEC)) as f:
        return json.load(f)


def entry(spec: Dict, key: str, name: str) -> Dict:
    for e in spec[key]:
        if e["name"] == name:
            return e
    raise KeyError(f"{SPEC} has no {key} entry named {name!r}")


def applies(metric: Dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


# ------------------------------------------------------------- the chip --
def require_device(chips: int) -> Dict:
    """The accelerator this run measures; anything else is an error."""
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(f"no TPU: JAX reports {d.platform} "
                         f"({d.device_kind}); this benchmark measures the "
                         f"chip only")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX reports "
                         f"{len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def peaks_for(root: str, kind: str) -> Dict:
    table = load_json(os.path.join(root, "bench", "peaks.json"))
    if kind not in table["devices"]:
        raise SystemExit(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


def memory_peak_bytes(chips: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


# -------------------------------------------------------------- the run --
@dataclasses.dataclass
class Run:
    """What a loop drives: the client, the context and the claims."""
    client: Any
    handle: Any
    config: Dict
    traffic: Dict
    stream: Any

    @property
    def slots(self) -> int:
        return int(self.config["serving"]["slots"])

    @property
    def max_new(self) -> int:
        return int(self.traffic["max_new_tokens"])

    def submit(self, first: int, n: int):
        from bench import tasks
        prompts = self.stream.batch(first, n)
        return prompts, self.client.submit(
            tasks.answer_claims, prompts, self.max_new, context=self.handle)


@dataclasses.dataclass
class Reading:
    """What a per-layer metric's reader is given."""
    config: Dict
    window: Dict
    stats: Dict            # EngineStats counters over the window
    trace: Optional[Dict]  # bench.tracing.reduce of the traced window
    peaks: Dict


def _stats_delta(before: Dict, after: Dict) -> Dict:
    return {k: after[k] - before[k] for k in after
            if isinstance(after[k], (int, float))
            and not isinstance(after[k], bool)}


class CompileCounter:
    """Counts the backend compiles (persistent-cache loads among them)
    that start while ``on`` is set: the window should hold none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event: str, duration: float, **_) -> None:
        if self.on and event == self.EVENT:
            self.count += 1


_COUNTER: Optional[CompileCounter] = None


def compile_counter() -> CompileCounter:
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = CompileCounter()
    return _COUNTER


def _recipe(c: Dict, seed: int):
    from repro.core import make_recipe
    from bench import model
    return make_recipe(f"{os.path.basename(c['file'])}.bench",
                       model.build_context, (c["file"], int(seed), c["root"]),
                       **model.footprint(c))


def measure(root: str, workload: str, seed: int, seconds: float,
            trace: bool, t_process: float,
            control: bool = False) -> Dict:
    """One run of ``workload``. Returns the result object, with the
    numbers compared under ``compared`` (its last key)."""
    from bench import architectures, check, claims, model, tracing
    spec = load_spec(root)
    cell = entry(spec, "workloads", workload)
    c = model.load_config(os.path.join(
        root, entry(spec, "configs", cell["config"])["file"]), root)
    info = require_device(int(cell["chips"]))
    peaks = peaks_for(root, info["kind"])
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     f"{cell['traffic']}.json"))
    loop = load_module(os.path.join(root, "bench", "loops",
                                    f"{traffic['loop']}.py"), traffic["loop"])
    limits = check.load_limits(root, workload)
    readers = {m["name"]: load_module(
        os.path.join(root, "bench", "metrics", f"{m['name']}.py"), m["name"])
        for m in spec["per_layer"] if applies(m, workload)} if trace else {}
    wanted = [m["name"] for m in spec["end_to_end"] if applies(m, workload)]

    import jax
    from repro.core import ContextMode, PCMClient, PCMManager
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    # refuse a mismatch before building
    architectures.of(c).program_config(c)
    spill = os.path.join(root, ".pcm_spill")
    trace_dir = os.path.join(root, ".bench_trace")
    mgr = PCMManager(mode=ContextMode.FULL, n_workers=1, spill_dir=spill)
    client = PCMClient(backend=mgr)
    try:
        handle = client.context(_recipe(c, seed))
        run = Run(client=client, handle=handle, config=c, traffic=traffic,
                  stream=claims.ClaimStream(traffic, seed, c["vocab_size"]))
        loop.prepare(run)
        from bench import tasks
        before = client.submit(tasks.engine_stats, context=handle).result()
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=tracing.options())
        counter = compile_counter()
        counter.on, counter.count = True, 0
        with jax.profiler.TraceAnnotation(tracing.WINDOW):
            win = loop.window(run, seconds)
        counter.on = False
        if trace:
            jax.profiler.stop_trace()
        setup_s = win["open"] - t_process
        after = client.submit(tasks.engine_stats, context=handle).result()
        e2e = loop.end_to_end(run, win)
        peak = memory_peak_bytes(int(cell["chips"]))
        build_s = mgr.stats().get("context_build_seconds")
    finally:
        client.shutdown()
        del client, mgr
        shutil.rmtree(spill, ignore_errors=True)
    run = handle = None
    gc.collect()
    live = sum(a.nbytes for a in jax.live_arrays())

    e2e["setup_s"] = setup_s
    missing = [m for m in wanted if m not in e2e]
    if missing:
        raise RuntimeError(f"loop {traffic['loop']!r} reports no {missing}")
    metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
               for m in spec["end_to_end"] if m["name"] in wanted}

    device = dict(info, memory_peak_bytes=peak)
    result: Dict[str, Any] = {}
    if trace:
        red = tracing.reduce(tracing.read_events(
            tracing.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        if red is None:
            raise RuntimeError("the trace holds no device operation inside "
                               "the window")
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        reading = Reading(config=c, window=win,
                          stats=_stats_delta(before, after), trace=red,
                          peaks=peaks)
        metrics = {}
        for m in spec["per_layer"]:
            if m["name"] in readers:
                v = readers[m["name"]].read(reading)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}

    # ---- correctness, once the program's state is gone
    answers = win["answers"]
    max_new, V = int(traffic["max_new_tokens"]), c["vocab_size"]
    answered = [(p, g) for p, g, _ in answers if g]
    malformed = sum(1 for _, g in answered
                    if len(g) != max_new or not all(0 <= t < V for t in g))
    picked = check.sample([a for a in answered if len(a[1]) == max_new],
                          int(traffic["check_claims"]), seed)
    t_ref = time.monotonic()
    gap = check.logit_gap(c, seed, picked, control=control)
    compared = {
        "unanswered": {"value": win["attempted"] - len(answered), "limit": 0},
        "malformed": {"value": malformed, "limit": 0},
    }
    for name, lim in limits.items():
        compared[name] = {"value": gap[name], "limit": lim["limit"]}
    if control:
        # the control in the program's place: the same positions of the
        # same prompts and served tokens, read at the tokens it puts first
        result["control_compared"] = dict(compared, **{
            name: {"value": gap["control_" + name], "limit": lim["limit"]}
            for name, lim in limits.items()})
        result["control_correct"] = check.verdict(result["control_compared"])
    result.update(
        correct=check.verdict(compared),
        attempted=win["attempted"],
        failed=win["attempted"] - len(answered) + malformed,
        metrics=metrics, device=device)
    result["info"] = {
        "setup_s": setup_s,
        "build_s": build_s,
        **{k: v for k, v in gap.items() if not k.startswith("control")},
        "reference_s": time.monotonic() - t_ref,
        "live_bytes_before_reference": live,
        "window_s": win["close"] - win["open"],
        "window_compiles": counter.count,
        "task_body_s": [t1 - t0 for t0, t1, _ in win["tasks"]],
        "engine": _stats_delta(before, after)}
    if control:
        result["info"].update({k: v for k, v in gap.items()
                               if k.startswith("control")})
    result["compared"] = compared
    return result


def report(result: Dict, out=None, err=None) -> None:
    """Print the numbers compared, each beside its limit, as the last
    lines on standard error; then the result as the last line of
    standard output (``compared`` is its last key)."""
    out, err = out or sys.stdout, err or sys.stderr
    print("info " + json.dumps(result.get("info"), default=str), file=err)
    for name, v in result["compared"].items():
        print(f"compared {name} = {v['value']!r} (limit {v['limit']!r})",
              file=err)
    err.flush()
    print(json.dumps(result), file=out)
    out.flush()
