"""Reduction of a profiler trace to the device's busy time, the time of
each executable, and the idle gaps named by what the host was doing.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes. Device
planes are named ``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds
one event per operation that ran, and the ``XLA Modules`` line one event
per executable run, named after its jitted function. The host's planes
hold the benchmark's own ``TraceAnnotation`` spans (names starting with
``bench.``), on the same clock. The measured window is the span named
``bench.window``.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
TOP = 10

Interval = Tuple[int, int]


def options():
    """Profiler options of a traced run: device operations and the host's
    TraceMe spans (the benchmark's among them), without the Python
    function tracer, which would record every call of the engine's host
    code and slow it."""
    import jax
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    o.host_tracer_level = 2
    return o


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def module_name(name: str) -> str:
    """``jit__paged_megastep_impl(12)`` -> ``_paged_megastep_impl``."""
    name = re.sub(r"\(\d+\)$", "", name)
    return re.sub(r"^jit_", "", name)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def clip_events(events, lo: int, hi: int):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def self_times(events):
    """``(name, start, self_ns)`` per event: its duration less the part
    that events nested inside it cover (a ``while`` op holds its body's
    ops on the same line)."""
    out = []
    stack: list = []          # [index into out, end]
    for n, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            parent = out[stack[-1][0]]
            out[stack[-1][0]] = (parent[0], parent[1],
                                 parent[2] - (min(e, stack[-1][1]) - s))
        out.append((n, s, e - s))
        stack.append([len(out) - 1, e])
    return out


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def read_events(path: str) -> Dict:
    """The events the reduction needs, as plain tuples:
    ``device_ops[plane] = [(name, start_ns, end_ns)]``, likewise
    ``device_modules``, and ``spans`` = the host's ``bench.`` spans."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    ops: Dict[str, list] = {}
    modules: Dict[str, list] = {}
    spans: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                evs = [(e.name, int(e.start_ns),
                        int(e.start_ns + e.duration_ns))
                       for e in line.events]
                if line.name == "XLA Ops":
                    ops[plane.name] = evs
                elif line.name == "XLA Modules":
                    modules[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, int(e.start_ns),
                              int(e.start_ns + e.duration_ns))
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return {"device_ops": ops, "device_modules": modules, "spans": spans}


def op_name(name: str) -> str:
    """``%fusion.271 = bf16[16777216]{0:T(1024)} fusion(...), kind=kCustom``
    -> ``%fusion.271 bf16[16777216] kCustom``: the operation, its result
    type without layout, and its fusion kind."""
    m = re.match(r"(%[\w.\-]+) = (\()?([a-z0-9]+\[[\d,]*\])?", name)
    if not m:
        return name[:80]
    kind = re.search(r"kind=(k\w+)", name)
    return " ".join(x for x in (m.group(1),
                                "tuple" if m.group(2) else m.group(3),
                                kind and kind.group(1)) if x)


def host_activity(spans: Sequence[Tuple[str, int, int]], t: int) -> str:
    """The innermost benchmark span open at ``t``."""
    open_ = [(e - s, n) for n, s, e in spans
             if s <= t < e and n != WINDOW]
    return min(open_)[1] if open_ else "no bench span (runtime between tasks)"


def reduce(events: Dict, top: int = TOP) -> Optional[Dict]:
    """Busy seconds, window seconds, per-executable device seconds,
    the longest device operations and idle gaps, over the window.
    None when the trace holds no window or no device operation."""
    wins = [(s, e) for n, s, e in events["spans"] if n == WINDOW]
    planes = [p for p, evs in events["device_ops"].items() if evs]
    if not wins or not planes:
        return None
    lo, hi = wins[0]
    busy_s = []
    op_s: Dict[str, float] = collections.defaultdict(float)
    gap_list: List[Tuple[float, str]] = []
    for p in planes:
        ivs = clip([(s, e) for _, s, e in events["device_ops"][p]], lo, hi)
        u = union(ivs)
        busy_s.append(sum(e - s for s, e in u) / 1e9)
        mods = sorted(events["device_modules"].get(p, []),
                      key=lambda m: m[1])
        starts = [m[1] for m in mods]
        for n, s, e in self_times(clip_events(events["device_ops"][p],
                                              lo, hi)):
            i = bisect.bisect_right(starts, s) - 1
            mod = (module_name(mods[i][0])
                   if i >= 0 and s < mods[i][2] else "?")
            op_s[f"{mod}: {op_name(n)}"] += e / 1e9
        for s, e in gaps(u, lo, hi):
            gap_list.append(((e - s) / 1e9,
                             host_activity(events["spans"], (s + e) // 2)))
    module_s: Dict[str, float] = collections.defaultdict(float)
    module_n: Dict[str, int] = collections.defaultdict(int)
    for p, evs in events["device_modules"].items():
        for n, s, e in evs:
            c = clip([(s, e)], lo, hi)
            if c:
                module_s[module_name(n)] += (c[0][1] - c[0][0]) / 1e9
                module_n[module_name(n)] += 1
    gap_list.sort(key=lambda g: -g[0])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_s) / len(busy_s),
        "module_s": dict(module_s),
        "module_n": dict(module_n),
        "device_ops": [[n, s] for n, s in sorted(
            op_s.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, s] for s, n in gap_list[:top]],
    }


def module_seconds(red: Dict, needle: str) -> Tuple[float, int]:
    """Device seconds and run count of the executables whose jitted
    function's name contains ``needle``."""
    s = sum(v for k, v in red["module_s"].items() if needle in k)
    n = sum(v for k, v in red["module_n"].items() if needle in k)
    return s, n
