"""The program's own spans in a profiler trace, and what they read.

The runtime (``pcm.*``) and the engine (``engine.*``) open
``jax.profiler.TraceAnnotation`` spans on the thread that does the work:
``pcm.task`` > ``pcm.fn`` > ``engine.step`` > ``engine.admit`` /
``engine.decode`` > ``engine.prefill`` / ``engine.megastep`` /
``engine.sync`` on the worker, ``pcm.submit`` on the client. A span is
``(name, line, start_ns, end_ns)``; ``line`` names the host thread it ran
on. From them, over the window:

  engine_host_ms  mean over ``engine.step`` spans of the step's time less
                  the union of its ``engine.sync`` children on its own
                  line: the engine's host work per step, device waits
                  left out
  runtime_gap_ms  mean gap between successive ``pcm.fn`` spans on a
                  worker's line: the runtime's time between one task
                  function and the next
  idle_gaps       the device's idle gaps, each named by the innermost
                  span open at its middle, the benchmark's and the
                  program's alike

Print them for a kept trace (a directory or an ``.xplane.pb``):

  python3 bench/spans.py TRACE
"""

from __future__ import annotations

import collections
import json
import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import tracing  # noqa: E402

PREFIXES = ("pcm.", "engine.")
Span = Tuple[str, str, int, int]


def read_program_spans(path: str) -> List[Span]:
    """The program's spans on the host planes of an ``.xplane.pb``."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out: List[Span] = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            out.extend((e.name, f"{plane.name}#{i}", int(e.start_ns),
                        int(e.start_ns + e.duration_ns))
                       for e in line.events if e.name.startswith(PREFIXES))
    return out


def _inside(spans: Sequence[Span], name: str, lo: int, hi: int):
    return [s for s in spans if s[0] == name and lo <= s[2] and s[3] <= hi]


def engine_host_ms(spans: Sequence[Span], lo: int, hi: int
                   ) -> Optional[float]:
    steps = _inside(spans, "engine.step", lo, hi)
    if not steps:
        return None
    syncs = collections.defaultdict(list)
    for n, line, s, e in spans:
        if n == "engine.sync":
            syncs[line].append((s, e))
    host = []
    for _, line, s, e in steps:
        waits = tracing.union(tracing.clip(syncs[line], s, e))
        host.append(e - s - sum(b - a for a, b in waits))
    return sum(host) / len(host) / 1e6


def runtime_gap_ms(spans: Sequence[Span], lo: int, hi: int
                   ) -> Optional[float]:
    per_line = collections.defaultdict(list)
    for _, line, s, e in _inside(spans, "pcm.fn", lo, hi):
        per_line[line].append((s, e))
    gaps = [b[0] - a[1] for fns in map(sorted, per_line.values())
            for a, b in zip(fns, fns[1:])]
    if not gaps:
        return None
    return sum(gaps) / len(gaps) / 1e6


def idle_gaps(events: Dict, spans: Sequence[Span], lo: int, hi: int,
              top: int = tracing.TOP) -> List[list]:
    """The longest idle gaps of the device planes inside the window, each
    as ``[label, seconds]``."""
    named = list(events["spans"]) + [(n, s, e) for n, _, s, e in spans]
    out = []
    for ops in filter(None, events["device_ops"].values()):
        busy = tracing.union(tracing.clip([(s, e) for _, s, e in ops],
                                          lo, hi))
        out.extend([tracing.host_activity(named, (s + e) // 2),
                    (e - s) / 1e9]
                   for s, e in tracing.gaps(busy, lo, hi))
    return sorted(out, key=lambda g: -g[1])[:top]


def span_seconds(spans: Sequence[Span], lo: int, hi: int) -> Dict:
    """Per span name: count and seconds, clipped to the window."""
    out: Dict[str, list] = {}
    for n, _, s, e in spans:
        if e > lo and s < hi:
            c = out.setdefault(n, [0, 0.0])
            c[0] += 1
            c[1] += (min(e, hi) - max(s, lo)) / 1e9
    return out


def summarize(events: Dict, spans: Sequence[Span]) -> Optional[Dict]:
    """What the program's spans read over the trace's window; None when
    the trace holds no window."""
    wins = [(s, e) for n, s, e in events["spans"] if n == tracing.WINDOW]
    if not wins:
        return None
    lo, hi = wins[0]
    return {"engine_host_ms": engine_host_ms(spans, lo, hi),
            "runtime_gap_ms": runtime_gap_ms(spans, lo, hi),
            "idle_gaps": idle_gaps(events, spans, lo, hi),
            "span_s": span_seconds(spans, lo, hi)}


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        raise SystemExit(__doc__)
    path = argv[0]
    if os.path.isdir(path):
        path = tracing.find_xplane(path)
    out = summarize(tracing.read_events(path), read_program_spans(path))
    if out is None:
        raise SystemExit(f"{path} holds no {tracing.WINDOW} span")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
