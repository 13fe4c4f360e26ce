"""Benchmark entry: one cell of ``BENCHMARK.json`` on this machine's chip.

  python3 bench/run.py --workload smollm2-1.7b.factcheck --seed 7 \\
      --seconds 20 --trace 0

Run from the root of a checkout. ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a profiled
window. The last line of standard output is the result object; the last
lines of standard error give each number compared beside its limit. A
machine without a TPU, or with a device that ``bench/peaks.json`` does not
list, gets an error and no result.

Compiled programs are kept in ``.compile_cache/`` in the checkout (JAX's
persistent cache and the engine's serialized executables), so only the
first run of a cell in a checkout compiles.
"""

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _setup_imports() -> None:
    """This checkout's benchmark and program, and its compile cache."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        ROOT, ".compile_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs in /tmp
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"no program to measure: {src}/repro is missing")
    sys.path[:0] = [ROOT, src]
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    import repro
    where = [os.path.abspath(p) for p in repro.__path__]
    if where != [os.path.join(src, "repro")]:
        raise SystemExit(f"repro imported from {where}, not {src}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _setup_imports()
    from bench import harness
    result = harness.measure(ROOT, args.workload, args.seed, args.seconds,
                             bool(args.trace), T_PROCESS)
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
