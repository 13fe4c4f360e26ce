"""Readings that a cell's limits are set from, on the chip, in one process.

  python3 bench/calibrate.py --workload smollm2-1.7b.factcheck \\
      --seeds 101,102,...,112 --control-seeds 3 --seconds 4

For each seed this makes a run of the cell as ``bench/run.py`` would, at
the cell's own sizes and load but with a short window, and reads the
program's logit gaps (``bench.check``). For the first ``--control-seeds``
seeds it also reads the control's: the reference in the next precision
below the configuration's, at the same positions of the same prompts and
served tokens, put in the program's place and judged by the harness's
own verdict against the cell's limits (``control_correct``, which has to
come out false). The lower reading is the largest program gap, the upper
the smallest control gap; PERF.md gives both and the limit set between
them. The benchmark's own runs never run the control.

One JSON line per seed, then a summary line, on standard output; with
``--out``, the same lines are written to that file too.
"""

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run as bench_run  # noqa: E402


def calibrate(root, workload, seeds, control_seeds, seconds, measure,
              emit=print):
    """Program gaps on every seed, control gaps on the first
    ``control_seeds``; returns the summary."""
    program, control = [], []
    t = T_PROCESS
    for i, seed in enumerate(seeds):
        res = measure(root, workload, seed, seconds, False, t,
                      control=i < control_seeds)
        t = time.monotonic()
        info = res["info"]
        line = {"workload": workload, "seed": seed, "correct": res["correct"],
                **{k: info[k] for k in ("logit_gap", "mean_gap", "flip_share",
                                        "near_tie_share", "tokens_compared",
                                        "reference_s", "window_compiles",
                                        "task_body_s")}}
        if "control_correct" in res:
            line.update({k: v for k, v in info.items()
                         if k.startswith("control")},
                        control_correct=res["control_correct"])
            control.append(line)
        program.append(line)
        emit(json.dumps(line))
    summary = {"workload": workload, "seeds": len(seeds)}
    summary["control_correct"] = [c["control_correct"] for c in control]
    for name, ctl in (("logit_gap", "control_logit_gap"),
                      ("mean_gap", "control_mean_gap"),
                      ("flip_share", "control_flip_share")):
        summary[name] = {
            "lower": max(p[name] for p in program),
            "upper": min(c[ctl] for c in control) if control else None,
            "program": [p[name] for p in program],
            "control": [c[ctl] for c in control]}
    emit(json.dumps(summary))
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bench_run._setup_imports()
    from bench import harness
    out = open(args.out, "a") if args.out else None

    def emit(s):
        print(s, flush=True)
        if out:
            print(s, file=out, flush=True)

    calibrate(bench_run.ROOT, args.workload,
              [int(s) for s in args.seeds.split(",")], args.control_seeds,
              args.seconds, harness.measure, emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
