"""Run one cell several times, each in a process of its own, and summarise
the spread of every metric.

    python3 benchmarks/chip/repeat.py --workload <name> --seconds 45 \
        --seeds 11 12 13 14 15 16 [--sets 2] [--trace 0] \
        [--out chiprun_out/<dir>]

Each set runs every seed once, in order; ``--sets 2`` repeats the list, so
both sets see the same seeds. This process never imports JAX, so each run
has the chip to itself. Each run's output goes to ``--out``; the summary
(per metric and set: values, median, and the spread, the distance between
the first and third quartiles of ``statistics.quantiles`` over the median)
is printed as JSON.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def spread(values: list) -> float | None:
    if len(values) < 2:
        return None
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2 if q2 else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=os.path.join("chiprun_out", "repeat"))
    ap.add_argument("--timeout", type=float, default=1200)
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    runs = []
    for s in range(args.sets):
        for seed in args.seeds:
            cmd = [sys.executable, os.path.join(HERE, "run.py"),
                   "--workload", args.workload, "--seed", str(seed),
                   "--seconds", str(args.seconds), "--trace",
                   str(args.trace)]
            tag = f"{args.workload}.set{s}.seed{seed}.trace{args.trace}"
            t0 = time.perf_counter()
            try:
                p = subprocess.run(cmd, cwd=ROOT, capture_output=True,
                                   text=True, timeout=args.timeout)
                out, err, rc = p.stdout, p.stderr, p.returncode
            except subprocess.TimeoutExpired as e:
                out, err, rc = e.stdout or "", e.stderr or "", 124
                out = out if isinstance(out, str) else out.decode()
                err = err if isinstance(err, str) else err.decode()
            wall = time.perf_counter() - t0
            with open(os.path.join(args.out, tag + ".out"), "w") as f:
                f.write(out)
            with open(os.path.join(args.out, tag + ".err"), "w") as f:
                f.write(err)
            last = out.strip().splitlines()[-1] if out.strip() else ""
            try:
                res = json.loads(last)
            except ValueError:
                res = None
            runs.append({"set": s, "seed": seed, "rc": rc, "wall_s": wall,
                         "result": res})
            print(json.dumps({"set": s, "seed": seed, "rc": rc,
                              "wall_s": round(wall, 3), "result": res}),
                  flush=True)
    summary: dict = {}
    for s in range(args.sets):
        got = [r["result"] for r in runs if r["set"] == s and r["result"]]
        names = sorted({m for g in got for m in g["metrics"]})
        for m in names:
            v = [g["metrics"][m]["value"] for g in got if m in g["metrics"]]
            summary.setdefault(m, {})[f"set{s}"] = {
                "values": v, "median": statistics.median(v),
                "spread": spread(v)}
    ok = sum(bool(r["result"] and r["result"]["correct"]) for r in runs)
    print(json.dumps({"workload": args.workload, "runs": len(runs),
                      "correct": ok, "summary": summary}), flush=True)
    return 0 if ok == len(runs) else 1


if __name__ == "__main__":
    sys.exit(main())
