"""Runs of one cell, and their spread the way the driver reads it.

    python -m perfbench.spread --workload NAME --seconds S --sets 2 --runs 6
        [--trace-last] [--out chiprun_out/spread]

Each set makes ``--runs`` runs of the same code, run i of every set with
the same seed.  For each end-to-end metric it prints the per-run values,
each set's median and spread -- the distance between the first and third
quartile (``statistics.quantiles(values, n=4)``) as a share of the
median -- and the wider of the sets' spreads.  With fewer than six runs
to a set the spread is also shown scaled by ``RANGE_SCALE`` (the range
of three draws is about two thirds of the range of six).  Every run's
result line is appended to ``<out>/<workload>.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = [2147483659, 3000000019, 1234567891, 2718281829, 3141592653,
         1618033989, 2020202021, 2999999929]
RANGE_SCALE = {3: 1.5, 4: 1.25, 5: 1.1}


def spread(values: List[float]) -> float:
    if len(values) < 2:
        return 0.0
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def one_run(workload: str, seed: int, seconds: float, trace: int
            ) -> Dict[str, Any]:
    proc = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)],
        cwd=ROOT, stdout=subprocess.PIPE, text=True,
    )
    lines = proc.stdout.strip().splitlines()
    for line in lines[:-1]:
        print(line, flush=True)  # e.g. compiles inside the window
    if proc.returncode != 0 or not lines:
        return {"error": f"exit {proc.returncode}", "seed": seed}
    return json.loads(lines[-1])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--trace-last", action="store_true",
                    help="one more run with --trace 1 at the end")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "spread"))
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    log = os.path.join(args.out, args.workload + ".jsonl")
    sets: List[List[Dict[str, Any]]] = []
    for k in range(args.sets):
        runs = []
        for i in range(args.runs):
            r = one_run(args.workload, SEEDS[i], args.seconds, 0)
            r["set"], r["run"], r["seconds"] = k, i, args.seconds
            with open(log, "a") as fh:
                fh.write(json.dumps(r) + "\n")
            # the client's other percentiles ride along as "x:" rows
            for n, v in r.get("extras", {}).items():
                r.get("metrics", {}).setdefault(
                    "x:" + n, {"value": v, "unit": "ms"})
            flat = {n: round(m["value"], 3)
                    for n, m in r.get("metrics", {}).items()}
            print(f"set {k} run {i} seed {SEEDS[i]}: correct="
                  f"{r.get('correct')} {r.get('error', '')} {flat}",
                  flush=True)
            runs.append(r)
            if "error" in r:
                print("a run failed: stopping here", flush=True)
                return 1
        sets.append(runs)
    names = sorted({n for runs in sets for r in runs
                    for n in r.get("metrics", {})})
    scale = RANGE_SCALE.get(args.runs, 1.0)
    for name in names:
        widest = 0.0
        for k, runs in enumerate(sets):
            # the first run of the first set compiles: its set-up is apart
            vals = [r["metrics"][name]["value"] for j, r in enumerate(runs)
                    if name in r.get("metrics", {})
                    and not (name == "setup_s" and k == 0 and j == 0)]
            if not vals:
                continue
            s = spread(vals)
            widest = max(widest, s)
            print(f"{name}: set {k} median {statistics.median(vals):.6g} "
                  f"spread {100 * s:.2f}% values "
                  f"{[round(v, 3) for v in vals]}", flush=True)
        print(f"{name}: widest spread {100 * widest:.2f}%, scaled for "
              f"{args.runs} runs a set {100 * widest * scale:.2f}%, "
              f"five times that {500 * widest * scale:.1f}%", flush=True)
    if args.trace_last:
        r = one_run(args.workload, SEEDS[0], args.seconds, 1)
        r["set"], r["run"], r["seconds"] = "traced", 0, args.seconds
        with open(log, "a") as fh:
            fh.write(json.dumps(r) + "\n")
        print("traced:", json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
