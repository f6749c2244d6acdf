"""Run cells several times, one process each, one after another (this
parent never touches jax, so each child gets the chip), and print each
metric's median and spread the way the driver reads it: the distance
between the first and third quartile (``statistics.quantiles(n=4)``) as
a share of the median.

    python3 benchmark/tools/repeat.py --workload W [--workload W2 ...] \
        --seeds 11,12,13,14,15,16 [--sets 2] [--seconds S] [--trace 0|1]

Every run's lines are appended to ``chiprun_out/runs/<workload>.log``
and its last line to ``chiprun_out/runs/<workload>.jsonl`` (with the
set, seed and wall seconds).
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
RUN = os.path.join(os.path.dirname(HERE), "run.py")


def spread(values):
    if len(values) < 2:
        return None
    q = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q[2] - q[0]) / med if med else None


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", action="append", required=True)
    ap.add_argument("--seeds", default="11,12,13,14,15,16")
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/runs")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    rc = 0
    for workload in args.workload:
        rows = []
        for k in range(args.sets):
            for seed in seeds:
                cmd = [sys.executable, RUN, "--workload", workload,
                       "--seed", str(seed), "--trace", str(args.trace)]
                if args.seconds is not None:
                    cmd += ["--seconds", str(args.seconds)]
                t0 = time.time()
                p = subprocess.run(cmd, capture_output=True, text=True)
                wall = time.time() - t0
                lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
                with open(os.path.join(args.out, workload + ".log"),
                          "a") as fh:
                    fh.write(f"### set {k} seed {seed} rc {p.returncode} "
                             f"wall {wall:.1f}\n" + p.stdout)
                    if p.returncode:
                        fh.write("--- stderr\n" + p.stderr[:3000] + "\n...\n"
                                 + p.stderr[-4000:])
                try:
                    last = json.loads(lines[-1])
                    if "metrics" not in last:
                        raise ValueError("no result line")
                except (IndexError, ValueError):
                    last = {"correct": False, "metrics": {}}
                last.update(set=k, seed=seed, wall_s=wall, rc=p.returncode,
                            trace=args.trace)
                with open(os.path.join(args.out, workload + ".jsonl"),
                          "a") as fh:
                    fh.write(json.dumps(last) + "\n")
                rows.append(last)
                rc = rc or p.returncode
                print(json.dumps({"workload": workload, "set": k,
                                  "seed": seed, "rc": p.returncode,
                                  "wall_s": round(wall, 1),
                                  "correct": last.get("correct"),
                                  "metrics": {n: m["value"] for n, m in
                                              last["metrics"].items()}}),
                      flush=True)
                if p.returncode:
                    print(p.stderr[-3000:], flush=True)
        names = sorted({n for r in rows for n in r["metrics"]})
        for k in range(args.sets):
            for n in names:
                vals = [r["metrics"][n]["value"] for r in rows
                        if r["set"] == k and n in r["metrics"]]
                if n == "setup_s":
                    vals = vals[1:] if k == 0 else vals   # first compiles
                if vals:
                    print(json.dumps({
                        "workload": workload, "set": k, "metric": n,
                        "n": len(vals), "median": statistics.median(vals),
                        "min": min(vals), "max": max(vals),
                        "iqr_spread": spread(vals)}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
