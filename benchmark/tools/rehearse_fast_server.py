"""A traced run against a server several times faster than today's,
without changing the server: the chat-p80 and batch mixes through the
same ``benchmark/run.py`` against ``gpt2-1.3b-serve`` with all 24 layers
and ``num_slots`` cut to 4 IN MEMORY (no file is edited). The per-layer
cut of K and V out of the pool shrinks with the pool, so
``jit_serve_decode`` takes 9-14 ms instead of 48 and runs >= 55 times a
second with the same instructions per execution: the run a traced cell
has to survive once decode attends the pool in place. The open loop's
rate is halved (2.9 requests/s: ~185 output tokens/s offered to four
slots that give ~300), so its queue does not grow. Not a cell: nothing
here is in ``BENCHMARK.json`` and no number it prints is a metric.

    chiprun -- python3 benchmark/tools/rehearse_fast_server.py \
        [--mix chat-p80 --mix batch] [--slots 4] [--rate 2.9] \
        [--seconds 50] [--seed N] [--warm]

One child process per mix (this parent never touches jax), each from a
fresh compile cache unless ``--warm``. For each it prints the wall
seconds, ``tail_marks`` / ``tail_seconds``, ``traced_executions``
(``[under the profiler, inside bench:window]`` per program), the
executions a second under the profiler, and the seconds after
``trace_schedule_done`` (open loop) or ``window_closed`` (backlog).
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(ROOT))

CELLS = {"chat-p80": "serve-gpt2-1.3b-chat-p80",
         "batch": "serve-gpt2-1.3b-batch"}


def child(args) -> int:
    from benchmark import run as bench

    def edit(cell):
        cell["config"]["engine"]["num_slots"] = args.slots
        if cell["traffic"]["kind"] == "open_loop":
            cell["traffic"]["rate_per_s"] = args.rate
    return bench.main(["--workload", CELLS[args.child], "--seed",
                       str(args.seed), "--seconds", str(args.seconds),
                       "--trace", "1"], edit=edit)


def after(marks: list, label: str, end: float) -> float:
    at = dict(marks)
    return end - at[label] if label in at else float("nan")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mix", action="append", choices=sorted(CELLS))
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--rate", type=float, default=2.9)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--seed", type=int, default=2147483701)
    ap.add_argument("--warm", action="store_true")
    ap.add_argument("--out", default="chiprun_out/rehearsal")
    ap.add_argument("--child", choices=sorted(CELLS))
    args = ap.parse_args()
    if args.child:
        return child(args)
    os.makedirs(args.out, exist_ok=True)
    rc = 0
    for mix in args.mix or ["chat-p80", "batch"]:
        cache = os.path.join(ROOT, "out", "rehearsal_cache", mix)
        if not args.warm:
            shutil.rmtree(cache, ignore_errors=True)
        os.makedirs(cache, exist_ok=True)
        cmd = [sys.executable, os.path.abspath(__file__), "--child", mix,
               "--slots", str(args.slots), "--rate", str(args.rate),
               "--seconds", str(args.seconds), "--seed", str(args.seed)]
        t0 = time.time()
        p = subprocess.run(cmd, capture_output=True, text=True,
                           env=dict(os.environ,
                                    JAX_COMPILATION_CACHE_DIR=cache))
        wall = time.time() - t0
        with open(os.path.join(args.out, mix + ".out"), "w") as fh:
            fh.write(p.stdout)
        with open(os.path.join(args.out, mix + ".err"), "w") as fh:
            fh.write(p.stderr)
        rc = rc or p.returncode
        lines = {}
        for ln in p.stdout.splitlines():
            try:
                obj = json.loads(ln)
            except ValueError:
                continue
            if isinstance(obj, dict):
                lines.update({k: obj for k in obj})
        tail = lines.get("tail_marks", {})
        marks = tail.get("tail_marks", [])
        execs = tail.get("traced_executions", {})
        profiled = tail.get("tail_seconds", {}).get("profiled_s")
        done = ("trace_schedule_done" if mix == "chat-p80"
                else "window_closed")
        last = lines.get("metrics", {})
        print(json.dumps({
            "mix": mix, "slots": args.slots, "rc": p.returncode,
            "cold": not args.warm, "wall_s": round(wall, 1),
            "setup_marks": lines.get("setup_marks", {}).get("setup_marks"),
            "tail_marks": marks, "tail_seconds": tail.get("tail_seconds"),
            "traced_executions": execs,
            "trace_events": tail.get("trace_events"),
            "trace_window_cut_s": tail.get("trace_window_cut_s"),
            "decode_executions_per_profiled_s": (
                execs.get("jit_serve_decode", [0])[0] / profiled
                if profiled else None),
            "seconds_after_" + done: round(after(marks, done, wall), 1),
            "correct": last.get("correct"),
            "metrics": {k: v["value"] for k, v in
                        last.get("metrics", {}).items()}}), flush=True)
        if p.returncode:
            print(p.stderr[-3000:], flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
