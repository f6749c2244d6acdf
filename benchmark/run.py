"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips it asks for, in this
process alone, and prints as its last line the contract's JSON object:
with ``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, the device's busy time and the breakdown. Every
other number goes on earlier lines. Without a TPU of the cell's chip
count it prints the reason on standard error, no result, and exits 2:
there is no CPU fallback and no switch that makes one.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import harness  # noqa: E402


def main(argv=None, edit=None) -> int:
    """``edit(cell)``: a tool's change to the resolved cell, in memory
    (``tools/rehearse_fast_server.py``); the command takes none."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    harness.TAIL.begin(T_START)
    contract = harness.load_contract()
    if args.seconds is None:
        args.seconds = float(contract["run_seconds"])
    cell = harness.resolve_cell(contract, args.workload)
    if edit is not None:
        edit(cell)
    chips = int(cell["cell"]["chips"])
    try:
        device = harness.require_tpu(chips)
    except harness.NoDevice as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2

    import jax

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    from deepspeed_tpu.utils.logging import logger
    for handler in logger.handlers:     # standard output carries results
        handler.setStream(sys.stderr)
    cache_dir = enable_compile_cache()
    harness.log({"workload": args.workload, "seed": args.seed,
                 "seconds": args.seconds, "trace": args.trace,
                 "device": device, "compile_cache": cache_dir})
    try:
        run, tracer = harness.run_cell(cell, args, T_START,
                                       jax.devices()[:chips], device["kind"])
    except harness.RunCeiling as e:
        # a loop of the benchmark passed its own ceiling: a failed run
        # with its reason, and no result
        print(f"benchmark: {type(e).__name__}: {e}", file=sys.stderr)
        return 3
    tail = harness.TAIL
    trace = tracer.reduced() if args.trace else None
    if trace is not None:
        from benchmark.lib import program_spans as ps
        run["trace_t1"] = tracer.t1     # where the execution cap cut it
        for program in ps.watched_programs():
            with tail.timed("tables_parsed", program):
                ps.tables(program)
        tail.mark("tables_parsed")
    names = cell["per_layer"] if args.trace else cell["end_to_end"]
    metrics = harness.read_metrics(names, run, trace,
                                   harness.units_of(contract), cell["root"])
    tail.mark("metrics_read")
    device["memory_peak_bytes"] = run["memory_peak_bytes"]
    breakdown = None
    if trace is not None:
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        with tail.timed("breakdown"):
            breakdown = trace.breakdown()
        harness.log({"trace_summary": trace.summary()})
        tail.mark("breakdown")
    correct = all(run["checks"].values())
    harness.log({"checks": run["checks"], "setup_s": run["setup_s"],
                 "window_s": run["window_s"],
                 "compile_s": run["compile_s"],
                 "jax_compile_s": run["jax_compile_s"]})
    tail.mark("result_printed")
    harness.log(tail.line())    # what follows: the result, and the exit
    print(harness.result_line(correct, run["attempted"], run["failed"],
                              metrics, device, breakdown,
                              compared=run.get("compared")), flush=True)
    # the same numbers as the last lines of standard error
    for name, (number, limit) in (run.get("compared") or {}).items():
        print(f"benchmark: compared {name} = {number} (limit {limit})",
              file=sys.stderr)
    print(f"benchmark: correct = {correct}", file=sys.stderr, flush=True)
    return 0 if correct else 1


if __name__ == "__main__":
    sys.exit(main())
