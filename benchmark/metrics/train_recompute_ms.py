"""Device time of the train step's instructions in pass ``recompute`` of
the program's pass table (``telemetry/compile_watch.py`` ``pass_table``:
the compiler's own rematerialisation clones, ``<instruction>.remat<n>``,
and what ``jax.checkpoint`` runs again, ``rematted_computation`` on the
path) per traced step (self times, so nothing counts twice), on the chip
where it is largest: work the step does twice. 0.0 for a step that ran
and repeats nothing; None without a pass table (an older program)."""

from benchmark.lib import movement_readers, program_spans as ps


def read(run, trace):
    if trace is None or run.get("kind") != "train" \
            or not run.get("trace_steps"):
        return None
    passes = movement_readers.pass_table()
    if not passes:
        return None
    worst, ran = 0.0, False
    for k in range(len(trace.devices)):
        runs = ps.ops_by_execution(trace, movement_readers.PROGRAM, k)
        ran = ran or bool(runs)
        worst = max(worst, sum(op.own for ops in runs for op in ops
                               if passes.get(op.name) == "recompute"))
    return 1e3 * worst / run["trace_steps"] if ran else None
