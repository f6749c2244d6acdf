"""Share of the measured window's worked ``serve:step`` spans that ran
lagged (the span's ``pipelined`` attribute: the step dispatched its
program beside one still in flight, or started such a chain, and left
the commit to a later step), the same steps ``serve_goodput_pct`` logs
as ``serve_step_spans``. 0.0 where every step committed what it
dispatched (a server that runs a backlog at lag 0), printed and not left
out; nothing where the program keeps no span log."""

from benchmark.lib import program_spans as ps


def read(run, trace):
    if run["kind"] != "serve":
        return None
    records = ps.span_records("serve:")
    if not records:
        return None
    steps = ps.window_steps(records, run["t0"], run["t1"])
    if not steps:
        return None
    return 100.0 * sum(1 for s in steps
                       if (s[ps.ATTRS] or {}).get("pipelined")) / len(steps)
