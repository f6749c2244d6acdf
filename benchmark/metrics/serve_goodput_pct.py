"""The program's own goodput gauge over the measured window: the
device-attributed share of ``serve:step`` wall (what
``serve_goodput_fraction`` shows an operator), to sit beside the device
trace's ``batch_device_idle_pct``."""

from benchmark.lib import harness, program_spans as ps


def read(run, trace):
    if run["kind"] != "serve":
        return None
    records = ps.span_records("serve:")
    if not records:
        return None
    steps = ps.window_steps(records, run["t0"], run["t1"])
    harness.log({"serve_step_spans": {
        "steps_in_window": len(steps),
        "pipelined": sum(1 for s in steps
                         if (s[ps.ATTRS] or {}).get("pipelined")),
        "span_log": ps.span_log_stats()}})
    return ps.goodput_pct(records, run["t0"], run["t1"])
