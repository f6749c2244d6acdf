"""What an admitted request costs the measured window beyond the steps
the slots would have run anyway: the wall time of the window's worked
``serve:step`` spans that admitted a request (the span's ``admitted``
attribute above 0), less a pipelined step's wall for each of them (the
mean of the window's ``pipelined`` steps: what a step of the 256
decoding slots takes when nothing moves in), over the requests they
admitted; the LongCat-Flash decode-batch cell. An admitting step decodes
its slots once whatever it admits, so this is the part of it a refill
adds (a prefill program of its own or the prompt's rows in the step's
program, the flush, the lag-0 round): it falls when refills get
cheaper, where the admitting steps' whole wall over their requests does
not (the earlier line has both, with the steps, the requests and how
many steps carried a rider). Nothing where the window has no pipelined
step to compare with."""

from benchmark.lib import harness, program_spans as ps


def read(run, trace):
    if run["kind"] != "serve":
        return None
    records = ps.span_records("serve:")
    if not records:
        return None
    window = ps.window_steps(records, run["t0"], run["t1"])
    steps = [s for s in window if (s[ps.ATTRS] or {}).get("admitted")]
    plain = [s[ps.END] - s[ps.START] for s in window
             if (s[ps.ATTRS] or {}).get("pipelined")
             and not s[ps.ATTRS].get("admitted")]
    admitted = sum(s[ps.ATTRS]["admitted"] for s in steps)
    if not admitted or not plain:
        return None
    wall = sum(s[ps.END] - s[ps.START] for s in steps)
    pipelined = sum(plain) / len(plain)
    harness.log({"longcat_admission_steps": {
        "steps": len(steps), "admitted": admitted,
        "rider_steps": sum(1 for s in steps if s[ps.ATTRS].get("rider")),
        "wall_s": wall, "whole_step_ms_per_request": 1e3 * wall / admitted,
        "pipelined_steps": len(plain), "pipelined_step_ms": 1e3 * pipelined}})
    return 1e3 * (wall - len(steps) * pipelined) / admitted
