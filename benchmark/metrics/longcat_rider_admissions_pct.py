"""Of the requests admitted by the measured window's worked
``serve:step`` spans (``admitted`` attribute), the share admitted by a
step whose decode program carried the prompt (``rider`` attribute set:
one program read the weights for the decoding rows and the prompt);
the LongCat-Flash decode-batch cell. Nothing where the program's steps
do not say (a server that has no such round)."""

from benchmark.lib import program_spans as ps


def read(run, trace):
    if run["kind"] != "serve":
        return None
    records = ps.span_records("serve:")
    if not records:
        return None
    steps = [s for s in ps.window_steps(records, run["t0"], run["t1"])
             if (s[ps.ATTRS] or {}).get("admitted")]
    admitted = sum(s[ps.ATTRS]["admitted"] for s in steps)
    if not admitted or not any("rider" in s[ps.ATTRS] for s in steps):
        return None
    return 100.0 * sum(s[ps.ATTRS]["admitted"] for s in steps
                       if s[ps.ATTRS].get("rider")) / admitted
