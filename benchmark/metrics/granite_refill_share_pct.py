"""Share of the measured window's worked ``serve:step`` time spent in
steps that ran a prefill program (the program's span log: a step inside
which a request's ``serve:prefill`` phase ended): the refill of freed
slots, during which no slot decodes."""

from benchmark.lib import granite_readers as gr


def read(run, trace):
    if run["kind"] != "serve":
        return None
    return gr.refill_share_pct(run["t0"], run["t1"])
