"""Share of the measured window's worked ``serve:step`` time spent in
steps that ran a chunk program (the program's span log: a step whose
``serve:prefill_chunk`` span names the program): the refill of freed
slots, during which no slot decodes."""

from benchmark.lib import gigachat_readers as gr, program_spans as ps


def read(run, trace):
    if run["kind"] != "serve":
        return None
    spans = gr.chunk_spans(run["t0"], run["t1"])
    if spans is None:
        return None
    steps = ps.window_steps(ps.span_records("serve:"), run["t0"], run["t1"])
    wall = sum(s[ps.END] - s[ps.START] for s in steps)
    if wall <= 0:
        return None
    refilled = {r[ps.PARENT] for r in spans}
    return 100.0 * sum(s[ps.END] - s[ps.START] for s in steps
                       if s[ps.ID] in refilled) / wall
