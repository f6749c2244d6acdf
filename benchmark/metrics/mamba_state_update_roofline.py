"""The decode state update's share of its roofline: every live slot's
state (128 heads x 64 x 128 x 4 B a Mamba layer) once in and once out,
all state layers, over the peak bandwidth, over the device time of the
scope ``mamba_state`` (or of the kernel NAMED ``mamba_state_update``) in
the decode programs of the traced window: the same bytes whatever
implements the update."""

from benchmark.lib import flops_granite, granite_readers as gr
from benchmark.lib.trace_select import DECODE, traced_steps


def read(run, trace):
    if trace is None or run["kind"] != "serve":
        return None
    s = run["shapes"]
    per = gr.scope_seconds(trace, DECODE[0], gr.STATE_SCOPE, gr.STATE_KERNEL)
    steps = [st for st in traced_steps(run) if st[2] > 0]
    if not per or not steps or sum(per) <= 0 or "state_bytes" not in s:
        return None
    live = sum(st[2] for st in steps) / len(steps)       # mean live slots
    need = len(per) * s["state_layers"] * flops_granite.state_update_bytes(
        live, s["state_bytes"]) / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * need / sum(per)
