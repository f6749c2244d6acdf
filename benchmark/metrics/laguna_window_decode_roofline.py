"""The paged decode kernel's share of its roofline on the WINDOW layers:
the bytes of the rows INSIDE the window of every live slot
(``min(context, window)`` rows, from the program's own counter: rows a
live slot a window layer, whole process, times the live slots of the
traced steps), over the peak bandwidth, over the device time of the
kernel NAMED ``paged_window_decode_attention`` in the decode programs of
the traced window. A kernel that reads the ring's slack block, or the
whole context, reads low, not high."""

from benchmark.lib import flops_laguna, laguna_readers as lg
from benchmark.lib.trace_select import DECODE, kernel_calls, traced_steps


def read(run, trace):
    if trace is None or run["kind"] != "serve":
        return None
    s = run["shapes"]
    got = lg.counters()
    calls = kernel_calls(trace, DECODE, lg.WINDOW_KERNEL)
    spent = sum(e - b for b, e in calls)
    steps = [st for st in traced_steps(run) if st[2] > 0]
    if (not got or not calls or not steps or spent <= 0
            or not s.get("window_layers")):
        return None
    rows_a_slot = got["window_rows"] / s["window_layers"] / got["slot_steps"]
    live = sum(st[2] for st in steps) / len(steps)       # mean live slots
    need = len(calls) * flops_laguna.decode_read_bytes(
        live * rows_a_slot, s["kv_heads"], s["head_dim"], s["itemsize"]
    ) / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * need / spent
