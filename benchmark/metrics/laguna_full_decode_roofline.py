"""The paged decode kernel's share of its roofline on the FULL layers:
the bytes of the cached rows of every live position that its calls had
to read (decode attention is bound by that read), over the peak
bandwidth, over the device time of the kernel NAMED
``paged_decode_attention`` in the decode programs of the traced window.
Live positions are the host's step records of the traced window."""

from benchmark.lib import flops_laguna, laguna_readers as lg
from benchmark.lib.trace_select import DECODE, kernel_calls, traced_steps


def read(run, trace):
    if trace is None or run["kind"] != "serve":
        return None
    s = run["shapes"]
    calls = kernel_calls(trace, DECODE, lg.FULL_KERNEL)
    spent = sum(e - b for b, e in calls)
    steps = [st for st in traced_steps(run) if st[2] > 0]
    if not calls or not steps or spent <= 0 or "full_layers" not in s:
        return None
    live = sum(st[3] for st in steps) / len(steps)   # mean live positions
    need = len(calls) * flops_laguna.decode_read_bytes(
        live, s["kv_heads"], s["head_dim"], s["itemsize"]
    ) / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * need / spent
