"""The state-update decode kernel's share of its roofline: the bytes of
every live slot's PACKED state (8 heads x 8256 pairs x (128 values + the
normaliser) x 4 B), once in and once out a call, over the peak
bandwidth, over the device time of the kernel NAMED
``power_retention_decode`` in the decode programs of the traced
window. The pool stores 8320 rows a head; the packed count keeps the
share from passing 100 % whatever the layout."""

from benchmark.lib import brumby_readers as br, flops_brumby
from benchmark.lib.trace_select import DECODE, kernel_calls, traced_steps


def read(run, trace):
    if trace is None or run["kind"] != "serve":
        return None
    s = run["shapes"]
    calls = kernel_calls(trace, DECODE, br.KERNEL)
    spent = sum(e - b for b, e in calls)
    steps = [st for st in traced_steps(run) if st[2] > 0]
    if not calls or not steps or spent <= 0 or "state_itemsize" not in s:
        return None
    live = sum(st[2] for st in steps) / len(steps)       # mean live slots
    need = len(calls) * flops_brumby.decode_bytes(
        live, s["kv_heads"], s["head_dim"], s["state_itemsize"]
    ) / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * need / spent
