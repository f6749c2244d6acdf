"""The paged-decode kernel's share of its roofline: the bytes of K and V
of every live position that its calls had to read (decode attention is
bound by that read), over the peak bandwidth, over the kernel's device
time in the decode programs of the traced window."""

from benchmark.lib import flops
from benchmark.lib.trace_select import is_paged, traced_steps


def read(run, trace):
    if trace is None or run["kind"] != "serve":
        return None
    s = run["shapes"]
    progs = trace.modules_with(lambda t: is_paged(t, run))
    calls = sum(len(ks) for _, _, _, ks in progs)
    spent = sum(e - b for _, _, _, ks in progs for _, b, e in ks)
    steps = [st for st in traced_steps(run) if st[2] > 0]
    if not calls or not steps or spent <= 0:
        return None
    live = sum(st[3] for st in steps) / len(steps)   # mean live positions
    need = calls * flops.paged_decode_bytes(
        live, s["kv_heads"], s["head_dim"], s["itemsize"]
    ) / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * need / spent
