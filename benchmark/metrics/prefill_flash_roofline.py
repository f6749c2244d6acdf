"""The flash-attention kernel's share of its roofline in prefill: the
least time for the causal attention of the prompts admitted in the
traced window (their real lengths, not the padded buckets) over the
device time of the kernel NAMED ``flash_attention_fwd`` in the prefill
programs (``jit_serve_prefill``, ``jit_serve_prefill_chunk``)."""

from benchmark.lib import flops
from benchmark.lib.trace_select import (PREFILL, kernel_calls, program_runs,
                                        traced_admissions)


def read(run, trace):
    if trace is None or run["kind"] != "serve":
        return None
    s = run["shapes"]
    progs = program_runs(trace, PREFILL)
    spent = sum(e - b for b, e in kernel_calls(trace, PREFILL,
                                               "flash_attention_fwd"))
    need = sum(s["n_layer"] * flops.roofline_seconds(
        flops.flash_flops(1, s["n_head"], p, p, s["head_dim"]),
        flops.flash_bytes(1, s["n_head"], p, p, s["head_dim"],
                          s["itemsize"]), run["peaks"])
        for p in traced_admissions(run, len(progs)))
    return 100.0 * need / spent if spent > 0 and need > 0 else None
