"""The prefill's chunked (SSD) form's share of its roofline: for the
prompts admitted in the traced window (their real lengths, not the
padded buckets), all state layers, the form's FLOPs at peak or its bytes
at the bandwidth, whichever is longer, over the device time of the scope
``mamba_scan`` (or of the kernel NAMED ``mamba_chunk_scan``) in the
prefill programs."""

from benchmark.lib import flops_granite, granite_readers as gr
from benchmark.lib.trace_select import traced_admissions


def read(run, trace):
    if trace is None or run["kind"] != "serve":
        return None
    s = run["shapes"]
    per = gr.scope_seconds(trace, gr.PREFILL, gr.SCAN_SCOPE, gr.SCAN_KERNEL)
    if not per or sum(per) <= 0 or "mamba_chunk" not in s:
        return None
    need = sum(s["state_layers"] * flops_granite.scan_seconds(
        p, s, run["peaks"]) for p in traced_admissions(run, len(per)))
    return 100.0 * need / sum(per) if need > 0 else None
