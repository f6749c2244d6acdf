"""The chunk-attention kernel's share of the chip's bfloat16 peak: the
operations of the kernel NAMED ``latent_chunk_attention``
(``flops_gigachat.chunk_kernel_flops``: the pairs a causal chunk sees,
and K and V of the pool blocks that hold them rebuilt once a head, which
this form of latent attention does and the count includes) at the
contexts of the chunks the traced window ran (the program's span log:
each chunk's first position), over the device time of the kernel's calls
in the chunk programs of the traced window. The kernel is compute-bound:
a 33k-row context is 39 MB of latents a call."""

from benchmark.lib import flops_gigachat, gigachat_readers as gr
from benchmark.lib.trace_select import kernel_calls


def read(run, trace):
    if trace is None or run["kind"] != "serve":
        return None
    s = run["shapes"]
    calls = kernel_calls(trace, (gr.CHUNK,), gr.CHUNK_KERNEL)
    spent = sum(e - b for b, e in calls)
    spans = gr.chunk_spans(run["trace_t0"], run["trace_t1"])
    if not calls or not spans or spent <= 0 or "kv_rank" not in s:
        return None
    block = run["config"]["engine"]["block_size"]
    chunks = gr.chunk_starts(spans)
    # one call an attention a chunk; the two clocks' edges can differ by
    # a chunk, so the calls are taken at the chunks' mean
    per_call = sum(flops_gigachat.chunk_kernel_flops(start, rows, s, block)
                   for start, rows in chunks) / len(chunks)
    return 100.0 * len(calls) * per_call / run["peaks"]["bf16_flops"] / spent
