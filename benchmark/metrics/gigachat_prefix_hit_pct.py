"""Share of the admitted prompt tokens that were served from cached
prefix blocks and cost no prefill (the server's counters
``serve_prompt_tokens_total``, whole process: the check's cold samples,
the four cold contexts of the fill and every hit): ~97 % of a prompt is
its tenant's shared context, so a reading far below that says reuse
broke."""

from benchmark.lib import gigachat_readers as gr, harness


def read(run, trace):
    cached = gr.counter_total("serve_prompt_tokens_total", source="cached")
    cold = gr.counter_total("serve_prompt_tokens_total", source="prefilled")
    if cached is None or cold is None or cached + cold <= 0:
        return None
    harness.log({"gigachat_prompt_tokens": {
        "cached": cached, "prefilled": cold,
        "chunk_rows": gr.counter_total("serve_prefill_chunk_rows_total")}})
    return 100.0 * cached / (cached + cold)
