"""Latent-cache bytes a decode step had to read, in GB (1e9): the
program's own counter (``serve_kv_rows_read_total{program="decode",
kind="latent"}``: every live slot's whole context, each of the six
attentions) times a cached row ``[c_kv ; k_rope]``, over its decode
steps, whole process."""

from benchmark.lib import flops_longcat, gigachat_readers as gr


def read(run, trace):
    rows = gr.counter_total("serve_kv_rows_read_total", program="decode",
                            kind="latent")
    steps = gr.counter_total("serve_decode_steps_total")
    s = run.get("shapes") or {}
    if not rows or not steps or "latent_width" not in s:
        return None
    return flops_longcat.latent_decode_bytes(
        rows, s["latent_width"], s["itemsize"]) / steps / 1e9
