"""What Laguna's per-layer metrics share: the groups of scopes its
readers sum (through ``longcat_readers.scope_group_ms``: a known scope
that no instruction carries reads 0.0, a program without these scopes
None), the two decode kernels' names, and the cache counters as the
program published them (``serve_kv_rows_read_total``, the expert layer's
``serve_moe_*`` and the server's pool series; cumulative over the
process: warm-up, slot filling and the window). Without the counters (an
older checkout, another model) :func:`counters` returns None; nothing
raises."""
from __future__ import annotations

from typing import Dict, Optional

FULL_KERNEL = "paged_decode_attention"
WINDOW_KERNEL = "paged_window_decode_attention"
# an attention by the kind of its layer: projections, rotary, gate,
# cache write and the kernel
FULL = ("attn_full",)
WINDOW = ("attn_window",)
SHARED = ("moe_shared",)


def counters() -> Optional[Dict[str, float]]:
    """``{"full_rows", "window_rows"`` (cache rows the decode program had
    to read, by layer kind, all layers of the kind), ``"slot_steps"``
    (live slots summed over decode steps), ``"steps"`` (decode steps),
    ``"used_block_steps"``, ``"blocked_steps"``, ``"ring_bytes"}``, or
    None where the program counted no rows."""
    try:
        from deepspeed_tpu.telemetry import get_registry
        snap = get_registry().snapshot()
    except Exception:  # noqa: BLE001 — an older program: nothing to read
        return None

    def total(name, **labels):
        return sum(s["value"] for s in snap.get(name, {}).get("series", ())
                   if all(s["labels"].get(k) == v
                          for k, v in labels.items()))
    out = {kind + "_rows": total("serve_kv_rows_read_total",
                                 program="decode", kind=kind)
           for kind in ("full", "window")}
    calls = total("serve_moe_layer_calls_total", program="decode")
    routed = total("serve_moe_tokens_routed_total", program="decode")
    steps = total("serve_decode_steps_total")
    if not out["full_rows"] or not calls or not steps:
        return None
    # the expert layers route every live slot once a step each
    out["slot_steps"] = routed / calls * steps
    out["steps"] = steps
    out["used_block_steps"] = total("serve_kv_used_block_steps_total")
    out["blocked_steps"] = total("serve_kv_admission_blocked_steps_total")
    out["ring_bytes"] = total("serve_kv_ring_bytes")
    return out
