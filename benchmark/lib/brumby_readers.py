"""What Brumby's per-layer metrics share: the groups of scopes its
readers sum (through ``longcat_readers.scope_group_ms``: a known scope
that no instruction carries reads 0.0, a program without these scopes
None), and the retention counters as the program published them
(``serve_retention_*`` in the registry; cumulative over the process:
warm-up, slot filling and the window). Without the counters (an older
checkout, another model) :func:`counters` returns None; nothing
raises."""
from __future__ import annotations

from typing import Dict, Optional

KERNEL = "power_retention_decode"
# the mixer: projections, head norms, rotary and gate; the state update
# and query (the kernel); the output projection
STATE = ("ret_qkvg", "ret_state", "ret_out")
MLP = ("mlp",)
HEAD = ("lm_head",)


def counters(program: str = "decode") -> Optional[Dict[str, float]]:
    """``{"steps", "live_slots", "state_bytes", "prefill_tokens",
    "prefill_chunks"}`` of one program, or None where nothing was
    counted."""
    try:
        from deepspeed_tpu.telemetry import get_registry
        snap = get_registry().snapshot()
    except Exception:  # noqa: BLE001 — an older program: nothing to read
        return None

    def total(name):
        return sum(s["value"] for s in snap.get(name, {}).get("series", ())
                   if s["labels"].get("program") == program)
    out = {key: total(f"serve_retention_{key}_total")
           for key in ("steps", "live_slots", "state_bytes",
                       "prefill_tokens", "prefill_chunks")}
    return out if out["steps"] else None
