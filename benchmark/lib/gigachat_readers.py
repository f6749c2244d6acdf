"""What GigaChat3's per-layer metrics share: the names of the chunk
program and its attention kernel, the groups of scopes its readers sum
(through ``longcat_readers.scope_group_ms``), the chunk phase's spans of
the program's span log (a step that ran chunks names their program on
its ``serve:prefill_chunk`` span, with the first chunk's position and
how many ran), and the counters as the program published them
(``serve_prompt_tokens_total``, ``serve_kv_rows_read_total``; cumulative
over the process: warm-up, slot filling and the window).

A program without these spans or counters (an older checkout, another
model) makes every function here return None; nothing raises."""
from __future__ import annotations

from typing import List, Optional

from benchmark.lib import longcat_readers as lr, program_spans as ps

CHUNK = "serve_prefill_chunk"
CHUNK_KERNEL = "latent_chunk_attention"
CHUNK_SPAN = "serve:prefill_chunk"
MLA = lr.MLA
MOE = lr.MOE + ("moe_shared",)


def counter_total(name: str, **labels) -> Optional[float]:
    """Sum of the registry series of ``name`` that carry ``labels``, or
    None where the program keeps no such series."""
    try:
        from deepspeed_tpu.telemetry import get_registry
        snap = get_registry().snapshot()
    except Exception:  # noqa: BLE001 — an older program: nothing to read
        return None
    series = [s for s in snap.get(name, {}).get("series", ())
              if all(s["labels"].get(k) == v for k, v in labels.items())]
    return sum(s["value"] for s in series) if series else None


def chunk_spans(lo: float, hi: float) -> Optional[List[tuple]]:
    """The chunk-phase spans that ended in ``[lo, hi]`` and ran a chunk
    program: span-log records whose attributes name it."""
    records = ps.span_records("serve:")
    if not records:
        return None
    return [r for r in ps.ending_in(records, lo, hi, CHUNK_SPAN)
            if (r[ps.ATTRS] or {}).get("program") == CHUNK]


def chunk_starts(spans: List[tuple]) -> List[tuple]:
    """``(start, rows)`` of every chunk those spans ran."""
    out = []
    for r in spans:
        a = r[ps.ATTRS]
        out.extend((a["start"] + i * a["rows"], a["rows"])
                   for i in range(a["chunks"]))
    return out
