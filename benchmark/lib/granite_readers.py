"""What the Granite hybrid's per-layer metrics share: the groups of
scopes its readers sum (through ``longcat_readers.scope_group_ms``: a
known scope that no instruction carries reads 0.0, a program without
these scopes None), the device time of ONE scope (or of the kernel named
for it) in every execution of a program, the prefill spans of the
program's span log, and the counters as the program published them
(``serve_hybrid_*``, ``serve_kv_rows_read_total``; cumulative over the
process: warm-up, slot filling and the window).

A program without these scopes, spans or counters (an older checkout,
another model) makes every function here return None; nothing raises."""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional

from benchmark.lib import longcat_readers as lr, program_spans as ps

PREFILL = "serve_prefill"
PREFILL_SPAN = "serve:prefill"
# the mixer of a Mamba layer: projections, convolution, state update and
# query, gated norm and output projection
MAMBA = ("mamba_in", "mamba_conv", "mamba_state", "mamba_out")
ATTN = ("attn_full",)
MOE = lr.MOE + ("moe_shared",)
# the decode state update and the prefill's chunked form, each by the
# scope it runs under or, once a kernel does it, by the kernel's name
STATE_SCOPE, STATE_KERNEL = "mamba_state", "mamba_state_update"
SCAN_SCOPE, SCAN_KERNEL = "mamba_scan", "mamba_chunk_scan"


def scope_seconds(trace, program: str, scope: str,
                  kernel: str) -> Optional[List[float]]:
    """For each execution of ``program`` on chip 0 inside the traced
    window, the self seconds of the instructions inside ``scope`` plus
    those of the kernel calls NAMED ``kernel`` that carry no scope. None
    without a trace, a scope table or an execution, or where neither the
    program nor the compile watch knows the scope."""
    if trace is None:
        return None
    table, _ = ps.tables(program)
    runs = ps.ops_by_execution(trace, program)
    if not runs or not table:
        return None
    if scope not in ps.known_scopes() and not any(
            s and scope in s.split("/") for s in table.values()):
        return None
    return [ps.in_scope(ops, scope)
            + sum(op.own for op in ops
                  if op.kernel == kernel and not op.scope)
            for ops in runs]


def counters(program: str = "decode") -> Optional[Dict[str, float]]:
    """``{"steps", "live_slots", "state_bytes", "prefill_tokens",
    "prefill_chunks", "kv_rows"}`` of one program, or None where nothing
    was counted."""
    try:
        from deepspeed_tpu.telemetry import get_registry
        snap = get_registry().snapshot()
    except Exception:  # noqa: BLE001 — an older program: nothing to read
        return None

    def total(name, **labels):
        return sum(s["value"] for s in snap.get(name, {}).get("series", ())
                   if s["labels"].get("program") == program
                   and all(s["labels"].get(k) == v
                           for k, v in labels.items()))
    out = {key: total(f"serve_hybrid_{key}_total")
           for key in ("steps", "live_slots", "state_bytes",
                       "prefill_tokens", "prefill_chunks")}
    out["kv_rows"] = total("serve_kv_rows_read_total", kind="full")
    return out if out["steps"] else None


def refill_share_pct(lo: float, hi: float) -> Optional[float]:
    """Share of the worked ``serve:step`` time in ``[lo, hi]`` spent in
    steps inside which a request's ``serve:prefill`` phase ended (a step
    that ran a prefill program: no slot decodes meanwhile)."""
    records = ps.span_records("serve:")
    if not records:
        return None
    steps = sorted(ps.window_steps(records, lo, hi),
                   key=lambda r: r[ps.START])
    wall = sum(s[ps.END] - s[ps.START] for s in steps)
    if wall <= 0:
        return None
    starts = [s[ps.START] for s in steps]
    refilled = set()
    for r in records:
        if r[ps.NAME] != PREFILL_SPAN:
            continue
        i = bisect.bisect_right(starts, r[ps.END]) - 1
        if i >= 0 and r[ps.END] <= steps[i][ps.END]:
            refilled.add(i)
    return 100.0 * sum(steps[i][ps.END] - steps[i][ps.START]
                       for i in refilled) / wall
