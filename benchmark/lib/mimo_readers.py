"""What MiMo-V2's per-layer metrics share: the groups of scopes its
readers sum (through ``longcat_readers.scope_group_ms``: a known scope
that no instruction carries reads 0.0, a program without these scopes
None), the kernels' names, a kernel's share of the bandwidth's roof from
the rows its calls had to read, and the cache counters as the program
published them (``laguna_readers.counters``: the same series, this
family publishes them too). Without the counters or the sizes (an older
checkout, another model) every reader returns None; nothing raises."""
from __future__ import annotations

from typing import Optional

from benchmark.lib import flops_mimo
from benchmark.lib.flops import roofline_seconds
from benchmark.lib.laguna_readers import counters  # noqa: F401
from benchmark.lib.trace_select import (DECODE, PREFILL, kernel_calls,
                                        program_runs, traced_admissions,
                                        traced_steps)

FULL_KERNEL = "paged_decode_attention"
WINDOW_KERNEL = "paged_window_decode_attention"
WINDOW_FLASH_KERNEL = "flash_attention_window_fwd"
# an attention by the kind of its layer: projections, rotary, value
# scale, cache write and the kernel (with its sink on a window layer)
FULL = ("attn_full",)
WINDOW = ("attn_window",)


def sizes(run) -> Optional[dict]:
    """The run's shapes where they are this family's, else None."""
    s = run.get("shapes") or {}
    return s if "full_kv_heads" in s and "v_head_dim" in s else None


def row_bytes(s: dict, kind: str) -> int:
    return flops_mimo.row_bytes(s[kind + "_kv_heads"], s["head_dim"],
                                s["v_head_dim"], s["itemsize"])


def decode_roofline(run, trace, kind: str) -> Optional[float]:
    """The ``kind`` layers' decode kernel: the bytes of the rows its
    calls had to read (a full layer: every live position, from the
    host's step records of the traced window; a window layer:
    ``min(context, window)`` rows a live slot, from the program's own
    counter, times the live slots of the traced steps), over the peak
    bandwidth, over the device time of the kernel's calls in the decode
    programs of the traced window, in percent."""
    if trace is None or run.get("kind") != "serve":
        return None
    s = sizes(run)
    kernel = FULL_KERNEL if kind == "full" else WINDOW_KERNEL
    calls = kernel_calls(trace, DECODE, kernel)
    spent = sum(e - b for b, e in calls)
    steps = [st for st in traced_steps(run) if st[2] > 0]
    if not s or not calls or not steps or spent <= 0:
        return None
    if kind == "full":
        rows = sum(st[3] for st in steps) / len(steps)  # live positions
    else:
        got = counters()
        if not got or not s.get("window_layers"):
            return None
        rows = (got["window_rows"] / s["window_layers"] / got["slot_steps"]
                * sum(st[2] for st in steps) / len(steps))
    need = len(calls) * rows * row_bytes(s, kind) / run["peaks"][
        "hbm_bytes_per_s"]
    return 100.0 * need / spent


def window_flash_roofline(run, trace) -> Optional[float]:
    """The sink-window flash forward's share of its roofline in prefill:
    the least time (operations over the peak rate or bytes over the peak
    bandwidth, whichever is more) for the windowed attention of the
    prompts admitted in the traced window at their real lengths, all
    window layers, over the device time of the kernel's calls in the
    prefill programs. None where no prefill ran under the profiler (a
    backlog cell's traced window: PERF.md section 7)."""
    if trace is None or run.get("kind") != "serve":
        return None
    s = sizes(run)
    progs = program_runs(trace, PREFILL)
    spent = sum(e - b for b, e in kernel_calls(trace, PREFILL,
                                               WINDOW_FLASH_KERNEL))
    if not s or spent <= 0:
        return None
    need = sum(s["window_layers"] * roofline_seconds(
        flops_mimo.prefill_attention_flops(
            p, s["heads"], s["head_dim"], s["v_head_dim"], s["window"]),
        flops_mimo.prefill_attention_bytes(
            p, s["heads"], s["window_kv_heads"], s["head_dim"],
            s["v_head_dim"], s["itemsize"]), run["peaks"])
        for p in traced_admissions(run, len(progs)))
    return 100.0 * need / spent if need > 0 else None
