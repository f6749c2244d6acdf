"""What LongCat-Flash's per-layer metrics share: the decode program's
device time by GROUP of scopes (a scope anywhere on an instruction's
path counts, so the norms inside ``mla_qkv`` are MLA's), and the expert
layer's routing counters as the program published them
(``serve_moe_*`` in ``deepspeed_tpu/telemetry/registry.py``; cumulative
over the process: warm-up, slot filling and the window).

A program without these scopes or counters (an older checkout, another
model) makes every function here return None; nothing raises."""
from __future__ import annotations

from statistics import median
from typing import Dict, Optional, Sequence

from benchmark.lib import program_spans as ps

DECODE = "serve_decode"
KERNEL = "paged_latent_decode_attention"
MLA = ("mla_qkv", "latent_write", "mla_attn", "attn_out")
MOE = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")
DENSE = ("dense_ffn",)


# the compiler's grouped-matmul custom calls carry its own op_name
# ("ragged-dot-none"), not the scope they were traced under
EXPERT_KERNELS = ("ragged-dot-none", "ragged-dot-metadata")


def scope_group_ms(trace, scopes: Sequence[str], kernels: Sequence[str] = (),
                   program: str = DECODE) -> Optional[float]:
    """Median over the executions of ``program`` on chip 0 of the self
    time of the instructions inside any of ``scopes`` (sibling scopes:
    no instruction is in two), plus that of the kernel calls NAMED in
    ``kernels`` that carry no scope of their own. Scopes that the
    compile watch knows and no instruction carries read 0.0 (the work
    is gone); scopes it does not know (another program) read None."""
    if trace is None:
        return None
    table, _ = ps.tables(program)
    runs = ps.ops_by_execution(trace, program)
    if not runs or not table:
        return None
    if not set(scopes) <= ps.known_scopes() and not any(
            s and set(scopes) & set(s.split("/")) for s in table.values()):
        return None
    return 1e3 * median(
        sum(ps.in_scope(ops, s) for s in scopes)
        + sum(op.own for op in ops
              if op.kernel in kernels and not op.scope)
        for ops in runs)


def experts_ms(trace) -> Optional[float]:
    """The held experts' grouped matmuls in one execution of the decode
    program: the ``moe_experts`` scope and the compiler's kernels."""
    return scope_group_ms(trace, ("moe_experts",), EXPERT_KERNELS)


def routing(program: Optional[str] = None) -> Optional[Dict]:
    """``{"held": [picks per held expert], "identity_picks", "absent_picks",
    "tokens_routed", "layer_calls", "held_experts_hit"}`` summed over the
    programs (or of one), or None where nothing was counted."""
    try:
        from deepspeed_tpu.telemetry import get_registry
        snap = get_registry().snapshot()
    except Exception:  # noqa: BLE001 — an older program: nothing to read
        return None

    def series(name):
        return [s for s in snap.get(name, {}).get("series", ())
                if program is None or s["labels"].get("program") == program]
    held: Dict[int, float] = {}
    for s in series("serve_moe_held_expert_picks_total"):
        x = int(s["labels"]["expert"])
        held[x] = held.get(x, 0.0) + s["value"]
    out = {"held": [held.get(x, 0.0) for x in range(max(held) + 1)]
           if held else []}
    for name in ("identity_picks", "absent_picks", "tokens_routed",
                 "layer_calls", "held_experts_hit"):
        out[name] = sum(s["value"] for s in series(f"serve_moe_{name}_total"))
    return out if out["layer_calls"] else None
