"""The held-experts grouped matmul's share of its roofline in the decode
program: per execution of an expert layer, the weights of the held
experts that got a token read once each, or the landed picks' FLOPs at
peak, whichever takes longer, over the device time of the scope
``moe_experts`` and of the compiler's grouped-matmul kernels
(``ragged-dot-*``). Experts hit per execution come from the program's
routing counters (decode program, whole process); landed picks are the
counters' picks per routed token times the live slots of the traced
steps."""

from benchmark.lib import flops_longcat, longcat_readers as lr
from benchmark.lib.trace_select import traced_steps


def read(run, trace):
    if trace is None or run["kind"] != "serve":
        return None
    s = run["shapes"]
    got = lr.routing("decode")
    spent_ms = lr.experts_ms(trace)
    steps = [st for st in traced_steps(run) if st[2] > 0]
    if not got or not spent_ms or not steps or not got["tokens_routed"]:
        return None
    live = sum(st[2] for st in steps) / len(steps)      # mean live slots
    picks = live * sum(got["held"]) / got["tokens_routed"]
    hit = got["held_experts_hit"] / got["layer_calls"]
    need = s["layers"] * flops_longcat.experts_seconds(
        hit, picks, s["hidden"], s["expert_ffn"], s["itemsize"],
        run["peaks"])
    return 100.0 * need / (spent_ms / 1e3)
