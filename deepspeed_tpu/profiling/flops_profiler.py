"""Flops profiler.

Analog of ``deepspeed/profiling/flops_profiler/profiler.py`` (1,226 LoC of
torch monkey-patching to count MACs per module). On TPU the compiler
already knows: XLA's cost analysis reports exact flops/bytes for the
*optimized* computation, so the profiler asks the compiled executable
instead of shimming every op — more accurate (post-fusion) and zero
overhead in the hot path.

``get_model_profile(fn, args)`` mirrors the reference's standalone API;
:class:`FlopsProfiler` mirrors the engine-integrated start/stop/print flow
(``runtime/engine.py:1779-1798``).

Per-module attribution (the reference's module tree, its
``print_model_profile`` aggregated-depth view): where torch hooks every
``nn.Module``, the TPU-native source of truth is the jaxpr — flax wraps
every module call in ``jax.named_scope``, so each equation carries its
module path (``GPT2/h_3/attn/c_attn``). :func:`module_flops_breakdown`
walks the jaxpr (recursing through pjit/remat/scan/cond, scaling scan
bodies by trip count) counting analytic FLOPs per equation and groups
them by name-stack prefix. The per-module numbers sum exactly to the
walk's aggregate by construction; XLA's post-fusion executable count is
reported alongside (fusion/remat make it differ — both are printed).
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np


def _params_count(tree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree)
               if hasattr(x, "shape"))


# ------------------------------------------------------- jaxpr walking
# Analytic per-equation FLOP estimates. Matmuls/convs carry ~all model
# FLOPs (the reference's profiler counts the same way: MACs of
# Linear/conv modules + elementwise, flops_profiler/profiler.py); memory
# movement (reshape/slice/broadcast/gather) counts 0.

_ELEMENTWISE = {
    "add", "sub", "mul", "div", "pow", "max", "min", "rem", "neg", "abs",
    "exp", "log", "log1p", "expm1", "tanh", "sqrt", "rsqrt", "logistic",
    "erf", "erfc", "erf_inv", "sign", "floor", "ceil", "round", "cos",
    "sin", "tan", "atan2", "integer_pow", "select_n", "clamp", "nextafter",
    "and", "or", "xor", "not", "eq", "ne", "ge", "gt", "le", "lt",
    "is_finite", "add_any", "square",
}
_REDUCTIONS = {"reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
               "reduce_and", "reduce_or", "argmax", "argmin",
               "cumsum", "cumprod", "cummax", "cummin", "reduce_precision"}
_CALL_PRIMS = {"pjit", "closed_call", "core_call", "xla_call", "remat2",
               "remat", "custom_jvp_call", "custom_vjp_call",
               "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr",
               "checkpoint", "named_call", "custom_vjp_call_fwd"}


def _aval_size(v) -> int:
    try:
        return int(np.prod(v.aval.shape))
    except Exception:  # noqa: BLE001 — abstract tokens etc.
        return 0


def _eqn_flops(eqn) -> float:
    name = eqn.primitive.name
    if name == "dot_general":
        (lc, _), _ = eqn.params["dimension_numbers"]
        k = 1
        for d in lc:
            k *= eqn.invars[0].aval.shape[d]
        return 2.0 * _aval_size(eqn.outvars[0]) * k
    if name == "conv_general_dilated":
        rhs = eqn.invars[1].aval.shape
        dn = eqn.params["dimension_numbers"]
        out_feature = rhs[dn.rhs_spec[0]]
        per_out = (2.0 * int(np.prod(rhs)) / max(out_feature, 1))
        return per_out * _aval_size(eqn.outvars[0])
    if name in _ELEMENTWISE:
        return float(_aval_size(eqn.outvars[0]))
    if name in _REDUCTIONS:
        return float(_aval_size(eqn.invars[0]))
    return 0.0


def _inner_jaxprs(eqn):
    """(jaxpr, multiplier) pairs for call-like primitives. Scan bodies
    run ``length`` times; cond branches are counted at their max (an
    upper bound — the trace cannot know which branch runs)."""
    from jax._src.core import Jaxpr  # stable across recent jax

    def as_jaxpr(x):
        if isinstance(x, Jaxpr):
            return x
        if hasattr(x, "jaxpr"):
            return x.jaxpr
        return None

    name = eqn.primitive.name
    if name == "scan":
        body = as_jaxpr(eqn.params["jaxpr"])
        return [(body, float(eqn.params.get("length", 1)))]
    if name == "while":
        # body trip count is data-dependent; count one iteration
        return [(as_jaxpr(eqn.params["body_jaxpr"]), 1.0)]
    if name == "cond":
        branches = [as_jaxpr(b) for b in eqn.params["branches"]]
        totals = [(_jaxpr_flops_total(b), b) for b in branches if b]
        if not totals:
            return []
        return [(max(totals, key=lambda t: t[0])[1], 1.0)]
    if name in _CALL_PRIMS:
        out = []
        for v in eqn.params.values():
            for item in (v if isinstance(v, (list, tuple)) else [v]):
                j = as_jaxpr(item)
                if j is not None:
                    out.append((j, 1.0))
        return out
    return []


def _jaxpr_flops_total(jx) -> float:
    total = 0.0
    for eqn in jx.eqns:
        total += _eqn_flops(eqn)
        for inner, mult in _inner_jaxprs(eqn):
            total += mult * _jaxpr_flops_total(inner)
    return total


def _walk_modules(jx, prefix: str, mult: float, acc: Dict[str, float]):
    for eqn in jx.eqns:
        ns = str(eqn.source_info.name_stack)
        # inner name stacks are relative to the enclosing call site
        full = "/".join(s for s in (prefix, ns) if s)
        inner = _inner_jaxprs(eqn)
        if inner:
            for ij, m in inner:
                _walk_modules(ij, full, mult * m, acc)
        else:
            f = _eqn_flops(eqn)
            if f:
                acc[full] = acc.get(full, 0.0) + mult * f


def module_flops_breakdown(fn: Callable, *args, depth: Optional[int] = 2,
                           jaxpr=None, **kwargs) -> Dict[str, float]:
    """Per-module analytic FLOPs for one call of ``fn`` — the TPU-native
    analog of the reference profiler's per-module tree
    (``flops_profiler/profiler.py``, torch module hooks): flax's
    ``named_scope`` paths in the jaxpr are the module boundaries.

    ``depth`` collapses paths to their first N segments (``None`` keeps
    full paths). Values sum EXACTLY to the ``""``-keyed aggregate (ops
    outside any named module are keyed by their call-site path, at
    minimum the empty root). Pass ``jaxpr`` (a ClosedJaxpr, e.g. from
    ``jax.jit(fn).trace(...).jaxpr``) to reuse an existing trace."""
    if jaxpr is None:
        jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)
    acc: Dict[str, float] = {}
    _walk_modules(jaxpr.jaxpr, "", 1.0, acc)
    if depth is not None:
        collapsed: Dict[str, float] = {}
        for path, f in acc.items():
            key = "/".join(path.split("/")[:depth]) if path else ""
            collapsed[key] = collapsed.get(key, 0.0) + f
        acc = collapsed
    return acc


def _params_by_module(params, path: str):
    """Best-effort param count for a module path: strip the root module
    segment, then walk dict keys."""
    if params is None or not isinstance(params, dict):
        return None
    segs = path.split("/")
    if len(segs) < 2:  # root rows would claim the whole tree — show '-'
        return None
    node = params
    if "params" in node and isinstance(node["params"], dict):
        node = node["params"]
    for seg in segs[1:]:  # segs[0] is the root module's own name
        if isinstance(node, dict) and seg in node:
            node = node[seg]
        else:
            return None
    return _params_count(node)


def format_module_table(breakdown: Dict[str, float],
                        params: Any = None) -> str:
    """Reference-style per-module table: FLOPs, share of total, params.
    Total line is the exact sum of the rows above it."""
    total = sum(breakdown.values()) or 1.0
    rows = sorted(breakdown.items(), key=lambda kv: -kv[1])
    width = max([len(k) for k in breakdown] + [8])
    lines = [f"{'module':<{width}}  {'flops':>10}  {'%':>6}  {'params':>9}"]
    for path, f in rows:
        pcount = _params_by_module(params, path)
        lines.append(
            f"{path or '(root)':<{width}}  "
            f"{number_to_string(f):>10}  {100 * f / total:>5.1f}%  "
            f"{number_to_string(pcount) if pcount is not None else '-':>9}")
    lines.append(f"{'TOTAL':<{width}}  "
                 f"{number_to_string(sum(breakdown.values())):>10}  "
                 f"{'100.0%':>6}  "
                 f"{number_to_string(_params_count(params)) if params is not None else '-':>9}")
    return "\n".join(lines)


def number_to_string(num: float, units: Optional[str] = None,
                     precision: int = 2) -> str:
    """Human units like the reference's flops_to_string/params_to_string."""
    for threshold, suffix in ((1e12, "T"), (1e9, "G"), (1e6, "M"),
                              (1e3, "K")):
        if units == suffix or (units is None and abs(num) >= threshold):
            return f"{num / threshold:.{precision}f} {suffix}"
    return f"{num:.{precision}f}"


def get_model_profile(fn: Callable, args: Tuple = (), kwargs: Dict = None,
                      warm_up: int = 1, num_steps: int = 3,
                      as_string: bool = False,
                      params: Any = None,
                      per_module_depth: Optional[int] = 2) -> Dict[str, Any]:
    """Profile a jittable callable: flops, HBM bytes, params, latency,
    achieved FLOP/s (reference ``get_model_profile``), plus the
    per-module breakdown table (``per_module_depth=None`` disables;
    reference analog: the profiler's aggregated module tree)."""
    kwargs = kwargs or {}
    # ONE trace serves both the compiled cost analysis and the module
    # walk (jit(fn).trace exposes the jaxpr and lowers from it)
    traced = jax.jit(fn).trace(*args, **kwargs)
    closed = traced.jaxpr
    compiled = traced.lower().compile()
    # one executable-stats plumbing for the whole codebase
    # (telemetry/compile_watch.py) — the profiler and the compile watch
    # can never report different numbers for the same executable
    from deepspeed_tpu.telemetry.compile_watch import executable_cost
    cost = executable_cost(compiled)
    breakdown = None
    if per_module_depth is not None:
        # never let attribution break the aggregate profile (a custom
        # primitive whose params the jaxpr walker doesn't recognize, a
        # jax version drifting a param key) — omit the breakdown instead
        try:
            breakdown = module_flops_breakdown(
                fn, *args, depth=per_module_depth, jaxpr=closed, **kwargs)
        except Exception as e:  # noqa: BLE001
            from deepspeed_tpu.utils.logging import logger
            logger.warning(f"per-module breakdown failed: {e}")
            breakdown = None
    for _ in range(max(warm_up, 1)):
        out = compiled(*args, **kwargs)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(max(num_steps, 1)):
        out = compiled(*args, **kwargs)
    # force a host sync: fetching an output is the barrier that ends the
    # timed work (dispatch is asynchronous)
    np.asarray(jax.tree.leaves(out)[0])
    latency = (time.perf_counter() - t0) / max(num_steps, 1)

    prof = {
        "flops": cost["flops"],
        "bytes_accessed": cost["bytes_accessed"],
        "hbm_bytes": cost.get("hbm_bytes", 0.0),
        "params": _params_count(params if params is not None else args),
        "latency_s": latency,
        "flops_per_s": cost["flops"] / latency if latency > 0 else 0.0,
    }
    if breakdown is not None:
        prof["module_breakdown"] = breakdown
        prof["module_flops_total"] = sum(breakdown.values())
    if as_string:
        prof = {
            "flops": number_to_string(prof["flops"]) + "FLOPs",
            "bytes_accessed": number_to_string(prof["bytes_accessed"]) + "B",
            "hbm_bytes": number_to_string(prof["hbm_bytes"]) + "B",
            "params": number_to_string(prof["params"]),
            "latency_s": f"{latency * 1e3:.2f} ms",
            "flops_per_s": number_to_string(prof["flops_per_s"]) + "FLOPS",
        }
        if breakdown is not None:
            prof["module_table"] = format_module_table(
                breakdown, params if params is not None
                else (args[0] if args else None))
    return prof


class FlopsProfiler:
    """Engine-integrated profiler (config section ``flops_profiler``):
    records the step's cost analysis + wall time at ``profile_step`` and
    prints the reference-style summary."""

    def __init__(self, engine=None, profile_step: int = 1,
                 top_modules: int = 1, detailed: bool = True,
                 output_file: Optional[str] = None):
        self.engine = engine
        self.profile_step = profile_step
        self.detailed = detailed
        self.output_file = output_file
        self.started = False
        self._t0 = 0.0
        self.results: Dict[str, Any] = {}

    def start_profile(self) -> None:
        self.started = True
        self._latency = None
        self._t0 = time.perf_counter()

    def mark_step_done(self) -> None:
        """Call right after the host sync — freezes the latency BEFORE any
        cost-analysis work so compile/analysis time never pollutes it."""
        if self.started:
            self._latency = time.perf_counter() - self._t0

    def stop_profile(self, flops: float = 0.0, params: int = 0,
                     module_breakdown: Optional[Dict[str, float]] = None
                     ) -> None:
        if not self.started:
            return
        latency = (self._latency if self._latency is not None
                   else time.perf_counter() - self._t0)
        self.results = {
            "flops": flops, "params": params, "latency_s": latency,
            "flops_per_s": flops / latency if latency > 0 else 0.0}
        if module_breakdown:
            self.results["module_breakdown"] = module_breakdown
        self.started = False

    def print_model_profile(self) -> str:
        r = self.results
        lines = [
            "-" * 60,
            "DeepSpeed-TPU Flops Profiler",
            f"params:               {number_to_string(r.get('params', 0))}",
            f"fwd+bwd+step flops:   {number_to_string(r.get('flops', 0))}",
            f"step latency:         {r.get('latency_s', 0) * 1e3:.2f} ms",
            f"achieved:             "
            f"{number_to_string(r.get('flops_per_s', 0))}FLOPS",
            "-" * 60,
        ]
        if r.get("module_breakdown"):
            # the reference's aggregated module tree (forward
            # attribution; its bwd convention is 2x fwd)
            ptree = getattr(getattr(self.engine, "state", None),
                            "params", None)
            lines += ["per-module forward FLOPs:",
                      format_module_table(r["module_breakdown"], ptree),
                      "-" * 60]
        text = "\n".join(lines)
        if self.output_file:
            with open(self.output_file, "a") as f:
                f.write(text + "\n")
        else:
            print(text)
        return text
