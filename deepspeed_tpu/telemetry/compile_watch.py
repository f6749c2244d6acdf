"""Compile watch: every jit entry point becomes attributable.

Silent retracing is the dominant TPU serving regression: one unexpected
argument shape recompiles the decode step or the whole generation loop,
and the job stalls for seconds to minutes with nothing in the logs. This
module wraps the jit entry points (``utils/jit.instance_cached_jit``,
the engines' step/decode/prefill closures) so that every (re)trace is:

* **detected** — the wrapper keys calls by abstract signature (shape /
  dtype / weak-type per leaf, value for statics), exactly the shape of
  jax's own trace cache, so a new key IS a retrace;
* **attributed** — the signature diff against the previous executable
  names the argument whose shape/dtype changed (``input_ids:
  i32[1,128] -> i32[1,256]``), recorded as a ``retrace`` flight-recorder
  event and a ``jit_retraces_total{fn=...}`` counter;
* **costed** — compilation runs ahead-of-time (``lower().compile()``)
  under a wall-clock timer, and the executable's ``cost_analysis()`` /
  ``memory_analysis()`` (flops, bytes accessed, HBM footprint) land in
  the record, the registry, and the human-readable
  :func:`compile_report`.

The AOT path manages its own executable cache (one ``Compiled`` per
signature) instead of re-entering ``jax.jit`` dispatch — that is what
makes compile time exact (no first-execution pollution) and the cost
analysis free (no second compile). A compile error raises from the call
that asked for the program: there is no retry through plain dispatch,
which would compile a second time and surface the same refusal from
another frame. The one call the AOT path cannot serve is a call under an
outer trace (``jax.eval_shape`` / ``jit`` / ``grad`` around a watched
function): a ``Compiled`` takes no tracers, so such a call goes to the
plain ``jax.jit`` and is inlined into the outer program, unrecorded.

Hot-path cost: the cache-hit path is one C-level ``tree_flatten`` plus
an O(leaves) python key build and the AOT ``Compiled.__call__``
(measured ~90 µs/call over plain jit dispatch on a 40-leaf tree, CPU) —
under 1% of a real decode step, and dwarfed by the retraces it
catches. Path strings and signature diffs are built only on a miss.
"""
from __future__ import annotations

import dataclasses
import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Tuple

import deepspeed_tpu.telemetry.events as _ev
from deepspeed_tpu.telemetry.registry import MetricRegistry, get_registry

# compile times span ~1 ms (tiny CPU test program) to ~30 min (cold
# multi-host train step); the default 100 µs ladder covers it
_DTYPE_SHORT = (("bfloat16", "bf16"), ("float", "f"), ("uint", "u"),
                ("int", "i"), ("complex", "c"))


def _short_dtype(name: str) -> str:
    for long, short in _DTYPE_SHORT:
        if name.startswith(long):
            return short + name[len(long):]
    return name


def _leaf_key(x) -> Tuple:
    """Abstract key for one pytree leaf — shape/dtype/weak-type for
    arrays (jax's trace-cache granularity), type identity for python
    scalars (jit keys them weakly, not by value). Runs on the hot path
    (every watched call), so the dtype stays an object — hashable and
    comparable without a per-call str() allocation."""
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return (tuple(x.shape), x.dtype,
                bool(getattr(x, "weak_type", False)))
    if isinstance(x, (bool, int, float, complex)):
        return ("py", type(x).__name__)
    return ("static", repr(x))


def _fmt_key(key: Tuple) -> str:
    if key and key[0] == "py":
        return f"py:{key[1]}"
    if key and key[0] == "static":
        return f"static:{key[1]}"
    shape, dtype, weak = key
    dims = ",".join(str(d) for d in shape)
    return f"{_short_dtype(str(dtype))}[{dims}]{'~' if weak else ''}"


def executable_cost(compiled) -> Dict[str, float]:
    """Normalized cost/memory stats for ONE compiled executable — the
    single plumbing ``get_model_profile``, the training profiler step,
    and the compile watch all share, so no two surfaces can report
    different numbers for the same executable.

    ``hbm_bytes`` is the executable's device-memory footprint:
    arguments + outputs + scratch, minus donated aliasing."""
    c: Any = {}
    try:
        c = compiled.cost_analysis() or {}
    except Exception:  # noqa: BLE001 — cost analysis is best-effort
        c = {}
    out = {"flops": float(c.get("flops", 0.0)),
           "bytes_accessed": float(c.get("bytes accessed", 0.0))}
    try:
        m = compiled.memory_analysis()
        arg = float(m.argument_size_in_bytes)
        outp = float(m.output_size_in_bytes)
        tmp = float(m.temp_size_in_bytes)
        alias = float(m.alias_size_in_bytes)
        out.update(argument_bytes=arg, output_bytes=outp, temp_bytes=tmp,
                   alias_bytes=alias,
                   hbm_bytes=max(arg + outp + tmp - alias, 0.0))
    except Exception:  # noqa: BLE001
        out["hbm_bytes"] = 0.0
    return out


@dataclasses.dataclass
class ExecutableRecord:
    """One compiled executable of a watched function."""
    index: int
    summary: str                       # per-arg aval summary (report)
    leaves: Dict[str, Tuple]           # path -> leaf key (retrace diff)
    compile_seconds: float
    cost: Dict[str, float]
    calls: int = 0
    compiled: Any = None


_registry_lock = threading.Lock()
_watched: "weakref.WeakSet" = weakref.WeakSet()
_watched_counter = [0]


def all_watched() -> List["WatchedFunction"]:
    """Live watched functions, in creation order."""
    with _registry_lock:
        return sorted(_watched, key=lambda w: w._order_id)


class WatchedFunction:
    """``jax.jit`` with a flight recorder attached. Drop-in: call it,
    ``.lower()`` it, read ``._cache_size()`` — plus ``.retraces``,
    ``.executables``, ``.report()``."""

    def __init__(self, fun, name: str,
                 registry: Optional[MetricRegistry] = None,
                 ring: Optional[_ev.EventRing] = None, **jit_kwargs):
        import jax
        self._fun = fun
        self.name = name
        self._jit = jax.jit(fun, **jit_kwargs)
        self._registry = registry
        self._ring = ring
        self._static_names = tuple(jit_kwargs.get("static_argnames") or ())
        self._static_nums = tuple(jit_kwargs.get("static_argnums") or ())
        self._execs: Dict[Tuple, ExecutableRecord] = {}
        self._records: List[ExecutableRecord] = []   # creation order
        self._last: Optional[ExecutableRecord] = None
        self.retraces: List[dict] = []
        self._lock = threading.RLock()
        self._arg_names = self._positional_names(fun)
        # static_argnames resolved to POSITIONS too — a static passed
        # positionally must be value-keyed exactly like jit specializes
        self._static_idx = tuple(sorted(set(
            list(self._static_nums)
            + [self._arg_names.index(n) for n in self._static_names
               if n in self._arg_names])))
        with _registry_lock:
            _watched_counter[0] += 1
            self._order_id = _watched_counter[0]
        _watched.add(self)

    @staticmethod
    def _positional_names(fun) -> List[str]:
        try:
            import inspect
            return [p.name for p in
                    inspect.signature(fun).parameters.values()
                    if p.kind in (p.POSITIONAL_ONLY,
                                  p.POSITIONAL_OR_KEYWORD)]
        except (TypeError, ValueError):
            return []

    # ---------------------------------------------------------- signature

    def _path_str(self, path) -> str:
        """Human path for one leaf of ``(args, kwargs)``: the top-level
        argument name (from the wrapped function's signature when
        resolvable) plus the intra-tree remainder."""
        import jax
        top, rest = path[0], path[1:]
        idx = getattr(top, "idx", getattr(top, "key", None))
        if idx == 0:       # positional args
            i = getattr(rest[0], "idx", 0) if rest else 0
            base = (self._arg_names[i] if i < len(self._arg_names)
                    else f"args[{i}]")
            rest = rest[1:]
        else:              # kwargs
            base = str(getattr(rest[0], "key", rest[0])) if rest else "kwargs"
            rest = rest[1:]
        tail = jax.tree_util.keystr(tuple(rest)) if rest else ""
        return base + tail

    def _signature(self, args, kwargs) -> Tuple:
        """Hot-path cache key: treedef (hashable) + per-leaf abstract
        keys. Path strings for retrace diffing are NOT built here — see
        :meth:`_leaves_with_paths`, which only runs on a miss."""
        import jax
        flat, treedef = jax.tree_util.tree_flatten((args, dict(kwargs)))
        key: Tuple = (treedef, tuple(_leaf_key(x) for x in flat))
        # static args are keyed by VALUE (jit specializes on them); the
        # coarse leaf key above would collide e.g. K=4 with K=8
        statics = tuple(
            (n, repr(kwargs[n])) for n in self._static_names
            if n in kwargs) + tuple(
            (i, repr(args[i])) for i in self._static_idx
            if i < len(args))
        if statics:
            key = key + (statics,)
        return key

    def _leaves_with_paths(self, args, kwargs) -> Dict[str, Tuple]:
        import jax
        flat, _ = jax.tree_util.tree_flatten_with_path(
            (args, dict(kwargs)))
        out = {self._path_str(p): _leaf_key(x) for p, x in flat}
        # static args are VALUE-keyed in the signature (_signature), so
        # the retrace diff must see their values too — otherwise a
        # static toggle (e.g. the engine's numerics flag) retraces with
        # an empty attribution
        for i in self._static_idx:
            if i < len(args):
                name = (self._arg_names[i] if i < len(self._arg_names)
                        else f"args[{i}]")
                out[name] = ("static", repr(args[i]))
        for n in self._static_names:
            if n in kwargs:
                out[n] = ("static", repr(kwargs[n]))
        return out

    def _summarize(self, args, kwargs) -> str:
        """Per-argument aval summary: small args spelled out, big trees
        as leaf counts — ``params:<58 leaves>, input_ids:i32[1,128]``."""
        import jax
        parts = []
        for i, a in enumerate(args):
            name = (self._arg_names[i] if i < len(self._arg_names)
                    else f"args[{i}]")
            parts.append((name, a))
        parts += sorted(kwargs.items())
        out = []
        for name, val in parts:
            lv = jax.tree_util.tree_leaves(val)
            if len(lv) == 1:
                out.append(f"{name}:{_fmt_key(_leaf_key(lv[0]))}")
            else:
                out.append(f"{name}:<{len(lv)} leaves>")
        return ", ".join(out)

    # ------------------------------------------------------------- helpers

    def _reg(self) -> MetricRegistry:
        return self._registry if self._registry is not None \
            else get_registry()

    def _events(self) -> _ev.EventRing:
        # explicit None check: an EMPTY ring is falsy (__len__ == 0) and
        # `or` would silently swap in the process ring
        return self._ring if self._ring is not None \
            else _ev.get_event_ring()

    def _diff(self, prev: Dict[str, Tuple], new: Dict[str, Tuple]):
        """What changed between two signatures: per-leaf transitions plus
        the set of top-level argument names they belong to."""
        changed, args = [], []
        for path in sorted(set(prev) | set(new)):
            a, b = prev.get(path), new.get(path)
            if a == b:
                continue
            a_s = _fmt_key(a) if a is not None else "<absent>"
            b_s = _fmt_key(b) if b is not None else "<absent>"
            changed.append(f"{path}: {a_s} -> {b_s}")
            top = path.split("[")[0].split(".")[0]
            if top not in args:
                args.append(top)
        return changed, args

    # ---------------------------------------------------------------- call

    def _compile(self, key, args, kwargs) -> ExecutableRecord:
        """Build (and record) the executable for a new signature. Caller
        holds the lock."""
        leaves = self._leaves_with_paths(args, kwargs)
        summary = self._summarize(args, kwargs)
        ring, reg = self._events(), self._reg()
        prev = self._last
        is_retrace = prev is not None
        ring.record(_ev.COMPILE_BEGIN, fn=self.name, signature=summary,
                    index=len(self._records))
        if is_retrace:
            changed, arg_names = self._diff(prev.leaves, leaves)
            info = {"fn": self.name, "changed": changed,
                    "args": arg_names,
                    "prev_signature": prev.summary,
                    "signature": summary}
            self.retraces.append(info)
            ring.record(_ev.RETRACE, **info)
            reg.counter(
                "jit_retraces_total",
                help="recompiles after the first trace, by function "
                     "(the silent-stall regression — see "
                     "docs/observability.md)",
                labels={"fn": self.name}).inc()
        t0 = time.perf_counter()   # a compile error raises from here
        compiled = self._jit.lower(*args, **kwargs).compile()
        dt = time.perf_counter() - t0
        cost = executable_cost(compiled)
        rec = ExecutableRecord(
            index=len(self._records), summary=summary, leaves=leaves,
            compile_seconds=dt, cost=cost, compiled=compiled)
        self._execs[key] = rec
        self._records.append(rec)
        self._last = rec
        reg.counter("jit_compiles_total",
                    help="executables compiled, by function",
                    labels={"fn": self.name}).inc()
        reg.histogram("jit_compile_seconds",
                      help="trace+lower+compile wall time, by function",
                      labels={"fn": self.name}).observe(dt)
        reg.gauge("jit_executable_flops",
                  help="cost_analysis flops of the latest executable",
                  labels={"fn": self.name}).set(cost.get("flops", 0.0))
        reg.gauge("jit_executable_hbm_bytes",
                  help="memory_analysis footprint (args+outputs+temp-"
                       "aliased) of the latest executable",
                  labels={"fn": self.name}).set(cost.get("hbm_bytes", 0.0))
        ring.record(_ev.COMPILE_END, fn=self.name, seconds=round(dt, 6),
                    flops=cost.get("flops", 0.0),
                    hbm_bytes=cost.get("hbm_bytes", 0.0),
                    index=rec.index)
        return rec

    def _dynamic_only(self, args, kwargs):
        """Args/kwargs with the statics stripped — ``Compiled.__call__``
        takes only the dynamic arguments (statics were burned into the
        executable at lower time); passing them through raises a pytree
        mismatch."""
        if not self._static_idx and not self._static_names:
            return args, kwargs
        dyn = tuple(a for i, a in enumerate(args)
                    if i not in self._static_idx)
        dkw = {k: v for k, v in kwargs.items()
               if k not in self._static_names}
        return dyn, dkw

    @staticmethod
    def _traced(args, kwargs) -> bool:
        """Called under an outer trace? Only asked off the hot path: on
        a signature miss and when a ``Compiled`` refuses its arguments."""
        import jax
        return any(isinstance(x, jax.core.Tracer)
                   for x in jax.tree_util.tree_leaves((args, kwargs)))

    def __call__(self, *args, **kwargs):
        key = self._signature(args, kwargs)
        rec = self._execs.get(key)
        if rec is None:
            if self._traced(args, kwargs):
                return self._jit(*args, **kwargs)   # inlined, unrecorded
            with self._lock:
                rec = self._execs.get(key)   # lost the race → reuse
                if rec is None:
                    rec = self._compile(key, args, kwargs)
        dyn_args, dyn_kwargs = self._dynamic_only(args, kwargs)
        try:
            out = rec.compiled(*dyn_args, **dyn_kwargs)
        except TypeError:
            # a signature compiled earlier, met again under an outer
            # trace: tracers carry the same avals but cannot enter a
            # Compiled. Anything else is the caller's error.
            if not self._traced(args, kwargs):
                raise
            return self._jit(*args, **kwargs)
        rec.calls += 1
        return out

    # ----------------------------------------------------------- jit parity

    def lower(self, *args, **kwargs):
        return self._jit.lower(*args, **kwargs)

    def _cache_size(self) -> int:
        """Executable count — keeps ``server.stats`` trace accounting
        working on a watched function."""
        return len(self._records)

    # ----------------------------------------------------------- profiling

    def warm(self, *args, **kwargs) -> ExecutableRecord:
        """Compile (if needed) for this signature WITHOUT executing —
        the profiler's pre-compile, and the cost source for
        :meth:`cost` (no second compile ever happens for a signature)."""
        key = self._signature(args, kwargs)
        with self._lock:
            rec = self._execs.get(key)
            if rec is None:
                rec = self._compile(key, args, kwargs)
        return rec

    def cost(self, *args, **kwargs) -> Dict[str, float]:
        """cost/memory stats of this signature's executable."""
        return dict(self.warm(*args, **kwargs).cost)

    # -------------------------------------------------------------- report

    @property
    def executables(self) -> List[ExecutableRecord]:
        return list(self._records)

    def report(self) -> str:
        from deepspeed_tpu.profiling.flops_profiler import number_to_string
        lines = [f"{self.name}: {len(self._records)} executable(s), "
                 f"{len(self.retraces)} retrace(s)"]
        for rec in self._records:
            lines.append(
                f"  [{rec.index}] {rec.summary}\n"
                f"      compile {rec.compile_seconds * 1e3:.1f} ms, "
                f"{number_to_string(rec.cost.get('flops', 0.0))}FLOPs, "
                f"hbm {number_to_string(rec.cost.get('hbm_bytes', 0.0))}B, "
                f"calls {rec.calls}")
        for r in self.retraces:
            lines.append("  retrace: " + "; ".join(r["changed"][:4])
                         + (" …" if len(r["changed"]) > 4 else ""))
        return "\n".join(lines)


def watched_jit(fun, name: str,
                registry: Optional[MetricRegistry] = None,
                ring: Optional[_ev.EventRing] = None,
                **jit_kwargs) -> WatchedFunction:
    """``jax.jit(fun, **jit_kwargs)`` with retrace detection, compile
    timing, and executable cost attribution (see module docstring)."""
    return WatchedFunction(fun, name, registry=registry, ring=ring,
                           **jit_kwargs)


def compile_report() -> str:
    """Human-readable report over every live watched function: per
    executable its signature, compile time, flops, and HBM footprint;
    per function its retrace history with argument attribution. The
    after-the-fact answer to "why did that step take 40 s"."""
    watched = all_watched()
    if not watched:
        return "compile report: no watched functions"
    total_execs = sum(len(w._records) for w in watched)
    total_re = sum(len(w.retraces) for w in watched)
    total_s = sum(r.compile_seconds for w in watched for r in w._records)
    lines = [f"compile report: {len(watched)} function(s), "
             f"{total_execs} executable(s), {total_re} retrace(s), "
             f"{total_s:.2f} s total compile time"]
    lines += [w.report() for w in watched]
    return "\n".join(lines)
