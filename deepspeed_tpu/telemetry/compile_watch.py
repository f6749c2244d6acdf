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

Names and phases (docs/observability.md "Spans"):

* the jitted callable carries the watch's name, so the compiled module
  and the device trace's ``XLA Modules`` events read
  ``jit_serve_decode``, never ``jit__unknown``;
* every compile leaves a ``compile:<program>`` span in the span log with
  its ``compile:trace`` / ``compile:lower`` and ``compile:backend_compile``
  or ``compile:cache_read`` children;
* ``jax.monitoring`` listeners, registered once
  (:func:`install_phase_listeners`), keep per function name jax reports
  (watched or not) the seconds traced, lowered, compiled and read from
  the persistent cache, and hit / miss counts: :func:`phase_totals`;
* each compiled program's optimized HLO is parsed on demand, in one
  pass, into four tables keyed by instruction name: its scopes
  (:func:`scope_table`), for custom calls the kernel's name
  (:func:`kernel_table`), the pass of a train step it belongs to
  (:func:`pass_table`: ``fwd`` / ``recompute`` / ``bwd`` /
  ``optimizer``) and, for every instruction that moves data between
  the host's memory and the device's or between chips, what it moves
  (:func:`movement_table`: kind, bytes, the other half of its pair,
  pass, scopes; the collectives the partitioner inserted and the
  offload stream's copies are in no Python source, only here). The
  device trace names instructions but carries no ``op_name``, and the
  executables are gone by the time a trace is read: a watched
  function's modules are taken when it is finalized, so the tables
  survive it and setting a program up pays nothing for them.

Hot-path cost: the cache-hit path is one C-level ``tree_flatten`` plus
an O(leaves) python key build and the AOT ``Compiled.__call__``
(measured ~90 µs/call over plain jit dispatch on a 40-leaf tree, CPU) —
under 1% of a real decode step, and dwarfed by the retraces it
catches. Path strings and signature diffs are built only on a miss.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import re
import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Tuple

import deepspeed_tpu.telemetry.events as _ev
from deepspeed_tpu.telemetry.registry import MetricRegistry, get_registry
from deepspeed_tpu.telemetry.spans import annotation, get_span_log

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


# ------------------------------------------------ compile phases (monitoring)

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_phase_lock = threading.Lock()
_phases: Dict[str, Dict[str, float]] = {}
# persistent-cache hits jax announced, and how many of them a backend
# event has been counted as (a hit is announced inside its interval)
_hits = {"seen": 0, "claimed": 0}
_listening = [False]
_tracing = threading.local()  # this thread's open and just-ended traces


def _new_phase_row() -> Dict[str, float]:
    return {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
            "cache_read_s": 0.0, "text_s": 0.0, "traces": 0, "compiles": 0,
            "cache_hits": 0, "cache_misses": 0}


def _program_of(fun_name: Optional[str]) -> str:
    """jax reports a function's own name while tracing and the module's
    (``jit(<name>)`` / ``jit_<name>``) while lowering and compiling: one
    key for both."""
    name = fun_name or "_unnamed_"
    if name.startswith("jit(") and name.endswith(")"):
        return name[4:-1]
    return name[4:] if name.startswith("jit_") else name


def _on_begin(event: str, value: float, **kw) -> None:
    if event == TRACE_EVENT:
        _tracing.depth = getattr(_tracing, "depth", 0) + 1


def _own_trace_seconds(start: float, seconds: float) -> float:
    """A trace's seconds less those of the traces that ran inside it
    (a jitted function traced while an outer one was being traced
    reports first, and the outer one's interval covers it): summed over
    functions, ``trace_s`` is then wall time and counts nothing twice."""
    ended = getattr(_tracing, "ended", None)
    if ended is None:
        ended = _tracing.ended = []
    inner = 0.0
    while ended and ended[-1][0] >= start:
        inner += ended.pop()[1]
    depth = _tracing.depth = max(getattr(_tracing, "depth", 1) - 1, 0)
    if depth:
        ended.append((start, seconds))     # for the trace still open
    else:
        ended.clear()
    return max(seconds - inner, 0.0)


def _on_span(event: str, start: float, end: float, **kw) -> None:
    field = {TRACE_EVENT: "trace_s", LOWER_EVENT: "lower_s",
             BACKEND_EVENT: "compile_s"}.get(event)
    if field is None:
        return
    seconds = end - start
    if event == TRACE_EVENT:
        seconds = _own_trace_seconds(start, seconds)
    with _phase_lock:
        row = _phases.setdefault(_program_of(kw.get("fun_name")),
                                 _new_phase_row())
        if event == TRACE_EVENT:
            row["traces"] += 1
        if event == BACKEND_EVENT:
            # jax times compile_or_get_cached as one interval; a hit of
            # the persistent cache was announced inside it
            row["compiles"] += 1
            if _hits["seen"] > _hits["claimed"]:
                row["cache_hits"] += 1
                field = "cache_read_s"
            else:
                row["cache_misses"] += 1
            _hits["claimed"] = _hits["seen"]
        row[field] += seconds


def _on_event(event: str, **kw) -> None:
    if event == CACHE_HIT_EVENT:
        with _phase_lock:
            _hits["seen"] += 1


def install_phase_listeners() -> None:
    """Register the ``jax.monitoring`` listeners behind
    :func:`phase_totals`, once per process (``import deepspeed_tpu``
    does, so a program compiled before the first watch is counted)."""
    with _phase_lock:
        if _listening[0]:
            return
        _listening[0] = True
    import jax
    jax.monitoring.register_scalar_listener(_on_begin)
    jax.monitoring.register_event_time_span_listener(_on_span)
    jax.monitoring.register_event_listener(_on_event)


def phase_totals() -> Dict[str, Dict[str, float]]:
    """Per function name jax reported (watched or not): seconds tracing
    its own body (``trace_s``: functions traced inside it are under
    their own names), lowering to MLIR (``lower_s``), in backend
    compiles the persistent cache did not serve (``compile_s``) and in
    those it did (``cache_read_s``), for a watched program the seconds
    taking its modules for the scope table at teardown (``text_s``), with
    the counts ``traces``, ``compiles``, ``cache_hits``,
    ``cache_misses`` (``compiles`` = hits + misses; with the cache off
    every compile is a miss)."""
    with _phase_lock:
        return {k: dict(v) for k, v in _phases.items()}


def _named(fun, name: str):
    """``fun`` under the watch's name. ``jax.jit`` names the compiled
    module after ``fun.__name__`` and a ``functools.partial`` has none
    (``jit__unknown``); the wrapper keeps the signature jit resolves
    ``static_argnames`` / ``donate_argnames`` against."""
    @functools.wraps(fun)
    def named(*args, **kwargs):
        return fun(*args, **kwargs)
    named.__name__ = named.__qualname__ = name
    return named


# ------------------------------------------------- scopes on the device

# the scopes the programs open (model_implementations/transformer.py,
# inference/kv_cache.py, inference/server.py; runtime/engine.py opens
# the train step's three: fwd_bwd, optimizer, grad_exchange)
SCOPES = frozenset((
    "embed", "ln", "attn_qkv", "kv_write", "kv_read", "attn_kernel",
    "attn_out", "mlp", "lm_head", "sample",
    "fwd_bwd", "optimizer", "grad_exchange",
    # model_implementations/longcat_flash.py
    "mla_qkv", "latent_write", "mla_attn", "dense_ffn", "moe_router",
    "moe_dispatch", "moe_experts", "moe_combine",
    # model_implementations/brumby.py
    "ret_qkvg", "ret_state", "ret_out",
    # model_implementations/laguna.py (an attention by the kind of its
    # layer, over its projections, rotary, gate, cache write and kernel)
    "attn_full", "attn_window", "moe_shared",
    # model_implementations/granite_hybrid.py (a Mamba mixer's input
    # projections, convolution, decode state update / prefill chunked
    # form, gated norm and output projection; its attention layers are
    # ``attn_full``)
    "mamba_in", "mamba_conv", "mamba_state", "mamba_scan", "mamba_out"))

_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s")
_COMPUTATION = re.compile(
    r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"(?:calls|to_apply|body)=%?([\w.\-]+)")
_OPERAND = re.compile(r"\(\s*(?:[\w\[\]{},:()\s]*?)%([\w.\-]+)")
_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")
_NAMES = re.compile(r"%([\w.\-]+)")
# the opcode: the first word followed by "(" after the result's shape (a
# shape has no space before a parenthesis: "{1,0:T(8,128)S(5)}")
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9\-]*)\(")
# one array of a shape: element type, dimensions, layout (its memory
# space is the "S(n)" in the layout: none = the device's HBM)
_ARRAY = re.compile(r"\b([a-z]+\d+\w*|pred)\[([\d,]*)\](\{[^}]*\})?")
_SPACE = re.compile(r"S\((\d+)\)")
_GROUPS = re.compile(r"replica_groups=(?:\[\d+,(\d+)\]|\{\{([\d,]*)\})")
_TRIPS = re.compile(r'known_trip_count"?:\{"?n"?:"?(\d+)')
_CONTROL = re.compile(
    r"(?:to_apply|true_computation|false_computation)=%?([\w.\-]+)"
    r"|branch_computations=\{([^}]*)\}")
HOST_SPACE = 5                   # pinned host memory, as the TPU compiler
#                                  writes it: "f32[2048]{0:T(1024)S(5)}"

# what moves data between chips (the opcodes; each may come as a
# "<op>-start" / "<op>-done" pair)
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast",
               "ragged-all-to-all")
# ONE list of the opcodes that move or forward data. True: where the
# compiler gave such an instruction no metadata (it gives a memory-space
# copy none) the scope table lets it take its operand's scope and,
# failing that, its first scoped consumer's. The movement table reads
# the rest of the list: every copy pair, collective and async wrapper.
_DATA_OPS = {
    "copy-start": True, "copy-done": True, "async-start": True,
    "async-done": True, "slice-start": True, "slice-done": True,
    "all-gather-done": True, "all-reduce-done": True,
    "collective-permute-done": True, "get-tuple-element": True,
    "bitcast": True, "async-update": False,
    **{c + h: False for c in COLLECTIVES for h in ("", "-start", "-done")
       if c + h not in ("all-gather-done", "all-reduce-done",
                        "collective-permute-done")}}
_FORWARDS = frozenset(k for k, forwards in _DATA_OPS.items() if forwards)
_ASYNC_TARGETS = {"AsyncCollectiveStart": "start",
                  "AsyncCollectiveDone": "done"}

_texts: Dict[str, list] = {}     # program -> modules of executables now gone
KEEP_EXECUTABLES = 8             # of them, per program name
_tables: Dict[str, tuple] = {}   # program -> (sources parsed, its tables)
_tables_lock = threading.Lock()


def scopes_of(op_name: str) -> Optional[str]:
    """``jit(serve_decode)/kv_read/slice`` -> ``kv_read``;
    ``jit(train_step)/fwd_bwd/transpose(jvp(mlp))/dot_general`` ->
    ``fwd_bwd/mlp``: the known scopes on an instruction's path,
    outermost first (the innermost is the last part), or None."""
    found = []
    # where the compiler merged instructions it joined their op_names
    # with ";", the consumer first: the producer's (last) path is kept,
    # so a gather's ``squeeze`` stays ``kv_read`` after merging with its
    # consumer's reshape
    for part in op_name.rsplit(";", 1)[-1].split("/")[:-1]:
        for word in _WORD.findall(part):
            if word in SCOPES and (not found or found[-1] != word):
                found.append(word)
    return "/".join(found) if found else None


def pass_of(op_name: str) -> Optional[str]:
    """The pass of a train step an instruction belongs to, from the path
    it carries: ``fwd`` (``jvp(`` without ``transpose(``), ``bwd``
    (``transpose(``), ``recompute`` (``rematted_computation``: what
    ``jax.checkpoint`` runs again inside the backward pass; the word
    ``checkpoint`` alone is on every instruction of a rematerialised
    block's backward, its real gradients included, and says nothing),
    ``optimizer`` (the step's own scope), or None. Read off the two
    train cells' programs compiled for the TPU:
    ``jit(train_step)/fwd_bwd/jvp(GPT2)/h_0/mlp/c_fc/dot_general``,
    ``.../fwd_bwd/transpose(jvp(GPT2))/fwd_bwd/jvp(GPT2)/checkpoint/
    h_1/attn/c_proj/dot_general``, ``.../transpose(jvp(GPT2))/fwd_bwd/
    jvp(GPT2)/checkpoint/rematted_computation/h_1/ln_1/mul``."""
    path = op_name.rsplit(";", 1)[-1]
    if "rematted_computation" in path:
        return "recompute"
    if "transpose(" in path:
        return "bwd"
    if "jvp(" in path:
        return "fwd"
    return "optimizer" if "optimizer" in path.split("/")[:-1] else None


def _arrays(shape: str) -> List[Tuple[float, int]]:
    """``(bytes, memory space)`` of each array in a shape's text."""
    out = []
    for m in _ARRAY.finditer(shape):
        bits = 8 if m.group(1) == "pred" else int(
            re.search(r"\d+", m.group(1)).group())
        n = 1
        for d in m.group(2).split(","):
            n *= int(d) if d else 1
        space = _SPACE.search(m.group(3) or "")
        out.append((n * bits / 8, int(space.group(1)) if space else 0))
    return out


def _elements(shape: str) -> List[str]:
    """A tuple shape's top-level elements (the shape itself where it is
    no tuple): ``(f32[4]{0}, (f32[2]{0}, u32[]))`` -> two."""
    shape = shape.strip()
    if not shape.startswith("("):
        return [shape]
    out, depth, at = [], 0, 1
    for i, ch in enumerate(shape):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
            if depth == 0:
                out.append(shape[at:i])
                break
        elif ch == "," and depth == 1:
            out.append(shape[at:i])
            at = i + 1
    return out


def wire_bytes(kind: str, nbytes: float, group: Optional[int]) -> float:
    """Bytes that cross a link to or from ONE chip when a buffer of
    ``nbytes`` goes through a collective over ``group`` chips: a gather,
    a reduce-scatter or an all-to-all keeps 1/n at home, an all-reduce
    is a reduce-scatter and a gather; a host copy, a permute or a
    broadcast moves the buffer whole."""
    if kind not in ("all-gather", "reduce-scatter", "all-to-all",
                    "all-reduce", "ragged-all-to-all") or not group:
        return nbytes
    part = nbytes * (group - 1) / group
    return 2 * part if kind == "all-reduce" else part


def _resolve(values: Dict[str, Optional[str]], calls, roots, forwards,
             users) -> None:
    """Fill the None entries of ``values`` (scopes, or passes): a call
    takes its called computation's root's, a forwarding instruction its
    operand's and, failing that, its first consumer's that has one."""
    for name, comp in calls.items():
        root = roots.get(comp)
        if values.get(name) is None and root is not None \
                and values.get(root) is not None:
            values[name] = values[root]
    for _ in range(3):                  # done(start(...)) chains are short
        for name, src in forwards.items():
            if values.get(name) is None and values.get(src) is not None:
                values[name] = values[src]
    for _ in range(3):                  # start <- done <- the consumer
        for name, used_by in users.items():
            if values.get(name) is None:
                values[name] = next((values[u] for u in used_by
                                     if values.get(u) is not None), None)


def _movement_row(opcode: str, shape: str, rhs: str) -> Optional[dict]:
    """The start of a movement row for a data-moving opcode (None for
    one that only forwards); ``kind`` None = not known from this line
    alone (a ``-done`` learns it from its ``-start``, a copy inside one
    memory keeps None and is dropped)."""
    base, half = opcode, "sync"
    for suffix in ("-start", "-done"):
        if opcode.endswith(suffix):
            base, half = opcode[:-len(suffix)], suffix[1:]
    if base == "copy" and half != "sync":
        row = {"kind": None, "role": half, "bytes": 0.0, "group": None}
        arrays = _arrays(shape)
        if half == "start" and len(arrays) >= 2:
            (nbytes, dst), (_, src) = arrays[0], arrays[1]
            if (dst == HOST_SPACE) != (src == HOST_SPACE):
                row.update(kind="device_to_host" if dst == HOST_SPACE
                           else "host_to_device", bytes=nbytes)
        return row
    if base == "async" and half != "sync":
        return {"kind": None, "role": half, "bytes": 0.0, "group": None}
    if base not in COLLECTIVES:
        return None
    g = _GROUPS.search(rhs)
    group = None
    if g is not None:
        group = int(g.group(1)) if g.group(1) else \
            len([x for x in g.group(2).split(",") if x])
    # a combined collective moves several arrays: a synchronous one's
    # result holds them all, a start's holds (operands, results, context)
    sizes = [sum(b for b, _ in _arrays(part)) for part in _elements(shape)]
    if half == "start" and len(sizes) >= 2:
        nbytes = max(sizes[0], sizes[1])
    else:
        nbytes = sum(sizes)
    if base == "reduce-scatter" and half == "sync":
        nbytes *= group or 1            # the operand's: what is reduced
    return {"kind": base, "role": half, "bytes": nbytes,
            "group": group or None}


def _finish_movement(rows, wrapped, forwards, loops, parent, scope,
                     passes) -> Dict[str, dict]:
    """Pair the halves, drop what is no movement, count the loops."""
    rows = {k: r for k, r in rows.items()
            if r["computation"] not in wrapped}

    def start_of(name: str, hops: int = 64) -> Optional[str]:
        """The ``-start`` a ``-done`` descends from: through the
        ``get-tuple-element`` of its tuple and, on the TPU, through the
        matmul fusions that carry the collective along."""
        todo = list(reversed(rows[name]["operands"]))
        while todo and hops > 0:
            src, hops = todo.pop(), hops - 1
            while src not in rows and src in forwards:
                src = forwards[src]
            row = rows.get(src)
            if row is None:
                continue
            if row["role"] == "start" and "pair" not in row:
                return src
            if row["role"] == "carrier":
                todo.extend(reversed(row["operands"][:2]))
        return None

    for name, row in rows.items():
        if row["role"] == "done":
            src = start_of(name)
            if src is not None:
                rows[src]["pair"], row["pair"] = name, src
                for key in ("kind", "bytes", "group"):
                    row[key] = rows[src][key]
    out = {}
    for name, row in rows.items():
        if row["kind"] is None:
            continue                    # a copy inside one memory
        calls, known, comp = 1, True, row["computation"]
        while comp in loops or comp in parent:
            if comp in loops:
                known = known and loops[comp] is not None
                calls *= loops[comp] or 1
            comp = parent.get(comp)
        other = row.get("pair")
        out[name] = {
            "kind": row["kind"], "role": row["role"],
            "bytes": row["bytes"] * calls, "group": row["group"],
            "wire_bytes": wire_bytes(row["kind"], row["bytes"],
                                     row["group"]) * calls,
            "calls": calls, "per_iteration": not known,
            "pair": other,
            # the halves of a pair say the same: a start the compiler
            # gave no metadata takes its done's
            "pass": passes.get(name) or passes.get(other),
            "scopes": scope.get(name) or scope.get(other)}
    return out


Tables = collections.namedtuple("Tables", "scopes kernels movement passes")
Tables.__doc__ = """What one parse of a compiled program's text gives:
``scopes`` and ``kernels`` (:func:`parse_scopes`), ``movement``
(:func:`movement_table`) and ``passes`` (:func:`pass_table`)."""


def parse(text: str) -> Tables:
    """A compiled program's text (``compiled.as_text()``) to its four
    tables, in one pass over its lines.

    Scopes: an instruction's come from its own ``op_name`` metadata; a
    fusion (or any call) without one takes its called computation's
    root's; a copy (``copy-start`` / ``copy-done``, the offload stream's
    transfers among them), ``get-tuple-element`` or ``bitcast`` without
    one its first operand's and, failing that, its first scoped
    consumer's. Passes (:func:`pass_of`) travel the same way.

    Movement: one row for every instruction that moves data between the
    host's memory and the device's, or between chips, and that the
    device trace can show (an instruction INSIDE a fusion is not one):

    * ``kind``: ``host_to_device`` / ``device_to_host`` for a
      ``copy-start`` / ``copy-done`` pair with pinned host memory
      (``S(5)``) on one side only (a copy inside the device, HBM to HBM
      or to its on-chip memories, is no row); else the collective's
      opcode (:data:`COLLECTIVES`), also for what wraps one: an
      ``async-start`` / ``async-done`` pair takes the opcode of the
      computation it wraps, and so does a fusion that holds a collective;
    * ``role``: ``sync``, ``start`` or ``done`` (the halves of an
      asynchronous pair: the compiler's own ``<op>-start`` / ``-done``,
      ``async-start`` / ``-done``, or the TPU's fusions around an
      ``AsyncCollectiveStart`` / ``AsyncCollectiveDone`` call, which the
      trace shows as ``async-collective-start.N``), ``fused`` (a fusion
      that is nothing but a collective, such as the TPU's all-reduce +
      slice written as ``fusion.N``) or ``carrier`` (a matmul fusion that
      carries a collective's steps along: compute, with the exchange
      under it);
    * ``bytes``: the buffer that moves, per execution of the program:
      the result's for a copy, a gather (the gathered array: the larger
      of operand and result), an all-reduce, an all-to-all or a permute,
      the OPERAND's (result x group) for a reduce-scatter; of a pair,
      both halves carry it. ``wire_bytes``: what of it crosses a link to
      or from one chip (:func:`wire_bytes`; ``group`` = chips in the
      instruction's replica group). ``calls``: executions per execution
      of the program, the product of the trip counts of the ``while``
      bodies around it where the text states them
      (``known_trip_count``); where it does not, ``per_iteration`` is
      True and bytes and calls are those of ONE iteration;
    * ``pair``: the ``-done`` of a ``-start`` and the other way;
    * ``pass`` and ``scopes``: as in the pass and scope tables."""
    scope: Dict[str, Optional[str]] = {}
    passes: Dict[str, Optional[str]] = {}
    kernels: Dict[str, str] = {}
    roots: Dict[str, str] = {}          # computation -> its root instruction
    calls: Dict[str, str] = {}          # instruction -> computation it calls
    forwards: Dict[str, str] = {}       # instruction -> its first operand
    users: Dict[str, List[str]] = {}    # such an instruction -> consumers
    rows: Dict[str, dict] = {}          # the movement table, being made
    inside: Dict[str, dict] = {}        # computation -> collective in it
    computes = set()                    # computations with a matmul
    async_root: Dict[str, str] = {}     # computation -> start | done
    root_op: Dict[str, str] = {}        # computation -> its root's opcode
    wrapped = set()                     # computations of fusions / asyncs
    loops: Dict[str, Optional[int]] = {}    # while body -> trip count
    parent: Dict[str, str] = {}         # computation -> where it is called
    computation = None
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                computation = c.group(1)
            continue
        name = m.group(2)
        if m.group(1) and computation is not None:
            roots[computation] = name
        op = _OP_NAME.search(line)
        scope[name] = scopes_of(op.group(1)) if op else None
        # XLA's own rematerialisation names its clones "<name>.remat<n>"
        # and leaves them their original's (forward) path
        passes[name] = "recompute" if ".remat" in name else \
            pass_of(op.group(1)) if op else None
        rhs = line[m.end():]
        at = _OPCODE.search(rhs)
        opcode = at.group(1) if at else ""
        if "custom_call_target=\"tpu_custom_call\"" in rhs:
            kernels[name] = ".".join(
                p for p in name.split(".") if not p.isdigit())
        c = _CALLS.search(rhs)
        if c is not None:
            calls[name] = c.group(1)
        elif opcode in _FORWARDS:
            o = _OPERAND.search(rhs[rhs.index("("):]
                                if "(" in rhs else "")
            if o is not None:
                forwards[name] = o.group(1)
            users[name] = []
        for operand in _NAMES.findall(rhs):
            if operand in users:
                users[operand].append(name)
        # ---- what the movement table needs of this line
        if m.group(1) and computation is not None:
            root_op[computation] = opcode
            if opcode == "custom-call":
                for target, role in _ASYNC_TARGETS.items():
                    if target in rhs:
                        async_root[computation] = role
        if opcode in ("convolution", "dot"):
            computes.add(computation)
        elif opcode == "while":
            body = re.search(r"body=%?([\w.\-]+)", rhs)
            trips = _TRIPS.search(rhs)
            if body is not None:
                loops[body.group(1)] = int(trips.group(1)) if trips \
                    else None
                parent[body.group(1)] = computation
        elif opcode in ("call", "conditional"):
            for one, many in _CONTROL.findall(rhs):
                for callee in _NAMES.findall(many) if many else (one,):
                    parent[callee] = computation
        row = _movement_row(opcode, rhs[:at.start()], rhs) \
            if opcode in _DATA_OPS else None
        if opcode in ("fusion", "async-start") and c is not None:
            callee = c.group(1)
            wrapped.add(callee)
            held = inside.get(callee)
            if held is not None:
                row = dict(held, role=async_root.get(callee) or (
                    "start" if opcode == "async-start" else
                    "carrier" if callee in computes else "fused"))
                if row["kind"] == "all-reduce" and row["role"] == "fused" \
                        and root_op.get(callee) == "dynamic-slice":
                    # the TPU's reduce-scatter: reduce all, keep a slice
                    row["kind"] = "reduce-scatter"
        if row is not None:
            row["computation"] = computation
            row["operands"] = _NAMES.findall(
                rhs[at.end():].split("), ", 1)[0])
            rows[name] = row
            if row["kind"] in COLLECTIVES and computation not in inside:
                inside[computation] = {k: row[k] for k in
                                       ("kind", "bytes", "group")}
    _resolve(scope, {k: v for k, v in calls.items() if scope[k] is None},
             roots, {k: v for k, v in forwards.items() if scope[k] is None},
             {k: v for k, v in users.items() if scope[k] is None})
    _resolve(passes, calls, roots, forwards, users)
    return Tables(scope, kernels,
                  _finish_movement(rows, wrapped, forwards, loops, parent,
                                   scope, passes), passes)


def parse_scopes(text: str) -> Tuple[Dict[str, Optional[str]],
                                     Dict[str, str]]:
    """``({instruction: scopes or None}, {custom-call instruction: kernel
    name})`` of a compiled program's text: :func:`parse`'s first two."""
    return parse(text)[:2]


def _modules_of(compiled):
    """What a scope table is parsed from: the executable's optimized
    HLO modules (host objects; rendering them to text waits for the
    first reader), or its text where it cannot hand its modules out."""
    try:
        return compiled.runtime_executable().hlo_modules()
    except Exception:  # noqa: BLE001 — e.g. compiled for a described device
        return compiled.as_text()


def _harvest(name: str, records: List["ExecutableRecord"]) -> None:
    """Finalizer of a :class:`WatchedFunction`: take its executables'
    modules just before they go (``engine.destroy()`` /
    ``server.close()`` come before a trace is read). Taking them costs
    tenths of a second for a large program, so it is done here, at
    teardown, and never while a program is being set up."""
    t0 = time.perf_counter()
    kept = []
    for rec in records:
        try:
            kept.append(_modules_of(rec.compiled))
        except Exception:  # noqa: BLE001 — a table is best-effort
            pass
    with _phase_lock:
        _phases.setdefault(name, _new_phase_row())["text_s"] += \
            time.perf_counter() - t0
    with _tables_lock:
        # bounded: the newest executables of a name (its prompt buckets
        # and retraces), however many servers a process builds and drops
        _texts[name] = (_texts.get(name, []) + kept)[-KEEP_EXECUTABLES:]
        _tables.pop(name, None)


def _text_of(source) -> str:
    return source if isinstance(source, str) else \
        "\n\n".join(m.to_string() for m in source)


def executable_tables(rec: "ExecutableRecord") -> Tables:
    """The tables of ONE executable of a watched function, parsed when
    first asked for and kept on its record."""
    if rec.tables is None:
        rec.tables = parse(_text_of(_modules_of(rec.compiled)))
    return rec.tables


def _tables_for(name: str) -> Tables:
    live = [rec for w in all_watched() if w.name == name
            for rec in w._records]
    with _tables_lock:
        kept = list(_texts.get(name, ()))
        got = _tables.get(name)
        if got is not None and got[0] == (len(kept), len(live)):
            return got[1]
    merged = Tables({}, {}, {}, {})
    for one in [parse(_text_of(source)) for source in kept] + \
            [executable_tables(rec) for rec in live]:
        # executables of one program (prompt buckets) may number their
        # instructions alike: a name that means two scopes (passes,
        # movement rows) means none
        for into, table in ((merged.scopes, one.scopes),
                            (merged.passes, one.passes),
                            (merged.movement, one.movement)):
            for k, v in table.items():
                into[k] = v if into.get(k, v) == v else None
        merged.kernels.update(one.kernels)
    with _tables_lock:
        _tables[name] = ((len(kept), len(live)), merged)
    return merged


def scope_table(name: str) -> Dict[str, Optional[str]]:
    """For the watched program ``name`` (every executable compiled under
    it in this process): compiled instruction name -> the known scopes on
    its path, ``"fwd_bwd/mlp"`` (innermost last), or None where it has
    none. Parsed on demand from the live executables' modules and from
    those harvested when a watched function went; survives the
    executables."""
    return _tables_for(name).scopes


def kernel_table(name: str) -> Dict[str, str]:
    """Custom-call (Pallas) instruction name -> kernel name, for the
    watched program ``name``."""
    return _tables_for(name).kernels


def movement_table(name: str) -> Dict[str, Optional[dict]]:
    """Instruction name -> its movement row (:func:`parse`: ``kind``,
    ``role``, ``bytes``, ``wire_bytes``, ``group``, ``calls``,
    ``per_iteration``, ``pair``, ``pass``, ``scopes``) for every
    instruction of the watched program ``name`` that moves data between
    the host's memory and the device's or between chips; None for a name
    that means two different rows in two executables. The same parse, the
    same laziness and the same survival as :func:`scope_table`."""
    return _tables_for(name).movement


def pass_table(name: str) -> Dict[str, Optional[str]]:
    """Instruction name -> ``fwd`` / ``recompute`` / ``bwd`` /
    ``optimizer`` / None, for every instruction of the watched program
    ``name``: from its path (:func:`pass_of`), or ``recompute`` for a
    clone XLA's rematerialisation made to save memory (named
    ``<instruction>.remat<n>``; its path is still the forward's)."""
    return _tables_for(name).passes


def movement_per_step(table, detail: bool = False) -> Dict:
    """What one execution moves, by kind: ``{kind: {"bytes": wire bytes,
    "calls": transfers}}`` (``detail``: by ``(kind, pass, innermost
    scope)``), of a watched program by name (every executable compiled
    under it) or of one movement table
    (``executable_tables(rec).movement``: one executable's). Each pair
    and each synchronous instruction is counted once (a compute fusion
    that carries a collective is its pair's, not a transfer of its own);
    rows in a loop of unknown trip count for one iteration."""
    if isinstance(table, str):
        table = movement_table(table)
    out: Dict = {}
    for row in table.values():
        if row is None or row["role"] in ("done", "carrier"):
            continue
        key = row["kind"] if not detail else (
            row["kind"], row["pass"],
            (row["scopes"] or "").rsplit("/", 1)[-1] or None)
        moved = out.setdefault(key, {"bytes": 0.0, "calls": 0})
        moved["bytes"] += row["wire_bytes"]
        moved["calls"] += row["calls"]
    return out


def watched_programs() -> List[str]:
    """Names a table can be made for: live or harvested."""
    with _tables_lock:
        kept = set(_texts)
    return sorted(kept | {w.name for w in all_watched() if w._records})


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
    tables: Any = None                 # executable_tables(), once asked


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
        install_phase_listeners()
        self._fun = fun
        self.name = name
        self._jit = jax.jit(_named(fun, name), **jit_kwargs)
        self._registry = registry
        self._ring = ring
        self._static_names = tuple(jit_kwargs.get("static_argnames") or ())
        self._static_nums = tuple(jit_kwargs.get("static_argnums") or ())
        self._execs: Dict[Tuple, ExecutableRecord] = {}
        self._records: List[ExecutableRecord] = []   # creation order
        self._last: Optional[ExecutableRecord] = None
        self.last_index: Optional[int] = None    # executable last CALLED
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
        # the scope tables outlive the executables (see _harvest)
        weakref.finalize(self, _harvest, name, self._records).atexit = False

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
        index = len(self._records)
        ann = annotation("compile:" + self.name, index=index)
        hits0 = _hits["seen"]
        t0 = time.perf_counter()   # a compile error raises from here
        traced = self._jit.trace(*args, **kwargs)
        t1 = time.perf_counter()
        lowered = traced.lower()
        t2 = time.perf_counter()
        compiled = lowered.compile()
        t3 = time.perf_counter()
        dt = t3 - t0
        cache_hit = _hits["seen"] > hits0
        log = get_span_log()
        sid = log.next_id()
        for phase, a, b in (("trace", t0, t1), ("lower", t1, t2),
                            ("cache_read" if cache_hit
                             else "backend_compile", t2, t3)):
            log.record("compile:" + phase, a, b, parent=sid, key=index)
        log.record("compile:" + self.name, t0, t3, key=index, span_id=sid,
                   attrs={"program": self.name, "index": index,
                          "cache_hit": cache_hit})
        if ann is not None:
            ann.__exit__(None, None, None)
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
        self.last_index = rec.index
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
        return "compile report: no watched functions\n" + phase_report()
    total_execs = sum(len(w._records) for w in watched)
    total_re = sum(len(w.retraces) for w in watched)
    total_s = sum(r.compile_seconds for w in watched for r in w._records)
    lines = [f"compile report: {len(watched)} function(s), "
             f"{total_execs} executable(s), {total_re} retrace(s), "
             f"{total_s:.2f} s total compile time"]
    lines += [w.report() for w in watched]
    return "\n".join(lines + [phase_report()])


def phase_report(top: int = 12) -> str:
    """Where compile time went, by the function name jax reported
    (watched or not): tracing, lowering, backend compiles and reads of
    the persistent cache, slowest first."""
    rows = sorted(phase_totals().items(),
                  key=lambda kv: -(kv[1]["trace_s"] + kv[1]["lower_s"]
                                   + kv[1]["compile_s"]
                                   + kv[1]["cache_read_s"]))
    lines = ["compile phases (s): function trace(own) lower compile "
             "cache_read hits misses"]
    for name, r in rows[:top]:
        lines.append(
            f"  {name}: {r['trace_s']:.3f} {r['lower_s']:.3f} "
            f"{r['compile_s']:.3f} {r['cache_read_s']:.3f} "
            f"{r['cache_hits']} {r['cache_misses']}")
    if len(rows) > top:
        lines.append(f"  … and {len(rows) - top} more")
    return "\n".join(lines)
