"""What a train step moves and what it waits for: the program's movement
table (``telemetry/compile_watch.py`` ``movement_table``: one row per
instruction that moves data between the host's memory and the device's
or between chips, with its kind, bytes, pair, pass and scopes) joined
with a device trace BY INSTRUCTION NAME. Seven metrics read it:

  offload cell   ``offload_fetch_wait_ms``, ``offload_store_wait_ms``,
                 ``offload_duplex_pct``, ``offload_link_gb_per_s``
  x4 cell        ``gather_exposed_ms``, ``reduce_exposed_ms``,
                 ``exchange_other_exposed_ms``

and the first one read in a run logs, on an earlier line
(``movement``), the whole table by kind x pass x innermost scope:
calls, bytes, core ms and in-flight ms a step.

Two times of a transfer. EXPOSED: the seconds the core sat IN the
instruction (own time: ``program_spans.innermost_seconds``), so nothing
else ran on that chip. IN FLIGHT: from where its ``-start`` begins to
where its ``-done`` ends; the k-th ``-start`` of a name pairs with the
k-th ``-done`` of its ``pair`` inside one execution of the program (an
instruction in a loop body, or of a step that ran several times, comes
back under one name). A synchronous instruction is in flight while it
runs.

The metrics keep two identities with the accepted readers, because they
read the same instructions as those do (``trace_reduce.COPY_OPS`` /
``COLLECTIVES`` by opcode or name) and only split them by the table:

  fetch + store + same_memory  = copy_wait_pct x window / steps
  gather + reduce + other      = collective_exposed_pct x window / steps

(``same_memory``: copies inside the device; on the logged line, no
metric). What the table knows and those readers' names do not is logged
beside them and counted in no metric: ``fused`` rows (a fusion that is
nothing but a collective, ``fusion.N`` in the trace) and ``carrier``
rows (a matmul that carries a collective's steps along).

A program without a movement table (an older checkout) makes every
reader return None; nothing raises.
"""
from __future__ import annotations

import collections
from typing import Dict, List, Optional, Tuple

from benchmark.lib import harness, program_spans as ps
from benchmark.lib.trace_reduce import (COLLECTIVES, COPY_OPS, merge, op_kind,
                                        op_name, total)

PROGRAM = "train_step"
FETCH, STORE = "host_to_device", "device_to_host"
REDUCES = ("reduce-scatter", "all-reduce")
Interval = Tuple[float, float]


def movement_table(program: str = PROGRAM) -> dict:
    """The program's movement table, or nothing where it has none. The
    seconds go under ``tail_seconds.movement_table`` (the parse itself is
    shared with the scope table and paid where that is first read:
    ``tables_parsed``)."""
    with harness.TAIL.timed("movement_table", program):
        try:
            from deepspeed_tpu.telemetry.compile_watch import movement_table
            return {k: v for k, v in movement_table(program).items() if v}
        except Exception:  # noqa: BLE001 — an older program
            return {}


def pass_table(program: str = PROGRAM) -> dict:
    try:
        from deepspeed_tpu.telemetry.compile_watch import pass_table
        return pass_table(program)
    except Exception:  # noqa: BLE001
        return {}


def _is(op, kinds) -> bool:
    """As ``trace_reduce.Device.kind_seconds`` tells an instruction's
    kind: by opcode, or by name where XLA wraps it."""
    return op_kind(op.text).startswith(kinds) or \
        op_name(op.text).startswith(kinds)


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The parts two merged interval lists share."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def in_flight(ops, table: dict) -> Dict[str, List[Interval]]:
    """For ONE execution's instructions: row name (a ``-start``, or a
    synchronous instruction) -> the intervals it was in flight."""
    seen: Dict[str, list] = collections.defaultdict(list)
    for op in ops:
        if op.name in table:
            seen[op.name].append(op)
    out: Dict[str, List[Interval]] = {}
    for name, events in seen.items():
        row = table[name]
        if row["role"] == "start" and row["pair"] in seen:
            out[name] = [(s.start, d.end)
                         for s, d in zip(events, seen[row["pair"]])]
        elif row["role"] in ("sync", "fused") or (
                row["role"] == "start" and not row["pair"]):
            out[name] = [(e.start, e.end) for e in events]
    return out


def _group(row: dict) -> Tuple[str, Optional[str], Optional[str], str]:
    """kind x pass x innermost scope (x how the table holds it: the
    halves of a pair are one ``async``)."""
    role = "async" if row["role"] in ("start", "done") else row["role"]
    return (row["kind"], row["pass"],
            (row["scopes"] or "").rsplit("/", 1)[-1] or None, role)


def chip(trace, table: dict, k: int) -> Optional[dict]:
    """One chip's executions of the program against the table."""
    runs = ps.ops_by_execution(trace, PROGRAM, k)
    if not runs:
        return None
    exposed: Dict[str, float] = collections.defaultdict(float)
    groups: Dict[tuple, dict] = collections.defaultdict(
        lambda: {"calls": 0, "bytes": 0.0, "exposed_s": 0.0,
                 "in_flight": []})
    flight: Dict[str, List[Interval]] = collections.defaultdict(list)
    for ops in runs:
        for op in ops:
            row = table.get(op.name)
            copy, coll = _is(op, COPY_OPS), _is(op, COLLECTIVES)
            if copy:
                exposed[row["kind"] if row and row["kind"] in (FETCH, STORE)
                        else "same_memory"] += op.own
            elif coll:
                kind = row["kind"] if row else None
                exposed["gather" if kind == "all-gather" else
                        "reduce" if kind in REDUCES else "other"] += op.own
                if row is None:
                    exposed["unnamed"] += op.own
            if row is None:
                if coll:
                    groups[(op_name(op.text), None, None, "unnamed")][
                        "exposed_s"] += op.own
                continue
            g = groups[_group(row)]
            g["exposed_s"] += op.own
            if row["role"] != "done":
                g["calls"] += 1
                if row["role"] != "carrier":
                    g["bytes"] += row["wire_bytes"] / max(row["calls"], 1)
        for name, spans in in_flight(ops, table).items():
            groups[_group(table[name])]["in_flight"] += spans
            if table[name]["kind"] in (FETCH, STORE):
                flight[table[name]["kind"]] += spans
    link = {d: merge(flight[d]) for d in (FETCH, STORE)}
    return {"device": k, "executions": len(runs), "exposed": dict(exposed),
            "waited_s": sum(v for key, v in exposed.items()
                            if key != "unnamed"),
            "groups": groups, "link": link,
            "any_s": total(merge(link[FETCH] + link[STORE])),
            "both_s": total(intersect(link[FETCH], link[STORE]))}


def analyse(run: dict, trace) -> Optional[dict]:
    """Everything the seven metrics and the logged line need, once per
    trace (kept on it); None where there is nothing to read."""
    if trace is None or run.get("kind") != "train" \
            or not run.get("trace_steps"):
        return None
    if "_movement" in trace.__dict__:
        return trace.__dict__["_movement"]
    table = movement_table()
    chips = [c for c in (chip(trace, table, k)
                         for k in range(len(trace.devices))) if c] \
        if table else []
    out = None
    if chips:
        steps = run["trace_steps"]
        # ONE chip, the one whose core waited longest, for every number:
        # the parts then add up to that chip's whole
        c = max(chips, key=lambda c: c["waited_s"])
        worst = {key: 1e3 * c["exposed"].get(key, 0.0) / steps
                 for key in (FETCH, STORE, "same_memory", "gather", "reduce",
                             "other", "unnamed")}
        moved = {d: sum(r["bytes"] for r in table.values()
                        if r["kind"] == d and r["role"] == "start")
                 for d in (FETCH, STORE)}
        out = {"worst_ms": worst, "moved_bytes": moved, "link": None,
               "host_rows": any(r["kind"] in (FETCH, STORE)
                                for r in table.values()),
               "collective_rows": any(r["kind"] not in (FETCH, STORE)
                                      for r in table.values())}
        if c["any_s"] > 0:
            n = c["executions"]
            out["link"] = {
                "duplex_pct": 100.0 * c["both_s"] / c["any_s"],
                "gb_per_s": sum(moved.values()) * n / c["any_s"] / 1e9,
                "in_flight_ms_per_step": 1e3 * c["any_s"] / n,
                "by_direction": {
                    d: {"in_flight_ms_per_step":
                        1e3 * total(c["link"][d]) / n,
                        "gb_per_s_in_flight":
                            moved[d] * n / total(c["link"][d]) / 1e9
                            if c["link"][d] else None}
                    for d in (FETCH, STORE)}}
        harness.log({"movement": _line(run, trace, table, c, out)})
    trace.__dict__["_movement"] = out
    return out


def _line(run: dict, trace, table: dict, c: dict, out: dict) -> dict:
    """The logged line: the table by kind x pass x scope on the chip
    where the core waited longest, the two identities, the link, and the
    step's core time by pass."""
    steps = run["trace_steps"]
    rows = [{"kind": k[0], "pass": k[1], "scope": k[2], "as": k[3],
             "calls_per_step": g["calls"] / c["executions"],
             "bytes_per_step": g["bytes"] / c["executions"],
             "exposed_ms_per_step": 1e3 * g["exposed_s"] / steps,
             "in_flight_ms_per_step":
                 1e3 * total(merge(g["in_flight"])) / steps}
            for k, g in c["groups"].items()]
    rows.sort(key=lambda r: -r["exposed_ms_per_step"])
    w = out["worst_ms"]
    per_step = trace.window_s * 10.0 / steps     # pct x window -> ms a step
    line = {
        "program": PROGRAM, "table_rows": len(table), "chip": c["device"],
        "executions": c["executions"], "trace_steps": steps,
        "by_kind_pass_scope": rows, "link": out["link"],
        "moved_bytes_per_step": out["moved_bytes"],
        "same_memory_ms": w["same_memory"], "unnamed_ms": w["unnamed"],
        "identity": {
            "copies_ms": w[FETCH] + w[STORE] + w["same_memory"],
            "copy_wait_ms": trace.exposed_pct(COPY_OPS) * per_step,
            "collectives_ms": w["gather"] + w["reduce"] + w["other"],
            "collective_exposed_ms":
                trace.exposed_pct(COLLECTIVES) * per_step}}
    passes = pass_table()
    if passes:
        acc: Dict[str, float] = collections.defaultdict(float)
        for ops in ps.ops_by_execution(trace, PROGRAM, c["device"]):
            for op in ops:
                acc[passes.get(op.name) or "none"] += op.own
        line["core_ms_by_pass"] = {k: 1e3 * v / steps
                                   for k, v in sorted(acc.items())}
    return line


def metric(run: dict, trace, name: str) -> Optional[float]:
    """One of the seven, by name; None where the program, the table or
    the trace holds nothing for it."""
    got = analyse(run, trace)
    if got is None:
        return None
    if name in ("offload_fetch_wait_ms", "offload_store_wait_ms"):
        return got["worst_ms"][FETCH if "fetch" in name else STORE] \
            if got["host_rows"] else None
    if name in ("offload_duplex_pct", "offload_link_gb_per_s"):
        return got["link"][{"offload_duplex_pct": "duplex_pct",
                            "offload_link_gb_per_s": "gb_per_s"}[name]] \
            if got["link"] else None
    if run.get("chips", 1) < 2 or not got["collective_rows"]:
        return None
    return got["worst_ms"][{"gather_exposed_ms": "gather",
                            "reduce_exposed_ms": "reduce",
                            "exchange_other_exposed_ms": "other"}[name]]
