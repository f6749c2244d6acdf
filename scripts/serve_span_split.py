#!/usr/bin/env python3
"""One untraced run of a serving cell through ``benchmark/run.py``'s own
``main``, then the measured window's ``serve:step`` spans split by kind
of step: a step that carried a rider, one that admitted by a program of
its own, a pipelined step, any other lag-0 step. For each kind the
steps, the requests they admitted, the mean wall, its share of the
stepping time, and the mean of each phase span and of the flush.

    chiprun -- python3 scripts/serve_span_split.py <checkout> <workload> <seed> [<steps.jsonl>]

``<checkout>`` is the tree to run (``.`` or a ``git archive`` of another
commit unpacked under the repository, which is how two trees are split
in one chip call). The run's own lines are printed as always; the split
is the line that starts ``SPAN_SPLIT``; with a fourth argument every
step of the window is also written there, a JSON line each (kind, start
and wall in ms from the window's opening, attributes, phases): where in
a window a slow stretch lies. The exit code is the run's."""
import collections
import importlib
import json
import os
import sys


def main(argv):
    root, workload, seed = os.path.abspath(argv[0]), argv[1], argv[2]
    os.chdir(root)
    sys.path.insert(0, root)
    run_mod = importlib.import_module("benchmark.run")
    from benchmark.lib import harness, program_spans as ps
    window = {}
    run_cell = harness.run_cell

    def keep_window(*a, **k):
        run, tracer = run_cell(*a, **k)
        window.update(t0=run["t0"], t1=run["t1"])
        return run, tracer
    harness.run_cell = keep_window
    rc = run_mod.main(["--workload", workload, "--seed", seed,
                       "--trace", "0"])
    records = ps.span_records("serve:")
    steps = ps.window_steps(records, window["t0"], window["t1"])
    phases = ps.by_parent(r for r in records
                          if r[ps.NAME] not in ps.OVERLAYS)
    flushes = ps.by_parent(r for r in records
                           if r[ps.NAME] == "serve:flush")
    kinds = collections.defaultdict(lambda: {
        "n": 0, "wall": 0.0, "admitted": 0, "flush": 0.0,
        "phases": collections.Counter()})
    each = open(argv[3], "w") if len(argv) > 3 else None
    for s in steps:
        a = s[ps.ATTRS] or {}
        kind = ("rider" if a.get("rider") else
                "admit" if a.get("admitted") else
                "pipelined" if a.get("pipelined") else "lag0")
        if each is not None:
            each.write(json.dumps({
                "kind": kind, "at_ms": 1e3 * (s[ps.START] - window["t0"]),
                "ms": 1e3 * (s[ps.END] - s[ps.START]), "attrs": a,
                "phases_ms": {c[ps.NAME]: 1e3 * (c[ps.END] - c[ps.START])
                              for c in phases.get(s[ps.ID], ())}}) + "\n")
        k = kinds[kind]
        k["n"] += 1
        k["wall"] += s[ps.END] - s[ps.START]
        k["admitted"] += a.get("admitted") or 0
        for c in phases.get(s[ps.ID], ()):
            k["phases"][c[ps.NAME]] += c[ps.END] - c[ps.START]
        for c in flushes.get(s[ps.ID], ()):
            k["flush"] += c[ps.END] - c[ps.START]
    if each is not None:
        each.close()
    stepping = sum(k["wall"] for k in kinds.values())
    print("SPAN_SPLIT " + json.dumps({
        "checkout": argv[0], "workload": workload, "seed": seed,
        "window_s": window["t1"] - window["t0"],
        "kinds": {kind: {
            "steps": k["n"], "admitted": k["admitted"],
            "mean_ms": 1e3 * k["wall"] / k["n"],
            "share_pct": 100 * k["wall"] / stepping,
            "flush_ms": 1e3 * k["flush"] / k["n"],
            "phases_ms": {p: 1e3 * v / k["n"]
                          for p, v in k["phases"].most_common()}}
            for kind, k in kinds.items()}}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
