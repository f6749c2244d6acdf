"""Record the test data of ``benchmark/tests/test_program_span_readers.py``
on a chip: a short device trace of a SMALL server built from this checkout
(named programs, named kernels, the program's own spans), with what the
readers join it to, since none of that is in the trace file itself:

    chiprun -- python3 benchmark/tools/record_named_trace.py

writes under ``chiprun_out/named_trace/``

  ``tiny_named_trace.xplane.pb``   the profiler's trace
  ``tiny_named_trace.json``        ``run`` (the clocks' anchor, the window),
                                   the span log's records of the window,
                                   the scope and kernel tables of the
                                   programs that ran, and what the readers
                                   read from them here (for the record)

and, beside them, ``capture/``: a capture taken through
``server.capture_decode_steps`` inside a caller's span, with
``tools/dump_trace.py``'s view of its ``/host:CPU`` plane (the program's
``serve:`` annotations under the caller's).

The model is two layers of GPT-2 at d_head 128 (the kernels' native
head size), 4 slots x 256 positions, bf16: seconds to compile, and the
same programs and kernels as the real cells.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark.lib import harness, program_spans as ps  # noqa: E402

CONFIG = {
    "kind": "serve",
    "model": {"family": "gpt2", "n_embd": 256, "n_layer": 2, "n_head": 2,
              "vocab_size": 512, "n_positions": 512, "dtype": "bfloat16"},
    "engine": {"dtype": "bfloat16", "max_out_tokens": 256,
               "block_size": 128, "num_slots": 4,
               "max_queued_requests": 64},
}
STEPS = 12          # decode steps under the profiler


def host_nesting(path: str) -> dict:
    """How many of the capture's ``serve:step`` events lie inside a
    ``caller:step`` event of the same thread, and ``serve:phase`` events
    inside a ``serve:step``."""
    from jax.profiler import ProfileData

    from benchmark.tools.dump_trace import newest_xplane
    data = ProfileData.from_file(newest_xplane(path))
    out = {}
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                  for e in line.events
                  if e.name.startswith(("serve:", "caller:"))]
            if not ev:
                continue

            def inside(child, parent):
                ps_ = [(s, e) for n, s, e in ev if n == parent]
                cs = [(s, e) for n, s, e in ev if n == child]
                return [sum(1 for s, e in cs
                            if any(a <= s and e <= b for a, b in ps_)),
                        len(cs)]
            out[line.name] = {
                "serve:step inside caller:step": inside("serve:step",
                                                        "caller:step"),
                "serve:phase inside serve:step": inside("serve:phase",
                                                        "serve:step")}
    return out


def main(out: str) -> int:
    import numpy as np

    from benchmark.lib import serve_cell
    from benchmark.lib.serve_cell import Tracked
    harness.require_tpu(1)
    os.makedirs(out, exist_ok=True)
    family = harness.load_family("gpt2")
    cfg, engine, server = serve_cell.build(CONFIG, 5, family)
    sess = serve_cell.Session(server)
    rng = np.random.default_rng(5)

    def request(rid, n_prompt, n_out):
        return Tracked(rid, [int(t) for t in rng.integers(
            1, cfg.vocab_size, n_prompt)], n_out, 0.0, True)

    # warm every program the window uses: one prefill bucket, the decode
    # program through its pipelined path
    for r in [request(900 + i, 40 + 7 * i, 6) for i in range(4)]:
        sess.submit(r)
    sess.drain()
    # as many requests as slots: with nothing queued the steps of the
    # window take the pipelined path, as a steady server's do
    reqs = [request(i, 30 + 11 * i, 60 + 5 * i)
            for i in range(server.num_slots)]
    for r in reqs:
        sess.submit(r)
    while len(server.scheduler.slots) < server.num_slots:
        sess.step()
    for _ in range(3):
        sess.step()
    tracer = harness.Tracer(True, "named_trace")
    tracer.start()
    t0 = sess.clock()
    for _ in range(STEPS):
        sess.step()
    t1 = sess.clock()
    tracer.stop()
    sess.drain()
    found = sorted(glob.glob(os.path.join(tracer.dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    shutil.copy(found[-1], os.path.join(out, "tiny_named_trace.xplane.pb"))
    trace = tracer.reduced()
    run = {"kind": "serve", "t0": t0, "t1": t1, "trace_t0": tracer.t0,
           "trace_t1": tracer.t1, "rids": [r.rid for r in reqs]}
    lo = t0 - 0.05
    records = [list(r) for r in ps.span_records()
               if r[ps.END] >= lo and r[ps.START] <= t1 + 0.05
               or r[ps.NAME] in ("serve:request", "serve:queue_wait",
                                 "serve:prefill", "serve:decode")
               and r[ps.KEY] in run["rids"]]
    programs = sorted({ps.program_of(n) for n, _, _ in
                       trace.devices[0].modules} - {None})
    ran = {ps.instruction(t) for d in trace.devices for t, _, _, _ in d.ops}
    tables = {p: {"scopes": {k: v for k, v in ps.tables(p)[0].items()
                             if k in ran},
                  "kernels": {k: v for k, v in ps.tables(p)[1].items()
                              if k in ran}}
              for p in programs}

    class Counted:        # what the request readers need of a Tracked
        def __init__(self, rid):
            self.rid = rid
    run_for_readers = dict(run, counted=[Counted(r) for r in run["rids"]])
    read = {}
    for name in ("decode_kv_read_ms", "decode_kernel_ms",
                 "decode_dispatch_gap_ms", "serve_goodput_pct",
                 "admission_phase_p90_ms", "server_queue_wait_p90_ms",
                 "request_prefill_p90_ms", "trace_lower_s",
                 "compile_cache_misses"):
        read[name] = harness.load_reader(name)(run_for_readers, trace)
    with open(os.path.join(out, "tiny_named_trace.json"), "w") as fh:
        json.dump({"run": run, "spans": records, "tables": tables,
                   "read_on_the_chip": read,
                   "summary": trace.summary(),
                   "phase_totals": ps.phase_totals()}, fh, default=float)
    harness.log({"read_on_the_chip": read, "programs": programs,
                 "summary": trace.summary()})

    # ---- the operator's capture, under a caller's span
    import jax
    cap = os.path.join(out, "capture")
    shutil.rmtree(cap, ignore_errors=True)
    for r in [request(100 + i, 25 + 9 * i, 30) for i in range(4)]:
        sess.submit(r)
    while len(server.scheduler.slots) < server.num_slots:
        sess.step()
    server.capture_decode_steps(4, cap)
    # the capture starts inside the first of these steps and stops
    # inside the last: the steps between are whole
    while server.profiler_capture.active:
        with jax.profiler.TraceAnnotation("caller:step"):
            sess.step()
    sess.drain()
    sess.close()
    from benchmark.tools.dump_trace import structure
    host = structure(cap, top=400).get("/host:CPU", {})
    keep = {}
    for line, body in host.items():
        rows = [r for r in body["top"]
                if r["name"].startswith(("serve:", "caller:"))]
        if rows:
            keep[line] = rows
    nesting = host_nesting(cap)
    with open(os.path.join(out, "capture_host_plane.json"), "w") as fh:
        json.dump({"lines": keep, "nesting": nesting}, fh, indent=1)
    harness.log({"capture_host_plane": keep, "nesting": nesting})
    for f in glob.glob(os.path.join(cap, "**", "*.xplane.pb"),
                       recursive=True):
        os.remove(f)                  # the dump is what is kept
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1
                  else os.path.join(REPO, "chiprun_out", "named_trace")))
