"""The program's own spans (ISSUE 24): one bounded span log inside the
process, fed by the serving step profiler (``serve:step`` and its
phases, ``serve:flush``), the server's request stamps (``serve:request``
and its lifecycle), the engine (``train:step``) and the compile watch
(``compile:<program>``), plus the names the device trace shows: watched
programs, Pallas kernels, ``jax.named_scope`` layer boundaries and the
compile watch's scope table over them.

Fake or ticking clocks throughout: counts and identities, never a time.
That the programs compiled for the chip carry these names is checked in
``tests/test_tpu_aot_compile.py`` (the one file that loads the TPU's
compiler).
"""
import functools
import gc

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.inference import (ContinuousBatchingServer,
                                     DeepSpeedInferenceConfig,
                                     InferenceEngine)
from deepspeed_tpu.model_implementations.transformer import (
    InferenceTransformerConfig, init_params)
from deepspeed_tpu.telemetry import (EventRing, MetricRegistry, SpanLog,
                                     StepProfiler, compile_watch,
                                     get_event_ring, get_registry,
                                     get_span_log, set_event_ring,
                                     set_registry, set_span_log, watched_jit)
from deepspeed_tpu.telemetry import spans as sp_mod
from deepspeed_tpu.telemetry.spans import (END, ID, KEY, NAME, PARENT, START,
                                           ATTRS)
from deepspeed_tpu.telemetry.step_profile import PHASES

PHASE_SPANS = {"serve:" + p for p in PHASES}


@pytest.fixture()
def fresh():
    """Private registry, event ring and span log for one test."""
    prev = (set_registry(MetricRegistry()), set_event_ring(EventRing(256)),
            set_span_log(SpanLog()))
    try:
        yield get_span_log()
    finally:
        set_registry(prev[0])
        set_event_ring(prev[1])
        set_span_log(prev[2])


class TickClock:
    """Strictly increasing: every read is one tick later, so every
    interval between two reads has a length and none repeats."""

    def __init__(self, tick=0.001):
        self.t = 0.0
        self.tick = tick

    def __call__(self):
        self.t += self.tick
        return self.t


def make_engine(num_slots=2, **knobs):
    cfg = InferenceTransformerConfig(
        vocab_size=128, n_positions=256, n_embd=32, n_layer=2, n_head=4,
        dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return InferenceEngine((cfg, params), DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=256, block_size=32,
        num_slots=num_slots, **knobs))


# ================================================================ the log

def test_log_is_bounded_and_counts_drops():
    prev = set_event_ring(EventRing(64))
    try:
        log = SpanLog(capacity=4)
        for i in range(4):
            log.record("x", float(i), i + 0.5, key=i)
        assert not get_event_ring().snapshot()      # full, nothing lost yet
        for i in range(4, 6):
            log.record("x", float(i), i + 0.5, key=i)
        assert len(log) == 4 and log.dropped == 2
        assert [r[KEY] for r in log.snapshot()] == [2, 3, 4, 5]
        log.extend([("y", 0.0, 1.0, 0, log.next_id(), 9, None)] * 3)
        assert len(log) == 4 and log.dropped == 5
        assert log.stats() == {"capacity": 4, "records": 4, "dropped": 5}
        # the loss is said where it happens, once: at the first drop
        events = [e for e in get_event_ring().snapshot()
                  if e["kind"] == "span_log_overflow"]
        assert len(events) == 1 and events[0]["data"] == {"capacity": 4}
    finally:
        set_event_ring(prev)
    # ISSUE 40's floor; a traced chat run leaves 101,296 records
    assert get_span_log().capacity >= max(327680, 4 * 101296)
    log.clear()
    assert len(log) == 0 and log.dropped == 0


def test_span_links_parent_and_key(fresh):
    with sp_mod.span("outer", registry=MetricRegistry(), key=7):
        with sp_mod.span("inner", registry=MetricRegistry(),
                         labels={"k": "v"}):
            pass
    inner, outer = fresh.snapshot()
    assert (inner[NAME], outer[NAME]) == ("inner", "outer")
    assert inner[PARENT] == outer[ID] and outer[PARENT] == 0
    assert outer[KEY] == 7 and inner[ATTRS] == {"k": "v"}
    assert outer[START] <= inner[START] <= inner[END] <= outer[END]
    assert fresh.snapshot(prefix="in") == [inner]
    assert fresh.snapshot(since=outer[END]) == [outer]


# ===================================================== the steps of a server

def _tiles(parent, kids):
    """``kids`` (any order) cover ``parent`` exactly, end to start."""
    kids = sorted(kids, key=lambda r: r[START])
    assert kids, parent
    assert kids[0][START] == parent[START]
    assert kids[-1][END] == parent[END]
    for a, b in zip(kids, kids[1:]):
        assert a[END] == b[START], (a, b)
    total = sum(k[END] - k[START] for k in kids)
    assert total == pytest.approx(parent[END] - parent[START], abs=1e-12)


def _steps_with_phases(log):
    recs = log.snapshot(prefix="serve:")
    steps = [r for r in recs if r[NAME] == "serve:step"]
    out = []
    for s in steps:
        kids = [r for r in recs if r[PARENT] == s[ID]
                and r[NAME] in PHASE_SPANS]
        assert all(k[KEY] == s[KEY] for k in kids)
        out.append((s, kids))
    return out


@pytest.mark.parametrize("shape", [
    "sync", "pipelined", "idle", "sync-verify", "pipelined-verify",
    "pipelined-lag2"])
def test_phase_spans_tile_the_step(fresh, shape):
    """The sum identity, on spans: at lag 0, at lag 1 and 2, with the
    decode and with the verify program, and for an idle poll, the phase
    spans of a step cover ``serve:step`` exactly, under a clock that
    ticks at every read. A step that ran at lag 0 is not ``pipelined``,
    whichever server ran it."""
    knobs = {"async_loop": not shape.startswith("sync")}
    if shape.endswith("verify"):
        knobs["speculation_tokens"] = 4
    if shape.endswith("lag2"):
        knobs["max_commit_lag"] = 2
    srv = ContinuousBatchingServer(make_engine(**knobs), clock=TickClock())
    if shape == "idle":
        srv.step()
    else:
        for i in range(3):      # three requests, two slots: a queue
            srv.submit([1 + i, 2, 3], max_new_tokens=6 + i)
        srv.drain()
    got = _steps_with_phases(fresh)
    assert got
    for step, kids in got:
        _tiles(step, kids)
    attrs = [s[ATTRS] for s, _ in got]
    assert all(a["profiler"] == srv._profiler.uid for a in attrs)
    if shape == "idle":
        assert [a.get("idle") for a in attrs] == [True]
    else:
        worked = [a for a in attrs if not a.get("idle")]
        assert any(a["pipelined"] for a in worked) \
            == shape.startswith("pipelined")
        # a step that admitted had a queue to serve: it ran at lag 0
        admitting = [a for a in worked if a["admitted"]]
        assert admitting and not any(a["pipelined"] for a in admitting)
        assert sum(a["admitted"] for a in worked) == 3
        # the profiler's own totals are the same identity
        snap = srv.stats["step_profile"]
        assert sum(snap["phases_s"].values()) == pytest.approx(
            snap["wall_s"])
        assert len(worked) == snap["steps"]
        assert sum(a["device_s"] for a in worked) == pytest.approx(
            snap["device_s"])
        assert snap["commit_lag"]["depth_max"] == (
            1 if shape.startswith("sync") or shape.endswith("verify")
            else 3 if shape.endswith("lag2") else 2)
        # dispatch and sync_wait name the watched program
        named = [k[ATTRS]["program"] for _, kids in got for k in kids
                 if k[NAME] in ("serve:dispatch", "serve:sync_wait")
                 and k[ATTRS]]
        assert named and set(named) == {
            "serve_spec_verify" if shape.endswith("verify")
            else "serve_decode"}
    srv.close()


def test_dispatch_span_carries_gap_and_depth(fresh):
    """A dispatch into a busy device is countable as such: ``gap_s`` 0.0
    with ``busy`` true and the chain depth; a dispatch after a drained
    device carries the gap the histogram observed."""
    srv = ContinuousBatchingServer(make_engine(), clock=TickClock())
    srv.submit([1, 2, 3], max_new_tokens=12)
    srv.drain()
    disp = [r[ATTRS] for r in fresh.snapshot() if r[NAME] == "serve:dispatch"]
    assert disp and all({"gap_s", "depth", "busy"} <= set(a) for a in disp)
    busy = [a for a in disp if a["busy"]]
    assert busy and all(a["gap_s"] == 0.0 and a["depth"] >= 2 for a in busy)
    assert len(busy) == srv.stats["step_profile"]["commit_lag"][
        "pipelined_dispatches"]
    real = [a["gap_s"] for a in disp if not a["busy"]
            and a["gap_s"] is not None]
    assert sum(real) == pytest.approx(
        srv.stats["step_profile"]["dispatch_gap"]["total_s"])
    srv.close()


def test_flush_span_names_its_reason(fresh):
    srv = ContinuousBatchingServer(make_engine(num_slots=1),
                                   clock=TickClock())
    srv.submit([1, 2, 3], max_new_tokens=8)
    for _ in range(4):
        srv.step()              # steady: the pipeline is in flight
    srv.submit([4, 5], max_new_tokens=2)    # a host action: flush
    srv.drain()
    flushes = [r for r in fresh.snapshot() if r[NAME] == "serve:flush"]
    reasons = {r[ATTRS]["reason"] for r in flushes}
    assert "host_action" in reasons
    assert all(r[ATTRS]["programs"] >= 1 for r in flushes)
    steps = {r[ID] for r in fresh.snapshot() if r[NAME] == "serve:step"}
    inside = [r for r in flushes if r[ATTRS]["reason"] == "host_action"]
    assert all(r[PARENT] in steps for r in inside)
    assert set(srv.stats["async_loop"]["flushes"]) >= reasons
    srv.close()


def test_request_lifecycle_tiles_the_request(fresh):
    """``queue_wait + prefill + decode`` cover ``serve:request`` exactly,
    a preempted and requeued request's two rounds included."""
    srv = ContinuousBatchingServer(
        make_engine(num_slots=1, async_loop=False), clock=TickClock())
    a = srv.submit([1, 2, 3], max_new_tokens=10, priority=0)
    for _ in range(4):
        srv.step()
    b = srv.submit([4, 5, 6], max_new_tokens=4, priority=5)
    out = srv.drain()
    assert srv.stats["preempted"] == 1
    recs = fresh.snapshot(prefix="serve:")
    reqs = {r[KEY]: r for r in recs if r[NAME] == "serve:request"}
    assert set(reqs) == {a, b}
    for rid, root in reqs.items():
        kids = [r for r in recs if r[PARENT] == root[ID]]
        assert all(k[KEY] == rid for k in kids)
        _tiles(root, kids)
    names = [k[NAME] for k in sorted(
        (r for r in recs if r[PARENT] == reqs[a][ID]),
        key=lambda r: r[START])]
    assert names == ["serve:queue_wait", "serve:prefill", "serve:decode",
                     "serve:queue_wait", "serve:prefill", "serve:decode"]
    assert reqs[a][ATTRS] == {
        "prompt_tokens": 3, "output_tokens": len(out[a]) - 3,
        "finish_reason": srv.finish_reason(a), "preemptions": 1}
    assert reqs[b][ATTRS]["preemptions"] == 0
    # a request cancelled while queued is queue_wait alone
    c = srv.submit([7, 8], max_new_tokens=3)
    srv.cancel(c)
    recs = fresh.snapshot(prefix="serve:")
    root = [r for r in recs if r[NAME] == "serve:request" and r[KEY] == c][0]
    assert root[ATTRS]["finish_reason"] == "cancelled"
    _tiles(root, [r for r in recs if r[PARENT] == root[ID]])
    srv.close()


def test_profiler_off_records_nothing(fresh):
    srv = ContinuousBatchingServer(
        make_engine(telemetry={"step_profile": False}), clock=TickClock())
    srv.submit([1, 2, 3], max_new_tokens=4)
    srv.drain()
    srv.close()
    assert fresh.snapshot(prefix="serve:") == []


def test_slow_step_leaves_one_ring_event(fresh):
    """A worked step slower than max(0.4 s, 8 x the running median)
    leaves one ``slow_step`` event with its phases, chain depth and the
    program it waited on; an ordinary step and a step with too little
    history leave none."""
    class Clock:
        t = 0.0

        def __call__(self):
            return self.t
    fc = Clock()
    prof = StepProfiler(registry=MetricRegistry(), clock=fc)

    def step(wait):
        sp = prof.begin()
        fc.t += 0.01
        sp.mark("propose", dispatch=True)
        fc.t += 0.01
        sp.mark("dispatch", program="serve_decode")
        fc.t += wait
        sp.mark("sync_wait", fetch=True, program="serve_decode")
        sp.finish()

    step(3.0)                       # slow, but there is no median yet
    for _ in range(8):
        step(0.03)
    step(0.2)                       # 4 x the median and under 0.4 s
    step(3.0)
    evs = [e["data"] for e in get_event_ring().snapshot()
           if e["kind"] == "slow_step"]
    assert len(evs) == 1 and prof.snapshot()["slow_steps"] == 1
    ev = evs[0]
    assert ev["wall"] == pytest.approx(3.02)
    assert ev["median_wall"] == pytest.approx(0.05)
    assert ev["program"] == "serve_decode" and ev["depth"] == 0
    assert [p[0] for p in ev["phases"]] == [
        "serve:propose", "serve:dispatch", "serve:sync_wait"]
    assert ev["phases"][2][1] == pytest.approx(3.0)


# ============================================================= the engine

def test_train_step_spans(fresh):
    import deepspeed_tpu
    import numpy as np
    params = {"blk0": {"w": jnp.full((16, 8), 0.1, jnp.float32)},
              "blk1": {"w": jnp.full((8, 4), 0.1, jnp.float32)}}

    def loss_fn(p, b, rng):
        y = jnp.tanh(b["x"] @ p["blk0"]["w"]) @ p["blk1"]["w"]
        return jnp.mean((y - b["y"]) ** 2)
    engine, _, _, _ = deepspeed_tpu.initialize(
        loss_fn=loss_fn, model_parameters=params,
        config={"train_micro_batch_size_per_gpu": 4,
                "optimizer": {"type": "sgd", "params": {"lr": 0.01}},
                "telemetry": {"goodput": True}})
    rng = np.random.default_rng(0)
    B = engine.train_batch_size
    for _ in range(2):
        engine.train_batch({
            "x": jnp.asarray(rng.normal(size=(B, 16)), jnp.float32),
            "y": jnp.zeros((B, 4), jnp.float32)})
    engine.destroy()
    recs = fresh.snapshot(prefix="train:")
    steps = [r for r in recs if r[NAME] == "train:step"]
    assert [s[KEY] for s in steps] == [1, 2]
    # which executable's movement-table rows each step ran
    assert [s[ATTRS] for s in steps] == [
        {"program": "train_step", "executable": 0}] * 2
    for s in steps:
        kids = sorted((r for r in recs if r[PARENT] == s[ID]),
                      key=lambda r: r[START])
        # the meter's sync is on: dispatch, then the wait it pays for
        assert [k[NAME] for k in kids] == ["train:dispatch", "train:wait"]
        assert s[START] <= kids[0][START] and kids[-1][END] <= s[END]
        assert kids[0][END] == kids[1][START]
    # compiled once, under the watch's name, with both train scopes
    assert "train_step" in compile_watch.watched_programs()
    scopes = {v for v in compile_watch.scope_table("train_step").values()
              if v}
    assert any(v.split("/")[0] == "fwd_bwd" for v in scopes)
    assert any(v.split("/")[0] == "optimizer" for v in scopes)



def test_zero3_step_says_what_it_moves(fresh, monkeypatch):
    """A ZeRO-3 step on four devices: every exchange in it was written
    by the partitioner, none by the program. The movement table reads
    them off the compiled text: parameters gathered (n-1)/n over the
    wire, gradients reduced (the CPU partitioner writes the
    reduce-scatter as an all-reduce and a slice: counted as written);
    the engine's families say it when the registry is read, and the
    ``comms_logger`` block prints it."""
    import deepspeed_tpu
    import numpy as np
    from deepspeed_tpu import comm
    from deepspeed_tpu.comm.mesh import MeshConfig, build_mesh
    said = []
    monkeypatch.setattr(comm.comm.logger, "info",
                        lambda msg, *a, **k: said.append(str(msg)))
    was = (comm.comms_logger.enabled, comm.comms_logger.verbose)
    params = {"blk0": {"w": jnp.full((64, 128), 0.1, jnp.float32)},
              "blk1": {"w": jnp.full((128, 32), 0.1, jnp.float32)}}
    param_bytes = (64 * 128 + 128 * 32) * 4

    def loss_fn(p, b, rng):
        y = jnp.tanh(b["x"] @ p["blk0"]["w"]) @ p["blk1"]["w"]
        return jnp.mean((y - b["y"]) ** 2)
    n = 4
    mesh = build_mesh(MeshConfig(data=1, fsdp=n), devices=jax.devices()[:n])
    # rows enough that gathering the parameters is cheaper than
    # gathering the batch: the partitioner chooses by bytes
    engine, _, _, _ = deepspeed_tpu.initialize(
        loss_fn=loss_fn, model_parameters=params, mesh=mesh,
        config={"train_micro_batch_size_per_gpu": 256,
                "optimizer": {"type": "sgd", "params": {"lr": 0.01}},
                "zero_optimization": {
                    "stage": 3, "stage3_param_persistence_threshold": 0},
                "comms_logger": {"enabled": True, "verbose": True}})
    try:
        rng = np.random.default_rng(0)
        B = engine.train_batch_size
        for _ in range(3):
            engine.train_batch({
                "x": jnp.asarray(rng.normal(size=(B, 64)), jnp.float32),
                "y": jnp.zeros((B, 32), jnp.float32)})
        # this engine's own executable (the program-level table merges
        # every executable a process compiled under the name)
        table = compile_watch.executable_tables(
            engine._step_fn.executables[0]).movement
        rows = list(table.values())
        assert {r["kind"] for r in rows} <= set(compile_watch.COLLECTIVES)
        gathers = [r for r in rows if r["kind"] == "all-gather"]
        # each parameter gathered once (a program this small keeps the
        # gathered copy for its backward pass): passes = 1
        assert sum(r["bytes"] for r in gathers) == param_bytes
        assert sum(r["wire_bytes"] for r in gathers) == \
            param_bytes * (n - 1) / n
        assert all(r["group"] == n and r["pass"] == "fwd"
                   and r["scopes"] == "fwd_bwd" for r in gathers)
        # the gradients: as many bytes as the parameters, reduced in the
        # backward pass
        reduces = [r for r in rows if r["pass"] == "bwd"
                   and r["kind"] in ("all-reduce", "reduce-scatter")]
        assert sum(r["bytes"] for r in reduces) == param_bytes
        per_step = compile_watch.movement_per_step(table)
        assert per_step["all-gather"] == {
            "bytes": param_bytes * (n - 1) / n, "calls": len(gathers)}
        # the families appear when the registry is READ, not before
        reg = get_registry()
        assert "train_moved_bytes_per_step" not in reg._families
        snap = reg.snapshot()

        def series(name):
            return {s["labels"]["kind"]: s["value"]
                    for s in snap[name]["series"]}
        assert series("train_moved_bytes_per_step") == {
            k: v["bytes"] for k, v in per_step.items()}
        assert series("train_movement_calls_per_step") == {
            k: v["calls"] for k, v in per_step.items()}
        assert series("train_moved_bytes_total") == {
            k: 3 * v["bytes"] for k, v in per_step.items()}
        engine.train_batch({
            "x": jnp.asarray(rng.normal(size=(B, 64)), jnp.float32),
            "y": jnp.zeros((B, 32), jnp.float32)})
        assert "train_moved_bytes_total{kind=\"all-gather\"} " + str(
            int(4 * per_step["all-gather"]["bytes"])) \
            in reg.prometheus_text()
        # the comms_logger block printed the step's table once, after
        # the first step, by kind and (verbose) by pass and scope
        lines = [m for m in said if m.startswith("comm: train_step: ")]
        assert sum("all-gather: %d transfers" % len(gathers) in m
                   for m in lines) == 1
        assert any("all-gather | pass fwd | scope fwd_bwd" in m
                   for m in lines)
        assert comm.comms_logger.summary()["train_step"] == \
            compile_watch.movement_per_step("train_step")
    finally:
        engine.destroy()
        comm.comms_logger.configure(*was)
    assert not get_registry()._collectors        # gone with the engine


# ======================================================= the compile watch

def test_compile_spans_phases_and_cache_counts(fresh):
    """A watched function compiled twice (two signatures): two
    ``compile:<program>`` spans, each tiled by trace, lower and
    backend_compile, and phase totals that count both, every compile a
    miss with the persistent cache off (as the suite keeps it)."""
    def fn(x, *, k):
        with jax.named_scope("mlp"):
            return jnp.tanh(x) * k
    before = compile_watch.phase_totals().get("spans_test_prog", {})
    w = watched_jit(functools.partial(fn, k=2.0), name="spans_test_prog",
                    registry=MetricRegistry())
    w(jnp.ones((4, 4)))
    w(jnp.ones((8, 4)))
    w(jnp.ones((8, 4)))             # a hit of the watch's own cache
    recs = fresh.snapshot(prefix="compile:")
    roots = [r for r in recs if r[NAME] == "compile:spans_test_prog"]
    assert [r[KEY] for r in roots] == [0, 1]
    for root in roots:
        assert root[ATTRS] == {"program": "spans_test_prog",
                               "index": root[KEY], "cache_hit": False}
        kids = [r for r in recs if r[PARENT] == root[ID]]
        assert [k[NAME] for k in sorted(kids, key=lambda r: r[START])] == [
            "compile:trace", "compile:lower", "compile:backend_compile"]
        _tiles(root, kids)
    row = compile_watch.phase_totals()["spans_test_prog"]
    delta = {k: row[k] - before.get(k, 0) for k in row}
    assert delta["traces"] == 2 and delta["compiles"] == 2
    assert delta["cache_misses"] == 2 and delta["cache_hits"] == 0
    assert delta["trace_s"] > 0 and delta["lower_s"] > 0
    assert delta["compile_s"] > 0 and delta["cache_read_s"] == 0
    assert "spans_test_prog:" in compile_watch.compile_report()
    # the module carries the watch's name, and the table its scope
    text = w.executables[0].compiled.as_text()
    assert "HloModule jit_spans_test_prog" in text
    assert "mlp" in set(compile_watch.scope_table("spans_test_prog").values())
    # the table survives the watched function and its executables
    del w
    gc.collect()
    assert "spans_test_prog" in compile_watch.watched_programs()
    table = compile_watch.scope_table("spans_test_prog")
    assert "mlp" in set(table.values())
    assert compile_watch.phase_totals()["spans_test_prog"]["text_s"] > 0


def test_phase_listeners_count_hits_and_nested_traces():
    """The listeners' bookkeeping, driven by hand: a hit announced
    inside a backend-compile interval makes that compile a cache read,
    and a trace that ran inside another is taken out of the outer's
    seconds (the sum over functions is wall time)."""
    name = "jit(spans_fake_prog)"
    cw = compile_watch
    cw._on_begin(cw.TRACE_EVENT, 100.0, fun_name="spans_fake_prog")
    for k in range(2):              # two kernels traced inside it
        cw._on_begin(cw.TRACE_EVENT, 100.5 + k, fun_name="spans_fake_inner")
        cw._on_span(cw.TRACE_EVENT, 100.5 + k, 100.75 + k,
                    fun_name="spans_fake_inner")
    cw._on_span(cw.TRACE_EVENT, 100.0, 102.0, fun_name="spans_fake_prog")
    cw._on_span(cw.LOWER_EVENT, 102.0, 102.25, fun_name=name)
    cw._on_span(cw.BACKEND_EVENT, 102.25, 104.25, fun_name=name)
    cw._on_event(cw.CACHE_HIT_EVENT)
    cw._on_span(cw.BACKEND_EVENT, 105.0, 105.125, fun_name=name)
    totals = cw.phase_totals()
    assert totals["spans_fake_prog"] == {
        "trace_s": 1.5, "lower_s": 0.25, "compile_s": 2.0,
        "cache_read_s": 0.125, "text_s": 0.0, "traces": 1, "compiles": 2,
        "cache_hits": 1, "cache_misses": 1}
    assert totals["spans_fake_inner"]["trace_s"] == 0.5
    assert totals["spans_fake_inner"]["traces"] == 2


HLO = """\
HloModule jit_serve_decode, is_scheduled=true

%fused_computation.7 (param_0.1: bf16[8,256]) -> bf16[8,256] {
  %param_0.1 = bf16[8,256]{1,0} parameter(0)
  ROOT %multiply.3 = bf16[8,256]{1,0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(serve_decode)/mlp/mul" stack_frame_id=9}
}

%body.2 (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %get-tuple-element.5 = f32[4]{0} get-tuple-element(%p), index=1
  ROOT %add.9 = f32[4]{0} add(%get-tuple-element.5, %get-tuple-element.5), metadata={op_name="jit(train_step)/fwd_bwd/while/body/transpose(jvp(mlp))/add"}
}

ENTRY %main.44 (Arg_0.1: bf16[2,65,128,2,128], Arg_1.2: bf16[8,256]) -> bf16[8,256] {
  %Arg_0.1 = bf16[2,65,128,2,128]{4,3,2,1,0} parameter(0), metadata={op_name="cache.k"}
  %Arg_1.2 = bf16[8,256]{1,0} parameter(1)
  %slice.25 = bf16[1,65,128,2,128]{4,3,2,1,0} slice(%Arg_0.1), slice={[0:1], [0:65], [0:128], [0:2], [0:128]}, metadata={op_name="jit(serve_decode)/kv_read/slice" stack_frame_id=46}
  %squeeze.10 = bf16[65,128,256]{2,1,0} reshape(%slice.25), metadata={op_name="jit(serve_decode)/attn_kernel/reshape;jit(serve_decode)/kv_read/squeeze"}
  %paged_decode_attention.2 = bf16[8,2,1,128]{3,2,1,0} custom-call(%Arg_1.2, %squeeze.10), custom_call_target="tpu_custom_call", metadata={op_name="jit(serve_decode)/attn_kernel/paged_decode_attention/pallas_call"}
  %fusion.7 = bf16[8,256]{1,0} fusion(%Arg_1.2), kind=kLoop, calls=%fused_computation.7
  %copy-start.3 = (bf16[8,256]{1,0}, bf16[8,256]{1,0}, u32[]) copy-start(%fusion.7), metadata={op_name="jit(train_step)/optimizer/device_put"}
  %copy-done.3 = bf16[8,256]{1,0} copy-done(%copy-start.3)
  %while.1 = (s32[], f32[4]{0}) while(%tuple.1), condition=%cond.2, body=%body.2
  %copy-start.8 = (bf16[8,256]{1,0}, bf16[8,256]{1,0:S(5)}, u32[]) copy-start(%Arg_1.2)
  %copy-done.8 = bf16[8,256]{1,0} copy-done(%copy-start.8)
  %fusion.9 = bf16[8,256]{1,0} fusion(%copy-done.8), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(train_step)/optimizer/add"}
  ROOT %add.1 = bf16[8,256]{1,0} add(%copy-done.3, %fusion.7)
}
"""


def test_scope_table_parser_on_a_fixed_text():
    scopes, kernels = compile_watch.parse_scopes(HLO)
    assert scopes["slice.25"] == "kv_read"
    # merged instructions keep the producer's scope
    assert scopes["squeeze.10"] == "kv_read"
    assert scopes["paged_decode_attention.2"] == "attn_kernel"
    assert kernels == {"paged_decode_attention.2": "paged_decode_attention"}
    assert scopes["fusion.7"] == "mlp"          # its root's
    assert scopes["copy-start.3"] == "optimizer"
    assert scopes["copy-done.3"] == "optimizer"   # its operand's
    # a host-to-device copy of a parameter has no metadata and no scoped
    # operand: it is its consumer's
    assert scopes["copy-done.8"] == scopes["copy-start.8"] == "optimizer"
    assert scopes["add.9"] == "fwd_bwd/mlp"       # outermost first
    assert scopes["while.1"] == "fwd_bwd/mlp"     # its body's root's
    assert scopes["add.1"] is None and scopes["Arg_0.1"] is None
    assert compile_watch.scopes_of("jit(f)/jit(main)/mul") is None
    assert compile_watch.scopes_of(
        "jit(f)/optimizer/optimizer/add") == "optimizer"


# ------------------------------------------------------ the movement table

MOVES = """\
HloModule jit_train_step, is_scheduled=true

%add.clone (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add.2 = f32[] add(%x, %y)
}

%wrapped_rs (p0: f32[1024,64]) -> f32[256,64] {
  %p0 = f32[1024,64]{1,0} parameter(0)
  ROOT %reduce-scatter.9 = f32[256,64]{1,0} reduce-scatter(%p0), channel_id=3, replica_groups=[1,4]<=[4], dimensions={0}, to_apply=%add.clone
}

%fused_computation.5 (param_0.1: bf16[512,64]) -> (bf16[512,64], bf16[2048,64], u32[]) {
  %param_0.1 = bf16[512,64]{1,0:T(8,128)(2,1)S(1)} parameter(0)
  %all-gather.7 = bf16[2048,64]{1,0:T(8,128)(2,1)} all-gather(%param_0.1), channel_id=5, replica_groups=[1,4]<=[4], dimensions={0}, metadata={op_name="jit(train_step)/fwd_bwd/transpose(jvp(GPT2))/fwd_bwd/jvp(GPT2)/checkpoint/h_1/mlp/c_fc/dot_general"}
  ROOT %custom-call.3 = (bf16[512,64]{1,0:T(8,128)(2,1)S(1)}, bf16[2048,64]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) custom-call(%all-gather.7), custom_call_target="AsyncCollectiveStart"
}

%async_collective_fusion.6 (param_0.2: bf16[64,64], param_1.2: bf16[512,64]) -> (bf16[64,64], bf16[512,64], bf16[2048,64], u32[]) {
  %param_0.2 = bf16[64,64]{1,0} parameter(0)
  %param_1.2 = bf16[512,64]{1,0} parameter(1)
  %convolution.4 = bf16[64,64]{1,0} convolution(%param_0.2, %param_0.2), dim_labels=bf_io->bf, metadata={op_name="jit(train_step)/fwd_bwd/transpose(jvp(GPT2))/fwd_bwd/jvp(GPT2)/checkpoint/h_1/attn/c_proj/dot_general"}
  %all-gather.8 = bf16[2048,64]{1,0} all-gather(%param_1.2), channel_id=5, replica_groups=[1,4]<=[4], dimensions={0}
  ROOT %tuple.6 = (bf16[64,64]{1,0}, bf16[512,64]{1,0}, bf16[2048,64]{1,0}, u32[]{:S(2)}) tuple(%convolution.4, %param_1.2, %all-gather.8)
}

%fused_computation.7 (param_0.3: bf16[512,64], param_1.3: bf16[2048,64]) -> bf16[2048,64] {
  %param_0.3 = bf16[512,64]{1,0} parameter(0)
  %param_1.3 = bf16[2048,64]{1,0} parameter(1)
  %all-gather.9 = bf16[2048,64]{1,0} all-gather(%param_0.3), channel_id=5, replica_groups=[1,4]<=[4], dimensions={0}
  ROOT %custom-call.4 = bf16[2048,64]{1,0:T(8,128)(2,1)S(1)} custom-call(%param_0.3, %param_1.3, %all-gather.9), custom_call_target="AsyncCollectiveDone"
}

%all-reduce-scatter.1 (input.1: bf16[16,64]) -> bf16[4,64] {
  %input.1 = bf16[16,64]{1,0} parameter(0)
  %all-reduce.6 = bf16[16,64]{1,0} all-reduce(%input.1), channel_id=9, replica_groups={{0,1,2,3}}, to_apply=%add.clone
  %partition-id.1 = u32[] partition-id()
  ROOT %dynamic-slice.2 = bf16[4,64]{1,0} dynamic-slice(%all-reduce.6, %partition-id.1), dynamic_slice_sizes={4,64}
}

%body.3 (p: (s32[], f32[4,64])) -> (s32[], f32[4,64]) {
  %p = (s32[], f32[4,64]{1,0}) parameter(0)
  %get-tuple-element.5 = f32[4,64]{1,0} get-tuple-element(%p), index=1
  %all-reduce.3 = f32[4,64]{1,0} all-reduce(%get-tuple-element.5), channel_id=4, replica_groups={{0,1,2,3}}, to_apply=%add.clone, metadata={op_name="jit(train_step)/fwd_bwd/while/body/closed_call/transpose(jvp(GPT2))/jvp(GPT2)/checkpoint/rematted_computation/h_0/ln_1/mul"}
  ROOT %tuple.3 = (s32[], f32[4,64]{1,0}) tuple(%get-tuple-element.5, %all-reduce.3)
}

%body.4 (q: (s32[], f32[4,64])) -> (s32[], f32[4,64]) {
  %q = (s32[], f32[4,64]{1,0}) parameter(0)
  %get-tuple-element.6 = f32[4,64]{1,0} get-tuple-element(%q), index=1
  %collective-permute.2 = f32[4,64]{1,0} collective-permute(%get-tuple-element.6), channel_id=6, source_target_pairs={{0,1},{1,2}}
  ROOT %tuple.4 = (s32[], f32[4,64]{1,0}) tuple(%get-tuple-element.6, %collective-permute.2)
}

ENTRY %main.9 (master: f32[2048,64], grads: f32[1024,64], w: bf16[512,64]) -> f32[2048,64] {
  %master = f32[2048,64]{1,0:T(8,128)S(5)} parameter(0), metadata={op_name="state.master"}
  %grads = f32[1024,64]{1,0} parameter(1)
  %w = bf16[512,64]{1,0} parameter(2)
  %copy-start.1 = (f32[2048,64]{1,0:T(8,128)}, f32[2048,64]{1,0:T(8,128)S(5)}, u32[]{:S(2)}) copy-start(%master)
  %copy-done.1 = f32[2048,64]{1,0:T(8,128)} copy-done(%copy-start.1)
  %copy-start.2 = (f32[2048,64]{1,0:T(8,128)S(1)}, f32[2048,64]{1,0:T(8,128)}, u32[]{:S(2)}) copy-start(%copy-done.1)
  %copy-done.2 = f32[2048,64]{1,0:T(8,128)S(1)} copy-done(%copy-start.2)
  %all-gather.1 = bf16[2048,64]{1,0} all-gather(%w), channel_id=1, replica_groups=[1,4]<=[4], dimensions={0}, use_global_device_ids=true, metadata={op_name="jit(train_step)/fwd_bwd/jvp(GPT2)/h_0/mlp/c_fc/dot_general"}
  %async-start.1 = ((f32[1024,64]{1,0}), f32[256,64]{1,0}, u32[]) async-start(%grads), calls=%wrapped_rs
  %async-done.1 = f32[256,64]{1,0} async-done(%async-start.1), metadata={op_name="jit(train_step)/fwd_bwd/transpose(jvp(GPT2))/h_0/mlp/c_fc/dot_general"}
  %all-to-all.1 = bf16[4,64,4,8]{3,1,0,2} all-to-all(%all-gather.1), channel_id=2, replica_groups=[1,4]<=[4], dimensions={2}, metadata={op_name="jit(train_step)/fwd_bwd/jvp(GPT2)/h_0/add"}
  %async-collective-start.1 = (bf16[512,64]{1,0:S(1)}, bf16[2048,64]{1,0:S(1)}, u32[]{:S(2)}) fusion(%w), kind=kCustom, calls=%fused_computation.5
  %get-tuple-element.7 = bf16[512,64]{1,0} get-tuple-element(%async-collective-start.1), index=0
  %fusion.6 = (bf16[64,64]{1,0}, bf16[512,64]{1,0}, bf16[2048,64]{1,0}, u32[]{:S(2)}) fusion(%w, %get-tuple-element.7), kind=kOutput, calls=%async_collective_fusion.6, metadata={op_name="jit(train_step)/fwd_bwd/transpose(jvp(GPT2))/fwd_bwd/jvp(GPT2)/checkpoint/h_1/attn/c_proj/dot_general"}
  %get-tuple-element.8 = bf16[512,64]{1,0} get-tuple-element(%fusion.6), index=1
  %get-tuple-element.9 = bf16[2048,64]{1,0} get-tuple-element(%fusion.6), index=2
  %async-collective-done.1 = bf16[2048,64]{1,0:S(1)} fusion(%get-tuple-element.8, %get-tuple-element.9), kind=kCustom, calls=%fused_computation.7, metadata={op_name="jit(train_step)/fwd_bwd/transpose(jvp(GPT2))/fwd_bwd/jvp(GPT2)/checkpoint/h_1/mlp/c_fc/dot_general"}
  %fusion.5 = bf16[4,64]{1,0} fusion(%all-to-all.1), kind=kCustom, calls=%all-reduce-scatter.1, metadata={op_name="jit(train_step)/fwd_bwd/jvp(GPT2)/h_0/mlp/c_fc/dot_general"}
  %while.3 = (s32[], f32[4,64]{1,0}) while(%tuple.0), condition=%cond.3, body=%body.3, backend_config={"known_trip_count":{"n":"8"}}
  %while.4 = (s32[], f32[4,64]{1,0}) while(%tuple.0), condition=%cond.4, body=%body.4
  %fusion.8 = f32[2048,64]{1,0:T(8,128)} fusion(%copy-done.2), kind=kLoop, calls=%add.clone, metadata={op_name="jit(train_step)/optimizer/add"}
  %copy-start.3 = (f32[2048,64]{1,0:T(8,128)S(5)}, f32[2048,64]{1,0:T(8,128)}, u32[]{:S(2)}) copy-start(%fusion.8)
  ROOT %copy-done.3 = f32[2048,64]{1,0:T(8,128)S(5)} copy-done(%copy-start.3)
}
"""

_F32_2048_64 = 2048 * 64 * 4
_BF16_2048_64 = 2048 * 64 * 2

MOVE_ROWS = {
    # a pinned-host parameter into HBM: no metadata, so pass and scope
    # are its consumer's consumer's (forwarded as in the scope table)
    "copy-start.1": dict(kind="host_to_device", role="start",
                         bytes=_F32_2048_64, wire_bytes=_F32_2048_64,
                         pair="copy-done.1", calls=1, per_iteration=False),
    "copy-done.1": dict(kind="host_to_device", role="done",
                        bytes=_F32_2048_64, pair="copy-start.1"),
    # HBM to on-chip memory: inside the device, no row
    "copy-start.2": None, "copy-done.2": None,
    "copy-start.3": dict(kind="device_to_host", role="start",
                         bytes=_F32_2048_64, pair="copy-done.3",
                         scopes="optimizer", **{"pass": "optimizer"}),
    "copy-done.3": dict(kind="device_to_host", role="done",
                        pair="copy-start.3", scopes="optimizer",
                        **{"pass": "optimizer"}),
    "all-gather.1": dict(kind="all-gather", role="sync", group=4,
                         bytes=_BF16_2048_64,
                         wire_bytes=_BF16_2048_64 * 3 / 4, pair=None,
                         scopes="fwd_bwd/mlp", **{"pass": "fwd"}),
    # a generic async pair is named by what it wraps; a reduce-scatter
    # moves its OPERAND; the start takes its done's pass and scope
    "async-start.1": dict(kind="reduce-scatter", role="start", group=4,
                          bytes=1024 * 64 * 4,
                          wire_bytes=1024 * 64 * 4 * 3 / 4,
                          pair="async-done.1", scopes="fwd_bwd/mlp",
                          **{"pass": "bwd"}),
    "async-done.1": dict(kind="reduce-scatter", role="done",
                         bytes=1024 * 64 * 4, pair="async-start.1",
                         **{"pass": "bwd"}),
    "all-to-all.1": dict(kind="all-to-all", role="sync", group=4,
                         bytes=4 * 64 * 4 * 8 * 2, scopes="fwd_bwd",
                         **{"pass": "fwd"}),
    # the TPU's async pair: fusions around AsyncCollectiveStart / Done,
    # joined through the matmul fusion that carries the gather along
    "async-collective-start.1": dict(
        kind="all-gather", role="start", bytes=_BF16_2048_64,
        pair="async-collective-done.1", scopes="fwd_bwd/mlp",
        **{"pass": "bwd"}),
    "async-collective-done.1": dict(
        kind="all-gather", role="done", bytes=_BF16_2048_64,
        pair="async-collective-start.1", **{"pass": "bwd"}),
    "fusion.6": dict(kind="all-gather", role="carrier", pair=None,
                     scopes="fwd_bwd", **{"pass": "bwd"}),
    # all-reduce + slice in a fusion's clothes: the TPU's reduce-scatter
    "fusion.5": dict(kind="reduce-scatter", role="fused", group=4,
                     bytes=16 * 64 * 2, wire_bytes=16 * 64 * 2 * 3 / 4,
                     **{"pass": "fwd"}),
    # in a while body whose trip count the text states: x 8
    "all-reduce.3": dict(kind="all-reduce", role="sync", calls=8,
                         per_iteration=False, bytes=8 * 4 * 64 * 4,
                         wire_bytes=8 * 2 * 4 * 64 * 4 * 3 / 4,
                         **{"pass": "recompute"}),
    # in one whose trip count it does not state: one iteration's
    "collective-permute.2": dict(kind="collective-permute", role="sync",
                                 calls=1, per_iteration=True,
                                 bytes=4 * 64 * 4, wire_bytes=4 * 64 * 4,
                                 group=None, **{"pass": None}),
    # instructions INSIDE a fusion or an async wrapper are no rows
    "all-gather.7": None, "all-gather.8": None, "reduce-scatter.9": None,
    "all-reduce.6": None,
}


@pytest.mark.parametrize("name", sorted(MOVE_ROWS))
def test_movement_table_parser_on_a_fixed_text(name):
    table = compile_watch.parse(MOVES).movement
    want = MOVE_ROWS[name]
    if want is None:
        assert name not in table
        return
    row = table[name]
    assert {k: row[k] for k in want} == want
    assert row["kind"] not in ("async-collective", "async", "fusion")


def test_movement_table_totals_passes_and_disagreement(fresh):
    parsed = compile_watch.parse(MOVES)
    # the scope table of the same parse is what parse_scopes gives
    assert (parsed.scopes, parsed.kernels) == \
        compile_watch.parse_scopes(MOVES)
    assert parsed.passes["all-gather.1"] == "fwd"
    assert parsed.passes["fusion.8"] == "optimizer"
    assert parsed.passes["copy-start.3"] == "optimizer"   # its operand's
    assert parsed.passes["master"] is None
    # XLA's own rematerialisation: the clone keeps the forward's path
    remat = compile_watch.parse(MOVES.replace("%all-gather.1 =",
                                              "%all-gather.1.remat2 ="))
    assert remat.passes["all-gather.1.remat2"] == "recompute"
    assert remat.movement["all-gather.1.remat2"]["pass"] == "recompute"
    for path, want in [
            ("jit(train_step)/fwd_bwd/jvp(GPT2)/h_0/mlp/c_fc/dot_general",
             "fwd"),
            ("jit(train_step)/fwd_bwd/while/body/closed_call/"
             "transpose(jvp(GPT2))/jvp(GPT2)/checkpoint/h_1/attn/add", "bwd"),
            ("jit(train_step)/fwd_bwd/transpose(jvp(GPT2))/fwd_bwd/"
             "jvp(GPT2)/checkpoint/rematted_computation/shard_map/"
             "flash_attention_fwd/pallas_call", "recompute"),
            ("jit(train_step)/optimizer/mul", "optimizer"),
            ("jit(train_step)/fwd_bwd/add", None),
            ("jit(serve_decode)/mlp/mul", None)]:
        assert compile_watch.pass_of(path) == want, path
    # one watched program, two executables that number their
    # instructions alike and disagree about one of them: no row
    other = MOVES.replace(
        "all-to-all(%all-gather.1), channel_id=2, replica_groups=[1,4]<=[4]",
        "all-to-all(%all-gather.1), channel_id=2, replica_groups=[1,2]<=[2]")
    compile_watch._harvest("moves_fixture", [])
    with compile_watch._tables_lock:
        compile_watch._texts["moves_fixture"] = [MOVES, other]
        compile_watch._tables.pop("moves_fixture", None)
    try:
        table = compile_watch.movement_table("moves_fixture")
        assert table["all-to-all.1"] is None
        assert table["all-gather.1"]["kind"] == "all-gather"
        assert compile_watch.pass_table("moves_fixture")["fusion.8"] == \
            "optimizer"
        per_step = compile_watch.movement_per_step("moves_fixture")
        # pairs and synchronous instructions once; a carrier never
        assert per_step["host_to_device"] == {
            "bytes": _F32_2048_64, "calls": 1}
        assert per_step["device_to_host"] == {
            "bytes": _F32_2048_64, "calls": 1}
        assert per_step["all-gather"] == {
            "bytes": 2 * _BF16_2048_64 * 3 / 4, "calls": 2}
        assert per_step["all-reduce"]["calls"] == 8
        assert "all-to-all" not in per_step
    finally:
        with compile_watch._tables_lock:
            compile_watch._texts.pop("moves_fixture", None)
            compile_watch._tables.pop("moves_fixture", None)
