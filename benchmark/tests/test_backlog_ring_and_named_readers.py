"""CPU tests of what PR 25 changed: the backlog as an endless ring of laps
topped up between steps, the serving readers keyed by program and kernel
NAME (none by the KV pool's shape), and idle gaps named by the program's
own ``serve:`` spans. Counts, orders and recorded device times only.

    python -m pytest benchmark/tests -q -p no:cacheprovider

The pinned numbers were read with the parent commit's operand-keyed
readers (they told the paged kernel by the pool operand's shape) on the
same traces, before those were deleted.
"""
import hashlib
import itertools
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)

from benchmark.lib import harness, peaks, program_spans as ps  # noqa: E402
from benchmark.lib import serve_cell, trace_reduce as tr  # noqa: E402
from benchmark.lib import traffic as T  # noqa: E402

VOCAB = 50257
BATCH = harness.load_json(os.path.join(BENCH, "traffic", "batch.json"))
FIVE = ("decode_program_ms", "chat_decode_program_ms",
        "paged_decode_roofline", "prefill_ms_per_ktok",
        "prefill_flash_roofline")


def reader(name):
    return harness.load_reader(name, BENCH)


# ------------------------------------------------------------- the ring

def digest(requests) -> str:
    """Lengths, output budgets and token ids of a list of requests."""
    h = hashlib.sha256()
    for r in requests:
        h.update(json.dumps([r["due"], r["out"], r["counted"],
                             r["prompt"]]).encode())
    return h.hexdigest()


# sha256 over the 384 requests ``traffic.build_requests`` of the PARENT
# commit (b85833f) makes from ``traffic/batch.json`` (whose generator
# parameters this PR leaves as they were)
PARENT_LAP0 = {
    31: "ec7125e5e70f0e347747275cf6e1093f52f325cbd615a5bec79f5038fe6c44ca",
    32: "b3e416b22a71ce6800f34a2e7b98e5077504bccdfe15b04f2638b1d17547b18a",
    33: "71df6667cecce89a3eb5a70ad252201a35890980f6f3742071712dae9fec0889",
    2147483659: "0abc8fe4b4f90c641f5b5cada53424fcbd46f3e1111cc41cab0613dc24bdae72",
}


@pytest.mark.parametrize("seed", sorted(PARENT_LAP0))
def test_lap_0_is_the_parents_list_request_for_request(seed):
    n = BATCH["requests"]
    made = T.build_requests(BATCH, 50.0, seed, VOCAB)["requests"]
    assert digest(made) == PARENT_LAP0[seed]
    lap0 = list(itertools.islice(T.backlog_ring(BATCH, seed, VOCAB), n))
    assert [i for i, _ in lap0] == list(range(n))
    assert [r for _, r in lap0] == made


@pytest.mark.parametrize("seed", [31, 2147483659])
def test_later_laps_are_the_same_multiset_in_another_order(seed):
    n = BATCH["requests"]
    ring = T.backlog_ring(BATCH, seed, VOCAB)
    laps = [list(itertools.islice(ring, n)) for _ in range(4)]

    def pairs(lap):
        return [(len(r["prompt"]), r["out"]) for _, r in lap]
    for k, lap in enumerate(laps):
        assert [i for i, _ in lap] == list(range(k * n, (k + 1) * n))
        assert sorted(pairs(lap)) == sorted(pairs(laps[0]))
        assert all(r["due"] == 0.0 and r["counted"] for _, r in lap)
        # any stretch of a lap is close to the whole (stratified order)
        whole = sum(p for p, _ in pairs(lap)) / n
        for at in range(0, n, 32):
            part = pairs(lap)[at:at + 32]
            assert abs(sum(p for p, _ in part) / 32 - whole) < 0.12 * whole
    assert len({tuple(pairs(lap)) for lap in laps}) == 4
    assert len({tuple(lap[0][1]["prompt"]) for lap in laps}) == 4
    # a lap depends on (seed, lap) alone: begun at lap 2, the same lap 2
    again = list(itertools.islice(
        T.backlog_ring(BATCH, seed, VOCAB, first_lap=2), n))
    assert again == laps[2]


def test_laps_keep_lap_0s_shared_prefixes():
    mix = dict(BATCH, requests=32, shared_prefix_tokens=24,
               shared_prefix_groups=2)
    ring = T.backlog_ring(mix, 5, VOCAB)
    laps = [list(itertools.islice(ring, 32)) for _ in range(3)]
    heads = [{tuple(r["prompt"][:24]) for _, r in lap} for lap in laps]
    assert len(heads[0]) == 2 and heads[0] == heads[1] == heads[2]


# ----------------------------------------------- the loop and its top-up

class _Slot:
    def __init__(self, request_id, prompt, budget):
        self.request = type("R", (), {"request_id": request_id})
        self.prompt, self.budget = prompt, budget
        self.generated = []


class _Scheduler:
    def __init__(self):
        self.queue, self.slots = [], {}

    @property
    def pending_requests(self):
        return len(self.queue)

    @property
    def idle(self):
        return not self.queue and not self.slots


class GreedyServer:
    """A server that takes no time: every step it admits ``admit`` queued
    requests into its free slots and then finishes EVERY resident
    request (all its tokens at once), so each step frees all slots: the
    fastest server a backlog can meet. ``max_queued`` refuses beyond."""

    def __init__(self, num_slots=4, admit=None, max_queued=10 ** 6):
        self.num_slots = num_slots
        self.admit = admit or num_slots
        self.max_queued = max_queued
        self.scheduler = _Scheduler()
        self.seen, self.out = [], {}
        self.free_with_empty_queue = 0
        self.queue_at_step = []

    def submit(self, prompt, max_new_tokens, eos_token_id, request_id):
        if len(self.scheduler.queue) >= self.max_queued:
            raise RuntimeError("request queue is full")
        self.seen.append(request_id)
        self.scheduler.queue.append((request_id, list(prompt),
                                     max_new_tokens))

    def step(self):
        sch = self.scheduler
        self.queue_at_step.append(len(sch.queue))
        finished = []
        for slot, st in list(sch.slots.items()):
            st.generated = [1] * st.budget
            self.out[st.request.request_id] = st.prompt + st.generated
            finished.append(st.request.request_id)
            del sch.slots[slot]
        for _ in range(self.admit):
            free = [k for k in range(self.num_slots) if k not in sch.slots]
            if not free:
                break
            if not sch.queue:
                self.free_with_empty_queue += 1
                break
            rid, prompt, budget = sch.queue.pop(0)
            sch.slots[free[0]] = _Slot(rid, prompt, budget)
            sch.slots[free[0]].generated = [1]
        return finished

    def result(self, rid):
        return self.out[rid]

    def finish_reason(self, rid):
        return "length"

    def forget(self, rid):
        del self.out[rid]

    def close(self):
        pass


def _drive(server, mix, steps, seed=31):
    """``run_backlog`` for ``steps`` steps of the window: the session's
    clock counts the server's steps."""
    sess = serve_cell.Session(server)
    sess.clock = lambda: float(len(server.queue_at_step))
    made = T.build_requests(mix, 1.0, seed, VOCAB)["requests"]
    reqs = serve_cell.make_tracked(made)
    ring = T.backlog_ring(mix, seed, VOCAB, first_lap=1)
    try:
        win = serve_cell.run_backlog(sess, reqs, ring, float(steps),
                                     harness.Tracer(False, ""), 0.0)
    finally:
        sess.close()
    return sess, reqs, win


@pytest.mark.parametrize("slots,admit", [(4, 4), (32, 32), (2, 2)])
def test_a_server_that_empties_every_slot_every_step_never_runs_dry(
        slots, admit):
    mix = dict(BATCH, requests=48)
    server = GreedyServer(num_slots=slots, admit=admit)
    sess, reqs, win = _drive(server, mix, steps=200)
    steps = len(sess.steps) - win["first_step"]
    assert steps == 200
    assert not win["ran_dry"] and server.free_with_empty_queue == 0
    # the server met the starting depth at every step of the window
    assert set(server.queue_at_step[win["first_step"]:]) == {48}
    assert win["top_up"]["lowest_queue"] == 48 - min(admit, slots)
    assert win["top_up"]["requests"] == steps * min(admit, slots)
    # several laps were touched, and no number was given twice
    assert len(server.seen) == len(set(server.seen)) == len(reqs)
    assert [r.rid for r in reqs] == list(range(len(reqs)))
    assert len(reqs) > 3 * 48
    assert not any(r.refused for r in reqs)
    # FIFO: what was served was served in the ring's order
    served = [r.rid for r in reqs if r.done is not None]
    assert served == sorted(served) and len(served) > 2 * 48


def test_a_step_that_finds_the_queue_empty_is_still_reported_dry():
    class Shedding(GreedyServer):
        def step(self):
            if len(self.queue_at_step) == 30:
                self.scheduler.queue.clear()     # as a shedding server might
            return super().step()
    sess, reqs, win = _drive(Shedding(num_slots=4), dict(BATCH, requests=48),
                             steps=60)
    assert win["ran_dry"] and win["top_up"]["lowest_queue"] == 0


def test_a_refused_top_up_is_a_failed_request_and_ends_that_top_up():
    mix = dict(BATCH, requests=48)
    server = GreedyServer(num_slots=4, max_queued=46)
    sess, reqs, win = _drive(server, mix, steps=20)
    refused = [r for r in reqs if r.refused]
    # lap 0's last two, then one attempt (not an endless loop) a step
    assert [r.rid for r in refused[:2]] == [46, 47]
    steps = len(sess.steps)
    assert 2 < len(refused) <= 2 + steps
    assert all(r.failed for r in refused)


def test_the_tiny_backlog_outlasts_its_first_lap(tmp_path):
    """The whole runner at a tiny size, with a backlog of 16 that the
    tiny server finishes many times over in the window: the parent's
    loop read ``backlog_never_dry: false`` here."""
    import test_benchmark_harness as H
    root, contract = H._tmp_benchmark(tmp_path)
    mix = harness.load_json(root / "traffic" / "tiny-batch.json")
    (root / "traffic" / "tiny-batch.json").write_text(
        json.dumps(dict(mix, requests=16)))
    _, run, _ = H._run_cell(contract, tmp_path, "serve-gpt2-1.3b-batch",
                            seconds=2.0, seed=31)
    assert all(run["checks"].values()), run["checks"]
    assert run["failed"] == 0
    totals = run["totals"]
    assert totals["requests"] == 16 and totals["laps_touched"] >= 3
    assert totals["requests_offered"] >= run["attempted"] > 32
    assert run["window_tokens"] > 2 * totals["output_tokens"]
    rids = [r.rid for r in run["requests"]]
    assert rids == list(range(len(rids)))


# ------------------------------------- the five readers, old against new

def named():
    """The trace recorded on the chip (PR 24) with the tables recorded
    beside it, and a run record as the serving runner leaves it: four
    residents, one step record for each ``serve:step`` span."""
    with open(os.path.join(BENCH, "testdata",
                           "tiny_named_trace.json")) as fh:
        data = json.load(fh)
    trace = tr.read(os.path.join(BENCH, "testdata",
                                 "tiny_named_trace.xplane.pb"))
    steps = [(r[ps.START], r[ps.END], 4, 4 * (40 + k))
             for k, r in enumerate(x for x in data["spans"]
                                   if x[ps.NAME] == ps.STEP)]
    run = dict(data["run"], steps=steps, admissions=[],
               peaks=peaks.peaks_for("TPU v5 lite"),
               shapes={"kv_heads": 2, "head_dim": 128, "n_head": 2,
                       "n_layer": 2, "itemsize": 2})
    return data, run, trace


POOL = "bf16[49,128,2048]{2,1,0}"


def hand_made(pool=POOL):
    """Three decode programs (two layers: a cut of the pool and a paged
    kernel call each) around two prefill programs (two flash calls
    each), and small programs with no kernel at all."""
    def paged(k):
        return (f"%paged_decode_attention.{k} = bf16[4,16,1,128] "
                f"custom-call(s32[4] %copy-done.{k}, bf16[4,16,1,128] "
                f"%copy.{k}, {pool} %squeeze.{k}, {pool} %squeeze.{k + 1}),"
                " custom_call_target=\"tpu_custom_call\"")

    def flash(k):
        return (f"%flash_attention_fwd.{k} = (bf16[16,256,128], "
                "f32[16,256,1]) custom-call(bf16[16,256,128] %fusion.1, "
                "bf16[16,256,128] %fusion.2, bf16[16,256,128] %fusion.3), "
                "custom_call_target=\"tpu_custom_call\"")
    ops, mods = [], []

    def decode(at):
        mods.append(("jit_serve_decode(7)", at, at + 0.050))
        for layer in range(2):
            t = at + 0.025 * layer
            ops.append((f"%squeeze.{layer} = {pool} fusion(%p)", t,
                        t + 0.018))
            ops.append((paged(layer), t + 0.018, t + 0.0205 + 0.001 * layer))

    def prefill(at, ident, dur):
        mods.append((f"jit_serve_prefill({ident})", at, at + dur))
        ops.append(("%fusion.9 = bf16[256,2048] fusion(%x)", at,
                    at + 0.3 * dur))
        for layer in range(2):
            t = at + (0.4 + 0.25 * layer) * dur
            ops.append((flash(layer), t, t + 0.125 * dur))
    decode(1.0)
    prefill(1.06, 11, 0.040)
    decode(1.11)
    mods.append(("jit_convert_element_type(3)", 1.165, 1.1651))
    ops.append(("%convert.1 = s32[4] convert(%a)", 1.165, 1.1651))
    prefill(1.17, 12, 0.012)
    decode(1.19)
    spans = [("bench:window", 0.9, 1.3)]
    trace = tr.Reduced({0: {"ops": ops, "modules": mods}}, spans)
    run = {"kind": "serve", "trace_t0": 10.0, "trace_t1": 10.4,
           "steps": [(10.1 + 0.05 * k, 10.15 + 0.05 * k, 4, 900 + 4 * k)
                     for k in range(3)],
           "admissions": [(10.16, 100), (10.27, 300), (10.9, 64)],
           "peaks": peaks.peaks_for("TPU v5 lite"),
           "shapes": {"kv_heads": 16, "head_dim": 128, "n_head": 16,
                      "n_layer": 2, "itemsize": 2}}
    return run, trace


# read by the PARENT's readers (by the pool operand's shape) on these traces
OLD = {
    "named": {
        "decode_program_ms": 0.032516000000003265,
        "chat_decode_program_ms": 0.032516000000003265,
        "paged_decode_roofline": 9.502691464050002,
        "prefill_ms_per_ktok": None,
        "prefill_flash_roofline": None,
    },
    "hand_made": {
        "decode_program_ms": 50.00000000000004,
        "chat_decode_program_ms": 50.00000000000004,
        "paged_decode_roofline": 0.3014069190069299,
        "prefill_ms_per_ktok": 130.00000000000009,
        "prefill_flash_roofline": 0.12310697849159481,
    },
}


@pytest.mark.parametrize("name", FIVE)
def test_named_readers_read_what_the_operand_keyed_ones_read_recorded(
        name, monkeypatch):
    data, run, trace = named()
    monkeypatch.setattr(ps, "tables", lambda program: (
        data["tables"].get(program, {}).get("scopes", {}),
        data["tables"].get(program, {}).get("kernels", {})))
    assert reader(name)(run, trace) == OLD["named"][name]


@pytest.mark.parametrize("name", FIVE)
def test_named_readers_read_what_the_operand_keyed_ones_read_hand_made(
        name):
    run, trace = hand_made()
    got = reader(name)(run, trace)
    assert got is not None and got == OLD["hand_made"][name]


@pytest.mark.parametrize("name", FIVE)
def test_no_reader_follows_the_pools_shape(name, monkeypatch):
    """The decode kernel handed the WHOLE pool (layer picked in its index
    map) is still ``paged_decode_attention`` in ``serve_decode``, and no
    prefill metric counts it."""
    whole = "bf16[24,49,128,2048]{3,2,1,0}"
    run, trace = hand_made(pool=whole)
    assert any(whole in t for t, *_ in trace.devices[0].ops)
    assert reader(name)(run, trace) == OLD["hand_made"][name]
    # and on the recorded trace, with its operand's text edited
    data, run, trace = named()
    monkeypatch.setattr(ps, "tables", lambda program: ({}, {}))
    dev = trace.devices[0]
    assert any("bf16[9,128,256]" in t for t, *_ in dev.ops)
    edited = tr.Reduced(
        {0: {"ops": [(t.replace("bf16[9,128,256]", "bf16[2,9,128,256]"),
                      s, e) for t, s, e, _ in dev.ops],
             "modules": dev.modules}},
        trace.host_spans + [(tr.WINDOW_SPAN, trace.lo, trace.hi)])
    assert not any("bf16[9,128,256]" in t for t, *_ in edited.devices[0].ops)
    assert reader(name)(run, edited) == OLD["named"][name]


def test_kernel_calls_are_found_by_name_inside_their_program():
    run, trace = hand_made()
    calls = ps.kernel_calls(trace, "serve_decode", "paged_decode_attention")
    assert len(calls) == 6
    assert not ps.kernel_calls(trace, "serve_prefill",
                               "paged_decode_attention")
    assert len(ps.kernel_calls(trace, "serve_prefill",
                               "flash_attention_fwd")) == 4
    assert not ps.kernel_calls(trace, "serve_prefill_chunk",
                               "flash_attention_fwd")
    assert len(ps.executions(trace, "serve_decode")) == 3
    # per execution, the two layers' calls: 2.5 + 3.5 ms
    assert ps.kernel_ms(trace, "serve_decode", "paged_decode_attention"
                        ) == pytest.approx(6.0)


def test_nothing_in_the_benchmark_matches_an_operands_shape():
    for dp, _, files in os.walk(BENCH):
        if os.path.basename(dp) in ("tests", "out", "__pycache__"):
            continue
        for f in files:
            if f.endswith(".py"):
                text = open(os.path.join(dp, f)).read()
                for gone in ("is_" "paged", "num_" "blocks", "modules_" "with"):
                    assert gone not in text, (f, gone)


# ------------------------------------------- idle gaps by the program's spans

class _Event:
    def __init__(self, name, **stats):
        self.name, self.stats = name, list(stats.items())


def test_a_phase_event_is_named_by_its_phase_stat():
    assert tr.span_name(_Event("serve:phase", phase="sync_wait",
                               depth=1)) == "serve:sync_wait"
    assert tr.span_name(_Event("serve:phase")) == "serve:phase"
    assert tr.span_name(_Event("serve:step", step=3)) == "serve:step"
    assert tr.span_name(_Event("bench:step")) == "bench:step"


def test_idle_gaps_go_to_the_innermost_span_the_programs_included():
    ops = [("%fusion.1 = f32[8] fusion(%a)", 1.0, 2.0),
           ("%fusion.2 = f32[8] fusion(%a)", 3.0, 4.0),
           ("%fusion.3 = f32[8] fusion(%a)", 6.0, 7.0)]
    spans = [("bench:window", 1.0, 9.0), ("bench:step", 0.5, 4.5),
             ("serve:step", 0.6, 4.4), ("serve:dispatch", 2.1, 2.8),
             ("bench:stamp", 4.5, 5.0), ("train:step", 5.0, 9.0)]
    red = tr.Reduced({0: {"ops": ops, "modules": []}}, spans)
    # 2-3 -> its middle is in serve:dispatch (inside serve:step inside
    # bench:step); 4-6 -> middle 5.0: the stamp and the train step both
    # touch it, the shorter wins; 7-9 -> train:step
    assert dict(red.idle_gaps()) == {"serve:dispatch": 1.0,
                                     "bench:stamp": 2.0, "train:step": 2.0}


def test_recorded_trace_names_its_idle_gaps_by_server_phase():
    _, _, trace = named()
    names = {n for n, _, _ in trace.host_spans}
    assert {"bench:step", "serve:step", "serve:dispatch",
            "serve:sync_wait"} <= names
    assert "serve:phase" not in names
    gaps = trace.idle_gaps()
    assert gaps[0][0].startswith("serve:") and gaps[0][0] != "serve:step"
    total = sum(v for _, v in gaps)
    assert total == pytest.approx(trace.window_s - trace.busy_s, rel=1e-6)
