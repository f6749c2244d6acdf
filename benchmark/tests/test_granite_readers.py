"""CPU tests of the Granite hybrid's readers (``lib/granite_readers.py``,
``lib/flops_granite.py`` and the eight metric files) on a hand-made trace
with a hand-made scope table, a synthetic span log and a private
registry: what each reads, that a share of a roofline is the bytes or
operations the algorithm needs over the time the scope took, and that a
program without the scopes, spans or counters reads nothing. Counts and
identities only.

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))

from benchmark.lib import (flops_granite, granite_readers as gr,  # noqa: E402
                           harness, program_spans as ps, trace_reduce as tr)
from deepspeed_tpu.telemetry import (MetricRegistry,  # noqa: E402
                                     get_registry, set_registry)
from deepspeed_tpu.telemetry.spans import SpanLog, set_span_log  # noqa: E402

CELL = "serve-granite4-h-small-ep2-decode-batch"
NEW = ("granite_decode_mamba_ms", "granite_decode_attn_ms",
       "granite_decode_moe_ms", "mamba_state_update_roofline",
       "granite_state_gb_per_step", "granite_kv_gb_per_step",
       "granite_refill_share_pct")
# a reader the contract does not list yet: the traced window of a backlog
# cell opens with every slot freshly filled and is over (160 steps)
# before the first request ends, so no prefill program runs inside it
UNLISTED = ("mamba_scan_roofline",)
TRACE_READERS = NEW[:4] + UNLISTED
PEAKS = {"hbm_bytes_per_s": 8e11, "bf16_flops": 2e14}
SHAPES = {"hidden": 4096, "layers": 10, "expert_ffn": 768, "top_k": 10,
          "state_layers": 9, "mamba_heads": 128,
          "mamba_d_head": 64, "mamba_d_state": 128, "mamba_chunk": 256,
          "state_bytes": 128 * 64 * 128 * 4,
          "kv_heads": 8, "head_dim": 128, "itemsize": 2}

DECODE_TABLE = {
    "fusion.1": "mamba_in", "fusion.2": "mamba_conv",
    "fusion.3": "mamba_state", "fusion.4": "mamba_state",
    "fusion.5": "mamba_out/ln", "fusion.6": "attn_full/kv_write",
    "paged_decode_attention.7": "attn_full", "fusion.8": "moe_router",
    "fusion.9": "moe_shared", "ragged-dot.10": None, "fusion.11": "lm_head"}
PREFILL_TABLE = {"while.1": "mamba_scan", "fusion.2": "mamba_scan",
                 "fusion.3": "mamba_in", "fusion.4": "attn_full"}


def reader(name):
    return harness.load_reader(name)


def op(name, opcode, start, end):
    return (f"%{name} = f32[8]{{0}} {opcode}(%x)", start, end)


def decode_step(t):
    """One decode execution from ``t`` (seconds): mixers 1 + 1 + 4 + 1
    ms, attention 2 ms, router + shared + grouped matmul 1 + 1 + 3 ms,
    head 1 ms."""
    ms = 1e-3
    cuts = [("fusion.1", "fusion", 1), ("fusion.2", "fusion", 1),
            ("fusion.3", "fusion", 3), ("fusion.4", "fusion", 1),
            ("fusion.5", "fusion", 1), ("fusion.6", "fusion", 0.5),
            ("paged_decode_attention.7", "custom-call", 1.5),
            ("fusion.8", "fusion", 1), ("fusion.9", "fusion", 1),
            ("ragged-dot.10", "custom-call", 3), ("fusion.11", "fusion", 1)]
    ops, at = [], t
    for name, opcode, dur in cuts:
        ops.append(op(name, opcode, at, at + dur * ms))
        at += dur * ms
    return ("jit_serve_decode(3)", t, at), ops


def prefill_run(t, scan_ms):
    """One prefill execution: the scan's while (its body's fusion inside
    it) and two other fusions."""
    ms = 1e-3
    end = t + (scan_ms + 2) * ms
    return ("jit_serve_prefill(5)", t, end), [
        op("fusion.3", "fusion", t, t + ms),
        op("while.1", "while", t + ms, t + (1 + scan_ms) * ms),
        op("fusion.2", "fusion", t + 1.5 * ms, t + (0.5 + scan_ms) * ms),
        op("fusion.4", "fusion", t + (1 + scan_ms) * ms, end)]


@pytest.fixture()
def traced(monkeypatch):
    """Three decode executions and two prefills on chip 0, the tables
    that name their instructions, and the host's records beside them."""
    tables = {"serve_decode": (DECODE_TABLE, {
        "paged_decode_attention.7": "paged_decode_attention",
        "ragged-dot.10": "ragged-dot-none"}),
        "serve_prefill": (PREFILL_TABLE, {})}
    monkeypatch.setattr(ps, "tables",
                        lambda program: tables.get(program, ({}, {})))
    mods, ops = [], []
    for t in (0.0, 0.1, 0.2):
        m, o = decode_step(t)
        mods.append(m)
        ops += o
    for t, scan_ms in ((0.3, 10.0), (0.4, 30.0)):
        m, o = prefill_run(t, scan_ms)
        mods.append(m)
        ops += o
    trace = tr.Reduced({0: {"modules": mods, "ops": ops}}, [],
                       window=(-1.0, 1.0))
    run = {"kind": "serve", "shapes": SHAPES, "peaks": PEAKS,
           "trace_t0": -1.0, "trace_t1": 1.0,
           "steps": [(t, t + 0.02, 96, 0) for t in (0.0, 0.1, 0.2)],
           "admissions": [(0.3, 1000), (0.4, 4000)]}
    return run, trace


def test_the_contract_names_each_new_reader_and_its_cell():
    contract = harness.load_contract()
    by = {m["name"]: m for m in contract["per_layer"]}
    assert set(NEW) <= set(by)
    for name in NEW:
        assert by[name]["workloads"] == [CELL]
        assert by[name]["moves"] == "serve_out_tokens_per_s"
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py"))
    assert by["mamba_state_update_roofline"]["unit"] == "%"
    assert by["mamba_state_update_roofline"]["source"] == "device_trace"
    for name in UNLISTED:
        assert name not in by
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py"))


def test_scope_groups_of_one_decode_execution(traced):
    run, trace = traced
    assert reader("granite_decode_mamba_ms")(run, trace) == \
        pytest.approx(7.0)
    assert reader("granite_decode_attn_ms")(run, trace) == pytest.approx(2.0)
    # the grouped matmul carries no scope and is found by its name
    assert reader("granite_decode_moe_ms")(run, trace) == pytest.approx(5.0)
    assert reader("granite_decode_mamba_ms")(run, None) is None


def test_state_update_roofline_is_bytes_over_the_scopes_time(traced):
    """96 live slots x 9 layers x 4 MB once in and once out, over 4 ms a
    step: the same whatever does the update."""
    run, trace = traced
    need = 9 * 2 * 96 * SHAPES["state_bytes"] / PEAKS["hbm_bytes_per_s"]
    got = reader("mamba_state_update_roofline")(run, trace)
    assert got == pytest.approx(100.0 * need / 4e-3)
    assert flops_granite.state_update_bytes(96, SHAPES["state_bytes"]) == \
        2 * 96 * 128 * 64 * 128 * 4
    # a kernel NAMED for the update, outside any scope, counts the same
    per = gr.scope_seconds(trace, "serve_decode", gr.STATE_SCOPE,
                           "ragged-dot-none")
    assert per == pytest.approx([7e-3] * 3)


def test_scan_roofline_is_the_chunked_forms_work_over_its_scope(traced):
    run, trace = traced
    need = sum(9 * flops_granite.scan_seconds(p, SHAPES, PEAKS)
               for p in (1000, 4000))
    got = reader("mamba_scan_roofline")(run, trace)
    # the while and the fusion inside it: self times add up to the while
    assert got == pytest.approx(100.0 * need / 40e-3)
    assert 0 < got < 100
    # from live tokens alone; the longer of the two bounds (at these
    # shapes the bytes, by a little)
    f = flops_granite.scan_flops(4000, 256, 128, 64, 128)
    assert f == 4000 * 128 * 2 * (128 + 8192) + (
        3744 + 4000) * 2 * 128 * 8192
    b = flops_granite.scan_bytes(4000, 128, 64, 128, SHAPES["state_bytes"])
    assert b == 4000 * (2 * 8192 + 256 + 128) * 2 + SHAPES["state_bytes"]
    assert flops_granite.scan_seconds(4000, SHAPES, PEAKS) == max(
        f / PEAKS["bf16_flops"], b / PEAKS["hbm_bytes_per_s"])
    # a prompt inside its first chunk reads no earlier state
    assert flops_granite.scan_flops(100, 256, 128, 64, 128) == \
        100 * 50 * 2 * 8320 + 100 * 2 * 128 * 8192


def test_readers_read_nothing_without_their_scopes(traced, monkeypatch):
    """Another model's program (no Mamba scope on any instruction, and a
    compile watch that does not know the scopes): nothing is read, and
    nothing raises."""
    run, trace = traced
    monkeypatch.setattr(ps, "tables", lambda program: (
        {"fusion.1": "mlp", "fusion.3": "attn_kernel"}, {}))
    monkeypatch.setattr(ps, "known_scopes", lambda: frozenset({"mlp"}))
    trace.__dict__.pop("_ops", None)
    for name in TRACE_READERS:
        assert reader(name)(run, trace) is None, name
    monkeypatch.setattr(ps, "tables", lambda program: ({}, {}))
    trace.__dict__.pop("_ops", None)
    for name in TRACE_READERS:
        assert reader(name)(run, trace) is None, name


def test_counter_readers_read_the_programs_series():
    prev = get_registry()
    reg = MetricRegistry()
    set_registry(reg)
    try:
        assert reader("granite_state_gb_per_step")({}, None) is None
        assert reader("granite_kv_gb_per_step")({"shapes": SHAPES},
                                                None) is None
        by = {"program": "decode"}
        reg.counter("serve_hybrid_steps_total", labels=by).inc(10)
        reg.counter("serve_hybrid_live_slots_total", labels=by).inc(960)
        reg.counter("serve_hybrid_state_bytes_total", labels=by).inc(7.3e10)
        reg.counter("serve_hybrid_steps_total",
                    labels={"program": "prefill"}).inc(4)
        reg.counter("serve_kv_rows_read_total",
                    labels={"program": "decode", "kind": "full"}).inc(2e6)
        assert reader("granite_state_gb_per_step")({}, None) == \
            pytest.approx(7.3)
        assert reader("granite_kv_gb_per_step")({"shapes": SHAPES}, None) \
            == pytest.approx(2e6 * 4096 / 10 / 1e9)
        assert gr.counters("prefill")["steps"] == 4
    finally:
        set_registry(prev)


def test_refill_share_is_the_steps_a_prefill_phase_ended_in():
    fresh = SpanLog()
    prev = set_span_log(fresh)
    try:
        run = {"kind": "serve", "t0": 0.0, "t1": 10.0}
        assert reader("granite_refill_share_pct")(run, None) is None
        # four worked steps of 1, 1, 3 and 1 s and an idle one; a prefill
        # phase ends inside the third
        for i, (a, b, attrs) in enumerate([
                (0.0, 1.0, None), (1.0, 2.0, None), (2.0, 5.0, None),
                (5.0, 6.0, None), (6.0, 9.0, {"idle": True})]):
            fresh.record("serve:step", a, b, key=i, attrs=attrs)
        assert reader("granite_refill_share_pct")(run, None) == 0.0
        fresh.record("serve:prefill", 2.1, 4.5, key=77)
        assert reader("granite_refill_share_pct")(run, None) == \
            pytest.approx(100.0 * 3 / 6)
        # one that ended before the window opened is no step of it
        fresh.record("serve:prefill", -3.0, -1.0, key=78)
        assert reader("granite_refill_share_pct")(run, None) == \
            pytest.approx(50.0)
        assert reader("granite_refill_share_pct")({"kind": "train"},
                                                  None) is None
    finally:
        set_span_log(prev)
