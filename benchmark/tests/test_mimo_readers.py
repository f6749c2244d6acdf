"""CPU tests of MiMo-V2's readers (``lib/mimo_readers.py``,
``lib/flops_mimo.py`` and the seven metric files: six listed, one not)
on a hand-made trace with a hand-made scope table and a private
registry: what each reads, that a share of a roofline is the bytes or
operations the algorithm needs (at the REAL bytes of a row of each kind)
over the time the kernel took, and that a program without the scopes,
counters or sizes reads nothing. Counts and identities only.

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))

from benchmark.lib import (flops_mimo, harness,  # noqa: E402
                           mimo_readers as mm, program_spans as ps,
                           trace_reduce as tr)
from deepspeed_tpu.telemetry import (MetricRegistry,  # noqa: E402
                                     get_registry, set_registry)

CELL = "serve-mimo-v2-flash-ep32-reasoning-batch"
NEW = ("mimo_decode_full_attn_ms", "mimo_decode_window_attn_ms",
       "mimo_decode_moe_ms", "mimo_full_decode_roofline",
       "mimo_window_decode_roofline", "mimo_kv_gb_per_step")
# a reader the contract does not list: no prefill program runs inside a
# backlog cell's traced window
UNLISTED = ("mimo_window_flash_roofline",)
TRACE_READERS = NEW[:5] + UNLISTED
PEAKS = {"hbm_bytes_per_s": 8e11, "bf16_flops": 2e14}
SHAPES = {"hidden": 4096, "layers": 11, "expert_ffn": 2048, "top_k": 8,
          "full_layers": 3, "window_layers": 9, "window": 128, "heads": 64,
          "full_kv_heads": 4, "window_kv_heads": 8, "head_dim": 192,
          "v_head_dim": 128, "itemsize": 2}

DECODE_TABLE = {
    "fusion.1": "attn_full", "fusion.2": "attn_full/kv_write",
    "paged_decode_attention.3": "attn_full",
    "fusion.4": "attn_window/ln", "fusion.5": "attn_window/kv_write",
    "paged_window_decode_attention.6": "attn_window",
    "fusion.7": "moe_router", "fusion.8": "moe_dispatch",
    "held_experts_grouped_matmul.9": "moe_experts",
    "fusion.10": "moe_combine", "fusion.11": "dense_ffn",
    "fusion.12": "lm_head"}
PREFILL_TABLE = {"flash_attention_window_fwd.1": "attn_window",
                 "flash_attention_fwd.2": "attn_full",
                 "fusion.3": "attn_window"}


def reader(name):
    return harness.load_reader(name)


def op(name, opcode, start, end):
    return (f"%{name} = f32[8]{{0}} {opcode}(%x)", start, end)


def decode_step(t):
    """One decode execution from ``t`` (seconds): full attention 1 + 0.5
    + 2 ms, window attention 1 + 0.5 + 4 ms, expert layer 1 + 0.5 + 6 +
    0.5 ms, dense FFN 1 ms, head 1 ms."""
    ms = 1e-3
    cuts = [("fusion.1", "fusion", 1), ("fusion.2", "fusion", 0.5),
            ("paged_decode_attention.3", "custom-call", 2),
            ("fusion.4", "fusion", 1), ("fusion.5", "fusion", 0.5),
            ("paged_window_decode_attention.6", "custom-call", 4),
            ("fusion.7", "fusion", 1), ("fusion.8", "fusion", 0.5),
            ("held_experts_grouped_matmul.9", "custom-call", 6),
            ("fusion.10", "fusion", 0.5), ("fusion.11", "fusion", 1),
            ("fusion.12", "fusion", 1)]
    ops, at = [], t
    for name, opcode, dur in cuts:
        ops.append(op(name, opcode, at, at + dur * ms))
        at += dur * ms
    return ("jit_serve_decode(3)", t, at), ops


def prefill_run(t, window_ms):
    ms = 1e-3
    end = t + (window_ms + 3) * ms
    return ("jit_serve_prefill(5)", t, end), [
        op("fusion.3", "fusion", t, t + ms),
        op("flash_attention_window_fwd.1", "custom-call", t + ms,
           t + (1 + window_ms) * ms),
        op("flash_attention_fwd.2", "custom-call",
           t + (1 + window_ms) * ms, end)]


@pytest.fixture()
def traced(monkeypatch):
    """Three decode executions and two prefills on chip 0, the tables
    that name their instructions, the host's records beside them and the
    program's counters in a private registry."""
    tables = {"serve_decode": (DECODE_TABLE, {
        "paged_decode_attention.3": "paged_decode_attention",
        "paged_window_decode_attention.6": "paged_window_decode_attention",
        "held_experts_grouped_matmul.9": "held_experts_grouped_matmul"}),
        "serve_prefill": (PREFILL_TABLE, {
            "flash_attention_window_fwd.1": "flash_attention_window_fwd",
            "flash_attention_fwd.2": "flash_attention_fwd"})}
    monkeypatch.setattr(ps, "tables",
                        lambda program: tables.get(program, ({}, {})))
    mods, ops = [], []
    for t in (0.0, 0.1, 0.2):
        m, o = decode_step(t)
        mods.append(m)
        ops += o
    for t, window_ms in ((0.3, 2.0), (0.4, 6.0)):
        m, o = prefill_run(t, window_ms)
        mods.append(m)
        ops += o
    trace = tr.Reduced({0: {"modules": mods, "ops": ops}}, [],
                       window=(-1.0, 1.0))
    run = {"kind": "serve", "shapes": SHAPES, "peaks": PEAKS,
           "trace_t0": -1.0, "trace_t1": 1.0,
           "steps": [(t, t + 0.02, 96, 300000) for t in (0.0, 0.1, 0.2)],
           "admissions": [(0.3, 1000), (0.4, 4000)]}
    prev = get_registry()
    reg = MetricRegistry()
    set_registry(reg)
    by = {"program": "decode"}
    # 100 steps of 96 live slots, contexts of 3125 on average
    reg.counter("serve_kv_rows_read_total",
                labels=dict(by, kind="full")).inc(3 * 96 * 3125 * 100)
    reg.counter("serve_kv_rows_read_total",
                labels=dict(by, kind="window")).inc(9 * 96 * 128 * 100)
    reg.counter("serve_moe_layer_calls_total", labels=by).inc(11 * 100)
    reg.counter("serve_moe_tokens_routed_total", labels=by).inc(
        11 * 96 * 100)
    reg.counter("serve_decode_steps_total").inc(100)
    yield run, trace
    set_registry(prev)


def test_the_contract_names_each_new_reader_and_its_cell():
    contract = harness.load_contract()
    by = {m["name"]: m for m in contract["per_layer"]}
    assert set(NEW) <= set(by)
    for name in NEW:
        assert by[name]["workloads"] == [CELL]
        assert by[name]["moves"] == "serve_out_tokens_per_s"
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py"))
    for name in ("mimo_full_decode_roofline", "mimo_window_decode_roofline"):
        assert (by[name]["unit"], by[name]["source"]) == ("%",
                                                          "device_trace")
    for name in UNLISTED:
        assert name not in by
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py"))


def test_scope_groups_of_one_decode_execution(traced):
    run, trace = traced
    assert reader("mimo_decode_full_attn_ms")(run, trace) == \
        pytest.approx(3.5)
    assert reader("mimo_decode_window_attn_ms")(run, trace) == \
        pytest.approx(5.5)
    assert reader("mimo_decode_moe_ms")(run, trace) == pytest.approx(8.0)
    assert reader("mimo_decode_moe_ms")(run, None) is None


def test_a_row_is_its_kinds_real_bytes():
    """A full layer's row is 4 heads of 192 + 128 lanes, a window
    layer's 8 heads: 2560 and 5120 B in bfloat16, not ``2 KH D``."""
    assert flops_mimo.row_bytes(4, 192, 128) == 2560
    assert flops_mimo.row_bytes(8, 192, 128) == 5120
    assert mm.row_bytes(SHAPES, "full") == 2560
    assert mm.row_bytes(SHAPES, "window") == 5120
    assert flops_mimo.decode_read_bytes(1000, 8, 192, 128) == 5120000
    assert flops_mimo.seen_pairs(4096, 128) == 128 * 129 / 2 + 3968 * 128
    assert flops_mimo.seen_pairs(100, 128) == 100 * 101 / 2
    assert flops_mimo.prefill_attention_flops(4096, 64, 192, 128, 128) == \
        2 * 320 * 64 * flops_mimo.seen_pairs(4096, 128)
    assert flops_mimo.prefill_attention_bytes(1000, 64, 8, 192, 128) == \
        1000 * 2 * (64 * 320 + 8 * 320)


def test_decode_rooflines_are_live_rows_over_the_kernels_time(traced):
    """Full: 300,000 live positions a step x 2560 B x a call, over 2 ms
    a call. Window: 128 rows a live slot (the counter's) x 96 slots x
    5120 B over 4 ms a call: a kernel that reads both ring blocks reads
    the same bytes NEEDED, in more time."""
    run, trace = traced
    full = reader("mimo_full_decode_roofline")(run, trace)
    assert full == pytest.approx(
        100.0 * 300000 * 2560 / PEAKS["hbm_bytes_per_s"] / 2e-3)
    window = reader("mimo_window_decode_roofline")(run, trace)
    assert window == pytest.approx(
        100.0 * 96 * 128 * 5120 / PEAKS["hbm_bytes_per_s"] / 4e-3)
    assert 0 < window < full < 100


def test_window_flash_roofline_is_the_windows_work_over_the_kernel(traced):
    run, trace = traced
    need = sum(9 * max(
        flops_mimo.prefill_attention_flops(p, 64, 192, 128, 128)
        / PEAKS["bf16_flops"],
        flops_mimo.prefill_attention_bytes(p, 64, 8, 192, 128)
        / PEAKS["hbm_bytes_per_s"]) for p in (1000, 4000))
    got = reader("mimo_window_flash_roofline")(run, trace)
    assert got == pytest.approx(100.0 * need / 8e-3)
    assert 0 < got < 100


def test_kv_gb_per_step_is_rows_times_the_real_row(traced):
    run, _ = traced
    got = reader("mimo_kv_gb_per_step")(run, None)
    assert got == pytest.approx(
        (3 * 96 * 3125 * 2560 + 9 * 96 * 128 * 5120) / 1e9)


def test_readers_read_nothing_without_their_scopes_or_sizes(traced,
                                                            monkeypatch):
    """Another family's shapes (Laguna's keys: one head count, one
    width), another model's program, or no counters: nothing is read,
    and nothing raises."""
    run, trace = traced
    other = dict(run, shapes={"kv_heads": 8, "head_dim": 128, "itemsize": 2,
                              "full_layers": 3, "window_layers": 9})
    for name in NEW + UNLISTED:
        assert reader(name)(other, trace) is None, name
        assert reader(name)({"kind": "train"}, None) is None, name
    monkeypatch.setattr(ps, "tables", lambda program: (
        {"fusion.1": "mlp", "fusion.3": "attn_kernel"}, {}))
    monkeypatch.setattr(ps, "known_scopes", lambda: frozenset({"mlp"}))
    trace.__dict__.pop("_ops", None)
    for name in NEW[:3]:        # the kernels are still found by name
        assert reader(name)(run, trace) is None, name
    set_registry(MetricRegistry())
    assert reader("mimo_kv_gb_per_step")(run, None) is None
    assert mm.decode_roofline(run, trace, "window") is None
