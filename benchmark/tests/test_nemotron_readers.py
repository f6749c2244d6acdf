"""CPU tests of the Nemotron-H cell's readers (``lib/flops_nemotron.py``
and the six metric files) on a hand-made trace with a hand-made scope
table and a private registry: what each reads, that an expert's bytes
are TWO matrices at the published width whatever the storage, and that
a program without the scopes or counters reads nothing. Counts and
identities only.

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))

from benchmark.lib import (flops_longcat, flops_nemotron,  # noqa: E402
                           harness, program_spans as ps, trace_reduce as tr)
from deepspeed_tpu.telemetry import (MetricRegistry,  # noqa: E402
                                     get_registry, set_registry)

CELL = "serve-nemotron3-nano-ep2-reasoning-batch"
NEW = ("nemotron_decode_mamba_ms", "nemotron_decode_attn_ms",
       "nemotron_decode_moe_ms", "nemotron_state_update_roofline",
       "nemotron_state_gb_per_step", "nemotron_kv_gb_per_step")
TRACE_READERS = NEW[:4]
PEAKS = {"hbm_bytes_per_s": 8e11, "bf16_flops": 2e14}
MODEL = {"hybrid_override_pattern": "MEMEM*EMEMEM*", "hidden_size": 2688,
         "mamba_num_heads": 64, "mamba_head_dim": 64, "ssm_state_size": 128,
         "chunk_size": 128, "moe_intermediate_size": 1856,
         "num_experts_per_tok": 6, "num_key_value_heads": 2, "head_dim": 128,
         "dtype": "bfloat16", "state_dtype": "float32"}

DECODE_TABLE = {
    "fusion.1": "mamba_in", "fusion.2": "mamba_conv",
    "fusion.3": "mamba_state", "fusion.5": "mamba_out/ln",
    "fusion.6": "attn_full/kv_write",
    "paged_decode_attention.7": "attn_full", "fusion.8": "moe_router",
    "fusion.9": "moe_shared", "held_experts_grouped_matmul.10": "moe_experts",
    "held_experts_grouped_matmul.12": "moe_experts",
    "fusion.11": "lm_head"}


def reader(name):
    return harness.load_reader(name)


def shapes():
    return harness.load_family("nemotron_h").shapes(MODEL)


def op(name, opcode, start, end):
    return (f"%{name} = f32[8]{{0}} {opcode}(%x)", start, end)


def decode_step(t):
    """One decode execution from ``t`` (seconds): mixers 1 + 1 + 4 + 1
    ms, attention 0.5 + 1.5 ms, router + shared 1 + 1 ms, the two
    grouped matmuls 2 + 2 ms, head 1 ms."""
    ms = 1e-3
    cuts = [("fusion.1", "fusion", 1), ("fusion.2", "fusion", 1),
            ("fusion.3", "fusion", 4), ("fusion.5", "fusion", 1),
            ("fusion.6", "fusion", 0.5),
            ("paged_decode_attention.7", "custom-call", 1.5),
            ("fusion.8", "fusion", 1), ("fusion.9", "fusion", 1),
            ("held_experts_grouped_matmul.10", "custom-call", 2),
            ("held_experts_grouped_matmul.12", "custom-call", 2),
            ("fusion.11", "fusion", 1)]
    ops, at = [], t
    for name, opcode, dur in cuts:
        ops.append(op(name, opcode, at, at + dur * ms))
        at += dur * ms
    return ("jit_serve_decode(3)", t, at), ops


@pytest.fixture()
def traced(monkeypatch):
    """Three decode executions on chip 0 and the table that names their
    instructions."""
    tables = {"serve_decode": (DECODE_TABLE, {
        "paged_decode_attention.7": "paged_decode_attention",
        "held_experts_grouped_matmul.10": "held_experts_grouped_matmul",
        "held_experts_grouped_matmul.12": "held_experts_grouped_matmul"})}
    monkeypatch.setattr(ps, "tables",
                        lambda program: tables.get(program, ({}, {})))
    mods, ops = [], []
    for t in (0.0, 0.1, 0.2):
        m, o = decode_step(t)
        mods.append(m)
        ops += o
    trace = tr.Reduced({0: {"modules": mods, "ops": ops}}, [],
                       window=(-1.0, 1.0))
    run = {"kind": "serve", "shapes": shapes(), "peaks": PEAKS,
           "trace_t0": -1.0, "trace_t1": 1.0,
           "steps": [(t, t + 0.02, 256, 0) for t in (0.0, 0.1, 0.2)]}
    return run, trace


def test_the_contract_names_each_new_reader_and_its_cell():
    contract = harness.load_contract()
    by = {m["name"]: m for m in contract["per_layer"]}
    assert set(NEW) <= set(by)
    for name in NEW:
        assert by[name]["workloads"] == [CELL]
        assert by[name]["moves"] == "serve_out_tokens_per_s"
        assert by[name]["layer"] == \
            "model step (model_implementations/nemotron_h.py)"
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py"))
    # the held experts' share of their roofline waits for counters that
    # can be read over the traced window (PERF.md section 7)
    assert "nemotron_experts_roofline" not in by
    # the accepted state-update reader under this cell's own name (an
    # accepted test pins the accepted metric's list to the Granite cell)
    assert CELL not in by["mamba_state_update_roofline"]["workloads"]
    assert by["nemotron_state_update_roofline"]["unit"] == "%"
    # three matrices an expert would count 1.5 x this model's bytes
    assert CELL not in by["moe_experts_roofline"]["workloads"]
    cell = next(w for w in contract["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (
        1, "nemotron-reasoning-decode-batch")


def test_shapes_are_what_the_shared_readers_divide_by():
    s = shapes()
    assert (s["layers"], s["state_layers"]) == (5, 6)
    assert s["state_bytes"] == 64 * 64 * 128 * 4
    assert (s["hidden"], s["expert_ffn"], s["top_k"]) == (2688, 1856, 6)
    assert (s["kv_heads"], s["head_dim"], s["itemsize"]) == (2, 128, 2)


def test_scope_groups_of_one_decode_execution(traced):
    run, trace = traced
    assert reader("nemotron_decode_mamba_ms")(run, trace) == \
        pytest.approx(7.0)
    assert reader("nemotron_decode_attn_ms")(run, trace) == \
        pytest.approx(2.0)
    assert reader("nemotron_decode_moe_ms")(run, trace) == pytest.approx(6.0)
    assert reader("nemotron_decode_mamba_ms")(run, None) is None


def test_state_update_roofline_is_the_accepted_readers_number(traced):
    """256 live slots x 6 layers x 2.1 MB once in and once out, over the
    4 ms a step the scope took: what ``mamba_state_update_roofline``
    reads of the same run."""
    run, trace = traced
    s = run["shapes"]
    need = 6 * 2 * 256 * s["state_bytes"] / PEAKS["hbm_bytes_per_s"]
    got = reader("nemotron_state_update_roofline")(run, trace)
    assert got == pytest.approx(100.0 * need / 4e-3)
    assert got == reader("mamba_state_update_roofline")(run, trace)
    assert reader("nemotron_state_update_roofline")(run, None) is None


def test_an_experts_bytes_are_two_matrices_at_the_published_width():
    """What ``flops_nemotron`` counts for an ungated expert: two matrices
    of 2688 x 1856 bfloat16 whatever the storage (1920 wide on the chip),
    4 FLOPs a weight a pick; 62 experts hit at 12 rows each are bound by
    their bytes, a million picks by their FLOPs."""
    assert flops_nemotron.expert_weight_bytes(2688, 1856) == \
        2 * 2688 * 1856 * 2
    assert flops_nemotron.expert_flops_per_pick(2688, 1856) == \
        4 * 2688 * 1856
    # two thirds of what a gated expert of the same width would count
    assert 3 * flops_nemotron.expert_weight_bytes(2688, 1856) == \
        2 * flops_longcat.expert_weight_bytes(2688, 1856)
    assert flops_nemotron.experts_seconds(62, 768, 2688, 1856, 2, PEAKS) == \
        62 * 2 * 2688 * 1856 * 2 / PEAKS["hbm_bytes_per_s"]
    assert flops_nemotron.experts_seconds(64, 1e6, 2688, 1856, 2, PEAKS) == \
        1e6 * 4 * 2688 * 1856 / PEAKS["bf16_flops"]


def test_readers_read_nothing_without_their_scopes(traced, monkeypatch):
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
        run = {"shapes": shapes()}
        assert reader("nemotron_state_gb_per_step")(run, None) is None
        assert reader("nemotron_kv_gb_per_step")(run, None) is None
        by = {"program": "decode"}
        reg.counter("serve_hybrid_steps_total", labels=by).inc(10)
        reg.counter("serve_hybrid_state_bytes_total", labels=by).inc(6.5e10)
        reg.counter("serve_kv_rows_read_total",
                    labels={"program": "decode", "kind": "full"}).inc(1e7)
        assert reader("nemotron_state_gb_per_step")(run, None) == \
            pytest.approx(6.5)
        # a row: K and V of 2 heads of 128, bfloat16
        assert reader("nemotron_kv_gb_per_step")(run, None) == \
            pytest.approx(1e7 * 1024 / 10 / 1e9)
        assert reader("nemotron_kv_gb_per_step")({}, None) is None
    finally:
        set_registry(prev)
