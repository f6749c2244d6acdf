"""CPU test of ``metrics/train_recompute_ms.py`` on a hand-made
timeline: the self time of the instructions the pass table marks
``recompute``, per traced step, on the chip where it is largest; 0.0 for
a step that repeats nothing; nothing without a pass table (an older
program) or outside a train cell. Not tier-1.

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))

from benchmark.lib import (harness, movement_readers as mr,  # noqa: E402
                           program_spans as ps, trace_reduce as tr)

NAME = "train_recompute_ms"
RUN = {"kind": "train", "trace_steps": 2, "chips": 4}


def reduced(chips):
    events = {k: {"modules": [(f"jit_train_step({k})", 0.0, 1.0)],
                  "ops": [(f"%{n} = f32[8]{{0}} fusion(%x)", s, e)
                          for n, s, e in ops]}
              for k, ops in enumerate(chips)}
    return tr.Reduced(events, [(tr.WINDOW_SPAN, 0.0, 1.0)])


@pytest.fixture()
def passes(monkeypatch):
    held = {}
    monkeypatch.setattr(mr, "pass_table", lambda program=mr.PROGRAM: held)
    monkeypatch.setattr(ps, "tables", lambda program: ({}, {}))
    return held


def read(run, trace):
    return harness.load_reader(NAME)(run, trace)


def test_the_worst_chips_recompute_time_a_step(passes):
    passes.update({"fusion.1": "fwd", "fusion.1.remat2": "recompute",
                   "fusion.9": "recompute", "fusion.2": "bwd"})
    chip = [("fusion.1", 0.0, 0.2), ("fusion.1.remat2", 0.2, 0.3),
            ("fusion.2", 0.3, 0.6)]
    worst = chip + [("fusion.9", 0.6, 0.9)]     # rematted_computation
    trace = reduced([chip, worst, chip, chip])
    # (0.1 + 0.3) s over two traced steps, on the second chip
    assert read(RUN, trace) == pytest.approx(200.0)


def test_a_step_that_repeats_nothing_reads_zero_and_an_old_program_nothing(
        passes):
    trace = reduced([[("fusion.1", 0.0, 0.2)]] * 4)
    assert read(RUN, trace) is None                 # no pass table
    passes["fusion.1"] = "fwd"
    assert read(RUN, trace) == 0.0
    assert read(dict(RUN, kind="serve"), trace) is None
    assert read(RUN, None) is None


def test_the_contract_lists_it_in_both_train_cells():
    contract = harness.load_contract()
    entry = harness.find(contract["per_layer"], NAME, "metric")
    assert entry["moves"] == "train_tokens_per_s_per_chip"
    assert entry["workloads"] == ["train-gpt2-1.3b-offload",
                                  "train-gpt2-1.3b-zero3-x4"]
