"""CPU tests of ``metrics/serve_pipelined_steps_pct.py`` against the
span log recorded on a TPU v5 lite (``testdata/tiny_named_trace.json``:
12 ``serve:step`` spans in its window, all lagged) and copies of it
with the ``pipelined`` attribute rewritten. Counts only.

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))

from benchmark.lib import harness, program_spans as ps  # noqa: E402
from deepspeed_tpu.telemetry.spans import SpanLog, set_span_log  # noqa: E402

NAME = "serve_pipelined_steps_pct"
CELLS = ("serve-gpt2-1.3b-batch", "serve-longcat-flash-ep32-decode-batch")


@pytest.fixture()
def recorded():
    with open(os.path.join(BENCH, "testdata",
                           "tiny_named_trace.json")) as fh:
        data = json.load(fh)
    return [tuple(r) for r in data["spans"]], dict(data["run"])


def _with_log(spans, pipelined=None):
    """A fresh span log holding ``spans``; ``pipelined(i)`` rewrites the
    attribute of the i-th ``serve:step`` of the window's steps."""
    out, i = [], 0
    for r in spans:
        if r[ps.NAME] == ps.STEP and pipelined is not None:
            r = r[:ps.ATTRS] + (dict(r[ps.ATTRS] or {},
                                     pipelined=pipelined(i)),)
            i += 1
        out.append(r)
    log = SpanLog()
    log.extend(out)
    return log


def _read(log, run):
    prev = set_span_log(log)
    try:
        units = harness.units_of(harness.load_contract())
        return harness.read_metrics([NAME], run, None, units)
    finally:
        set_span_log(prev)


def test_the_contract_lists_the_reader_in_the_two_backlog_cells():
    contract = harness.load_contract()
    entry = harness.find(contract["per_layer"], NAME, "metric")
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter",
        "layer": "server host loop (inference/server.py, scheduler.py)",
        "moves": "serve_out_tokens_per_s", "workloads": list(CELLS)}
    assert contract["per_layer"][-1] is entry        # appended, last
    for cell in contract["workloads"]:
        listed = harness.resolve_cell(contract, cell["name"])["per_layer"]
        assert (NAME in listed) == (cell["name"] in CELLS)
        if cell["name"] in CELLS:      # it reports what the metric moves
            assert entry["moves"] in harness.resolve_cell(
                contract, cell["name"])["end_to_end"]


def test_the_recorded_log_reads_every_step_lagged(recorded):
    spans, run = recorded
    steps = ps.window_steps(spans, run["t0"], run["t1"])
    assert len(steps) == 12 and all(s[ps.ATTRS]["pipelined"] for s in steps)
    assert _read(_with_log(spans), run) == {
        NAME: {"value": 100.0, "unit": "%"}}


def test_an_all_lag0_log_reads_zero_printed_and_not_left_out(recorded):
    spans, run = recorded
    got = _read(_with_log(spans, lambda i: False), run)
    assert got == {NAME: {"value": 0.0, "unit": "%"}}
    # a program that never set the attribute (every step committed what
    # it dispatched) reads the same
    bare = [r[:ps.ATTRS] + ({k: v for k, v in (r[ps.ATTRS] or {}).items()
                             if k != "pipelined"},)
            if r[ps.NAME] == ps.STEP else r for r in spans]
    assert _read(_with_log(bare), run) == got


@pytest.mark.parametrize("every", [2, 3, 4])
def test_a_mixed_log_reads_the_share_of_the_windows_steps(recorded, every):
    spans, run = recorded
    log = _with_log(spans, lambda i: i % every != 0)
    steps = ps.window_steps(log.snapshot(), run["t0"], run["t1"])
    lagged = sum(1 for s in steps if s[ps.ATTRS]["pipelined"])
    assert 0 < lagged < len(steps) == 12
    got = _read(log, run)[NAME]["value"]
    assert got == pytest.approx(100.0 * lagged / 12)
    # idle polls and steps outside the window are not steps of it
    extra = SpanLog()
    extra.extend(log.snapshot())
    extra.record(ps.STEP, run["t0"], run["t0"] + 1e-4,
                 attrs={"idle": True, "pipelined": False})
    extra.record(ps.STEP, run["t1"] + 1.0, run["t1"] + 1.1,
                 attrs={"pipelined": False})
    assert _read(extra, run)[NAME]["value"] == got


def test_nothing_to_read_is_nothing_reported(recorded, monkeypatch):
    spans, run = recorded
    assert _read(_with_log(spans), {"kind": "train"}) == {}
    assert _read(SpanLog(), run) == {}                     # an empty log
    assert _read(_with_log(spans), dict(run, t0=0.0, t1=1.0)) == {}
    # a program with no span log at all: nothing raises
    monkeypatch.setattr(ps, "span_records", lambda prefix=None: None)
    assert _read(_with_log(spans), run) == {}
