"""CPU tests of ``metrics/longcat_admission_step_ms.py`` and
``metrics/longcat_rider_admissions_pct.py`` against the span log
recorded on a TPU v5 lite (``testdata/tiny_named_trace.json``: 12
``serve:step`` spans in its window, all pipelined, none admitted
anything) and copies of it with the ``admitted`` / ``rider`` /
``pipelined`` attributes rewritten. Counts and the recorded spans' own
walls only.

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

STEP_MS = "longcat_admission_step_ms"
RIDERS = "longcat_rider_admissions_pct"
CELL = "serve-longcat-flash-ep32-decode-batch"


@pytest.fixture()
def recorded():
    with open(os.path.join(BENCH, "testdata",
                           "tiny_named_trace.json")) as fh:
        data = json.load(fh)
    return [tuple(r) for r in data["spans"]], dict(data["run"])


def _with_log(spans, rewrite=None):
    """A fresh span log holding ``spans``; ``rewrite(i, attrs)`` gives
    the attributes of the i-th ``serve:step``."""
    out, i = [], 0
    for r in spans:
        if r[ps.NAME] == ps.STEP and rewrite is not None:
            r = r[:ps.ATTRS] + (rewrite(i, dict(r[ps.ATTRS] or {})),)
            i += 1
        out.append(r)
    log = SpanLog()
    log.extend(out)
    return log


def _read(log, run):
    prev = set_span_log(log)
    try:
        units = harness.units_of(harness.load_contract())
        return {k: v["value"] for k, v in harness.read_metrics(
            [STEP_MS, RIDERS], run, None, units).items()}
    finally:
        set_span_log(prev)


@pytest.mark.parametrize("name,unit,better", [
    (STEP_MS, "ms", "lower"), (RIDERS, "%", "higher")])
def test_the_contract_lists_the_readers_in_the_longcat_cell(name, unit,
                                                            better):
    contract = harness.load_contract()
    entry = harness.find(contract["per_layer"], name, "metric")
    assert entry == {
        "name": name, "unit": unit, "better": better,
        "source": "program_span",
        "layer": "server host loop (inference/server.py, scheduler.py)",
        "moves": "serve_out_tokens_per_s", "workloads": [CELL]}
    for cell in contract["workloads"]:
        listed = harness.resolve_cell(contract, cell["name"])["per_layer"]
        assert (name in listed) == (cell["name"] == CELL)
    assert entry["moves"] in harness.resolve_cell(
        contract, CELL)["end_to_end"]


def test_a_window_that_admitted_nothing_reports_nothing(recorded):
    spans, run = recorded
    assert _read(_with_log(spans), run) == {}
    assert _read(_with_log(spans), {"kind": "train"}) == {}
    assert _read(SpanLog(), run) == {}


@pytest.mark.parametrize("riders", ["all", "none", "some"])
def test_what_an_admission_adds_to_its_step_and_the_riders_share(
        recorded, riders):
    """Every third step of the window admits at lag 0 (one request, or
    two where it ran no rider) and the others are pipelined: the wall of
    the admitting steps less a pipelined step's mean wall each, over the
    requests, and of the requests the share a rider step admitted."""
    spans, run = recorded

    def rewrite(i, attrs):
        rode = i % 3 == 0 and (riders == "all"
                               or (riders == "some" and i % 2 == 0))
        return dict(attrs, rider=rode, pipelined=bool(i % 3),
                    admitted=0 if i % 3 else (1 if rode else 2))
    log = _with_log(spans, rewrite)
    window = ps.window_steps(log.snapshot(), run["t0"], run["t1"])
    steps = [s for s in window if s[ps.ATTRS]["admitted"]]
    plain = [s[ps.END] - s[ps.START] for s in window
             if not s[ps.ATTRS]["admitted"]]
    assert (len(steps), len(plain)) == (4, 8)
    admitted = sum(s[ps.ATTRS]["admitted"] for s in steps)
    rode = sum(s[ps.ATTRS]["admitted"] for s in steps
               if s[ps.ATTRS]["rider"])
    assert (admitted, rode) == {"all": (4, 4), "none": (8, 0),
                                "some": (6, 2)}[riders]
    got = _read(log, run)
    wall = sum(s[ps.END] - s[ps.START] for s in steps)
    assert got[STEP_MS] == pytest.approx(
        1e3 * (wall - 4 * sum(plain) / 8) / admitted)
    assert got[RIDERS] == pytest.approx(100.0 * rode / admitted)
    # idle polls and steps outside the window are not steps of it
    extra = SpanLog()
    extra.extend(log.snapshot())
    extra.record(ps.STEP, run["t0"], run["t0"] + 1e-4,
                 attrs={"idle": True, "admitted": 3, "rider": True})
    extra.record(ps.STEP, run["t1"] + 1.0, run["t1"] + 1.1,
                 attrs={"admitted": 3, "rider": True})
    extra.record(ps.STEP, run["t1"] + 2.0, run["t1"] + 2.5,
                 attrs={"admitted": 0, "pipelined": True})
    assert _read(extra, run) == got


def test_a_cheaper_refill_reads_lower_where_the_whole_step_does_not(
        recorded):
    """The same admitting steps, once admitting two requests each in a
    step twice as long (a prefill program beside the decode program) and
    once one each (the prompt rides): a request costs its step the same
    whole wall, and adds less to it."""
    spans, run = recorded
    log = _with_log(spans, lambda i, attrs: dict(
        attrs, pipelined=bool(i % 3), admitted=0 if i % 3 else 1))
    riding = _read(log, run)[STEP_MS]
    doubled = []
    for r in log.snapshot():
        if r[ps.NAME] == ps.STEP and r[ps.ATTRS]["admitted"]:
            r = (r[:ps.END] + (2 * r[ps.END] - r[ps.START],)
                 + r[ps.END + 1:ps.ATTRS]
                 + (dict(r[ps.ATTRS], admitted=2),))
        doubled.append(r)
    apart = SpanLog()
    apart.extend(doubled)
    # the doubled spans end later: keep them all inside the window
    wide = dict(run, t1=run["t1"] + 1.0)
    assert _read(apart, wide)[STEP_MS] > _read(log, wide)[STEP_MS]
    assert _read(log, wide)[STEP_MS] == pytest.approx(riding)


def test_a_program_whose_steps_say_nothing_of_riders(recorded, monkeypatch):
    """The parent's span log: steps admit, none has the attribute. The
    step time reads, the share is left out; a window with no pipelined
    step to compare with reads nothing; no span log, nothing raises."""
    spans, run = recorded
    log = _with_log(spans, lambda i, attrs: dict(
        attrs, admitted=i % 2, pipelined=not i % 2))
    got = _read(log, run)
    assert set(got) == {STEP_MS}
    lag0 = _with_log(spans, lambda i, attrs: dict(
        attrs, admitted=i % 2, pipelined=False))
    assert _read(lag0, run) == {}
    monkeypatch.setattr(ps, "span_records", lambda prefix=None: None)
    assert _read(log, run) == {}
