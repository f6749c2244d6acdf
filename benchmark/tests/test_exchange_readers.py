"""CPU tests of the exchange readers (``lib/exchange_readers.py``) on
the hand-made x4 timeline of ``test_movement_readers.py``: the wire
bytes are the table's own (pairs once, carriers never, host link left
out), the fused time is the ``movement`` line's on the same chip, and a
program without a movement table, or one chip, reads nothing. Counts
only; not tier-1.

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_movement_readers import (OFFLOAD_TABLE, X4_TABLE,  # noqa: E402,F401
                                   read, reduced, table, x4_step)

X4 = {"kind": "train", "trace_steps": 3, "chips": 4}


def x4_trace():
    chips = []
    for slow in (1, 2, 1, 1):               # chip 1 waits longest
        runs, ops = zip(*(x4_step(1.0 + k * 0.2, slow) for k in range(3)))
        chips.append((list(runs), [o for more in ops for o in more]))
    return reduced(chips, (1.0, 1.6))


def test_wire_bytes_and_fused_time_of_the_x4_table(table, capsys):
    table.update(X4_TABLE)
    table.update(OFFLOAD_TABLE)             # host-link rows: left out
    trace = x4_trace()
    # sync gather + the pair once + all-reduce + all-to-all + the fused
    # reduce-scatter, 1 GB each; not the done half, not the carrier
    assert read("exchange_wire_gb_per_step", X4, trace) == pytest.approx(5.0)
    assert read("exchange_wire_gb_per_step", X4, None) == pytest.approx(5.0)
    fused = read("exchange_fused_ms", X4, trace)
    assert fused == pytest.approx(8.0)
    read("gather_exposed_ms", X4, trace)    # logs the movement line
    line = [json.loads(ln)["movement"] for ln in
            capsys.readouterr().out.splitlines() if '"movement"' in ln][0]
    assert line["chip"] == 1
    assert fused == pytest.approx(sum(
        r["exposed_ms_per_step"] for r in line["by_kind_pass_scope"]
        if r["as"] == "fused"))


def test_a_table_without_fused_rows_reads_zero(table):
    table.update({k: v for k, v in X4_TABLE.items() if v["role"] != "fused"})
    assert read("exchange_fused_ms", X4, x4_trace()) == 0.0
    assert read("exchange_wire_gb_per_step", X4, None) == pytest.approx(4.0)


@pytest.mark.parametrize("name", ["exchange_wire_gb_per_step",
                                  "exchange_fused_ms"])
def test_nothing_to_read(table, name):
    trace = x4_trace()
    assert read(name, X4, trace) is None                # no table
    table.update(OFFLOAD_TABLE)
    assert read(name, X4, trace) is None                # no collective row
    table.update(X4_TABLE)
    assert read(name, dict(X4, chips=1), trace) is None
    assert read(name, {"kind": "serve", "chips": 4}, trace) is None
    if name == "exchange_fused_ms":
        assert read(name, X4, None) is None
