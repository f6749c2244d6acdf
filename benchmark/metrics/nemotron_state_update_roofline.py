"""The decode state update's share of its roofline in THIS cell: the
accepted reader ``mamba_state_update_roofline`` as it is (every live
slot's state, 64 heads x 64 x 128 x 4 B an ``M`` layer, once in and once
out, all six state layers, over the peak bandwidth, over the device time
of the scope ``mamba_state``: 8 B/C groups do not change the count),
under a name of this cell's own: ``benchmark/tests/
test_granite_readers.py`` holds that metric's list of cells to the
Granite cell alone, and no file the benchmark already has is this PR's
to edit."""

from benchmark.lib import harness


def read(run, trace):
    return harness.load_reader("mamba_state_update_roofline")(run, trace)
