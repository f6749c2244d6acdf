"""Bytes the train step moves over the host link, both directions
(movement table), over the seconds at least one such transfer was in
flight: what the offload stream reaches while it is moving anything.
Read it against ``benchmark/tools/host_link_rate.py``'s three rates
(PERF.md section 5); no metric divides by them."""

from benchmark.lib import movement_readers


def read(run, trace):
    return movement_readers.metric(run, trace, "offload_link_gb_per_s")
