"""What the train step exchanges BETWEEN CHIPS, from the program's
movement table (``lib/movement_readers.py`` ``movement_table`` /
``chip``), ``fused`` rows included. Two metrics of the x4 cell:

  ``exchange_wire_gb_per_step``  the wire bytes of every collective row
      a chip a step (a pair and a synchronous instruction once, a
      carrier never, host-link rows left out): the table alone, no
      trace. ZeRO-3 over ``fsdp=4`` as written moves a layer's
      parameters twice and its gradients once, 5.8 GB at GPT-2 1.3B;
      a partitioner that exchanges activations instead moved 30.5.
  ``exchange_fused_ms``  core time a traced step inside the table's
      ``fused`` rows (a reduce-scatter the TPU writes as all-reduce +
      slice inside ``fusion.N``, which ``collective_exposed_pct`` and
      the three ``*_exposed_ms`` take for compute), on the chip whose
      core waited longest in what those count (the ``movement`` line's
      chip).

A program without a movement table, a run on one chip or a table with
no collective row reads None; nothing raises.
"""
from __future__ import annotations

from typing import Optional

from benchmark.lib import movement_readers as mr

HOST_LINK = (mr.FETCH, mr.STORE)


def _collective_rows(run: dict) -> dict:
    if run.get("kind") != "train" or run.get("chips", 1) < 2:
        return {}
    table = mr.movement_table()
    return table if any(r["kind"] not in HOST_LINK
                        for r in table.values()) else {}


def wire_gb_per_step(run: dict, trace) -> Optional[float]:
    table = _collective_rows(run)
    if not table:
        return None
    return sum(r["wire_bytes"] for r in table.values()
               if r["kind"] not in HOST_LINK
               and r["role"] not in ("done", "carrier")) / 1e9


def fused_ms(run: dict, trace) -> Optional[float]:
    if trace is None or not run.get("trace_steps"):
        return None
    table = _collective_rows(run)
    if not table:
        return None
    chips = [c for c in (mr.chip(trace, table, k)
                         for k in range(len(trace.devices))) if c]
    if not chips:
        return None
    worst = max(chips, key=lambda c: c["waited_s"])
    return 1e3 * sum(g["exposed_s"] for key, g in worst["groups"].items()
                     if key[3] == "fused") / run["trace_steps"]
