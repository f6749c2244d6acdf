"""Bytes the train step's collectives move over the wire a chip a step
(movement table: every collective row, ``fused`` ones included, host
link left out). Says from the table alone whether the step exchanges
parameters (ZeRO-3: 5.8 GB at GPT-2 1.3B over ``fsdp=4``) or
activations (30.5)."""

from benchmark.lib import exchange_readers


def read(run, trace):
    return exchange_readers.wire_gb_per_step(run, trace)
