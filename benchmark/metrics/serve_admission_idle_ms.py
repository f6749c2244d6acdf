"""How long the host left the device's queue EMPTY for an admitted
request: over the measured window's worked ``serve:step`` spans that
admitted one, the seconds of each that no ``serve:program`` record
covers, summed, over the requests they admitted, in milliseconds (the
program's span log)."""

from benchmark.lib import program_queue as pq


@pq.guarded
def read(run, trace):
    return pq.admission_idle_ms(run)
