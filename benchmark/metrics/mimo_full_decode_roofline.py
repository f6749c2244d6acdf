"""The paged decode kernel's share of its roofline on MiMo-V2's FULL
layers: the bytes of the cached rows of every live position that its
calls had to read (4 heads x (192 + 128) lanes a row; decode attention
is bound by that read), over the peak bandwidth, over the device time of
the kernel NAMED ``paged_decode_attention`` in the decode programs of
the traced window. Live positions are the host's step records of the
traced window."""

from benchmark.lib import mimo_readers as mm


def read(run, trace):
    return mm.decode_roofline(run, trace, "full")
