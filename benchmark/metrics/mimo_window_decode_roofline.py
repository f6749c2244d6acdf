"""The paged decode kernel's share of its roofline on MiMo-V2's WINDOW
layers: the bytes of the rows INSIDE the window of every live slot
(``min(context, window)`` rows of 8 heads x (192 + 128) lanes, from the
program's own counter, times the live slots of the traced steps), over
the peak bandwidth, over the device time of the kernel NAMED
``paged_window_decode_attention`` in the decode programs of the traced
window. A kernel that reads the ring's slack block reads low, not
high."""

from benchmark.lib import mimo_readers as mm


def read(run, trace):
    return mm.decode_roofline(run, trace, "window")
