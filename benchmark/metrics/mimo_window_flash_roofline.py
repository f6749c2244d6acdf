"""The sink-window flash forward's share of its roofline in MiMo-V2's
prefill: the least time for the windowed attention (keys 192, values 128
wide, a window of 128, 64 query heads over 8 key/value heads) of the
prompts admitted in the traced window at their real lengths, all window
layers, over the device time of the kernel NAMED
``flash_attention_window_fwd`` in the prefill programs. NOT listed in
``BENCHMARK.json``: no prefill runs inside a backlog cell's traced
window (PERF.md section 7), and a listed metric that is not printed
refuses a PR; a scratch run that traces a prefill reads it."""

from benchmark.lib import mimo_readers as mm


def read(run, trace):
    return mm.window_flash_roofline(run, trace)
