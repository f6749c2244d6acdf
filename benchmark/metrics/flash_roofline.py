"""The flash-attention kernel's share of its roofline in the training
step, forward and backward together: the least time the chip could take
for the calls' FLOPs and bytes (from shapes: causal, the step's rows per
chip) over the device time of every Pallas kernel call in the traced
steps. Worst chip. The training step holds no other Pallas kernel."""

from benchmark.lib import flops


def read(run, trace):
    if trace is None or run["kind"] != "train":
        return None
    s = run["shapes"]
    rows = run["rows"] // run["chips"]            # sequences per chip a step
    need = s["n_layer"] * flops.roofline_seconds(
        flops.flash_flops(rows, s["n_head"], run["seq"], run["seq"],
                          s["head_dim"], causal=True, backward=True),
        flops.flash_bytes(rows, s["n_head"], run["seq"], run["seq"],
                          s["head_dim"], s["itemsize"], backward=True),
        run["peaks"])
    worst = max(sum(e - b for _, b, e in d.kernels())
                for d in trace.devices)
    return 100.0 * need * run["trace_steps"] / worst if worst > 0 else None
