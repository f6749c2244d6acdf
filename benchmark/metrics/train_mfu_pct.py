"""Model FLOP/s utilisation: forward + backward FLOPs from shapes (every
matmul incl. the LM head over the unpadded vocabulary, causal attention;
no recomputation, no position table) times tokens per second at the
median step, over chips times the bf16 peak."""

from statistics import median

from benchmark.lib import flops


def read(run, trace):
    if run["kind"] != "train":
        return None
    s = run["shapes"]
    per_token = flops.train_flops_per_token(
        s["n_embd"], s["n_layer"], s["ffn"], s["vocab"], run["seq"])
    rate = run["tokens_per_step"] / median(run["step_seconds"])
    return 100.0 * per_token * rate / (run["chips"]
                                       * run["peaks"]["bf16_flops"])
