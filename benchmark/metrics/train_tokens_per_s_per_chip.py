"""Tokens of the whole optimizer steps between the window's two step
boundaries, over the seconds between them, per chip. Host clock; both
boundaries are read after the step's loss reached the host."""



def read(run, trace):
    if run["kind"] != "train":
        return None
    return (run["steps"] * run["tokens_per_step"]
            / run["window_s"] / run["chips"])
