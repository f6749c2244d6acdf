"""Output tokens stamped inside the window (whether or not their request
finished in it) over the window's seconds. Host clock at step
boundaries."""



def read(run, trace):
    if run["kind"] != "serve":
        return None
    return run["window_tokens"] / run["window_s"]
