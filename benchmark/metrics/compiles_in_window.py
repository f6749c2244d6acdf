"""Backend compilations jax reported between the opening of the window
and its close (watched programs and small unwatched jits alike). A run
with any is not correct."""



def read(run, trace):
    return run["compiles_in_window"]
