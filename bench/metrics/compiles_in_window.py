"""Programs JAX compiled or loaded from the persistent compilation cache
while the window ran (its backend-compile monitoring events): each is a
shape the warm-up did not reach, and each stalls the tick that needs it."""


def read(run):
    return run.compiles_in_window
