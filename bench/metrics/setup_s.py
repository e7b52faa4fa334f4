"""Set-up seconds: process start to the window's open (generation, build,
stacking, compiles or cache loads, warm-up)."""


def read(run):
    return run.setup_s
