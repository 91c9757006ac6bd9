"""Set-up: from the start of the process to the window's opening (JAX
start-up, weights, engine, compilation or cache load, tuning, warm-up)."""
NAME, UNIT = "setup_s", "s"


def read(run):
    return run.setup_s
