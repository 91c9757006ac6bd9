"""Device time per engine step: the device's busy time in the traced part
of the window over the engine steps that ended in it."""
NAME, UNIT = "step_ms.serve", "ms"
LAYER, MOVES = "model step", "itl_p95_ms"


def read(run):
    if run.trace is None or run.traced is None:
        return None
    steps = run.steps_between(*run.traced)
    if not steps or run.trace.busy_s <= 0:
        return None
    return 1e3 * run.trace.busy_s / len(steps)
