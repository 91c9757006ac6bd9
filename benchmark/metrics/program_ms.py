"""The window's length over the number of compiled-program calls that
completed in it, each call ending when its outputs are ready."""
from benchmark.graph import calls_in_window

NAME, UNIT = "program_ms", "ms"


def read(run):
    calls = calls_in_window(run)
    return 1e3 * run.window_s / len(calls) if calls else None
