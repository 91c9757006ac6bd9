"""The programs' operations, counted from their nodes' shapes, for every
call completed in the window, over the window's length times the chip's
peak."""
from benchmark.graph import calls_in_window

NAME, UNIT = "graph_mfu", "%"
LAYER, MOVES = "whole program", "program_ms"


def read(run):
    flops = sum(run.extra["flops"][c.label] for c in calls_in_window(run))
    if not flops:
        return None
    return 100.0 * flops / (run.window_s * run.peak["flops_bf16"])
