"""The matmul nodes' share of their roofline: for the matmul nodes run in
the traced part of the window, the least time of each (its operations
over peak or its bytes over peak bandwidth, from its shapes) over the
device time inside its dispatch span."""
from benchmark.graph import roofline

NAME, UNIT = "matmul_roofline.graph", "%"
LAYER, MOVES = "kernels", "program_ms"


def read(run):
    return roofline(run, "matmul")
