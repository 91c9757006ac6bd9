"""The attention nodes' share of their roofline: causal attention's
operations (half the square) and bytes from its shapes, over the device
time inside each attention node's dispatch span in the traced part of
the window."""
from benchmark.graph import roofline

NAME, UNIT = "flash_attention_roofline.graph", "%"
LAYER, MOVES = "kernels", "program_ms"


def read(run):
    return roofline(run, "flash_attention")
