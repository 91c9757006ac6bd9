"""Cost of the predictor's decision per dispatched node: the median, over
every node run in the traced part of the window, of the dispatcher's own
record of the time from entering the dispatch to choosing the variant
(``Selection.overhead_s``, the ``dispatch.decide`` span)."""
from benchmark.record import percentile

NAME, UNIT = "dispatch_us.graph", "us"
LAYER, MOVES = "predictor", "program_ms"


def read(run):
    return percentile((1e6 * d.overhead_s
                       for d in run.extra.get("decisions") or ()), 50)
