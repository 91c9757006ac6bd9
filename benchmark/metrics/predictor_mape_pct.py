"""Mean absolute error of the predictor's time for the variant it chose,
against what it predicts: the node's call as the host clock sees it (its
``bench.node.*`` dispatch span, which ends when the node's output is
ready), as a share of that time, over every node run in the traced part
of the window."""
from benchmark.graph import node_spans

NAME, UNIT = "predictor_mape_pct", "%"
LAYER, MOVES = "predictor", "program_ms"


def read(run):
    errs = [abs(d.predicted_s[d.chosen] - t) / t
            for d, t in ((d, (s.end - s.start) * 1e-9)
                         for d, s in (node_spans(run) or []))
            if t > 0 and d.predicted_s]
    return 100.0 * sum(errs) / len(errs) if errs else None
