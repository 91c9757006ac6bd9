"""Host time between two nodes of one program call: the median, over
consecutive nodes of each call in the traced part of the window, of when
node k's variant call returned (its program enqueued) minus when node
k-1's output was ready, from the dispatcher's own record of each node
(``Selection.launched_at``, ``Selection.done_at``: the ends of its
``dispatch.launch`` and ``dispatch.wait`` spans).  None where the
dispatcher does not record them."""
import bisect

from benchmark.record import percentile

NAME, UNIT = "node_gap_us.graph", "us"
LAYER, MOVES = "whole program", "program_ms"


def read(run):
    decisions = run.extra.get("decisions") or ()
    if not all(getattr(d, "done_at", 0.0) for d in decisions):
        return None
    starts = [s.start for s in run.steps]

    def call(t):
        return bisect.bisect_right(starts, t) - 1

    gaps = [1e6 * (d.launched_at - p.done_at)
            for p, d in zip(decisions, decisions[1:])
            if call(p.done_at) == call(d.done_at)]
    return percentile(gaps, 50)
