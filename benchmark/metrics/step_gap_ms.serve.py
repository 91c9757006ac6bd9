"""Host time between two device steps of the engine: the median, over
the steps in the traced part of the window, of the end of step k's
``dispatch.launch`` span (its program enqueued) minus the end of the
``dispatch.wait`` span before it (step k-1's result ready).  Read from the
program's own spans in ``run.trace.spans``; None where the trace holds
none."""
import bisect

from benchmark.record import percentile

NAME, UNIT = "step_gap_ms.serve", "ms"
LAYER, MOVES = "engine host", "itl_p95_ms"


def read(run):
    if run.trace is None:
        return None
    ends = sorted(s.end for s in run.trace.spans if s.name == "dispatch.wait")
    gaps = []
    for s in run.trace.spans:
        if s.name == "dispatch.launch":
            i = bisect.bisect_right(ends, s.start)
            if i:
                gaps.append(1e-6 * (s.end - ends[i - 1]))
    return percentile(gaps, 50)
