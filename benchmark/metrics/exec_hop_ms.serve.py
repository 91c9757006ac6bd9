"""What the one-node program adds to the engine's step: the median, per
step in the traced part of the window, of the ``program.call`` span minus
the ``dispatch.serve_step`` span inside it (the executor's worker thread
started and joined, its queue, futures and execution trace).  Read from
the program's own spans in ``run.trace.spans``; None where the trace holds
none."""
from benchmark.record import percentile

NAME, UNIT = "exec_hop_ms.serve", "ms"
LAYER, MOVES = "executor", "itl_p95_ms"


def read(run):
    if run.trace is None:
        return None
    calls = [s for s in run.trace.spans if s.name == "program.call"]
    steps = [s for s in run.trace.spans if s.name == "dispatch.serve_step"]
    hops = []
    for c in calls:
        inner = [s for s in steps if c.start <= s.start and s.end <= c.end]
        if len(inner) == 1:
            hops.append(1e-6 * ((c.end - c.start)
                                - (inner[0].end - inner[0].start)))
    return percentile(hops, 50)
