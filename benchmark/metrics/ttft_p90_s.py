"""90th percentile of time to first token over every request due in the
window, timed from its due time.  A request without a first token when
the window closes (refused by the queue, or still waiting) counts with
the time it has waited by then, so a stall cannot drop out of the tail."""
from benchmark.record import percentile

NAME, UNIT = "ttft_p90_s", "s"


def read(run):
    t0, close = run.extra["t0"], run.window[1]
    waits = []
    for p in run.planned:
        if p.due is None or not run.in_window(t0 + p.due):
            continue
        times = p.token_times
        first = times[0] if times and not p.rejected else close
        waits.append(min(first, close) - (t0 + p.due))
    return percentile(waits, 90)
