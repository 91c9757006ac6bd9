"""90th percentile of the admission wait (admitted minus due) of every
request due in the window; one not admitted by the window's close counts
with the wait until the close."""
from benchmark.record import percentile

NAME, UNIT = "queue_wait_p90_s", "s"
LAYER, MOVES = "admission", "ttft_p90_s"


def read(run):
    t0, close = run.extra["t0"], run.window[1]
    waits = []
    for p in run.planned:
        if p.due is None or not run.in_window(t0 + p.due):
            continue
        admitted = getattr(p.req, "admitted_s", None) if p.req else None
        waits.append(min(admitted if admitted is not None else close, close)
                     - (t0 + p.due))
    return percentile(waits, 90)
