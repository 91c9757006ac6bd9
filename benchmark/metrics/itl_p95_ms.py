"""95th percentile of the gap between consecutive output tokens of a
request, over every gap that ends inside the window."""
from benchmark.record import percentile

NAME, UNIT = "itl_p95_ms", "ms"


def read(run):
    gaps = []
    for p in run.planned:
        times = p.token_times
        gaps += [(b - a) * 1e3 for a, b in zip(times, times[1:])
                 if run.in_window(b)]
    return percentile(gaps, 95)
