"""The engine step's share of its roofline: for each step that ended in
the traced part of the window, the least time the chip could take (the
larger of its operations over peak and its bytes over peak bandwidth;
bytes are the weights plus each rider's live KV, worked out from the
benchmark's record of each request), summed, over the device's busy time
there."""
from benchmark import counting, peaks
from benchmark.record import cache_spans

NAME, UNIT = "decode_roofline.serve", "%"
LAYER, MOVES = "kernels", "itl_p95_ms"


def read(run):
    if run.trace is None or run.traced is None or run.trace.busy_s <= 0:
        return None
    m = counting.Dims.of(run.arch)
    spans = cache_spans(run)
    lo, hi = run.traced
    least = sum(peaks.least_seconds(
        *counting.serve_step(m, spans.get(i, ())), run.peak)
        for i, s in enumerate(run.steps) if lo <= s.end < hi)
    return 100.0 * least / run.trace.busy_s if least else None
