"""Model FLOP utilisation of serving: the operations of every position the
engine processed in the steps that ended in the window (prompt and
generated, worked out from the benchmark's record of each request) over
the window's length times the chip's peak."""
from benchmark import counting
from benchmark.record import cache_spans

NAME, UNIT = "step_mfu.serve", "%"
LAYER, MOVES = "model step", "itl_p95_ms"


def read(run):
    m = counting.Dims.of(run.arch)
    spans = cache_spans(run)
    flops = sum(counting.serve_step(m, spans.get(i, ()))[0]
                for i, s in enumerate(run.steps) if run.in_window(s.end))
    if not flops:
        return None
    return 100.0 * flops / (run.window_s * run.peak["flops_bf16"])
