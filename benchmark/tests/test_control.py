"""The control: the reference in the next precision below (float8
weights) in the program's place must come out as not correct, while the
program passes, at a size a CPU test run holds.  On the chip the same
readings were made at the cells' own sizes (``benchmark/calibrate.py``,
``PERF.md``)."""
import tempfile
import time

import pytest

from benchmark import graph, serve, weights
from benchmark.counting import Dims
from benchmark.record import Run

from tiny import ARCH, GRAPH_LIMIT, SERVE_LIMIT, config, graph_traffic, \
    serve_traffic


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serve_control_fails_where_the_program_passes(seed):
    cfg, traffic = config(), serve_traffic()
    model = serve.build_model(cfg)
    with tempfile.TemporaryDirectory() as tmp:
        params = weights.params(weights.seed_key(seed), Dims.of(ARCH))
        eng = serve.Engine(model, params, traffic["engine"], tmp)
        eng.compile()
        planned = serve.plan(cfg, traffic, seed, 2.0)
        run = Run("serve", ARCH, 2.0, {}, planned=planned)
        run.extra["t0"] = t0 = time.perf_counter()
        serve.drive(eng, run, planned, traffic, t0)
        eng.free()
    chosen = serve.sample(planned, traffic["check_requests"], seed)
    gaps = serve.logit_gaps(ARCH, seed, chosen, ("f32", "fp8"))
    assert gaps["f32"].size >= 100
    assert gaps["f32"].max() <= SERVE_LIMIT < gaps["fp8"].max()


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_graph_control_fails_where_the_program_passes(seed):
    from repro.runtime import default_registry
    reg = default_registry(include=("matmul", "flash_attention"))
    prog, bindings, a = graph.build(ARCH, seed, 128, reg)
    with tempfile.TemporaryDirectory() as tmp:
        from repro.runtime import TuningCache
        from repro.runtime.seeding import measure_from_programs
        cache = TuningCache(root=tmp)
        t = graph_traffic()["tuning"]
        measure_from_programs(graph.make_dispatcher(reg, cache), [prog],
                              min_window=t["min_window"], best_of=1,
                              fit_epochs=t["fit_epochs"], reset=True)
        outs = prog.compile(devices={"chip": graph.make_dispatcher(reg, cache)},
                            bindings=bindings)()
    ref = graph.reference(a)
    assert graph.rel_err(outs, ref) <= GRAPH_LIMIT
    assert graph.rel_err(graph.reference(a, control=True), ref) > GRAPH_LIMIT
