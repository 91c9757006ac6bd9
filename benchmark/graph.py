"""Graph cells: one decoder layer's kernels as a ``repro.api`` program.

The program holds the projections and the attention of one layer at the
configuration's widths, for a chunk of ``S`` tokens: q, k, v, o, gate, up
and down as ``ops.matmul`` and the causal attention as ``ops.attention``,
eight nodes over inputs drawn from the seed.  The registry's attention
variants take equal head counts, so K and V are drawn at the KV heads and
repeated to the query heads before they enter the program.  Each node's
input is drawn on its own (there is no reshape or elementwise node), so
the nodes are independent: the program is the layer's kernel work, not its
mathematics.

Set-up measures every variant of every node into a fresh tuning cache,
fits the predictor, compiles one program per chunk size with
predictor-best dispatch, and calls each once.  The window calls the
programs alternately, each call ending when its outputs are ready.

``correct`` compares the outputs of the last call of each program, and of
one more call drawn from the seed, with a float32 reference at
``Precision.HIGHEST`` computed from the same inputs: the largest
difference over the largest reference magnitude, over every output.
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import counting, tracereduce, weights
from benchmark.reference import fp8
from benchmark.record import Run, Step

clock = time.perf_counter
HI = jax.lax.Precision.HIGHEST
NODE_SPAN = "bench.node."
_draw = jax.jit(weights.uniform, static_argnums=(1, 2, 3))


def _shapes(arch: dict, s: int) -> dict:
    d, h, kv, hd, ff = (arch[k] for k in ("d_model", "n_heads", "n_kv_heads",
                                          "head_dim", "d_ff"))
    return {"x": (s, d), "wq": (d, h * hd), "wk": (d, kv * hd),
            "wv": (d, kv * hd), "attn": (s, h * hd), "wo": (h * hd, d),
            "w_gate": (d, ff), "w_up": (d, ff), "mid": (s, ff),
            "w_down": (ff, d), "q": (1, s, h, hd), "k": (1, s, kv, hd),
            "v": (1, s, kv, hd)}


def inputs(arch: dict, seed: int, s: int, dtype=jnp.bfloat16) -> dict:
    """The program's inputs for a chunk of ``s`` tokens, on the device:
    activations of unit scale, weights of 1/sqrt(fan-in)."""
    key = jax.random.fold_in(weights.seed_key(seed), s)
    out = {}
    for i, (name, shape) in enumerate(sorted(_shapes(arch, s).items())):
        std = shape[0] ** -0.5 if name.startswith("w") else 1.0
        out[name] = _draw(jax.random.fold_in(key, i), shape, std,
                          jnp.dtype(dtype).name)
    rep = arch["n_heads"] // arch["n_kv_heads"]
    out["k"] = jnp.repeat(out["k"], rep, axis=2)
    out["v"] = jnp.repeat(out["v"], rep, axis=2)
    return out


def build(arch: dict, seed: int, s: int, registry):
    """``(program, bindings, inputs)`` for a chunk of ``s`` tokens."""
    from repro.api import ops, trace

    a = inputs(arch, seed, s)
    with trace(registry=registry) as tb:
        outs = [ops.matmul(a["x"], a["wq"]), ops.matmul(a["x"], a["wk"]),
                ops.matmul(a["x"], a["wv"]),
                ops.attention(a["q"], a["k"], a["v"]),
                ops.matmul(a["attn"], a["wo"]),
                ops.matmul(a["x"], a["w_gate"]), ops.matmul(a["x"], a["w_up"]),
                ops.matmul(a["mid"], a["w_down"])]
        tb.mark_output(*outs)
    return tb.program, dict(tb.bindings), a


@jax.jit
def _mm_ref(a, b):
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                   precision=HI)


@jax.jit
def _mm_fp8(a, b):
    (qa, sa), (qb, sb) = fp8(a), fp8(b)
    return jnp.dot(qa, qb, preferred_element_type=jnp.float32) * sa * sb


def _attn(q, k, v, control: bool):
    """Causal attention in float32; q, k, v: [1, S, H, D]."""
    if control:
        (q, sq), (k, sk), (v, sv) = fp8(q), fp8(k), fp8(v)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * sq * sk
    else:
        q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI)
    s = q.shape[1]
    sc = sc * q.shape[-1] ** -0.5
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    if control:
        pq, sp = fp8(p)
        return jnp.einsum("bhqk,bkhd->bqhd", pq, v,
                          preferred_element_type=jnp.float32) * sp * sv
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI)


_attn_ref = jax.jit(lambda q, k, v: _attn(q, k, v, False))
_attn_fp8 = jax.jit(lambda q, k, v: _attn(q, k, v, True))


def reference(a: dict, control: bool = False) -> list:
    """The program's eight outputs, in float32 (or in the control's
    float8), in the program's output order."""
    mm = _mm_fp8 if control else _mm_ref
    at = _attn_fp8 if control else _attn_ref
    return [mm(a["x"], a["wq"]), mm(a["x"], a["wk"]), mm(a["x"], a["wv"]),
            at(a["q"], a["k"], a["v"]), mm(a["attn"], a["wo"]),
            mm(a["x"], a["w_gate"]), mm(a["x"], a["w_up"]),
            mm(a["mid"], a["w_down"])]


def rel_err(outs, refs) -> float:
    """Largest |out - ref| over largest |ref|, the worst output."""
    worst = 0.0
    for o, r in zip(outs, refs):
        o = jnp.asarray(o, jnp.float32)
        r = jnp.asarray(r, jnp.float32)
        if o.shape != r.shape:
            return float("inf")
        e = float(jnp.max(jnp.abs(o - r)) / jnp.maximum(jnp.max(jnp.abs(r)),
                                                        1e-30))
        worst = max(worst, e if np.isfinite(e) else float("inf"))
    return worst


def node_cost(kernel: str, p: dict) -> tuple:
    """``(flops, bytes)`` of a node of ``kernel`` with predictor params
    ``p``, from its shapes."""
    if kernel == "matmul":
        return counting.matmul(p["m"], p["n"], p["k"])
    if kernel == "flash_attention":
        return counting.causal_attention(p["b"], p["s"], p["h"], p["d"])
    raise KeyError(kernel)


def node_spans(run: Run):
    """``[(decision, span)]`` for every node dispatched in the traced part
    of the window: the k-th ``bench.node.*`` host span there is the k-th
    recorded decision.  None when the two do not pair up."""
    if run.trace is None or "decisions" not in run.extra:
        return None
    lo, hi = run.trace.window
    spans = [s for s in run.trace.spans if s.name.startswith(NODE_SPAN)
             and s.start >= lo and s.end <= hi]
    decisions = run.extra["decisions"]
    if len(spans) != len(decisions) or any(
            s.name != NODE_SPAN + d.kernel for s, d in zip(spans, decisions)):
        return None
    return list(zip(decisions, spans))


def roofline(run: Run, kernel: str):
    """Least time over device time of the traced nodes of ``kernel``, %:
    the device time of a node is the device's busy time inside its
    dispatch span."""
    pairs = [(d, run.trace.busy_between(s.start, s.end))
             for d, s in (node_spans(run) or []) if d.kernel == kernel]
    busy = sum(t for _, t in pairs)
    if not pairs or busy <= 0:
        return None
    from benchmark import peaks
    least = sum(peaks.least_seconds(*node_cost(d.kernel, d.params), run.peak)
                for d, _ in pairs)
    return 100.0 * least / busy


def make_dispatcher(registry, cache):
    """The program's dispatcher with a ``bench.node.<kernel>`` span around
    each dispatch, and a record of each decision while ``record`` is set."""
    from repro.runtime import Dispatcher

    class Spanned(Dispatcher):
        record = None

        def dispatch(self, kernel, *args, **kwargs):
            with jax.profiler.TraceAnnotation(NODE_SPAN + kernel):
                out = super().dispatch(kernel, *args, **kwargs)
            if self.record is not None:
                self.record.append(self.selections[-1])
            return out

    return Spanned(registry=registry, cache=cache)


def run_cell(cell, seed: int, seconds: float, trace: bool, peak: dict,
             tmp: str, t_start: float, log) -> tuple:
    """One run of a graph cell: ``(run, checks, memory_peak_bytes)``."""
    from repro.runtime import TuningCache, default_registry
    from repro.runtime.seeding import measure_from_programs

    from benchmark.serve import memory_peak_bytes

    arch, cfg = cell.config["arch"], cell.traffic
    registry = default_registry(include=("matmul", "flash_attention"))
    built = {s: build(arch, seed, s, registry) for s in cfg["chunks"]}
    cache = TuningCache(root=os.path.join(tmp, "tuning"))
    tune = cfg["tuning"]
    t = clock()
    measure_from_programs(make_dispatcher(registry, cache),
                          [b[0] for b in built.values()],
                          seed=seed % 2 ** 32, min_window=tune["min_window"],
                          best_of=tune["best_of"],
                          fit_epochs=tune["fit_epochs"], reset=True)
    log(f"measured and fitted the predictor in {clock() - t:.1f}s")
    disp = make_dispatcher(registry, cache)
    progs = {s: b[0].compile(devices={"chip": disp}, bindings=b[1])
             for s, b in built.items()}
    for prog in progs.values():
        jax.block_until_ready(prog())
    chosen = sorted({(sel.kernel, str(sel.params), sel.chosen)
                     for sel in disp.selections})
    log(f"chosen variants: {chosen}")
    order = list(cfg["chunks"])
    run = Run("graph", arch, seconds, peak)
    run.extra["flops"] = {str(s): sum(node_cost(n.kernel, n.params)[0]
                                      for n in b[0].nodes)
                          for s, b in built.items()}
    rng = np.random.default_rng([seed, 0x6772])
    keep_at = {s: int(rng.integers(0, cfg["sample_calls"])) for s in order}
    kept, calls = {}, {s: 0 for s in order}
    trace_dir = os.path.join(tmp, "trace") if trace else None
    open_ = clock()
    close = open_ + seconds
    run.window = (open_, close)
    run.setup_s = open_ - t_start
    trace_from = close - cfg.get("trace_s", seconds) if trace else None
    tracing, i, end = None, 0, open_
    while end < close:
        if trace_from is not None and tracing is None and end >= trace_from:
            jax.profiler.start_trace(trace_dir)
            tracing = jax.profiler.TraceAnnotation(tracereduce.WINDOW_SPAN)
            tracing.__enter__()
            run.traced = (clock(), None)
            disp.record = []
        s = order[i % len(order)]
        start = clock()
        with jax.profiler.TraceAnnotation("bench.call"):
            outs = jax.block_until_ready(progs[s]())
        end = clock()
        run.steps.append(Step(start, end, label=str(s)))
        if calls[s] == keep_at[s]:
            kept[(s, "drawn")] = outs
        kept[(s, "last")] = outs
        calls[s] += 1
        i += 1
    run.window = (open_, end)
    if tracing is not None:
        run.traced = (run.traced[0], clock())
        run.extra["decisions"] = disp.record
        disp.record = None
        tracing.__exit__(None, None, None)
        jax.profiler.stop_trace()
    memory = memory_peak_bytes()
    del progs
    worst = 0.0
    for (s, _), outs in kept.items():
        worst = max(worst, rel_err(outs, reference(built[s][2])))
    log(f"calls {calls}; compared {len(kept)} calls' outputs")
    checks = {"max_rel_err": (worst, cell.config["limits"]["max_rel_err"])}
    if trace_dir:
        run.trace = tracereduce.reduce(tracereduce.find_xplane(trace_dir))
    return run, checks, memory


def attempted_failed(run: Run) -> tuple:
    return len(calls_in_window(run)), 0


def calls_in_window(run: Run) -> list:
    lo, hi = run.window
    return [s for s in run.steps if lo < s.end <= hi]
