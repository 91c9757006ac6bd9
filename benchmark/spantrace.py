#!/usr/bin/env python3
"""The program's own spans on a profiler trace, and the device's idle time
split among them.

``tracereduce.reduce`` gives each idle gap of the device, whole, to the
benchmark span (``bench.*``) around its middle.  Here every host span
counts: the benchmark's, the program's (``serve.*``, ``program.*``,
``exec.*``, ``dispatch.*``) and JAX's compiles (its ``backend_compile*``
events, named ``jax.compile``).  Each gap is cut at every span boundary
inside it, and each piece goes to the innermost span over it, on any
thread: the latest-starting span that holds it (``host.other`` where none
does).  ``reduce`` returns tracereduce's ``Reduction`` of the same trace
with those spans in ``spans`` and the split gaps in ``gaps_s``; its window,
busy time, operation self times and clock shift are tracereduce's own, so
every metric that reads them reads the same numbers.

    python3 benchmark/spantrace.py --workload <cell> --seed <n> --seconds <s>

runs one cell once with its trace (as ``run.py --trace 1`` does), keeps
the trace, and prints one JSON line: the cell's per-layer metrics read from
this reduction, every metric module that reads program spans, the idle
gaps by span, where the idle time inside each kernel's ``dispatch.wait``
lies, and the host time per step or call inside and outside the traced
part (what tracing costs the host).  The tracereduce reduction of the same
trace is printed under ``tracereduce`` for comparison.
"""
from __future__ import annotations

import bisect
import dataclasses
import os
import sys

if __package__ in (None, ""):
    sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))]

from benchmark import tracereduce
from benchmark.tracereduce import OTHER, SPAN_PREFIX, WINDOW_SPAN, Span

PROGRAM_PREFIXES = ("serve.", "program.", "exec.", "dispatch.")
COMPILE_EVENT = "backend_compile"
COMPILE = "jax.compile"
DECIDE, LAUNCH, WAIT = "dispatch.decide", "dispatch.launch", "dispatch.wait"
# metric modules that read the program's spans from ``run.trace.spans``
SPAN_METRICS = ("step_gap_ms.serve", "exec_hop_ms.serve")


def host_spans(path: str) -> list:
    """Benchmark, program and compile spans of the ``/host:CPU`` plane, in
    start order."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                n = e.name
                if n.startswith(SPAN_PREFIX) or n.startswith(PROGRAM_PREFIXES):
                    out.append(Span(n, float(e.start_ns), float(e.end_ns)))
                elif n.startswith(COMPILE_EVENT):
                    out.append(Span(COMPILE, float(e.start_ns),
                                    float(e.end_ns)))
    return sorted(out, key=lambda s: (s.start, -s.end))


def split_gaps(busy: list, spans: list, lo: float, hi: float) -> dict:
    """Seconds of ``[lo, hi)`` outside ``busy`` (merged ``[start, end)``
    ns intervals inside the window) by the innermost span over them."""
    bounds = sorted({t for s in spans for t in (s.start, s.end)})
    order = sorted(spans, key=lambda s: (s.start, -s.end))
    out: dict = {}
    active: list = []
    k, prev = 0, lo
    for s0, e0 in list(busy) + [[hi, hi]]:
        a, b = prev, min(s0, hi)
        if b > a:
            cuts = [a] + bounds[bisect.bisect_right(bounds, a):
                                bisect.bisect_left(bounds, b)] + [b]
            for p, q in zip(cuts, cuts[1:]):
                t = (p + q) / 2.0
                while k < len(order) and order[k].start <= t:
                    active.append(order[k])
                    k += 1
                active = [s for s in active if s.end >= t]
                # the latest start; of two that start together, the shorter
                name = max(active, key=lambda s: (s.start, -s.end)).name \
                    if active else OTHER
                out[name] = out.get(name, 0.0) + (q - p) * 1e-9
        prev = max(prev, e0)
    return out


def reduce(path: str) -> tracereduce.Reduction:
    red = tracereduce.reduce(path)
    spans = host_spans(path)
    inner = [s for s in spans if s.name != WINDOW_SPAN]
    return dataclasses.replace(red, spans=spans,
                               gaps_s=split_gaps(red.busy, inner, *red.window))


def host_cost(run) -> dict:
    """Median host time of a step (serve) or of a call of each program
    (graph, by chunk), inside the traced part of the window and before
    it, ms: what the profiler costs while it runs."""
    from benchmark.record import percentile

    lo, _ = run.window
    t0, t1 = run.traced
    out = {}
    for label in sorted({s.label for s in run.steps}):
        steps = [s for s in run.steps if s.label == label]
        inside = [1e3 * (s.end - s.start) for s in steps
                  if t0 <= s.start and s.end <= t1]
        before = [1e3 * (s.end - s.start) for s in steps
                  if lo <= s.start and s.end <= t0]
        out[label or "step"] = {
            "traced_ms": percentile(inside, 50),
            "untraced_ms": percentile(before, 50),
            "n_traced": len(inside), "n_untraced": len(before)}
    return out


def wait_split(red) -> dict:
    """For the ``dispatch.wait`` spans of each kernel, the median of how
    long the device stayed idle after the wait began (``lead``), between
    operations (``gaps``) and after the last one ended (``tail``), us:
    where the idle time inside a wait lies."""
    from benchmark.record import percentile

    starts = [s for s, _ in red.busy]
    kernels = [s for s in red.spans if s.name.startswith("dispatch.")
               and s.name not in (DECIDE, LAUNCH, WAIT)]
    k_starts = [k.start for k in kernels]
    rows: dict = {}
    for w in (s for s in red.spans if s.name == WAIT):
        j = bisect.bisect_right(k_starts, w.start) - 1
        if j < 0 or kernels[j].end < w.end:
            continue
        inside = []
        for s, e in red.busy[max(bisect.bisect_right(starts, w.start) - 1,
                                 0):]:
            if s >= w.end:
                break
            if e > w.start:
                inside.append((max(s, w.start), min(e, w.end)))
        if not inside:
            continue
        busy = sum(e - s for s, e in inside)
        lead = inside[0][0] - w.start
        tail = w.end - inside[-1][1]
        rows.setdefault(kernels[j].name, []).append(
            (lead, w.end - w.start - busy - lead - tail, tail))
    return {k: {"n": len(v), **{name: percentile(
        (1e-3 * r[j] for r in v), 50) for j, name in enumerate(
            ("lead_us", "gaps_us", "tail_us"))}} for k, v in rows.items()}


def main(argv=None) -> int:
    import argparse
    import json
    import tempfile
    import time

    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None,
                    help="directory to keep the trace in")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [root, os.path.join(root, "src")]
    import jax

    from benchmark import graph, manifest, peaks, serve
    from benchmark.run import device_info, log
    from repro.compile_cache import enable_compile_cache

    cell = manifest.cell(manifest.load(root), root, args.workload)
    dev = device_info()
    if dev["platform"] != "tpu" or dev["count"] < cell.chips:
        log(f"{args.workload} needs {cell.chips} TPU chip(s)")
        return 3
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    driver = {"serve": serve, "graph": graph}[cell.traffic["kind"]]
    with tempfile.TemporaryDirectory(prefix="spantrace_") as scratch:
        tmp = args.keep or scratch
        run, checks, memory = driver.run_cell(
            cell, args.seed, args.seconds, True, peaks.peak_for(dev["kind"]),
            tmp, t_start, log)
        old = run.trace
        run.trace = reduce(tracereduce.find_xplane(os.path.join(tmp,
                                                                "trace")))
    names = [m["name"] for m in cell.per_layer] + [
        m for m in SPAN_METRICS if m.endswith("." + cell.traffic["kind"])]
    metrics = {n: manifest.metric_module(root, n).read(run) for n in names}
    idle = run.trace.window_s - run.trace.busy_s
    program = sum(v for k, v in run.trace.gaps_s.items()
                  if k.startswith(PROGRAM_PREFIXES) or k == COMPILE)
    out = {"workload": args.workload, "seed": args.seed, "device": dev,
           "checks": checks, "memory_peak_bytes": memory,
           "window_s": run.trace.window_s, "busy_s": run.trace.busy_s,
           "metrics": metrics, "idle_gaps": run.trace.top_gaps(30),
           "idle_under_program_share": program / idle if idle > 0 else None,
           "host_cost": host_cost(run), "wait_split": wait_split(run.trace),
           "tracereduce": {"busy_s": old.busy_s, "window_s": old.window_s,
                           "idle_gaps": old.top_gaps(10)}}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
