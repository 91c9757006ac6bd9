#!/usr/bin/env python3
"""Readings that set the benchmark's numbers, made in one process.

    python3 benchmark/calibrate.py --workload yi-9b.chat --sweep 0.9,1.1,1.3
    python3 benchmark/calibrate.py --workload yi-9b.chat --seeds 11,12,13 \\
        --control 1

Both run the cell as ``run.py`` does (``serve.window``: the cell's own
warm-up, then a window of ``--seconds``, by default the benchmark's
``run_seconds``) and read its numbers with the cell's own metric modules.

``--sweep``: the cell's open-loop traffic at each rate (requests per
second) on a fresh engine; prints the cell's metrics and how many
requests due in the window were still not admitted when it closed, to
find the highest rate the engine sustains.

``--seeds``: for each seed, the cell's own traffic, then the comparison
that decides ``correct`` (the widest logit gap of the served tokens) and,
with ``--control 1``, the same reading for the control: the reference
with float8 weights in place of the program.  For a graph cell,
``--seeds`` gives the control's reading only (the largest relative
difference of the float8 reference from the float32 one, at each chunk
size); the program's readings come from ``run.py``.

The benchmark's runs never run this.  Each line of output is one JSON
object; ``--out`` also appends them to a file.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def emit(obj: dict, out) -> None:
    line = json.dumps(obj)
    print(line, flush=True)
    if out:
        with open(out, "a") as f:
            f.write(line + "\n")


def readings(cell, run) -> dict:
    """The cell's end-to-end metrics and the serve per-layer metrics that
    need no trace, by their own modules, and the backlog at the close."""
    from benchmark import manifest, serve
    names = [m["name"] for m in cell.end_to_end if m["name"] != "setup_s"] \
        + ["queue_wait_p90_s", "slot_occupancy", "output_tok_s",
           "step_mfu.serve"]
    out = {}
    for name in dict.fromkeys(names):
        out[name] = manifest.metric_module(ROOT, name).read(run)
    t0, (lo, hi) = run.extra["t0"], run.window
    due = [p for p in run.planned if p.due is not None
           and run.in_window(t0 + p.due)]
    out["due"] = len(due)
    out["not_admitted_at_close"] = sum(
        1 for p in due if p.req is None or p.req.admitted_s is None
        or p.req.admitted_s >= hi)
    out["window"] = serve.describe(run)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--sweep", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from benchmark import manifest, peaks, serve
    from repro.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 3
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = manifest.load(ROOT)
    cell = manifest.cell(bench, ROOT, args.workload)
    seconds = args.seconds or bench["run_seconds"]
    peak = peaks.peak_for(jax.devices()[0].device_kind)
    if cell.traffic["kind"] == "graph":
        from benchmark import graph
        for seed in [int(s) for s in args.seeds.split(",") if s]:
            for s in cell.traffic["chunks"]:
                a = graph.inputs(cell.config["arch"], seed, s)
                emit({"seed": seed, "chunk": s, "control_rel_err":
                      graph.rel_err(graph.reference(a, control=True),
                                    graph.reference(a))}, args.out)
        return 0
    with tempfile.TemporaryDirectory(prefix="calib_") as tmp:
        runs = [(1000 + int(float(r) * 100),
                 dict(cell.traffic, rate_per_s=float(r)), False)
                for r in args.sweep.split(",") if r]
        runs += [(int(s), cell.traffic, True)
                 for s in args.seeds.split(",") if s]
        for seed, traffic, check in runs:
            t = time.perf_counter()
            eng, run = serve.window(cell, traffic, seed, seconds,
                                    os.path.join(tmp, str(seed)))
            run.peak = peak
            eng.free()
            del eng
            gc.collect()
            line = {"seed": seed, "rate": traffic.get("rate_per_s"),
                    **readings(cell, run)}
            if check:
                chosen = serve.sample(run.planned, traffic["check_requests"],
                                      seed)
                t_ref = time.perf_counter()
                precs = ("f32", "fp8") if args.control else ("f32",)
                gaps = serve.logit_gaps(cell.config["arch"], seed, chosen,
                                        precs)
                line.update(requests=len(chosen),
                            tokens=int(gaps["f32"].size),
                            program_gap=float(gaps["f32"].max()),
                            reference_s=time.perf_counter() - t_ref)
                if args.control:
                    line["control_gap"] = float(gaps["fp8"].max())
            line["run_s"] = time.perf_counter() - t
            emit(line, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
