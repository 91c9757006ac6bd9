#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything runs in this one process, which holds the chip.  Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints
no result.  With ``--trace 0`` the result carries the cell's end-to-end
metrics; with ``--trace 1`` its per-layer metrics, read from a profiler
trace of the window's last ``trace_s`` seconds.  The last line of standard
output is the result; the last lines of standard error give each number
compared for ``correct`` beside its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def execute(cell, seed: int, seconds: float, trace: bool, peak: dict,
            t_start: float) -> dict:
    """Run ``cell`` and reduce it to the result line's fields."""
    from benchmark import graph, manifest, serve

    driver = {"serve": serve, "graph": graph}[cell.traffic["kind"]]
    with tempfile.TemporaryDirectory(prefix="bench_") as tmp:
        run, checks, memory = driver.run_cell(cell, seed, seconds, trace,
                                              peak, tmp, t_start, log)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = manifest.metric_module(ROOT, m["name"]).read(run)
        if value is None:
            if not trace:
                raise RuntimeError(f"end-to-end metric {m['name']} has "
                                   "nothing to read")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    attempted, failed = driver.attempted_failed(run)
    out = {"correct": all(v is not None and v <= lim
                          for v, lim in checks.values()),
           "attempted": attempted, "failed": failed, "metrics": metrics,
           "device": {"memory_peak_bytes": memory}}
    if trace:
        out["device"].update(busy_s=run.trace.busy_s,
                             window_s=run.trace.window_s)
        out["breakdown"] = {"device_ops": run.trace.top_ops(10),
                            "idle_gaps": run.trace.top_gaps(10)}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the TPU runtime would otherwise write its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from benchmark import manifest, peaks

    cell = manifest.cell(manifest.load(ROOT), ROOT, args.workload)
    dev = device_info()
    if dev["platform"] != "tpu" or dev["count"] < cell.chips:
        log(f"{args.workload} needs {cell.chips} TPU chip(s); JAX found "
            f"{dev['count']} {dev['platform']} device(s)")
        return 3
    import jax
    from repro.compile_cache import enable_compile_cache
    # the cache lives inside this checkout, at a fixed path (the path is
    # part of the cache key), whatever the environment names
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    log(f"compile cache {enable_compile_cache()}")
    # every program, however quick to compile, comes from the cache after
    # the first run, so that set-up is the same work each time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    peak = peaks.peak_for(dev["kind"])
    out = execute(cell, args.seed, args.seconds, bool(args.trace), peak,
                  T_START)
    out["device"] = {**dev, **out["device"]}
    for k, c in out["checks"].items():
        log(f"check {k} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
