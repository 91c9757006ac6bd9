"""End-to-end runtime dispatch: cold -> warm -> reload from disk.

1. COLD: a fresh tuning cache forces measured dispatch — every variant of
   the blur kernel is timed (black-box protocol), rows are recorded, and
   the lightweight NN+C model is fitted and persisted.
2. WARM: the same shapes dispatch again — now every decision is a <75-weight
   prediction, no measurement; steady-state overhead is reported as a
   fraction of kernel wall time.
3. RELOAD: a fresh ``Dispatcher`` over a fresh ``TuningCache`` opens the
   cache from disk and must make identical selections (the persisted model
   round-trips bit-exactly).  It runs in this process: on a chip a second
   process could not reach the device this one holds.

    PYTHONPATH=src python examples/runtime_dispatch.py
"""
import os
import shutil
import sys

import jax.numpy as jnp
import numpy as np

SHAPES = [(384, 384), (512, 384), (512, 512), (768, 512),
          (768, 768), (1024, 768), (1024, 1024), (1536, 1024)]
WARM_REPS = 25


def make_dispatcher(root):
    from repro.runtime import (Dispatcher, DispatchPolicy, TuningCache,
                               default_registry)
    return Dispatcher(
        registry=default_registry(include=["blur"]),
        cache=TuningCache(root=root),
        policy=DispatchPolicy(min_rows_to_fit=5 * len(SHAPES),
                              fit_epochs=6000))


def run_shapes(dispatcher, reps=1):
    rng = np.random.RandomState(0)
    selections = {}
    for (m, n) in SHAPES:
        a = jnp.asarray(rng.rand(m, n), jnp.float32)
        for _ in range(reps):
            dispatcher.dispatch("blur", a)
        sel = dispatcher.selections[-1]
        selections[f"{m}x{n}"] = sel.chosen
    return selections


def main():
    # dedicated demo root, cleared so the cold run is genuinely cold
    root = os.path.join("results", "tunecache-demo")
    shutil.rmtree(root, ignore_errors=True)
    d = make_dispatcher(root)

    print(f"== cold run (cache: {d.cache.dir}) ==")
    cold = run_shapes(d)
    print(f"dispatches: {d.stats()['dispatches']}, measured: {d.n_measured}, "
          f"predicted: {d.n_predicted}")
    if d._entry("blur").model is None:
        d.fit("blur")               # small shape set: fit explicitly
    for size, chosen in cold.items():
        print(f"  {size:10s} -> {chosen}")

    print("\n== warm run (same process) ==")
    run_shapes(d)                   # decision-memo warm-up pass
    d.reset_stats()                 # ...then measure the steady state
    n_measured_before = d.n_measured
    warm = run_shapes(d, reps=WARM_REPS)
    stats = d.stats()
    assert d.n_measured == n_measured_before, "warm run must not measure"
    for size, chosen in warm.items():
        print(f"  {size:10s} -> {chosen}")
    print(f"steady-state dispatch overhead: "
          f"{stats['steady_overhead_s']*1e6:.0f}us "
          f"= {stats['steady_overhead_pct']:.2f}% of wall time "
          f"(target <5%)")

    print("\n== a fresh dispatcher reloads the cache from disk ==")
    reloaded = make_dispatcher(root)
    selections = run_shapes(reloaded)
    assert reloaded.n_measured == 0, "reload must dispatch purely from cache"
    assert selections == warm, (selections, warm)
    print("reloaded selections identical to warm run; 0 measurements — OK")

    overhead_ok = stats["steady_overhead_pct"] < 5.0
    print(f"\noverhead target met: {overhead_ok}")
    return 0 if overhead_ok else 1


if __name__ == "__main__":
    sys.exit(main())
