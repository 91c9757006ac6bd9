#!/usr/bin/env python3
"""Record the small chip trace that ``test_tracereduce.py`` reads.

    python3 benchmark/tests/record_trace.py <out_dir>

On a TPU: a jitted matmul and a scanned (looped) matmul, each called a few
times inside ``bench.step`` spans, with host sleeps between them under
``bench.wait``, all inside the ``bench.traced`` window.  Writes the
``.xplane.pb`` under ``out_dir`` and prints, per plane and line, the
number of events and a few names, so the trace's layout can be read by
hand.
"""
import os
import sys
import time

import jax
import jax.numpy as jnp


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 3
    mm = jax.jit(lambda a, b: a @ b)

    @jax.jit
    def looped(a, b):
        return jax.lax.scan(lambda c, _: (jnp.tanh(c @ b), None), a,
                            None, length=4)[0]

    a = jnp.ones((1024, 1024), jnp.bfloat16)
    mm(a, a).block_until_ready()
    looped(a, a).block_until_ready()
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("bench.traced"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                mm(a, a).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.002)
            with jax.profiler.TraceAnnotation("bench.step"):
                looped(a, a).block_until_ready()
    jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.tracereduce import find_xplane
    path = find_xplane(out)
    print(path, os.path.getsize(path))
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            ev = list(line.events)
            print(f"{plane.name} | {line.name} | {len(ev)} | "
                  + "; ".join(f"{e.name}@{e.start_ns:.0f}+{e.duration_ns:.0f}"
                              for e in ev[:6]))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
