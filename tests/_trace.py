"""Record a block under the JAX profiler and read back its host spans."""
import contextlib
import dataclasses
import glob
import os
import warnings

import jax


@dataclasses.dataclass
class HostSpan:
    name: str
    start: float                  # ns, the profiler's host clock
    end: float
    thread: int                   # index of the host line (one per thread)
    stats: dict

    def holds(self, other: "HostSpan") -> bool:
        return self.start <= other.start and other.end <= self.end


@contextlib.contextmanager
def profiled(directory):
    jax.profiler.start_trace(str(directory))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def host_spans(directory, prefixes: tuple) -> list:
    """Spans of the ``/host:CPU`` plane whose names start with one of
    ``prefixes``, in start order."""
    from jax.profiler import ProfileData

    found = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(found) == 1, found
    out = []
    for plane in ProfileData.from_file(found[0]).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(prefixes):
                    with warnings.catch_warnings():
                        # the stats type has no __module__ (jax 0.9)
                        warnings.simplefilter("ignore", DeprecationWarning)
                        stats = dict(e.stats)
                    out.append(HostSpan(e.name, float(e.start_ns),
                                        float(e.end_ns), i, stats))
    return sorted(out, key=lambda s: (s.start, -s.end))
