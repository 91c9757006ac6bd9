"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

Reads the trace with ``jax.profiler.ProfileData`` and gives, inside a
window set by the host span ``WINDOW_SPAN``:

- device busy time: the union of the intervals in which an operation ran,
  per chip, averaged over the chips;
- device self time per operation name (an operation that encloses others
  on its line, such as a loop, keeps only the time they leave uncovered);
- idle gaps: the window minus the busy union, each gap attributed to the
  innermost benchmark span (``bench.*``) on the host around its middle.

Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane; host spans are the events named ``bench.*`` on
the ``/host:CPU`` plane.  The two planes' nanoseconds are not on one
clock: on a TPU v5e the device's read about 1.4 ms early.  The device
times are shifted onto the host's by pairing the k-th program the device
ran (its ``XLA Modules`` line) with the k-th ``PJRT_LoadedExecutable_Execute``
call on the host: a program starts when the call that enqueues it
returns, so the shift is the median of (call end - program start).  Where
the two counts differ no shift is made, and ``shift_ns`` is None.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.traced"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
EXECUTE = "PJRT_LoadedExecutable_Execute"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OTHER = "host.other"


@dataclasses.dataclass
class Span:
    name: str
    start: float                  # ns
    end: float


@dataclasses.dataclass
class Reduction:
    window: tuple                 # (start ns, end ns)
    busy_s: float                 # union of op intervals, mean over chips
    op_s: dict                    # op name -> device seconds (all chips)
    gaps_s: dict                  # host span name -> idle seconds
    busy: list                    # merged [start, end) ns intervals, chip 0
    spans: list                   # Span, in start order
    n_devices: int
    shift_ns: object = None       # added to device times; None: unaligned

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_between(self, start: float, end: float) -> float:
        """Seconds of chip 0's busy union inside [start, end) ns."""
        if not hasattr(self, "_starts"):
            self._starts = [s for s, _ in self.busy]
        return _overlap(self.busy, self._starts, start, end) * 1e-9

    def top_ops(self, n: int = 10) -> list:
        return [[k, v] for k, v in sorted(self.op_s.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10) -> list:
        return [[k, v] for k, v in sorted(self.gaps_s.items(),
                                          key=lambda kv: -kv[1])[:n]]


def merge(intervals) -> list:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(merged: list, starts: list, lo: float, hi: float) -> float:
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    total = 0.0
    for s, e in merged[i:]:
        if s >= hi:
            break
        total += max(0.0, min(e, hi) - max(s, lo))
    return total


def find_xplane(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {directory}, "
                                f"found {found}")
    return found[0]


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


_HLO = re.compile(r"^%?(\S+) = (\S+?)[{ ]")


def op_name(name: str) -> str:
    """``%fusion.3 = bf16[8,128]{...} fusion(...)`` -> ``fusion.3
    bf16[8,128]``: the instruction and its result type."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name


def reduce(path: str, window_span: str = WINDOW_SPAN) -> Reduction:
    """Reduce the trace at ``path`` inside the last ``window_span`` span
    (the whole trace's device activity when the span is absent)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans, ops, modules, calls = [], [], [], []
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for n, s, e in _events(line):
                    if n.startswith(SPAN_PREFIX):
                        spans.append(Span(n, s, e))
                    elif n == EXECUTE:
                        calls.append(e)
        elif _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.append([(op_name(n), s, e)
                                for n, s, e in _events(line)])
                elif line.name == MODULES_LINE and not modules:
                    modules = sorted(s for _, s, _ in _events(line))
    if not ops:
        raise ValueError(f"{path}: no {OPS_LINE!r} line on a TPU plane")
    shift = _shift(sorted(calls), modules)
    if shift:
        ops = [[(n, s + shift, e + shift) for n, s, e in dev] for dev in ops]
    spans.sort(key=lambda s: s.start)
    marks = [s for s in spans if s.name == window_span]
    if marks:
        lo, hi = marks[-1].start, marks[-1].end
    else:
        lo = min(s for dev in ops for _, s, _ in dev)
        hi = max(e for dev in ops for _, _, e in dev)
    busy_per_dev, op_s, first = [], {}, None
    for dev in ops:
        clipped = [(max(s, lo), min(e, hi), n) for n, s, e in dev
                   if e > lo and s < hi]
        for n, t in _self_times(clipped):
            op_s[n] = op_s.get(n, 0.0) + t * 1e-9
        merged = merge((s, e) for s, e, _ in clipped)
        busy_per_dev.append(sum(e - s for s, e in merged))
        if first is None:
            first = merged
    gaps_s: dict = {}
    inner = [s for s in spans if s.name != window_span]
    starts = [s.start for s in inner]
    prev = lo
    for s, e in first + [[hi, hi]]:
        if s > prev:
            name = _innermost(inner, starts, (prev + s) / 2.0)
            gaps_s[name] = gaps_s.get(name, 0.0) + (s - prev) * 1e-9
        prev = max(prev, e)
    return Reduction(window=(lo, hi),
                     busy_s=sum(busy_per_dev) / len(busy_per_dev) * 1e-9,
                     op_s=op_s, gaps_s=gaps_s, busy=first, spans=spans,
                     n_devices=len(ops), shift_ns=shift)


def _shift(call_ends: list, module_starts: list):
    """Nanoseconds to add to device times: the median of (k-th call end -
    k-th program start); None when the counts differ."""
    if not module_starts or len(call_ends) != len(module_starts):
        return None
    d = sorted(c - m for c, m in zip(call_ends, module_starts))
    return d[len(d) // 2]


def _self_times(events):
    """``(name, self ns)`` of possibly nested ``(start, end, name)``."""
    out, stack = [], []             # stack of [end, name, self]
    for s, e, n in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            _, pn, ps = stack.pop()
            out.append((pn, ps))
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, n, e - s])
    out += [(n, t) for _, n, t in stack]
    return out


def _innermost(spans: list, starts: list, t: float,
               depth: int = 64) -> str:
    """The latest-starting span that contains ``t``: the innermost one,
    since spans of one thread nest.  Looks back ``depth`` spans at most."""
    i = bisect.bisect_right(starts, t) - 1
    for s in spans[max(i - depth, -1) + 1:i + 1][::-1]:
        if s.end >= t:
            return s.name
    return OTHER
