"""What one run recorded, as every metric module reads it."""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Step:
    """One engine step or one program call, on the host clock."""
    start: float
    end: float
    label: str = ""               # graph cells: which program ran


@dataclasses.dataclass
class Run:
    kind: str                     # "serve" or "graph"
    arch: dict                    # the configuration's model numbers
    seconds: float
    peak: dict                    # peaks.PEAKS entry of this chip
    setup_s: float = 0.0
    window: tuple = (0.0, 0.0)    # host clock, seconds
    traced: Optional[tuple] = None  # host clock bounds of the traced part
    steps: list = dataclasses.field(default_factory=list)
    planned: list = dataclasses.field(default_factory=list)
    counters: dict = dataclasses.field(default_factory=dict)
    trace: object = None          # tracereduce.Reduction, --trace 1 only
    extra: dict = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def in_window(self, t: Optional[float]) -> bool:
        return t is not None and self.window[0] <= t < self.window[1]

    def steps_between(self, lo: float, hi: float) -> list:
        return [s for s in self.steps if lo <= s.end < hi]


def cache_spans(run: Run) -> dict:
    """For each serve step, ``[(before, after)]``: how many of a rider's
    positions were in the cache before the step and after it, worked out
    from the benchmark's own record of each request (its prompt, and how
    many tokens it had generated after each step it rode).

    Once a request has its first token, ``after`` is the prompt plus every
    generated token but the newest, which the next step feeds.  The prompt
    is spread evenly over the steps up to and including the one that gave
    the first token, so one prompt token per step and a whole prompt in
    one step both count exactly; a request still without a first token is
    taken at one prompt token per step."""
    out: dict = {}
    for p in run.planned:
        rides = p.rides
        if not rides:
            continue
        n = len(p.prompt)
        pre = next((i + 1 for i, (_, g) in enumerate(rides) if g > 0), None)
        before = 0
        for i, (step, g) in enumerate(rides):
            if g > 0:
                after = n + g - 1
            elif pre is not None:
                after = (n * (i + 1)) // pre
            else:
                after = min(i + 1, n)
            out.setdefault(step, []).append((before, after))
            before = after
    return out


def percentile(values, q: float) -> Optional[float]:
    """The ``q``-th percentile (linear interpolation); None when empty."""
    values = list(values)
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))
