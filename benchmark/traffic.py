"""Seeded traffic: request sizes, token ids and wall-clock due times.

One general generator reads every traffic file.  Sizes are stratified:
``n`` requests take the ``n`` quantiles ``(i + 0.5) / n`` of their length
distribution, in an order drawn from the traffic file's ``schedule_seed``;
open-loop gaps are stratified the same way over the exponential
distribution.  The run's seed draws the token ids.  Every seed therefore
sends the same sizes at the same times: on the chip, shuffling the order
by the run's seed moved ``ttft_p90_s`` of the chat mix by 14% (quartile
distance over median) between seeds, against under 1% between two runs of
one seed (``PERF.md``).

A length distribution is one of

    {"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}
    {"dist": "uniform", "min": a, "max": b}
    {"dist": "fixed", "value": v}
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request as the generator made it, with what happened to it."""
    rid: int
    prompt: list
    max_new: int
    due: Optional[float] = None      # seconds after the clock's origin
    sent: Optional[float] = None     # when it was handed to the engine
    req: object = None               # the engine's request object
    token_times: list = dataclasses.field(default_factory=list)
    rejected: bool = False           # refused by the engine's queue
    # (step index, tokens generated after it) for each step it rode
    rides: list = dataclasses.field(default_factory=list)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` stratified lengths of ``spec``, in an order drawn from
    ``rng``."""
    q = _quantiles(n)
    kind = spec["dist"]
    if kind == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(float(p)) for p in q])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
        vals = np.clip(np.rint(vals), spec["min"], spec["max"])
    elif kind == "uniform":
        lo, hi = spec["min"], spec["max"]
        vals = lo + np.floor(q * (hi - lo + 1))
    elif kind == "fixed":
        vals = np.full(n, spec["value"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return rng.permutation(vals.astype(np.int64))


def poisson_gaps(rate: float, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` stratified exponential gaps of mean ``1 / rate``, shuffled."""
    gaps = -np.log1p(-_quantiles(n)) / rate
    return rng.permutation(gaps)


def requests(traffic: dict, seed: int, vocab: int, n: int) -> list:
    """The first ``n`` requests of ``traffic``, with token ids from
    ``seed``."""
    order = np.random.default_rng([traffic["schedule_seed"], 0x7261])
    tokens = np.random.default_rng([seed, 0x746b])
    prompts = lengths(traffic["prompt"], n, order)
    outputs = lengths(traffic["output"], n, order)
    out = []
    for i in range(n):
        ids = tokens.integers(0, vocab, size=int(prompts[i]))
        out.append(Planned(rid=i, prompt=[int(t) for t in ids],
                           max_new=int(outputs[i])))
    if traffic["loop"] == "open":
        due = np.cumsum(poisson_gaps(traffic["rate_per_s"], n, order))
        for p, t in zip(out, due):
            p.due = float(t)
    return out


def open_loop_count(traffic: dict, horizon_s: float) -> int:
    """Requests enough to keep an open loop fed for ``horizon_s``."""
    return int(math.ceil(traffic["rate_per_s"] * horizon_s * 1.5)) + 16


def lateness(planned, t0: float) -> dict:
    """How late the generator handed requests over, in seconds; due times
    count from ``t0``."""
    late = [p.sent - (t0 + p.due) for p in planned
            if p.sent is not None and p.due is not None]
    if not late:
        return {"n": 0}
    return {"n": len(late), "p50": float(np.percentile(late, 50)),
            "p99": float(np.percentile(late, 99)), "max": float(max(late))}
