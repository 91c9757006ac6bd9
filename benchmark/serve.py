"""Serve cells: the program's ``ServeEngine`` under seeded traffic.

The window drives ``ServeEngine.submit`` and ``ServeEngine.step`` from one
thread.  An open loop hands each request over when its wall-clock due
time has come; a closed loop keeps one request in flight per client and
sends a client's next request when its last one completes.  Set-up builds
the engine, compiles its step with a one-token request and then runs the
traffic for ``warmup_s`` so that the slots fill before the window
opens.  The loop stops when the window closes.

After the window the engine's cache and weights are freed and the plain
reference (``benchmark/reference.py``) is run over a sample of the
finished requests: each prompt with its served tokens.  The number
compared is the widest gap by which a served token's reference logit lies
below the reference's best logit at that position.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import time
from collections import deque

import jax
import numpy as np

from benchmark import reference, tracereduce, traffic as traffic_mod, weights
from benchmark.counting import Dims
from benchmark.record import Run, Step

clock = time.perf_counter


def _annotate(name: str):
    return jax.profiler.TraceAnnotation(name)


def build_model(config: dict):
    """The program's model for ``config``: its named architecture with the
    configuration's changes, every other number checked against the
    file."""
    from repro.configs import get_arch
    from repro.models import build_model as build

    arch = config["arch"]
    base = get_arch(config["model"])
    changed = {k: arch[k] for k in config["reduced"]}
    cfg = dataclasses.replace(base, **changed)
    for k, v in arch.items():
        if k != "rms_norm_eps" and getattr(cfg, k) != v:
            raise ValueError(f"{config['model']}: the program's {k} is "
                             f"{getattr(cfg, k)!r}, the configuration file "
                             f"says {v!r}")
    return build(cfg)


def check_layout(model, params) -> None:
    """The benchmark's weight tree must be the program's parameter tree."""
    from repro.models.module import shape_tree

    want = jax.tree.map(lambda s: (tuple(s.shape), str(s.dtype)),
                        shape_tree(model.param_specs()))
    have = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), params)
    if want != have:
        raise ValueError(f"weight layout differs from the program's: "
                         f"{have} != {want}")


class Engine:
    """The program's engine as a user drives it: ``submit``, ``step`` and
    the fields of each request."""

    def __init__(self, model, params, engine_cfg: dict, tuning_dir: str):
        from repro.runtime import TuningCache
        from repro.serve.engine import ServeEngine

        self.engine = ServeEngine(model, TuningCache(root=tuning_dir),
                                  params=params, **engine_cfg)
        self.max_slots = int(engine_cfg["max_slots"])

    def compile(self) -> None:
        from repro.serve.request import ServeRequest
        e = self.engine
        e.submit(ServeRequest(rid=-1, prompt=[1], max_new=1))
        while e.step():
            pass

    def free(self) -> None:
        """Delete the engine's cache and weights on the device."""
        e = self.engine
        for x in jax.tree.leaves((e.cache, e.params)):
            x.delete()


def _send(eng: Engine, p, now: float, queued: list) -> None:
    from repro.serve.request import ServeRequest
    p.req = ServeRequest(rid=p.rid, prompt=list(p.prompt), max_new=p.max_new)
    p.sent = now
    p.token_times = []
    p.rides = []
    with _annotate("bench.submit"):
        p.rejected = not eng.engine.submit(p.req)
    if not p.rejected:
        queued.append(p)


class Epochs:
    """Where the engine's shared cache index stands, for the log only: no
    metric reads it, and an engine without one logs nothing."""

    def __init__(self, engine):
        self.engine, self.marks, self.resets = engine, {}, []
        self.last = self.index()

    def index(self):
        i = getattr(self.engine, "index", None)
        return int(i) if isinstance(i, (int, np.integer)) else None

    def after_step(self, now: float) -> None:
        i = self.index()
        if i is not None and self.last is not None and i < self.last:
            self.resets.append(now)
        self.last = i

    def mark(self, name: str) -> None:
        self.marks[name] = self.index()


def drive(eng: Engine, run: Run, planned: list, cfg: dict, t0: float,
          trace_dir=None) -> None:
    """Run the traffic from ``t0`` through warm-up and window, filling
    ``run.steps``, ``run.counters`` and each request's times and rides.

    Which requests rode a step comes from the requests alone: those
    admitted by its end that had not finished before it.  A step in which
    a request waited while a slot was free counts as held."""
    e = eng.engine
    open_, close = t0 + cfg["warmup_s"], t0 + cfg["warmup_s"] + run.seconds
    run.window = (open_, close)
    closed = cfg["loop"] == "closed"
    waiting = deque(planned)
    queued: list = []                 # submitted, not yet admitted
    inflight: list = []               # admitted, not yet done
    epochs = Epochs(e)
    if closed:
        for _ in range(cfg["clients"]):
            p = waiting.popleft()
            p.due = 0.0
            _send(eng, p, t0, queued)
    tracing = None
    marks = {}
    held = 0
    trace_from = close - cfg.get("trace_s", run.seconds) if trace_dir else None
    while True:
        now = clock()
        if "open" not in marks and now >= open_:
            marks["open"] = (e.steps, e.busy_slot_steps)
            epochs.mark("open")
        if trace_from is not None and tracing is None and now >= trace_from:
            jax.profiler.start_trace(trace_dir)
            tracing = _annotate("bench.traced")
            tracing.__enter__()
            run.traced = (clock(), None)
        if now >= close:
            marks["close"] = (e.steps, e.busy_slot_steps)
            epochs.mark("close")
            if tracing is not None:
                run.traced = (run.traced[0], clock())
                tracing.__exit__(None, None, None)
                jax.profiler.stop_trace()
            break
        if not closed:
            while waiting and t0 + waiting[0].due <= now:
                _send(eng, waiting.popleft(), now, queued)
        if not queued and not inflight:
            nxt = t0 + waiting[0].due if (waiting and not closed) else now
            with _annotate("bench.wait"):
                time.sleep(max(0.0, min(nxt, close) - now) + 1e-4)
            continue
        free = eng.max_slots - len(inflight)
        start = clock()
        with _annotate("bench.step"):
            e.step()
        end = clock()
        epochs.after_step(end)
        admitted = [p for p in queued if p.req.admitted_s is not None]
        if admitted:
            queued = [p for p in queued if p.req.admitted_s is None]
            inflight += admitted
        if queued and free > len(admitted) and open_ <= start:
            held += 1
        if not inflight:
            continue
        k = len(run.steps)
        run.steps.append(Step(start, end))
        for p in inflight:
            r = p.req
            p.rides.append((k, len(r.generated)))
            while len(p.token_times) < len(r.generated):
                p.token_times.append(end)
        done = [p for p in inflight if p.req.done]
        if done:
            inflight = [p for p in inflight if not p.req.done]
        for p in done:
            if closed and waiting:
                nxt = waiting.popleft()
                nxt.due = end - t0
                _send(eng, nxt, end, queued)
    run.counters = {"open": marks.get("open"), "close": marks.get("close"),
                    "max_slots": e.max_slots, "policy": e.policy_name,
                    "held_steps": held, "index": epochs.marks,
                    "resets": [t - t0 for t in epochs.resets]}


def sample(planned: list, k: int, seed: int) -> list:
    """``k`` finished requests drawn from the seed, the one with the most
    served tokens always among them."""
    done = [p for p in planned if p.req is not None and p.req.done]
    if not done:
        return []
    longest = max(done, key=lambda p: (len(p.req.generated), p.rid))
    rest = [p for p in done if p is not longest]
    rng = np.random.default_rng([seed, 0x636b])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False) \
        if rest else []
    return [longest] + [rest[int(i)] for i in pick]


def logit_gaps(arch: dict, seed: int, chosen: list,
               precisions=("f32",)) -> dict:
    """For each precision, the gap at every served position between the
    float32 reference's best logit and the reference logit of the token
    that was served (``f32``) or that the control puts first (others)."""
    seqs = [p.prompt + p.req.generated[:-1] for p in chosen]
    pos = [list(range(len(p.prompt) - 1,
                      len(p.prompt) - 1 + len(p.req.generated)))
           for p in chosen]
    out = reference.logits(arch, seed, seqs, pos, precisions)
    gaps = {}
    for prec in precisions:
        g = []
        for p, ref, got in zip(chosen, out["f32"], out[prec]):
            pick = np.asarray(p.req.generated) if prec == "f32" \
                else np.argmax(got, axis=1)
            g += list(ref.max(axis=1) - ref[np.arange(len(pick)), pick])
        gaps[prec] = np.asarray(g, np.float64)
    return gaps


def setup(config: dict, traffic: dict, seed: int, tuning_dir: str):
    """Weights made from the seed, the engine built and its step compiled."""
    model = build_model(config)
    dims = Dims.of(config["arch"])
    params = weights.params(weights.seed_key(seed), dims)
    check_layout(model, params)
    eng = Engine(model, params, traffic["engine"], tuning_dir)
    eng.compile()
    return eng


def plan(config: dict, traffic: dict, seed: int, seconds: float) -> list:
    horizon = traffic["warmup_s"] + seconds
    n = traffic_mod.open_loop_count(traffic, horizon) \
        if traffic["loop"] == "open" else traffic["requests"]
    return traffic_mod.requests(traffic, seed, config["arch"]["vocab_size"],
                                n)


def attempted_failed(run: Run) -> tuple:
    t0 = run.extra["t0"]
    due = [p for p in run.planned if p.due is not None
           and run.in_window(t0 + p.due)]
    return len(due), sum(1 for p in due if p.rejected)


def window(cell, traffic: dict, seed: int, seconds: float, tmp: str,
           trace_dir=None) -> tuple:
    """Set-up and window of one serve run: ``(engine, run)``."""
    config = cell.config
    eng = setup(config, traffic, seed, os.path.join(tmp, "tuning"))
    planned = plan(config, traffic, seed, seconds)
    run = Run("serve", config["arch"], seconds, {}, planned=planned)
    run.extra["t0"] = t0 = clock()
    drive(eng, run, planned, traffic, t0, trace_dir)
    return eng, run


def describe(run: Run) -> str:
    """Where the window sat in the engine's admission, for the log."""
    c, t0 = run.counters, run.extra["t0"]
    lo, hi = run.window
    steps = sum(1 for s in run.steps if run.in_window(s.end))
    return (f"admission policy {c['policy']}; generator lateness "
            f"{traffic_mod.lateness(run.planned, t0)}; window steps {steps}, "
            f"held {c['held_steps']} (a request waiting while a slot was "
            f"free); shared cache index at open {c['index'].get('open')}, "
            f"at close {c['index'].get('close')}, reset at "
            f"{[round(t, 2) for t in c['resets']]} s (window "
            f"{lo - t0:.1f}-{hi - t0:.1f} s)")


def run_cell(cell, seed: int, seconds: float, trace: bool, peak: dict,
             tmp: str, t_start: float, log) -> tuple:
    """One run of a serve cell: ``(run, checks, memory_peak_bytes)``."""
    config, traffic = cell.config, cell.traffic
    trace_dir = os.path.join(tmp, "trace") if trace else None
    eng, run = window(cell, traffic, seed, seconds, tmp, trace_dir)
    run.peak = peak
    run.setup_s = run.window[0] - t_start
    memory = memory_peak_bytes()
    log(describe(run))
    eng.free()
    del eng
    gc.collect()
    chosen = sample(run.planned, traffic["check_requests"], seed)
    limit = config["limits"]["max_logit_gap"]
    if chosen:
        gaps = logit_gaps(config["arch"], seed, chosen)["f32"]
        value, n = float(gaps.max()), int(gaps.size)
    else:
        value, n = None, 0
    log(f"checked {len(chosen)} finished requests, {n} served tokens")
    checks = {"max_logit_gap": (value, limit)}
    if trace_dir:
        run.trace = tracereduce.reduce(tracereduce.find_xplane(trace_dir))
    return run, checks, memory


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, where the backend says."""
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in jax.local_devices()]
    return int(max(peaks_))
