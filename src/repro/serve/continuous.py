"""Continuous (iteration-level) batching engine.

Slots share one global cache index; a request admitted at step t gets
``start[slot] = t`` — its stale cache region is masked by the attention
visibility test and its rope positions are request-local, so NO cache reset
or copy is needed on admission for KV-cache state.  Prompt tokens are
consumed one per step (piggyback/chunked prefill): a freshly admitted
request "catches up" while other slots keep generating, which is exactly
the orca-style schedule that keeps the decode batch full.

Recurrent state (SSM/xLSTM/hybrid) has no positional masking to hide
behind, so on admission the new tenant's slot is zeroed in every
non-KV cache leaf (``_reset_slot``) — with that, any
``layer_pattern`` of attn/local/moe/mlstm/slstm/hybrid blocks can
continuously batch; only encoder-decoder archs are out.

Admission order can be cost-aware: with a fitted NN+C model the queue is
served shortest-predicted-job-first (the paper's runtime mapping decision,
§1).  The predictors live in the runtime tuning cache as the split
``prefill_step``/``decode_step`` pseudo-kernels (see ``serve.policy``), so
every engine on the same hardware fingerprint shares the fitted models.

``ContinuousBatcher`` is the mechanism layer: queue/slot/token accounting
with overridable hooks (``_order_queue``, ``_execute``, ``_on_admit``,
``_on_token``, ``_on_done``).  ``serve.engine.ServeEngine`` builds the
predictor-driven, telemetry-reporting engine on top of these hooks.
"""
from __future__ import annotations

import dataclasses
import functools
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.registry import Model
from repro.models.transformer import KV_LEAVES
from repro.obs.telemetry import trace_span, trace_step
# Back-compat re-exports: the admission cost model moved to serve.policy
# when the decode_step pseudo-kernel split into prefill_step/decode_step.
from repro.serve.policy import (  # noqa: F401
    ColdCacheError, DECODE_STEP_FEATURES, DECODE_STEP_KERNEL,
    PREFILL_STEP_FEATURES, PREFILL_STEP_KERNEL, cost_model_from_cache,
    record_request_time, split_cost_model_from_cache)

_RECURRENT_KINDS = frozenset({"mlstm", "slstm", "hybrid"})
_SUPPORTED_KINDS = frozenset({"attn", "local", "moe"}) | _RECURRENT_KINDS

# the step's spans on the profiler's trace
STEP_SPAN = "serve.step"
ADMIT_SPAN = "serve.admit"
ASSEMBLE_SPAN = "serve.assemble"
EXECUTE_SPAN = "serve.execute"
EMIT_SPAN = "serve.emit"


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list                 # token ids
    max_new: int
    # filled by the engine
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False


# One jitted step per (model, stream_kv): engines sharing a model reuse the
# same trace cache instead of paying a fresh jit per engine instance (the
# serve bench builds several engines per process).  The model reference in
# the value keeps the id() key stable for the cache's lifetime.
_STEP_FNS: dict = {}


def _jitted_step(model: Model, stream_kv: bool):
    key = (id(model), bool(stream_kv))
    hit = _STEP_FNS.get(key)
    if hit is not None and hit[0] is model:
        return hit[1]

    def step_fn(params, cache, tokens, index, start):
        logits, cache = model.decode_step(params, cache, tokens, index,
                                          start=start, stream_kv=stream_kv)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    fn = jax.jit(step_fn, donate_argnums=(1,))
    _STEP_FNS[key] = (model, fn)
    return fn


def _zero_slot(tree: dict, slot, axis: int) -> dict:
    out = {}
    for name, leaf in tree.items():
        if isinstance(leaf, dict):
            out[name] = _zero_slot(leaf, slot, axis)
        elif name in KV_LEAVES:      # positional: masked via start, kept
            out[name] = leaf
        else:
            row = jnp.zeros(leaf.shape[:axis] + leaf.shape[axis + 1:],
                            leaf.dtype)
            out[name] = jax.lax.dynamic_update_index_in_dim(
                leaf, row, slot, axis)
    return out


@functools.partial(jax.jit, donate_argnums=(0,))
def _reset_slot(cache: dict, slot) -> dict:
    """Zero one slot's recurrent state across the whole cache tree.  The
    batch axis is 1 under "scan" (leaves are period-stacked) and 0 under
    "tail"."""
    new = {}
    if "scan" in cache:
        new["scan"] = {k: _zero_slot(v, slot, 1)
                       for k, v in cache["scan"].items()}
    new["tail"] = {k: _zero_slot(v, slot, 0)
                   for k, v in cache["tail"].items()}
    return new


class ContinuousBatcher:
    def __init__(self, model: Model, params, *, max_slots: int,
                 max_seq: int, cost_model=None, stream_kv: bool = False):
        cfg = model.cfg
        assert not cfg.encdec, \
            "continuous batching does not support encoder-decoder archs"
        assert all(k in _SUPPORTED_KINDS for k in cfg.layer_pattern), \
            f"continuous batching supports {sorted(_SUPPORTED_KINDS)} " \
            f"blocks, got {cfg.layer_pattern}"
        self.model = model
        self.params = params
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.cost_model = cost_model
        self.stream_kv = bool(stream_kv)
        self.recurrent = any(k in _RECURRENT_KINDS
                             for k in cfg.layer_pattern)
        self.cache = model.init_cache(max_slots, max_seq)
        self.index = 0
        self.slots: list[Optional[Request]] = [None] * max_slots
        self.start = np.zeros(max_slots, np.int32)
        self.prompt_left = np.zeros(max_slots, np.int32)
        self.queue: deque[Request] = deque()
        self.steps = 0
        self.busy_slot_steps = 0
        self._step = _jitted_step(model, self.stream_kv)

    # -- queue ---------------------------------------------------------------
    def submit(self, req: Request):
        self.queue.append(req)

    def _order_queue(self) -> None:
        """Reorder the waiting queue before admission (hook).  Base policy:
        shortest-predicted-job-first when a cost model is set, else FIFO."""
        if self.cost_model is not None:
            jobs = sorted(self.queue,
                          key=lambda r: self.cost_model(len(r.prompt),
                                                        r.max_new))
            self.queue = deque(jobs)

    def _admit(self):
        free = [i for i, s in enumerate(self.slots) if s is None]
        if not free or not self.queue:
            return
        self._order_queue()
        for slot in free:
            if not self.queue:
                break
            req = self.queue.popleft()
            if self.index + len(req.prompt) + req.max_new > self.max_seq:
                self.queue.appendleft(req)   # would overflow: wait for reset
                break
            self.slots[slot] = req
            self.start[slot] = self.index
            self.prompt_left[slot] = len(req.prompt)
            if self.recurrent:
                # positional masking can't hide a previous tenant's
                # recurrent state — zero the slot's non-KV leaves
                self.cache = _reset_slot(self.cache, jnp.int32(slot))
            self._on_admit(req, slot)

    # -- hooks (no-ops here; ServeEngine instruments them) -------------------
    def _on_admit(self, req: Request, slot: int) -> None:
        pass

    def _on_token(self, req: Request, slot: int, first: bool) -> None:
        pass

    def _on_done(self, req: Request, slot: int) -> None:
        pass

    # -- one engine iteration ------------------------------------------------
    def _assemble(self, active: list) -> np.ndarray:
        """Token batch for this iteration: the next prompt token for slots
        still prefilling, else the last generated token."""
        tokens = np.zeros((self.max_slots, 1), np.int32)
        for i in active:
            req = self.slots[i]
            consumed = len(req.prompt) - int(self.prompt_left[i])
            if self.prompt_left[i] > 0:
                tokens[i, 0] = req.prompt[consumed]
            else:
                tokens[i, 0] = req.generated[-1]
        return tokens

    def _execute(self, tokens: np.ndarray) -> np.ndarray:
        """Run one model step (hook — ServeEngine routes this through a
        compiled ``repro.api`` program on the executor)."""
        next_tok, self.cache = self._step(
            self.params, self.cache, jnp.asarray(tokens),
            jnp.int32(self.index), jnp.asarray(self.start))
        return np.asarray(next_tok)

    def _admit_active(self) -> list:
        """Admit what fits; the active slots (empty: no work)."""
        self._admit()
        active = [i for i, s in enumerate(self.slots) if s is not None]
        if not active and self.queue:
            # every slot is drained but the queue head would overflow the
            # shared cache region: all positions are dead tenants, so the
            # region is reclaimable — rewind and re-admit.  Still empty:
            # a request that can never fit.
            self.index = 0
            self._admit()
            active = [i for i, s in enumerate(self.slots) if s is not None]
        return active

    def _emit(self, active: list, next_tok: np.ndarray) -> None:
        """Hand each active slot its token; release finished requests."""
        for i in active:
            req = self.slots[i]
            if self.prompt_left[i] > 1:
                self.prompt_left[i] -= 1          # still prefilling: ignore
            else:
                if self.prompt_left[i] == 1:
                    self.prompt_left[i] = 0       # last prompt token
                req.generated.append(int(next_tok[i, 0]))
                self._on_token(req, i, first=len(req.generated) == 1)
            if len(req.generated) >= req.max_new:
                req.done = True
                self.slots[i] = None
                self._on_done(req, i)

    def step(self) -> bool:
        """Returns True while there is work.  On the profiler's trace a step
        is a ``serve.step`` span carrying its number, holding
        ``serve.admit``, ``serve.assemble``, ``serve.execute`` and
        ``serve.emit``."""
        with trace_step(STEP_SPAN, self.steps):
            with trace_span(ADMIT_SPAN):
                active = self._admit_active()
            if not active:
                return False
            with trace_span(ASSEMBLE_SPAN):
                tokens = self._assemble(active)
            with trace_span(EXECUTE_SPAN):
                next_tok = self._execute(tokens)
            with trace_span(EMIT_SPAN):
                self._emit(active, next_tok)
            self.index += 1
            self.steps += 1
            self.busy_slot_steps += len(active)
        return True

    def run(self, max_steps: int = 100000) -> dict:
        while self.step():
            if self.steps >= max_steps:
                break
        return {"engine_steps": self.steps,
                "occupancy": self.busy_slot_steps
                / max(self.steps * self.max_slots, 1)}
