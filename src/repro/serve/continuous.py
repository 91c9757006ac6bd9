"""Continuous (iteration-level) batching engine.

Slots share one global cache index: each engine step decodes one token for
every live slot at that index.  A request's rows sit below the index from
``start[slot]`` on; rows below ``start`` are a previous tenant's and are
masked by the attention visibility test, and rope positions are
request-local, so admission needs NO cache reset or copy for KV-cache
state.  How a prompt gets in depends on what the model's blocks are:

- **Whole prompt** (every block ``attn``, cache unsharded: no active mesh,
  no ``stream_kv``).  On admission at index I, one jitted prefill
  (``Model.prefill_into``) runs the prompt of length P, right-padded to a
  power-of-two bucket Pb, through the stack and writes its K/V into the
  slot's rows [I - P, I) (padding up to I - P + Pb, overwritten by the
  slot's own decode writes before it is read); ``start = I - P``.  The
  prefill's logits give the first token, and the slot rides the same
  step's decode with it.  A request fits when P <= I, I + max_new <=
  max_seq and I - P + Pb <= max_seq (``dynamic_update_slice`` would clamp,
  never silently).  When no slot is live every row is dead, so the index
  moves to max_seq // 2 (half behind for prompts, half ahead for answers),
  or as far as the head request needs to fit.  Every bucket compiles
  while the engine is built.
- **One token per step** (``local`` ring buffers, ``moe`` whose capacity
  drop differs between a prompt and a token, ``mlstm``/``slstm``/``hybrid``
  recurrent state, sharded caches).  A request admitted at index I gets
  ``start = I`` and consumes its prompt one token per step (piggyback
  prefill) while other slots keep generating, the orca-style schedule; it
  fits while I + prompt + answer <= max_seq, and the index rewinds to 0
  once every slot has drained and the queue head does not fit.

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
with overridable hooks (``_order_queue``, ``_execute``, ``_prefill``,
``_on_admit``, ``_on_token``, ``_on_done``).  ``serve.engine.ServeEngine``
builds the predictor-driven, telemetry-reporting engine on top of these
hooks.
"""
from __future__ import annotations

import dataclasses
import functools
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.sharding import active_mesh
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
PREFILL_SPAN = "serve.prefill"    # inside serve.admit, one per prefill

SMALLEST_BUCKET = 32


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list                 # token ids
    max_new: int
    # filled by the engine
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False


# One jitted program per (model, program): engines sharing a model reuse
# the same trace cache instead of paying a fresh jit per engine instance
# (the serve bench builds several engines per process).  The model
# reference in the value keeps the id() key stable for the cache's
# lifetime.
_STEP_FNS: dict = {}


def _jitted(model: Model, program: tuple, fn):
    """``fn`` jitted with the cache, its second argument, donated."""
    key = (id(model),) + program
    hit = _STEP_FNS.get(key)
    if hit is not None and hit[0] is model:
        return hit[1]
    jitted = jax.jit(fn, donate_argnums=(1,))
    _STEP_FNS[key] = (model, jitted)
    return jitted


def _jitted_step(model: Model, stream_kv: bool):
    def step_fn(params, cache, tokens, index, start):
        logits, cache = model.decode_step(params, cache, tokens, index,
                                          start=start, stream_kv=stream_kv)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    return _jitted(model, ("step", bool(stream_kv)), step_fn)


def _jitted_prefill(model: Model):
    """The whole-prompt prefill: (params, cache, tokens [1, Pb], slot, row,
    length) -> (first token [1], cache)."""
    def prefill_fn(params, cache, tokens, slot, row, length):
        logits, cache = model.prefill_into(params, cache, tokens, slot, row,
                                           length)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    return _jitted(model, ("prefill",), prefill_fn)


def prefill_buckets(max_seq: int) -> tuple:
    """Padded prompt lengths the whole-prompt path compiles: powers of two
    from ``SMALLEST_BUCKET`` below ``max_seq``, then ``max_seq``."""
    out, b = [], SMALLEST_BUCKET
    while b < max_seq:
        out.append(b)
        b *= 2
    return tuple(out) + (max_seq,)


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
        # full-length positional KV that a prefill and the decode step
        # compute alike, unsharded: the prompt goes in with one call
        self.whole_prompt = (all(k == "attn" for k in cfg.layer_pattern)
                             and not self.stream_kv
                             and active_mesh() is None)
        self.cache = model.init_cache(max_slots, max_seq)
        self.index = 0
        self.slots: list[Optional[Request]] = [None] * max_slots
        self.start = np.zeros(max_slots, np.int32)
        self.prompt_left = np.zeros(max_slots, np.int32)
        self.queue: deque[Request] = deque()
        self.steps = 0
        self.busy_slot_steps = 0
        self.prefills = 0
        self.prefill_tokens = 0
        self._step = _jitted_step(model, self.stream_kv)
        if self.whole_prompt:
            self.buckets = prefill_buckets(max_seq)
            self._prefill_fn = _jitted_prefill(model)
            self._compile_whole_prompt()

    def _compile_whole_prompt(self) -> None:
        """Compile, without running, what the whole-prompt path calls: the
        prefill at every bucket, and the decode step (an engine whose
        first request ends at its prefill has run no step yet), so that
        nothing compiles once traffic flows.  A jitted function's later
        calls find these compiles."""
        scalar = jnp.zeros((), jnp.int32)
        for b in self.buckets:
            self._prefill_fn.lower(self.params, self.cache,
                                   jnp.zeros((1, b), jnp.int32),
                                   scalar, scalar, scalar).compile()
        lower = getattr(self._step, "lower", None)   # a plain wrapper has none
        if lower is not None:
            lower(self.params, self.cache,
                  jnp.zeros((self.max_slots, 1), jnp.int32), scalar,
                  jnp.zeros((self.max_slots,), jnp.int32)).compile()

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

    def _bucket(self, prompt_len: int) -> int:
        return next(b for b in self.buckets if b >= prompt_len)

    def _fits(self, req: Request) -> bool:
        """Whether ``req`` can be admitted at the current index."""
        p, i = len(req.prompt), self.index
        if self.whole_prompt:
            return (p <= i and i + req.max_new <= self.max_seq
                    and i - p + self._bucket(p) <= self.max_seq)
        return i + p + req.max_new <= self.max_seq

    def _rebased_index(self, head: Request) -> int:
        """Where the whole-prompt path puts the index when no slot is live:
        ``max_seq // 2``, half the row behind for prompts and half ahead
        for answers, moved only as far as ``head`` needs to fit (at least
        its prompt; low enough for its answer and padded prompt).  A
        request that fits nowhere is left where it cannot fit."""
        p = len(head.prompt)
        if p > self.max_seq:
            return self.index
        hi = min(self.max_seq - head.max_new,
                 self.max_seq - self._bucket(p) + p)
        return max(p, min(self.max_seq // 2, hi))

    def _admit(self):
        if not self.queue or None not in self.slots:
            return
        self._order_queue()
        # lowest free slot first; a request whose prefill already gave its
        # last token frees its slot for the next one at once
        while self.queue and None in self.slots:
            slot = self.slots.index(None)
            if self.whole_prompt and self.slots.count(None) == self.max_slots:
                # no live slot: every row is a dead tenant's, so the
                # shared index may move anywhere
                self.index = self._rebased_index(self.queue[0])
            req = self.queue.popleft()
            if not self._fits(req):
                self.queue.appendleft(req)   # would overflow: wait
                break
            self.slots[slot] = req
            if self.whole_prompt:
                self.start[slot] = self.index - len(req.prompt)
            else:
                self.start[slot] = self.index
                self.prompt_left[slot] = len(req.prompt)
            if self.recurrent:
                # positional masking can't hide a previous tenant's
                # recurrent state — zero the slot's non-KV leaves
                self.cache = _reset_slot(self.cache, jnp.int32(slot))
            self._on_admit(req, slot)
            if self.whole_prompt:
                self._prefill(req, slot)

    def _prefill(self, req: Request, slot: int) -> None:
        """Put ``req``'s whole prompt into ``slot``'s rows behind the index
        with one call, and hand the request its first token (hook)."""
        p = len(req.prompt)
        b = self._bucket(p)
        row = self.index - p
        if row < 0 or row + b > self.max_seq:
            raise ValueError(f"prefill of {p} tokens (bucket {b}) at row "
                             f"{row} would be clamped to max_seq "
                             f"{self.max_seq}")
        tokens = np.zeros((1, b), np.int32)
        tokens[0, :p] = req.prompt
        with trace_span(PREFILL_SPAN):
            first, self.cache = self._prefill_fn(
                self.params, self.cache, jnp.asarray(tokens),
                jnp.int32(slot), jnp.int32(row), jnp.int32(p))
            first = int(np.asarray(first)[0])
        self.prefills += 1
        self.prefill_tokens += p
        req.generated.append(first)
        self._on_token(req, slot, first=True)
        if len(req.generated) >= req.max_new:
            self._release(req, slot)

    def _release(self, req: Request, slot: int) -> None:
        req.done = True
        self.slots[slot] = None
        self._on_done(req, slot)

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
        if not active and self.queue and not self.whole_prompt:
            # every slot is drained but the queue head would overflow the
            # shared cache region: all positions are dead tenants, so the
            # region is reclaimable — rewind and re-admit.  Still empty:
            # a request that can never fit.  (The whole-prompt path moved
            # the index in _admit already.)
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
                self._release(req, i)

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
                / max(self.steps * self.max_slots, 1),
                "prefills": self.prefills,
                "prefill_tokens": self.prefill_tokens}
