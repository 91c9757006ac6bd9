#!/usr/bin/env python3
"""Smoke run of the system's main path on a TPU, through its entry points.

    python chip_smoke.py             # one chip: kernels, predictor, serve
    python chip_smoke.py --chips 4   # four chips: multi-chip placement only

Everything runs in this one process: a second process could not reach the
chip this one holds.  Phases, in order:

1. device    -- anything but a TPU is refused; there is no CPU fallback.
2. kernels   -- every variant of ``runtime.registry.default_registry()`` at
   each of its shapes in the workloads' ``large`` preset, the Pallas blur
   (direct and separable) and the Pallas flash attention (forward and
   gradient, head_dim 128, GQA 32:4), each against its kernel's ``ref.py``.
   Pallas kernels run compiled.
3. predictor -- measures the five ``large`` workloads into a fresh tuning
   cache, fits NN+C, and runs each workload through ``Program.compile``
   under predicted-best dispatch against its pure-JAX reference.
4. serve     -- ``ServeEngine`` over yi-9b at its published widths in
   bfloat16, depth cut to fit one chip, answering requests; each answer is
   checked against a float32 reference.

``--chips 4`` runs only the multi-chip placement path: four dispatchers,
each bound to its own chip, run ``mixed_dag`` at ``large`` on the async
executor with ``jax.device_put`` moves, against the one-chip sequential run.

Each line names the device it ran on.  The last line of standard output is
the JSON verdict; a failure raises before it is printed and exits non-zero.
References run at ``jax.default_matmul_precision("highest")``: float32
matmuls and convolutions on the TPU default to bfloat16 passes, so each
comparison states its bound.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

import numpy as np

# largest |out - ref| over largest |ref|: float32 work at the TPU's default
# matmul precision against a "highest"-precision reference
KERNEL_REL_TOL = 2e-2
WORKLOAD_REL_TOL = 2e-2
# yi-9b (arXiv:2403.04652) has 48 layers; 24 of them, in bfloat16, plus the
# embedding and head take about 9.4 GB of the chip's 16 GB
SERVE_LAYERS = 24
# an engine token counts as right when its float32 reference logit is within
# this many standard deviations of the reference's top logit (a near-tie
# that bfloat16 rounding may break either way)
SERVE_MARGIN_STDS = 0.25
SERVE_CHECKED_TOKENS = 8

DEVICE = "?"


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {DEVICE} {msg}", flush=True)


def require(ok: bool, msg: str) -> None:
    if not ok:
        raise AssertionError(msg)


def rel_err(out, ref) -> float:
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    require(out.shape == ref.shape, f"shape {out.shape} != {ref.shape}")
    require(bool(np.all(np.isfinite(out))), "non-finite output")
    return float(np.max(np.abs(out - ref)) / max(np.max(np.abs(ref)), 1e-30))


def check(phase: str, what: str, err: float, bound: float) -> None:
    log(phase, f"{what} max_rel_err={err:.3e} (bound {bound:g})")
    require(err <= bound, f"{phase}: {what} error {err} above {bound}")


def require_tpu(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); this script runs only on "
                         "the chip")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPU "
                         f"devices, JAX found {len(devs)}")
    return devs


# --------------------------------------------------------------------------
# phase 2: kernels
# --------------------------------------------------------------------------

def _kernel_refs():
    from repro.kernels.blur import ref as blur_ref
    from repro.kernels.conv2d import ref as conv2d_ref
    from repro.kernels.flash_attention import ref as fa_ref
    from repro.kernels.matmul import ref as matmul_ref
    from repro.kernels.matvec import ref as matvec_ref
    from repro.kernels.maxpool import ref as maxpool_ref

    def attention(q, k, v, p):
        # the registry's attention variants take [B, S, H, D]
        t = lambda x: x.transpose(0, 2, 1, 3)
        return t(fa_ref.attention(t(q), t(k), t(v), causal=True))

    return {
        "matmul": lambda a, b, p: matmul_ref.matmul(a, b),
        "matvec": lambda a, x, p: matvec_ref.matvec(a, x),
        "conv2d": lambda a, w, p: conv2d_ref.conv2d(a, w),
        "maxpool": lambda a, p: maxpool_ref.maxpool(a, r=p["r"], s=p["s"]),
        "blur": lambda a, p: blur_ref.blur(a),
        "flash_attention": attention,
    }


def node_operands(programs, seed: int) -> list:
    """``(kernel, params, args)`` for every distinct (kernel, params) node
    of ``programs``, operands drawn from ``seed`` at the nodes' avals."""
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)
    out, seen = [], set()
    for prog in programs:
        avals = {s.name: s.aval for s in prog.inputs}
        for node in prog.nodes:
            avals[node.name] = node.aval
            key = (node.kernel, tuple(sorted(node.params.items())))
            if key in seen:
                continue
            seen.add(key)
            args = tuple(jnp.asarray(rng.rand(*avals[d].shape) - 0.5,
                                     np.dtype(str(avals[d].dtype)))
                         for d in node.deps)
            out.append((node.kernel, dict(node.params), args))
    return out


def phase_kernels(size: str, seed: int, *, attention_shape=(1, 32, 4, 512,
                                                            128)) -> int:
    import jax
    import jax.numpy as jnp

    from repro.kernels import default_interpret
    from repro.kernels.blur import ops as blur_ops, ref as blur_ref
    from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
    from repro.runtime import default_registry
    from repro.workloads import get_workload, workload_names

    log("kernels", f"Pallas interpret mode: {default_interpret()}")
    reg = default_registry()
    refs = _kernel_refs()
    programs = [get_workload(n).build(size=size, registry=reg).program
                for n in workload_names()]
    n_calls = 0
    for kernel, params, args in node_operands(programs, seed):
        with jax.default_matmul_precision("highest"):
            ref = refs[kernel](*args, params)
        for v in reg.variants(kernel):
            out = jax.block_until_ready(v.call(args, params))
            check("kernels", f"{kernel}/{v.name} {params}", rel_err(out, ref),
                  KERNEL_REL_TOL)
            n_calls += 1

    # the Pallas blur, which the registry reaches only through its jnp
    # schedules, at the image pipeline's shape
    p = get_workload("image_pipeline").presets[size]
    a = jnp.asarray(np.random.RandomState(seed).rand(p["m"], p["n"]) - 0.5,
                    jnp.float32)
    ref = blur_ref.blur(a)
    for separable in (False, True):
        out = blur_ops.blur(a, separable=separable,
                            interpret=default_interpret())
        check("kernels", f"pallas blur separable={separable} "
              f"{tuple(a.shape)}", rel_err(out, ref), KERNEL_REL_TOL)
        n_calls += 1

    # the Pallas flash attention, forward and gradient, at yi-9b's head
    # geometry in bfloat16
    b, h, kv, s, d = attention_shape
    rng = np.random.RandomState(seed + 1)
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, kv, s, d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, kv, s, d), jnp.bfloat16)
    ct = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * ct)

    kern = lambda q, k, v: fa_ops.attention(q, k, v, causal=True,
                                            interpret=default_interpret())
    oracle = lambda q, k, v: fa_ref.attention(q, k, v, causal=True)
    out = jax.jit(kern)(q, k, v)
    grads = jax.jit(jax.grad(loss(kern), argnums=(0, 1, 2)))(q, k, v)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(oracle)(q, k, v)
        ref_grads = jax.jit(jax.grad(loss(oracle), argnums=(0, 1, 2)))(q, k, v)
    geo = f"b={b} h={h} kv={kv} s={s} d={d} bf16"
    check("kernels", f"pallas flash_attention fwd {geo}", rel_err(out, ref),
          KERNEL_REL_TOL)
    for name, g, rg in zip(("dq", "dk", "dv"), grads, ref_grads):
        check("kernels", f"pallas flash_attention grad {name} {geo}",
              rel_err(g, rg), KERNEL_REL_TOL)
    return n_calls + 2


# --------------------------------------------------------------------------
# phase 3: predictor
# --------------------------------------------------------------------------

def phase_predictor(size: str, seed: int, root: str, *,
                    fit_epochs: int = 4000) -> None:
    import jax

    from repro.runtime import (Dispatcher, TuningCache, default_registry,
                               measure_from_programs)
    from repro.workloads import get_workload, workload_names

    reg = default_registry()
    built = {n: get_workload(n).build(size=size, registry=reg, seed=seed)
             for n in workload_names()}
    cache = TuningCache(root=root)
    t0 = time.perf_counter()
    kernels = measure_from_programs(
        Dispatcher(registry=reg, cache=cache),
        [b.program for b in built.values()], seed=seed,
        fit_epochs=fit_epochs, reset=True)
    log("predictor", f"measured and fitted {kernels} at {size} in "
        f"{time.perf_counter() - t0:.1f}s (cache {cache.dir})")
    for kernel in kernels:
        e = cache.entry(kernel)
        log("predictor", f"{kernel}: fit MAPE {e.fit_mape:.2f}% over "
            f"{e.n_rows} rows, variants {e.variant_names}")
    for name, b in built.items():
        disp = Dispatcher(registry=reg, cache=cache)
        compiled = b.program.compile(devices={"chip": disp},
                                     bindings=b.bindings)
        outs = compiled()
        outs = outs if isinstance(outs, tuple) else (outs,)
        with jax.default_matmul_precision("highest"):
            refs = b.reference()
        chosen = {}
        for sel in disp.selections:
            chosen.setdefault(sel.kernel, set()).add(sel.chosen)
        log("predictor", f"{name}: chosen "
            f"{ {k: sorted(v) for k, v in sorted(chosen.items())} }, "
            f"modes {sorted({s.mode for s in disp.selections})}")
        err = max(rel_err(o, r) for o, r in zip(outs, refs))
        check("predictor", f"{name} vs reference ({len(outs)} outputs)", err,
              WORKLOAD_REL_TOL)


# --------------------------------------------------------------------------
# phase 4: serve
# --------------------------------------------------------------------------

def serve_requests(n: int, prompt_lens, max_new: int, vocab: int,
                   seed: int) -> list:
    from repro.serve.request import ServeRequest
    rng = np.random.RandomState(seed)
    lens = rng.randint(prompt_lens[0], prompt_lens[1] + 1, size=n)
    return [ServeRequest(rid=i, prompt=[int(t) for t in
                                        rng.randint(1, vocab, size=int(p))],
                         max_new=max_new) for i, p in enumerate(lens)]


def phase_serve(arch_name: str, n_layers: int, seed: int, root: str, *,
                n_requests: int = 8, prompt_lens=(64, 256), max_new: int = 16,
                slots: int = 4) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_arch
    from repro.models import build_model
    from repro.runtime import TuningCache
    from repro.serve.decode import generate
    from repro.serve.engine import ServeEngine

    full = get_arch(arch_name)
    cfg = dataclasses.replace(full, n_layers=n_layers,
                              param_dtype="bfloat16")
    model = build_model(cfg)
    log("serve", f"{arch_name}: d_model {cfg.d_model}, {cfg.n_heads} heads, "
        f"{cfg.n_kv_heads} KV heads, head_dim {cfg.head_dim}, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}; {n_layers} of "
        f"{full.n_layers} layers, bfloat16 parameters")
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(model.init_params)(jax.random.PRNGKey(seed)))
    nbytes = sum(x.size * x.dtype.itemsize
                 for x in jax.tree_util.tree_leaves(params))
    log("serve", f"initialized {nbytes / 1e9:.2f} GB of parameters in "
        f"{time.perf_counter() - t0:.1f}s")

    max_seq = 2 * (prompt_lens[1] + max_new)
    engine = ServeEngine(model, TuningCache(root=root), params=params,
                         max_slots=slots, max_seq=max_seq, admission="fifo")
    t0 = time.perf_counter()
    engine.run_trace(serve_requests(1, (8, 8), 2, cfg.vocab_size, seed + 1))
    log("serve", f"warm-up request (compiles the step) took "
        f"{time.perf_counter() - t0:.1f}s")
    reqs = serve_requests(n_requests, prompt_lens, max_new, cfg.vocab_size,
                          seed)
    stats = engine.run_trace(reqs)
    require(all(len(r.generated) == max_new for r in reqs),
            f"serve: generated {[len(r.generated) for r in reqs]} tokens")
    ttft = np.array([r.ttft_s for r in reqs])
    log("serve", f"{len(reqs)} requests (prompts {[len(r.prompt) for r in reqs]}, "
        f"{max_new} new tokens, {slots} slots): {stats['engine_steps']} "
        f"engine steps in {stats['wall_s']:.2f}s, "
        f"{stats['goodput_tok_s']:.1f} tokens/s, TTFT mean "
        f"{ttft.mean() * 1e3:.1f} ms, max {ttft.max() * 1e3:.1f} ms")

    # reference 1: decode.generate on each request alone (bfloat16 path)
    # reference 2: a float32 forward over prompt + engine tokens
    ref_model = build_model(dataclasses.replace(cfg,
                                                compute_dtype="float32"))
    fwd = jax.jit(lambda p, t: ref_model.forward(p, {"tokens": t},
                                                 remat=False)[0])
    n_check = min(SERVE_CHECKED_TOKENS, max_new)
    ref_len = prompt_lens[1] + n_check - 1
    same_as_generate = near_ties = 0
    worst = 0.0
    for r in reqs:
        prompt = jnp.asarray([r.prompt], jnp.int32)
        alone = np.asarray(generate(model, params, prompt, n_check,
                                    len(r.prompt) + n_check))[0]
        same_as_generate += int(np.sum(alone == np.asarray(
            r.generated[:n_check])))
        # padded to one length (one compile); the model is causal, so the
        # padding cannot change the logits read below
        tokens = r.prompt + r.generated[:n_check - 1]
        tokens = jnp.asarray([tokens + [0] * (ref_len - len(tokens))],
                             jnp.int32)
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(fwd(params, tokens)[0], np.float64)
        for t in range(n_check):
            row = logits[len(r.prompt) - 1 + t]
            margin = (row.max() - row[r.generated[t]]) / row.std()
            worst = max(worst, margin)
            near_ties += int(margin > 0)
            if margin > SERVE_MARGIN_STDS:
                raise AssertionError(
                    f"serve: request {r.rid} token {t} = {r.generated[t]} "
                    f"is {margin:.3f} std below the float32 reference's top "
                    f"logit (bound {SERVE_MARGIN_STDS})")
    total = n_check * len(reqs)
    log("serve", f"float32 reference: {total - near_ties}/{total} engine "
        f"tokens are its argmax, the rest within {worst:.4f} std of it "
        f"(bound {SERVE_MARGIN_STDS}); {same_as_generate}/{total} equal "
        "decode.generate on the request alone")


# --------------------------------------------------------------------------
# --chips 4: multi-chip placement
# --------------------------------------------------------------------------

def phase_multichip(devices, size: str, seed: int, root: str) -> None:
    import jax

    from repro.exec import CommModel, device_pair_transfer
    from repro.runtime import (Dispatcher, TuningCache, default_registry,
                               measure_from_programs)
    from repro.workloads import get_workload

    class Recording(Dispatcher):
        """Keeps the devices of every array it produced."""

        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            self.placed = []

        def dispatch(self, kernel, *args, **kwargs):
            out = super().dispatch(kernel, *args, **kwargs)
            self.placed.append(frozenset(out.devices()))
            return out

    reg = default_registry()
    built = get_workload("mixed_dag").build(size=size, registry=reg,
                                            seed=seed)
    cache = TuningCache(root=os.path.join(root, "kernels"))
    measure_from_programs(Dispatcher(registry=reg, cache=cache,
                                     device=devices[0]),
                          [built.program], seed=seed, reset=True)
    names = {f"chip{d.id}": d for d in devices}
    comm = CommModel(TuningCache(root=os.path.join(root, "comm")))
    for a, da in names.items():
        for b, db in names.items():
            if a != b:
                comm.measure_pair(a, b, device_pair_transfer(da, db))
    log("multichip", "comm model (seconds for 1 MiB): " + ", ".join(
        f"{a}->{b} {comm.predict(a, b, 2 ** 20):.2e}"
        for a in names for b in names if a != b))

    disps = {n: Recording(registry=reg, cache=cache, device=d)
             for n, d in names.items()}
    compiled = built.program.compile(devices=disps, bindings=built.bindings,
                                     executor="async", comm=comm)
    outs = compiled()
    single = built.program.compile(
        devices={"chip0": Dispatcher(registry=reg, cache=cache,
                                     device=devices[0])},
        bindings=built.bindings, executor="sequential")
    ref = single()
    placement = {}
    for node in built.program.nodes:
        placement.setdefault(compiled.device_of(node.name), []).append(
            node.kernel)
    log("multichip", f"mixed_dag/{size}: {len(built.program.nodes)} nodes "
        f"placed {dict((k, len(v)) for k, v in sorted(placement.items()))}, "
        f"{len(compiled.transfers)} planned transfers, predicted makespan "
        f"{compiled.makespan * 1e3:.3f} ms")

    # (a) the four-chip run computes what one chip computes
    for i, (o, r) in enumerate(zip(outs, ref)):
        if not np.array_equal(np.asarray(o), np.asarray(r)):
            raise AssertionError(f"multichip: output {i} differs from the "
                                 "one-chip sequential run (max abs diff "
                                 f"{np.max(np.abs(np.asarray(o) - np.asarray(r)))})")
    log("multichip", f"(a) {len(outs)} outputs equal the one-chip "
        "sequential run")
    # (b) node outputs live on the chip of the dispatcher that made them,
    # and on at least two chips
    used = set()
    for name, d in disps.items():
        for devs in d.placed:
            require(devs == {names[name]},
                    f"multichip: {name} produced an array on {devs}")
        if d.placed:
            used.add(name)
    if len(used) < 2:
        raise AssertionError(f"multichip: node outputs on {sorted(used)} "
                             "only; the schedule used one chip")
    log("multichip", f"(b) node outputs on {len(used)} chips: " + ", ".join(
        f"{n} {len(disps[n].placed)}" for n in sorted(used)))
    # (c) every planned transfer ran
    ran = [e for e in compiled.last_trace.events if e.kind == "transfer"]
    planned = {t.name for t in compiled.transfers}
    if {e.name for e in ran} != planned or len(ran) != len(planned):
        raise AssertionError(f"multichip: planned transfers {sorted(planned)}"
                             f", ran {sorted(e.name for e in ran)}")
    log("multichip", f"(c) all {len(planned)} planned transfers ran "
        f"({sum(t.nbytes for t in compiled.transfers)} bytes)")


def main(argv=None) -> int:
    global DEVICE
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip placement path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = require_tpu(args.chips)
    dev = devs[0]
    DEVICE = f"{dev.platform}:{dev.device_kind}"
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.compile_cache import enable_compile_cache
    log("device", f"{len(devs)} devices, compile cache "
        f"{enable_compile_cache()}")

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        if args.chips == 4:
            phase_multichip(devs[:4], "large", args.seed, tmp)
        else:
            t0 = time.perf_counter()
            n = phase_kernels("large", args.seed)
            log("kernels", f"{n} kernel calls passed in "
                f"{time.perf_counter() - t0:.1f}s")
            t0 = time.perf_counter()
            phase_predictor("large", args.seed,
                            os.path.join(tmp, "tunecache"))
            log("predictor", f"done in {time.perf_counter() - t0:.1f}s")
            t0 = time.perf_counter()
            phase_serve("yi-9b", SERVE_LAYERS, args.seed,
                        os.path.join(tmp, "serve_tunecache"))
            log("serve", f"done in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
