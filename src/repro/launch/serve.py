"""Serving launcher: batched prefill + greedy decode on a checkpoint.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --reduced \
        --batch 4 --prompt-len 32 --max-new 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.compile_cache import enable_compile_cache
from repro.configs import get_arch
from repro.models import build_model
from repro.serve.decode import ServeConfig, generate


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    arch = get_arch(args.arch)
    if args.reduced:
        arch = arch.reduced()
    model = build_model(arch)
    params = model.init_params(jax.random.PRNGKey(args.seed))
    if args.checkpoint_dir:
        ckpt = CheckpointManager(args.checkpoint_dir)
        restored = ckpt.restore_latest({"params": params})
        if restored:
            _, tree, _ = restored
            params = tree["params"]
            print(f"[serve] restored checkpoint step {restored[0]}")

    rng = np.random.RandomState(args.seed)
    prompt = jnp.asarray(rng.randint(1, arch.vocab_size,
                                     (args.batch, args.prompt_len)), jnp.int32)
    extras = {}
    if arch.frontend == "patch":
        extras["patches"] = jnp.asarray(
            rng.randn(args.batch, arch.n_frontend_tokens, arch.d_model) * 0.05,
            jnp.dtype(arch.compute_dtype))
    if arch.frontend == "frame":
        extras["frames"] = jnp.asarray(
            rng.randn(args.batch, arch.n_frontend_tokens, arch.d_model) * 0.05,
            jnp.dtype(arch.compute_dtype))

    max_seq = args.prompt_len + args.max_new
    t0 = time.time()
    out = generate(model, params, prompt, args.max_new, max_seq,
                   ServeConfig(), extras=extras)
    dt = time.time() - t0
    n_tok = args.batch * args.max_new
    print(f"[serve] generated {out.shape} in {dt:.2f}s "
          f"({n_tok/dt:.1f} tok/s incl. compile)")
    print("[serve] first sequence:", np.asarray(out[0][:16]))
    return out


if __name__ == "__main__":
    main()
