import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds ShapeDtypeStruct stand-ins for params, optimizer
state, batch and KV caches (NO allocation), jits the train_step/serve_step
with explicit in/out shardings, lowers and compiles against the production
mesh, and records memory_analysis / cost_analysis / collective bytes into a
JSON results file (incremental — finished cells are skipped on re-run).

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, get_arch, get_shape, shape_applicable
from repro.dist import sharding as shd
from repro.launch import roofline
from repro.launch.mesh import make_production_mesh
from repro.models import build_model, module
from repro.optim.adamw import AdamW, AdamWState
from repro.serve.decode import ServeConfig, make_serve_step
from repro.train.step import TrainStepConfig, make_train_step


def _opt_state_specs(param_specs):
    """ShapeDtypeStruct tree for AdamW state mirroring the param tree."""
    f32 = lambda s: dataclasses.replace(s)  # same dtype/shape as params
    return AdamWState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        mu=module.tree_map_specs(f32, param_specs),
        nu=module.tree_map_specs(f32, param_specs),
    )


def _replicated(mesh):
    return NamedSharding(mesh, P())


# the chip the production mesh describes; its peak rates price the roofline
DESCRIBED_DEVICE_KIND = "TPU v5 lite"

VARIANTS = ("localattn", "moelocal", "moeshard", "sp", "bigtile", "rematdots", "bf16norm", "fulldp", "ring")


def build_cell(arch_name: str, shape_name: str, mesh, *,
               step_cfg: TrainStepConfig | None = None,
               variant: str = ""):
    """Returns (fn, arg_shapes, in_shardings, out_shardings, donate).

    ``variant`` is a '+'-separated list of §Perf optimisation names:
      localattn — banded sliding-window attention (O(S*2w))
      moelocal  — per-data-shard MoE dispatch capacity
      sp        — sequence-parallel activations over the model axis
      bigtile   — 512x2048 flash-attention tiles (fewer accumulator sweeps)
    """
    arch = get_arch(arch_name)
    shape = get_shape(shape_name)
    vset = set(v for v in variant.split("+") if v)
    unknown = vset - set(VARIANTS)
    if unknown:
        raise ValueError(f"unknown variants {unknown}")
    step_cfg = step_cfg or TrainStepConfig()
    if "localattn" in vset:
        step_cfg = dataclasses.replace(step_cfg, local_block=True)
    if "bigtile" in vset:
        step_cfg = dataclasses.replace(step_cfg, k_chunk=2048)
    if "rematdots" in vset:
        step_cfg = dataclasses.replace(step_cfg, remat_policy="dots")
    if "ring" in vset:
        step_cfg = dataclasses.replace(step_cfg, ring=True)
    if "moelocal" in vset:
        arch = dataclasses.replace(arch, moe_dispatch="local")
    if "moeshard" in vset:
        arch = dataclasses.replace(arch, moe_dispatch="shardmap")
    if "bf16norm" in vset:
        arch = dataclasses.replace(arch, norm_impl="bf16_apply")
    seq_parallel = "sp" in vset
    full_dp = "fulldp" in vset
    model = build_model(arch)

    if shape.is_decode:
        rules = shd.serve_rules(long_context=(shape.kind == "long_decode"))
        if arch.family == "ssm":
            rules = shd.ShardingRules({**rules.rules, "head_dim": "model"})
        # serving weights are bf16 (decode reads every weight once per token;
        # fp32 masters + per-step converts would double the dominant traffic)
        if arch.param_dtype == "float32":
            arch = dataclasses.replace(arch, param_dtype="bfloat16")
            model = build_model(arch)
        param_specs = model.param_specs()
        cache_specs = model.cache_specs(shape.global_batch, shape.seq_len)
        p_shard = shd.tree_shardings(param_specs, mesh, rules)
        c_shard = shd.tree_shardings(cache_specs, mesh, rules)
        tok_sds = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
        tok_shard = NamedSharding(
            mesh, rules.spec(["batch", None], shape=tok_sds.shape, mesh=mesh))
        serve_step = make_serve_step(model, ServeConfig())

        def fn(params, cache, tokens, cache_index):
            with shd.use_mesh(mesh, rules):
                return serve_step(params, cache, tokens, cache_index)

        args = (module.shape_tree(param_specs), module.shape_tree(cache_specs),
                tok_sds, jax.ShapeDtypeStruct((), jnp.int32))
        in_sh = (p_shard, c_shard, tok_shard, _replicated(mesh))
        out_sh = (tok_shard, NamedSharding(mesh, P()), c_shard)
        donate = (1,)
        return fn, args, in_sh, out_sh, donate, model, shape

    if shape.kind == "prefill":
        # inference-prefill lowers forward + KV-cache fill + first sample
        rules = shd.serve_rules(long_context=False)
        if arch.param_dtype == "float32":
            arch = dataclasses.replace(arch, param_dtype="bfloat16")
            model = build_model(arch)
        param_specs = model.param_specs()
        cache_specs = model.cache_specs(shape.global_batch, shape.seq_len)
        p_shard = shd.tree_shardings(param_specs, mesh, rules)
        c_shard = shd.tree_shardings(cache_specs, mesh, rules)
        batch_specs = model.input_specs(shape)
        batch_specs.pop("labels", None)
        b_shard = shd.batch_shardings(batch_specs, mesh, rules)
        from repro.serve.decode import make_prefill_step
        prefill_step = make_prefill_step(model, shape.seq_len,
                                         ServeConfig(k_chunk=step_cfg.k_chunk))

        def fn(params, batch):
            with shd.use_mesh(mesh, rules):
                return prefill_step(params, batch)

        tok_shard = b_shard["tokens"]
        args = (module.shape_tree(param_specs), batch_specs)
        in_sh = (p_shard, b_shard)
        out_sh = (tok_shard, c_shard)
        donate = ()
        return fn, args, in_sh, out_sh, donate, model, shape

    # training cells lower the full train step
    rules = shd.train_rules(fsdp=True, seq_parallel=seq_parallel)
    if full_dp:
        # attention-free / small-head archs: the TP axis is idle for the
        # recurrent core — use it for 256-way data parallelism instead
        rules = shd.ShardingRules({**rules.rules,
                                   "batch": ("pod", "data", "model"),
                                   "mlp": None, "heads": None,
                                   "vocab": "model",
                                   "embed": ("data", "model")})
    param_specs = model.param_specs()
    p_shard = shd.tree_shardings(param_specs, mesh, rules)
    opt_specs = _opt_state_specs(param_specs)
    o_shard = AdamWState(step=_replicated(mesh),
                         mu=shd.tree_shardings(param_specs, mesh, rules),
                         nu=shd.tree_shardings(param_specs, mesh, rules))
    batch_specs = model.input_specs(shape)
    b_shard = shd.batch_shardings(batch_specs, mesh, rules)
    optimizer = AdamW(learning_rate=1e-4)
    train_step = make_train_step(model, optimizer, step_cfg)

    def fn(params, opt_state, batch):
        with shd.use_mesh(mesh, rules):
            return train_step(params, opt_state, batch)

    args = (module.shape_tree(param_specs), module.shape_tree(opt_specs),
            batch_specs)
    in_sh = (p_shard, o_shard, b_shard)
    out_sh = (p_shard, o_shard, None)
    donate = (0, 1)
    return fn, args, in_sh, out_sh, donate, model, shape


def run_cell(arch_name: str, shape_name: str, *, multi_pod: bool = False,
             step_cfg: TrainStepConfig | None = None,
             variant: str = "", verbose: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    chips = mesh.devices.size
    t0 = time.time()
    fn, args, in_sh, out_sh, donate, model, shape = build_cell(
        arch_name, shape_name, mesh, step_cfg=step_cfg, variant=variant)
    jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=donate)
    lowered = jitted.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    cost = compiled.cost_analysis()
    try:
        ma = compiled.memory_analysis()
        mem = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "total_bytes": int(ma.argument_size_in_bytes
                               + ma.output_size_in_bytes
                               + ma.temp_size_in_bytes
                               - ma.alias_size_in_bytes),
        }
    except Exception as e:                       # pragma: no cover
        mem = {"error": str(e)}
    hlo = compiled.as_text()
    mf = roofline.model_flops(model, shape)
    # the forced host mesh stands in for a pod of TPU v5e chips
    report = roofline.analyze(arch_name, shape_name, mesh_name, chips,
                              cost, hlo, mf, memory_stats=mem,
                              device_kind=DESCRIBED_DEVICE_KIND)
    result = report.to_dict()
    result.update(lower_s=t_lower, compile_s=t_compile, ok=True,
                  variant=variant)
    if verbose:
        print(f"[dryrun] {arch_name} x {shape_name} x {mesh_name}"
              f"{' [' + variant + ']' if variant else ''}: "
              f"compile {t_compile:.1f}s | per-dev flops {report.per_device_flops:.3e} "
              f"| mem/dev {mem.get('total_bytes', 0)/1e9:.2f} GB "
              f"| bottleneck {report.bottleneck} "
              f"(c={report.compute_s*1e3:.2f}ms m={report.memory_s*1e3:.2f}ms "
              f"coll={report.collective_s*1e3:.2f}ms)")
    return result


def cells(include_skips: bool = False):
    for arch_name, arch in ARCHS.items():
        for shape_name, shape in SHAPES.items():
            runs, reason = shape_applicable(arch, shape)
            if runs or include_skips:
                yield arch_name, shape_name, runs, reason


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--variant", default="",
                    help="'+'-separated perf variants: " + ", ".join(VARIANTS))
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)

    if args.all:
        todo = [(a, s) for a, s, runs, _ in cells() if runs]
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        todo = [(args.arch, args.shape)]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    failures = []
    # record skips
    for a, s, runs, reason in cells(include_skips=True):
        if not runs:
            for mp in meshes:
                key = f"{a}|{s}|{'pod2x16x16' if mp else 'pod16x16'}"
                results.setdefault(key, {"ok": True, "skipped": True,
                                         "reason": reason})
    for arch_name, shape_name in todo:
        for mp in meshes:
            mesh_name = "pod2x16x16" if mp else "pod16x16"
            key = f"{arch_name}|{shape_name}|{mesh_name}"
            if args.variant:
                key += f"|{args.variant}"
            if key in results and results[key].get("ok") and not args.force:
                continue
            try:
                results[key] = run_cell(arch_name, shape_name, multi_pod=mp,
                                        variant=args.variant)
            except Exception as e:
                traceback.print_exc()
                results[key] = {"ok": False, "error": f"{type(e).__name__}: {e}"}
                failures.append(key)
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
    print(f"[dryrun] wrote {args.out}; "
          f"{sum(1 for r in results.values() if r.get('ok'))} ok, "
          f"{len(failures)} failed this run")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
