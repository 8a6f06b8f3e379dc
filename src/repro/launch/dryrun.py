import os
_opt = os.environ.get("REPRO_OPT_LEVEL", "0")   # "default" = full XLA opt
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + ("" if _opt == "default" else f"--xla_backend_optimization_level={_opt} ")
    + os.environ.get("REPRO_EXTRA_XLA_FLAGS", ""))
os.environ.setdefault("REPRO_ATTN_CHUNK", "8192")
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST precede any jax import: jax locks the device
count at first init, and the production meshes need 512 host devices.

Per cell this driver:
  1. builds the mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. builds ShapeDtypeStruct stand-ins for params / optimizer / cache /
     batch with NamedShardings from parallel.ShardingRules (no allocation),
  3. jits the train_step / prefill_step / serve_step, .lower()s and
     .compile()s it,
  4. prints memory_analysis() + cost_analysis(), parses collective bytes
     from the post-SPMD HLO, and
  5. writes a CellResult JSON.

Usage:
  python -m repro.launch.dryrun --arch glm4-9b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh multi --out runs/dryrun
"""
import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, ASSIGNED, LONG_CONTEXT_OK, SHAPES, cells
from repro.core import analytical, blocks, hlo_analysis
from repro.core.model_config import ModelSpec, ShapeSpec
from repro.core.roofline import CellResult
from repro.launch.mesh import make_production_mesh
from repro.models import lm
from repro.parallel.sharding import ShardingRules, dp_axes
from repro.serve.engine import make_prefill_step, make_serve_step
from repro.train.optimizer import AdamWState, adamw_init
from repro.train.train_step import TrainConfig, make_train_step
from repro.quant.qlinear import quantize_params


# ---------------------------------------------------------------------------
# Abstract inputs
# ---------------------------------------------------------------------------

def _sds(tree, shardings):
    return jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        tree, shardings)


def input_specs(spec: ModelSpec, shape: ShapeSpec, rules: ShardingRules,
                dtype=jnp.bfloat16):
    """ShapeDtypeStruct stand-ins for the data batch of one cell."""
    B, S = shape.global_batch, shape.seq_len
    mesh = rules.mesh
    toks = jax.ShapeDtypeStruct(
        (B, S if shape.kind != "decode" else 1), jnp.int32)
    batch = {"tokens": toks}
    if shape.kind == "train":
        batch["labels"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
    if spec.vision_tokens and shape.kind != "decode":
        batch["patch_embeds"] = jax.ShapeDtypeStruct(
            (B, spec.vision_tokens, spec.vision_embed_dim), dtype)
    if spec.encoder_layers and shape.kind != "decode":
        batch["frames"] = jax.ShapeDtypeStruct(
            (B, spec.encoder_seq, spec.d_model), dtype)
    shardings = rules.batch_shardings(
        {k: v for k, v in batch.items()})
    return _sds(batch, shardings)


def abstract_params(spec: ModelSpec, rules: ShardingRules, dtype=jnp.bfloat16,
                    quant: str | None = None):
    shapes = jax.eval_shape(
        lambda: lm.init(jax.random.PRNGKey(0), spec, dtype=dtype))
    if quant:
        shapes = jax.eval_shape(lambda p: quantize_params(p, quant), shapes)
    shardings = rules.param_shardings(shapes)
    return _sds(shapes, shardings)


def abstract_opt_state(params_sds, spec: ModelSpec, rules: ShardingRules):
    shapes = jax.eval_shape(adamw_init, params_sds)
    opt_sh = rules.opt_shardings(
        jax.tree_util.tree_map(lambda s: s, params_sds))
    shardings = AdamWState(step=NamedSharding(rules.mesh, P()),
                           m=opt_sh, v=opt_sh)
    return _sds(shapes, shardings)


def abstract_cache(spec: ModelSpec, shape: ShapeSpec, rules: ShardingRules,
                   dtype=jnp.bfloat16):
    B, S = shape.global_batch, shape.seq_len
    shapes = jax.eval_shape(
        lambda: lm.init_cache(spec, B, S, dtype=dtype))
    shardings = rules.cache_shardings(shapes)
    shardings["pos"] = NamedSharding(rules.mesh, P())
    return _sds(shapes, shardings)


# ---------------------------------------------------------------------------
# Cell runner
# ---------------------------------------------------------------------------

def build_step(spec: ModelSpec, shape: ShapeSpec, rules: ShardingRules, args):
    dtype = jnp.bfloat16
    if shape.kind == "train":
        tcfg = TrainConfig(microbatches=args.microbatches,
                           remat=True, attention_impl=args.attn_impl)
        step = make_train_step(spec, tcfg)
        params = abstract_params(spec, rules, dtype)
        opt = abstract_opt_state(params, spec, rules)
        batch = input_specs(spec, shape, rules, dtype)
        return jax.jit(step, donate_argnums=(0, 1)), (params, opt, batch)
    if shape.kind == "prefill":
        step = make_prefill_step(spec, max_seq=shape.seq_len,
                                 impl=args.attn_impl)
        params = abstract_params(spec, rules, dtype, quant=args.quant)
        batch = input_specs(spec, shape, rules, dtype)
        return jax.jit(step), (params, batch)
    # decode
    step = make_serve_step(spec)
    params = abstract_params(spec, rules, dtype, quant=args.quant)
    # fp8 KV cache: halves the cache-read memory term; values cast back to
    # the compute dtype inside decode attention (beyond-paper opt, §Perf)
    cache_dtype = jnp.float8_e4m3fn if args.cache_quant else dtype
    cache = abstract_cache(spec, shape, rules, dtype=cache_dtype)
    batch = input_specs(spec, shape, rules, dtype)
    # pin the output cache layout to the input layout so donation aliases
    # (otherwise XLA inserts full-cache copies — found in §Perf)
    cache_sh = jax.tree_util.tree_map(lambda s: s.sharding, cache)
    return (jax.jit(step, donate_argnums=(1,),
                    out_shardings=(None, cache_sh)),
            (params, cache, batch["tokens"]))


def _compile_once(spec, shape, mesh, args):
    rules = ShardingRules(mesh, spec, expert_layout=args.expert_layout,
                      fsdp=getattr(args, "fsdp", False),
                      cache_layout=getattr(args, "cache_layout", "auto"))
    step, abstract_args = build_step(spec, shape, rules, args)
    lowered = step.lower(*abstract_args)
    compiled = lowered.compile()
    cost = hlo_analysis.extract_cost(compiled)
    hlo_text = compiled.as_text()
    coll = hlo_analysis.parse_collective_bytes(hlo_text)
    metrics = {**cost, **coll.as_dict()}
    return compiled, metrics, hlo_text


def measure_exact_costs(spec, shape, mesh, args):
    """Exact per-step costs via unrolled reduced-depth variants
    (launch/cost_extrapolation.py)."""
    import argparse as _ap
    from repro.launch import cost_extrapolation as ce
    vargs = _ap.Namespace(**vars(args))
    vargs.microbatches = 1              # mb count does not change step FLOPs
    os.environ["REPRO_UNROLL_SCANS"] = "1"
    try:
        counts, costs = [], []
        for vspec in ce.depth_variants(spec):
            _, metrics, _ = _compile_once(vspec, shape, mesh, vargs)
            counts.append(ce.kind_counts(vspec))
            costs.append(metrics)
        return ce.solve_costs(counts, costs, ce.kind_counts(spec))
    finally:
        os.environ.pop("REPRO_UNROLL_SCANS", None)


def run_cell(arch: str, shape_name: str, mesh_kind: str, args) -> CellResult:
    spec = ARCHS[arch]
    shape = SHAPES[shape_name]
    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    n_dev = mesh.devices.size

    t0 = time.time()
    # 1) the required artifact: rolled scans, production microbatching —
    #    this is the compile that MUST succeed per cell, and the one whose
    #    memory_analysis is meaningful
    compiled, rolled_metrics, hlo_text = _compile_once(spec, shape, mesh, args)
    memory = hlo_analysis.extract_memory(compiled)
    remat_info = hlo_analysis.count_remat_duplicates(hlo_text)

    # 2) exact costs (single-pod roofline table only): decode HLO is small
    #    enough to fully unroll; train/prefill extrapolate over depth
    exact = dict(rolled_metrics)
    note = args.tag or ""
    if not multi and args.exact != "off":
        if shape.kind == "decode":
            os.environ["REPRO_UNROLL_SCANS"] = "1"
            try:
                _, exact, _ = _compile_once(spec, shape, mesh, args)
            finally:
                os.environ.pop("REPRO_UNROLL_SCANS", None)
            note = (note + " exact=unrolled").strip()
        else:
            exact = measure_exact_costs(spec, shape, mesh, args)
            note = (note + " exact=extrapolated").strip()
        if spec.xlstm is not None or spec.ssm is not None:
            note += " (token-recurrence loop flops undercounted; see DESIGN)"
    cost = {"flops": exact.get("flops", 0.0),
            "bytes_accessed": exact.get("bytes_accessed", 0.0)}

    class _C:                      # adapt extrapolated dict to CollectiveStats
        total_bytes = exact.get("collective_bytes", 0.0)

        @staticmethod
        def as_dict():
            return {k: v for k, v in exact.items()
                    if k.startswith(("collective", "bytes_", "count_"))}
    coll = _C
    compile_s = time.time() - t0

    # analytical prediction for the same cell
    pods = 2 if multi else 1
    ms = analytical.MeshShape(dp=16, tp=16, pods=pods)
    from repro.core.precision import get as get_prec
    prec = get_prec(args.quant or "bf16")
    mb = (max(1, shape.global_batch // ms.total_dp // args.microbatches)
          if shape.kind == "train" else 0)
    an = analytical.analyze(spec, shape, prec, mesh=ms, microbatch=mb)

    res = CellResult(
        arch=arch, shape=shape_name,
        mesh=("2x16x16" if multi else "16x16") + (f"+{args.tag}" if args.tag else ""),
        num_devices=n_dev,
        hlo_flops=cost.get("flops", 0.0),
        hlo_bytes=cost.get("bytes_accessed", 0.0),
        collective_bytes=coll.total_bytes,
        collective_detail=coll.as_dict(),
        memory_detail={**memory,
                       **{f"remat_{k}": float(v) for k, v in remat_info.items()},
                       "rolled_flops": rolled_metrics.get("flops", 0.0),
                       "rolled_bytes": rolled_metrics.get("bytes_accessed", 0.0)},
        model_flops_total=an.model_flops,
        analytic_flops=an.step_flops / n_dev,
        analytic_hbm=an.hbm_traffic,
        analytic_collective=an.collectives.total,
        compile_seconds=compile_s,
        note=note,
    )
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="runs/dryrun")
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--attn-impl", default="chunked")
    ap.add_argument("--expert-layout", default="ep", choices=["ep", "tp"])
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--cache-layout", default="auto",
                    choices=["auto", "seq", "headdim"])
    ap.add_argument("--quant", default=None, choices=[None, "int8", "int4"])
    ap.add_argument("--cache-quant", action="store_true")
    ap.add_argument("--exact", default="auto", choices=["auto", "off"],
                    help="off: skip unrolled cost measurement (artifact only)")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    todo = []
    if args.all:
        for spec, shape, skip in cells(include_skipped=False):
            todo.append((spec.name, shape.name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        todo.append((args.arch, args.shape))

    failures = []
    for arch, shape_name in todo:
        label = f"{arch} x {shape_name} x {args.mesh}"
        print(f"=== dryrun {label}", flush=True)
        try:
            res = run_cell(arch, shape_name, args.mesh, args)
            path = res.save(args.out)
            row = res.row()
            print(f"    devices={res.num_devices} compile={res.compile_seconds:.1f}s "
                  f"flops/dev={res.hlo_flops:.3e} bytes/dev={res.hlo_bytes:.3e} "
                  f"coll/dev={res.collective_bytes:.3e}")
            print(f"    memory={res.memory_detail}")
            print(f"    terms: comp={row['t_compute_ms']:.2f}ms "
                  f"mem={row['t_memory_ms']:.2f}ms coll={row['t_collective_ms']:.2f}ms "
                  f"dominant={row['dominant']} useful={row['useful_ratio']} "
                  f"roofline_frac={row['roofline_frac']}")
            print(f"    -> {path}", flush=True)
        except Exception as e:
            traceback.print_exc()
            failures.append((label, repr(e)))
    if failures:
        print(f"FAILED {len(failures)} cells:")
        for l, e in failures:
            print(f"  {l}: {e}")
        sys.exit(1)
    print("ALL CELLS OK")


if __name__ == "__main__":
    main()
