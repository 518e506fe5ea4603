import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
).strip()

# ^ MUST precede any jax import (jax locks the device count on first init).
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this script:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. assembles abstract (ShapeDtypeStruct) params / optimizer state / caches
     with their NamedShardings,
  3. ``jit(step).lower(...).compile()`` — proving the distribution config is
     coherent (shardings consistent, collectives legal, memory bounded),
  4. records memory_analysis / cost_analysis / per-collective bytes into
     ``artifacts/dryrun/<arch>__<shape>__<mesh>[__tag].json`` for §Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both      (full 40-cell table)
"""
import argparse
import gzip
import json
import re
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch import hlo_analysis

from repro.configs import ALL_SHAPES, ARCHS, SHAPES, get_config, shape_applicable
from repro.core import EngineContext, FXP8, PrecisionPolicy
from repro.data.pipeline import input_specs
from repro.launch.mesh import make_production_mesh
from repro.models import get_model
from repro.sharding import partition
from repro.train import optimizer as opt
from repro.train.train_loop import TrainConfig, make_train_step

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun")

def engine_ctx(mode: str, attn: str = "xla", tp_bf16: bool = False) -> EngineContext:
    if mode == "exact":
        return EngineContext(mode="exact", attn_impl=attn, tp_reduce_bf16=tp_bf16)
    return EngineContext(mode=mode, policy=PrecisionPolicy.accurate(FXP8), attn_impl=attn,
                         tp_reduce_bf16=tp_bf16)


def _batch_sharding(mesh, shape_tuple):
    """Shard dim 0 over (pod, data) when divisible; replicate otherwise."""
    axes = tuple(a for a in partition.BATCH_AXES if a in mesh.axis_names)
    import numpy as np

    extent = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    if shape_tuple and shape_tuple[0] % max(extent, 1) == 0 and extent > 1:
        return NamedSharding(mesh, P(axes))
    return NamedSharding(mesh, P())


def build_cell(arch: str, shape_name: str, mesh, mode: str = "exact", attn: str = "xla",
               pad_heads_to: int = 0, tp_bf16: bool = False, microbatches: int = 1,
               prepared: bool = False):
    """Returns (step_fn, example_args, in_shardings, out_shardings)."""
    cfg = get_config(arch)
    if pad_heads_to:
        import dataclasses as _dc

        # Megatron-style head padding: allocate ceil(H/TP)*TP heads so the TP
        # axis divides them; extra heads carry zero weights (beyond-paper).
        new_h = ((cfg.num_heads + pad_heads_to - 1) // pad_heads_to) * pad_heads_to
        cfg = _dc.replace(cfg, num_heads=new_h)
    shape = SHAPES[shape_name]
    model = get_model(cfg)
    ctx = engine_ctx(mode, attn, tp_bf16)
    specs = model.specs()
    param_sh, _ = partition.param_shardings(specs, mesh)
    aparams = model.abstract_params(jnp.bfloat16)
    if prepared and mode != "exact" and shape.kind != "train":
        # lower the serving fast path: weight banks pre-formatted by the
        # backend registry (inference cells only — QAT trains raw weights)
        from repro.core import prepare_params

        aprep = jax.eval_shape(
            lambda p: prepare_params(p, ctx.policy, mode, specs=specs), aparams
        )
        # shared serving placement rules (sharding/partition.py): payloads
        # inherit the raw leaf's sharding, per-channel scales ride the axes
        # they share with the payload, tied lm_head uses the transposed
        # embedding rule
        param_sh = partition.prepared_shardings(aprep, specs, mesh)
        aparams = aprep
    batch = input_specs(cfg, shape)
    batch_sh = {k: _batch_sharding(mesh, v.shape) for k, v in batch.items()}
    repl = NamedSharding(mesh, P())

    if shape.kind == "train":
        tcfg = TrainConfig(remat=True, microbatches=microbatches)
        step = make_train_step(model, ctx, tcfg)
        aopt = opt.abstract_state(aparams)
        opt_sh = opt.AdamWState(step=repl, m=param_sh, v=param_sh)
        metrics_sh = {k: repl for k in ("ce_loss", "grad_norm", "lr", "loss")}
        return (
            step,
            (aparams, aopt, batch),
            (param_sh, opt_sh, batch_sh),
            (param_sh, opt_sh, metrics_sh),
        )

    if shape.kind == "prefill":
        def prefill(params, batch):
            logits, _ = model.forward(params, batch, ctx)
            return logits
        logits_sh = _batch_sharding(mesh, (shape.global_batch,))
        return prefill, (aparams, batch), (param_sh, batch_sh), logits_sh

    # decode: one token against a seq_len cache
    cache = model.make_cache(shape.global_batch, shape.seq_len, jnp.bfloat16, abstract=True)
    cache_sh = partition.cache_shardings(cache, mesh, cfg, row_axis_len=shape.seq_len)

    def decode(params, tokens, cache):
        return model.decode_step(params, tokens, cache, ctx)

    toks = batch["tokens"]
    toks_sh = _batch_sharding(mesh, toks.shape)
    logits_sh = _batch_sharding(mesh, (shape.global_batch,))
    return (
        decode,
        (aparams, toks, cache),
        (param_sh, toks_sh, cache_sh),
        (logits_sh, cache_sh),
    )


def run_cell(arch: str, shape_name: str, mesh_kind: str, mode: str = "exact",
             out_dir: Optional[str] = None, tag: str = "", attn: str = "xla",
             pad_heads_to: int = 0, tp_bf16: bool = False, microbatches: int = 1,
             prepared: bool = False) -> Dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if prepared and not tag:
        tag = "prepared"
    rec: Dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "mode": mode, "tag": tag,
        "prepared": prepared,
    }
    if not ok:
        rec.update(status="skip", reason=why)
        return _emit(rec, out_dir)

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
        with jax.set_mesh(mesh):
            step, args, in_sh, out_sh = build_cell(
                arch, shape_name, mesh, mode, attn=attn, pad_heads_to=pad_heads_to,
                tp_bf16=tp_bf16, microbatches=microbatches, prepared=prepared,
            )
            lowered = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):  # older jax: one dict per device
                cost = cost[0] if cost else {}
            hlo = compiled.as_text()
            costs = hlo_analysis.analyze(hlo)  # per-DEVICE program costs
        # persist the optimized HLO so perf iterations re-analyze offline
        hlo_dir = os.path.join(out_dir or ARTIFACTS, "hlo")
        os.makedirs(hlo_dir, exist_ok=True)
        tagpart = f"__{tag}" if tag else ""
        modepart = f"__{mode}" if mode != "exact" else ""
        with gzip.open(os.path.join(
                hlo_dir, f"{arch}__{shape_name}__{mesh_kind}{modepart}{tagpart}.hlo.gz"),
                "wt") as f:
            f.write(hlo)
        rec.update(
            status="ok",
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            memory={
                k: int(getattr(mem, k, 0) or 0)
                for k in (
                    "argument_size_in_bytes",
                    "output_size_in_bytes",
                    "temp_size_in_bytes",
                    "generated_code_size_in_bytes",
                )
            },
            # loop-corrected per-device terms (launch/hlo_analysis.py)
            flops_dev=costs.dot_flops,
            hbm_bytes_dev=costs.hbm_bytes,
            hbm_bytes_upper_dev=costs.hbm_bytes_upper,
            coll_bytes_dev=costs.collective_bytes,
            coll_by_kind={k: float(v) for k, v in costs.collective_by_kind.items()},
            while_trips=costs.while_trips[:64],
            # raw XLA numbers for reference (scan bodies counted once)
            xla_flops=float(cost.get("flops", 0.0)),
            xla_bytes=float(cost.get("bytes accessed", 0.0)),
            hlo_size=len(hlo),
        )
        print(f"[ok] {arch} x {shape_name} x {mesh_kind} ({mode}{'/' + tag if tag else ''}): "
              f"lower {t_lower:.0f}s compile {t_compile:.0f}s "
              f"flops/dev {costs.dot_flops:.3e} coll/dev {costs.collective_bytes/1e9:.2f} GB")
    except Exception as e:  # noqa: BLE001 — record the failure, keep the sweep going
        rec.update(status="fail", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
        print(f"[FAIL] {arch} x {shape_name} x {mesh_kind}: {type(e).__name__}: {e}")
    return _emit(rec, out_dir)


def _emit(rec: Dict, out_dir: Optional[str]) -> Dict:
    out_dir = out_dir or ARTIFACTS
    os.makedirs(out_dir, exist_ok=True)
    tag = f"__{rec['tag']}" if rec.get("tag") else ""
    mode = f"__{rec['mode']}" if rec.get("mode", "exact") != "exact" else ""
    path = os.path.join(
        out_dir, f"{rec['arch']}__{rec['shape']}__{rec['mesh']}{mode}{tag}.json"
    )
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=[s.name for s in ALL_SHAPES], default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--mode", choices=["exact", "carmen", "int8"], default="exact")
    ap.add_argument("--tag", default="", help="artifact suffix for perf experiments")
    ap.add_argument("--attn", choices=["xla", "flash"], default="xla")
    ap.add_argument("--pad-heads-to", type=int, default=0,
                    help="pad attention heads up to a multiple (TP divisibility)")
    ap.add_argument("--tp-bf16", action="store_true",
                    help="bf16 dot outputs (TP partial-sums all-reduce in bf16)")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="gradient-accumulation microbatches inside train_step")
    ap.add_argument("--prepared", action="store_true",
                    help="lower inference cells with prepared weight banks "
                         "(prepare_params; ignored for train shapes / exact mode)")
    ap.add_argument("--all", action="store_true", help="sweep every cell")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    archs = sorted(ARCHS) if args.arch is None else [args.arch]
    shapes = [s.name for s in ALL_SHAPES] if args.shape is None else [args.shape]
    if not args.all and args.arch is None and args.shape is None:
        ap.error("pass --arch/--shape or --all")

    failures = 0
    for mesh_kind in meshes:
        for arch in archs:
            for shape in shapes:
                rec = run_cell(arch, shape, mesh_kind, args.mode, args.out, args.tag,
                               attn=args.attn, pad_heads_to=args.pad_heads_to,
                               tp_bf16=args.tp_bf16, microbatches=args.microbatches,
                               prepared=args.prepared)
                failures += rec["status"] == "fail"
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
