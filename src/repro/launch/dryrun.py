import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x shape x mesh)
cell on the production meshes, prove memory fits, and dump roofline raw
material.  MUST be run as a module entrypoint:

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

The two lines above this docstring run before ANY other import (jax locks
the device count on first init); nothing else in the repo sets XLA_FLAGS.
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs import ARCHS, SHAPES, shape_applicable
from repro.launch.cells import CellPlan, build_cell
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import summarize_cell
from repro.launch.sharding import ShardingPolicy

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def run_fpca_cell(
    shape_name: str, multi_pod: bool, *,
    fuse_phases: bool = False, bf16: bool = False, row_shard: bool = False,
) -> dict:
    """Paper-representative cell: the FPCA frontend at production scale."""
    from repro.core.curvefit import fit_bucket_model
    from repro.launch.fpca_cell import FPCA_SHAPES, build_fpca_cell
    from repro.launch.hlo_analysis import analyze_hlo
    from repro.launch.roofline import HW, roofline_terms

    shape = FPCA_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = fit_bucket_model()
    t0 = time.time()
    import jax.numpy as jnp

    with jax.sharding.set_mesh(mesh):
        jitted, args, info = build_fpca_cell(
            shape, mesh, model,
            fuse_phases=fuse_phases,
            compute_dtype=jnp.bfloat16 if bf16 else None,
            row_shard=row_shard,
        )
        compiled = jitted.lower(*args).compile()
    t_compile = time.time() - t0
    hlo = analyze_hlo(compiled.as_text(), mesh.size)
    terms = roofline_terms(hlo.flops, hlo.bytes_proxy, hlo.wire_bytes)
    mem = compiled.memory_analysis()
    model_flops = info.model_flops()
    hw = HW()
    print(mem)
    return {
        "arch": "fpca-frontend",
        "shape": shape_name,
        "mesh": "multi_pod_2x16x16" if multi_pod else "single_pod_16x16",
        "world": mesh.size,
        "compile_s": round(t_compile, 2),
        "flops_per_device": hlo.flops,
        "bytes_per_device": hlo.bytes_proxy,
        "collectives": {
            "per_op": hlo.collectives,
            "total_wire_bytes": hlo.wire_bytes,
            "n_whiles": hlo.n_whiles,
            "unknown_trip_whiles": hlo.unknown_trip_whiles,
        },
        "terms": terms,
        "model_flops": model_flops,
        "useful_flop_ratio": model_flops / (hlo.flops * mesh.size) if hlo.flops else 0.0,
        "roofline_mfu": (
            model_flops / (mesh.size * hw.peak_flops * terms["bound_s"])
            if terms["bound_s"] else 0.0
        ),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
    }


def run_cell(
    arch: str, shape_name: str, multi_pod: bool, plan: CellPlan,
    cfg_overrides: dict | None = None,
) -> dict:
    import dataclasses as _dc

    cfg = ARCHS[arch]
    if cfg_overrides:
        cfg = _dc.replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    world = mesh.size
    t0 = time.time()
    # set_mesh: in-graph sharding constraints (e.g. the vocab reshard in
    # layers.unembed) need the ambient abstract mesh during tracing.
    with jax.sharding.set_mesh(mesh):
        jitted, args = build_cell(cfg, shape, mesh, plan)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    rec = summarize_cell(compiled, cfg, shape, world)
    rec.update(
        mesh="multi_pod_2x16x16" if multi_pod else "single_pod_16x16",
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        plan={
            "remat": plan.remat,
            "n_micro": plan.n_micro,
            "fsdp": plan.policy.fsdp,
            "tp": plan.policy.tp,
            "expert_parallel": plan.policy.expert_parallel,
        },
    )
    print(compiled.memory_analysis())
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    from repro.launch.fpca_cell import FPCA_SHAPES

    ap.add_argument(
        "--arch", choices=sorted(ARCHS) + ["fpca-frontend"], help="single architecture"
    )
    ap.add_argument("--shape", choices=sorted(SHAPES) + sorted(FPCA_SHAPES), help="single shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true", help="run the full matrix")
    ap.add_argument("--tag", default="baseline", help="artifact subdirectory")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--n-micro", type=int, default=0)
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--capacity-factor", type=float, default=0.0, help="override MoE capacity")
    ap.add_argument("--block-k", type=int, default=0, help="override flash KV block")
    ap.add_argument("--no-vocab-shard", action="store_true", help="disable logits vocab reshard")
    ap.add_argument("--moe-local-dispatch", action="store_true", help="per-sequence expert routing")
    ap.add_argument("--fpca-fuse", action="store_true", help="fpca cell: fuse pos/neg phases")
    ap.add_argument("--fpca-bf16", action="store_true", help="fpca cell: bf16 operands")
    ap.add_argument("--fpca-rowshard", action="store_true", help="fpca cell: shard image rows over model")
    ap.add_argument("--no-tp", action="store_true")
    ap.add_argument("--expert-parallel", action="store_true")
    ap.add_argument("--no-expert-tp", action="store_true", help="replicate expert ff at use")
    ap.add_argument("--force", action="store_true", help="recompute existing artifacts")
    args = ap.parse_args()

    plan = CellPlan(
        policy=ShardingPolicy(
            fsdp=not args.no_fsdp,
            tp=not args.no_tp,
            expert_parallel=args.expert_parallel,
            expert_tp=not args.no_expert_tp,
        ),
        remat=args.remat,
        n_micro=args.n_micro,
    )
    archs = [args.arch] if args.arch else sorted(ARCHS)
    if args.arch == "fpca-frontend":
        shapes = [args.shape] if args.shape else sorted(FPCA_SHAPES)
    else:
        shapes = [args.shape] if args.shape else sorted(SHAPES)
    if args.all and not args.arch:
        archs = archs + ["fpca-frontend"]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    if not (args.all or args.arch):
        ap.error("pass --all or --arch")

    out_dir = ARTIFACTS / args.tag
    out_dir.mkdir(parents=True, exist_ok=True)
    failures = []
    for arch in archs:
        if args.shape:
            arch_shapes = [args.shape]
        else:
            arch_shapes = sorted(FPCA_SHAPES) if arch == "fpca-frontend" else shapes
        for shape_name in arch_shapes:
            for multi in meshes:
                mesh_tag = "multi" if multi else "single"
                path = out_dir / f"{arch}__{shape_name}__{mesh_tag}.json"
                if path.exists() and not args.force:
                    print(f"[skip existing] {path.name}")
                    continue
                label = f"{arch} x {shape_name} x {mesh_tag}"
                print(f"=== {label} ===", flush=True)
                try:
                    if arch == "fpca-frontend":
                        rec = run_fpca_cell(
                            shape_name, multi,
                            fuse_phases=args.fpca_fuse, bf16=args.fpca_bf16,
                            row_shard=args.fpca_rowshard,
                        )
                    else:
                        overrides = {}
                        if args.capacity_factor:
                            overrides["moe_capacity_factor"] = args.capacity_factor
                        if args.block_k:
                            overrides["attn_block_k"] = args.block_k
                        if args.no_vocab_shard:
                            overrides["logits_vocab_shard"] = False
                        if args.moe_local_dispatch:
                            overrides["moe_local_dispatch"] = True
                        rec = run_cell(arch, shape_name, multi, plan, overrides)
                    path.write_text(json.dumps(rec, indent=2, default=float))
                    if "skipped" in rec:
                        print(f"[skipped] {rec['skipped']}")
                    else:
                        t = rec["terms"]
                        print(
                            f"[ok] compile={rec['compile_s']}s "
                            f"compute={t['compute_s']:.4f}s memory={t['memory_s']:.4f}s "
                            f"collective={t['collective_s']:.4f}s dominant={t['dominant']}",
                            flush=True,
                        )
                except Exception as e:  # noqa: BLE001 — sweep must survive cell bugs
                    failures.append(label)
                    path.with_suffix(".error").write_text(traceback.format_exc())
                    print(f"[FAIL] {label}: {e}", flush=True)
    if failures:
        print(f"\n{len(failures)} FAILED cells: {failures}")
        raise SystemExit(1)
    print("\nall requested cells compiled OK")


if __name__ == "__main__":
    main()
