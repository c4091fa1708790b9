import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes and extract roofline terms.

The two lines above MUST run before any other import — jax locks the
device count at first init. 512 host devices back both the 16x16
single-pod mesh and the 2x16x16 multi-pod mesh.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun                 # full 40x2 matrix
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --mesh multi_pod
    PYTHONPATH=src python -m repro.launch.dryrun --out results.json

Results (roofline terms, memory analysis, collective breakdown) append to
a JSON file consumed by benchmarks/bench_roofline.py and EXPERIMENTS.md.
"""
import argparse
import json
import sys
import time
import traceback

import jax

from repro.configs import ARCH_NAMES, INPUT_SHAPES, get_config
from repro.launch import roofline as R
from repro.launch.mesh import PRODUCTION_DEVICE_KIND, make_production_mesh
from repro.launch.specs import build_lowering


def should_skip(cfg, shape) -> str:
    if shape.name == "long_500k" and cfg.is_encoder_decoder:
        return ("enc-dec (whisper): no 500k-token decode use-case; "
                "see DESIGN.md §Arch-applicability")
    return ""


def run_one(arch: str, shape_name: str, multi_pod: bool, verbose: bool = True,
            analyze: bool = True, optimized: bool = False):
    import dataclasses

    from repro.models.backbone.config import PerfConfig

    cfg = get_config(arch)
    if optimized:
        # The §Perf-validated production set (EXPERIMENTS.md §Perf
        # conclusions): masked_nll measured neutral, act_shard measured
        # HARMFUL under current XLA SPMD — both stay off.
        cfg = dataclasses.replace(cfg, perf=PerfConfig(
            pad_vocab=True, zero_opt=True, microbatch=4, pad_heads=16))
    shape = INPUT_SHAPES[shape_name]
    skip = should_skip(cfg, shape)
    if skip:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi_pod" if multi_pod else "single_pod",
                "status": "skipped", "reason": skip}
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "multi_pod" if multi_pod else "single_pod"
    chips = mesh.size
    t0 = time.time()
    with jax.set_mesh(mesh):
        # --- production compile: proves lowering; memory analysis ---------
        fn, args = build_lowering(cfg, shape, mesh)
        lowered = jax.jit(fn).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        if verbose:
            print(f"--- {arch} x {shape_name} on {mesh_name} ({chips} chips) ---")
            print(f"memory_analysis: {mem}")
        roof = R.analyze(
            compiled, arch, shape_name, mesh_name, chips,
            model_flops=R.model_flops(cfg, shape),
            device_kind=PRODUCTION_DEVICE_KIND,
        )
        # --- analysis compiles: scan-aware flops/bytes/collectives --------
        if analyze:
            period = R._unit_period(cfg)
            n_units = cfg.num_layers // period
            ms = []
            for k in (1, 2):
                cfg_k = R.analysis_variant(cfg, k)
                fnk, argsk = build_lowering(cfg_k, shape, mesh)
                ck = jax.jit(fnk).lower(*argsk).compile()
                ms.append(R._extract(ck))
            ext = R.extrapolate(ms[0], ms[1], n_units)
            # Microbatch accumulation is a lax.scan: scale by k (see
            # launch/perf.py — the same scan-body-counted-once caveat).
            k_mb = max(1, cfg.perf.microbatch)
            roof.flops_per_chip = ext["flops"] * k_mb
            roof.bytes_per_chip = ext["bytes"] * k_mb
            roof.coll_bytes_per_chip = ext["coll"] * k_mb
            roof.coll_breakdown = {kk: v * k_mb
                                   for kk, v in ext["coll_breakdown"].items()}
    rec = roof.to_dict()
    rec.update(status="ok", t_lower_s=round(t_lower, 1),
               t_compile_s=round(t_compile, 1),
               analysis="2pt-extrapolated" if analyze else "scan-undercount")
    if verbose:
        print(f"t_compute={roof.t_compute:.3e}s t_memory={roof.t_memory:.3e}s "
              f"t_collective={roof.t_collective:.3e}s -> {roof.bottleneck}; "
              f"useful_flops_ratio={roof.useful_flops_ratio:.2f} "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all)")
    ap.add_argument("--mesh", default="single_pod",
                    choices=["single_pod", "multi_pod", "both"])
    ap.add_argument("--out", default=None, help="append results to this JSON file")
    ap.add_argument("--optimized", action="store_true",
                    help="enable all §Perf levers (beyond-paper optimized run)")
    args = ap.parse_args(argv)

    archs = [args.arch] if args.arch else list(ARCH_NAMES)
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    meshes = (["single_pod", "multi_pod"] if args.mesh == "both" else [args.mesh])

    results, failures = [], []
    for mesh_name in meshes:
        for arch in archs:
            for shape_name in shapes:
                try:
                    # Roofline analysis compiles are single-pod only
                    # (the roofline table is single-pod per EXPERIMENTS.md).
                    rec = run_one(arch, shape_name, mesh_name == "multi_pod",
                                  analyze=(mesh_name == "single_pod"),
                                  optimized=args.optimized)
                    results.append(rec)
                except Exception as e:  # noqa: BLE001 — report, continue
                    traceback.print_exc()
                    failures.append((arch, shape_name, mesh_name, str(e)))
                    results.append({
                        "arch": arch, "shape": shape_name, "mesh": mesh_name,
                        "status": "FAILED", "error": str(e)[:500],
                    })
    if args.out:
        existing = []
        if os.path.exists(args.out):
            with open(args.out) as f:
                existing = json.load(f)
        # Newest record wins per (arch, shape, mesh).
        key = lambda r: (r["arch"], r["shape"], r["mesh"])  # noqa: E731
        merged = {key(r): r for r in existing}
        merged.update({key(r): r for r in results})
        with open(args.out, "w") as f:
            json.dump(list(merged.values()), f, indent=1)
        print(f"wrote {len(merged)} records to {args.out}")
    ok = sum(1 for r in results if r["status"] == "ok")
    sk = sum(1 for r in results if r["status"] == "skipped")
    print(f"\n=== dry-run summary: {ok} ok, {sk} skipped, {len(failures)} failed ===")
    for f_ in failures:
        print("FAILED:", f_)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
