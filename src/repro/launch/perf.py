import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""§Perf hillclimb driver: measure one (arch, shape) pair under a named
set of PerfConfig levers and append the result to a JSON log.

    PYTHONPATH=src python -m repro.launch.perf --arch qwen3-32b \
        --shape train_4k --levers masked_nll,zero_opt \
        --out benchmarks/data/perf_iterations.json

Each record carries the lever set, the three roofline terms, peak HBM, and
the collective breakdown — EXPERIMENTS.md §Perf is written from this log.
"""
import argparse
import dataclasses
import json
import sys
import time

import jax

from repro.configs import INPUT_SHAPES, get_config
from repro.launch import roofline as R
from repro.launch.mesh import PRODUCTION_DEVICE_KIND, make_production_mesh
from repro.launch.specs import build_lowering
from repro.models.backbone.config import PerfConfig

LEVERS = ("masked_nll", "pad_vocab", "zero_opt", "act_shard", "microbatch", "pad_heads")


def _parse_levers(levers: list) -> dict:
    kw = {}
    for lv in levers:
        if "=" in lv:
            k, v = lv.split("=")
            kw[k] = int(v)
        else:
            kw[lv] = True
    return kw


def measure(arch: str, shape_name: str, levers: list) -> dict:
    cfg = get_config(arch)
    cfg = dataclasses.replace(cfg, perf=PerfConfig(**_parse_levers(levers)))
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh()
    t0 = time.time()
    with jax.set_mesh(mesh):
        fn, args = build_lowering(cfg, shape, mesh)
        compiled = jax.jit(fn).lower(*args).compile()
        roof = R.analyze(compiled, arch, shape_name, "single_pod", mesh.size,
                         model_flops=R.model_flops(cfg, shape),
                         device_kind=PRODUCTION_DEVICE_KIND)
        n_units = cfg.num_layers // R._unit_period(cfg)
        ms = []
        for k in (1, 2):
            cfg_k = R.analysis_variant(cfg, k)
            fnk, argsk = build_lowering(cfg_k, shape, mesh)
            ms.append(R._extract(jax.jit(fnk).lower(*argsk).compile()))
        ext = R.extrapolate(ms[0], ms[1], n_units)
        # The microbatch accumulation loop is itself a lax.scan whose body
        # XLA cost-counts once; scale by k (the optimizer epilogue outside
        # the loop is negligible, and the per-microbatch gradient
        # all-reduce genuinely runs k times).
        k_mb = max(1, cfg.perf.microbatch)
        roof.flops_per_chip = ext["flops"] * k_mb
        roof.bytes_per_chip = ext["bytes"] * k_mb
        roof.coll_bytes_per_chip = ext["coll"] * k_mb
        roof.coll_breakdown = {kk: v * k_mb for kk, v in ext["coll_breakdown"].items()}
    rec = roof.to_dict()
    rec.update(levers=sorted(levers), wall_s=round(time.time() - t0, 1))
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--levers", default="", help="comma-separated PerfConfig fields")
    ap.add_argument("--out", default="benchmarks/data/perf_iterations.json")
    args = ap.parse_args(argv)
    levers = [lv for lv in args.levers.split(",") if lv]
    for lv in levers:
        assert lv.split("=")[0] in LEVERS, lv
    rec = measure(args.arch, args.shape, levers)
    rows = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            rows = json.load(f)
    rows.append(rec)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    print(json.dumps({k: rec[k] for k in (
        "arch", "shape", "levers", "t_compute", "t_memory", "t_collective",
        "bottleneck", "useful_flops_ratio")}, indent=1))
    print(f"peak HBM {rec['peak_bytes_per_chip']/2**30:.1f} GiB/chip; "
          f"coll {rec['coll_bytes_per_chip']:.3g} B/chip")
    return 0


if __name__ == "__main__":
    sys.exit(main())
