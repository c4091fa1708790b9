import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Communication-efficiency measurement on the production mesh: per-step
collective traffic of SFVI vs SFVI-Avg's local step vs its averaging step
— the paper's §3.2 claim expressed in compiled-HLO bytes at LLM scale.

    PYTHONPATH=src python -m repro.launch.comm --arch qwen3-4b \
        --out benchmarks/data/comm.json
"""
import argparse
import json
import sys

import jax

from repro.configs import INPUT_SHAPES, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import collective_bytes
from repro.launch.specs import build_avg_lowering, build_lowering


def measure(arch: str, shape_name: str = "train_4k") -> dict:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh()
    out = {"arch": arch, "shape": shape_name}
    with jax.set_mesh(mesh):
        fn, args = build_lowering(cfg, shape, mesh)
        c = jax.jit(fn).lower(*args).compile()
        out["sfvi"] = sum(collective_bytes(c.as_text()).values())
        for name, inc in [("avg_local", False), ("avg_round", True)]:
            fn, args = build_avg_lowering(cfg, shape, mesh, include_barycenter=inc)
            c = jax.jit(fn).lower(*args).compile()
            out[name] = sum(collective_bytes(c.as_text()).values())
    # NOTE: production compiles (scan-over-units counted once) — identical
    # structure across the three variants, so the RATIOS are meaningful
    # even though absolute bytes undercount per-layer collectives.
    for m in (10, 100, 1000):
        out[f"avg_amortized_m{m}"] = (
            out["avg_local"] * (m - 1) + out["avg_round"]) / m
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    rec = measure(args.arch, args.shape)
    print(json.dumps(rec, indent=1))
    eta_saving = rec["sfvi"] / max(rec["avg_amortized_m100"], 1.0)
    print(f"\nSFVI-Avg(m=100) moves {1/eta_saving:.2%} of SFVI's per-step "
          f"collective bytes (theta psum remains every step on the mesh; "
          f"the eta_G barycenter collective amortizes 1/m).")
    if args.out:
        rows = []
        if os.path.exists(args.out):
            with open(args.out) as f:
                rows = json.load(f)
        rows.append(rec)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
