"""Benchmark harness — one benchmark per paper table/figure, plus the
roofline suite for the assigned architectures.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]

Default mode is CPU-budget "quick" (reduced dims/iters; same protocols).
"""
from __future__ import annotations

import argparse
import time
import traceback

SUITES = ["hier_bnn", "prodlda", "glmm", "multinomial", "kernels", "serving",
          "federated", "roofline"]


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--full", action="store_true", help="paper-scale (slow) settings")
    parser.add_argument("--only", type=str, default=None, help="comma-separated suite names")
    args = parser.parse_args()
    from repro import compile_cache

    compile_cache.enable()
    quick = not args.full
    wanted = args.only.split(",") if args.only else SUITES

    print(f"# SFVI benchmark harness (quick={quick})")
    t_all = time.perf_counter()
    failures = []
    for name in wanted:
        print(f"\n{'='*72}\n# suite: {name}\n{'='*72}")
        t0 = time.perf_counter()
        try:
            if name == "hier_bnn":
                from benchmarks import bench_hier_bnn
                bench_hier_bnn.run(quick=quick, seeds=(0,) if quick else (0, 1, 2, 3, 4))
            elif name == "prodlda":
                from benchmarks import bench_prodlda
                bench_prodlda.run(quick=quick)
            elif name == "glmm":
                from benchmarks import bench_glmm
                bench_glmm.run(quick=quick)
            elif name == "multinomial":
                from benchmarks import bench_multinomial
                bench_multinomial.run(quick=quick)
            elif name == "kernels":
                from benchmarks import bench_kernels
                bench_kernels.run(quick=quick)
            elif name == "serving":
                from benchmarks import bench_serving
                bench_serving.run(quick=quick)
            elif name == "federated":
                from benchmarks import bench_federated
                bench_federated.run(quick=quick)
            elif name == "roofline":
                from benchmarks import bench_roofline
                bench_roofline.run(quick=quick)
            else:
                print(f"unknown suite {name}")
                continue
            print(f"[{name}] OK in {time.perf_counter()-t0:.1f}s")
        except Exception:
            failures.append(name)
            print(f"[{name}] FAILED in {time.perf_counter()-t0:.1f}s")
            traceback.print_exc()
    print(f"\n# total {time.perf_counter()-t_all:.1f}s; failures: {failures or 'none'}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
