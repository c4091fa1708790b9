#!/usr/bin/env python3
"""Readings that the limits of a training cell are set from.

    python3 perfbench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--out calib.jsonl]

For each seed, in one process: the program's sound readings (set-up and
the recorded rounds of the timed path, exactly as a run makes them, then
the reference), and for each control seed the control (the reference in
bfloat16) and the half-batch fault, each compared with the float32
reference the way a run compares the program. A state left unchanged
reads 1 by the change-norm measure and needs no run. One JSON line per
seed goes to standard output and to ``--out``.
"""
import argparse
import gc
import json
import pathlib
import sys
import time

T0 = time.perf_counter()
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from pbench import compare, harness  # noqa: E402


def serve_readings(run, seed, controls):
    """A short window at the cell's own load, then the comparison; the
    control recomputes the same sampled answers in bfloat16."""
    import jax.numpy as jnp

    drv = harness.load_module(harness.BENCH / "drivers" / "serve.py", "drv")
    run.seed = seed
    drv.run(run)
    out = {"seed": seed, "program": {k: c["value"] for k, c in run.checks.items()},
           "counters": dict(run.counters),
           "end_to_end": dict(run.end_to_end)}
    if controls:
        d = run.detail
        cell, cfg, tr = run.cell, run.cell.cfg, run.cell.traffic
        low = drv.reference_answers(cell.model, cfg, d["eta_G"], d["eta_L"],
                                    d["pool"], d["checked"], jnp.bfloat16)
        checks = drv.compare_sample(cell.model, cfg, tr, d["eta_G"], d["eta_L"],
                                    d["pool"], d["checked"], 0, answers=low)
        out["control_bf16"] = {k: c["value"] for k, c in checks.items()}
    return out


def readings(run, seed, controls):
    import jax
    import jax.numpy as jnp

    from pbench.reference import Reference

    drv = harness.load_module(harness.BENCH / "drivers" / "train.py", "drv")
    cell, cfg, tr = run.cell, run.cell.cfg, run.cell.traffic
    model = cell.model
    rs = harness.run_seed(seed)
    key = jax.random.PRNGKey(rs)
    data = model.make_data(jax.random.fold_in(key, 1), cfg)
    init = model.make_init(jax.random.fold_in(key, 2), cfg)
    run.seed = seed
    exp = drv.build_program(run, data, init)
    prog = drv.record(exp, tr["check"]["rounds"], tr["algorithm"])
    del exp
    gc.collect()
    theta0, eta_G0, eta_L0 = init
    init_h = drv.host({"theta": theta0, "eta_G": eta_G0, "eta_L": eta_L0})
    args = (tr["algorithm"], init, data, rs, tr["check"]["rounds"],
            tr["local_steps"], tr["optimizer"]["learning_rate"])
    t = time.perf_counter()
    ref = Reference(model, cfg).run(*args)
    out = {"seed": seed, "reference_s": time.perf_counter() - t,
           "program": compare.training_gaps(prog, ref, init_h)}
    if controls:
        low = Reference(model, cfg, dtype=jnp.bfloat16).run(*args)
        out["control_bf16"] = compare.training_gaps(low, ref, init_h)
        half = Reference(model, cfg).run(*args, fault="half_batch")
        out["half_batch"] = compare.training_gaps(half, ref, init_h)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--seconds", type=float, default=5.0,
                    help="window of a serving cell's readings")
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    jax = harness.setup_jax()
    devices = harness.chip_devices(jax, cell.chips, platform=args.platform)
    run = harness.Run(cell=cell, seed=0, seconds=args.seconds, trace=False,
                      t0=T0, devices=devices, peaks={})
    measure = serve_readings if cell.traffic["kind"] == "serve" else readings
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    seeds = [int(s) for s in args.seeds.split(",")]
    out = open(args.out, "a") if args.out else None
    for seed in seeds:
        line = json.dumps({"workload": cell.name,
                           **measure(run, seed, seed in controls)})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
