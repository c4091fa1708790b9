#!/usr/bin/env python3
"""Find the knee of a serving cell: offer its mix at several fixed rates.

    python3 perfbench/sweep.py --workload <serving cell> --rates 50,100,200 \
        --seconds 8 [--out sweep.jsonl]

One process builds and warms the posterior once, then serves the mix
open-loop at each rate for ``--seconds`` and prints, per rate, what was
offered and answered inside the window, the latency median and 95th
percentile, and the queries still waiting when the window closed. The
knee is the highest rate at which the backlog does not grow through the
window; a serving cell offers about four fifths of it, written into its
traffic file as a number.
"""
import argparse
import json
import math
import pathlib
import statistics
import sys
import time

T0 = time.perf_counter()
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from pbench import harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    jax = harness.setup_jax()
    devices = harness.chip_devices(jax, cell.chips)
    drv = harness.load_module(harness.BENCH / "drivers" / "serve.py", "drv")
    run = harness.Run(cell=cell, seed=args.seed, seconds=args.seconds,
                      trace=False, t0=T0, devices=devices, peaks={})
    seed = harness.run_seed(args.seed)
    _, _, _, pool, post = drv.prepare(run, jax.random.PRNGKey(seed))
    out = open(args.out, "a") if args.out else None
    for rate in [float(r) for r in args.rates.split(",")]:
        tr = dict(cell.traffic, rate_per_s=rate)
        items = drv.schedule(tr, cell.cfg["num_silos"], args.seconds)
        with harness.CompileClock() as clock:
            calls, widest = drv.serve(post, items, pool, args.seconds, 2.0)
        lat = sorted((it.done - it.due) if not math.isnan(it.done)
                     else math.inf for it in items)
        row = {"workload": cell.name, "rate": rate, "offered": len(items),
               "answered_in_window": sum(1 for it in items
                                         if it.done <= args.seconds),
               "backlog_at_close": sum(1 for it in items
                                       if not it.done <= args.seconds),
               "p50_ms": statistics.median(lat) * 1e3,
               "p95_ms": drv.percentile(lat, 0.95) * 1e3,
               "calls": len(calls),
               "queries_per_call": len(items) / max(len(calls), 1),
               "widest_group": widest,
               "compiles": clock.programs,
               "call_p50_ms": statistics.median(b - a for a, b in calls) * 1e3}
        print(json.dumps(row), flush=True)
        if out:
            out.write(json.dumps(row) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
