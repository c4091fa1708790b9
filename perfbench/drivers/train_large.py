"""Training cells whose θ fills the chip: the ``train`` driver's set-up,
recorded rounds and window, with two differences that such a θ forces.

* The program's round, which accumulates silos and donates its state at
  this size (``runtime.Server.accumulates``), consumes the initial state
  it was built from; the comparison makes that state again from the seed
  (``make_init`` is a pure function of it).
* The plain reference is ``pbench/reference_seq.py``: the SFVI run of
  ``pbench/reference.py`` with silos taken in turn and a donated state,
  which fits beside θ and Adam on one chip where the vmapped one does not.

``readings`` gives ``calibrate_large.py`` its numbers per seed (the
program's gaps, and the bf16 control's and the half-batch fault's).
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp

from pbench import compare, harness
from pbench import trace as tracing
from pbench.reference_seq import SequentialReference

train = harness.load_module(harness.BENCH / "drivers" / "train.py",
                            "pbench_driver_train")


def inputs(model, cfg, seed):
    key = jax.random.PRNGKey(harness.run_seed(seed))
    return (model.make_data(jax.random.fold_in(key, 1), cfg),
            model.make_init(jax.random.fold_in(key, 2), cfg))


def program_rounds(run, data, init):
    """Build the experiment from ``data``/``init`` and record its first
    rounds: (experiment, recorded numbers)."""
    tr = run.cell.traffic
    exp = train.build_program(run, data, init)
    return exp, train.record(exp, tr["check"]["rounds"], tr["algorithm"])


def reference(run, data, dtype=jnp.float32, fault=None):
    """(initial state on the host, reference run with its parameters on
    the host): the reference in ``dtype``, with ``fault``, from a state
    made anew from the seed, which it consumes."""
    cfg, tr = run.cell.cfg, run.cell.traffic
    _, init = inputs(run.cell.model, cfg, run.seed)
    theta0, eta_G0, eta_L0 = init
    init_h = train.host({"theta": theta0, "eta_G": eta_G0, "eta_L": eta_L0})
    ref = SequentialReference(run.cell.model, cfg, dtype=dtype).run(
        tr["algorithm"], init, data, harness.run_seed(run.seed),
        tr["check"]["rounds"], tr["local_steps"],
        tr["optimizer"]["learning_rate"], fault=fault)
    ref["params"] = train.host(ref["params"])
    gc.collect()
    return init_h, ref


def run(run):
    cell, cfg, tr = run.cell, run.cell.cfg, run.cell.traffic
    model = cell.model
    check = tr["check"]
    with harness.CompileClock() as setup_clock:
        data, init = inputs(model, cfg, run.seed)
        exp, prog = program_rounds(run, data, init)
        del init
    run.counters["compile_s"] = setup_clock.seconds
    rows = model.rows_per_silo(cfg)
    per_step = cfg["num_silos"] * tr["local_steps"]
    run.counters["flops_per_round"] = float(
        per_step * model.matmul_flops_per_silo_step(cfg, rows))
    run.counters["expert_flops_per_round"] = float(
        per_step * model.expert_matmul_flops_per_silo_step(cfg, rows))
    seconds = min(run.seconds, tr["trace_seconds"]) if run.trace else run.seconds
    trace_dir = harness.temp_dir("trace_") if run.trace else None
    run.end_to_end["setup_s"] = time.perf_counter() - run.t0
    with harness.CompileClock() as window_clock:
        if trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            with jax.profiler.trace(trace_dir, profiler_options=opts):
                rounds, elapsed = train.drive(exp, seconds)
        else:
            rounds, elapsed = train.drive(exp, seconds)
    run.counters["compiles_in_window"] = window_clock.programs
    run.counters["rounds_in_window"] = rounds
    run.end_to_end["rounds_per_s"] = rounds / elapsed
    run.attempted, run.failed = rounds, 0
    run.memory_peak = harness.memory_peak(run.devices)
    del exp
    gc.collect()
    if trace_dir:
        run.reduced = tracing.reduce_dir(trace_dir)
    t = time.perf_counter()
    init_h, ref = reference(run, data)
    gaps = compare.training_gaps(prog, ref, init_h)
    run.checks = {k: {"value": v, "limit": check["limits"][k]}
                  for k, v in gaps.items() if k in check["limits"]}
    run.counters["check_s"] = time.perf_counter() - t


def readings(run, seed, controls):
    """One seed's readings for the limits: the program's gaps, and with
    ``controls`` those of the bf16 control and the half-batch fault,
    each against the float32 reference."""
    cell, cfg = run.cell, run.cell.cfg
    run.seed = seed
    data, init = inputs(cell.model, cfg, seed)
    exp, prog = program_rounds(run, data, init)
    del exp, init
    gc.collect()
    t = time.perf_counter()
    init_h, ref = reference(run, data)
    out = {"seed": seed, "reference_s": time.perf_counter() - t,
           "program": compare.training_gaps(prog, ref, init_h)}
    if controls:
        _, low = reference(run, data, dtype=jnp.bfloat16)
        out["control_bf16"] = compare.training_gaps(low, ref, init_h)
        _, half = reference(run, data, fault="half_batch")
        out["half_batch"] = compare.training_gaps(half, ref, init_h)
    return out
