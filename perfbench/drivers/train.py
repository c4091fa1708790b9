"""Training cells: federated rounds through the program's normal path.

Set-up makes the data and the initial state from the seed on the device,
builds ONE experiment (spec -> ``build`` -> ``Experiment``), and drives
it through its first rounds with the window's own call,
``Experiment.run(rounds=1)``; those rounds compile, warm up, and are
recorded for the comparison. The window then goes on calling the same
object until ``--seconds`` have passed. A round is finished when
``Experiment.run`` returns, which is after the program's own ELBO
``device_get``. After the window the program is freed and the plain
reference (pbench/reference.py) replays the recorded rounds.

Traffic keys: ``algorithm``, ``local_steps``, ``optimizer`` (name,
learning_rate), ``wire``, ``mesh`` (MeshSpec fields),
``round_program`` (the name of the program's jitted round),
``trace_seconds``, and ``check``: ``rounds`` recorded and ``limits``.
"""
from __future__ import annotations

import gc
import time

import jax
import numpy as np

from pbench import compare, harness
from pbench import trace as tracing
from pbench.reference import Reference


def host(tree):
    return jax.tree_util.tree_map(np.asarray, jax.device_get(tree))


def build_program(run, data, init):
    """The experiment the window drives, from benchmark-made inputs."""
    from repro.federated.api import (ExperimentSpec, ModelSpec, OptimizerSpec,
                                     RuntimeSpec, build)
    from repro.federated.scheduler import Scenario
    from repro.launch.mesh import MeshSpec
    from repro.models.paper.registry import ModelBundle

    cell, cfg, tr = run.cell, run.cell.cfg, run.cell.traffic
    model = cell.model
    J = cfg["num_silos"]
    theta0, eta_G0, eta_L0 = init
    datas = [jax.tree_util.tree_map(lambda x, j=j: x[j], data)
             for j in range(J)]
    rows = model.rows_per_silo(cfg)
    bundle = ModelBundle(problem=model.program_model(cfg), theta0=theta0,
                         datas=datas, num_obs=[rows] * J)
    spec = ExperimentSpec(
        model=ModelSpec(cfg["model"], {}),
        scenario=Scenario(algorithm=tr["algorithm"]),
        num_silos=J,
        rounds=10 ** 9,
        local_steps=tr["local_steps"],
        server_opt=OptimizerSpec(**tr["optimizer"]),
        seed=harness.run_seed(run.seed),
        runtime=RuntimeSpec(wire=tr["wire"], mesh=MeshSpec(**tr["mesh"])),
    )
    exp = build(spec, bundle)
    exp.server.state["eta_L"] = exp.server.pad_silo_axis(eta_L0)
    exp.warm_start(eta_G=eta_G0)  # places the whole state on the mesh
    return exp


def first_moment(opt_state):
    """The first moment of a chain(scale_by_adam, ...) optimizer state."""
    return opt_state[0].mu


def record(exp, rounds, algorithm):
    """Run the first ``rounds`` rounds; keep what the comparison needs."""
    J, K = exp.server.J, exp.spec.local_steps
    m1 = None
    for r in range(rounds):
        exp.run(rounds=1)
        if r == 0:
            st = exp.server.state
            m1 = {"eta_L": jax.tree_util.tree_map(
                lambda x: x[:J], first_moment(st["opt_local"]))}
            if algorithm == "sfvi":
                m1.update(first_moment(st["opt_server"]))
            m1 = host(m1)
    st = exp.server.state
    params = host({"theta": st["theta"], "eta_G": st["eta_G"],
                   "eta_L": jax.tree_util.tree_map(lambda x: x[:J],
                                                   st["eta_L"])})
    elbo = np.asarray(exp.history["elbo_trace"], np.float64).reshape(rounds, K)
    return {"elbo": elbo, "m1": m1, "params": params}


def drive(exp, seconds):
    """Call ``Experiment.run`` one round at a time until ``seconds`` have
    passed."""
    rounds = 0
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        while True:
            with jax.profiler.TraceAnnotation("bench.experiment_run"):
                exp.run(rounds=1)
            rounds += 1
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                return rounds, elapsed


def run(run):
    cell, cfg, tr = run.cell, run.cell.cfg, run.cell.traffic
    model = cell.model
    seed = harness.run_seed(run.seed)
    key = jax.random.PRNGKey(seed)
    check = tr["check"]
    with harness.CompileClock() as setup_clock:
        data = model.make_data(jax.random.fold_in(key, 1), cfg)
        init = model.make_init(jax.random.fold_in(key, 2), cfg)
        exp = build_program(run, data, init)
        prog = record(exp, check["rounds"], tr["algorithm"])
    run.counters["compile_s"] = setup_clock.seconds
    run.counters["flops_per_round"] = float(
        cfg["num_silos"] * tr["local_steps"]
        * model.matmul_flops_per_silo_step(cfg, model.rows_per_silo(cfg)))
    seconds = min(run.seconds, tr["trace_seconds"]) if run.trace else run.seconds
    trace_dir = harness.temp_dir("trace_") if run.trace else None
    run.end_to_end["setup_s"] = time.perf_counter() - run.t0
    with harness.CompileClock() as window_clock:
        if trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            with jax.profiler.trace(trace_dir, profiler_options=opts):
                rounds, elapsed = drive(exp, seconds)
        else:
            rounds, elapsed = drive(exp, seconds)
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
    ref = Reference(model, cfg).run(
        tr["algorithm"], init, data, seed, check["rounds"],
        tr["local_steps"], tr["optimizer"]["learning_rate"])
    theta0, eta_G0, eta_L0 = init
    gaps = compare.training_gaps(
        prog, ref, host({"theta": theta0, "eta_G": eta_G0, "eta_L": eta_L0}))
    run.checks = {k: {"value": v, "limit": check["limits"][k]}
                  for k, v in gaps.items() if k in check["limits"]}
    run.counters["check_s"] = time.perf_counter() - t
