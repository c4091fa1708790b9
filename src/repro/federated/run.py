"""Federated runtime CLI — a thin spec-builder over ``repro.federated.api``.

    PYTHONPATH=src python -m repro.federated.run --model hier_bnn \
        --silos 8 --rounds 5 --local-steps 4

Flags build a declarative :class:`~repro.federated.api.ExperimentSpec`
(model registry name + kwargs, scenario, optimizers, seed), which is the
ONLY construction path — the CLI never wires a Server by hand. That makes
every run serializable and resumable:

    ... --dump-spec > exp.json          # print the spec as JSON, exit
    ... --spec exp.json                 # run exactly that spec
    ... --ckpt-dir runs/a               # checkpoint full round state
    ... --resume runs/a                 # continue a preempted run
    ... --list-models                   # registered models + descriptions

Variational families are spec-overridable (``repro.core.family``):

    ... --global-family cholesky           # full unitriangular η_G factor
    ... --global-family lowrank --global-family-kwargs '{"rank": 2}'

Server strategies are pluggable (``repro.federated.strategy``): ``--algo``
picks a registered name (or ``both`` for the SFVI/SFVI-Avg pair), and
``--strategy``/``--strategy-kwargs`` select one with hyperparameters:

    ... --strategy pvi --strategy-kwargs '{"damping": 0.2}'

Scenario knobs cover partial participation, straggler dropout, robust
aggregation, int8 wire compression and differential privacy:

    ... --participation 0.5 --dropout 0.1 --aggregator trimmed --compress int8
    ... --dp-noise 1.0 --dp-clip 0.5 --dp-delta 1e-5   # DP round + (ε, δ)

Buffered-asynchronous execution (FedBuff-style, docs/federated.md):

    ... --async --buffer-size 2 --staleness-decay 0.5 --latency lognormal

``--sweep`` ignores the single-scenario knobs and walks the full
scenario matrix (participation × stragglers × compression × DP from
``scenario_matrix``) in one invocation, printing an ELBO/ε/bytes table:

    ... --sweep --sweep-participation 1.0,0.5 --sweep-dp-noise 0.0,1.0

``--devices N`` forces N XLA host devices (as ``launch/comm.py`` does) so
the ``silo`` mesh axis actually spans devices and
``Server.compiled_collective_bytes`` reports real collective traffic. It
is refused on an accelerator backend, where ``--mesh`` pins the devices.

Execution topology is spec state (``spec.runtime``), set here with:

    ... --mesh silo=4,model=2 --devices 8    # 2-D (silo x model) mesh
    ... --wire fused                          # Pallas wire pipeline

Multi-process federation (one jax process per host; every process runs
the SAME command plus its process identity — or exports the
REPRO_COORDINATOR / REPRO_NUM_PROCESSES / REPRO_PROCESS_ID env schema):

    ... --mesh silo=8,multiprocess \
        --coordinator 10.0.0.1:8476 --num-processes 2 --process-id 0

JAX is imported *after* argument parsing so --devices can set XLA_FLAGS
(the registry lists model names without importing JAX).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from repro.models.paper.registry import list_models, model_names


def build_parser() -> argparse.ArgumentParser:
    """CLI schema (kept separate so docs/tests can introspect flags)."""
    ap = argparse.ArgumentParser(prog="repro.federated.run", description=__doc__)
    ap.add_argument("--model", default="hier_bnn", choices=model_names())
    ap.add_argument("--model-kwargs", default="", metavar="JSON",
                    help="JSON dict forwarded to the registry builder")
    ap.add_argument("--global-family", default=None, metavar="NAME",
                    help="override the model's q(Z_G) family with a "
                         "registered one (diag, cholesky, lowrank, ...); "
                         "default: the model's own choice")
    ap.add_argument("--global-family-kwargs", default="", metavar="JSON",
                    help="JSON kwargs for --global-family (e.g. "
                         '\'{"rank": 2}\' for lowrank)')
    ap.add_argument("--local-family", default=None, metavar="NAME",
                    help="override the model's q(Z_L | Z_G) family "
                         "(conditional, batched_diag, ...)")
    ap.add_argument("--local-family-kwargs", default="", metavar="JSON",
                    help="JSON kwargs for --local-family")
    ap.add_argument("--silos", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=None,
                    help="total rounds (default 5; with --resume, extends "
                         "the checkpointed spec's budget)")
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--algo", default="both",
                    choices=["both", "sfvi", "sfvi_avg", "pvi", "fed_ep"])
    ap.add_argument("--strategy", default=None, metavar="NAME",
                    help="registered ServerStrategy name (sfvi, sfvi_avg, "
                         "pvi, fed_ep, or any plugin registered through "
                         "repro.federated.strategy); overrides --algo. "
                         "Validated against the registry at build time so "
                         "plugin strategies need no CLI change")
    ap.add_argument("--strategy-kwargs", default="", metavar="JSON",
                    help="JSON dict of strategy hyperparameters, e.g. "
                         '\'{"damping": 0.2}\' for --strategy pvi')
    ap.add_argument("--lr", type=float, default=2e-2)
    ap.add_argument("--participation", type=float, default=1.0)
    ap.add_argument("--dropout", type=float, default=0.0)
    ap.add_argument("--aggregator", default="mean", choices=["mean", "trimmed"])
    ap.add_argument("--trim-frac", type=float, default=0.1)
    ap.add_argument("--compress", default="none", choices=["none", "int8"])
    ap.add_argument("--eta-mode", default="barycenter",
                    choices=["barycenter", "param"])
    ap.add_argument("--async", dest="async_mode", action="store_true",
                    help="buffered-asynchronous execution (FedBuff-style "
                         "flushes; round-cadence strategies only: sfvi_avg, "
                         "pvi, fed_ep — see docs/federated.md)")
    ap.add_argument("--buffer-size", type=int, default=2,
                    help="with --async: contributions per server flush")
    ap.add_argument("--staleness-decay", type=float, default=0.5,
                    help="with --async: weight (1+staleness)^-decay")
    ap.add_argument("--latency", default="lognormal",
                    choices=["constant", "lognormal", "straggler"],
                    help="with --async: deterministic per-silo latency model")
    ap.add_argument("--latency-scale", type=float, default=1.0,
                    help="with --async: median simulated seconds per task")
    ap.add_argument("--latency-sigma", type=float, default=0.5,
                    help="with --async: lognormal latency spread")
    ap.add_argument("--dp-noise", type=float, default=0.0,
                    help="Gaussian noise multiplier z (0 = DP off)")
    ap.add_argument("--dp-clip", type=float, default=1.0,
                    help="L2 clip norm C for silo uploads")
    ap.add_argument("--dp-delta", type=float, default=1e-5,
                    help="target delta for (eps, delta) reports")
    ap.add_argument("--eval-every", type=int, default=0,
                    help="run the registry eval hook every N rounds")
    ap.add_argument("--sweep", action="store_true",
                    help="run the full scenario matrix instead of one config")
    ap.add_argument("--sweep-participation", default="1.0,0.5")
    ap.add_argument("--sweep-dropout", default="0.0,0.2")
    ap.add_argument("--sweep-compress", default="none,int8")
    ap.add_argument("--sweep-dp-noise", default="0.0,1.0")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default="", metavar="SPEC",
                    help="federated mesh topology as 'silo=N,model=N' "
                         "(append ',multiprocess' for jax.distributed "
                         "runs), e.g. --mesh silo=4,model=2; default: the "
                         "auto 1-D silo mesh. Lands on spec.runtime.mesh; "
                         "with --resume, overrides the checkpointed "
                         "topology (re-padding/resharding keeps the real "
                         "silos bit-exact)")
    ap.add_argument("--wire", default="flat",
                    choices=["flat", "fused"],
                    help="silo->server wire layout (spec.runtime.wire)")
    ap.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                    help="jax.distributed coordinator address; starts the "
                         "multi-process runtime before any jax use "
                         "(or export REPRO_COORDINATOR)")
    ap.add_argument("--num-processes", type=int, default=None,
                    help="with --coordinator: total process count "
                         "(or REPRO_NUM_PROCESSES)")
    ap.add_argument("--process-id", type=int, default=None,
                    help="with --coordinator: this process's rank "
                         "(or REPRO_PROCESS_ID)")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N XLA host devices (0 = real devices)")
    ap.add_argument("--hlo-bytes", action="store_true",
                    help="also report compiled-HLO collective bytes")
    ap.add_argument("--sanitize", action="store_true",
                    help="run under repro.debug.sanitize(): transfer guard, "
                         "NaN checks, and a one-trace-per-config recompile "
                         "watchdog")
    ap.add_argument("--list-models", action="store_true",
                    help="print registered model names + descriptions, exit 0")
    ap.add_argument("--spec", default=None, metavar="FILE",
                    help="run this ExperimentSpec JSON (flags are ignored)")
    ap.add_argument("--dump-spec", action="store_true",
                    help="print the spec the flags build as JSON, exit 0 "
                         "(requires a single --algo)")
    ap.add_argument("--ckpt-dir", default=None, metavar="DIR",
                    help="save full round state here (at the end, and every "
                         "--ckpt-every rounds during the run)")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="with --ckpt-dir: also checkpoint every N rounds, "
                         "making long runs preemption-safe (0 = end only)")
    ap.add_argument("--resume", default=None, metavar="DIR",
                    help="resume a checkpointed run (reads DIR/spec.json)")
    ap.add_argument("--population", default="", metavar="JSON",
                    help="population dynamics (docs/federated.md): a "
                         "PopulationSpec as JSON, e.g. "
                         '\'{"initial": 2, "arrival_rate": 0.3, '
                         '"departure_rate": 0.1, "return_rate": 0.5}\'. '
                         "--silos becomes the roster MAXIMUM; only "
                         "'initial' silos are live at round 0")
    return ap


def _async_cfg_from_args(args):
    """The --async flags as an AsyncConfig, or None without --async."""
    if not args.async_mode:
        return None
    from repro.federated.scheduler import AsyncConfig

    return AsyncConfig(
        buffer_size=args.buffer_size,
        staleness_decay=args.staleness_decay,
        latency=args.latency,
        latency_scale=args.latency_scale,
        latency_sigma=args.latency_sigma,
    )


def _family_spec(name, kwargs_json):
    """A FamilySpec from the CLI's (name, JSON-kwargs) flag pair."""
    if name is None:
        return None
    from repro.core.family import FamilySpec

    return FamilySpec(name, kwargs=json.loads(kwargs_json or "{}"))


def _spec_from_args(args, algorithm: str):
    """The thin spec-builder: CLI flags -> declarative ExperimentSpec."""
    from repro.federated.api import (ExperimentSpec, ModelSpec,
                                     OptimizerSpec, RuntimeSpec)
    from repro.federated.population import PopulationSpec
    from repro.federated.scheduler import Scenario
    from repro.federated.strategy import StrategySpec
    from repro.launch.mesh import MeshSpec

    strat_kwargs = json.loads(args.strategy_kwargs or "{}")
    async_cfg = _async_cfg_from_args(args)
    scenario = Scenario(
        algorithm=algorithm,
        participation=args.participation,
        dropout=args.dropout,
        compression=args.compress,
        dp_noise=args.dp_noise,
        dp_clip=args.dp_clip,
        dp_delta=args.dp_delta,
        aggregator=args.aggregator,
        trim_frac=args.trim_frac,
        async_cfg=async_cfg,
    )
    return ExperimentSpec(
        model=ModelSpec(
            args.model,
            kwargs=json.loads(args.model_kwargs or "{}"),
            global_family=_family_spec(
                args.global_family, args.global_family_kwargs),
            local_family=_family_spec(
                args.local_family, args.local_family_kwargs),
        ),
        scenario=scenario,
        strategy=(StrategySpec(algorithm, strat_kwargs)
                  if strat_kwargs else None),
        num_silos=args.silos,
        rounds=args.rounds if args.rounds is not None else 5,
        local_steps=args.local_steps,
        server_opt=OptimizerSpec("adam", args.lr),
        eta_mode=args.eta_mode,
        eval_every=args.eval_every,
        seed=args.seed,
        runtime=RuntimeSpec(
            wire=args.wire,
            mesh=MeshSpec.parse(args.mesh),
            sanitize=args.sanitize,
        ),
        population=(PopulationSpec.from_dict(json.loads(args.population))
                    if args.population else None),
    )


def _log_round(total_silos: int):
    def log(r, m):
        eps = f"  eps={m['epsilon']:7.3f}" if "epsilon" in m else ""
        # Async flushes additionally report simulated time + staleness.
        sim = (f"  t={m['sim_time']:8.2f}s  stale<={m['staleness']:.0f}"
               if "sim_time" in m else "")
        print(f"  round {r:3d}  elbo={m['elbo']:14.2f}  "
              f"up={m['bytes_up']:>9d}B  down={m['bytes_down']:>9d}B  "
              f"active={m['n_active']}/{total_silos}{sim}{eps}")
    return log


def _report(exp, hlo_bytes: bool) -> None:
    srv, spec = exp.server, exp.spec
    print(f"  total: {srv.comm.total:,} B in {srv.comm.rounds} rounds "
          f"({srv.comm.per_round:,.0f} B/round)")
    if srv.comm.sim_seconds:
        print(f"  simulated wall-clock: {srv.comm.sim_seconds:,.1f}s "
              f"({srv.comm.sim_seconds / max(srv.comm.rounds, 1):.2f}s/flush)")
    if exp.accountant is not None:
        policy = spec.scenario.privacy()
        eps, order = exp.accountant.epsilon(policy.delta)
        print(f"  privacy: ({eps:.3f}, {policy.delta:g})-DP after "
              f"{exp.accountant.steps} exchanges (RDP order {order})")
    for k, v in exp.evaluate().items():
        print(f"  {k}: {v:.3f}")
    if hlo_bytes:
        coll = srv.compiled_collective_bytes(spec.algorithm, spec.local_steps)
        total = sum(coll.values())
        print(f"  compiled-HLO collective bytes/round: {total:,.0f} "
              f"({ {k: int(v) for k, v in coll.items() if v} })")


def _run_one(spec, bundle, hlo_bytes: bool = False, ckpt_dir=None,
             ckpt_every: int = 0, sanitize=None):
    """Build + run one spec against a pre-staged bundle; print a report."""
    from repro.federated.api import build

    exp = build(spec, bundle=bundle)
    from repro.federated.scheduler import algorithm_label
    name = algorithm_label(spec.algorithm)
    sc = spec.scenario
    print(f"\n== {name}: {spec.model.name}, J={spec.num_silos}, "
          f"{spec.rounds} rounds x {spec.local_steps} local steps"
          + (f", {sc.async_cfg.name}" if sc.async_cfg is not None else "")
          + (f", DP(z={sc.dp_noise:g}, C={sc.dp_clip:g})" if sc.dp_noise > 0 else "")
          + " ==")
    t0 = time.time()
    log = _log_round(spec.num_silos)

    def cb(r, metrics):
        log(r, metrics)
        # Periodic mid-run checkpoint: a preempted run restarts from the
        # last multiple of --ckpt-every instead of from scratch.
        if ckpt_dir and ckpt_every and (r + 1) % ckpt_every == 0 \
                and (r + 1) < spec.rounds:
            exp.save(ckpt_dir)

    exp.run(callback=cb, sanitize=sanitize)
    print(f"  wall time: {time.time() - t0:.1f}s")
    if ckpt_dir:
        print(f"  checkpoint: {exp.save(ckpt_dir)}")
    _report(exp, hlo_bytes)
    return exp


def _run_sweep(args, base_spec, bundle) -> int:
    """One invocation, the whole scenario grid (ELBO / ε / bytes table)."""
    from repro.federated.api import build, scenario_specs
    from repro.federated.scheduler import scenario_matrix

    def floats(s):
        return tuple(float(x) for x in s.split(","))

    # --async adds an async axis to the sweep (sync rows kept for
    # comparison; the matrix drops invalid async combinations itself).
    async_cfg = _async_cfg_from_args(args)
    grid = scenario_matrix(
        algorithms=(["sfvi", "sfvi_avg"] if args.algo == "both"
                    else [args.algo]),
        participation=floats(args.sweep_participation),
        dropout=floats(args.sweep_dropout),
        compression=tuple(args.sweep_compress.split(",")),
        dp_noise=floats(args.sweep_dp_noise),
        dp_clip=args.dp_clip,
        dp_delta=args.dp_delta,
        async_cfgs=((None,) if async_cfg is None else (None, async_cfg)),
    )
    specs = scenario_specs(base_spec, grid)
    print(f"\n== scenario sweep: {base_spec.model.name}, J={base_spec.num_silos}, "
          f"{len(specs)} scenarios x {base_spec.rounds} rounds ==")
    rows = []
    for spec in specs:
        exp = build(spec, bundle=bundle)
        t0 = time.time()
        h = exp.run()
        dt = time.time() - t0
        eps = h["epsilon"][-1] if "epsilon" in h else float("inf")
        rows.append((spec.scenario.name, h["elbo"][-1], eps,
                     exp.comm.per_round / 1024, dt / spec.rounds))
    w = max(len(r[0]) for r in rows)
    print(f"  {'scenario':<{w}}  {'ELBO':>12}  {'eps':>8}  "
          f"{'KiB/round':>10}  {'s/round':>8}")
    for name, elbo, eps, kib, spr in rows:
        eps_s = f"{eps:8.3f}" if eps != float("inf") else "     inf"
        print(f"  {name:<{w}}  {elbo:12.2f}  {eps_s}  {kib:10.1f}  {spr:8.2f}")
    return 0


def _resume(args) -> int:
    """Continue a checkpointed run from ``--resume DIR``.

    ``--rounds N`` extends (or shrinks) the checkpointed spec's total
    budget — e.g. resume a finished 20-round run out to 50.
    """
    import dataclasses

    from repro.federated.api import Experiment, ExperimentSpec

    spec = ExperimentSpec.load(os.path.join(args.resume, "spec.json"))
    if args.rounds is not None:
        spec = dataclasses.replace(spec, rounds=args.rounds)
    if args.mesh:
        # Topology override at resume time: the runtime re-pads and
        # reshards the stacked silo state for the new mesh; the real
        # silos' trajectory is unchanged.
        from repro.launch.mesh import MeshSpec

        spec = dataclasses.replace(
            spec, runtime=dataclasses.replace(
                spec.runtime, mesh=MeshSpec.parse(args.mesh)))
    exp = Experiment.resume(args.resume, spec=spec)
    remaining = exp.remaining_rounds
    print(f"== resume: {spec.name} at round {exp.round}/{spec.rounds} "
          f"({remaining} remaining) ==")
    if remaining:
        out = args.ckpt_dir or args.resume
        log = _log_round(spec.num_silos)

        def cb(r, metrics):
            log(r, metrics)
            # Resumed runs stay preemption-safe under --ckpt-every too.
            if args.ckpt_every and (r + 1) % args.ckpt_every == 0 \
                    and (r + 1) < spec.rounds:
                exp.save(out)

        exp.run(callback=cb, sanitize=True if args.sanitize else None)
        exp.save(out)
    _report(exp, args.hlo_bytes)
    return 0


def main(argv=None) -> int:
    """Run the requested spec(s) and assert the §3.2 byte ordering."""
    parser = build_parser()
    args = parser.parse_args(argv)
    if args.list_models:
        width = max(len(n) for n, _ in list_models())
        for name, desc in list_models():
            print(f"{name:<{width}}  {desc}")
        return 0
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}"
        )
    if args.coordinator or os.environ.get("REPRO_COORDINATOR"):
        # Multi-process runtime must start before ANY other jax use —
        # the gloo CPU-collectives switch and the device topology are
        # locked at first jax init.
        from repro.federated import distributed

        distributed.initialize(args.coordinator, args.num_processes,
                               args.process_id)
    import jax

    from repro import compile_cache

    if args.devices and jax.default_backend() != "cpu":
        # The flag only multiplies CPU host devices; on an accelerator
        # the mesh would silently span fewer devices than asked for.
        parser.error(f"--devices forces CPU host devices, but the backend "
                     f"is {jax.default_backend()!r}; pin the accelerator "
                     f"mesh with --mesh instead")
    compile_cache.enable()
    if args.resume:
        return _resume(args)

    from repro.federated.api import ExperimentSpec

    if args.spec:
        specs = [ExperimentSpec.load(args.spec)]
    else:
        if args.strategy:
            algos = [args.strategy]
        elif args.async_mode:
            # Buffered-async execution needs a round-cadence strategy
            # (step-cadence SFVI has no round-granular contribution to
            # buffer); default to SFVI-Avg, or --strategy pvi/fed_ep.
            algos = ["sfvi_avg"]
        elif args.algo == "both":
            algos = ["sfvi", "sfvi_avg"]
        else:
            algos = [args.algo]
        specs = [_spec_from_args(args, a) for a in algos]
    if args.dump_spec:
        if len(specs) != 1:
            print("--dump-spec needs a single algorithm; pass --algo or "
                  "--strategy with one registered name", file=sys.stderr)
            return 2
        print(specs[0].to_json())
        return 0

    # One dataset/problem staging, shared by every run of this invocation.
    from repro.models.paper.registry import get_model

    base = specs[0]
    # Mirror api.build's staging rule: data_seed overrides seed. Staging
    # with base.seed here would hand --spec runs a different dataset than
    # build(spec)/--resume rebuild.
    data_seed = base.data_seed if base.data_seed is not None else base.seed
    bundle = get_model(base.model.name).build(
        data_seed, base.num_silos, **base.model.kwargs)
    if args.sweep:
        return _run_sweep(args, base, bundle)

    def ckpt_dir_for(spec):
        if not args.ckpt_dir:
            return None
        return (args.ckpt_dir if len(specs) == 1
                else os.path.join(args.ckpt_dir, spec.algorithm))

    exps = {s.algorithm: _run_one(s, bundle, args.hlo_bytes,
                                  ckpt_dir=ckpt_dir_for(s),
                                  ckpt_every=args.ckpt_every,
                                  sanitize=True if args.sanitize else None)
            for s in specs}
    if len(exps) == 2:
        sfvi_pr = exps["sfvi"].comm.per_round
        avg_pr = exps["sfvi_avg"].comm.per_round
        print(f"\nbytes/round: SFVI={sfvi_pr:,.0f}  SFVI-Avg={avg_pr:,.0f}  "
              f"(x{sfvi_pr / max(avg_pr, 1):.1f} reduction — §3.2: one sync "
              f"per round instead of one per local step)")
        if args.local_steps > 1:
            assert avg_pr < sfvi_pr, \
                "SFVI-Avg must ship strictly fewer bytes/round"
        else:
            # K=1: both algorithms exchange once per round — equal cost.
            assert avg_pr <= sfvi_pr, \
                "SFVI-Avg must never ship more bytes/round than SFVI"
    return 0


if __name__ == "__main__":
    sys.exit(main())
