#!/usr/bin/env python3
"""Smoke test of the federated SFVI main path on a TPU.

    python3 chip_smoke.py              # one chip: phases 1-5
    python3 chip_smoke.py --chips 4    # four chips: the silo and 2-D meshes

The path is the one a user drives: an ``ExperimentSpec``, ``build``,
``Experiment.run``, ``Experiment.save`` and ``Posterior``. The model is
hier_bnn at the widths of ``build_hier_bnn`` itself (784 inputs, 64
hidden units, 10 classes, so η_G is a 100,354-float wire row), federated
over J=16 silos of 200 synthetic rows each and trained with SFVI-Avg at
K=4 local steps. Data and initial weights come from seeds.

Phases, all in this one process (it starts no child):

  1. device    — JAX must find a TPU; prints its kind and count.
  2. toy       — the toy model's posterior mean against its closed form.
  3. train     — 5 hier_bnn rounds on the chip: compile seconds, a finite
                 and rising ELBO, one trace of the round graph, metered
                 upload bytes equal to the compiled all-gather; then 2
                 rounds of the same spec on host CPU devices, and the gap.
  4. fused     — the same spec on the fused Pallas wire, plain and int8,
                 against phase 3's ELBO trajectory.
  5. posterior — save, restore through ``Posterior.from_checkpoint``,
                 answer sample / global_sample / predict queries.

``--chips 4`` runs instead phase 3's chip checks on ``MeshSpec(silo=4)``
and ``MeshSpec(silo=2, model=2)`` against ``MeshSpec(silo=1)``, and
reports the gaps and the bytes each device holds.

The last line of standard output is ``{"ok": true, "device": {...}}``,
printed only when every phase passed. Without a TPU, or outside a
checkout of this repository, the script exits non-zero without it.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import pathlib
import sys
import tempfile
import time
import traceback

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import compile_cache, debug  # noqa: E402
from repro.federated.api import (  # noqa: E402
    ExperimentSpec,
    ModelSpec,
    OptimizerSpec,
    RuntimeSpec,
    build,
)
from repro.federated.runtime import Server  # noqa: E402
from repro.federated.scheduler import Scenario  # noqa: E402
from repro.federated.serve import Posterior, Query  # noqa: E402
from repro.launch.mesh import MeshSpec, build_mesh  # noqa: E402

# build_hier_bnn's own widths (the registry fixture defaults to 196/32).
HIER_BNN = {"in_dim": 784, "hidden": 64}
NUM_SILOS = 16
LOCAL_STEPS = 4
TRAIN_ROUNDS = 5
REF_ROUNDS = 2
CPU_REF_DEVICES = 4

# Bounds, fixed before the first chip run.
# |E_q[mu] - exact posterior mean| of the toy model: under 4% of the exact
# posterior sd (0.578); the CPU run lands within 1e-6.
TOY_BOUND = 0.02
# Chip vs host CPU after REF_ROUNDS rounds. TPU f32 matmuls run at reduced
# (bf16-pass) precision by default, so the runs differ; a wrong placement,
# silo order or sign differs by O(1).
ELBO_REL_GAP = 1e-2  # |ELBO_chip - ELBO_cpu| / |ELBO_cpu|, every round
ETA_STEP_REL_GAP = 0.5  # ||Δη_chip - Δη_cpu|| / ||Δη_cpu||, Δ from init
# Fused wire vs flat wire, max over rounds of the relative ELBO gap.
FUSED_REL_GAP = {"none": 1e-3, "int8": 1e-2}


class PhaseError(RuntimeError):
    """A smoke check failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseError(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def phase(name: str):
    t0 = time.perf_counter()
    log(f"[{name}] start")
    try:
        yield
    except Exception:
        log(f"[{name}] FAILED after {time.perf_counter() - t0:.1f}s")
        raise
    log(f"[{name}] ok in {time.perf_counter() - t0:.1f}s")


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling while active."""

    def __init__(self):
        self.seconds = 0.0

    def _listen(self, event: str, duration: float, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._listen)


def _flat(tree) -> np.ndarray:
    return np.concatenate([np.asarray(x, np.float64).ravel()
                           for x in jax.tree_util.tree_leaves(
                               jax.device_get(tree))])


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


def device_bytes(tree) -> dict:
    """Bytes each device holds of ``tree``'s arrays, keyed by device id."""
    out: dict = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in leaf.addressable_shards:
            out[shard.device.id] = out.get(shard.device.id, 0) + \
                shard.data.nbytes
    return dict(sorted(out.items()))


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------


def hier_bnn_spec(*, model_kwargs=None, num_silos=NUM_SILOS,
                  rounds=TRAIN_ROUNDS, wire="flat", compression="none",
                  mesh=MeshSpec()) -> ExperimentSpec:
    return ExperimentSpec(
        model=ModelSpec("hier_bnn",
                        dict(HIER_BNN if model_kwargs is None
                             else model_kwargs)),
        scenario=Scenario(algorithm="sfvi_avg", compression=compression),
        num_silos=num_silos,
        rounds=rounds,
        local_steps=LOCAL_STEPS,
        server_opt=OptimizerSpec("adam", 2e-2),
        seed=0,
        runtime=RuntimeSpec(wire=wire, mesh=mesh),
    )


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def phase_device(platform: str = "tpu", min_count: int = 1) -> dict:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"[device] platform={info['platform']} kind={info['kind']!r} "
        f"count={info['count']} jax={jax.__version__}")
    check(info["platform"] == platform,
          f"need a {platform} device, JAX found {info['platform']!r}")
    check(info["count"] >= min_count,
          f"need {min_count} devices, JAX found {info['count']}")
    return info


def phase_toy(rounds: int = 80, local_steps: int = 25) -> float:
    spec = ExperimentSpec(
        model=ModelSpec("toy", {"num_obs": 40}),
        scenario=Scenario(algorithm="sfvi"),
        num_silos=3,
        rounds=rounds,
        local_steps=local_steps,
        server_opt=OptimizerSpec("adam", 5e-2),
        seed=0,
    )
    exp = build(spec)
    h = exp.run()
    err = exp.evaluate()["abs_error_vs_exact"]
    log(f"[toy] {rounds} rounds x {local_steps} steps: final ELBO "
        f"{h['elbo'][-1]!r}; |E_q[mu] - exact| = {err!r} "
        f"(bound {TOY_BOUND}, posterior sd "
        f"{exp.bundle.extras['posterior_sd']!r})")
    check(bool(np.all(np.isfinite(h["elbo"]))), "toy ELBO not finite")
    check(err < TOY_BOUND, f"toy posterior mean off by {err} >= {TOY_BOUND}")
    return err


def run_spec(spec: ExperimentSpec, label: str, keep_round: int = -1):
    """Build and run ``spec`` on the default devices.

    Returns ``(exp, history, eta_G after round keep_round)`` after the
    chip checks every training run must pass: finite and rising ELBO,
    one trace of the round graph, and per-round wall times printed.
    """
    exp = build(spec)
    kept = {}
    stamps = [time.perf_counter()]

    def cb(r, _metrics):
        stamps.append(time.perf_counter())
        if r == keep_round:
            kept["eta_G"] = jax.device_get(exp.server.eta_G)

    with CompileClock() as clock, debug.watch_recompiles() as wd:
        h = exp.run(callback=cb)
    walls = np.diff(stamps)
    elbo = np.asarray(h["elbo"], np.float64)
    log(f"[{label}] compile {clock.seconds:.2f}s; round wall s "
        f"{[round(float(w), 4) for w in walls]}")
    log(f"[{label}] ELBO per round {[float(e) for e in elbo]}")
    log(f"[{label}] round-graph traces {wd.total}; metered bytes/round up "
        f"{h['bytes_up'][0]} down {h['bytes_down'][0]}")
    check(bool(np.all(np.isfinite(elbo))), f"{label}: ELBO not finite")
    check(bool(np.all(np.diff(elbo) > 0)), f"{label}: ELBO not rising")
    check(wd.total == 1, f"{label}: round graph traced {wd.total} times")
    return exp, h, kept.get("eta_G")


def cpu_reference(exp, rounds: int, devices):
    """The same spec's first ``rounds`` rounds on a mesh of CPU devices,
    through ``Server(mesh=...)`` with the bundle ``build`` staged."""
    spec, bundle = exp.spec, exp.bundle
    problem = bundle.problem
    mesh = build_mesh(MeshSpec(silo=len(devices)), devices=devices)
    local = spec.local_opt if spec.local_opt is not None else spec.server_opt
    with jax.default_device(devices[0]):
        srv = Server(
            problem, bundle.datas, bundle.theta0,
            # repro-lint: allow[R1] — the spec's η_G init root, as api.build
            problem.global_family.init(jax.random.PRNGKey(spec.seed)),
            num_obs=bundle.num_obs,
            server_opt=spec.server_opt.build(),
            local_opt=local.build(),
            aggregator=spec.scenario.make_aggregator(),
            compressor=spec.scenario.compressor(),
            eta_mode=spec.eta_mode,
            wire=spec.runtime.wire,
            mesh=mesh,
            privacy=spec.scenario.privacy(),
            seed=spec.seed,
            strategy=spec.algorithm,
        )
        h = srv.run(rounds, local_steps=spec.local_steps,
                    scheduler=spec.scenario.scheduler(spec.num_silos,
                                                      seed=spec.seed))
    return srv, h


def phase_train(spec: ExperimentSpec, ref_devices):
    """Phase 3: train on the chip, then compare with host CPU devices."""
    exp, h, eta_kept = run_spec(spec, "train", keep_round=REF_ROUNDS - 1)
    srv, h_ref = cpu_reference(exp, REF_ROUNDS, ref_devices)
    gathered = srv.compiled_collective_bytes(None, spec.local_steps)
    log(f"[train] compiled all-gather bytes/round on {len(ref_devices)} "
        f"CPU devices {gathered['all-gather']!r}; metered "
        f"{h['bytes_up'][0]}")
    check(gathered["all-gather"] == h["bytes_up"][0],
          "metered upload bytes differ from the compiled all-gather")
    elbo_gap = _rel(h["elbo"][:REF_ROUNDS], h_ref["elbo"])
    eta0 = _flat(exp.bundle.problem.global_family.init(
        # repro-lint: allow[R1] — the spec's η_G init root, as api.build
        jax.random.PRNGKey(spec.seed)))
    step_chip = _flat(eta_kept) - eta0
    step_cpu = _flat(srv.eta_G) - eta0
    eta_gap = float(np.linalg.norm(step_chip - step_cpu)
                    / np.linalg.norm(step_cpu))
    log(f"[train] chip vs CPU after {REF_ROUNDS} rounds: ELBO chip "
        f"{h['elbo'][:REF_ROUNDS]} cpu {h_ref['elbo']}; rel gap "
        f"{elbo_gap!r} (bound {ELBO_REL_GAP}); eta_G step rel gap "
        f"{eta_gap!r} (bound {ETA_STEP_REL_GAP}); max |eta_chip - "
        f"eta_cpu| {float(np.max(np.abs(step_chip - step_cpu)))!r}")
    check(elbo_gap <= ELBO_REL_GAP, f"chip/CPU ELBO gap {elbo_gap}")
    check(eta_gap <= ETA_STEP_REL_GAP, f"chip/CPU eta_G gap {eta_gap}")
    return exp, h


def phase_fused(spec: ExperimentSpec, flat_elbo) -> dict:
    gaps = {}
    for compression in ("none", "int8"):
        label = f"fused-{compression}"
        fspec = dataclasses.replace(
            spec,
            runtime=dataclasses.replace(spec.runtime, wire="fused"),
            scenario=dataclasses.replace(spec.scenario,
                                         compression=compression))
        _, h, _ = run_spec(fspec, label)
        gaps[compression] = _rel(h["elbo"], flat_elbo)
        log(f"[{label}] ELBO rel gap to flat {gaps[compression]!r} "
            f"(bound {FUSED_REL_GAP[compression]})")
        check(gaps[compression] <= FUSED_REL_GAP[compression],
              f"{label}: ELBO gap {gaps[compression]} to the flat wire")
    return gaps


def phase_posterior(exp, n: int = 8) -> None:
    model = exp.bundle.problem.model
    test = exp.bundle.extras["test"]
    with tempfile.TemporaryDirectory() as d:
        exp.save(d)
        post = Posterior.from_checkpoint(d)
        check(post.round == exp.round, "restored the wrong round")
        check(bool(np.array_equal(_flat(post.server.eta_G),
                                  _flat(exp.server.eta_G))),
              "restored eta_G differs from the saved one")
        t0 = time.perf_counter()
        s = post.sample(silo=0, n=n, seed=1)
        g = post.global_sample(n=n, seed=2)
        x = test[1]["x"]
        p = post.predict(silo=1, x=x, n=n, seed=3)
        batch = post.answer_batch([
            Query("sample", silo=2, n=3),
            Query("global_sample", n=2),
            Query("predict", silo=3, n=4, x=x[:5]),
        ], seed=4)
        jax.block_until_ready((s, g, p, batch))
        wall = time.perf_counter() - t0
    num_classes = exp.bundle.extras["bnn"].num_classes
    expect = [
        ("sample z_G", s["z_G"], (n, model.global_dim)),
        ("sample z_L", s["z_L"], (n, model.local_dim)),
        ("global_sample", g, (n, model.global_dim)),
        ("predict", p, (x.shape[0], num_classes)),
        ("batch sample z_L", batch[0]["z_L"], (3, model.local_dim)),
        ("batch global z_G", batch[1]["z_G"], (2, model.global_dim)),
        ("batch predict", batch[2], (5, num_classes)),
    ]
    for name, arr, shape in expect:
        arr = np.asarray(arr)
        check(arr.shape == shape, f"{name}: shape {arr.shape} != {shape}")
        check(bool(np.all(np.isfinite(arr))), f"{name}: not finite")
    log(f"[posterior] round {post.round}: "
        + ", ".join(f"{name} {np.asarray(a).shape}" for name, a, _ in expect)
        + f"; all finite; first queries (compile included) {wall:.2f}s")


def phase_meshes(model_kwargs=None, num_silos=NUM_SILOS) -> dict:
    """Phase 3's chip checks on a 4-chip silo mesh and a 2x2 (silo, model)
    mesh, compared with the one-chip mesh."""
    meshes = {"silo=1": MeshSpec(silo=1), "silo=4": MeshSpec(silo=4),
              "silo=2,model=2": MeshSpec(silo=2, model=2)}
    runs = {}
    for label, mesh in meshes.items():
        spec = hier_bnn_spec(model_kwargs=model_kwargs, num_silos=num_silos,
                             mesh=mesh)
        exp, h, _ = run_spec(spec, f"mesh {label}")
        srv = exp.server
        gathered = srv.compiled_collective_bytes(None, spec.local_steps)
        silo_state = {k: srv.state[k] for k in ("eta_L", "opt_local")}
        held = {"data": device_bytes(srv.data),
                "silo state": device_bytes(silo_state),
                "server state": device_bytes(
                    {k: srv.state[k] for k in ("theta", "eta_G",
                                               "opt_server")})}
        log(f"[mesh {label}] compiled collective bytes/round "
            f"{ {k: v for k, v in gathered.items() if v} }; metered up "
            f"{h['bytes_up'][0]}")
        log(f"[mesh {label}] bytes held per device id: {held}")
        n_silo = mesh.silo
        if mesh.model == 1 and n_silo > 1:
            check(gathered["all-gather"] == h["bytes_up"][0],
                  f"mesh {label}: metered bytes differ from the compiled "
                  "all-gather")
        per_dev = list(held["silo state"].values())
        check(len(per_dev) == n_silo * mesh.model,
              f"mesh {label}: silo state on {len(per_dev)} devices")
        total = sum(per_dev) / mesh.model
        check(all(b == total / n_silo for b in per_dev),
              f"mesh {label}: silo state not split evenly: {per_dev}")
        runs[label] = (np.asarray(h["elbo"]), _flat(srv.eta_G))
    base_elbo, base_eta = runs["silo=1"]
    gaps = {}
    for label in ("silo=4", "silo=2,model=2"):
        elbo, eta = runs[label]
        gaps[label] = {"elbo_rel": _rel(elbo, base_elbo),
                       "eta_G_max_abs": float(np.max(np.abs(eta - base_eta)))}
        log(f"[meshes] {label} vs silo=1: ELBO rel gap "
            f"{gaps[label]['elbo_rel']!r} (bound {ELBO_REL_GAP}); eta_G "
            f"max abs diff {gaps[label]['eta_G_max_abs']!r}")
        check(gaps[label]["elbo_rel"] <= ELBO_REL_GAP,
              f"{label}: ELBO gap {gaps[label]['elbo_rel']} to silo=1")
    return gaps


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip mesh comparison")
    args = ap.parse_args(argv)
    # Host CPU devices for phase 3's reference run; set before any backend
    # starts. It changes the CPU platform only, never the TPU.
    jax.config.update("jax_num_cpu_devices", CPU_REF_DEVICES)
    cache = pathlib.Path(compile_cache.enable())
    try:
        with phase("device"):
            info = phase_device("tpu", min_count=args.chips)
            # A warm cache shortens every "compile" line below.
            log(f"[device] compile cache {cache}: "
                f"{len(list(cache.glob('*'))) if cache.is_dir() else 0} "
                f"entries before this run")
        if args.chips == 4:
            with phase("meshes"):
                phase_meshes()
        else:
            with phase("toy"):
                phase_toy()
            spec = hier_bnn_spec()
            with phase("train"):
                exp, h = phase_train(spec, jax.devices("cpu"))
            with phase("fused"):
                phase_fused(spec, h["elbo"])
            with phase("posterior"):
                phase_posterior(exp)
    except Exception:  # noqa: BLE001 — report any failure, print no result
        traceback.print_exc()
        return 1
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
