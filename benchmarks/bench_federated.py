"""Federated-runtime scenario sweep (paper §3.2 + robustness + privacy).

Runs the hierarchical BNN through the declarative experiment API
(``repro.federated.api``) under the scenario grid the runtime exposes —
sync cadence (SFVI vs SFVI-Avg), wire compression (int8), robust
aggregation (trimmed mean), partial participation with stragglers, and
differentially private rounds — and reports final ELBO, test accuracy,
per-round communication, per-round wall time and cumulative ε. Each row
is one :class:`ExperimentSpec` (the same object ``--sweep`` builds in the
CLI), so every benchmarked configuration is serializable and resumable.

``privacy_utility_sweep`` traces the ε↔utility frontier: one row per
noise multiplier, ε vs ELBO vs accuracy vs wire bytes.

``--smoke --json BENCH_federated.json`` runs a tiny fixed configuration
(toy model) and writes a machine-readable result — the CI perf gate
(``benchmarks/check_perf.py``) compares it against the committed
``benchmarks/baseline.json`` and fails on >25% calibrated regression.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
import time

from benchmarks.common import print_table, staged_experiment
from repro.federated import AsyncConfig, Scenario
from repro.models.paper.fixtures import bnn_posterior_accuracy
from repro.models.paper.registry import get_model

# The same declarative Scenario the CLI's --sweep walks (scheduler.py);
# row labels come from Scenario.name.
SCENARIOS = [
    Scenario(algorithm="sfvi"),
    Scenario(algorithm="sfvi_avg"),
    # Natural-parameter strategies from the registry: same round cadence
    # and wire as SFVI-Avg, but silos ship damped natural-parameter site
    # deltas instead of posterior averages.
    Scenario(algorithm="pvi"),
    Scenario(algorithm="fed_ep"),
    Scenario(algorithm="sfvi_avg", compression="int8"),
    Scenario(algorithm="sfvi", aggregator="trimmed", trim_frac=0.1,
             participation=0.5, dropout=0.1),
    Scenario(algorithm="sfvi_avg", dp_noise=1.0),
    Scenario(algorithm="sfvi_avg", dp_noise=1.0, compression="int8",
             participation=0.5),
    # Buffered-async rows: flush every B=2 arrivals under a heavy
    # straggler tail — the regime the synchronous server pays for in
    # wall-clock, composed with DP + int8 to cover the whole stack.
    Scenario(algorithm="sfvi_avg",
             async_cfg=AsyncConfig(buffer_size=2, latency="straggler")),
    Scenario(algorithm="sfvi_avg", dp_noise=1.0, compression="int8",
             async_cfg=AsyncConfig(buffer_size=2, latency="straggler")),
]


def _fit(bundle, sc: Scenario, *, J, rounds, local, lr, seed):
    exp = staged_experiment(
        "hier_bnn", bundle, scenario=sc, num_silos=J, rounds=rounds,
        local_steps=local, lr=lr, seed=seed)
    t0 = time.time()
    hist = exp.run()
    dt = time.time() - t0
    bnn, test = bundle.extras["bnn"], bundle.extras["test"]
    acc, _ = bnn_posterior_accuracy(bnn, exp.eta_G, exp.eta_L, test)
    eps = hist["epsilon"][-1] if "epsilon" in hist else math.inf
    return exp, hist, acc, eps, dt


def run(quick: bool = True, seed: int = 0) -> dict:
    J = 4 if quick else 8
    rounds, local = (6, 10) if quick else (20, 25)
    lr = 2e-2

    bundle = get_model("hier_bnn").build(seed, J)

    rows, out = [], {}
    for sc in SCENARIOS:
        exp, hist, acc, eps, dt = _fit(
            bundle, sc, J=J, rounds=rounds, local=local, lr=lr, seed=seed)
        rows.append({
            "Scenario": sc.name,
            "ELBO": round(hist["elbo"][-1], 0),
            "Acc %": round(100 * acc, 1),
            "eps": "inf" if eps == math.inf else round(eps, 2),
            "KiB/round": round(exp.comm.per_round / 1024, 1),
            "s/round": round(dt / rounds, 2),
            "Sim s": round(exp.comm.sim_seconds, 1),
            "Total MiB": round(exp.comm.total / 2**20, 2),
        })
        out[sc.name] = rows[-1]

    print_table(
        f"Federated runtime scenarios (hier BNN, J={J}, "
        f"{rounds} rounds x {local} local steps; DP at delta=1e-05)",
        rows, ["Scenario", "ELBO", "Acc %", "eps", "KiB/round", "s/round",
               "Sim s", "Total MiB"],
    )
    sfvi, avg = out["SFVI"], out["SFVI-Avg"]
    dp = out[Scenario(algorithm="sfvi_avg", dp_noise=1.0).name]
    int8 = out[Scenario(algorithm="sfvi_avg", compression="int8").name]
    assert avg["KiB/round"] < sfvi["KiB/round"], (
        "SFVI-Avg must ship strictly fewer bytes per round than SFVI")
    assert dp["eps"] != "inf", (
        "DP scenario must report a finite cumulative epsilon")
    print(f"\nSFVI-Avg ships {sfvi['KiB/round']/avg['KiB/round']:.1f}x fewer "
          f"bytes/round than SFVI; int8 compression a further "
          f"{avg['KiB/round']/int8['KiB/round']:.1f}x; "
          f"DP adds eps={dp['eps']} at identical wire cost.")
    return out


def privacy_utility_sweep(quick: bool = True, seed: int = 0,
                          noise_multipliers=(0.0, 0.1, 0.25, 0.5, 1.0)) -> list:
    """ε vs ELBO vs accuracy vs comm bytes, one row per noise multiplier."""
    J = 4 if quick else 8
    rounds, local = (6, 10) if quick else (20, 25)
    lr = 2e-2
    bundle = get_model("hier_bnn").build(seed, J)

    rows = []
    for z in noise_multipliers:
        sc = Scenario(algorithm="sfvi_avg", dp_noise=z)
        exp, hist, acc, eps, dt = _fit(
            bundle, sc, J=J, rounds=rounds, local=local, lr=lr, seed=seed)
        rows.append({
            "z": z,
            "eps": "inf" if eps == math.inf else round(eps, 2),
            "ELBO": round(hist["elbo"][-1], 0),
            "Acc %": round(100 * acc, 1),
            "KiB/round": round(exp.comm.per_round / 1024, 1),
            "s/round": round(dt / rounds, 2),
        })

    print_table(
        f"Privacy-utility frontier (SFVI-Avg, hier BNN, J={J}, "
        f"{rounds} rounds x {local} local steps, delta=1e-5)",
        rows, ["z", "eps", "ELBO", "Acc %", "KiB/round", "s/round"],
    )
    return rows


# ---------------------------------------------------------------------------
# CI smoke benchmark + machine-readable output (the perf-gate input)
# ---------------------------------------------------------------------------

# The tiny FIXED configuration the CI gate tracks across commits. Never
# tune these to make a regression disappear — change them only together
# with a regenerated benchmarks/baseline.json (check_perf.py --update).
# rounds = 1 warmup (compile, reported but not gated) + 24 individually
# timed rounds; s_per_round is their median (robust under runner noise).
# The multinomial model (1970-dim global) keeps per-round work well
# above host-dispatch jitter, unlike the microscopic toy posterior.
SMOKE_CONFIG = {"model": "multinomial",
                "model_kwargs": {"n_per": 60, "in_dim": 196}, "silos": 4,
                "rounds": 25, "local_steps": 4, "lr": 2e-2, "seed": 0}

# DP rows use a gentle (z, C): the gate tracks ELBO as a sanity band,
# which needs a stable (non-diverging) trajectory on the toy posterior.
SMOKE_SCENARIOS = [
    Scenario(algorithm="sfvi"),
    Scenario(algorithm="sfvi_avg"),
    Scenario(algorithm="sfvi_avg", compression="int8"),
    Scenario(algorithm="sfvi_avg", dp_noise=0.3, dp_clip=0.3),
    Scenario(algorithm="sfvi_avg",
             async_cfg=AsyncConfig(buffer_size=2, latency="straggler")),
    Scenario(algorithm="sfvi_avg", dp_noise=0.3, dp_clip=0.3,
             compression="int8",
             async_cfg=AsyncConfig(buffer_size=2, latency="straggler")),
]


# 1-D vs 2-D mesh scaling row: the smoke config on a forced 4-host-device
# world, once on a (silo=2) mesh and once on (silo=2, model=2) — same
# J, same rounds, SFVI (the gather-heaviest cadence, one sync per local
# step). The 2-D mesh must reproduce the 1-D ELBO bit for bit (the
# sharding-layout contract, docs/federated.md) and both rows ride the
# same check_perf.py gate as every other scenario. Runs in a subprocess
# because XLA_FLAGS must be set before JAX initializes.
_MESH_PROBE_DEVICES = 4
_MESH_PROBE_MESHES = [("silo=2", {"silo": 2}),
                      ("silo=2,model=2", {"silo": 2, "model": 2})]
_MESH_PROBE_ROUNDS = 9  # 1 compile + 8 timed

_YARD_INPUT = None


def _yardstick(reps: int = 3) -> float:
    """Seconds for a fixed NumPy workload — a machine-speed yardstick.

    CI runners and developer laptops differ in raw speed by more than
    any regression we want to catch, so ``check_perf.py`` gates
    CALIBRATED times (round seconds / yardstick seconds): the yardstick
    cancels the machine out of the ratio. The smoke benchmark measures
    it INTERLEAVED with every timed round, so even load that arrives
    mid-benchmark hits both sides of the ratio. Deliberately
    single-threaded elementwise work (no BLAS): threaded matmuls
    measure the scheduler, not the machine, and flap ±25% run to run.
    """
    import numpy as np

    global _YARD_INPUT
    if _YARD_INPUT is None:
        _YARD_INPUT = np.linspace(0.0, 1.0, 1 << 20, dtype=np.float32)
    x = _YARD_INPUT
    t0 = time.perf_counter()
    for _ in range(reps):
        x = np.tanh(x) * 0.5 + 0.25
    return time.perf_counter() - t0


def _mesh_probe_rows() -> dict:
    """The 1-D vs 2-D mesh rows — call only under forced host devices."""
    import statistics

    from repro.federated import MeshSpec

    cfg = dict(SMOKE_CONFIG)
    bundle = get_model(cfg["model"]).build(
        cfg["seed"], cfg["silos"], **cfg["model_kwargs"])
    rows = {}
    for label, axes in _MESH_PROBE_MESHES:
        exp = staged_experiment(
            cfg["model"], bundle, scenario=Scenario(algorithm="sfvi"),
            num_silos=cfg["silos"], rounds=_MESH_PROBE_ROUNDS,
            local_steps=cfg["local_steps"], lr=cfg["lr"], seed=cfg["seed"],
            model_kwargs=cfg["model_kwargs"], mesh=MeshSpec(**axes))
        exp.run(1)  # compile
        per, ratios = [], []
        while exp.remaining_rounds:
            tick = _yardstick()
            t0 = time.perf_counter()
            exp.run(1)
            dt = time.perf_counter() - t0
            per.append(dt)
            ratios.append(dt / tick)
        rows[f"SFVI [mesh {label}]"] = {
            "elbo": float(exp.history["elbo"][-1]),
            "bytes_per_round": float(exp.comm.per_round),
            "s_per_round": statistics.median(per),
            "calibrated_round": statistics.median(ratios),
            "sim_seconds": 0.0,
            "epsilon": None,
        }
    one_d, two_d = (rows[f"SFVI [mesh {label}]"]
                    for label, _ in _MESH_PROBE_MESHES)
    assert one_d["elbo"] == two_d["elbo"], (
        "2-D (silo, model) mesh must reproduce the 1-D silo mesh "
        "bit-exactly", one_d["elbo"], two_d["elbo"])
    return rows


def _mesh_probe() -> dict:
    """Run the mesh rows in a fresh subprocess with forced host devices."""
    here = os.path.abspath(__file__)
    repo = os.path.dirname(os.path.dirname(here))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={_MESH_PROBE_DEVICES}")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(repo, "src"), repo]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    out = subprocess.run([sys.executable, here, "--mesh-probe"],
                         capture_output=True, text=True, env=env, cwd=repo)
    if out.returncode != 0:
        raise RuntimeError("mesh probe failed:\n"
                           + out.stdout[-2000:] + out.stderr[-2000:])
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("MESHPROBE ")][-1]
    return json.loads(line[len("MESHPROBE "):])


def smoke(json_path: str | None = None, seed: int | None = None) -> dict:
    """Tiny fixed benchmark for the CI perf gate (writes ``json_path``).

    One row per SMOKE_SCENARIO over the toy model: final ELBO,
    bytes/round (deterministic), wall s/round and the simulated async
    wall-clock, plus a calibration measurement so times compare across
    machines. The output schema is what ``benchmarks/check_perf.py``
    consumes.
    """
    cfg = dict(SMOKE_CONFIG)
    if seed is not None:
        cfg["seed"] = seed
    bundle = get_model(cfg["model"]).build(
        cfg["seed"], cfg["silos"], **cfg["model_kwargs"])

    import statistics

    scenarios = {}
    yardsticks = []

    def _timed_row(sc, wire="flat"):
        exp = staged_experiment(
            cfg["model"], bundle, scenario=sc, num_silos=cfg["silos"],
            rounds=cfg["rounds"], local_steps=cfg["local_steps"],
            lr=cfg["lr"], seed=cfg["seed"],
            model_kwargs=cfg["model_kwargs"], wire=wire)
        # Round 0 pays tracing + XLA compile; report it separately and
        # gate only the steady-state per-round time (compile latency on
        # shared CI runners is far noisier than the 25% gate). Every
        # remaining round is timed individually, bracketed by a
        # yardstick tick; the gated quantity is the MEDIAN of the
        # per-round (round s / yardstick s) ratios — machine speed and
        # even mid-benchmark load cancel, spikes fall to the median.
        t0 = time.perf_counter()
        exp.run(1)
        compile_s = time.perf_counter() - t0
        per_round, ratios = [], []
        while exp.remaining_rounds:
            tick = _yardstick()
            t0 = time.perf_counter()
            exp.run(1)
            dt = time.perf_counter() - t0
            per_round.append(dt)
            ratios.append(dt / tick)
            yardsticks.append(tick)
        hist = exp.history
        return exp, {
            "elbo": float(hist["elbo"][-1]),
            "bytes_per_round": float(exp.comm.per_round),
            "s_per_round": statistics.median(per_round),
            "calibrated_round": statistics.median(ratios),
            "compile_s": compile_s,
            "sim_seconds": float(exp.comm.sim_seconds),
            "epsilon": (float(hist["epsilon"][-1])
                        if "epsilon" in hist else None),
        }

    for sc in SMOKE_SCENARIOS:
        _, scenarios[sc.name] = _timed_row(sc)

    # The fused Pallas wire rides the same gate as every other row:
    # identical scenario to the int8 row, wire="fused" — a slowdown in
    # the kernels' interpret path (or a semantic drift moving the ELBO)
    # fails CI like any other regression.
    fused_sc = SMOKE_SCENARIOS[2]
    _, scenarios[fused_sc.name + " [wire=fused]"] = _timed_row(
        fused_sc, wire="fused")

    # The two upload paths head to head: the flat (J, P) matrix through
    # jnp ops vs the same matrix through the fused Pallas kernels — same
    # config, same bundle, timed back to back (median of per-round ratios
    # against the interleaved yardstick, like the gated rows), plus the
    # roofline terms of each compiled round (HBM bytes moved is what
    # the fused kernels attack). Reported for visibility — the gated
    # fused row above is what CI enforces.
    wire_compare = {}
    for sc in (SMOKE_SCENARIOS[1], SMOKE_SCENARIOS[2]):
        per, roofline = {}, {}
        for layout in ("flat", "fused"):
            exp = staged_experiment(
                cfg["model"], bundle, scenario=sc, num_silos=cfg["silos"],
                rounds=cfg["rounds"], local_steps=cfg["local_steps"],
                lr=cfg["lr"], seed=cfg["seed"],
                model_kwargs=cfg["model_kwargs"], wire=layout)
            exp.run(1)  # compile
            ratios = []
            for _ in range(8):
                tick = _yardstick()
                t0 = time.perf_counter()
                exp.run(1)
                ratios.append((time.perf_counter() - t0) / tick)
                yardsticks.append(tick)
            per[layout] = statistics.median(ratios)
            roofline[layout] = exp.server.compiled_roofline(
                sc.algorithm, cfg["local_steps"])
        wire_compare[sc.name] = {
            **per,
            "fused_speedup": per["flat"] / per["fused"],
            "roofline": roofline,
        }

    # Server strategies head to head: the registry's round-cadence
    # entries on the identical config and wire. PVI/FedEP ship
    # natural-parameter site deltas over the same flat (J, P) gather, so
    # bytes/round must match SFVI-Avg exactly; ELBO and calibrated time
    # are reported for visibility (not gated — the strategies optimize
    # different local objectives, so their trajectories diverge by
    # design; check_perf.py only gates the ``scenarios`` block).
    strategy_compare = {}
    for strat in ("sfvi_avg", "pvi", "fed_ep"):
        exp = staged_experiment(
            cfg["model"], bundle, scenario=Scenario(algorithm=strat),
            num_silos=cfg["silos"], rounds=9,
            local_steps=cfg["local_steps"], lr=cfg["lr"], seed=cfg["seed"],
            model_kwargs=cfg["model_kwargs"], wire="flat")
        exp.run(1)  # compile
        ratios = []
        while exp.remaining_rounds:
            tick = _yardstick()
            t0 = time.perf_counter()
            exp.run(1)
            ratios.append((time.perf_counter() - t0) / tick)
            yardsticks.append(tick)
        strategy_compare[strat] = {
            "elbo": float(exp.history["elbo"][-1]),
            "bytes_per_round": float(exp.comm.per_round),
            "calibrated_round": statistics.median(ratios),
        }

    # Posterior-serving row (bench_serving.federated_posterior_row):
    # trains + checkpoints a small CHURN run, restores the q(Z_L|Z_G)
    # endpoint and times batched query serving. Lands in ``scenarios``
    # so check_perf.py gates its ELBO (training determinism), refresh
    # bytes and calibrated batch latency like every other row; the
    # ungated queries_per_s / samples_per_s extras ride along for
    # visibility.
    from benchmarks.bench_serving import federated_posterior_row
    scenarios["serving(posterior)"] = federated_posterior_row(_yardstick)

    # 1-D vs 2-D mesh scaling (subprocess, 4 forced host devices): both
    # rows land in ``scenarios`` so check_perf.py gates their bytes,
    # ELBO and calibrated time like every other row.
    mesh_rows = _mesh_probe()
    scenarios.update(mesh_rows)
    (l1, _), (l2, _) = _MESH_PROBE_MESHES
    r1 = mesh_rows[f"SFVI [mesh {l1}]"]["calibrated_round"]
    r2 = mesh_rows[f"SFVI [mesh {l2}]"]["calibrated_round"]
    print(f"\nmesh scaling ({_MESH_PROBE_DEVICES} forced host devices): "
          f"{l1} {r1:.3f} vs {l2} {r2:.3f} calibrated s/round "
          f"(x{r1 / r2:.2f}); ELBO bit-identical")

    result = {
        "benchmark": "bench_federated-smoke",
        "config": cfg,
        "calibration_s": statistics.median(yardsticks),
        "scenarios": scenarios,
        "wire_compare": wire_compare,
        "strategy_compare": strategy_compare,
    }
    rows = [{"Scenario": name, **{k: (round(v, 4) if isinstance(v, float)
                                      else v) for k, v in r.items()}}
            for name, r in scenarios.items()]
    print_table(
        f"bench-smoke (toy, J={cfg['silos']}, {cfg['rounds']} rounds; "
        f"calibration {result['calibration_s']:.3f}s)",
        rows, ["Scenario", "elbo", "bytes_per_round", "s_per_round",
               "calibrated_round", "compile_s", "sim_seconds", "epsilon"],
    )
    print_table(
        "wire: fused Pallas vs flat (J, P) "
        "(calibrated s/round; MB = bytes accessed per compiled round)",
        [{"Scenario": name,
          "wire=fused": round(r["fused"], 4),
          "wire=flat": round(r["flat"], 4),
          "fused speedup": f"x{r['fused_speedup']:.2f}",
          "fused MB": round(r["roofline"]["fused"]["bytes_accessed"] / 1e6, 2),
          "flat MB": round(r["roofline"]["flat"]["bytes_accessed"] / 1e6, 2)}
         for name, r in wire_compare.items()],
        ["Scenario", "wire=fused", "wire=flat",
         "fused speedup", "fused MB", "flat MB"],
    )
    print_table(
        "server strategies head to head (round cadence, wire=flat; "
        "identical bytes/round by construction)",
        [{"Strategy": name,
          "elbo": round(r["elbo"], 2),
          "bytes/round": round(r["bytes_per_round"], 0),
          "calibrated s/round": round(r["calibrated_round"], 4)}
         for name, r in strategy_compare.items()],
        ["Strategy", "elbo", "bytes/round", "calibrated s/round"],
    )
    if json_path:
        with open(json_path, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
        print(f"\nwrote {json_path}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="bench_federated",
        description="Federated runtime scenario benchmark")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fixed config for the CI perf gate")
    ap.add_argument("--json", default=None, metavar="FILE",
                    help="write machine-readable results to FILE")
    ap.add_argument("--full", action="store_true",
                    help="non-quick sizes for the hier_bnn tables")
    ap.add_argument("--mesh-probe", action="store_true",
                    help=argparse.SUPPRESS)  # internal: smoke's subprocess
    args = ap.parse_args(argv)
    if args.mesh_probe:
        print("MESHPROBE " + json.dumps(_mesh_probe_rows()))
        return 0
    if args.smoke:
        smoke(json_path=args.json)
        return 0
    run(quick=not args.full)
    privacy_utility_sweep(quick=not args.full)
    return 0


if __name__ == "__main__":
    sys.exit(main())
