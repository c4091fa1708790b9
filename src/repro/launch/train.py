"""End-to-end SFVI training driver for the assigned LLM architectures.

On the production mesh this is the SPMD path (silos = data-axis slices,
server = psum; DESIGN.md §5.1). On CPU it runs the same jitted step on one
device with the reduced config — the math is identical (SFVI's partition
invariance), only the mesh differs.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --steps 50
    PYTHONPATH=src python -m repro.launch.train --arch olmoe-1b-7b \
        --full --steps 200          # full config (needs the real mesh)
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --algo avg \
        --avg-every 10              # SFVI-Avg schedule
"""
from __future__ import annotations

import argparse
import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.configs import get_config
from repro.data.synthetic import make_token_stream
from repro.checkpoint.io import CheckpointManager
from repro.federated import (CommMeter, ExperimentSpec, MeshSpec, ModelSpec,
                             NoCompression, OptimizerSpec, RuntimeSpec,
                             Scenario, run_rounds)
from repro.launch import steps as S
from repro.launch.mesh import build_mesh
from repro.models.backbone import transformer as T


def make_batches(key, cfg, batch: int, seq: int, steps: int):
    """Synthetic token stream (Zipf unigram; offline container has no real
    corpora — DESIGN.md §7) pre-chunked into (steps, batch, seq)."""
    toks = make_token_stream(key, steps * batch * (seq + 1), cfg.vocab_size)
    toks = np.asarray(toks[: steps * batch * (seq + 1)]).reshape(
        steps, batch, seq + 1
    )
    return toks


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--silos", type=int, default=4)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--algo", choices=["sfvi", "avg"], default="sfvi")
    ap.add_argument("--avg-every", type=int, default=10)
    ap.add_argument("--full", action="store_true",
                    help="use the FULL config (production mesh required)")
    ap.add_argument("--dp-noise", type=float, default=0.0,
                    help="account the sync schedule as (eps, delta)-DP with "
                         "this Gaussian noise multiplier (0 = off). The "
                         "mechanism itself rides repro.federated.Server "
                         "(docs/privacy.md); the SPMD psum path reports "
                         "the equivalent accounting for its exchange "
                         "cadence.")
    ap.add_argument("--dp-clip", type=float, default=1.0)
    ap.add_argument("--dp-delta", type=float, default=1e-5)
    ap.add_argument("--mesh", default="", metavar="SPEC",
                    help="federated mesh topology ('silo=N[,model=N]'), "
                         "recorded on the run's provenance spec "
                         "(spec.runtime.mesh) and activated for the jitted "
                         "step via launch.mesh.build_mesh; empty = the "
                         "default single-process device set")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--dump-spec", action="store_true",
                    help="print this run's declarative spec as JSON and "
                         "exit. The SPMD path executes outside "
                         "federated.api.build (its server is a psum, not "
                         "a Server object), so the spec is the run's "
                         "provenance record: the same scenario fields the "
                         "compiled runtime would be built from.")
    args = ap.parse_args(argv)
    compile_cache.enable()

    # Declarative record of the run: the SPMD cadence expressed in the
    # same (scenario, optimizer, seed) vocabulary as repro.federated.api.
    scenario = Scenario(
        algorithm="sfvi" if args.algo == "sfvi" else "sfvi_avg",
        dp_noise=args.dp_noise, dp_clip=args.dp_clip, dp_delta=args.dp_delta,
    )
    spec = ExperimentSpec(
        model=ModelSpec(f"llm/{args.arch}",
                        kwargs={"batch": args.batch, "seq": args.seq,
                                "full": bool(args.full)}),
        scenario=scenario,
        num_silos=args.silos,
        rounds=args.steps,
        local_steps=1 if args.algo == "sfvi" else args.avg_every,
        server_opt=OptimizerSpec("adam", args.lr),
        seed=0,
        runtime=RuntimeSpec(mesh=MeshSpec.parse(args.mesh)),
    )
    if args.dump_spec:
        print(spec.to_json())
        return None

    # The declared topology is also the executed one: the jitted step
    # lowers against the spec's mesh (one factory, launch.mesh.build_mesh,
    # for the CLI, api.build and the benchmarks alike).
    mesh_ctx = (jax.set_mesh(build_mesh(spec.runtime.mesh,
                                    num_silos=args.silos))
                if args.mesh else contextlib.nullcontext())

    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    assert args.batch % args.silos == 0
    # repro-lint: allow[R1] — demo CLI entry point roots its own init stream
    key = jax.random.PRNGKey(0)

    state, _ = S.init_train_state(key, cfg, args.silos, lr=args.lr)
    if args.algo == "avg":
        state = S.TrainState(
            theta=state.theta,
            eta_G=S.init_eta_G_silo(key, cfg, args.silos),
            eta_L=state.eta_L,
            opt_theta=state.opt_theta,
            opt_eta_G=None, opt_eta_L=state.opt_eta_L,
            step=state.step,
        )
        from repro.optim.adam import adam
        opt = adam(args.lr)
        state = S.TrainState(state.theta, state.eta_G, state.eta_L,
                             state.opt_theta, opt.init(state.eta_G),
                             state.opt_eta_L, state.step)
        step_fn = S.make_train_step_avg(cfg, args.silos, args.avg_every,
                                        lr=args.lr, remat=False)
    else:
        step_fn = S.make_train_step(cfg, args.silos, lr=args.lr, remat=False)
    step_fn = jax.jit(step_fn)

    # repro-lint: allow[R1] — demo CLI data stream root, disjoint from the init root above
    toks = make_batches(jax.random.PRNGKey(1), cfg, args.batch, args.seq,
                        args.steps)
    n_params = T.param_count(state.theta)
    print(f"arch={cfg.name} params={n_params:,} silos={args.silos} "
          f"algo={args.algo}")

    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    t0 = time.time()

    def batches():
        for i in range(args.steps):
            batch = {
                "tokens": jnp.asarray(toks[i, :, :-1]),
                "labels": jnp.asarray(toks[i, :, 1:]),
            }
            if cfg.is_encoder_decoder:
                batch["frames"] = jax.random.normal(
                    jax.random.fold_in(key, i),
                    (args.batch, cfg.encoder_seq_len, cfg.d_model), jnp.float32)
            if cfg.num_vision_tokens:
                batch["vision"] = jax.random.normal(
                    jax.random.fold_in(key, i),
                    (args.batch, cfg.num_vision_tokens, cfg.d_model), jnp.float32)
            yield batch

    def on_metrics(i, m, st):
        if i % args.log_every == 0 or i == args.steps - 1:
            print(f"step {i:4d} loss={m['loss']:.4f} "
                  + " ".join(f"{k}={v:.4f}" for k, v in m.items() if k != "loss")
                  + f" ({time.time()-t0:.1f}s)")
        if ckpt and (i + 1) % 50 == 0:
            ckpt.save(i + 1, {"theta": st.theta, "eta_G": st.eta_G})

    # On the SPMD mesh a "round" is one synchronized step: every silo ships
    # its global-shaped gradient tree to the virtual server (the psum).
    # SFVI-Avg amortizes that over --avg-every local steps. Under --algo avg
    # state.eta_G is silo-stacked (silos, n_G): each silo ships only its own
    # slice, so the per-silo cost divides the stacked size by --silos.
    meter = CommMeter()
    theta_bytes = NoCompression().wire_bytes({"theta": state.theta})
    eta_bytes = NoCompression().wire_bytes({"eta_G": state.eta_G})
    if args.algo == "avg":
        per_silo = theta_bytes + eta_bytes // args.silos
    else:
        per_silo = theta_bytes + eta_bytes
    syncs_per_step = 1.0 if args.algo == "sfvi" else 1.0 / args.avg_every
    per_round = int(args.silos * per_silo * syncs_per_step)

    # DP accounting for the sync schedule: SFVI ships per step, SFVI-Avg
    # every --avg-every steps. The noising itself lives in the compiled
    # round of repro.federated.Server; here we compose the equivalent
    # Gaussian-mechanism ledger so the SPMD path reports (eps, delta).
    # The policy comes from the run's declarative scenario so the two
    # paths can never configure DP differently.
    privacy = spec.scenario.privacy()
    exchanges = (1 if args.algo == "sfvi"
                 else (lambda i: 1 if (i + 1) % args.avg_every == 0 else 0))

    with mesh_ctx:
        state, hist = run_rounds(
            lambda st, batch, i: step_fn(st, batch, jnp.int32(i)),
            state, batches(), meter=meter,
            bytes_per_round=(per_round, per_round),
            privacy=privacy, exchanges_per_round=exchanges,
            on_metrics=on_metrics,
        )
    print(f"done: {args.steps} steps in {time.time()-t0:.1f}s; "
          f"comm {meter.total/2**20:.1f} MiB "
          f"({meter.per_round/2**20:.2f} MiB/step, algo={args.algo})")
    if privacy is not None:
        # Accounting only: the psum path exchanges raw gradients — the
        # clip+noise mechanism exists in repro.federated.Server. This
        # reports what the SAME sync cadence would cost there; it is NOT
        # a guarantee held by this run. Count exchanges from the same
        # schedule run_rounds composed so the two can never disagree.
        n_ex = (sum(exchanges(i) for i in range(args.steps))
                if callable(exchanges) else exchanges * args.steps)
        if n_ex == 0:
            print(f"privacy accounting: no silo->server exchange completed "
                  f"(steps={args.steps} < avg-every={args.avg_every}); "
                  f"nothing to account")
        else:
            print(f"privacy accounting (hypothetical — mechanism lives in "
                  f"repro.federated.Server, this run shipped raw gradients): "
                  f"{n_ex} exchanges at z={args.dp_noise:g}, "
                  f"C={args.dp_clip:g} would cost "
                  f"({hist['epsilon'][-1]:.3f}, {args.dp_delta:g})-DP")
    return state


if __name__ == "__main__":
    main()
