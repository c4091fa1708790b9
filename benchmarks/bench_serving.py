"""Serving micro-benchmark: prefill + decode throughput on CPU for the
reduced configs (the mesh-scale serving path is lowered in the dry-run;
these numbers verify the END-TO-END serve loop executes and give a CPU
baseline for regression tracking).

Also surfaces the federated runtime's per-round communication accounting
for the serving tier: each replica refreshes its posterior (θ, η_G) from
the training federation once per round, so the round-sync column is the
bytes a replica pulls per refresh — raw and under int8 wire compression
(``repro.federated.aggregation``)."""
from __future__ import annotations

import statistics
import tempfile
import time

import jax
import jax.numpy as jnp

from benchmarks.common import print_table
from repro.configs import get_config
from repro.federated import Int8Compressor, NoCompression
from repro.launch import steps as S


def federated_posterior_row(yardstick=None) -> dict:
    """Latency/throughput of the ``q(Z_L|Z_G)`` serving endpoint.

    Trains a small toy CHURN run (population dynamics exercised end to
    end), checkpoints it, restores a :class:`repro.federated.serve.
    Posterior` and times batched query serving: a fixed mixed batch
    (per-silo joint samples + global samples, grouped by silo into one
    vectorized draw per group) served repeatedly, median latency.

    Returns a row in the ``check_perf.py`` gate schema — ``elbo`` (the
    checkpointed training run; moves only if training changed),
    ``bytes_per_round`` (the posterior-refresh pull a replica pays, a
    deterministic wire quantity), ``s_per_round`` (median batch
    latency) and, when a ``yardstick`` callable is supplied,
    ``calibrated_round`` (latency / yardstick ratio, machine-neutral) —
    plus ungated ``queries_per_s`` / ``samples_per_s`` throughput.
    """
    from repro.federated import (ExperimentSpec, ModelSpec, PopulationSpec,
                                 Scenario, build)
    from repro.federated.serve import Posterior, Query

    spec = ExperimentSpec(
        model=ModelSpec("toy", {"num_obs": 40}),
        scenario=Scenario(algorithm="sfvi"),
        num_silos=6, rounds=8, seed=0,
        population=PopulationSpec(initial=2, arrival_rate=0.6,
                                  departure_rate=0.2, return_rate=0.5,
                                  seed=3))
    exp = build(spec)
    hist = exp.run()
    ckpt = tempfile.mkdtemp(prefix="bench_serving_")
    exp.save(ckpt)

    post = Posterior.from_checkpoint(ckpt)
    queries = [Query("sample", silo=j % post.num_silos, n=32)
               for j in range(48)] + [Query("global_sample", n=32)]
    n_samples = sum(q.n for q in queries)
    post.answer_batch(queries, seed=0)  # compile warmup
    lats, ratios = [], []
    for rep in range(16):
        tick = yardstick() if yardstick is not None else None
        t0 = time.perf_counter()
        ans = post.answer_batch(queries, seed=rep)
        jax.block_until_ready([a["z_G"] for a in ans])
        dt = time.perf_counter() - t0
        lats.append(dt)
        if tick is not None:
            ratios.append(dt / tick)
    lat = statistics.median(lats)
    refresh = {"theta": exp.theta, "eta_G": exp.eta_G}
    row = {
        "elbo": float(hist["elbo"][-1]),
        "bytes_per_round": float(
            NoCompression().wire_bytes(refresh)),
        "s_per_round": lat,
        "sim_seconds": 0.0,
        "epsilon": None,
        "queries_per_s": len(queries) / lat,
        "samples_per_s": n_samples / lat,
        "served_silos": post.num_silos,
    }
    if ratios:
        row["calibrated_round"] = statistics.median(ratios)
    return row


def run(quick: bool = True) -> dict:
    archs = ["qwen3-4b", "zamba2-7b", "olmoe-1b-7b"] if quick else [
        "qwen3-4b", "zamba2-7b", "olmoe-1b-7b", "xlstm-1.3b", "qwen2-vl-2b",
        "llama3.2-3b"]
    rows = []
    key = jax.random.PRNGKey(0)
    for arch in archs:
        cfg = get_config(arch).reduced()
        J, B, P, G = 2, 4, 32, 8
        state, _ = S.init_train_state(key, cfg, J)
        prefill = jax.jit(S.make_serve_prefill(cfg, J, max_len=P + G
                                               + cfg.num_vision_tokens))
        decode = jax.jit(S.make_serve_decode(cfg, J))
        batch = {"tokens": jax.random.randint(key, (B, P), 0, cfg.vocab_size)}
        if cfg.is_encoder_decoder:
            batch["frames"] = jax.random.normal(
                key, (B, cfg.encoder_seq_len, cfg.d_model), jnp.float32)
        if cfg.num_vision_tokens:
            batch["vision"] = jax.random.normal(
                key, (B, cfg.num_vision_tokens, cfg.d_model), jnp.float32)
        logits, cache = prefill(state.theta, state.eta_G, state.eta_L, batch)
        jax.block_until_ready(logits)
        t0 = time.perf_counter()
        logits, cache2 = prefill(state.theta, state.eta_G, state.eta_L, batch)
        jax.block_until_ready(logits)
        t_pre = time.perf_counter() - t0
        tok = jnp.argmax(logits[:, -1], axis=-1)
        # warm decode
        lg, cache2 = decode(state.theta, state.eta_G, state.eta_L,
                            tok[:, None], cache2)
        jax.block_until_ready(lg)
        t0 = time.perf_counter()
        for _ in range(G):
            lg, cache2 = decode(state.theta, state.eta_G, state.eta_L,
                                tok[:, None], cache2)
            tok = jnp.argmax(lg[:, -1], axis=-1)
        jax.block_until_ready(tok)
        t_dec = time.perf_counter() - t0
        sync_tree = {"theta": state.theta, "eta_G": state.eta_G}
        # The federated sync ships over the flat (J, P) wire: one int8
        # payload + ONE f32 scale per silo, not one scale per leaf.
        raw_b = NoCompression().wire_bytes(sync_tree)
        int8_b = Int8Compressor().wire_bytes(sync_tree)
        rows.append({
            "arch": cfg.name,
            "prefill tok/s": f"{B * P / t_pre:.0f}",
            "decode tok/s": f"{B * G / t_dec:.0f}",
            "sync MiB/round": f"{raw_b / 2**20:.1f}",
            "int8 MiB/round": f"{int8_b / 2**20:.1f}",
        })
    print_table("CPU serving throughput (reduced configs, B=4) + per-round "
                "posterior sync cost", rows,
                ["arch", "prefill tok/s", "decode tok/s", "sync MiB/round",
                 "int8 MiB/round"])
    fed = federated_posterior_row()
    print_table(
        "federated posterior serving (q(Z_L|Z_G) endpoint from a churn "
        "checkpoint; batched queries grouped per silo)",
        [{"served silos": fed["served_silos"],
          "batch s": f"{fed['s_per_round'] * 1e3:.2f} ms",
          "queries/s": f"{fed['queries_per_s']:.0f}",
          "samples/s": f"{fed['samples_per_s']:.0f}",
          "refresh KiB": f"{fed['bytes_per_round'] / 1024:.1f}",
          "ckpt ELBO": f"{fed['elbo']:.1f}"}],
        ["served silos", "batch s", "queries/s", "samples/s", "refresh KiB",
         "ckpt ELBO"])
    return {"rows": len(rows), "federated_posterior": fed}


if __name__ == "__main__":
    run(quick=True)
