"""SPMD train/serve steps: the paper's SFVI iteration as ONE jitted graph.

``train_step`` is Algorithm 1 with the server virtualized into collectives
(DESIGN.md §5.1):

  * ε_G comes from a REPLICATED PRNG key — every silo sees the same draw,
    replacing the server's ε_G broadcast with shared randomness (zero
    bytes on the wire).
  * Each silo j (= one slice of the batch along the data axes) computes
    L̂_j = log p_θ(y_j, Z_Lj | Z_G) − log q(Z_Lj | Z_G) on ITS data with
    ITS η_Lj (sharded over the data axes — privacy by placement).
  * The server term L̂_0 = log p(Z_G) − log q_{η_G}(Z_G) is added once.
  * jax.grad of the summed objective realizes (S4)-(S8): the cross-silo
    psum of g_jθ and g_jη is inserted by GSPMD exactly where Algorithm 1
    ships gradients to the server; ∇η_Lj stays silo-local (no collective).
  * Adam updates θ, η_G (replicated) and η_L (sharded) in-graph.

``serve_step_prefill`` / ``serve_step_decode`` run the posterior-mean
model (θ, E[Z_G], E[Z_Lj]) for inference shapes.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.backbone import transformer as T
from repro.models.backbone.bayes import (
    bayes_logits,
    latent_dims,
    log_prior_global,
    log_prior_local,
    token_nll,
)
from repro.models.backbone.config import ArchConfig
from repro.optim.adam import adam
from repro.optim.base import apply_updates

PyTree = Any

_LOG_2PI = 1.8378770664093453

AUX_LOSS_WEIGHT = 0.01  # MoE load-balance coefficient


# ---------------------------------------------------------------------------
# Variational state (diag Gaussians; paper §S2.1 uses the same family)
# ---------------------------------------------------------------------------

def init_eta_G(key, cfg: ArchConfig):
    n_G, _ = latent_dims(cfg)
    return {
        "mu": 0.01 * jax.random.normal(key, (n_G,), jnp.float32),
        "log_sigma": jnp.full((n_G,), -3.0, jnp.float32),
    }


def init_eta_L(key, cfg: ArchConfig, num_silos: int):
    _, n_L = latent_dims(cfg)
    return {
        "mu": 0.01 * jax.random.normal(key, (num_silos, n_L), jnp.float32),
        "log_sigma": jnp.full((num_silos, n_L), -3.0, jnp.float32),
    }


def _diag_sample(eta, eps):
    return eta["mu"] + jnp.exp(eta["log_sigma"]) * eps


def _diag_logq_stl(eta, z):
    """log q(z) with variational params stop-gradiented (STL estimator)."""
    mu = jax.lax.stop_gradient(eta["mu"])
    ls = jax.lax.stop_gradient(eta["log_sigma"])
    e = (z - mu) * jnp.exp(-ls)
    return -0.5 * jnp.sum(e * e) - jnp.sum(ls) - 0.5 * z.size * _LOG_2PI


@dataclasses.dataclass(frozen=True)
class TrainState:
    theta: PyTree
    eta_G: PyTree
    eta_L: PyTree
    opt_theta: PyTree
    opt_eta_G: PyTree
    opt_eta_L: PyTree
    step: jnp.ndarray

    def tree_flatten(self):
        return (
            (self.theta, self.eta_G, self.eta_L, self.opt_theta,
             self.opt_eta_G, self.opt_eta_L, self.step),
            None,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState, TrainState.tree_flatten, lambda aux, ch: TrainState(*ch)
)


def init_train_state(key, cfg: ArchConfig, num_silos: int, lr: float = 3e-4):
    k1, k2, k3 = jax.random.split(key, 3)
    theta = T.init_params(k1, cfg)
    eta_G = init_eta_G(k2, cfg)
    eta_L = init_eta_L(k3, cfg, num_silos)
    opt = adam(lr)
    return TrainState(
        theta=theta,
        eta_G=eta_G,
        eta_L=eta_L,
        opt_theta=opt.init(theta),
        opt_eta_G=opt.init(eta_G),
        opt_eta_L=opt.init(eta_L),
        step=jnp.zeros((), jnp.int32),
    ), opt


# ---------------------------------------------------------------------------
# The SFVI train step
# ---------------------------------------------------------------------------

def make_train_step(cfg: ArchConfig, num_silos: int, lr: float = 3e-4,
                    remat: bool = True):
    n_G, n_L = latent_dims(cfg)
    opt = adam(lr)

    def objective(theta, eta_G, eta_L, batch, rng,
                  l0_weight=1.0, ntok_total=None, silo_mask=None):
        kG, kL = jax.random.split(jax.random.fold_in(rng, 0))
        eps_G = jax.random.normal(kG, (n_G,), jnp.float32)  # shared randomness
        eps_L = jax.random.normal(kL, (num_silos, n_L), jnp.float32)

        z_G = _diag_sample(eta_G, eps_G)
        # Server term L̂_0 (computed once, replicated). Under microbatch
        # accumulation each slice carries 1/k of the L0/prior/entropy terms
        # so the SUM over slices equals the full-batch objective exactly.
        L0 = l0_weight * (log_prior_global(cfg, z_G) - _diag_logq_stl(eta_G, z_G))

        base_logits, aux_moe, h = T.forward(theta, cfg, batch, remat=remat)
        B, S, V = base_logits.shape
        Bj = B // num_silos
        base_j = base_logits.reshape(num_silos, Bj, S, V)
        h_j = h.reshape(num_silos, Bj, S, -1)
        labels_j = batch["labels"].reshape(num_silos, Bj, S)

        def silo_term(base, hh, lbl, eta_mu, eta_ls, eps):
            eta_Lj = {"mu": eta_mu, "log_sigma": eta_ls}
            z_Lj = _diag_sample(eta_Lj, eps)
            logits = bayes_logits(cfg, base, hh, z_G, z_Lj)
            loglik = -token_nll(logits, lbl, masked_gather=cfg.perf.masked_nll)
            return (
                loglik
                + l0_weight * (log_prior_local(cfg, z_G, z_Lj)
                               - _diag_logq_stl(eta_Lj, z_Lj))
            )

        Lj = jax.vmap(silo_term)(
            base_j, h_j, labels_j, eta_L["mu"], eta_L["log_sigma"], eps_L
        )
        if silo_mask is not None:
            # Partial silo participation (paper §1): only active silos
            # contribute; J/|active| rescale keeps the estimator unbiased
            # (the J/n_active rescale of the compiled Server's ELBO).
            m = silo_mask.astype(jnp.float32)
            Lj = Lj * m * (num_silos / jnp.maximum(jnp.sum(m), 1.0))
        elbo = L0 + jnp.sum(Lj)
        ntok = ntok_total if ntok_total is not None else B * S
        loss = -elbo / ntok + AUX_LOSS_WEIGHT * l0_weight * aux_moe
        return loss, {"elbo": elbo, "nll_per_tok": -jnp.sum(Lj) / ntok,
                      "aux_moe": aux_moe}

    def _grads_microbatched(state, batch, rng, k):
        """§Perf lever 5: gradient accumulation over k microbatches via
        lax.scan — only one microbatch's activations are live at a time,
        cutting the residual-saved-for-backward footprint ~k-fold. The
        SAME (ε_G, ε_L) draw serves every slice (one sample per SFVI
        iteration, Algorithm 1); L̂_0/prior terms carry weight 1/k so the
        accumulated gradient equals the full-batch gradient EXACTLY."""
        B, S = batch["tokens"].shape[:2]
        Bj = B // num_silos
        assert Bj % k == 0, (B, num_silos, k)

        def slice_mb(a):
            lead = a.shape[1:]
            a = a.reshape(num_silos, k, Bj // k, *lead)
            return jnp.moveaxis(a, 1, 0).reshape(
                k, num_silos * (Bj // k), *lead)

        mb = {kk: slice_mb(v) for kk, v in batch.items()}
        ntok_total = B * S

        def body(acc, mb_i):
            (loss, metrics), grads = jax.value_and_grad(
                objective, argnums=(0, 1, 2), has_aux=True
            )(state.theta, state.eta_G, state.eta_L, mb_i, rng,
              1.0 / k, ntok_total)
            acc_loss, acc_metrics, acc_grads = acc
            return (acc_loss + loss,
                    jax.tree_util.tree_map(jnp.add, acc_metrics, metrics),
                    jax.tree_util.tree_map(jnp.add, acc_grads, grads)), None

        zero_g = jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, a.dtype),
            (state.theta, state.eta_G, state.eta_L))
        zero_m = {"elbo": jnp.zeros(()), "nll_per_tok": jnp.zeros(()),
                  "aux_moe": jnp.zeros(())}
        (loss, metrics, grads), _ = jax.lax.scan(
            body, (jnp.zeros(()), zero_m, zero_g), mb)
        return (loss, metrics), grads

    def train_step(state: TrainState, batch: Dict[str, jnp.ndarray], seed,
                   silo_mask=None):
        # repro-lint: allow[R1] — in-graph key derivation from the caller's per-step seed argument (pure function of it)
        rng = jax.random.PRNGKey(seed)
        k = cfg.perf.microbatch
        if k and k > 1:
            (loss, metrics), grads = _grads_microbatched(state, batch, rng, k)
        else:
            (loss, metrics), grads = jax.value_and_grad(
                objective, argnums=(0, 1, 2), has_aux=True
            )(state.theta, state.eta_G, state.eta_L, batch, rng,
              1.0, None, silo_mask)
        g_theta, g_eta_G, g_eta_L = grads
        up_t, opt_t = opt.update(g_theta, state.opt_theta, state.theta)
        up_g, opt_g = opt.update(g_eta_G, state.opt_eta_G, state.eta_G)
        up_l, opt_l = opt.update(g_eta_L, state.opt_eta_L, state.eta_L)
        new_state = TrainState(
            theta=apply_updates(state.theta, up_t),
            eta_G=apply_updates(state.eta_G, up_g),
            eta_L=apply_updates(state.eta_L, up_l),
            opt_theta=opt_t,
            opt_eta_G=opt_g,
            opt_eta_L=opt_l,
            step=state.step + 1,
        )
        metrics = dict(metrics, loss=loss)
        return new_state, metrics

    return train_step


# ---------------------------------------------------------------------------
# SFVI-Avg on the mesh (communication-avoiding schedule for the latent head)
# ---------------------------------------------------------------------------

def make_train_step_avg(cfg: ArchConfig, num_silos: int, avg_every: int,
                        lr: float = 3e-4, remat: bool = True,
                        include_barycenter=None):
    """SFVI-Avg adapted to the mesh (DESIGN.md §5.3): η_G is carried
    PER-SILO (leading J axis, sharded like η_L); silos run local VI steps
    and every ``avg_every`` steps the server averages the per-silo global
    posteriors with the diagonal-Gaussian Wasserstein barycenter
    (μ* = mean μ_j, σ* = mean σ_j — the paper's analytic solution). θ uses
    the standard psum path every step (per-silo θ replicas are infeasible
    at LLM scale on one mesh; recorded as a deviation)."""
    n_G, n_L = latent_dims(cfg)
    opt = adam(lr)

    def objective(theta, eta_G_silo, eta_L, batch, rng):
        kG, kL = jax.random.split(jax.random.fold_in(rng, 0))
        # Per-silo eps_G: local steps use silo-local draws.
        eps_G = jax.random.normal(kG, (num_silos, n_G), jnp.float32)
        eps_L = jax.random.normal(kL, (num_silos, n_L), jnp.float32)

        base_logits, aux_moe, h = T.forward(theta, cfg, batch, remat=remat)
        B, S, V = base_logits.shape
        Bj = B // num_silos
        base_j = base_logits.reshape(num_silos, Bj, S, V)
        h_j = h.reshape(num_silos, Bj, S, -1)
        labels_j = batch["labels"].reshape(num_silos, Bj, S)
        scale = float(num_silos)  # N/N_j likelihood rescale (§3.2 point 2)

        def silo_term(base, hh, lbl, gmu, gls, lmu, lls, epsg, epsl):
            eta_Gj = {"mu": gmu, "log_sigma": gls}
            eta_Lj = {"mu": lmu, "log_sigma": lls}
            z_Gj = _diag_sample(eta_Gj, epsg)
            z_Lj = _diag_sample(eta_Lj, epsl)
            logits = bayes_logits(cfg, base, hh, z_Gj, z_Lj)
            loglik = -token_nll(logits, lbl, masked_gather=cfg.perf.masked_nll)
            L0 = log_prior_global(cfg, z_Gj) - _diag_logq_stl(eta_Gj, z_Gj)
            return (
                L0
                + scale * (loglik + log_prior_local(cfg, z_Gj, z_Lj))
                - _diag_logq_stl(eta_Lj, z_Lj)
            )

        Lj = jax.vmap(silo_term)(
            base_j, h_j, labels_j,
            eta_G_silo["mu"], eta_G_silo["log_sigma"],
            eta_L["mu"], eta_L["log_sigma"], eps_G, eps_L,
        )
        # Local objectives are independent; summing just runs them jointly.
        ntok = B * S
        loss = -jnp.sum(Lj) / (ntok * scale) + AUX_LOSS_WEIGHT * aux_moe
        return loss, {"elbo_local_mean": jnp.mean(Lj)}

    def barycenter(eta_G_silo):
        """Diagonal-Gaussian Wasserstein barycenter across the silo axis."""
        mu = jnp.mean(eta_G_silo["mu"], axis=0, keepdims=True)
        sigma = jnp.mean(jnp.exp(eta_G_silo["log_sigma"]), axis=0, keepdims=True)
        return {
            "mu": jnp.broadcast_to(mu, eta_G_silo["mu"].shape),
            "log_sigma": jnp.broadcast_to(
                jnp.log(sigma), eta_G_silo["log_sigma"].shape
            ),
        }

    def train_step(state: TrainState, batch, seed):
        # repro-lint: allow[R1] — in-graph key derivation from the caller's per-step seed argument (pure function of it)
        rng = jax.random.PRNGKey(seed)
        (loss, metrics), grads = jax.value_and_grad(
            objective, argnums=(0, 1, 2), has_aux=True
        )(state.theta, state.eta_G, state.eta_L, batch, rng)
        g_theta, g_eta_G, g_eta_L = grads
        up_t, opt_t = opt.update(g_theta, state.opt_theta, state.theta)
        up_g, opt_g = opt.update(g_eta_G, state.opt_eta_G, state.eta_G)
        up_l, opt_l = opt.update(g_eta_L, state.opt_eta_L, state.eta_L)
        eta_G = apply_updates(state.eta_G, up_g)
        # Every avg_every steps: the ONLY cross-silo communication for η_G.
        # ``include_barycenter`` statically includes/excludes the averaging
        # collective from the compiled graph (the communication-efficiency
        # measurement in benchmarks/bench_comm needs both variants); None
        # keeps the runtime-conditional path for actual training loops.
        if include_barycenter is None:
            do_avg = (state.step + 1) % avg_every == 0
            eta_G = jax.tree_util.tree_map(
                lambda avg, cur: jnp.where(do_avg, avg, cur),
                barycenter(eta_G), eta_G)
        elif include_barycenter:
            eta_G = barycenter(eta_G)
        new_state = TrainState(
            theta=apply_updates(state.theta, up_t),
            eta_G=eta_G,
            eta_L=apply_updates(state.eta_L, up_l),
            opt_theta=opt_t,
            opt_eta_G=opt_g,
            opt_eta_L=opt_l,
            step=state.step + 1,
        )
        return new_state, dict(metrics, loss=loss)

    return train_step


def init_eta_G_silo(key, cfg: ArchConfig, num_silos: int):
    n_G, _ = latent_dims(cfg)
    return {
        "mu": 0.01 * jax.random.normal(key, (num_silos, n_G), jnp.float32),
        "log_sigma": jnp.full((num_silos, n_G), -3.0, jnp.float32),
    }


# ---------------------------------------------------------------------------
# Serve steps (posterior-mean model)
# ---------------------------------------------------------------------------

def make_serve_prefill(cfg: ArchConfig, num_silos: int, max_len: int):
    def serve_step_prefill(theta, eta_G, eta_L, batch):
        logits, cache, h = T.prefill(theta, cfg, batch, max_len=max_len)
        B = logits.shape[0]
        Bj = B // num_silos
        z_G = eta_G["mu"]
        lj = logits.reshape(num_silos, Bj, 1, -1)
        hj = h.reshape(num_silos, Bj, 1, -1)
        out = jax.vmap(lambda b, hh, zl: bayes_logits(cfg, b, hh, z_G, zl))(
            lj, hj, eta_L["mu"]
        )
        return out.reshape(B, 1, -1), cache

    return serve_step_prefill


def make_serve_decode(cfg: ArchConfig, num_silos: int):
    def serve_step_decode(theta, eta_G, eta_L, tokens, cache):
        logits, new_cache, h = T.decode_step(theta, cfg, tokens, cache)
        B = logits.shape[0]
        Bj = B // num_silos
        z_G = eta_G["mu"]
        lj = logits.reshape(num_silos, Bj, 1, -1)
        hj = h.reshape(num_silos, Bj, 1, -1)
        out = jax.vmap(lambda b, hh, zl: bayes_logits(cfg, b, hh, z_G, zl))(
            lj, hj, eta_L["mu"]
        )
        return out.reshape(B, 1, -1), new_cache

    return serve_step_decode
