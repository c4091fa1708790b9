"""SFVI <-> backbone integration: the paper's structured latent decomposition
applied to LLM-scale architectures (the "fully-Bayesian FedPop"
generalization of paper §4.1).

    θ    = backbone weights (embedding, blocks, head)            — trainable
    Z_G  = global latent: rank-r_g low-rank LM-head adapter
           (A_G: r_g x d, B_G: r_g x V) + a log-scale ω_G           — shared
    Z_Lj = per-silo latent: rank-r_l head adapter + logit bias   — private

Generative model (paper eqs. (1)-(3)):

    Z_G  ~ N(0, I)                                   [adapter] , ω_G ~ N(0,1)
    Z_Lj | Z_G ~ N(0, exp(2 ω_G) I)                  (hierarchical scale —
                                                      exactly the GLMM/BNN
                                                      pattern of §4.1/S3.1)
    y_j | Z_G, Z_Lj ~ Categorical(softmax(logits))

    logits = h W_head + (h A_Gᵀ) B_G / r_g + (h A_Ljᵀ) B_Lj / r_l + b_j

The low-rank path means the Bayesian head costs O(r (d+V)) extra FLOPs per
token — negligible next to the backbone — yet every silo gets a personal,
uncertainty-carrying head, and the global adapter is inferred jointly
across silos exactly as SFVI prescribes.

The variational family is the paper's diagonal Gaussian over both Z_G and
(batched over silos) Z_Lj — the same choice the paper makes for its
high-dimensional MNIST experiment (§S2.1).

``sfvi_problem`` packages a backbone and this head as the repository's
:class:`~repro.core.sfvi.SFVIProblem`, so a backbone trains through the
normal path (spec -> ``build`` -> ``Server`` -> ``Experiment.run``) like
the paper's models: θ is the backbone pytree, and a silo's data is
``{"tokens": (n_seq, S + 1) int32}`` scored next-token.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.backbone.config import ArchConfig

PyTree = Any


def latent_dims(cfg: ArchConfig) -> Tuple[int, int]:
    d, V = cfg.d_model, cfg.vocab_size
    b = cfg.bayes
    n_G = b.global_rank * (d + V) + 1  # +1: ω_G hierarchical log-scale
    n_L = b.local_rank * (d + V) + (V if b.local_bias else 0)
    return n_G, n_L


def split_global(cfg: ArchConfig, z_G: jnp.ndarray):
    """z_G -> (A_G (r,d), B_G (r,V), ω_G scalar)."""
    d, V, r = cfg.d_model, cfg.vocab_size, cfg.bayes.global_rank
    A = z_G[: r * d].reshape(r, d)
    B = z_G[r * d : r * (d + V)].reshape(r, V)
    omega = z_G[-1]
    return A, B, omega


def split_local(cfg: ArchConfig, z_L: jnp.ndarray):
    """z_L -> (A_L (r,d), B_L (r,V), bias (V) or None). Supports a leading
    silo axis: (J, n_L) -> (J, r, d), ..."""
    d, V, r = cfg.d_model, cfg.vocab_size, cfg.bayes.local_rank
    lead = z_L.shape[:-1]
    A = z_L[..., : r * d].reshape(*lead, r, d)
    B = z_L[..., r * d : r * (d + V)].reshape(*lead, r, V)
    bias = z_L[..., r * (d + V) :] if cfg.bayes.local_bias else None
    return A, B, bias


def log_prior_global(cfg: ArchConfig, z_G: jnp.ndarray) -> jnp.ndarray:
    """log p(Z_G) = standard normal over all components."""
    return -0.5 * jnp.sum(z_G.astype(jnp.float32) ** 2)


def log_prior_local(cfg: ArchConfig, z_G: jnp.ndarray, z_L: jnp.ndarray) -> jnp.ndarray:
    """log p(Z_Lj | Z_G) = N(0, exp(2 ω_G) I) — per-silo, z_L: (n_L,)."""
    omega = z_G[-1].astype(jnp.float32)
    zl = z_L.astype(jnp.float32)
    n = zl.size
    return -0.5 * jnp.sum(zl * zl) * jnp.exp(-2.0 * omega) - n * omega


def bayes_logits(
    cfg: ArchConfig,
    base_logits: jnp.ndarray,  # (..., S, Vp) — h @ W_head, computed by backbone
    h: jnp.ndarray,  # (..., S, d)
    z_G: jnp.ndarray,  # (n_G,)
    z_L: jnp.ndarray,  # (n_L,) — ONE silo's latents (silo axis handled by caller)
) -> jnp.ndarray:
    Vp = base_logits.shape[-1]
    V = cfg.vocab_size

    def vpad(m):  # pad adapter vocab columns to the padded head width
        if Vp == V:
            return m
        return jnp.pad(m, [(0, 0)] * (m.ndim - 1) + [(0, Vp - V)])

    A_G, B_G, _ = split_global(cfg, z_G)
    out = base_logits + (h @ A_G.T.astype(h.dtype)) @ vpad(B_G).astype(base_logits.dtype) / cfg.bayes.global_rank
    A_L, B_L, bias = split_local(cfg, z_L)
    out = out + (h @ A_L.T.astype(h.dtype)) @ vpad(B_L).astype(base_logits.dtype) / cfg.bayes.local_rank
    if bias is not None:
        out = out + vpad(bias).astype(out.dtype)
    return out


def token_nll(logits: jnp.ndarray, labels: jnp.ndarray,
              masked_gather: bool = False) -> jnp.ndarray:
    """Summed negative log-likelihood. logits (..., S, V); labels (..., S).

    ``masked_gather`` replaces the per-token gather of the gold logit with
    an iota-masked sum. A gather along a model-sharded vocab axis forces
    GSPMD to all-gather the logits; the masked sum is elementwise on the
    shard followed by a tiny (…, S) reduction — §Perf lever 1.
    """
    lf = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(lf, axis=-1)
    if masked_gather:
        V = logits.shape[-1]
        col = jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1)
        gold = jnp.sum(jnp.where(col == labels[..., None], lf, 0.0), axis=-1)
    else:
        gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - gold)


def silo_log_lik(cfg, base_logits_j, h_j, z_G, z_Lj, labels_j):
    """log p(y_j | Z_G, Z_Lj, θ) for one silo's batch shard."""
    logits = bayes_logits(cfg, base_logits_j, h_j, z_G, z_Lj)
    return -token_nll(logits, labels_j, masked_gather=cfg.perf.masked_nll)


def log_local(cfg: ArchConfig, theta: PyTree, z_G: jnp.ndarray,
              z_L: jnp.ndarray, data_j: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """log p(Z_Lj | Z_G) + Σ next-token log-likelihood of silo j's
    sequences under the backbone θ and the Bayesian head."""
    from repro.models.backbone import transformer

    tokens = data_j["tokens"]
    base, _, h = transformer.forward(theta, cfg, {"tokens": tokens[:, :-1]},
                                     remat=True)
    with jax.named_scope("bayes_head"):
        ll = silo_log_lik(cfg, base, h, z_G, z_L, tokens[:, 1:])
    return log_prior_local(cfg, z_G, z_L) + ll


def sfvi_problem(cfg: ArchConfig):
    """The backbone under the Bayesian head as an SFVI problem: diagonal
    Gaussian q over Z_G and over each silo's Z_Lj (no auxiliary loss —
    the objective is the ELBO alone)."""
    from repro.core import DiagGaussian, SFVIProblem, StructuredModel

    n_G, n_L = latent_dims(cfg)
    model = StructuredModel(
        global_dim=n_G, local_dim=n_L,
        log_prior_global=lambda theta, z_G: log_prior_global(cfg, z_G),
        log_local=lambda theta, z_G, z_L, d: log_local(cfg, theta, z_G, z_L, d),
        name=f"bayes_lm[{cfg.name}]",
    )
    return SFVIProblem(model, DiagGaussian(n_G), DiagGaussian(n_L))
