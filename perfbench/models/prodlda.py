"""ProdLDA (arXiv:2302.03314, section 4.2; Srivastava & Sutton 2017):
data, initial state, the plain reference of its densities, and its
matmul FLOP count. Nothing here imports the program.

    T_t ~ logistic-normal Laplace approximation of Dirichlet(beta 1_V)
    W_k ~ N(alpha 1_T, I)                      one per document (local)
    c_k ~ Multinomial(l_k, softmax(W_k T))

theta = (alpha, log beta); Z_G = vec(T) (topics x vocab, row-major);
Z_Lj = the silo's (docs, topics) document weights.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LOG_2PI = math.log(2.0 * math.pi)
LOCAL_MEAN = "mu"  # q(Z_L) = prod_k N(mu_k, diag sigma_k^2)
MAX_DOC_LEN = 256  # draws per document; lengths above it are cut


def dims(cfg):
    return {"global": cfg["num_topics"] * cfg["vocab_size"],
            "local": (cfg["docs_per_silo"], cfg["num_topics"])}


def rows_per_silo(cfg):
    """N_j, the observations of one silo."""
    return cfg["docs_per_silo"]


def program_model(cfg):
    """The system under test's model at this configuration's widths."""
    from repro.models.paper.prodlda import build_prodlda

    return build_prodlda(vocab_size=cfg["vocab_size"],
                         num_topics=cfg["num_topics"],
                         docs_per_silo=cfg["docs_per_silo"]).problem


def make_data(key, cfg):
    """A corpus drawn from a true LDA model, on the device.

    Topics ~ Dirichlet(topic_beta), document mixtures ~
    Dirichlet(doc_alpha), lengths ~ Poisson(doc_length_mean) clipped to
    [10, MAX_DOC_LEN]; each word by inverse CDF of its document's word
    distribution. Returns stacked ``{"counts": (J, docs, V) int32}``.
    """
    J, D, V, T = (cfg["num_silos"], cfg["docs_per_silo"], cfg["vocab_size"],
                  cfg["num_topics"])

    @jax.jit
    def gen(key):
        kt, kd, kl, kw = jax.random.split(key, 4)
        topics = jax.random.dirichlet(kt, cfg["topic_beta"] * jnp.ones(V), (T,))
        mix = jax.random.dirichlet(kd, cfg["doc_alpha"] * jnp.ones(T), (J * D,))
        lengths = jnp.clip(jax.random.poisson(kl, cfg["doc_length_mean"],
                                              (J * D,)), 10, MAX_DOC_LEN)
        cdf = jnp.cumsum(mix @ topics, axis=-1)
        u = jax.random.uniform(kw, (J * D, MAX_DOC_LEN)) * cdf[:, -1:]
        words = jnp.minimum(jax.vmap(jnp.searchsorted)(cdf, u), V - 1).T
        live = (jnp.arange(MAX_DOC_LEN)[:, None] < lengths[None, :])
        doc = jnp.broadcast_to(jnp.arange(J * D)[None, :], words.shape)
        counts = jnp.zeros((J * D, V), jnp.int32).at[doc, words].add(
            live.astype(jnp.int32))
        return {"counts": counts.reshape(J, D, V)}

    return gen(key)


def make_init(key, cfg):
    """(theta, eta_G, eta_L): alpha 0, beta 0.05; means 0.01 * N(0, 1),
    log-scales -2."""
    dg, (D, T) = dims(cfg)["global"], dims(cfg)["local"]
    J = cfg["num_silos"]

    @jax.jit
    def gen(key):
        kg, kl = jax.random.split(key)
        theta = {"alpha": jnp.zeros((), jnp.float32),
                 "log_beta": jnp.full((), math.log(0.05), jnp.float32)}
        eta_G = {"mu": 0.01 * jax.random.normal(kg, (dg,)),
                 "log_sigma": jnp.full((dg,), -2.0, jnp.float32)}
        eta_L = {"mu": 0.01 * jax.random.normal(kl, (J, D, T)),
                 "log_sigma": jnp.full((J, D, T), -2.0, jnp.float32)}
        return theta, eta_G, eta_L

    return gen(key)


def log_prior_global(cfg, theta, z_G, mm):
    del mm
    V, T = cfg["vocab_size"], cfg["num_topics"]
    beta = jnp.exp(theta["log_beta"])
    # Laplace approximation of the symmetric Dirichlet in softmax basis:
    # mean 0, variance (1/beta)(1 - 2/V) + 1/(V beta).
    var = (1.0 / beta) * (1.0 - 2.0 / V) + 1.0 / (V * beta)
    t = z_G.reshape(T, V)
    return jnp.sum(-0.5 * t * t / var - 0.5 * jnp.log(var) - 0.5 * LOG_2PI)


def log_local(cfg, theta, z_G, z_L, data_j, mm):
    V, T = cfg["vocab_size"], cfg["num_topics"]
    w = z_L
    lp = jnp.sum(-0.5 * (w - theta["alpha"]) ** 2 - 0.5 * LOG_2PI)
    logp = jax.nn.log_softmax(mm(w, z_G.reshape(T, V)), axis=-1)
    counts = data_j["counts"].astype(logp.dtype)
    return lp + jnp.sum(counts * logp)


def matmul_flops_per_silo_step(cfg, rows):
    """Matmul FLOPs of one forward and backward pass of log_local.

    Forward: W T (docs x topics x vocab). Backward: dW = dLogits T^T and
    dT = W^T dLogits. Elementwise work is not counted.
    """
    del rows
    return 3 * 2 * cfg["docs_per_silo"] * cfg["num_topics"] * cfg["vocab_size"]
