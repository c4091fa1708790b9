"""Hierarchical BNN (arXiv:2302.03314, section 4.1): data, initial state,
the plain reference of its densities, and its matmul FLOP count.

Nothing here imports the program. The densities follow the paper:

    mu_ik ~ N(0, 1),  sigma ~ N+(0, 1)            global  Z_G = (mu, log sigma)
    eps_ik^(j) ~ N(0, 1),  W2^(j) ~ N(0, 1)       local   Z_Lj = (eps, W2)
    W1^(j) = mu + sigma * eps^(j)
    y | x ~ Categorical(softmax(relu(x W1^(j)) W2^(j)))

The flat layout of Z_G is [mu (in*hidden, row-major), log sigma]; that
of Z_Lj is [eps (in*hidden, row-major), W2 (hidden*classes)].
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LOG_2PI = math.log(2.0 * math.pi)
LOCAL_MEAN = "mu_bar"  # q(Z_L | Z_G) = N(mu_bar, diag sigma^2), no coupling


def dims(cfg):
    d_in, h, c = cfg["in_dim"], cfg["hidden"], cfg["num_classes"]
    return {"global": d_in * h + 1, "local": (d_in * h + h * c,)}


def rows_per_silo(cfg):
    """N_j, the observations of one silo."""
    return cfg["train_per_silo"]


def registry_kwargs(cfg):
    """The program's registry arguments for this federation (a restored
    checkpoint rebuilds its silos from them)."""
    return {"in_dim": cfg["in_dim"], "hidden": cfg["hidden"],
            "train_per_silo": cfg["train_per_silo"], "test_per_silo": 1}


def blank_silo(cfg):
    """One silo's data of the right shapes (zeros)."""
    n, d_in = cfg["train_per_silo"], cfg["in_dim"]
    return {"x": jnp.zeros((n, d_in), jnp.float32),
            "y": jnp.zeros((n,), jnp.int32)}


def make_inputs(key, cfg, count, rows):
    """``count`` blocks of ``rows`` query inputs drawn like the data."""
    c = dict(cfg, num_silos=count, train_per_silo=rows)
    return make_data(key, c)["x"]


def program_model(cfg):
    """The system under test's model at this configuration's widths."""
    from repro.models.paper.hier_bnn import build_hier_bnn

    return build_hier_bnn(in_dim=cfg["in_dim"], hidden=cfg["hidden"],
                          num_classes=cfg["num_classes"]).problem


def make_data(key, cfg):
    """Synthetic MNIST under the 90%-one-label protocol, on the device.

    Class prototypes are 7x7 Gaussian grids upsampled to the image side;
    silo j draws ``dominant_frac`` of its rows from class j mod C and the
    rest uniformly from the other classes; x = prototype + noise.
    Returns stacked ``{"x": (J, n, in), "y": (J, n)}``.
    """
    J, n, d_in, c = (cfg["num_silos"], cfg["train_per_silo"], cfg["in_dim"],
                     cfg["num_classes"])
    side = math.isqrt(d_in)
    n_dom = int(round(cfg["dominant_frac"] * n))

    @jax.jit
    def gen(key):
        kp, ko, kn = jax.random.split(key, 3)
        coarse = jax.random.normal(kp, (c, 7, 7))
        protos = jax.image.resize(coarse, (c, side, side), "bilinear")
        protos = cfg["prototype_scale"] * protos.reshape(c, d_in)
        dom = (jnp.arange(J) % c)[:, None]
        other = jax.random.randint(ko, (J, n), 1, c)
        y = jnp.where(jnp.arange(n)[None, :] < n_dom, dom, (dom + other) % c)
        x = protos[y] + cfg["noise_scale"] * jax.random.normal(kn, (J, n, d_in))
        return {"x": x.astype(jnp.float32), "y": y.astype(jnp.int32)}

    return gen(key)


def make_init(key, cfg):
    """(theta, eta_G, eta_L) as the families initialise them: means
    0.01 * N(0, 1), log-scales -2."""
    dg, (dl,) = dims(cfg)["global"], dims(cfg)["local"]
    J = cfg["num_silos"]

    @jax.jit
    def gen(key):
        kg, kl = jax.random.split(key)
        eta_G = {"mu": 0.01 * jax.random.normal(kg, (dg,)),
                 "log_sigma": jnp.full((dg,), -2.0, jnp.float32)}
        eta_L = {"mu_bar": 0.01 * jax.random.normal(kl, (J, dl)),
                 "log_sigma": jnp.full((J, dl), -2.0, jnp.float32)}
        return eta_G, eta_L

    eta_G, eta_L = gen(key)
    return {}, eta_G, eta_L


def _std_normal(x):
    return -0.5 * jnp.sum(x * x) - 0.5 * x.size * LOG_2PI


def _split(cfg, z_G, z_L):
    d_in, h, c = cfg["in_dim"], cfg["hidden"], cfg["num_classes"]
    mu_w1 = z_G[: d_in * h].reshape(d_in, h)
    log_sigma = z_G[d_in * h]
    eps_w1 = z_L[: d_in * h].reshape(d_in, h)
    w2 = z_L[d_in * h:].reshape(h, c)
    return mu_w1, log_sigma, eps_w1, w2


def log_prior_global(cfg, theta, z_G, mm):
    del theta, mm
    d_in, h = cfg["in_dim"], cfg["hidden"]
    mu_w1, omega = z_G[: d_in * h], z_G[d_in * h]
    sigma = jnp.exp(omega)
    # sigma ~ N+(0, 1) through omega = log sigma: log 2 + log N(sigma) + omega.
    return (_std_normal(mu_w1) - 0.5 * sigma * sigma + math.log(2.0)
            - 0.5 * LOG_2PI + omega)


def logits(cfg, z_G, z_L, x, mm):
    mu_w1, omega, eps_w1, w2 = _split(cfg, z_G, z_L)
    w1 = mu_w1 + jnp.exp(omega) * eps_w1
    return mm(jax.nn.relu(mm(x, w1)), w2)


def log_local(cfg, theta, z_G, z_L, data_j, mm):
    del theta
    _, _, eps_w1, w2 = _split(cfg, z_G, z_L)
    lp = _std_normal(w2) + _std_normal(eps_w1)
    logp = jax.nn.log_softmax(logits(cfg, z_G, z_L, data_j["x"], mm), axis=-1)
    ll = jnp.sum(jnp.take_along_axis(logp, data_j["y"][:, None], axis=-1))
    return lp + ll


def matmul_flops_per_silo_step(cfg, rows):
    """Matmul FLOPs of one forward and backward pass of log_local.

    Forward: x W1 (n x in x hidden) and h W2 (n x hidden x classes).
    Backward: dW1 = x^T dH (x is data, so no dX), dH = dLogits W2^T and
    dW2 = h^T dLogits. Elementwise work is not counted.
    """
    d_in, h, c = cfg["in_dim"], cfg["hidden"], cfg["num_classes"]
    first = 2 * rows * d_in * h
    second = 2 * rows * h * c
    return 2 * first + 3 * second
