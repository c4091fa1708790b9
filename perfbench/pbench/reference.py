"""The plain reference of a federated SFVI / SFVI-Avg run.

Written from arXiv:2302.03314 (Algorithm 1, section 3.2) and the
supplement's STL estimator, in straightforward jax.numpy; it imports
nothing of the program. What it shares with the program is the
specification of the run, not code:

* the random stream: round key ``fold_in(PRNGKey(seed), r)``; the
  global draw of local step t is ``normal(fold_in(round_key, t))`` on
  every silo (common random numbers); silo j's local draw is
  ``normal(fold_in(fold_in(round_key, 100003 + t), j))``;
* Adam (b1 0.9, b2 0.999, eps 1e-8) on the negated gradient, as the
  optimizer of both sides;
* the precision the configuration states: float32 storage, matmuls with
  bfloat16 operands and float32 accumulation (a TPU's default for a
  float32 matmul), written here as explicit casts in forward and
  backward, so that it does not depend on a backend default.

``dtype=bfloat16`` computes the same run with every array in bfloat16:
the control, one precision below what the configuration states.
``fault="half_batch"`` replaces the second half of every silo's rows
with copies of the first half, so the loss is twice the sum over half
of the batch.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

LOG_2PI = math.log(2.0 * math.pi)
B1, B2, ADAM_EPS = 0.9, 0.999, 1e-8
LOCAL_SALT = 100_003


def make_mm(dtype):
    """Matmul at the configuration's precision (float32), or all-bf16."""
    if dtype == jnp.bfloat16:
        return lambda a, b: jnp.matmul(a, b)

    def dot(a, b):
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)

    @jax.custom_vjp
    def mm(a, b):
        return dot(a, b)

    def fwd(a, b):
        return dot(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        return dot(g, b.T), dot(a.T, g)

    mm.defvjp(fwd, bwd)
    return mm


def _stop(tree):
    return jax.tree_util.tree_map(jax.lax.stop_gradient, tree)


def _diag_sample(mean, log_sigma, eps):
    return mean + jnp.exp(log_sigma) * eps


def _diag_log_prob(mean, log_sigma, z):
    e = (z - mean) / jnp.exp(log_sigma)
    return -0.5 * jnp.sum(e * e) - jnp.sum(log_sigma) - 0.5 * z.size * LOG_2PI


def halve_batch(data):
    """Rows 0..ceil(n/2)-1 of every silo, repeated to fill n rows."""
    def leaf(x):
        n = x.shape[1]
        return x[:, jnp.arange(n) % ((n + 1) // 2)]
    return jax.tree_util.tree_map(leaf, data)


class Reference:
    """One configuration's reference run (model module + config dict)."""

    def __init__(self, model, cfg, *, dtype=jnp.float32):
        self.model, self.cfg, self.dtype = model, cfg, dtype
        self.mm = make_mm(dtype)
        self.mean_key = model.LOCAL_MEAN
        d = model.dims(cfg)
        self.d_global, self.local_shape = d["global"], d["local"]

    def cast(self, tree):
        return jax.tree_util.tree_map(
            lambda x: x.astype(self.dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)

    # -- the objective ------------------------------------------------------

    def hat_L0(self, theta, eta_G, eps_G):
        z = _diag_sample(eta_G["mu"], eta_G["log_sigma"], eps_G)
        s = _stop(eta_G)
        logq = _diag_log_prob(s["mu"], s["log_sigma"], z)
        return self.model.log_prior_global(self.cfg, theta, z, self.mm) - logq

    def hat_Lj(self, theta, eta_G, eta_Lj, eps_G, eps_L, data_j, scale):
        z_G = _diag_sample(eta_G["mu"], eta_G["log_sigma"], eps_G)
        k = self.mean_key
        z_L = _diag_sample(eta_Lj[k], eta_Lj["log_sigma"], eps_L)
        s = _stop(eta_Lj)
        logq = _diag_log_prob(s[k], s["log_sigma"], z_L)
        ll = self.model.log_local(self.cfg, theta, z_G, z_L, data_j, self.mm)
        return scale * (ll - logq)

    # -- random stream ------------------------------------------------------

    def eps_G(self, round_key, t):
        e = jax.random.normal(jax.random.fold_in(round_key, t),
                              (self.d_global,))
        return e.astype(self.dtype)

    def eps_L(self, round_key, t, sid):
        k = jax.random.fold_in(jax.random.fold_in(round_key, LOCAL_SALT + t),
                               sid)
        return jax.random.normal(k, self.local_shape).astype(self.dtype)

    # -- Adam ----------------------------------------------------------------

    def adam_init(self, params):
        z = jax.tree_util.tree_map(jnp.zeros_like, params)
        return {"count": jnp.zeros((), jnp.int32), "mu": z, "nu": z}

    def adam_step(self, params, grads, st, lr):
        """Ascend the ELBO: Adam on the negated gradient."""
        count = st["count"] + 1
        mu = jax.tree_util.tree_map(lambda m, g: B1 * m + (1.0 - B1) * (-g),
                                    st["mu"], grads)
        nu = jax.tree_util.tree_map(lambda v, g: B2 * v + (1.0 - B2) * g * g,
                                    st["nu"], grads)
        bc1 = (1.0 - B1 ** count.astype(jnp.float32)).astype(self.dtype)
        bc2 = (1.0 - B2 ** count.astype(jnp.float32)).astype(self.dtype)
        new = jax.tree_util.tree_map(
            lambda p, m, v: p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + ADAM_EPS)),
            params, mu, nu)
        return new, {"count": count, "mu": mu, "nu": nu}

    # -- rounds ----------------------------------------------------------------

    def run(self, algorithm, state, data, seed, rounds, local_steps, lr,
            fault=None):
        """``rounds`` rounds from ``state`` (theta, eta_G, eta_L).

        Returns the per-step ELBO of every round ``(rounds, K)``, the
        first moment of each persistent Adam state after round 1, the
        parameters after the last round, and the norm of each leaf's
        gradient of the whole objective at the first step.
        """
        if fault not in (None, "half_batch"):
            raise ValueError(f"unknown fault {fault!r}")
        if fault == "half_batch":
            data = halve_batch(data)
        data = self.cast(data)
        theta, eta_G, eta_L = self.cast(state)
        J = jax.tree_util.tree_leaves(data)[0].shape[0]
        n_obs = float(self.model.rows_per_silo(self.cfg))
        base = jax.random.PRNGKey(seed)
        step = self._avg_round if algorithm == "sfvi_avg" else self._sfvi_round
        if algorithm not in ("sfvi_avg", "sfvi"):
            raise ValueError(f"no reference for algorithm {algorithm!r}")
        rnd = jax.jit(lambda *a: step(*a, K=local_steps, lr=lr, J=J,
                                      n_obs=n_obs))
        grad0 = leaf_norms(jax.jit(self._first_gradient)(
            theta, eta_G, eta_L, data, jax.random.fold_in(base, 0)))
        opt_L = jax.vmap(self.adam_init)(eta_L)
        opt_S = self.adam_init({"theta": theta, "eta_G": eta_G})
        carry = (theta, eta_G, eta_L, opt_L, opt_S)
        elbos, m1 = [], None
        for r in range(rounds):
            carry, e = rnd(carry, data, jax.random.fold_in(base, r))
            elbos.append(np.asarray(e, np.float64))
            if r == 0:
                m1 = {"eta_L": carry[3]["mu"]}
                if algorithm == "sfvi":
                    m1.update(carry[4]["mu"])
        theta, eta_G, eta_L = carry[:3]
        return {"elbo": np.stack(elbos), "m1": m1,
                "params": {"theta": theta, "eta_G": eta_G, "eta_L": eta_L},
                "grad0": grad0}

    def _first_gradient(self, theta, eta_G, eta_L, data, round_key):
        """Per-leaf norms of the gradient of L0 + sum_j Lj at step 0."""
        J = eta_L["log_sigma"].shape[0]
        sids = jnp.arange(J)
        eg = self.eps_G(round_key, 0)
        el = jax.vmap(lambda s: self.eps_L(round_key, 0, s))(sids)

        def total(th, g, l):
            per = jax.vmap(lambda lj, ej, dj: self.hat_Lj(th, g, lj, eg, ej, dj,
                                                          1.0))(l, el, data)
            return self.hat_L0(th, g, eg) + jnp.sum(per)

        grads = jax.grad(total, argnums=(0, 1, 2))(theta, eta_G, eta_L)
        return {"theta": grads[0], "eta_G": grads[1], "eta_L": grads[2]}

    def _avg_round(self, carry, data, round_key, *, K, lr, J, n_obs):
        """SFVI-Avg: K local steps per silo on the N/N_j-scaled objective,
        then the mean of theta and the diagonal W2 barycenter of eta_G."""
        theta, eta_G, eta_L, opt_L, opt_S = carry
        scale = (J * n_obs) / n_obs

        def silo(el, l_st, data_j, sid):
            def local_step(c, t):
                th, eg, el, s_st, l_st = c
                eps_G = self.eps_G(round_key, t)
                eps_L = self.eps_L(round_key, t, sid)

                def obj(th_, eg_, el_):
                    return (self.hat_L0(th_, eg_, eps_G)
                            + self.hat_Lj(th_, eg_, el_, eps_G, eps_L, data_j,
                                          scale))

                val, (g_th, g_eg, g_el) = jax.value_and_grad(
                    obj, argnums=(0, 1, 2))(th, eg, el)
                el, l_st = self.adam_step(el, g_el, l_st, lr)
                p, s_st = self.adam_step({"theta": th, "eta_G": eg},
                                         {"theta": g_th, "eta_G": g_eg},
                                         s_st, lr)
                return (p["theta"], p["eta_G"], el, s_st, l_st), val

            s_st = self.adam_init({"theta": theta, "eta_G": eta_G})
            (th, eg, el, _, l_st), vals = jax.lax.scan(
                local_step, (theta, eta_G, el, s_st, l_st), jnp.arange(K))
            return el, l_st, th, eg, vals

        eta_L, opt_L, ths, egs, vals = jax.vmap(silo)(
            eta_L, opt_L, data, jnp.arange(J))
        theta = jax.tree_util.tree_map(lambda x: jnp.mean(x, axis=0), ths)
        eta_G = {"mu": jnp.mean(egs["mu"], axis=0),
                 "log_sigma": jnp.log(jnp.mean(jnp.exp(egs["log_sigma"]),
                                               axis=0))}
        elbo = jnp.sum(vals, axis=0) / J
        return (theta, eta_G, eta_L, opt_L, opt_S), elbo

    def _sfvi_round(self, carry, data, round_key, *, K, lr, J, n_obs):
        """SFVI: K synchronised steps; silos ship (g_theta, g_eta), the
        server adds J x their mean to the gradient of L0."""
        del n_obs

        def sync_step(c, t):
            theta, eta_G, eta_L, opt_L, opt_S = c
            eps_G = self.eps_G(round_key, t)

            def silo(el, l_st, data_j, sid):
                eps_L = self.eps_L(round_key, t, sid)
                val, (g_th, g_eg, g_el) = jax.value_and_grad(
                    self.hat_Lj, argnums=(0, 1, 2))(
                        theta, eta_G, el, eps_G, eps_L, data_j, 1.0)
                el, l_st = self.adam_step(el, g_el, l_st, lr)
                return el, l_st, g_th, g_eg, val

            eta_L, opt_L, g_th, g_eg, vals = jax.vmap(silo)(
                eta_L, opt_L, data, jnp.arange(J))
            val0, (g_th0, g_eg0) = jax.value_and_grad(
                self.hat_L0, argnums=(0, 1))(theta, eta_G, eps_G)
            g = jax.tree_util.tree_map(
                lambda s, g0: jnp.mean(s, axis=0) * J + g0,
                {"theta": g_th, "eta_G": g_eg},
                {"theta": g_th0, "eta_G": g_eg0})
            p, opt_S = self.adam_step({"theta": theta, "eta_G": eta_G}, g,
                                      opt_S, lr)
            elbo = val0 + jnp.sum(vals)
            return (p["theta"], p["eta_G"], eta_L, opt_L, opt_S), elbo

        return jax.lax.scan(sync_step, carry, jnp.arange(K))


def leaf_norms(tree):
    """{"group/leaf": L2 norm} over a nested dict of arrays, in float64."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        out[name] = float(np.linalg.norm(np.asarray(x, np.float64).ravel()))
    return out
