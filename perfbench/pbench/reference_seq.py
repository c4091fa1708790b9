"""The plain reference of a federated SFVI run whose θ fills the chip.

The run of ``pbench/reference.py``'s SFVI — its densities, random stream,
Adam and precision are inherited unchanged — laid out to fit beside a
θ of hundreds of millions of floats: the silos are taken one after
another into a running sum of their (g_theta, g_eta), where the parent
vmaps them and stacks J gradients, and each round's program overwrites
its input state (donated), where the parent keeps both. The sum over
silos replaces the parent's ``J x mean``, which differs only by
rounding. ``run`` consumes the ``state`` it is given.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from pbench.reference import Reference, halve_batch, leaf_norms


def _add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


class SequentialReference(Reference):

    def adam_init(self, params):
        """Adam's zero moments, as separate buffers (both are donated)."""
        return {"count": jnp.zeros((), jnp.int32),
                "mu": jax.tree_util.tree_map(jnp.zeros_like, params),
                "nu": jax.tree_util.tree_map(jnp.zeros_like, params)}

    def run(self, algorithm, state, data, seed, rounds, local_steps, lr,
            fault=None):
        """As :meth:`Reference.run`, for ``algorithm="sfvi"``."""
        if algorithm != "sfvi":
            raise ValueError(f"no sequential reference for {algorithm!r}")
        if fault not in (None, "half_batch"):
            raise ValueError(f"unknown fault {fault!r}")
        if fault == "half_batch":
            data = halve_batch(data)
        data = self.cast(data)
        theta, eta_G, eta_L = self.cast(state)
        del state
        base = jax.random.PRNGKey(seed)
        grad0 = leaf_norms(jax.jit(self._first_gradient)(
            theta, eta_G, eta_L, data, jax.random.fold_in(base, 0)))
        carry = (theta, eta_G, eta_L, jax.vmap(self.adam_init)(eta_L),
                 self.adam_init({"theta": theta, "eta_G": eta_G}))
        del theta, eta_G, eta_L
        rnd = jax.jit(lambda c, d, k: self._round(c, d, k, local_steps, lr),
                      donate_argnums=(0,))
        elbos, m1 = [], None
        for r in range(rounds):
            carry, e = rnd(carry, data, jax.random.fold_in(base, r))
            elbos.append(np.asarray(e, np.float64))
            if r == 0:  # host copies: the next round overwrites the carry
                m1 = jax.tree_util.tree_map(
                    np.asarray, {"eta_L": carry[3]["mu"], **carry[4]["mu"]})
        theta, eta_G, eta_L = carry[:3]
        return {"elbo": np.stack(elbos), "m1": m1,
                "params": {"theta": theta, "eta_G": eta_G, "eta_L": eta_L},
                "grad0": grad0}

    def _silo_sum(self, theta, eta_G, eta_L, opt_L, data, round_key, t,
                  eps_G, lr):
        """Every silo's hat_Lj gradient in turn: the running sum of
        (g_theta, g_eta), and each silo's eta_L gradient, Adam step
        (``lr`` not None) and hat_Lj."""
        J = eta_L["log_sigma"].shape[0]

        def one(acc, xs):
            el, l_st, dj, sid = xs
            val, (g_th, g_eg, g_el) = jax.value_and_grad(
                self.hat_Lj, argnums=(0, 1, 2))(
                    theta, eta_G, el, eps_G, self.eps_L(round_key, t, sid),
                    dj, 1.0)
            acc = _add(acc, {"theta": g_th, "eta_G": g_eg})
            if lr is not None:
                el, l_st = self.adam_step(el, g_el, l_st, lr)
            return acc, (el, l_st, g_el, val)

        zero = jax.tree_util.tree_map(jnp.zeros_like,
                                      {"theta": theta, "eta_G": eta_G})
        return jax.lax.scan(one, zero, (eta_L, opt_L, data, jnp.arange(J)))

    def _first_gradient(self, theta, eta_G, eta_L, data, round_key):
        """Per-leaf gradient of L0 + sum_j Lj at step 0 (as the parent)."""
        eg = self.eps_G(round_key, 0)
        acc, (_, _, g_el, _) = self._silo_sum(
            theta, eta_G, eta_L, eta_L, data, round_key, 0, eg, None)
        g_th0, g_eg0 = jax.grad(self.hat_L0, argnums=(0, 1))(theta, eta_G, eg)
        return {"theta": _add(acc["theta"], g_th0),
                "eta_G": _add(acc["eta_G"], g_eg0), "eta_L": g_el}

    def _round(self, carry, data, round_key, K, lr):
        """SFVI: K synchronised steps; the server adds the silos' summed
        (g_theta, g_eta) to the gradient of L0 and takes an Adam step."""

        def sync_step(c, t):
            theta, eta_G, eta_L, opt_L, opt_S = c
            eps_G = self.eps_G(round_key, t)
            acc, (eta_L, opt_L, _, vals) = self._silo_sum(
                theta, eta_G, eta_L, opt_L, data, round_key, t, eps_G, lr)
            val0, (g_th0, g_eg0) = jax.value_and_grad(
                self.hat_L0, argnums=(0, 1))(theta, eta_G, eps_G)
            g = _add(acc, {"theta": g_th0, "eta_G": g_eg0})
            p, opt_S = self.adam_step({"theta": theta, "eta_G": eta_G}, g,
                                      opt_S, lr)
            return ((p["theta"], p["eta_G"], eta_L, opt_L, opt_S),
                    val0 + jnp.sum(vals))

        return jax.lax.scan(sync_step, carry, jnp.arange(K))
