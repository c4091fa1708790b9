"""Property tests for the paper's central Remark (§3): SFVI is invariant to
how the data is partitioned across silos — the federated gradient equals the
centralized gradient, for any partition."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis", reason="hypothesis not installed; pip install -e .[test]")
from hypothesis import given, settings, strategies as st

from repro.core import (
    ConditionalGaussian,
    DiagGaussian,
    SFVIProblem,
    StructuredModel,
)

# NOTE: float32 throughout (x64 would leak into the whole pytest session);
# invariance holds up to float32 reduction-order epsilon.


def _make_problem(dG, dL, use_coupling):
    def log_prior_global(theta, zg):
        return -0.5 * jnp.sum((zg - theta["m"]) ** 2)

    def log_local(theta, zg, zl, data):
        lp = -0.5 * jnp.sum((zl - jnp.mean(zg)) ** 2)
        ll = -0.5 * jnp.sum((data - zl[None, :]) ** 2) * jnp.exp(theta["lt"])
        return lp + ll

    model = StructuredModel(
        global_dim=dG, local_dim=dL,
        log_prior_global=log_prior_global, log_local=log_local,
    )
    gfam = DiagGaussian(dG)
    lfam = ConditionalGaussian(dL, dG, use_coupling=use_coupling)
    return SFVIProblem(model, gfam, lfam)


def _flat(tree):
    return jnp.concatenate([x.ravel() for x in jax.tree_util.tree_leaves(tree)])


@settings(max_examples=20, deadline=None)
@given(
    num_silos=st.integers(1, 5),
    dG=st.integers(1, 4),
    dL=st.integers(1, 3),
    use_coupling=st.booleans(),
    seed=st.integers(0, 10_000),
)
def test_federated_equals_centralized_gradient(num_silos, dG, dL, use_coupling, seed):
    prob = _make_problem(dG, dL, use_coupling)
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 4 + 3 * num_silos)
    theta = {"m": jax.random.normal(ks[0], ()), "lt": jnp.asarray(-0.5)}
    eta_G = prob.global_family.init(ks[1], mu_scale=0.5)
    eps_G = jax.random.normal(ks[2], (dG,))
    etas_L, eps_L, datas = [], [], []
    for j in range(num_silos):
        etas_L.append(prob.local_family.init(ks[3 + 3 * j], mu_scale=0.5))
        eps_L.append(jax.random.normal(ks[4 + 3 * j], (dL,)))
        datas.append(jax.random.normal(ks[5 + 3 * j], (3, dL)))

    # Federated: server term + Σ_j silo terms.
    g_theta, g_eta, _ = prob.server_grads(theta, eta_G, eps_G)
    for j in range(num_silos):
        gtj, gej, _, _ = prob.silo_grads(
            theta, eta_G, etas_L[j], eps_G, eps_L[j], datas[j]
        )
        g_theta = jax.tree_util.tree_map(jnp.add, g_theta, gtj)
        g_eta = jax.tree_util.tree_map(jnp.add, g_eta, gej)

    # Centralized single-graph gradient.
    cent = jax.grad(
        lambda th, eg: prob.centralized_objective(th, eg, etas_L, eps_G, eps_L, datas),
        argnums=(0, 1),
    )(theta, eta_G)

    np.testing.assert_allclose(_flat(g_theta), _flat(cent[0]), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(_flat(g_eta), _flat(cent[1]), rtol=2e-4, atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000))
def test_repartitioning_preserves_objective(seed):
    """Moving observations between silos (with their local latents) leaves the
    total objective unchanged when local latents are per-observation."""
    # Model where each silo's latent is per-observation: split freely.
    dG = 2
    prob = _make_problem(dG, 1, use_coupling=False)
    key = jax.random.PRNGKey(seed)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    theta = {"m": jnp.asarray(0.1), "lt": jnp.asarray(0.0)}
    eta_G = prob.global_family.init(k1, mu_scale=0.3)
    eps_G = jax.random.normal(k2, (dG,))

    # 6 observations, each its own "micro-silo".
    n = 6
    etas = [prob.local_family.init(jax.random.fold_in(k3, i)) for i in range(n)]
    eps = [jax.random.normal(jax.random.fold_in(k4, i), (1,)) for i in range(n)]
    datas = [jax.random.normal(jax.random.fold_in(k4, 100 + i), (1, 1)) for i in range(n)]

    def total_for_partition(groups):
        val = prob.hat_L0(theta, eta_G, eps_G)
        for grp in groups:
            for i in grp:
                val = val + prob.hat_Lj(theta, eta_G, etas[i], eps_G, eps[i], datas[i])
        return float(val)

    v1 = total_for_partition([[0, 1, 2], [3, 4, 5]])
    v2 = total_for_partition([[0], [1, 2, 3, 4], [5]])
    v3 = total_for_partition([[0, 1, 2, 3, 4, 5]])
    np.testing.assert_allclose(v1, v2, rtol=1e-6)
    np.testing.assert_allclose(v1, v3, rtol=1e-6)
