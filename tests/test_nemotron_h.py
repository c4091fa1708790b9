"""Nemotron-3-Nano's hybrid stack and its path through the Server.

The program (``models/backbone``: grouped chunked Mamba-2, the dropless
expert layer, the per-block pattern, the Bayesian head; ``runtime.Server``
summing silos in turn) against the plain float32 reference in
``tests/_nemotron_h_reference.py``, on seeded random weights at the
config's ``reduced()`` size.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _nemotron_h_reference as ref
from repro.configs import get_config
from repro.models.backbone import moe as moe_lib
from repro.models.backbone import ssm
from repro.models.backbone import transformer as T

ARCH = "nemotron3-nano-30b-a3b"
KEY = jax.random.PRNGKey(7)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def small(**kw):
    return dataclasses.replace(get_config(ARCH).reduced(), **kw)


def jitter(tree, key, scale=0.05):
    """Perturb every leaf so that biases, norms and gates are not trivial."""
    leaves, tdef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(tdef, [
        x + scale * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])


def close(a, b, tol=2e-5):
    a, b = np.asarray(a), np.asarray(b)
    scale = max(float(np.max(np.abs(b))), 1e-6)
    assert float(np.max(np.abs(a - b))) <= tol * scale, (
        float(np.max(np.abs(a - b))), scale)


# -- layers ----------------------------------------------------------------------


@pytest.mark.parametrize("groups,S", [(1, 24), (2, 24), (2, 13), (8, 16)])
def test_chunked_grouped_mamba2_matches_the_recurrence(groups, S):
    cfg = small(ssm_groups=groups)
    p = jitter(ssm.mamba2_init(KEY, cfg), jax.random.fold_in(KEY, 1))
    u = jax.random.normal(jax.random.fold_in(KEY, 2), (2, S, cfg.d_model))
    got = jax.jit(lambda p, u: ssm.mamba2_block(p, cfg, u))(p, u)
    close(got, ref.mamba2(cfg, p, u))


@pytest.mark.parametrize("held,router", [(4, 16), (16, 16), (8, 32)])
def test_dropless_layer_matches_every_expert_masked(held, router):
    cfg = small(num_experts=held, router_experts=router)
    p = jitter(moe_lib.moe_dropless_init(KEY, cfg), jax.random.fold_in(KEY, 3))
    x = jax.random.normal(jax.random.fold_in(KEY, 4), (2, 11, cfg.d_model))
    got = jax.jit(lambda p, x: moe_lib.moe_dropless(p, cfg, x))(p, x)
    close(got, ref.moe(cfg, p, x))
    # Gradients through the grouped matmuls (the custom VJP) agree too.
    loss_p = lambda p: jnp.sum(jnp.sin(moe_lib.moe_dropless(p, cfg, x)))
    loss_r = lambda p: jnp.sum(jnp.sin(ref.moe(cfg, p, x)))
    g_p, g_r = jax.grad(loss_p)(p), jax.grad(loss_r)(p)
    for k in ("w_up", "w_down", "router"):
        close(g_p[k], g_r[k], 1e-4)


def test_dropless_layer_ignores_rows_past_the_held_groups(monkeypatch):
    """The TPU's ragged-dot kernel leaves the rows past the held groups
    undefined, in the product and in its gradient; filled with NaN here,
    they reach neither the layer's output nor any gradient."""
    real = moe_lib.grouped_matmul

    def poison(out, sizes):
        dead = jnp.arange(out.shape[0]) >= jnp.sum(sizes)
        return jnp.where(dead[:, None], jnp.nan, out)

    @jax.custom_vjp
    def nan_rows(x, w, sizes):
        return poison(real(x, w, sizes), sizes)

    def fwd(x, w, sizes):
        return nan_rows(x, w, sizes), (x, w, sizes)

    def bwd(res, g):
        x, w, sizes = res
        dx, dw = jax.vjp(lambda x, w: real(x, w, sizes), x, w)[1](g)
        return poison(dx, sizes), dw, None

    nan_rows.defvjp(fwd, bwd)
    monkeypatch.setattr(moe_lib, "grouped_matmul", nan_rows)
    cfg = small(num_experts=4, router_experts=16)
    p = jitter(moe_lib.moe_dropless_init(KEY, cfg), jax.random.fold_in(KEY, 3))
    x = jax.random.normal(jax.random.fold_in(KEY, 4), (2, 11, cfg.d_model))
    close(moe_lib.moe_dropless(p, cfg, x), ref.moe(cfg, p, x))
    g_p = jax.grad(lambda p, x: jnp.sum(jnp.sin(moe_lib.moe_dropless(p, cfg, x))),
                   argnums=(0, 1))(p, x)
    g_r = jax.grad(lambda p, x: jnp.sum(jnp.sin(ref.moe(cfg, p, x))),
                   argnums=(0, 1))(p, x)
    close(g_p[1], g_r[1], 1e-4)
    for k in ("w_up", "w_down", "router"):
        close(g_p[0][k], g_r[0][k], 1e-4)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four holders of 4 experts each: their routed parts, with the
    shared expert counted once, give the layer with all 16 held."""
    cfg = small(num_experts=16, router_experts=16)
    full = jitter(moe_lib.moe_dropless_init(KEY, cfg), jax.random.fold_in(KEY, 5))
    x = jax.random.normal(jax.random.fold_in(KEY, 6), (2, 9, cfg.d_model))
    total = moe_lib.relu2_mlp(full["shared"], x.reshape(-1, cfg.d_model))
    for h in range(4):
        ids = list(range(4 * h, 4 * h + 4))
        part = dict(full, w_up=full["w_up"][4 * h:4 * h + 4],
                    w_down=full["w_down"][4 * h:4 * h + 4])
        share = moe_lib.moe_dropless(part, cfg, x, held=ids, shared=False)
        close(share, ref.moe(cfg, part, x, held=ids, shared=False))
        total = total + share.reshape(total.shape)
    uncut = moe_lib.moe_dropless(full, cfg, x)
    close(total.reshape(uncut.shape), uncut)
    close(uncut, ref.moe(cfg, full, x))


def test_the_pattern_gives_each_block_one_sublayer():
    full = get_config(ARCH)
    kinds = [full.block_kind(i) for i in range(full.num_layers)]
    assert (kinds.count("mamba2"), kinds.count("moe"), kinds.count("attn")) == (
        23, 23, 6)
    cfg = get_config(ARCH).reduced()
    assert cfg.block_pattern == ("mamba2", "moe", "mamba2", "moe", "mamba2",
                                 "attn")
    params = jax.eval_shape(lambda k: T.init_params(k, cfg), KEY)
    sub = [set(params["tail"][f"layer{i}"]) for i in range(cfg.num_layers)]
    assert sub == [{"norm1", "mixer"}, {"norm1", "moe"}, {"norm1", "mixer"},
                   {"norm1", "moe"}, {"norm1", "mixer"}, {"norm1", "attn"}]
    assert not jax.tree_util.tree_leaves(params["units"])


def test_forward_matches_the_reference():
    cfg = get_config(ARCH).reduced()
    theta = jitter(T.init_params(KEY, cfg), jax.random.fold_in(KEY, 8), 0.02)
    tokens = jax.random.randint(jax.random.fold_in(KEY, 9), (2, 17), 0,
                                cfg.vocab_size)
    _, _, h = jax.jit(lambda th, t: T.forward(th, cfg, {"tokens": t}))(
        theta, tokens)
    close(h, ref.backbone(cfg, theta, tokens), 1e-4)


# -- through build and the Server ------------------------------------------------


def _experiment(local_steps=1, rounds=1, seed=3, J=2):
    from repro.federated.api import (ExperimentSpec, ModelSpec,
                                     OptimizerSpec, build)
    from repro.federated.scheduler import Scenario

    spec = ExperimentSpec(
        model=ModelSpec("nemotron_h", {"seq_len": 8}),
        scenario=Scenario(algorithm="sfvi"), num_silos=J, rounds=rounds,
        local_steps=local_steps, server_opt=OptimizerSpec("adam", 0.01),
        seed=seed)
    return build(spec)


def test_one_sfvi_round_through_build_matches_the_reference():
    """The round's ELBO and the gradient the server folds in (Adam's first
    moment after one exchange) against the reference objective."""
    from repro.federated.strategy import global_eps, silo_eps

    exp = _experiment()
    srv = exp.server
    cfg = exp.bundle.extras["arch"]
    problem = srv.problem
    st0 = jax.tree_util.tree_map(jnp.array, srv.state)
    data = srv.data
    exp.run(rounds=1)
    key = jax.random.fold_in(jax.random.PRNGKey(exp.spec.seed), 0)
    eps_G = global_eps(problem, key, 0)

    def elbo(theta, eta_G, eta_L):
        z_G = eta_G["mu"] + jnp.exp(eta_G["log_sigma"]) * eps_G
        sg = jax.lax.stop_gradient(eta_G)
        lq = -0.5 * jnp.sum(((z_G - sg["mu"]) / jnp.exp(sg["log_sigma"])) ** 2) \
            - jnp.sum(sg["log_sigma"])
        total = ref.log_prior_global(cfg, z_G) - lq
        for j in range(srv.J):
            el = jax.tree_util.tree_map(lambda x: x[j], eta_L)
            z_L = el["mu"] + jnp.exp(el["log_sigma"]) * silo_eps(problem, key, 0, j)
            se = jax.lax.stop_gradient(el)
            lq = -0.5 * jnp.sum(((z_L - se["mu"]) / jnp.exp(se["log_sigma"])) ** 2) \
                - jnp.sum(se["log_sigma"])
            d_j = jax.tree_util.tree_map(lambda x: x[j], data)
            total = total + ref.log_local(cfg, theta, z_G, z_L, d_j) - lq
        return total

    val, (g_th, g_eg) = jax.jit(jax.value_and_grad(elbo, argnums=(0, 1)))(
        st0["theta"], st0["eta_G"], st0["eta_L"])
    # The Gaussian constants cancel between the program's log q terms.
    n = eps_G.size + srv.J * problem.model.local_dim
    want = float(val) - (-0.5 * n * np.log(2 * np.pi))
    got = exp.history["elbo_trace"][0]
    assert abs(got - want) <= 2e-5 * abs(want), (got, want)
    mu = srv.state["opt_server"][0].mu  # Adam's m after one step: 0.1 * -g
    for path, m in jax.tree_util.tree_flatten_with_path(mu["theta"])[0]:
        g = g_th
        for p in path:
            g = g[p.key]
        close(-np.asarray(m) / 0.1, g, 1e-3)
    close(-np.asarray(mu["eta_G"]["mu"]) / 0.1, g_eg["mu"], 1e-3)


@pytest.mark.parametrize("model,kw", [("toy", {}), ("nemotron_h", {"seq_len": 12})])
def test_silo_sum_round_equals_the_stacked_round(monkeypatch, model, kw):
    """The accumulating round (silos one after another into one running
    sum, donated state) reproduces the stacked gather round's history."""
    from repro.federated import runtime
    from repro.federated.api import (ExperimentSpec, ModelSpec,
                                     OptimizerSpec, build)
    from repro.federated.scheduler import Scenario

    def run(accumulate):
        monkeypatch.setattr(runtime.Server, "accumulates",
                            lambda self, algorithm=None: accumulate)
        spec = ExperimentSpec(
            model=ModelSpec(model, kw), scenario=Scenario(algorithm="sfvi"),
            num_silos=3, rounds=2, local_steps=2,
            server_opt=OptimizerSpec("adam", 0.01), seed=1)
        exp = build(spec)
        h = exp.run()
        return h, jax.tree_util.tree_map(np.asarray, exp.server.state)

    (h_s, st_s), (h_a, st_a) = run(False), run(True)
    np.testing.assert_allclose(h_a["elbo_trace"], h_s["elbo_trace"], rtol=1e-6)
    assert h_a["bytes_up"] == h_s["bytes_up"]  # every silo's row is billed
    for a, b in zip(jax.tree_util.tree_leaves(st_a),
                    jax.tree_util.tree_leaves(st_s), strict=True):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def test_the_round_accumulates_only_when_the_stack_would_crowd_the_device(
        monkeypatch):
    exp = _experiment()
    srv = exp.server
    assert not srv.accumulates()  # the CPU reports no memory limit
    stacked = 2 * srv.J_pad * srv.wire_spec().dim * 4  # gradients + rows
    dev = type(srv.mesh.local_devices[0])
    for limit, want in ((4 * stacked + 1, False), (4 * stacked - 1, True)):
        monkeypatch.setattr(dev, "memory_stats",
                            lambda self, limit=limit: {"bytes_limit": limit})
        assert srv.accumulates() is want
        assert srv.accumulates("sfvi_avg") is False  # round cadence stacks
