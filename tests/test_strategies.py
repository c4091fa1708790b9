"""Tests for the pluggable ServerStrategy layer (repro.federated.strategy).

Three anchors:

  * **Bit-exactness of the refactor** — the registry-built SFVI /
    SFVI-Avg strategies reproduce the pre-refactor ``Server`` round maps
    EXACTLY (elbo history, θ, η_G, η_L), on every wire layout, with and
    without DP + int8, synchronously and through the buffered-async
    engine. The oracle is ``tests/_legacy_server.py`` — a frozen
    verbatim snapshot of the pre-refactor runtime.
  * **PVI / federated-EP correctness** — damping=0 is an exact fixed
    point, and on a conjugate global-only Gaussian problem both
    strategies recover the analytic posterior (the η_G init is the
    implicit prior factor of the site decomposition).
  * **Registry / spec plumbing** — names, kwargs validation, scenario
    validation on deserialization, scheduler invitation rounding, and
    strategy state checkpoint/resume.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _legacy_server import LegacyServer
from repro.core import (
    ConditionalGaussian,
    DiagGaussian,
    SFVIProblem,
    StructuredModel,
)
from repro.federated import (
    Int8Compressor,
    PrivacyPolicy,
    RoundScheduler,
    Scenario,
    Server,
    ServerStrategy,
    StrategySpec,
    get_strategy,
    register_strategy,
    resolve_strategy,
    run_buffered,
    strategy_names,
)
from repro.federated.scheduler import AsyncConfig
from repro.federated.strategy import SFVIStrategy
from repro.optim.adam import adam

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _hier_problem(dG=3, dL=2):
    def log_prior_global(theta, zg):
        return -0.5 * jnp.sum((zg - theta["m"]) ** 2)

    def log_local(theta, zg, zl, data):
        lp = -0.5 * jnp.sum((zl - jnp.mean(zg)) ** 2)
        ll = -0.5 * jnp.sum((data["y"] - zl[None, :]) ** 2) * jnp.exp(theta["lt"])
        return lp + ll

    model = StructuredModel(
        global_dim=dG, local_dim=dL,
        log_prior_global=log_prior_global, log_local=log_local,
    )
    return SFVIProblem(model, DiagGaussian(dG), ConditionalGaussian(dL, dG))


def _datas(key, J, n=6, d=2):
    return [
        {"y": jax.random.normal(jax.random.fold_in(key, j), (n, d))}
        for j in range(J)
    ]


def _init(prob):
    theta = {"m": jnp.asarray(0.3), "lt": jnp.asarray(-0.5)}
    eta_G = prob.global_family.init(jax.random.PRNGKey(1), mu_scale=0.5)
    return theta, eta_G


def _flat(tree):
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.zeros((0,))
    return jnp.concatenate([jnp.ravel(x) for x in leaves])


def _assert_same_state(a, b, keys=("theta", "eta_G", "eta_L")):
    for k in keys:
        np.testing.assert_array_equal(
            np.asarray(_flat(a.state[k])), np.asarray(_flat(b.state[k])), err_msg=k)


# ---------------------------------------------------------------------------
# Registry / spec plumbing
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_builtin_names(self):
        assert set(strategy_names()) >= {"sfvi", "sfvi_avg", "pvi", "fed_ep"}
        assert strategy_names() == tuple(sorted(strategy_names()))

    def test_unknown_name_lists_registry(self):
        with pytest.raises(KeyError, match="sfvi"):
            get_strategy("nope")

    def test_duplicate_registration_raises(self):
        with pytest.raises(ValueError, match="already registered"):
            register_strategy("sfvi")(SFVIStrategy)

    def test_resolve_passthrough_and_name(self):
        inst = get_strategy("pvi")(damping=0.5)
        assert resolve_strategy(inst) is inst
        assert resolve_strategy("sfvi").name == "sfvi"
        # repro-lint: allow[R6] — protocol-membership test for resolve_strategy itself
        assert isinstance(resolve_strategy(StrategySpec("fed_ep")),
                          ServerStrategy)

    def test_spec_kwargs_validated(self):
        assert StrategySpec("pvi", {"damping": 0.1}).build().damping == 0.1
        with pytest.raises(ValueError, match="unknown kwargs"):
            StrategySpec("pvi", {"bogus": 1}).build()
        with pytest.raises(ValueError, match="unknown kwargs"):
            # sfvi is stateless: ANY kwarg is unknown.
            StrategySpec("sfvi", {"damping": 0.1}).build()

    def test_spec_round_trip(self):
        spec = StrategySpec("pvi", {"damping": 0.3})
        assert StrategySpec.from_dict(
            {"name": "pvi", "kwargs": {"damping": 0.3}}) == spec

    def test_cadences(self):
        assert get_strategy("sfvi").cadence == "step"
        for name in ("sfvi_avg", "pvi", "fed_ep"):
            assert get_strategy(name).cadence == "round"
        for name in ("pvi", "fed_ep"):
            assert get_strategy(name).has_silo_state

    def test_runtime_has_no_algorithm_name_branches(self):
        """The refactor's contract: the round bodies are generic — no
        algorithm-name literals survive in the runtime module."""
        with open(os.path.join(
                REPO, "src", "repro", "federated", "runtime.py")) as f:
            src = f.read()
        assert '"sfvi"' not in src and "'sfvi'" not in src
        assert "sfvi_avg" not in src


# ---------------------------------------------------------------------------
# Bit-exactness vs the frozen pre-refactor oracle
# ---------------------------------------------------------------------------


class TestLegacyEquivalence:
    @pytest.mark.parametrize("wire", ["flat", "fused"])
    def test_bit_exact_round_trajectories(self, wire):
        """Registry SFVI / SFVI-Avg == the pre-refactor Server, bit for
        bit over 3 rounds: elbo history and full (θ, η_G, η_L) state —
        plain AND under DP clip+noise with int8 wire compression."""
        prob = _hier_problem()
        datas = _datas(jax.random.PRNGKey(2), 4)
        theta, eta_G = _init(prob)
        for algo, K in (("sfvi", 2), ("sfvi_avg", 3)):
            for extra in ({}, {"compressor": Int8Compressor(),
                               "privacy": PrivacyPolicy(
                                   clip_norm=1.0, noise_multiplier=0.4)}):
                kw = dict(server_opt=adam(1e-2), local_opt=adam(1e-2),
                          seed=7, wire=wire, **extra)
                new = Server(prob, datas, theta, eta_G, strategy=algo, **kw)
                old = LegacyServer(prob, datas, theta, eta_G, **kw)
                h_new = new.run(3, local_steps=K)
                h_old = old.run(3, algorithm=algo, local_steps=K)
                np.testing.assert_array_equal(
                    np.asarray(h_new["elbo"]), np.asarray(h_old["elbo"]))
                _assert_same_state(new, old)

    def test_bit_exact_under_partial_participation(self):
        prob = _hier_problem()
        datas = _datas(jax.random.PRNGKey(2), 5)
        theta, eta_G = _init(prob)
        sched = RoundScheduler(num_silos=5, participation=0.6, dropout=0.2,
                               seed=3)
        kw = dict(server_opt=adam(1e-2), local_opt=adam(1e-2), seed=7)
        new = Server(prob, datas, theta, eta_G, strategy="sfvi_avg", **kw)
        old = LegacyServer(prob, datas, theta, eta_G, **kw)
        h_new = new.run(4, local_steps=2, scheduler=sched)
        h_old = old.run(4, algorithm="sfvi_avg", local_steps=2,
                        scheduler=RoundScheduler(num_silos=5,
                                                 participation=0.6,
                                                 dropout=0.2, seed=3))
        np.testing.assert_array_equal(
            np.asarray(h_new["elbo"]), np.asarray(h_old["elbo"]))
        _assert_same_state(new, old)

    def test_bit_exact_through_async_engine(self):
        """run_buffered drives the registry Server and the frozen oracle
        to identical trajectories (DP + int8, lognormal latencies)."""

        class _AsyncLegacy(LegacyServer):
            # run_buffered resolves the strategy through the server; the
            # oracle predates that API, so adapt it: the engine only
            # needs cadence/name for validation and the round fn itself.
            def _resolve(self, algorithm):
                return get_strategy("sfvi_avg")()

            def _get_round(self, algorithm, local_steps):
                fn = super()._get_round("sfvi_avg", local_steps)
                # The engine's round signature gained n_j (dynamic
                # population growth); the frozen oracle predates it and
                # bakes num_obs into the graph, so drop the argument.
                return lambda state, data, n_j, key, mask, weights: fn(
                    state, data, key, mask, weights)

            def bytes_up_per_silo(self, algorithm=None):
                return super().bytes_up_per_silo("sfvi_avg")

        prob = _hier_problem()
        datas = _datas(jax.random.PRNGKey(2), 4)
        theta, eta_G = _init(prob)
        cfg = AsyncConfig(buffer_size=2, latency="lognormal")
        kw = dict(server_opt=adam(1e-2), local_opt=adam(1e-2), seed=7,
                  compressor=Int8Compressor(),
                  privacy=PrivacyPolicy(clip_norm=1.0, noise_multiplier=0.4))
        new = Server(prob, datas, theta, eta_G, strategy="sfvi_avg", **kw)
        old = _AsyncLegacy(prob, datas, theta, eta_G, **kw)
        h_new, _ = run_buffered(new, 4, cfg, local_steps=2)
        h_old, _ = run_buffered(old, 4, cfg, local_steps=2)
        np.testing.assert_array_equal(
            np.asarray(h_new["elbo"]), np.asarray(h_old["elbo"]))
        assert h_new["bytes_up"] == h_old["bytes_up"]
        _assert_same_state(new, old)

    def test_async_rejects_step_cadence(self):
        prob = _hier_problem()
        datas = _datas(jax.random.PRNGKey(2), 3)
        theta, eta_G = _init(prob)
        srv = Server(prob, datas, theta, eta_G, server_opt=adam(1e-2),
                     local_opt=adam(1e-2), strategy="sfvi")
        with pytest.raises(ValueError, match="round-cadence"):
            run_buffered(srv, 1, AsyncConfig(buffer_size=2))


# ---------------------------------------------------------------------------
# PVI / federated-EP correctness
# ---------------------------------------------------------------------------


def _conjugate_setup(J=4, n=20, prior_sd=5.0, mu_true=1.5, seed=0):
    """Global-only Gaussian: y_jk ~ N(z_G, 1), flat log-prior — the
    implicit PVI prior factor is the η_G INIT (N(0, prior_sd²)), so the
    site fixed point has a closed form."""
    model = StructuredModel(
        global_dim=1, local_dim=0,
        log_prior_global=lambda th, zg: jnp.zeros(()),
        log_local=lambda th, zg, zl, d: -0.5 * jnp.sum((d["y"] - zg[0]) ** 2),
    )
    prob = SFVIProblem(model, DiagGaussian(1))
    rng = np.random.default_rng(seed)
    datas = [{"y": jnp.asarray(rng.normal(mu_true, 1.0, n), jnp.float32)}
             for _ in range(J)]
    eta0 = {"mu": jnp.zeros((1,)),
            "log_sigma": jnp.full((1,), np.log(prior_sd), jnp.float32)}
    ybar = float(np.mean([np.asarray(d["y"]).mean() for d in datas]))
    post_prec = prior_sd ** -2 + J * n
    post_mu = J * n * ybar / post_prec
    return prob, datas, eta0, post_mu, post_prec ** -0.5


class TestNaturalDeltaStrategies:
    def test_damping_zero_is_a_fixed_point(self):
        """ρ=0: θ and the sites λ_j do not move at all (bit-exact); η_G
        only round-trips through natural parameters (allclose)."""
        prob = _hier_problem()
        datas = _datas(jax.random.PRNGKey(2), 3)
        theta, eta_G = _init(prob)
        srv = Server(prob, datas, theta, eta_G, server_opt=adam(1e-2),
                     local_opt=adam(1e-2), seed=0,
                     strategy=get_strategy("pvi")(damping=0.0))
        srv.run(2, local_steps=3)
        np.testing.assert_array_equal(
            np.asarray(_flat(srv.state["theta"])), np.asarray(_flat(theta)))
        np.testing.assert_allclose(
            np.asarray(_flat(srv.state["eta_G"])), np.asarray(_flat(eta_G)),
            rtol=1e-5, atol=1e-6)
        lam = np.asarray(_flat(srv.state["strategy"]))
        np.testing.assert_array_equal(lam, np.zeros_like(lam))

    @pytest.mark.parametrize("algo", ["pvi", "fed_ep"])
    def test_recovers_conjugate_posterior(self, algo):
        """Both site strategies converge to the analytic posterior of
        the conjugate global-only Gaussian (paper's correctness anchor
        for the site decomposition: q_G → prior × Π_j lik_j)."""
        prob, datas, eta0, post_mu, post_sd = _conjugate_setup()
        srv = Server(prob, datas, {}, eta0, server_opt=adam(5e-2), seed=0,
                     strategy=algo)
        srv.run(60, local_steps=10)
        eg = srv.state["eta_G"]
        assert abs(float(eg["mu"][0]) - post_mu) < 0.05
        sd = float(np.exp(np.asarray(eg["log_sigma"])[0]))
        assert abs(sd / post_sd - 1.0) < 0.25

    def test_sites_sum_to_posterior_minus_prior(self):
        """The site decomposition invariant: Σ_j λ_j == nat(q_G) −
        nat(q_init), maintained exactly by the damped updates (full
        participation, no DP/compression)."""
        from repro.federated.strategy import natural_from_eta

        prob, datas, eta0, _, _ = _conjugate_setup()
        srv = Server(prob, datas, {}, eta0, server_opt=adam(5e-2), seed=0,
                     strategy="pvi")
        srv.run(5, local_steps=4)
        fam = prob.global_family
        nat0 = natural_from_eta(fam, eta0)
        natG = natural_from_eta(fam, srv.state["eta_G"])
        lam = srv.state["strategy"]["lam"]
        for k in ("h", "prec"):
            lam_sum = np.asarray(lam[k])[:srv.J].sum(axis=0)
            np.testing.assert_allclose(
                lam_sum, np.asarray(natG[k]) - np.asarray(nat0[k]),
                rtol=2e-3, atol=2e-3)

    def test_pvi_and_fed_ep_trajectories_differ(self):
        """Same fixed points, different finite-K paths: posterior-init
        (PVI) vs cavity-init (EP) local VI diverge once sites are
        non-zero."""
        prob, datas, eta0, _, _ = _conjugate_setup()
        out = {}
        for algo in ("pvi", "fed_ep"):
            srv = Server(prob, datas, {}, eta0, server_opt=adam(5e-2),
                         seed=0, strategy=algo)
            srv.run(3, local_steps=4)
            out[algo] = np.asarray(_flat(srv.state["eta_G"]))
        assert not np.array_equal(out["pvi"], out["fed_ep"])

    def test_requires_diag_moment_form(self):
        from repro.core.family import FamilySpec, build_family

        prob = _hier_problem()
        prob = prob.__class__(
            prob.model, build_family(FamilySpec("cholesky"), dim=3),
            prob.local_family)
        datas = _datas(jax.random.PRNGKey(2), 3)
        theta = {"m": jnp.asarray(0.3), "lt": jnp.asarray(-0.5)}
        eta_G = prob.global_family.init(jax.random.PRNGKey(1))
        with pytest.raises(ValueError, match="diag"):
            Server(prob, datas, theta, eta_G, server_opt=adam(1e-2),
                   local_opt=adam(1e-2), strategy="pvi")

    def test_run_time_strategy_switch_fills_state(self):
        """run(algorithm='pvi') on a Server built for SFVI-Avg lazily
        creates the per-silo site state."""
        prob, datas, eta0, _, _ = _conjugate_setup()
        srv = Server(prob, datas, {}, eta0, server_opt=adam(5e-2), seed=0,
                     strategy="sfvi_avg")
        assert not jax.tree_util.tree_leaves(srv.state["strategy"])
        srv.run(2, algorithm="pvi", local_steps=2)
        assert jax.tree_util.tree_leaves(srv.state["strategy"])


# ---------------------------------------------------------------------------
# Scheduler bugfixes (invitation rounding, from_dict validation)
# ---------------------------------------------------------------------------


class TestSchedulerFixes:
    @staticmethod
    def _n_invited(J, participation):
        sched = RoundScheduler(num_silos=J, participation=participation)
        counts = {int(np.asarray(sched.invited(r)).sum()) for r in range(6)}
        assert len(counts) == 1  # the invitation count is schedule-constant
        return counts.pop()

    def test_invited_rounds_half_up_on_odd_ties(self):
        """participation·J = 2.5 must invite 3 silos, not banker's-round
        down to 2: int(round(2.5)) == 2 under round-half-to-even."""
        assert self._n_invited(5, 0.5) == 3
        assert self._n_invited(7, 0.5) == 4

    def test_invited_even_j_unchanged(self):
        assert self._n_invited(8, 0.5) == 4
        assert self._n_invited(4, 0.25) == 1
        assert self._n_invited(8, 1.0) == 8

    def test_from_dict_validates(self):
        """Deserialized scenarios run the same validation as constructed
        ones — a bad spec fails at load, not deep inside build()."""
        with pytest.raises(ValueError, match="round-cadence"):
            Scenario.from_dict(
                {"algorithm": "sfvi", "async_cfg": {"buffer_size": 2}})
        with pytest.raises(ValueError, match="registered strategies"):
            Scenario.from_dict({"algorithm": "sfvi_average"})
        # A valid dict still round-trips.
        sc = Scenario.from_dict({"algorithm": "pvi", "compression": "int8"})
        assert sc.algorithm == "pvi"

    def test_scenario_matrix_covers_round_cadence_async(self):
        from repro.federated.scheduler import scenario_matrix

        grid = scenario_matrix(
            algorithms=("sfvi", "pvi"),
            participation=(1.0,), dropout=(0.0,), compression=("none",),
            dp_noise=(0.0,),
            async_cfgs=(None, AsyncConfig(buffer_size=2)),
        )
        by_algo = {}
        for sc in grid:
            by_algo.setdefault(sc.algorithm, []).append(sc.async_cfg)
        # Full-participation async SFVI rows are dropped; PVI keeps both.
        assert all(c is None for c in by_algo["sfvi"])
        assert any(c is not None for c in by_algo["pvi"])


# ---------------------------------------------------------------------------
# Strategy state checkpoint/resume (PVI sites ride the per-silo shards)
# ---------------------------------------------------------------------------


class TestStrategyCheckpoint:
    def _spec(self, **scenario_kw):
        from repro.federated.api import ExperimentSpec, ModelSpec, OptimizerSpec

        return ExperimentSpec(
            model=ModelSpec("toy"),
            scenario=Scenario(algorithm="pvi", **scenario_kw),
            strategy=StrategySpec("pvi", {"damping": 0.3}),
            num_silos=3, rounds=6, local_steps=2, seed=3,
            server_opt=OptimizerSpec("adam", 2e-2))

    def test_spec_round_trips_strategy(self):
        from repro.federated.api import ExperimentSpec

        spec = self._spec()
        assert ExperimentSpec.from_dict(spec.to_dict()) == spec
        assert ExperimentSpec.from_json(spec.to_json()) == spec

    def test_strategy_name_mismatch_raises(self):
        import dataclasses

        from repro.federated.api import build

        spec = dataclasses.replace(
            self._spec(), scenario=Scenario(algorithm="sfvi_avg"))
        with pytest.raises(ValueError, match="must agree"):
            build(spec)

    @pytest.mark.parametrize("scenario_kw", [
        {"compression": "int8", "dp_noise": 0.4, "dp_clip": 1.0},
        {"async_cfg": AsyncConfig(buffer_size=2, latency="lognormal")},
    ])
    def test_resume_is_bit_exact(self, tmp_path, scenario_kw):
        """save → resume of a PVI run (DP+int8, and buffered-async)
        replays the uninterrupted trajectory bit-exactly, INCLUDING the
        per-silo site state λ_j on the silo shards."""
        from repro.federated.api import Experiment, build

        spec = self._spec(**scenario_kw)
        full = build(spec)
        full.run(3)
        full.save(str(tmp_path))
        # PVI on the toy model: silo shards exist and carry λ even
        # though η_L does too — and the files are per-silo.
        assert (tmp_path / "step_00000003.silo_0002.msgpack").exists()
        full.run(3)

        resumed = Experiment.resume(str(tmp_path))
        assert resumed.round == 3
        resumed.run(3)
        np.testing.assert_array_equal(
            np.asarray(full.history["elbo"][3:]),
            np.asarray(resumed.history["elbo"]))
        for k in ("theta", "eta_G", "eta_L", "strategy"):
            np.testing.assert_array_equal(
                np.asarray(_flat(full.server.state[k])),
                np.asarray(_flat(resumed.server.state[k])), err_msg=k)


# ---------------------------------------------------------------------------
# Host meter == compiled collective (flat + int8, real 4-device mesh)
# ---------------------------------------------------------------------------

_METER_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    import jax, jax.numpy as jnp
    from repro.core import (ConditionalGaussian, DiagGaussian, SFVIProblem,
                            StructuredModel)
    from repro.federated import Int8Compressor, Server
    from repro.launch.roofline import collective_bytes
    from repro.optim.adam import adam

    model = StructuredModel(
        global_dim=3, local_dim=2,
        log_prior_global=lambda th, zg: -0.5 * jnp.sum((zg - th["m"]) ** 2),
        log_local=lambda th, zg, zl, d: (
            -0.5 * jnp.sum((zl - jnp.mean(zg)) ** 2)
            - 0.5 * jnp.sum((d["y"] - zl[None, :]) ** 2)),
    )
    prob = SFVIProblem(model, DiagGaussian(3),
                       ConditionalGaussian(2, 3, use_coupling=False))
    J = 4
    datas = [{"y": jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(2), j), (4, 2))}
        for j in range(J)]
    for algo in ("sfvi", "sfvi_avg"):
        srv = Server(prob, datas, {"m": jnp.asarray(0.1)},
                     prob.global_family.init(jax.random.PRNGKey(1)),
                     server_opt=adam(1e-2), local_opt=adam(1e-2),
                     compressor=Int8Compressor(), wire="flat", seed=0,
                     strategy=algo)
        # The ship template has several leaves; the flat wire must bill
        # ONE int8 row + ONE f32 scale per silo, matching the gathered
        # HLO result bytes exactly (gather result = J x per-silo bytes).
        n_leaves = len(jax.tree_util.tree_leaves(srv.ship_template()))
        assert n_leaves > 1, n_leaves
        hlo = srv._lower(None, 1).compile().as_text()
        gathered = collective_bytes(hlo)["all-gather"]
        host = srv.bytes_up_per_silo()
        assert gathered == J * host, (algo, gathered, J, host)
        print(algo, "OK", int(gathered), J * host)
""")


@pytest.mark.slow
def test_host_meter_matches_compiled_collective_bytes():
    """Satellite regression: ``bytes_up_per_silo`` (host meter) must
    equal the compiled all-gather's per-silo result bytes on the flat
    int8 wire. The pre-fix meter billed one 4-byte scale PER LEAF while
    the wire ships ONE (P,) int8 row + ONE f32 scale per silo."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _METER_SCRIPT],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=1200,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert out.stdout.count("OK") == 2, out.stdout
