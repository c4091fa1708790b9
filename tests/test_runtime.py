"""Integration tests for the federated runtime (Algorithms 1 & 2)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ConditionalGaussian,
    DiagGaussian,
    SFVIProblem,
    StructuredModel,
    family_barycenter,
)
from repro.federated import MeanAggregator, RoundScheduler, Server
from repro.federated.metering import tree_bytes
from repro.optim import adam


def _toy_problem(dG=2, dL=3):
    def log_prior_global(theta, zg):
        return -0.5 * jnp.sum(zg**2)

    def log_local(theta, zg, zl, data):
        return -0.5 * jnp.sum((zl - jnp.mean(zg)) ** 2) - 2.0 * jnp.sum(
            (data - zl[None, :]) ** 2
        )

    model = StructuredModel(
        global_dim=dG, local_dim=dL,
        log_prior_global=log_prior_global, log_local=log_local,
    )
    return SFVIProblem(model, DiagGaussian(dG), ConditionalGaussian(dL, dG))


def _server(prob, J=3, n=5, lr=5e-2, seed=0, strategy="sfvi"):
    """A Server over J silos of n standard-normal rows each; SFVI
    averages η_G in parameter space, SFVI-Avg through the barycenter."""
    datas = [
        jax.random.normal(jax.random.PRNGKey(100 + seed + j),
                          (n, prob.model.local_dim))
        for j in range(J)
    ]
    return Server(
        prob, datas, {}, prob.global_family.init(jax.random.PRNGKey(1)),
        num_obs=[n] * J, server_opt=adam(lr), local_opt=adam(lr),
        eta_mode="param" if strategy == "sfvi" else "barycenter",
        strategy=strategy, seed=seed)


class TestSFVIServer:
    """Algorithm 1 (one gather per local step) on the compiled Server."""

    def test_elbo_improves(self):
        srv = _server(_toy_problem())
        h = srv.run(200, local_steps=1)
        assert np.mean(h["elbo"][-20:]) > np.mean(h["elbo"][:20])

    def test_no_nans(self):
        srv = _server(_toy_problem())
        h = srv.run(50, local_steps=1)
        assert np.all(np.isfinite(h["elbo"]))
        for leaf in jax.tree_util.tree_leaves(srv.eta_G):
            assert bool(jnp.all(jnp.isfinite(leaf)))

    def test_communication_is_global_sized_only(self):
        """The up-link carries ONLY global-shaped gradients — nothing scaling
        with local latent dims or data size (the paper's privacy property)."""
        srv = _server(_toy_problem(dG=2, dL=50), J=2, n=40, lr=1e-2)
        h = srv.run(3, local_steps=1)
        # up-link per silo per round = g_theta (empty) + g_eta (2*dG floats)
        expected_up_per_silo = 2 * 2 * 4  # mu+log_sigma, dG=2, f32
        assert srv.bytes_up_per_silo() == expected_up_per_silo
        assert h["bytes_up"][0] == 2 * expected_up_per_silo

    def test_partial_participation_still_converges(self):
        srv = _server(_toy_problem(), J=4)
        h = srv.run(300, local_steps=1,
                    scheduler=RoundScheduler(4, participation=0.5, seed=0))
        assert min(h["n_active"]) < 4  # silos really sat rounds out
        assert np.mean(h["elbo"][-20:]) > np.mean(h["elbo"][:20])

    def test_local_params_never_in_messages(self):
        """Structural privacy check: no leaf of a silo's upload has the
        local dimension."""
        srv = _server(_toy_problem(dG=2, dL=17), J=1)
        for algorithm in ("sfvi", "sfvi_avg"):
            for leaf in jax.tree_util.tree_leaves(
                    srv.ship_template(algorithm)):
                assert 17 not in leaf.shape


class TestSFVIAvgServer:
    """Algorithm 2 (K local steps, one barycenter merge) on the Server."""

    def test_elbo_improves(self):
        """Late-window mean ELBO beats the early window by more than the
        estimator noise. The per-round ELBO is a single-sample MC
        estimate, so comparing two individual draws (first vs last) is a
        coin flip once the optimizer has converged — the old 25-step
        rounds converged inside round 0, leaving only noise to compare.
        Short rounds keep real signal across the run, the run is seeded,
        and the tolerance is derived from the within-window variance of
        the estimates themselves (2x the pooled standard error) instead
        of a magic constant."""
        srv = _server(_toy_problem(), lr=2e-2, strategy="sfvi_avg")
        h = srv.run(12, local_steps=3)
        elbo = np.asarray(h["elbo"])
        early, late = elbo[:3], elbo[-3:]
        pooled_se = np.sqrt(np.var(early, ddof=1) / early.size
                            + np.var(late, ddof=1) / late.size)
        assert late.mean() - early.mean() > 2.0 * pooled_se, (
            f"improvement {late.mean() - early.mean():.3f} not significant "
            f"vs estimator noise (2*SE = {2 * pooled_se:.3f}); trace {elbo}")

    def test_fewer_rounds_than_sfvi_for_same_steps(self):
        """Communication efficiency: m local steps per round -> 1 round of
        communication instead of m (the paper's whole point for SFVI-Avg)."""
        prob = _toy_problem()
        srv_a = _server(prob)
        h_a = srv_a.run(100, local_steps=1)

        srv_b = _server(prob, strategy="sfvi_avg")
        h_b = srv_b.run(4, local_steps=25)  # same 100 gradient steps

        assert srv_b.comm.rounds < srv_a.comm.rounds
        assert srv_b.comm.total < srv_a.comm.total
        # And it still reaches a comparable ELBO neighbourhood (coarse check).
        assert h_b["elbo"][-1] > h_a["elbo"][0]

    def test_barycenter_of_identical_silos_is_identity(self):
        """If all silos return the same η_G, averaging must not move it."""
        fam = _toy_problem().global_family
        eta = fam.init(jax.random.PRNGKey(0))
        stacked = jax.tree_util.tree_map(lambda x: jnp.stack([x] * 3), eta)
        out = family_barycenter(fam, stacked, jnp.ones((3,)),
                                MeanAggregator())
        for k in eta:
            np.testing.assert_allclose(out[k], eta[k], rtol=1e-5)


class TestTreeBytes:
    def test_counts_f32(self):
        assert tree_bytes({"a": jnp.zeros((3, 4), jnp.float32)}) == 48

    def test_empty(self):
        assert tree_bytes({}) == 0


class TestControlPlane:
    """Server.run's host control plane: a RoundScheduler's masks, the
    round key and the meter's counts come out of the round program, so
    a round is one launch and one pull."""

    @pytest.mark.parametrize("K,churn", [(1, False), (25, False), (3, True)])
    def test_two_pulls_per_round_and_history_matches_eager_masks(
            self, monkeypatch, K, churn):
        """One pull a round; the pulled counts, the sent membership
        vectors and the history are the eager oracle's."""
        from test_federated import EagerRoundScheduler

        from repro.federated import runtime
        from repro.federated.api import ExperimentSpec, ModelSpec, build
        from repro.federated.population import PopulationSpec
        from repro.federated.scheduler import Scenario

        J, R = 5, (12 if churn else 4)
        knobs = dict(participation=0.6, dropout=0.3, seed=2)
        spec = ExperimentSpec(
            model=ModelSpec("toy"),
            scenario=Scenario(algorithm="sfvi", participation=0.6,
                              dropout=0.3),
            num_silos=J, rounds=R, local_steps=K, seed=2,
            population=(PopulationSpec(initial=2, arrival_rate=0.6,
                                       departure_rate=0.2, return_rate=0.5,
                                       seed=3) if churn else None))
        exp = build(spec)
        assert exp.scheduler == runtime.RoundScheduler(J, **knobs)
        eager = EagerRoundScheduler(J, **knobs)
        ref = build(spec)
        ref.scheduler = eager
        h_ref = ref.run()

        pulls, sent, begun = [], [], []
        device_get, control = runtime.jax.device_get, exp.server._control

        def count_pull(x):
            pulls.append(x)
            return device_get(x)

        def keep_sent(host):
            if isinstance(host, dict) and "weight" in host:
                sent.append(host)  # a population round's membership
            return control(host)

        monkeypatch.setattr(runtime.jax, "device_get", count_pull)
        monkeypatch.setattr(exp.server, "_control", keep_sent)
        if churn:
            begin_round = exp.population.begin_round

            def keep_begun(server, r):
                out = begin_round(server, r)
                begun.append(out)
                return out

            monkeypatch.setattr(exp.population, "begin_round", keep_begun)
        exp.run(rounds=1)  # compiles; counted like any other round
        h = exp.run()
        assert len(pulls) == R
        assert h["elbo_trace"] == h_ref["elbo_trace"]
        for a, b in zip(jax.tree_util.tree_leaves(exp.server.state),
                        jax.tree_util.tree_leaves(ref.server.state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert len(sent) == (R if churn else 0)

        # The round's counts, the meter and the sent membership, rebuilt
        # from the eager draw.
        up1 = exp.server.bytes_up_per_silo()
        down1 = exp.server.bytes_down_per_silo()
        for r in range(R):
            inv, rep = eager.round_masks(range(r * K, (r + 1) * K))
            present, stale_w = begun[r] if churn else (np.ones(J),) * 2
            j = len(present)
            inv, rep = inv[:, :j] * present, rep[:, :j] * present
            if churn:
                np.testing.assert_array_equal(sent[r]["present"][:j], present)
                np.testing.assert_array_equal(sent[r]["weight"][:j], stale_w)
                assert not sent[r]["present"][j:].any()
                assert not sent[r]["weight"][j:].any()
            active = rep.sum(axis=1).astype(int)
            invited = np.maximum(inv.sum(axis=1).astype(int), active)
            report = np.asarray(pulls[r])  # ELBOs, then E active, E invited
            np.testing.assert_array_equal(report[-2 * K:-K], active)
            np.testing.assert_array_equal(report[-K:], invited)
            assert h["bytes_up"][r] == active.sum() * up1
            assert h["bytes_down"][r] == invited.sum() * down1
            assert h["n_active"][r] == active[-1]
        assert len(set(h["n_active"])) > 1  # the draws really vary
        if churn:  # silos joined, departed and came back stale
            assert exp.population.state.joined > 2
            assert any((p == 0).any() for p, _ in begun)
            assert any(((w > 0) & (w < 1)).any() for _, w in begun)

    def test_round_index_is_traced(self):
        """20 rounds, one Experiment.run(rounds=1) call each, lower the
        round program once: the absolute round index is a traced
        argument, not a static one."""
        from repro import debug
        from repro.federated.api import ExperimentSpec, ModelSpec, build
        from repro.federated.scheduler import Scenario

        lowered = []

        def listen(event, duration, **kw):
            if event.endswith("jaxpr_to_mlir_module_duration"):
                lowered.append(kw.get("fun_name"))

        exp = build(ExperimentSpec(
            model=ModelSpec("toy"),
            scenario=Scenario(algorithm="sfvi", participation=0.5,
                              dropout=0.2),
            num_silos=4, rounds=20, local_steps=3, seed=5))
        jax.monitoring.register_event_duration_secs_listener(listen)
        try:
            with debug.watch_recompiles() as wd:
                for _ in range(20):
                    exp.run(rounds=1)
        finally:
            jax.monitoring.unregister_event_duration_listener(listen)
        assert lowered.count("jit(round_fn)") == 1, lowered
        assert wd.total == 1, dict(wd.counts)
        assert exp.round == 20 and len(exp.history["elbo"]) == 20

    @pytest.mark.parametrize("algorithm", ["sfvi", "sfvi_avg"])
    def test_steady_loop_transfers_nothing_to_the_device(
            self, monkeypatch, algorithm):
        """After the first round, with no population, a round moves
        nothing host to device, explicitly or implicitly, and opens no
        sanctioned host window."""
        from repro import debug
        from repro.federated import runtime
        from repro.federated.api import ExperimentSpec, ModelSpec, build
        from repro.federated.scheduler import Scenario

        exp = build(ExperimentSpec(
            model=ModelSpec("toy"),
            scenario=Scenario(algorithm=algorithm, participation=0.5,
                              dropout=0.2),
            num_silos=4, rounds=6, local_steps=2, seed=1))
        exp.run(rounds=1)
        bridges = []
        real = debug.host_bridge

        def counted():
            bridges.append(1)
            return real()

        monkeypatch.setattr(runtime.debug, "host_bridge", counted)
        with jax.transfer_guard_host_to_device("disallow_explicit"):
            for _ in range(5):
                exp.run(rounds=1)
        assert not bridges
        assert len(exp.history["elbo"]) == 6
        assert np.isfinite(exp.history["elbo"]).all()
