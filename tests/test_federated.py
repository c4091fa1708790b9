"""Tests for the compiled federated runtime (repro.federated).

Covers the two correctness anchors from the paper:
  * partition invariance (§3 Remark): one Server SFVI round applies exactly
    the centralized gradient of ``SFVIProblem.centralized_objective``;
  * SFVI-Avg degenerates to SFVI at K=1 (§3.2): with SGD, equal silo
    sizes and parameter-space averaging the round maps are identical.
plus the aggregation/compression/scheduling plumbing.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ConditionalGaussian,
    DiagGaussian,
    SFVIProblem,
    StructuredModel,
)
from repro.federated import (
    Int8Compressor,
    MeanAggregator,
    NoCompression,
    RoundScheduler,
    Server,
    TrimmedMeanAggregator,
    global_eps,
    silo_eps,
)
from repro.federated.strategy import get_strategy
from repro.optim.adam import adam
from repro.optim.sgd import sgd


def _hier_problem(dG=3, dL=2, use_coupling=False):
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
    return SFVIProblem(
        model, DiagGaussian(dG), ConditionalGaussian(dL, dG, use_coupling=use_coupling)
    )


def _global_only_problem(dG=3):
    model = StructuredModel(
        global_dim=dG, local_dim=0,
        log_prior_global=lambda th, zg: -0.5 * jnp.sum((zg - th["m"]) ** 2),
        log_local=lambda th, zg, zl, d: -0.5 * jnp.sum((d["y"] - zg[None, :]) ** 2),
    )
    return SFVIProblem(model, DiagGaussian(dG))


def _datas(key, J, n, d):
    return [
        {"y": jax.random.normal(jax.random.fold_in(key, j), (n, d))}
        for j in range(J)
    ]


def _flat(tree):
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.zeros((0,))
    return jnp.concatenate([jnp.ravel(x) for x in leaves])


class TestPartitionInvariance:
    @pytest.mark.parametrize("J", [1, 3, 5])
    def test_server_round_matches_centralized_gradient(self, J):
        """One SFVI round with SGD(lr) moves (θ, η_G) by exactly
        lr · ∇ of the centralized single-graph objective."""
        lr = 0.05
        prob = _hier_problem()
        theta = {"m": jnp.asarray(0.3), "lt": jnp.asarray(-0.5)}
        eta_G = prob.global_family.init(jax.random.PRNGKey(1), mu_scale=0.5)
        datas = _datas(jax.random.PRNGKey(2), J, n=4, d=2)

        srv = Server(prob, datas, theta, eta_G,
                     server_opt=sgd(lr), local_opt=sgd(lr), seed=7)
        eta_L0 = jax.tree_util.tree_map(jnp.copy, srv.eta_L)
        srv.run(1, algorithm="sfvi", local_steps=1)

        # Replay the exact shared-randomness draws of round 0, step 0.
        round_key = jax.random.fold_in(jax.random.PRNGKey(7), 0)
        eps_G = global_eps(prob, round_key, 0)
        eps_L = [silo_eps(prob, round_key, 0, j) for j in range(J)]
        etas_L = [jax.tree_util.tree_map(lambda x: x[j], eta_L0) for j in range(J)]

        g_th, g_eta = jax.grad(
            lambda th, eg: prob.centralized_objective(
                th, eg, etas_L, eps_G, eps_L, datas),
            argnums=(0, 1),
        )(theta, eta_G)

        np.testing.assert_allclose(
            _flat(srv.theta), _flat(theta) + lr * _flat(g_th), rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(
            _flat(srv.eta_G), _flat(eta_G) + lr * _flat(g_eta), rtol=2e-4, atol=2e-5)

    def test_elbo_improves_with_adam(self):
        prob = _hier_problem()
        theta = {"m": jnp.asarray(0.0), "lt": jnp.asarray(0.0)}
        eta_G = prob.global_family.init(jax.random.PRNGKey(1))
        srv = Server(prob, _datas(jax.random.PRNGKey(2), 4, 6, 2), theta, eta_G,
                     server_opt=adam(2e-2), local_opt=adam(2e-2))
        h = srv.run(30, algorithm="sfvi", local_steps=2)
        assert h["elbo"][-1] > h["elbo"][0]


class TestAvgEqualsSfviAtK1:
    def test_full_state_equality_global_only(self):
        """No local latents: the K=1 SFVI-Avg round map IS the SFVI round
        map (SGD, equal N_j, parameter-space η_G merge)."""
        lr = 0.03
        prob = _global_only_problem()
        theta = {"m": jnp.asarray(0.2)}
        eta_G = prob.global_family.init(jax.random.PRNGKey(3), mu_scale=0.4)
        datas = _datas(jax.random.PRNGKey(4), 4, n=5, d=3)

        kw = dict(server_opt=sgd(lr), eta_mode="param", seed=11)
        a = Server(prob, datas, theta, eta_G, **kw)
        b = Server(prob, datas, theta, eta_G, **kw)
        a.run(3, algorithm="sfvi", local_steps=1)
        b.run(3, algorithm="sfvi_avg", local_steps=1)

        np.testing.assert_allclose(_flat(a.theta), _flat(b.theta), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(_flat(a.eta_G), _flat(b.eta_G), rtol=1e-5, atol=1e-6)

    def test_server_state_equality_with_locals(self):
        """With local latents, (θ, η_G) still agree after one K=1 round:
        mean_j[∇(L̂_0 + (N/N_j) L̂_j)] = ∇L̂_0 + Σ_j ∇L̂_j for equal N_j."""
        lr = 0.03
        prob = _hier_problem()
        theta = {"m": jnp.asarray(0.1), "lt": jnp.asarray(-0.2)}
        eta_G = prob.global_family.init(jax.random.PRNGKey(5), mu_scale=0.4)
        datas = _datas(jax.random.PRNGKey(6), 3, n=4, d=2)

        kw = dict(server_opt=sgd(lr), local_opt=sgd(lr), eta_mode="param", seed=13)
        a = Server(prob, datas, theta, eta_G, **kw)
        b = Server(prob, datas, theta, eta_G, **kw)
        a.run(1, algorithm="sfvi", local_steps=1)
        b.run(1, algorithm="sfvi_avg", local_steps=1)

        np.testing.assert_allclose(_flat(a.theta), _flat(b.theta), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(_flat(a.eta_G), _flat(b.eta_G), rtol=1e-5, atol=1e-6)

    def test_avg_improves_elbo(self):
        prob = _hier_problem()
        theta = {"m": jnp.asarray(0.0), "lt": jnp.asarray(0.0)}
        eta_G = prob.global_family.init(jax.random.PRNGKey(1))
        srv = Server(prob, _datas(jax.random.PRNGKey(2), 4, 6, 2), theta, eta_G,
                     server_opt=adam(2e-2), local_opt=adam(2e-2))
        h = srv.run(10, algorithm="sfvi_avg", local_steps=8)
        assert h["elbo"][-1] > h["elbo"][0]


class TestAggregation:
    def test_mean_respects_mask(self):
        stacked = {"g": jnp.asarray([[1.0], [3.0], [100.0]])}
        mask = jnp.asarray([1.0, 1.0, 0.0])
        out = MeanAggregator().combine(stacked, mask)
        np.testing.assert_allclose(out["g"], [2.0])

    def test_trimmed_mean_drops_outlier(self):
        stacked = {"g": jnp.asarray([[1.0], [2.0], [3.0], [1000.0]])}
        mask = jnp.ones((4,))
        out = TrimmedMeanAggregator(trim_frac=0.25).combine(stacked, mask)
        np.testing.assert_allclose(out["g"], [2.5])  # drops 1.0 and 1000.0

    def test_trimmed_mean_excludes_inactive(self):
        stacked = {"g": jnp.asarray([[1.0], [2.0], [jnp.inf]])}
        mask = jnp.asarray([1.0, 1.0, 0.0])
        out = TrimmedMeanAggregator(trim_frac=0.0).combine(stacked, mask)
        np.testing.assert_allclose(out["g"], [1.5])


class TestCompression:
    def test_int8_roundtrip_and_bytes(self):
        tree = {"a": jnp.linspace(-1.0, 1.0, 256), "b": jnp.ones((8, 8))}
        comp = Int8Compressor()
        dec = comp.decode(comp.encode(tree))
        np.testing.assert_allclose(dec["a"], tree["a"], atol=1.0 / 127 + 1e-6)
        np.testing.assert_allclose(dec["b"], tree["b"], atol=1.0 / 127 + 1e-6)
        assert comp.wire_bytes(tree) < NoCompression().wire_bytes(tree)

    def test_int8_inside_server_still_converges(self):
        prob = _hier_problem()
        theta = {"m": jnp.asarray(0.0), "lt": jnp.asarray(0.0)}
        eta_G = prob.global_family.init(jax.random.PRNGKey(1))
        srv = Server(prob, _datas(jax.random.PRNGKey(2), 4, 6, 2), theta, eta_G,
                     server_opt=adam(2e-2), local_opt=adam(2e-2),
                     compressor=Int8Compressor())
        h = srv.run(30, algorithm="sfvi", local_steps=2)
        assert h["elbo"][-1] > h["elbo"][0]
        raw = NoCompression().wire_bytes(srv.ship_template("sfvi"))
        assert srv.bytes_up_per_silo("sfvi") < raw


class EagerRoundScheduler(RoundScheduler):
    """Oracle: the per-index eager draw the batched ``round_masks``
    replaced, copied verbatim (``_keys``/``invited``/``mask``), so the
    batched draw is held to it bit for bit."""

    def _keys(self, round_idx: int):
        # repro-lint: allow[R1] — participation stream root, folded with the absolute round index on the same line
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), round_idx)
        return jax.random.split(key)

    def invited(self, round_idx: int) -> jnp.ndarray:
        k_inv, _ = self._keys(round_idx)
        J = self.num_silos
        mask = np.ones((J,), np.float32)
        if self.participation < 1.0:
            n_inv = max(1, int(self.participation * J + 0.5))
            chosen = np.asarray(
                jax.random.choice(k_inv, J, shape=(n_inv,), replace=False)
            )
            mask = np.zeros((J,), np.float32)
            mask[chosen] = 1.0
        return jnp.asarray(mask)

    def mask(self, round_idx: int) -> jnp.ndarray:
        _, k_drop = self._keys(round_idx)
        J = self.num_silos
        mask = np.asarray(self.invited(round_idx)).copy()
        if self.dropout > 0.0:
            survive = np.asarray(
                jax.random.bernoulli(k_drop, 1.0 - self.dropout, (J,))
            ).astype(np.float32)
            dropped = mask * survive
            mask = dropped if dropped.any() else _first_invited(mask)
        return jnp.asarray(mask)

    def round_masks(self, indices):
        """The oracle's rows, stacked as host (E, J) arrays."""
        return (np.stack([np.asarray(self.invited(i)) for i in indices]),
                np.stack([np.asarray(self.mask(i)) for i in indices]))


def _first_invited(mask: np.ndarray) -> np.ndarray:
    out = np.zeros_like(mask)
    out[int(np.argmax(mask))] = 1.0
    return out


class TestScheduling:
    def test_masks_are_deterministic(self):
        s = RoundScheduler(8, participation=0.5, dropout=0.2, seed=3)
        np.testing.assert_array_equal(s.mask(5), s.mask(5))

    @pytest.mark.parametrize("J,participation,dropout,seed", [
        (3, 1.0, 0.0, 7), (8, 0.5, 0.2, 3), (64, 0.25, 0.5, 11),
        (5, 0.5, 0.99, 0), (4, 0.01, 0.0, 0), (4, 1.0, 1.0, 5),
    ])
    @pytest.mark.parametrize("start", [0, 1000])
    def test_batched_draw_matches_eager_per_index_draw(
            self, J, participation, dropout, seed, start):
        """round_masks over 25 indices is the per-index eager draw,
        bit for bit; mask/invited/masks are views of its rows."""
        knobs = dict(participation=participation, dropout=dropout,
                     seed=seed)
        s = RoundScheduler(J, **knobs)
        idx = range(start, start + 25)
        inv, rep = jax.device_get(s.round_masks(idx))
        assert inv.dtype == rep.dtype == np.float32
        assert inv.shape == rep.shape == (25, J)
        ref_inv, ref_rep = EagerRoundScheduler(J, **knobs).round_masks(idx)
        np.testing.assert_array_equal(inv, ref_inv)
        np.testing.assert_array_equal(rep, ref_rep)
        for e, i in enumerate(idx):
            np.testing.assert_array_equal(s.mask(i), rep[e])
            np.testing.assert_array_equal(s.invited(i), inv[e])
        if start == 0:
            np.testing.assert_array_equal(s.masks(25), rep)

    def test_participation_counts(self):
        s = RoundScheduler(8, participation=0.5, seed=0)
        m = np.asarray(s.masks(20))
        assert (m.sum(axis=1) == 4).all()

    def test_never_empty_round(self):
        s = RoundScheduler(4, participation=0.25, dropout=0.99, seed=0)
        m = np.asarray(s.masks(50))
        assert (m.sum(axis=1) >= 1).all()

    def test_zero_participation_draw_still_invites_one(self):
        """participation so low it rounds to zero silos: the scheduler
        must never draw an empty invitation (at least one silo is always
        invited), and the round must still run."""
        s = RoundScheduler(4, participation=0.01, seed=0)
        m = np.asarray(s.masks(20))
        assert (m.sum(axis=1) == 1).all()

        prob = _hier_problem()
        theta = {"m": jnp.asarray(0.0), "lt": jnp.asarray(0.0)}
        eta_G = prob.global_family.init(jax.random.PRNGKey(1))
        srv = Server(prob, _datas(jax.random.PRNGKey(2), 4, 6, 2), theta, eta_G,
                     server_opt=adam(2e-2), local_opt=adam(2e-2))
        h = srv.run(3, algorithm="sfvi", local_steps=1,
                    scheduler=RoundScheduler(4, participation=0.01, seed=0))
        assert all(n == 1 for n in h["n_active"])
        assert all(np.isfinite(e) for e in h["elbo"])

    def test_all_silos_straggling_keeps_one_reporter(self):
        """dropout=1.0 (every invited silo straggles): the scheduler
        keeps the lowest-index invited silo so the round is never lost,
        only that silo's local state moves, and downloads are still
        billed for every invited straggler."""
        sched = RoundScheduler(4, dropout=1.0, seed=5)
        m = np.asarray(sched.masks(10))
        assert (m.sum(axis=1) == 1).all()
        assert (m[:, 0] == 1.0).all()  # lowest-index invitee survives

        prob = _hier_problem()
        theta = {"m": jnp.asarray(0.0), "lt": jnp.asarray(0.0)}
        eta_G = prob.global_family.init(jax.random.PRNGKey(1))
        srv = Server(prob, _datas(jax.random.PRNGKey(2), 4, 6, 2), theta, eta_G,
                     server_opt=adam(2e-2), local_opt=adam(2e-2))
        eta_L0 = jax.tree_util.tree_map(jnp.copy, srv.eta_L)
        h = srv.run(2, algorithm="sfvi", local_steps=1, scheduler=sched)
        assert all(n == 1 for n in h["n_active"])
        # Frozen stragglers: silos 1..3 kept their exact η_L.
        for j in range(1, 4):
            for a, b in zip(jax.tree_util.tree_leaves(eta_L0),
                            jax.tree_util.tree_leaves(srv.eta_L), strict=True):
                np.testing.assert_array_equal(np.asarray(a[j]), np.asarray(b[j]))
        # All 4 invited silos received the broadcast each round.
        assert h["bytes_down"][0] == 4 * srv.bytes_down_per_silo()
        assert h["bytes_up"][0] == 1 * srv.bytes_up_per_silo("sfvi")

    def test_partial_participation_round_runs(self):
        prob = _hier_problem()
        theta = {"m": jnp.asarray(0.0), "lt": jnp.asarray(0.0)}
        eta_G = prob.global_family.init(jax.random.PRNGKey(1))
        srv = Server(prob, _datas(jax.random.PRNGKey(2), 4, 6, 2), theta, eta_G,
                     server_opt=adam(2e-2), local_opt=adam(2e-2))
        h = srv.run(10, algorithm="sfvi", local_steps=1,
                    scheduler=RoundScheduler(4, participation=0.5, seed=1))
        assert all(n == 2 for n in h["n_active"])
        assert srv.comm.bytes_up < 10 * 4 * srv.bytes_up_per_silo("sfvi") + 1


def _assert_same_run(h, h_ref, exp, ref):
    """History and every state leaf bit-identical (NaN equal to NaN)."""
    for k in ("elbo", "elbo_trace", "bytes_up", "bytes_down", "n_active"):
        np.testing.assert_array_equal(np.asarray(h[k]), np.asarray(h_ref[k]),
                                      err_msg=k)
    leaves = jax.tree_util.tree_leaves(exp.state)
    ref_leaves = jax.tree_util.tree_leaves(ref.state)
    assert len(leaves) == len(ref_leaves)
    for a, b in zip(leaves, ref_leaves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestInGraphRound:
    """A RoundScheduler's draw, the round key and the meter's counts are
    derived inside the round program from the absolute round index; the
    eager host draw (EagerRoundScheduler, which overrides round_masks
    and so keeps the host path) is the oracle, bit for bit."""

    KNOBS = dict(participation=0.5, dropout=0.2, seed=2)

    def _spec(self, algorithm, churn, rounds=7):
        from repro.federated.api import ExperimentSpec, ModelSpec
        from repro.federated.population import PopulationSpec
        from repro.federated.scheduler import Scenario

        return ExperimentSpec(
            model=ModelSpec("toy"),
            scenario=Scenario(algorithm=algorithm, participation=0.5,
                              dropout=0.2),
            num_silos=5, rounds=rounds, local_steps=3, seed=2,
            population=(PopulationSpec(initial=2, arrival_rate=0.6,
                                       departure_rate=0.2, return_rate=0.5,
                                       seed=3) if churn else None))

    @pytest.mark.parametrize("churn", [False, True])
    @pytest.mark.parametrize("algorithm", ["sfvi", "sfvi_avg"])
    def test_history_and_state_match_eager_oracle(self, algorithm, churn):
        from repro.federated.api import build

        spec = self._spec(algorithm, churn)
        exp = build(spec)
        assert exp.scheduler.graph_rule is not None
        for n in (1, 2, None):  # ragged calls, as a user loop makes
            h = exp.run(n)
        ref = build(spec)
        ref.scheduler = EagerRoundScheduler(5, **self.KNOBS)
        assert ref.scheduler.graph_rule is None
        _assert_same_run(h, ref.run(), exp.server, ref.server)
        assert len(set(h["n_active"])) > 1

    @pytest.mark.parametrize("algorithm", ["sfvi", "sfvi_avg"])
    def test_split_runs_equal_one_run(self, algorithm):
        """run(a); run(b, start_round=a) is run(a + b), and a jump to an
        explicit start_round replays that round's draws, as the host
        draw does."""
        prob = _hier_problem()
        theta = {"m": jnp.asarray(0.0), "lt": jnp.asarray(0.0)}
        eta_G = prob.global_family.init(jax.random.PRNGKey(1))
        datas = _datas(jax.random.PRNGKey(2), 4, 6, 2)

        def server():
            return Server(prob, datas, theta, eta_G, server_opt=adam(2e-2),
                          local_opt=adam(2e-2), seed=9)

        sched = RoundScheduler(4, **self.KNOBS)
        kw = dict(algorithm=algorithm, local_steps=2)
        split, whole = server(), server()
        h = split.run(3, scheduler=sched, **kw)
        h2 = split.run(2, scheduler=sched, start_round=3, **kw)
        h_whole = whole.run(5, scheduler=sched, **kw)
        _assert_same_run({k: h[k] + h2[k] for k in h}, h_whole, split, whole)

        jump, oracle = server(), server()
        eager = EagerRoundScheduler(4, **self.KNOBS)
        h = jump.run(2, scheduler=sched, **kw)
        h2 = jump.run(2, scheduler=sched, start_round=7, **kw)
        o = oracle.run(2, scheduler=eager, **kw)
        o2 = oracle.run(2, scheduler=eager, start_round=7, **kw)
        _assert_same_run({k: h[k] + h2[k] for k in h},
                         {k: o[k] + o2[k] for k in o}, jump, oracle)

    @pytest.mark.parametrize("churn", [False, True])
    def test_save_resume_equals_one_run(self, tmp_path, churn):
        from repro.federated.api import Experiment, build

        spec = self._spec("sfvi", churn, rounds=6)
        whole = build(spec)
        h_whole = whole.run()
        exp = build(spec)
        exp.run(3)
        exp.save(str(tmp_path / "ckpt"))
        resumed = Experiment.resume(str(tmp_path / "ckpt"))
        h = resumed.run()
        assert resumed.round == 6 and len(h["elbo"]) == 3
        tail = {k: v[-len(h[k]):] for k, v in h_whole.items() if k in h}
        _assert_same_run(h, tail, resumed.server, whole.server)

    def test_setup_cache_follows_strategy_steps_and_growth(self):
        """The cached set-up is keyed on strategy and K, and a join
        that grows J_pad drops it; every phase matches the oracle."""
        from repro.federated.api import build
        from repro.federated.population import PopulationSpec

        spec = dataclasses.replace(
            self._spec("sfvi", churn=False),
            population=PopulationSpec(initial=2, arrival_rate=1.0, seed=3))
        exp, ref = build(spec), build(spec)
        ref.scheduler = EagerRoundScheduler(5, **self.KNOBS)
        pads, keys = [], []

        def snap(r, m):
            pads.append(exp.server.J_pad)
            keys.append([k[:3] for k in exp.server._setups])

        phases = (("sfvi", 3, 0, 2), ("sfvi", 2, 2, 1),
                  ("sfvi_avg", 2, 3, 2), ("sfvi", 2, 5, 1))
        runs = {id(exp): [], id(ref): []}
        for algorithm, K, r0, n in phases:
            for e in (exp, ref):
                runs[id(e)].append(e.server.run(
                    n, algorithm=algorithm, local_steps=K,
                    scheduler=e.scheduler, start_round=r0,
                    population=e.population,
                    callback=snap if e is exp else None))

        def joined(hs):
            return {k: sum((h[k] for h in hs), []) for k in hs[0]}

        _assert_same_run(joined(runs[id(exp)]), joined(runs[id(ref)]),
                         exp.server, ref.server)
        # One join a round on one device: J_pad steps 3, 4, 5 and each
        # step drops the set-up; strategy and K key entries of their own,
        # which a later run with the same pair finds again.
        sfvi = get_strategy("sfvi")().cache_key()
        avg = get_strategy("sfvi_avg")().cache_key()
        assert pads == [3, 4, 5, 5, 5, 5], pads
        assert keys == [[(sfvi, 3, 3)], [(sfvi, 3, 4)], [(sfvi, 2, 5)],
                        [(sfvi, 2, 5), (avg, 2, 5)],
                        [(sfvi, 2, 5), (avg, 2, 5)],
                        [(sfvi, 2, 5), (avg, 2, 5)]], keys

    def test_scheduler_overriding_round_masks_takes_effect(self):
        """A subclass that overrides round_masks has no rule the graph
        can trust: the Server keeps its host draw, and its masks rule
        the round."""

        class FirstSiloOnly(RoundScheduler):
            def round_masks(self, indices):
                rows = np.zeros((len(indices), self.num_silos), np.float32)
                rows[:, 0] = 1.0
                return rows, rows

        sched = FirstSiloOnly(4, seed=2)
        assert sched.graph_rule is None
        assert RoundScheduler(4, seed=2).graph_rule == (4, 1.0, 0.0)
        prob = _hier_problem()
        srv = Server(prob, _datas(jax.random.PRNGKey(2), 4, 6, 2),
                     {"m": jnp.asarray(0.0), "lt": jnp.asarray(0.0)},
                     prob.global_family.init(jax.random.PRNGKey(1)),
                     server_opt=adam(2e-2), local_opt=adam(2e-2))
        eta_L0 = np.asarray(jax.tree_util.tree_leaves(srv.eta_L)[0])
        h = srv.run(3, algorithm="sfvi", local_steps=2, scheduler=sched)
        assert h["n_active"] == [1, 1, 1]
        assert h["bytes_up"] == [2 * srv.bytes_up_per_silo("sfvi")] * 3
        assert h["bytes_down"] == [2 * srv.bytes_down_per_silo()] * 3
        eta_L = np.asarray(jax.tree_util.tree_leaves(srv.eta_L)[0])
        assert (eta_L[0] != eta_L0[0]).any()  # the one reporter moved
        np.testing.assert_array_equal(eta_L[1:], eta_L0[1:])  # the rest froze

    @pytest.mark.parametrize("sched_cls", [RoundScheduler,
                                           EagerRoundScheduler])
    @pytest.mark.parametrize("width", [3, 8])
    def test_scheduler_of_another_width_raises(self, sched_cls, width):
        """Without a population the scheduler covers exactly the J
        silos, whether it is drawn in the graph or on the host: a wider
        one would invite a share of silos that do not exist."""
        prob = _hier_problem()
        srv = Server(prob, _datas(jax.random.PRNGKey(2), 4, 6, 2),
                     {"m": jnp.asarray(0.0), "lt": jnp.asarray(0.0)},
                     prob.global_family.init(jax.random.PRNGKey(1)),
                     server_opt=adam(2e-2), local_opt=adam(2e-2))
        sched = sched_cls(width, seed=2, participation=0.5)
        with pytest.raises(ValueError, match="covers"):
            srv.run(1, algorithm="sfvi", local_steps=2, scheduler=sched)
        assert srv.run(1, algorithm="sfvi", local_steps=2,
                       scheduler=sched_cls(4, seed=2))["n_active"] == [4]


class TestCommAccounting:
    def test_sfvi_pays_per_step_avg_pays_per_round(self):
        prob = _hier_problem()
        theta = {"m": jnp.asarray(0.0), "lt": jnp.asarray(0.0)}
        eta_G = prob.global_family.init(jax.random.PRNGKey(1))
        K = 5
        a = Server(prob, _datas(jax.random.PRNGKey(2), 4, 6, 2), theta, eta_G,
                   server_opt=adam(2e-2), local_opt=adam(2e-2))
        b = Server(prob, _datas(jax.random.PRNGKey(2), 4, 6, 2), theta, eta_G,
                   server_opt=adam(2e-2), local_opt=adam(2e-2))
        a.run(2, algorithm="sfvi", local_steps=K)
        b.run(2, algorithm="sfvi_avg", local_steps=K)
        assert a.comm.per_round == K * b.comm.per_round
        assert b.comm.total < a.comm.total
