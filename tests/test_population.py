"""Population dynamics: churn, warm-start, padded growth, serving.

Covers the acceptance surface of the population subsystem:
  * the event stream is a pure function of (seed, kind, index, silo);
  * a churn run (sync AND buffered-async) checkpoints and resumes
    bit-exactly mid-run — population state, buffer state, η_L and the
    remaining trajectory all match the uninterrupted run;
  * amortized warm-start of a joining silo reaches the
    frozen-population ELBO level in measurably fewer rounds than the
    cold family init;
  * a join leaves the pre-existing silos' trajectory untouched up to
    the join round (the growth is purely additive);
  * PVI/FedEP churn: a departed silo's site λ_j is bit-frozen across
    the depart→return gap and the site-sum invariant
    Σλ_j == nat(q_G) − nat(q_init) survives churn;
  * (forced 2 host devices) the padded silo axis grows in mesh-sized
    chunks: the compiled round retraces exactly when J_pad steps, and
    a resume that re-grows past a J_pad boundary stays bit-exact;
  * graph-cache tokens split on j_pad exactly when it changes.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.federated import graph_cache
from repro.federated.api import (Experiment, ExperimentSpec, ModelSpec,
                                 build)
from repro.federated.population import (_ARRIVAL, _DEPART, _RETURN, ACTIVE,
                                        DEPARTED, PopulationSpec,
                                        PopulationState, event_draw)
from repro.federated.scheduler import AsyncConfig, Scenario

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spec(pop, *, algorithm="sfvi", num_silos=6, rounds=12, seed=0,
          async_buf=None, **over):
    scenario = Scenario(
        algorithm=algorithm,
        async_cfg=(AsyncConfig(buffer_size=async_buf)
                   if async_buf is not None else None))
    base = dict(model=ModelSpec("toy"), scenario=scenario,
                num_silos=num_silos, rounds=rounds, seed=seed,
                population=pop)
    base.update(over)
    return ExperimentSpec(**base)


_CHURN = PopulationSpec(initial=2, arrival_rate=0.6, departure_rate=0.2,
                        return_rate=0.5, seed=3)


def _tree_equal(a, b):
    flat_a = jax.tree_util.tree_leaves(a)
    flat_b = jax.tree_util.tree_leaves(b)
    return len(flat_a) == len(flat_b) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(flat_a, flat_b))


class TestEventStream:
    def test_draws_are_pure_and_distinct_per_cell(self):
        assert event_draw(0, _ARRIVAL, 3, 1) == event_draw(0, _ARRIVAL, 3, 1)
        cells = {(k, i, j): event_draw(7, k, i, j)
                 for k in (_ARRIVAL, _DEPART, _RETURN)
                 for i in range(4) for j in range(3)}
        assert len(set(cells.values())) == len(cells)
        assert all(0.0 <= v < 1.0 for v in cells.values())

    def test_state_round_trips_through_json(self):
        st = PopulationState(round=5, joined=3, status=[ACTIVE, DEPARTED,
                                                        ACTIVE],
                             last_present=[4, 1, 4])
        back = PopulationState.from_state(
            json.loads(json.dumps(st.state_dict())))
        assert back == st

    def test_spec_validation(self):
        with pytest.raises(ValueError, match="initial"):
            PopulationSpec(initial=0)
        with pytest.raises(ValueError, match="arrival_rate"):
            PopulationSpec(arrival_rate=1.5)
        with pytest.raises(ValueError, match="max_silos"):
            PopulationSpec(initial=4, max_silos=2)

    def test_roster_cap_enforced_against_staged_bundle(self):
        spec = _spec(PopulationSpec(initial=2, max_silos=9), num_silos=4)
        with pytest.raises(ValueError, match="max_silos"):
            build(spec)


class TestChurnResume:
    """Mid-run save → resume replays the churn schedule bit-exactly."""

    def _check(self, spec, tmp_path, cut):
        full = build(spec)
        h_full = full.run()

        exp = build(spec)
        exp.run(rounds=cut,
                callback=lambda r, m: exp.save(str(tmp_path))
                if r + 1 == cut else None)
        res = Experiment.resume(str(tmp_path))
        assert res.round == cut
        assert res.server.J == res.population.state.joined
        h_res = res.run()

        np.testing.assert_array_equal(
            np.asarray(h_full["elbo"][cut:]), np.asarray(h_res["elbo"]))
        assert _tree_equal(full.server.state["eta_L"],
                           res.server.state["eta_L"])
        assert _tree_equal(full.theta, res.theta)
        assert (full.population.state.state_dict()
                == res.population.state.state_dict())
        return full, res

    def test_sync_churn_resumes_bit_exact(self, tmp_path):
        spec = _spec(_CHURN)
        full, res = self._check(spec, tmp_path, cut=6)
        # The schedule actually churned: silos joined AND departed.
        assert full.population.state.joined > _CHURN.initial
        assert DEPARTED in full.population.state.status

    def test_async_churn_resumes_bit_exact(self, tmp_path):
        spec = _spec(_CHURN, algorithm="sfvi_avg", rounds=10, async_buf=2)
        full, res = self._check(spec, tmp_path, cut=5)
        assert (full.async_state.state_dict()
                == res.async_state.state_dict())

    def test_population_state_is_checkpointed_mid_async_run(self, tmp_path):
        """The regression this suite exists for: a mid-run save used to
        miss the async BufferState (it was only assigned after
        run_buffered returned), silently restarting the event loop."""
        spec = _spec(_CHURN, algorithm="sfvi_avg", rounds=10, async_buf=2)
        exp = build(spec)
        exp.run(rounds=4,
                callback=lambda r, m: exp.save(str(tmp_path))
                if r + 1 == 2 else None)
        step2 = json.load(open(os.path.join(tmp_path, "step_00000002.meta.json")))
        assert "async_state" in step2
        assert "population" in step2
        assert step2["population"]["round"] == 2


class TestWarmStart:
    def test_joining_silo_reaches_frozen_population_elbo_faster(self):
        """Acceptance criterion: the amortized warm-start closes the
        joining silo's ELBO gap in measurably fewer rounds than the
        cold family init. Target level: the same-length run with the
        full population present from round 0 (all-cold, so the target
        is what the federation itself reaches in this budget)."""
        rounds = 40

        def run(pop):
            spec = _spec(pop, num_silos=3, rounds=rounds)
            return np.asarray(build(spec).run()["elbo"])

        fixed = run(None)
        join = dict(initial=2, arrival_rate=1.0, seed=1)
        warm = run(PopulationSpec(warm_start=True, **join))
        cold = run(PopulationSpec(warm_start=False, **join))
        target = fixed[-5:].mean()

        def rounds_to_target(elbo):
            idx = np.nonzero(elbo >= target)[0]
            return int(idx[0]) if idx.size else len(elbo)

        r_warm, r_cold = rounds_to_target(warm), rounds_to_target(cold)
        assert r_warm + 5 <= r_cold, (r_warm, r_cold, target)


class TestAdditiveGrowth:
    def test_join_leaves_preexisting_trajectory_untouched(self):
        """Runs identical up to the join round: the growth is purely
        additive (satellite: pre-existing silos' trajectories
        unaffected by a mid-run join). pop seed 2 @ rate 0.3 first
        fires the arrival draw at round 3."""
        join_round = 3
        assert event_draw(2, _ARRIVAL, join_round, 2) < 0.3
        assert all(event_draw(2, _ARRIVAL, r, 2) >= 0.3
                   for r in range(join_round))

        def run(rate):
            pop = PopulationSpec(initial=2, arrival_rate=rate, seed=2)
            spec = _spec(pop, num_silos=3, rounds=6)
            exp = build(spec)
            snaps = []
            exp.run(callback=lambda r, m: snaps.append(
                jax.tree_util.tree_map(
                    lambda x: np.asarray(x)[:2].copy(),
                    exp.server.state["eta_L"])))
            return exp, snaps

        grown, snaps_g = run(0.3)
        frozen, snaps_f = run(0.0)
        assert grown.server.J == 3 and frozen.server.J == 2
        for r in range(join_round):
            assert _tree_equal(snaps_g[r], snaps_f[r]), r
        np.testing.assert_array_equal(
            np.asarray(grown.history["elbo"][:join_round]),
            np.asarray(frozen.history["elbo"][:join_round]))
        # ... and the join round itself diverges (the new silo enters
        # the round's aggregate ELBO) — additive, not inert.
        assert (grown.history["elbo"][join_round]
                != frozen.history["elbo"][join_round])


class TestSiteChurn:
    """Satellite: PVI/FedEP site state survives depart/return gaps."""

    @pytest.mark.parametrize("algorithm", ["pvi", "fed_ep"])
    def test_lambda_frozen_across_gap_and_site_sum_invariant(
            self, algorithm):
        from repro.federated.strategy import natural_from_eta

        pop = PopulationSpec(initial=3, arrival_rate=0.5,
                             departure_rate=0.25, return_rate=0.4,
                             staleness_decay=0.0, seed=5)
        spec = _spec(pop, algorithm=algorithm, num_silos=4, rounds=25,
                     local_steps=4)
        exp = build(spec)
        traj = []
        exp.run(callback=lambda r, m: traj.append((
            list(exp.population.state.status),
            jax.tree_util.tree_map(
                lambda x: np.asarray(x).copy(),
                exp.server.state["strategy"]["lam"]))))

        # Every depart→(return|end) gap: the λ row is bit-frozen while
        # the silo is away, for every silo that ever departed.
        gaps = 0
        J = exp.server.J
        for j in range(J):
            r = 0
            while r < len(traj):
                status, _ = traj[r]
                if j < len(status) and status[j] == DEPARTED:
                    start = r
                    while r < len(traj) and traj[r][0][j] == DEPARTED:
                        r += 1
                    gaps += 1
                    ref = jax.tree_util.tree_map(
                        lambda x: x[j], traj[start][1])
                    for rr in range(start + 1, r):
                        assert _tree_equal(ref, jax.tree_util.tree_map(
                            lambda x: x[j], traj[rr][1])), (j, rr)
                else:
                    r += 1
        assert gaps >= 2  # the schedule actually exercised the property

        # Σλ_j == nat(q_G) − nat(q_init), extended to churn.
        prob = exp.server.problem
        fam = prob.global_family
        eta0 = fam.init(jax.random.PRNGKey(spec.seed))
        nat0 = natural_from_eta(fam, eta0)
        natG = natural_from_eta(fam, exp.server.state["eta_G"])
        lam = exp.server.state["strategy"]["lam"]
        for k in ("h", "prec"):
            lam_sum = np.asarray(lam[k])[:exp.server.J].sum(axis=0)
            np.testing.assert_allclose(
                lam_sum, np.asarray(natG[k]) - np.asarray(nat0[k]),
                rtol=1e-3, atol=1e-3)


class TestGraphCacheToken:
    def test_token_changes_exactly_when_j_pad_does(self):
        spec_json = _spec(_CHURN).to_json(indent=0)
        mk = lambda jp: graph_cache.build_token(
            spec_json, "flat", 6, mesh_shape=(("silo", 2),), j_pad=jp)
        assert mk(2) == mk(2)
        assert mk(2) != mk(4)
        assert mk(4) == mk(4)


_GROWTH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=2")
    import tempfile

    import jax
    import numpy as np
    from repro.federated import (Experiment, ExperimentSpec, ModelSpec,
                                 PopulationSpec, Scenario, build)

    assert jax.device_count() == 2

    def leaves(exp):
        st = exp.server.state
        return [np.asarray(x) for k in ("theta", "eta_G", "eta_L")
                for x in jax.tree_util.tree_leaves(st[k])]

    # Every round joins the next roster silo: J walks 2,3,4,5 so the
    # padded axis must cross the 2-device chunk boundary (2 -> 4 -> 6).
    spec = ExperimentSpec(
        model=ModelSpec("toy", {"num_obs": 8}),
        scenario=Scenario(algorithm="sfvi"),
        num_silos=5, rounds=4, seed=0,
        population=PopulationSpec(initial=2, arrival_rate=1.0, seed=0))

    full = build(spec)
    pads, fns = [], []
    def snap(r, m):
        pads.append(full.server.J_pad)
        fns.append(sorted({k[2] for k in full.server._round_fns}))
    h_full = full.run(callback=snap)
    assert full.server.J == 5, full.server.J
    # Joins fire BEFORE their round, so the post-round snapshots see J
    # walk 3,4,5,5 — J_pad grows in mesh-sized (2) chunks...
    assert pads == [4, 4, 6, 6], pads
    # ...and the compiled round is refetched EXACTLY when J_pad steps:
    # the round-fn cache holds the programs of each J_pad seen (4, then
    # 6), none added within a chunk (round 1: J 3->4 inside the
    # 4-chunk). Churn runs before a round's set-up, so the pre-join
    # J_pad of 2 is never compiled; each J_pad holds the explicit-input
    # round and the in-graph-draw round that traces its body.
    assert fns == [[4], [4], [4, 6], [4, 6]], fns
    assert len(full.server._round_fns) == 4
    print("chunked-growth OK")

    # Resume saved at J=4 (J_pad=4) re-grows past the 6-boundary
    # bit-exactly: re-padding + per-(seed, j) fold-in init make the
    # re-grown rows identical to the uninterrupted run's.
    d = tempfile.mkdtemp()
    exp = build(spec)
    exp.run(rounds=2,
            callback=lambda r, m: exp.save(d) if r + 1 == 2 else None)
    res = Experiment.resume(d)
    assert res.server.J == 4 and res.server.J_pad == 4, (
        res.server.J, res.server.J_pad)
    h_res = res.run()
    np.testing.assert_array_equal(np.asarray(h_full["elbo"][2:]),
                                  np.asarray(h_res["elbo"]))
    for a, b in zip(leaves(full), leaves(res)):
        np.testing.assert_array_equal(a, b)
    print("boundary-resume OK")
""")


@pytest.mark.slow
def test_padded_growth_on_two_device_mesh():
    """Satellite: mesh-chunked silo-axis growth under forced host
    devices — J_pad steps in chunks, retrace count matches, and a
    resume that crosses a J_pad boundary stays bit-exact."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _GROWTH_SCRIPT],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=1200,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "chunked-growth OK" in out.stdout
    assert "boundary-resume OK" in out.stdout
