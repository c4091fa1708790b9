"""Tests for the declarative experiment API (repro.federated.api).

Covers the acceptance surface of the API redesign:
  * spec JSON round trip, including scenario/privacy fields;
  * save -> resume bit-exactness vs an uninterrupted run;
  * the retired wire layout is refused on resume, and ``repro.core``
    imports nothing from ``repro.federated``;
  * registry lookup + the CLI's --list-models / --dump-spec paths.
"""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CholeskyGaussian,
    ConditionalGaussian,
    DiagGaussian,
    SFVIProblem,
    StructuredModel,
)
from repro.core.families import LowRankGaussian
from repro.federated import (
    AsyncConfig,
    Experiment,
    ExperimentSpec,
    FamilySpec,
    ModelSpec,
    OptimizerSpec,
    RuntimeSpec,
    Scenario,
    Server,
    build,
)
from repro.federated import run as cli
from repro.models.paper.registry import get_model, list_models, model_names
from repro.optim.sgd import sgd

PAPER_MODELS = ["toy", "hier_bnn", "fedpop_bnn", "prodlda", "glmm", "multinomial"]


def _full_spec(**over):
    """A spec exercising every field, privacy, scenario and families."""
    base = dict(
        model=ModelSpec("toy", {"num_obs": 8},
                        global_family=FamilySpec("lowrank", {"rank": 1}),
                        local_family=FamilySpec("conditional",
                                                {"use_coupling": False})),
        scenario=Scenario(
            algorithm="sfvi_avg", participation=0.75, dropout=0.1,
            compression="int8", dp_noise=0.6, dp_clip=0.8, dp_delta=1e-6,
            aggregator="trimmed", trim_frac=0.2,
        ),
        num_silos=4, rounds=6, local_steps=2,
        server_opt=OptimizerSpec("adam", 3e-2, {"b1": 0.85}),
        local_opt=OptimizerSpec("sgd", 1e-2),
        eta_mode="param", eval_every=2, seed=5, data_seed=2,
    )
    base.update(over)
    return ExperimentSpec(**base)


class TestSpecRoundTrip:
    def test_dict_round_trip_includes_privacy_and_scenario(self):
        s = _full_spec()
        d = s.to_dict()
        assert d["scenario"]["dp_noise"] == 0.6
        assert d["scenario"]["participation"] == 0.75
        assert d["local_opt"]["name"] == "sgd"
        assert ExperimentSpec.from_dict(d) == s

    def test_json_round_trip(self):
        s = _full_spec()
        assert ExperimentSpec.from_json(s.to_json()) == s
        # And through an actual serialize -> parse cycle of the dict form.
        assert ExperimentSpec.from_dict(json.loads(json.dumps(s.to_dict()))) == s

    def test_defaults_round_trip(self):
        s = ExperimentSpec(model=ModelSpec("toy"))
        assert ExperimentSpec.from_json(s.to_json()) == s
        assert s.local_opt is None
        assert s.algorithm == s.scenario.algorithm

    def test_file_round_trip(self, tmp_path):
        s = _full_spec()
        path = str(tmp_path / "spec.json")
        s.save(path)
        assert ExperimentSpec.load(path) == s

    def test_unknown_optimizer_rejected_at_build(self):
        with pytest.raises(ValueError, match="unknown optimizer"):
            OptimizerSpec("lbfgs").build()

    def test_family_spec_round_trips_inside_model_spec(self):
        s = _full_spec()
        d = json.loads(s.to_json())
        assert d["model"]["global_family"] == {"name": "lowrank",
                                               "kwargs": {"rank": 1}}
        assert ExperimentSpec.from_dict(d) == s
        # Default (no override) serializes as null and round-trips too.
        bare = ExperimentSpec(model=ModelSpec("toy"))
        assert bare.model.global_family is None
        assert ExperimentSpec.from_json(bare.to_json()) == bare


class TestFamilyOverrides:
    def _spec(self, gfam, scenario=None, rounds=4):
        return ExperimentSpec(
            model=ModelSpec("toy", {"num_obs": 6}, global_family=gfam),
            scenario=scenario or Scenario(algorithm="sfvi_avg"),
            num_silos=4, rounds=rounds, local_steps=2,
            server_opt=OptimizerSpec("adam", 2e-2), seed=3,
        )

    def test_build_swaps_the_global_family(self):
        exp = build(self._spec(FamilySpec("cholesky")))
        fam = exp.server.problem.global_family
        # repro-lint: allow[R6] — registry-construction test: asserting WHICH class was built is the point
        assert isinstance(fam, CholeskyGaussian)
        assert fam.dim == exp.server.problem.model.global_dim
        assert "L_packed" in exp.server.eta_G
        exp.run(2)
        assert np.isfinite(exp.history["elbo"][-1])

    def test_lowrank_family_runs_end_to_end(self):
        exp = build(self._spec(FamilySpec("lowrank", {"rank": 1})))
        # repro-lint: allow[R6] — registry-construction test: asserting WHICH class was built is the point
        assert isinstance(exp.server.problem.global_family, LowRankGaussian)
        h = exp.run()
        assert np.all(np.isfinite(np.asarray(h["elbo"])))

    def test_default_spec_keeps_model_family(self):
        exp = build(self._spec(None))
        # repro-lint: allow[R6] — registry-construction test: asserting WHICH class was built is the point
        assert isinstance(exp.server.problem.global_family, DiagGaussian)

    def test_nondefault_family_resumes_bit_exact_under_dp_int8_async(
            self, tmp_path):
        """Acceptance: a spec carrying a non-default FamilySpec resumes
        bit-exactly mid-run with DP + int8 + async all live — the same
        guarantee the default family has."""
        sc = Scenario(algorithm="sfvi_avg", compression="int8",
                      dp_noise=0.5, dp_clip=0.9,
                      async_cfg=AsyncConfig(buffer_size=2,
                                            latency="lognormal"))
        spec = self._spec(FamilySpec("cholesky"), scenario=sc, rounds=6)
        full = build(spec)
        full.run()

        part = build(spec)
        part.run(3)
        part.save(str(tmp_path))
        resumed = Experiment.resume(str(tmp_path))
        # repro-lint: allow[R6] — resume-fidelity test: asserts the concrete family class survives the round trip
        assert isinstance(resumed.server.problem.global_family,
                          CholeskyGaussian)
        resumed.run()
        _assert_trees_bit_equal(_run_state(full), _run_state(resumed))
        assert full.comm.state_dict() == resumed.comm.state_dict()

    def test_unknown_family_raises_with_names(self):
        with pytest.raises(KeyError, match="registered families"):
            build(self._spec(FamilySpec("gumbel")))

    def test_underivable_family_kwargs_raise_cleanly(self):
        """batched_diag needs a 'batch' the model cannot supply — the
        error must name the missing kwarg, not die in __init__."""
        with pytest.raises(ValueError, match="batch"):
            build(self._spec(FamilySpec("batched_diag")))

    def test_legacy_wire_run_resumes_on_legacy_wire(self, tmp_path):
        """The wire layout is recorded in the checkpoint meta: a run
        built on the fused wire under DP+int8 must resume on the SAME
        layout, bit-exactly."""
        sc = Scenario(algorithm="sfvi_avg", compression="int8",
                      dp_noise=0.5, dp_clip=0.9)
        spec = dataclasses.replace(self._spec(None, scenario=sc, rounds=4),
                                   runtime=RuntimeSpec(wire="fused"))
        full = build(spec)
        full.run()

        part = build(spec)
        part.run(2)
        part.save(str(tmp_path))
        with open(Experiment._meta_path(str(tmp_path), 2)) as f:
            assert json.load(f)["wire"] == "fused"
        resumed = Experiment.resume(str(tmp_path))
        assert resumed.server.wire == "fused"
        resumed.run()
        _assert_trees_bit_equal(_run_state(full), _run_state(resumed))


class TestRegistry:
    def test_all_paper_models_registered(self):
        names = model_names()
        for name in PAPER_MODELS:
            assert name in names, f"{name} missing from registry"

    def test_descriptions_nonempty(self):
        for name, desc in list_models():
            assert desc.strip(), f"{name} has no description"

    def test_unknown_model_raises_with_available_names(self):
        with pytest.raises(KeyError, match="registered models"):
            get_model("nope")

    def test_toy_bundle_stages_equal_silos(self):
        bundle = get_model("toy").build(0, 3, num_obs=5)
        assert len(bundle.datas) == 3
        assert all(d["y"].shape == (5,) for d in bundle.datas)
        assert bundle.num_obs == [5, 5, 5]
        assert "posterior_mu" in bundle.extras

    def test_hetero_mn_stages_unequal_weighted_silos(self):
        """The heterogeneity generator: Dirichlet label skew, TRUE
        unequal N_j in num_obs, equal padded shapes + row weights."""
        bundle = get_model("hetero_mn").build(
            0, 5, n_total=120, in_dim=16, alpha=0.3)
        assert len(set(bundle.num_obs)) > 1  # genuinely unequal N_j
        assert sum(bundle.num_obs) == 120
        shapes = {d["x"].shape for d in bundle.datas}
        assert len(shapes) == 1  # padded to a common stackable shape
        for d, n in zip(bundle.datas, bundle.num_obs, strict=True):
            w = np.asarray(d["w"])
            assert w.sum() == n  # weights mark exactly the real rows
        # Padded rows contribute nothing to the likelihood: doubling a
        # padded row's features must not change log_local.
        prob = bundle.problem
        d0 = bundle.datas[int(np.argmin(bundle.num_obs))]
        z = jnp.zeros((prob.model.global_dim,))
        poked = dict(d0, x=d0["x"].at[-1].mul(2.0))
        assert float(prob.model.log_local({}, z, None, d0)) == pytest.approx(
            float(prob.model.log_local({}, z, None, poked)))


class TestCLI:
    def test_list_models_exits_zero(self, capsys):
        assert cli.main(["--list-models"]) == 0
        out = capsys.readouterr().out
        for name in PAPER_MODELS:
            assert name in out

    def test_dump_spec_round_trips_through_from_json(self, capsys):
        rc = cli.main(["--model", "toy", "--algo", "sfvi", "--silos", "3",
                       "--rounds", "2", "--dp-noise", "0.5", "--dump-spec"])
        assert rc == 0
        spec = ExperimentSpec.from_json(capsys.readouterr().out)
        assert spec.model.name == "toy"
        assert spec.algorithm == "sfvi"
        assert spec.num_silos == 3
        assert spec.scenario.dp_noise == 0.5
        assert ExperimentSpec.from_json(spec.to_json()) == spec

    def test_dump_spec_requires_single_algo(self, capsys):
        assert cli.main(["--model", "toy", "--dump-spec"]) == 2

    def test_spec_run_stages_with_data_seed(self, tmp_path, capsys):
        """The CLI must stage data with data_seed (api.build's rule) —
        staging with the run seed would hand --spec runs a different
        dataset than --resume/build(spec) rebuild."""
        spec = ExperimentSpec(
            model=ModelSpec("toy", {"num_obs": 6}),
            scenario=Scenario(algorithm="sfvi"),
            num_silos=3, rounds=1, local_steps=1, seed=1, data_seed=9)
        path = str(tmp_path / "spec.json")
        spec.save(path)
        assert cli.main(["--spec", path]) == 0
        out = capsys.readouterr().out
        ref = build(spec)
        ref.run()
        expected = ref.evaluate()["abs_error_vs_exact"]
        assert f"abs_error_vs_exact: {expected:.3f}" in out


def _run_state(exp):
    return {k: exp.server.state[k] for k in ("theta", "eta_G", "eta_L")}


def _assert_trees_bit_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb, strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestSaveResume:
    def _spec(self):
        # DP + compression + partial participation: the states the resume
        # guarantee must thread (accountant ledger, scheduler stream,
        # round keys) are all live.
        return ExperimentSpec(
            model=ModelSpec("toy", {"num_obs": 6}),
            scenario=Scenario(algorithm="sfvi_avg", participation=0.75,
                              compression="int8", dp_noise=0.5, dp_clip=0.9),
            num_silos=4, rounds=6, local_steps=2,
            server_opt=OptimizerSpec("adam", 2e-2), seed=3,
        )

    def test_resume_is_bit_exact(self, tmp_path):
        spec = self._spec()
        full = build(spec)
        full.run()  # uninterrupted: all 6 rounds

        part = build(spec)
        part.run(3)
        part.save(str(tmp_path))
        resumed = Experiment.resume(str(tmp_path))
        assert resumed.round == 3
        resumed.run()  # the remaining 3 rounds

        _assert_trees_bit_equal(_run_state(full), _run_state(resumed))
        # Accountant composed the same ledger -> identical epsilon.
        eps_full = full.accountant.epsilon(spec.scenario.dp_delta)
        eps_res = resumed.accountant.epsilon(spec.scenario.dp_delta)
        assert eps_full == eps_res
        # Communication counters carried across the boundary too.
        assert full.comm.state_dict() == resumed.comm.state_dict()

    def test_resume_restores_round_and_counters(self, tmp_path):
        spec = self._spec()
        exp = build(spec)
        exp.run(2)
        exp.save(str(tmp_path))
        resumed = Experiment.resume(str(tmp_path))
        assert resumed.round == 2
        assert resumed.remaining_rounds == spec.rounds - 2
        assert resumed.comm.state_dict() == exp.comm.state_dict()
        assert resumed.accountant.steps == exp.accountant.steps

    def test_midrun_callback_save_resumes_bit_exact(self, tmp_path):
        """Checkpointing FROM a run callback (the CLI's --ckpt-every
        path) records the in-flight absolute round, so the resume
        continues bit-exactly from mid-chunk."""
        spec = self._spec()
        full = build(spec)
        full.run()

        part = build(spec)

        def save_at_4(r, metrics):
            if r + 1 == 4:
                part.save(str(tmp_path))

        part.run(callback=save_at_4)
        resumed = Experiment.resume(str(tmp_path))
        assert resumed.round == 4
        resumed.run()
        _assert_trees_bit_equal(_run_state(full), _run_state(resumed))

    def test_data_seed_decouples_staging_from_run_seed(self):
        """Same data_seed + different run seeds -> identical silo data."""
        import dataclasses

        a = build(dataclasses.replace(self._spec(), seed=1, data_seed=9))
        b = build(dataclasses.replace(self._spec(), seed=2, data_seed=9))
        _assert_trees_bit_equal(a.bundle.datas, b.bundle.datas)

    def test_resume_without_checkpoint_raises(self, tmp_path):
        spec = self._spec()
        spec.save(str(tmp_path / "spec.json"))
        with pytest.raises(FileNotFoundError):
            Experiment.resume(str(tmp_path))


    def test_resume_refuses_a_legacy_wire_checkpoint(self, tmp_path):
        """A checkpoint whose meta records the retired per-leaf layout
        comes from outside this program: resume names the two layouts
        it runs instead of building a Server for it."""
        exp = build(self._spec())
        exp.run(1)
        exp.save(str(tmp_path))
        meta_path = Experiment._meta_path(str(tmp_path), 1)
        with open(meta_path) as f:
            meta = json.load(f)
        meta["wire"] = "legacy"
        with open(meta_path, "w") as f:
            json.dump(meta, f)
        with pytest.raises(ValueError, match=r"'legacy' \(flat/fused\)"):
            Experiment.resume(str(tmp_path))


def test_core_imports_nothing_from_federated():
    """The library layer stands below the runtime: importing repro.core
    loads no repro.federated module."""
    code = ("import sys, repro.core; "
            "print([m for m in sys.modules "
            "if m.startswith('repro.federated')])")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(os.path.dirname(
                       os.path.abspath(__file__))), "src"),
                    os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, check=True)
    assert out.stdout.strip() == "[]", out.stdout


def _hier_problem(dG=3, dL=2):
    model = StructuredModel(
        global_dim=dG, local_dim=dL,
        log_prior_global=lambda th, zg: -0.5 * jnp.sum((zg - th["m"]) ** 2),
        log_local=lambda th, zg, zl, d: (
            -0.5 * jnp.sum((zl - jnp.mean(zg)) ** 2)
            - 0.5 * jnp.sum((d["y"] - zl[None, :]) ** 2)
        ),
    )
    return SFVIProblem(model, DiagGaussian(dG), ConditionalGaussian(dL, dG))


def _datas(key, J, n, d):
    return [{"y": jax.random.normal(jax.random.fold_in(key, j), (n, d))}
            for j in range(J)]


class TestAdapterEquivalence:
    """What the retired eager SFVI-Avg adapter guaranteed, on Server."""

    def test_avg_adapter_rejects_family_without_moments(self):
        """A global family with no to_moments has no barycenter: SFVI-Avg
        fails loudly instead of silently averaging parameters."""
        class NoMoments(DiagGaussian):
            has_moments = False

        prob = _hier_problem()
        prob = SFVIProblem(prob.model, NoMoments(3), prob.local_family)
        datas = _datas(jax.random.PRNGKey(2), 2, 4, 2)
        with pytest.raises(ValueError, match="to_moments"):
            srv = Server(prob, datas, {"m": jnp.asarray(0.1)},
                         NoMoments(3).init(jax.random.PRNGKey(1)),
                         num_obs=[4, 4], server_opt=sgd(0.05),
                         local_opt=sgd(0.05), strategy="sfvi_avg")
            srv.run(1, local_steps=2)
