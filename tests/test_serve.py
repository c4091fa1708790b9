"""Posterior serving: q(Z_L|Z_G) queries from a federated checkpoint.

Covers the serving acceptance surface: checkpoint restore, joint
sampling through the problem's variational family, batched requests
grouped by silo (slices of one vectorized draw), determinism across
replicas, the predict hook, and the CLI endpoint.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.federated import serve
from repro.federated.api import ExperimentSpec, ModelSpec, build
from repro.federated.population import PopulationSpec
from repro.federated.scheduler import Scenario
from repro.federated.serve import Posterior, Query

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _toy_ckpt(tmp_path, **over):
    base = dict(model=ModelSpec("toy", {"num_obs": 16}),
                scenario=Scenario(algorithm="sfvi"),
                num_silos=3, rounds=2, seed=0)
    base.update(over)
    exp = build(ExperimentSpec(**base))
    exp.run()
    exp.save(str(tmp_path))
    return exp


class TestQuery:
    def test_validation(self):
        with pytest.raises(ValueError, match="kind"):
            Query("flarb")
        with pytest.raises(ValueError, match="silo"):
            Query("sample")
        with pytest.raises(ValueError, match="n must be"):
            Query("sample", silo=0, n=0)
        with pytest.raises(ValueError, match="inputs"):
            Query("predict", silo=0)

    def test_from_dict(self):
        q = Query.from_dict({"kind": "sample", "silo": 2, "n": 3})
        assert (q.kind, q.silo, q.n) == ("sample", 2, 3)


class TestPosterior:
    def test_joint_sampling_shapes_and_determinism(self, tmp_path):
        _toy_ckpt(tmp_path)
        post = Posterior.from_checkpoint(str(tmp_path))
        assert post.num_silos == 3 and post.round == 2
        s = post.sample(1, n=4, seed=9)
        assert np.asarray(s["z_G"]).shape == (4, 1)
        assert np.asarray(s["z_L"]).shape == (4, 1)
        # Same checkpoint + same seed on a second replica -> identical.
        replica = Posterior.from_checkpoint(str(tmp_path))
        s2 = replica.sample(1, n=4, seed=9)
        np.testing.assert_array_equal(np.asarray(s["z_G"]),
                                      np.asarray(s2["z_G"]))
        np.testing.assert_array_equal(np.asarray(s["z_L"]),
                                      np.asarray(s2["z_L"]))
        # Different silos draw from different streams.
        assert not np.array_equal(np.asarray(s["z_L"]),
                                  np.asarray(replica.sample(2, n=4,
                                                            seed=9)["z_L"]))

    def test_global_sample(self, tmp_path):
        _toy_ckpt(tmp_path)
        post = Posterior.from_checkpoint(str(tmp_path))
        z = post.global_sample(6, seed=1)
        assert np.asarray(z).shape == (6, 1)

    def test_silo_index_validated(self, tmp_path):
        _toy_ckpt(tmp_path)
        post = Posterior.from_checkpoint(str(tmp_path))
        with pytest.raises(IndexError, match="out of range"):
            post.sample(3)

    def test_samples_match_the_variational_family(self, tmp_path):
        """The serving path routes through SFVIProblem.sample_posterior:
        a direct (eager) call with the restored state + the same key
        gives the same draws — the endpoint adds batching and jit, not
        math (jit fusion may differ by float32 ULPs, hence allclose)."""
        _toy_ckpt(tmp_path)
        post = Posterior.from_checkpoint(str(tmp_path))
        got = post.sample(0, n=3, seed=5)
        prob = post.problem
        z_G, z_L = prob.sample_posterior(
            post.server.state["eta_G"], post.eta_row(0),
            post._key(5, 0), num_samples=3)
        np.testing.assert_allclose(np.asarray(got["z_G"]),
                                   np.asarray(z_G), rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(got["z_L"]),
                                   np.asarray(z_L), rtol=1e-6, atol=1e-7)

    def test_batched_queries_are_slices_of_one_grouped_draw(self, tmp_path):
        _toy_ckpt(tmp_path)
        post = Posterior.from_checkpoint(str(tmp_path))
        qs = [Query("sample", silo=1, n=2), Query("global_sample", n=2),
              Query("sample", silo=1, n=1), Query("sample", silo=0, n=1)]
        ans = post.answer_batch(qs, seed=0)
        grouped = post.sample(1, n=3, seed=0)
        np.testing.assert_array_equal(np.asarray(ans[0]["z_G"]),
                                      np.asarray(grouped["z_G"])[:2])
        np.testing.assert_array_equal(np.asarray(ans[2]["z_G"]),
                                      np.asarray(grouped["z_G"])[2:3])
        assert ans[1]["z_L"] is None
        assert np.asarray(ans[3]["z_G"]).shape == (1, 1)

    def test_serves_population_checkpoint_mid_roster(self, tmp_path):
        """A churn checkpoint restores with its live J; the endpoint
        serves exactly the joined silos."""
        exp = _toy_ckpt(
            tmp_path, num_silos=6, rounds=4,
            population=PopulationSpec(initial=2, arrival_rate=0.6,
                                      departure_rate=0.2, return_rate=0.5,
                                      seed=3))
        post = Posterior.from_checkpoint(str(tmp_path))
        assert post.num_silos == exp.population.state.joined
        s = post.sample(post.num_silos - 1, n=2)
        assert np.asarray(s["z_L"]).shape == (2, 1)
        with pytest.raises(IndexError):
            post.sample(post.num_silos)

    def test_predict_requires_model_hook(self, tmp_path):
        _toy_ckpt(tmp_path)
        post = Posterior.from_checkpoint(str(tmp_path))
        with pytest.raises(ValueError, match="predict hook"):
            post.predict(0, np.zeros((2, 1), np.float32))

    def test_predict_posterior_average(self, tmp_path):
        _toy_ckpt(tmp_path,
                  model=ModelSpec("hier_bnn",
                                  {"in_dim": 16, "hidden": 4,
                                   "train_per_silo": 16,
                                   "test_per_silo": 4}),
                  num_silos=2)
        post = Posterior.from_checkpoint(str(tmp_path))
        x = np.random.default_rng(0).normal(size=(5, 16)).astype(np.float32)
        out = post.predict(0, x, n=4, seed=2)
        assert np.asarray(out).shape == (5, 10)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(post.predict(0, x, n=4, seed=2)))


_BNN = ModelSpec("hier_bnn", {"in_dim": 16, "hidden": 4,
                              "train_per_silo": 16, "test_per_silo": 4})


@pytest.fixture(scope="module")
def bnn_post(tmp_path_factory):
    """A small hier_bnn posterior (local rows of 104 values, a predict
    hook) shared by the bucket and key tests."""
    d = tmp_path_factory.mktemp("bnn")
    _toy_ckpt(d, model=_BNN, num_silos=3)
    return Posterior.from_checkpoint(str(d))


def _split(total):
    """Query sizes from {64, 16, 4, 1}, largest first, summing to total."""
    ns = []
    for n in (64, 16, 4, 1):
        ns += [n] * ((total - sum(ns)) // n)
    return ns


def _unbucketed(post, kind, silo, total, seed):
    """A group's draw as the endpoint made it before row buckets: one
    jitted ``num_samples = total`` call keyed by the eager ``_key``,
    handed the silo's ``eta_row``."""
    prob, eta_G = post.problem, post.server.state["eta_G"]
    if kind == "global_sample":
        fn = jax.jit(lambda g, k: prob.sample_posterior(
            g, None, k, num_samples=total)[0])
        return {"z_G": fn(eta_G, post._key(seed, -1)), "z_L": None}
    fn = jax.jit(lambda g, l, k: prob.sample_posterior(
        g, l, k, num_samples=total))
    z_G, z_L = fn(eta_G, post.eta_row(silo), post._key(seed, silo))
    return {"z_G": z_G, "z_L": z_L}


class TestRowBuckets:
    @pytest.mark.parametrize("kind", ["sample", "global_sample"])
    @pytest.mark.parametrize("total", [1, 3, 5, 64, 85, 129, 192, 257])
    def test_group_answers_are_slices_of_the_unbucketed_draw(
            self, bnn_post, total, kind):
        silo = None if kind == "global_sample" else 1
        ns = _split(total)
        # A second group in the call must not disturb the first.
        qs = [Query(kind, silo=silo, n=n) for n in ns]
        qs.insert(1, Query("sample", silo=2, n=3))
        ans = bnn_post.answer_batch(qs, seed=11)
        del ans[1]
        want = _unbucketed(bnn_post, kind, silo, total, 11)
        off = 0
        for n, a in zip(ns, ans):
            for k, v in want.items():
                if v is None:
                    assert a[k] is None
                else:
                    np.testing.assert_array_equal(
                        np.asarray(a[k]), np.asarray(v)[off:off + n])
            off += n

    @pytest.mark.parametrize("kind", ["sample", "global_sample"])
    def test_group_totals_compile_one_sampler_per_power_of_two(
            self, bnn_post, kind):
        post = Posterior(bnn_post.experiment)
        silo = None if kind == "global_sample" else 0
        for total in range(1, 301):
            ans = post.answer_batch(
                [Query(kind, silo=silo, n=n) for n in _split(total)])
            assert sum(np.asarray(a["z_G"]).shape[0] for a in ans) == total
        rows = sorted(k[1] for k in post._compiled if k[0] == kind)
        assert rows == [2 ** i for i in range(10)]

    def test_public_sample_is_the_prefix_of_its_bucket(self, bnn_post):
        got = bnn_post.sample(2, n=5, seed=4)
        want = _unbucketed(bnn_post, "sample", 2, 5, 4)
        for k in ("z_G", "z_L"):
            assert np.asarray(got[k]).shape[0] == 5
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(want[k]))


class TestServingKey:
    @pytest.mark.parametrize("silo", [-1, 0, "last"])
    @pytest.mark.parametrize("seed", [0, 1, 2 ** 31 - 0x53E7 - 1, 2 ** 31,
                                      -1])
    def test_in_program_key_equals_the_eager_key(self, bnn_post, seed,
                                                 silo):
        post = bnn_post
        silo = post.num_silos - 1 if silo == "last" else silo
        key = jax.jit(serve._stream_key)(serve._stream(seed, silo))
        np.testing.assert_array_equal(np.asarray(key),
                                      np.asarray(post._key(seed, silo)))
        # ... and the served draws are made with it.
        if silo == -1:
            got = {"z_G": post.global_sample(4, seed=seed)}
        else:
            got = post.sample(silo, n=4, seed=seed)
        want = _unbucketed(post, "global_sample" if silo == -1 else
                           "sample", silo, 4, seed)
        for k, v in got.items():
            np.testing.assert_array_equal(np.asarray(v),
                                          np.asarray(want[k]))

    @pytest.mark.parametrize("seed", [2 ** 63 - 0x53E7,
                                      -2 ** 63 - 0x53E7 - 1])
    def test_seeds_the_eager_key_rejects_stay_rejected(self, bnn_post,
                                                       seed):
        with pytest.raises(OverflowError):
            bnn_post._key(seed, 0)
        with pytest.raises(OverflowError):
            bnn_post.answer_batch([Query("sample", silo=0)], seed=seed)


@pytest.mark.parametrize("n", [1, 4, 16, 64])
def test_predict_answers_unchanged(bnn_post, n):
    """predict against its program as it was: the row and the eager key
    handed in, a mean over exactly n draws."""
    post = bnn_post
    prob, st = post.problem, post.server.state
    predict = prob.model.predict

    def run(theta, eta_G, eta_L, x, k):
        z_G, z_L = prob.sample_posterior(eta_G, eta_L, k, num_samples=n)
        out = jax.vmap(lambda zg, zl: predict(theta, zg, zl, x))(z_G, z_L)
        return jnp.mean(out, axis=0)

    x = np.random.default_rng(n).normal(size=(5, 16)).astype(np.float32)
    want = jax.jit(run)(st["theta"], st["eta_G"], post.eta_row(2),
                        jnp.asarray(x), post._key(3, 2))
    np.testing.assert_array_equal(np.asarray(post.predict(2, x, n=n, seed=3)),
                                  np.asarray(want))
    ans = post.answer_batch([Query("predict", silo=2, n=n, x=x)], seed=3)
    np.testing.assert_array_equal(np.asarray(ans[0]), np.asarray(want))


class TestCLI:
    def test_cli_answers_batched_queries(self, tmp_path):
        _toy_ckpt(tmp_path)
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
        out = subprocess.run(
            [sys.executable, "-m", "repro.federated.serve",
             "--ckpt-dir", str(tmp_path), "--queries",
             json.dumps([{"kind": "sample", "silo": 0, "n": 2},
                         {"kind": "global_sample", "n": 1}])],
            capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        payload = json.loads(out.stdout)
        assert payload["num_silos"] == 3 and payload["round"] == 2
        assert len(payload["answers"]) == 2
        assert np.asarray(payload["answers"][0]["z_G"]).shape == (2, 1)
        assert payload["answers"][1]["z_L"] is None
