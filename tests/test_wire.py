"""Tests for the flat (J, P) wire format of the federated runtime.

Covers:
  * ``TreeSpec`` — the pytree <-> one-f32-vector bijection (structure,
    dtypes, jit-safety, empty-subtree edge cases);
  * flat wire vs the frozen per-leaf oracle (``tests/_legacy_server.py``):
    without DP/compression the packed path is a pure relayout, so
    trajectories must agree BIT FOR BIT;
  * the retired per-leaf layout is refused by Server, RuntimeSpec and
    the CLI;
  * fused vs flat wire equivalence end to end: the fused Pallas kernels
    replay the SAME op sequence and the SAME DP noise stream inside the
    compiled round, so trajectories agree bit for bit without
    compression and under DP — and stay tolerance-close when int8
    requantization + async fractional weights reorder the arithmetic;
  * save -> resume ACROSS a wire-mode change (flat <-> fused);
  * wire accounting: one packed f32 row per silo, one int8 scale per
    SILO (not per leaf);
  * the compiled-graph invariance (subprocess, 4 forced host devices):
    a DP + int8 round lowers to exactly ONE all_gather per wire dtype
    (s8 payload + f32 scale), and an uncompressed round to exactly one
    f32 gather — the §3.2 exchange structure, on BOTH the flat and the
    fused wire.
"""
import dataclasses
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
from repro.core.flatten import TreeSpec
from repro.federated import (
    AsyncConfig,
    Experiment,
    ExperimentSpec,
    FamilySpec,
    Int8Compressor,
    ModelSpec,
    NoCompression,
    OptimizerSpec,
    PrivacyPolicy,
    RuntimeSpec,
    Scenario,
    Server,
    build,
)
from repro.federated import run as cli
from repro.optim.sgd import sgd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestTreeSpec:
    def _tree(self):
        return {
            "a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.asarray(2.5), "d": jnp.ones((4,), jnp.float32)},
        }

    def test_round_trip_preserves_structure_and_values(self):
        tree = self._tree()
        spec = TreeSpec.of(tree)
        vec = spec.pack(tree)
        assert vec.shape == (spec.dim,) == (11,)
        assert vec.dtype == jnp.float32
        back = spec.unpack(vec)
        assert jax.tree_util.tree_structure(back) == \
            jax.tree_util.tree_structure(tree)
        for x, y in zip(jax.tree_util.tree_leaves(tree),
                        jax.tree_util.tree_leaves(back), strict=True):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
            assert x.dtype == y.dtype

    def test_empty_subtree_and_empty_tree(self):
        tree = {"theta": {}, "eta": {"mu": jnp.ones((3,))}}
        spec = TreeSpec.of(tree)
        assert spec.dim == 3
        back = spec.unpack(spec.pack(tree))
        assert back["theta"] == {}
        empty = TreeSpec.of({})
        assert empty.dim == 0
        assert empty.pack({}).shape == (0,)

    def test_jittable_and_static(self):
        tree = self._tree()
        spec = TreeSpec.of(tree)
        assert hash(spec) == hash(TreeSpec.of(self._tree()))
        vec = jax.jit(spec.pack)(tree)
        back = jax.jit(spec.unpack)(vec)
        np.testing.assert_array_equal(np.asarray(back["a"]),
                                      np.asarray(tree["a"]))

    def test_seeded_random_sweep(self):
        rng = np.random.default_rng(0)
        for _ in range(20):
            n = int(rng.integers(1, 5))
            tree = {
                f"k{i}": jnp.asarray(
                    rng.normal(size=tuple(rng.integers(1, 4, size=int(
                        rng.integers(0, 3))))).astype(np.float32))
                for i in range(n)
            }
            spec = TreeSpec.of(tree)
            back = spec.unpack(spec.pack(tree))
            for k in tree:
                np.testing.assert_array_equal(np.asarray(tree[k]),
                                              np.asarray(back[k]))


def _hier_problem(dG=3, dL=2):
    model = StructuredModel(
        global_dim=dG, local_dim=dL,
        log_prior_global=lambda th, zg: -0.5 * jnp.sum((zg - th["m"]) ** 2),
        log_local=lambda th, zg, zl, d: (
            -0.5 * jnp.sum((zl - jnp.mean(zg)) ** 2)
            - 0.5 * jnp.sum((d["y"] - zl[None, :]) ** 2)
        ),
    )
    return SFVIProblem(
        model, DiagGaussian(dG), ConditionalGaussian(dL, dG, use_coupling=False)
    )


def _server(wire, compressor=None, privacy=None, seed=11, cls=Server):
    """A 3-silo hierarchical federation; ``cls=LegacyServer`` builds the
    frozen oracle, whose ``wire="legacy"`` keeps the per-leaf upload."""
    prob = _hier_problem()
    datas = [{"y": jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(9), j), (4, 2))}
        for j in range(3)]
    return cls(
        prob, datas, {"m": jnp.asarray(0.2)},
        prob.global_family.init(jax.random.PRNGKey(1)),
        server_opt=sgd(3e-2), local_opt=sgd(3e-2),
        compressor=compressor, privacy=privacy, wire=wire, seed=seed,
    )


def _flat(tree):
    leaves = [np.ravel(np.asarray(x))
              for x in jax.tree_util.tree_leaves(tree)]
    return np.concatenate(leaves) if leaves else np.zeros((0,), np.float32)


class TestFlatVsLegacy:
    @pytest.mark.parametrize("algorithm", ["sfvi", "sfvi_avg"])
    def test_bit_exact_without_dp_or_compression(self, algorithm):
        """Packing is a relayout: the flat wire and the oracle's per-leaf
        wire must produce the SAME trajectory bit for bit when no
        codec/noise touches the payload (per-coordinate reduction order
        is unchanged)."""
        a, b = _server("flat"), _server("legacy", cls=LegacyServer)
        a.run(3, algorithm=algorithm, local_steps=2)
        b.run(3, algorithm=algorithm, local_steps=2)
        for k in ("theta", "eta_G", "eta_L"):
            np.testing.assert_array_equal(_flat(a.state[k]), _flat(b.state[k]))

    def test_int8_flat_close_to_legacy(self):
        """One scale per silo instead of per leaf changes quantization
        noise, not semantics: trajectories stay close."""
        a = _server("flat", compressor=Int8Compressor())
        b = _server("legacy", compressor=Int8Compressor(), cls=LegacyServer)
        a.run(3, algorithm="sfvi_avg", local_steps=2)
        b.run(3, algorithm="sfvi_avg", local_steps=2)
        np.testing.assert_allclose(_flat(a.eta_G), _flat(b.eta_G),
                                   rtol=0.05, atol=0.05)

    def test_rejects_unknown_wire(self):
        with pytest.raises(ValueError, match="wire layout"):
            _server("pigeon")

    @pytest.mark.parametrize("where", ["server", "runtime_spec", "cli"])
    def test_legacy_layout_is_refused(self, where, capsys):
        """The per-leaf layout is retired: every entry point that takes a
        layout accepts only flat and fused."""
        if where == "cli":
            with pytest.raises(SystemExit):
                cli.build_parser().parse_args(["--wire", "legacy"])
            err = capsys.readouterr().err
            assert "--wire: invalid choice: 'legacy'" in err
            assert "flat" in err and "fused" in err
            return
        with pytest.raises(ValueError, match=r"'legacy' \(flat/fused\)"):
            if where == "server":
                _server("legacy")
            else:
                RuntimeSpec(wire="legacy")


def _on_wire(spec, wire):
    return dataclasses.replace(spec, runtime=RuntimeSpec(wire=wire))


def _toy_spec(scenario, *, gfam=None, rounds=4):
    return ExperimentSpec(
        model=ModelSpec("toy", {"num_obs": 6}, global_family=gfam),
        scenario=scenario, num_silos=4, rounds=rounds, local_steps=2,
        server_opt=OptimizerSpec("adam", 2e-2), seed=3,
    )


class TestFusedVsFlat:
    """The fused Pallas wire against the flat reference, end to end.

    The equivalence contract (docs/federated.md): bit-exact whenever no
    requantization reorders arithmetic — including under DP, because the
    kernel draws the SAME per-silo noise stream in-kernel — and
    tolerance-equal once int8 + async fractional weights are live.
    """

    @pytest.mark.parametrize("algorithm", ["sfvi", "sfvi_avg"])
    def test_bit_exact_without_dp_or_compression(self, algorithm):
        a, b = _server("flat"), _server("fused")
        a.run(3, algorithm=algorithm, local_steps=2)
        b.run(3, algorithm=algorithm, local_steps=2)
        for k in ("theta", "eta_G", "eta_L"):
            np.testing.assert_array_equal(_flat(a.state[k]), _flat(b.state[k]))

    @pytest.mark.parametrize("algorithm", ["sfvi", "sfvi_avg"])
    def test_bit_exact_under_dp(self, algorithm):
        """In-kernel noise is the same stream PrivacyPolicy draws (same
        round key -> same folded per-silo keys -> same normals), and the
        clip/add pipeline is the same op sequence — so even DP
        trajectories agree bit for bit."""
        pol = PrivacyPolicy(clip_norm=0.8, noise_multiplier=0.7)
        a = _server("flat", privacy=pol)
        b = _server("fused", privacy=pol)
        a.run(3, algorithm=algorithm, local_steps=2)
        b.run(3, algorithm=algorithm, local_steps=2)
        for k in ("theta", "eta_G", "eta_L"):
            np.testing.assert_array_equal(_flat(a.state[k]), _flat(b.state[k]))

    def test_dp_int8_async_fractional_weights_close(self):
        """int8 requantization happens at a different point in the fused
        pipeline (one fused pass vs encode-then-decode), so under the
        full stack — DP + int8 + buffered-async fractional weights +
        trimmed aggregation — the contract relaxes to tolerance."""
        sc = Scenario(algorithm="sfvi_avg", compression="int8",
                      dp_noise=0.4, dp_clip=0.9,
                      aggregator="trimmed", trim_frac=0.2,
                      async_cfg=AsyncConfig(buffer_size=2,
                                            latency="lognormal"))
        spec = _toy_spec(sc, rounds=5)
        a, b = build(_on_wire(spec, "flat")), build(_on_wire(spec, "fused"))
        a.run()
        b.run()
        np.testing.assert_allclose(_flat(a.eta_G), _flat(b.eta_G),
                                   rtol=0.05, atol=0.05)
        np.testing.assert_allclose(np.asarray(a.history["elbo"]),
                                   np.asarray(b.history["elbo"]),
                                   rtol=0.05, atol=0.5)

    def test_full_covariance_barycenter_bit_exact(self):
        """sfvi_avg with a CholeskyGaussian global family routes the
        barycenter's matrix sqrt through the fused Newton-Schulz kernel
        — same normalization, same iteration, bit-identical states."""
        spec = _toy_spec(Scenario(algorithm="sfvi_avg"),
                         gfam=FamilySpec("cholesky"), rounds=3)
        a, b = build(_on_wire(spec, "flat")), build(_on_wire(spec, "fused"))
        a.run()
        b.run()
        for k in ("theta", "eta_G", "eta_L"):
            np.testing.assert_array_equal(_flat(a.server.state[k]),
                                          _flat(b.server.state[k]))

    @pytest.mark.parametrize("wires", [("flat", "fused"), ("fused", "flat")])
    def test_resume_across_wire_mode_change(self, tmp_path, wires):
        """A checkpoint taken on one wire continues on the other with no
        trajectory change (no DP/compression -> both wires are the same
        bit-exact program), via a resume spec on the other layout."""
        first, second = wires
        spec = _toy_spec(Scenario(algorithm="sfvi_avg"), rounds=4)
        full = build(_on_wire(spec, first))
        full.run()

        part = build(_on_wire(spec, first))
        part.run(2)
        part.save(str(tmp_path))
        resumed = Experiment.resume(str(tmp_path),
                                    spec=_on_wire(spec, second))
        assert resumed.server.wire == second
        resumed.run()
        for k in ("theta", "eta_G", "eta_L"):
            np.testing.assert_array_equal(
                _flat(full.server.state[k]), _flat(resumed.server.state[k]))

    def test_resume_defaults_to_recorded_wire(self, tmp_path):
        spec = _toy_spec(Scenario(algorithm="sfvi_avg"), rounds=3)
        part = build(_on_wire(spec, "fused"))
        part.run(1)
        part.save(str(tmp_path))
        assert Experiment.resume(str(tmp_path)).server.wire == "fused"


class TestWireAccounting:
    def test_int8_pays_one_scale_per_silo(self):
        for wire in ("flat", "fused"):
            srv = _server(wire, compressor=Int8Compressor())
            P = srv.wire_spec("sfvi").dim
            # payload + ONE scale, however many leaves the upload has
            assert srv.bytes_up_per_silo("sfvi") == P + 4
        n_leaves = len(jax.tree_util.tree_leaves(srv.ship_template("sfvi")))
        assert n_leaves > 1  # the saving is real

    def test_uncompressed_bytes_identical_across_wires(self):
        flat, fused = _server("flat"), _server("fused")
        for algo in ("sfvi", "sfvi_avg"):
            P = flat.wire_spec(algo).dim
            assert flat.bytes_up_per_silo(algo) == \
                fused.bytes_up_per_silo(algo) == \
                NoCompression().wire_bytes(flat.ship_template(algo)) == 4 * P


# ---------------------------------------------------------------------------
# Compiled-graph invariance: one all_gather per wire dtype (subprocess)
# ---------------------------------------------------------------------------

_HLO_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    import re
    import jax, jax.numpy as jnp
    from repro.core import (ConditionalGaussian, DiagGaussian, SFVIProblem,
                            StructuredModel)
    from repro.federated import Int8Compressor, PrivacyPolicy, Server
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
    datas = [{"y": jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(2), j), (4, 2))}
        for j in range(4)]
    pol = PrivacyPolicy(clip_norm=1.0, noise_multiplier=1.0)

    def gathers_by_dtype(hlo):
        # one entry per all-gather instruction: its result element type.
        out = {}
        for m in re.finditer(
                r"= (\\w+)\\[[0-9,]*\\](?:\\{[^}]*\\})? "
                r"all-gather(?:-start)?\\(", hlo):
            out[m.group(1)] = out.get(m.group(1), 0) + 1
        return out

    for wire in ("flat", "fused"):
        for comp, expect in ((Int8Compressor(), {"s8": 1, "f32": 1}),
                             (None, {"f32": 1})):
            for algo, K in (("sfvi", 2), ("sfvi_avg", 3)):
                srv = Server(prob, datas, {"m": jnp.asarray(0.1)},
                             prob.global_family.init(jax.random.PRNGKey(1)),
                             server_opt=adam(1e-2), local_opt=adam(1e-2),
                             compressor=comp, privacy=pol, wire=wire, seed=0)
                assert srv.wire == wire
                fn = srv._get_round(algo, K)
                mask_shape = (K, 4) if algo == "sfvi" else (4,)
                ones = jnp.ones(mask_shape, jnp.float32)
                args = (srv.state, srv.data, jnp.asarray(srv.num_obs),
                        jax.random.PRNGKey(0), ones, ones)
                hlo = fn.lower(*args).compile().as_text()
                got = gathers_by_dtype(hlo)
                assert got == expect, (wire, algo, K, type(comp).__name__,
                                       got, expect)
                print(wire, algo, K, type(comp).__name__, "OK", got)
""")


@pytest.mark.slow
def test_flat_round_compiles_to_one_gather_per_wire_dtype():
    """Flat AND fused wires preserve the §3.2 exchange structure in the
    optimized HLO: a DP + int8 round is exactly one s8 all_gather (the
    payload matrix) plus one f32 all_gather (the per-silo scales), an
    uncompressed DP round exactly one f32 all_gather — independent of
    algorithm and local_steps, on a real 4-device mesh. (The fused
    kernels change what happens per shard, not what crosses the wire.)
    """
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _HLO_SCRIPT],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=1200,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert out.stdout.count("OK") == 8, out.stdout
