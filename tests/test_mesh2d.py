"""Tests for the 2-D (silo x model) mesh and the MeshSpec/RuntimeSpec API.

Covers the acceptance surface of the mesh redesign:
  * MeshSpec / RuntimeSpec JSON round trips and the CLI parse form;
  * build_mesh as the single factory (shapes, validation, axis helpers);
  * the wire layout comes from ``spec.runtime`` (``build`` takes no
    ``wire=`` keyword);
  * graph_cache tokens split on mesh shape (the stale-graph regression);
  * (slow, 8 forced host devices) J=64 trajectories: parameter state is
    bit-exact across every silo device count, and the 2-D
    (silo=4, model=2) mesh reproduces the 1-D silo=4 mesh bit-exactly
    INCLUDING the reported ELBO — plus the same equivalence for the
    paper's hier_bnn on a reduced backbone.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.federated import ExperimentSpec, MeshSpec, ModelSpec, RuntimeSpec
from repro.federated import graph_cache
from repro.federated.api import build
from repro.federated.scheduler import Scenario
from repro.launch.mesh import build_mesh, data_axes, data_world, model_world

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _toy_spec(**over):
    base = dict(model=ModelSpec("toy", {"num_obs": 8}),
                scenario=Scenario(algorithm="sfvi"),
                num_silos=4, rounds=2, local_steps=1)
    base.update(over)
    return ExperimentSpec(**base)


class TestMeshSpec:
    def test_json_round_trip(self):
        for spec in (MeshSpec(), MeshSpec(silo=8),
                     MeshSpec(silo=4, model=2, multiprocess=True)):
            d = json.loads(json.dumps(spec.to_dict()))
            assert MeshSpec.from_dict(d) == spec

    def test_parse(self):
        assert MeshSpec.parse("") == MeshSpec()
        assert MeshSpec.parse("silo=8") == MeshSpec(silo=8)
        assert MeshSpec.parse("silo=4,model=2") == MeshSpec(silo=4, model=2)
        assert MeshSpec.parse("silo=2,multiprocess") == MeshSpec(
            silo=2, multiprocess=True)
        assert MeshSpec.parse("multiprocess=true") == MeshSpec(
            multiprocess=True)
        with pytest.raises(ValueError, match="unknown mesh axis"):
            MeshSpec.parse("rows=2")

    def test_validation(self):
        with pytest.raises(ValueError):
            MeshSpec(model=0)
        with pytest.raises(ValueError):
            MeshSpec(silo=0)

    def test_runtime_spec_rides_the_experiment_spec(self):
        s = _toy_spec(runtime=RuntimeSpec(
            wire="fused", mesh=MeshSpec(silo=2, model=1), sanitize=True))
        assert ExperimentSpec.from_json(s.to_json()) == s
        d = s.to_dict()
        assert d["runtime"]["mesh"]["silo"] == 2
        assert d["runtime"]["wire"] == "fused"
        # Absent runtime node (old spec.json files) -> defaults.
        d.pop("runtime")
        old = ExperimentSpec.from_dict(d)
        assert old.runtime == RuntimeSpec()

    def test_runtime_spec_rejects_unknown_wire(self):
        with pytest.raises(ValueError, match="wire layout"):
            RuntimeSpec(wire="nope")


class TestBuildMesh:
    def test_single_factory_shapes(self):
        m = build_mesh(MeshSpec(), num_silos=4)
        assert m.axis_names == ("silo",)
        assert m.shape["silo"] >= 1
        assert data_axes(m) == ("silo",)
        assert data_world(m) == m.shape["silo"]
        assert model_world(m) == 1

    def test_pinned_silo_axis_validates_device_budget(self):
        import jax
        have = len(jax.local_devices())
        with pytest.raises(ValueError, match="devices"):
            build_mesh(MeshSpec(silo=have + 1))

    def test_model_axis_needs_devices(self):
        import jax
        have = len(jax.local_devices())
        with pytest.raises(ValueError, match="devices"):
            build_mesh(MeshSpec(model=have + 1))

    def test_back_compat_wrapper(self):
        from repro.launch.mesh import make_silo_mesh
        assert make_silo_mesh(4).axis_names == ("silo",)


class TestWireKwargDeprecation:
    """The layout rides the spec; the old ``wire=`` keyword is gone."""

    def test_spec_runtime_wire_is_the_default(self):
        exp = build(_toy_spec(runtime=RuntimeSpec(wire="fused")))
        assert exp.server.wire == "fused"


class TestGraphCacheToken:
    def test_token_splits_on_mesh_shape(self):
        spec_json = _toy_spec().to_json(indent=0)
        t1 = graph_cache.build_token(spec_json, "flat", 4,
                                     mesh_shape=(("silo", 4),))
        t2 = graph_cache.build_token(spec_json, "flat", 4,
                                     mesh_shape=(("model", 2), ("silo", 2)))
        t3 = graph_cache.build_token(spec_json, "flat", 4,
                                     mesh_shape=(("silo", 8),))
        assert len({t1, t2, t3}) == 3
        assert t1 == graph_cache.build_token(spec_json, "flat", 4,
                                             mesh_shape=(("silo", 4),))


# ---------------------------------------------------------------------------
# 2-D mesh trajectory equivalence (8 forced host devices, subprocess)
# ---------------------------------------------------------------------------

_MESH2D_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
    import json
    import tempfile

    import jax
    import numpy as np
    from repro.federated import (Experiment, ExperimentSpec, MeshSpec,
                                 ModelSpec, RuntimeSpec, Scenario, build)

    assert jax.device_count() == 8

    def leaves(exp):
        st = exp.server.state
        keys = ("theta", "eta_G", "eta_L", "opt_server", "opt_local")
        return [np.asarray(x) for k in keys
                for x in jax.tree_util.tree_leaves(st[k])]

    def run(model, kwargs, J, mesh, rounds=3, steps=2):
        spec = ExperimentSpec(
            model=ModelSpec(model, kwargs),
            scenario=Scenario(algorithm="sfvi"),
            num_silos=J, rounds=rounds, local_steps=steps,
            runtime=RuntimeSpec(mesh=mesh))
        exp = build(spec)
        exp.run()
        return exp

    # --- toy, J=64 (divisible by every silo axis below) ------------------
    runs = {name: run("toy", {"num_obs": 8}, 64, mesh) for name, mesh in [
        ("1dev", MeshSpec(silo=1)),
        ("1d4", MeshSpec(silo=4)),
        ("1d8", MeshSpec(silo=8)),
        ("2d42", MeshSpec(silo=4, model=2)),
    ]}
    assert dict(runs["2d42"].server.mesh.shape) == {"silo": 4, "model": 2}
    assert dict(runs["1d8"].server.mesh.shape) == {"silo": 8}

    # Parameter state is bit-exact across EVERY topology (only the
    # reported ELBO scalar may differ across silo device counts — psum
    # association — and it never enters a parameter update).
    ref = leaves(runs["1dev"])
    for name in ("1d4", "1d8", "2d42"):
        got = leaves(runs[name])
        assert len(got) == len(ref), name
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b, err_msg=name)

    # The 2-D mesh reproduces its 1-D silo mesh bit-exactly INCLUDING
    # the reported ELBO: sharding P along the model axis must not move a
    # single bit anywhere.
    np.testing.assert_array_equal(
        np.asarray(runs["1d4"].history["elbo"], np.float64),
        np.asarray(runs["2d42"].history["elbo"], np.float64))
    # And across silo counts the ELBO still agrees to float tolerance.
    np.testing.assert_allclose(
        np.asarray(runs["1dev"].history["elbo"], np.float64),
        np.asarray(runs["1d8"].history["elbo"], np.float64),
        rtol=1e-5)
    print("TOY-OK")

    # --- resume across a topology change ---------------------------------
    # Save 2 rounds on the 1-D (silo=4) mesh, then resume with the mesh
    # changed to (silo=4, model=2) — the same spec.json edit the CLI's
    # ``--resume ... --mesh`` override performs. The checkpoint reshards
    # onto the 2-D mesh and the continued round matches the
    # uninterrupted 2-D run bit for bit.
    spec = ExperimentSpec(
        model=ModelSpec("toy", {"num_obs": 8}),
        scenario=Scenario(algorithm="sfvi"),
        num_silos=64, rounds=3, local_steps=2,
        runtime=RuntimeSpec(mesh=MeshSpec(silo=4)))
    exp = build(spec)
    exp.run(2)
    ckpt = tempfile.mkdtemp()
    exp.save(ckpt)
    sp = os.path.join(ckpt, "spec.json")
    with open(sp) as f:
        sd = json.load(f)
    sd["runtime"]["mesh"]["model"] = 2
    with open(sp, "w") as f:
        json.dump(sd, f)
    res = Experiment.resume(ckpt)
    assert dict(res.server.mesh.shape) == {"silo": 4, "model": 2}
    res.run()
    np.testing.assert_array_equal(
        np.asarray(res.history["elbo"], np.float64)[-1],
        np.asarray(runs["2d42"].history["elbo"], np.float64)[-1])
    for a, b in zip(leaves(res), leaves(runs["2d42"])):
        np.testing.assert_array_equal(a, b)
    print("RESUME-OK")

    # --- hier_bnn on a reduced backbone (acceptance criterion) -----------
    kw = {"hidden": 4, "in_dim": 16, "train_per_silo": 16,
          "test_per_silo": 8}
    b1 = run("hier_bnn", kw, 8, MeshSpec(silo=4), rounds=2)
    b2 = run("hier_bnn", kw, 8, MeshSpec(silo=4, model=2), rounds=2)
    np.testing.assert_array_equal(
        np.asarray(b1.history["elbo"], np.float64),
        np.asarray(b2.history["elbo"], np.float64))
    for a, b in zip(leaves(b1), leaves(b2)):
        np.testing.assert_array_equal(a, b)
    # The wire really is model-sharded: the compiled round gathers over
    # BOTH axes (silo blocks + the tiny model reconstruction gather).
    hlo = b2.server._lower(None, 2).compile().as_text()
    assert hlo.count("all-gather") >= 2, hlo.count("all-gather")
    print("BNN-OK")
""")


@pytest.mark.slow
def test_2d_mesh_matches_1d_and_single_device_trajectories():
    """Tentpole acceptance: on 8 forced host devices, J=64 parameter
    trajectories are bit-exact across 1/4/8-device silo axes and the
    (silo=4, model=2) mesh, and the 2-D mesh matches its 1-D silo mesh
    bit-exactly including the reported ELBO — same again for hier_bnn
    on a reduced backbone."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _MESH2D_SCRIPT],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=1800,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    for marker in ("TOY-OK", "RESUME-OK", "BNN-OK"):
        assert marker in out.stdout, (marker, out.stdout)
