"""chip_smoke.py on the CPU: its phases at a tiny size, and its refusals.

The script itself needs a TPU; these tests steer its phase functions onto
host CPU devices at reduced widths (the script has no option for that),
and check that it fails, printing no result line, where it must.
"""
import os
import shutil
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_TINY = textwrap.dedent("""
    import sys
    import jax
    jax.config.update("jax_num_cpu_devices", 4)
    sys.path.insert(0, {repo!r})
    import chip_smoke as cs

    cs.phase_device("cpu", min_count=4)
    cs.phase_toy(rounds=40, local_steps=25)
    kw = {{"in_dim": 16, "hidden": 8, "train_per_silo": 20,
           "test_per_silo": 6}}
    spec = cs.hier_bnn_spec(model_kwargs=kw, num_silos=4)
    exp, h = cs.phase_train(spec, jax.devices("cpu"))
    gaps = cs.phase_fused(spec, h["elbo"])
    assert gaps["none"] == 0.0, gaps  # interpret mode is bit-exact
    cs.phase_posterior(exp)
    cs.phase_meshes(model_kwargs=kw, num_silos=4)
    print("TINY-OK")
""")


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    return env


def test_phases_run_at_tiny_size_on_cpu():
    out = subprocess.run(
        [sys.executable, "-c", _TINY.format(repo=REPO)],
        capture_output=True, text=True, env=_env(), cwd=REPO, timeout=600)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert "TINY-OK" in out.stdout
    for name in ("toy", "train", "fused-none", "fused-int8", "posterior",
                 "mesh silo=4", "mesh silo=2,model=2"):
        assert f"[{name}]" in out.stdout, name


def test_refuses_without_a_tpu():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, env=_env(), cwd=REPO, timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "need a tpu device" in out.stdout + out.stderr


def test_fails_outside_a_checkout(tmp_path):
    script = tmp_path / "chip_smoke.py"
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), script)
    env = _env()
    env.pop("PYTHONPATH")
    out = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True, env=env, cwd=tmp_path, timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
