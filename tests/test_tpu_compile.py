"""The Pallas kernels compile for a TPU v5e chip (Mosaic, not interpret).

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology, and each case checks that the kernel reached the compiled
program as a ``tpu_custom_call``. Shapes are the real widths: the
hier_bnn η_G wire row (784×64 hidden layer → P = 100,354 floats) at J=16
silos, and the backbone attention/norm/GLA shapes. Configurations that
the compiler refuses (in-kernel DP noise, trimmed mean, J ≥ 64 at this
P, clipping against a reference row) are listed in docs/federated.md.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops, wire

J, P_WIRE = 16, 100_354


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache without that chip, so keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _wire_cases():
    return {
        "upload_plain": (
            lambda x, m, r: wire.fused_upload(
                x, mask=m, reference=r, interpret=False),
            [((J, P_WIRE), jnp.float32), ((J,), jnp.float32),
             ((P_WIRE,), jnp.float32)]),
        "upload_clip_int8": (
            lambda x, m: wire.fused_upload(
                x, mask=m, clip_norm=1.0, quantize=True, interpret=False),
            [((J, P_WIRE), jnp.float32), ((J,), jnp.float32)]),
        "combine_mean": (
            lambda x, w: wire.fused_combine(x, w, interpret=False),
            [((J, P_WIRE), jnp.float32), ((J,), jnp.float32)]),
        "combine_int8": (
            lambda q, w, s: wire.fused_combine(q, w, scales=s,
                                               interpret=False),
            [((J, P_WIRE), jnp.int8), ((J,), jnp.float32),
             ((J,), jnp.float32)]),
        "newton_schulz_d64": (
            lambda m: wire.sqrtm_newton_schulz_fused(
                m, num_iters=4, interpret=False),
            [((64, 64), jnp.float32)]),
        "newton_schulz_d1024": (
            lambda m: wire.sqrtm_newton_schulz_fused(
                m, num_iters=4, interpret=False),
            [((1024, 1024), jnp.float32)]),
    }


def _backbone_cases():
    N = 2 ** 20

    def reparam_grad(mu, ls, eps):
        def loss(mu, ls, eps):
            z, logq = ops.reparam_stl(mu, ls, eps, interpret=False)
            return jnp.sum(z) + logq
        return jax.grad(loss, argnums=(0, 1, 2))(mu, ls, eps)

    return {
        "flash_attention": (
            lambda q, k, v: ops.flash_attention(q, k, v, interpret=False),
            [((1, 2048, 32, 128), jnp.bfloat16),
             ((1, 2048, 8, 128), jnp.bfloat16),
             ((1, 2048, 8, 128), jnp.bfloat16)]),
        "rmsnorm": (
            lambda x, w: ops.rmsnorm(x, w, interpret=False),
            [((4096, 2560), jnp.bfloat16), ((2560,), jnp.bfloat16)]),
        "gla": (
            lambda q, k, v, a: ops.gla(q, k, v, a, interpret=False),
            [((1, 2048, 8, 128), jnp.float32)] * 3
            + [((1, 2048, 8), jnp.float32)]),
        "reparam_stl": (
            lambda mu, ls, eps: ops.reparam_stl(mu, ls, eps,
                                                interpret=False),
            [((N,), jnp.float32)] * 3),
        "reparam_stl_grad": (reparam_grad, [((N,), jnp.float32)] * 3),
    }


CASES = {**_wire_cases(), **_backbone_cases()}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, specs = CASES[name]
    shapes = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
              for s, dt in specs]
    _assert_kernel(_compile(fn, *shapes))
