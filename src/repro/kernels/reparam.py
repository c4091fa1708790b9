"""Fused Gaussian reparametrization + STL log q as a Pallas kernel.

Every SFVI iteration evaluates, for millions of latent components,

    z      = mu + exp(log_sigma) * eps
    logq_i = -0.5 eps_i^2 - log_sigma_i - 0.5 log 2*pi      (STL form)

Unfused, that is 4 HBM round-trips over (mu, log_sigma, eps) plus a
separate reduction pass. The kernel reads each operand once, emits z, and
reduces the per-element logq terms to ONE (8, 128) partial tile per grid
block in the same pass — the classic fuse-map-with-reduction pattern; the
caller sums the partials (a trivially small array). Latents ride as
lane-dense (rows, 128) tiles with rows a multiple of 8, the TPU's f32
tiling.

This is the SFVI-specific hot-spot kernel: it is memory-bound and sits on
the critical path of every silo's local step (paper Algorithm 1 lines
4-6), between the PRNG and the model forward.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HALF_LOG_2PI = 0.5 * math.log(2.0 * math.pi)
_LANES = 128
_SUBLANES = 8


def _reparam_kernel(mu_ref, ls_ref, eps_ref, z_ref, lq_ref):
    mu = mu_ref[...].astype(jnp.float32)
    ls = ls_ref[...].astype(jnp.float32)
    eps = eps_ref[...].astype(jnp.float32)
    z_ref[...] = (mu + jnp.exp(ls) * eps).astype(z_ref.dtype)
    lq = -0.5 * eps * eps - ls - _HALF_LOG_2PI  # (rows, 128)
    lq_ref[0] = jnp.sum(lq.reshape(-1, _SUBLANES, _LANES), axis=0)


def _reparam_bwd_kernel(ls_ref, eps_ref, dz_ref, dlq_ref, dmu_ref, dls_ref,
                        deps_ref):
    """Fused VJP: one pass over (log_sigma, eps, dz) emits all three grads.

        dmu  = dz
        dls  = dz * exp(ls) * eps - dlq          (entropy term: d(-ls)/dls)
        deps = dz * exp(ls)       - dlq * eps    (d(-eps^2/2)/deps)
    """
    ls = ls_ref[...].astype(jnp.float32)
    eps = eps_ref[...].astype(jnp.float32)
    dz = dz_ref[...].astype(jnp.float32)
    dlq = dlq_ref[0, 0]  # scalar cotangent of logq (SMEM)
    sig = jnp.exp(ls)
    dmu_ref[...] = dz.astype(dmu_ref.dtype)
    dls_ref[...] = (dz * sig * eps - dlq).astype(dls_ref.dtype)
    deps_ref[...] = (dz * sig - dlq * eps).astype(deps_ref.dtype)


def reparam_stl(
    mu: jnp.ndarray,  # (N,) flattened latent vector
    log_sigma: jnp.ndarray,
    eps: jnp.ndarray,
    block: int = 4096,
    interpret: bool = False,
):
    """Fused Gaussian reparametrization + STL log q in one HBM pass.

    Shapes: ``mu``, ``log_sigma``, ``eps`` are (N,) flattened latent
    vectors of matching length; returns ``(z, logq)`` with z (N,) in
    ``mu.dtype`` and logq a f32 scalar (the block partials are reduced
    in f32 regardless of input dtype). ``block`` is the elements per grid
    tile, rounded to whole (8, 128) tiles. Pads internally to a tile
    multiple; the pad contributes exactly 0 to logq (eps=0 and
    log_sigma=−0.5·log 2π padding).
    Differentiable via a fused Pallas backward kernel (custom VJP — the
    STL stop-gradient is structural: logq's pathwise term never
    references mu/log_sigma). Reference implementation:
    ``kernels/ref.py::reparam_stl_ref`` (elementwise logq; sum to match).
    """
    return _reparam_stl_vjp(mu, log_sigma, eps, block, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _reparam_stl_vjp(mu, log_sigma, eps, block, interpret):
    z, lq, _ = _reparam_fwd_impl(mu, log_sigma, eps, block, interpret)
    return z, lq


def _tile_rows(N: int, block: int) -> int:
    """Rows of one (rows, 128) grid tile: ~``block`` elements, a multiple
    of 8, and no more than the padded latent needs."""
    rows = max(block // _LANES // _SUBLANES, 1) * _SUBLANES
    need = -(-max(N, 1) // (_LANES * _SUBLANES)) * _SUBLANES
    return min(rows, need)


def _blocked(x, rows, fill=0.0):
    """(N,) -> ``fill``-padded (n_blocks * rows, 128) lane-dense tiles."""
    (N,) = x.shape
    pad = (-N) % (rows * _LANES)
    if pad:
        x = jnp.pad(x, (0, pad), constant_values=fill)
    return x.reshape(-1, _LANES)


def _tile_spec(rows):
    return pl.BlockSpec((rows, _LANES), lambda i: (i, 0))


def _reparam_fwd_impl(mu, log_sigma, eps, block, interpret):
    (N,) = mu.shape
    rows = _tile_rows(N, block)
    mu2 = _blocked(mu, rows)
    # Pad log_sigma with -½log 2π (and eps with 0): each pad element's
    # logq term is then exactly 0, so the pad never enters the sum.
    ls2 = _blocked(log_sigma, rows, fill=-_HALF_LOG_2PI)
    eps2 = _blocked(eps, rows)
    n_blocks = mu2.shape[0] // rows
    z, lq = pl.pallas_call(
        _reparam_kernel,
        grid=(n_blocks,),
        in_specs=[_tile_spec(rows)] * 3,
        out_specs=[
            _tile_spec(rows),
            pl.BlockSpec((1, _SUBLANES, _LANES), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(mu2.shape, mu.dtype),
            jax.ShapeDtypeStruct((n_blocks, _SUBLANES, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(mu2, ls2, eps2)
    logq = jnp.sum(lq)
    return z.reshape(-1)[:N], logq, (log_sigma, eps, rows, N)


def _reparam_fwd(mu, log_sigma, eps, block, interpret):
    z, lq, res = _reparam_fwd_impl(mu, log_sigma, eps, block, interpret)
    return (z, lq), res


def _reparam_bwd(block_arg, interpret, res, cts):
    log_sigma, eps, rows, N = res
    dz, dlq = cts
    ls2 = _blocked(log_sigma, rows)
    eps2 = _blocked(eps, rows)
    dz2 = _blocked(dz, rows)
    n_blocks = ls2.shape[0] // rows
    dmu, dls, deps = pl.pallas_call(
        _reparam_bwd_kernel,
        grid=(n_blocks,),
        in_specs=[_tile_spec(rows)] * 3
        + [pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[_tile_spec(rows)] * 3,
        out_shape=[
            jax.ShapeDtypeStruct(ls2.shape, log_sigma.dtype),
            jax.ShapeDtypeStruct(ls2.shape, log_sigma.dtype),
            jax.ShapeDtypeStruct(ls2.shape, eps.dtype),
        ],
        interpret=interpret,
    )(ls2, eps2, dz2, jnp.asarray(dlq, jnp.float32).reshape(1, 1))
    unpad = lambda a: a.reshape(-1)[:N]  # noqa: E731
    return unpad(dmu), unpad(dls), unpad(deps)


_reparam_stl_vjp.defvjp(_reparam_fwd, _reparam_bwd)
