"""Gated linear attention (Mamba2-SSD / mLSTM) as a Pallas TPU kernel.

The recurrence  S_t = exp(a_t) S_{t-1} + k_t v_t^T ;  y_t = q_t . S_t
is computed chunkwise: the grid is (B, H, n_chunks) with the chunk axis
sequential, and the (dk, dv) f32 state lives in VMEM scratch across chunk
steps — the TPU analogue of the CUDA "chunk-scan" SSD kernel, with the
within-chunk quadratic part expressed as two MXU matmuls:

    y_intra = (q k^T  *  D) v          D_ij = exp(L_i - L_j) for j <= i
    y_inter = (q * exp(L)) S_in
    S_out   = exp(L_C) S_in + (k * exp(L_C - L))^T v

Chunk length defaults to 128 (MXU-aligned); dk/dv are the model's
ssm_state / head_dim (64/64 for zamba2) — padding to the 128 lane width is
the wrapper's job. One kernel instance handles ONE (batch, head) pair per
grid cell, so GQA-style head grouping is not needed (every head owns its
state).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.attention import pl_scratch


def _gla_kernel(q_ref, k_ref, v_ref, a_ref, o_ref, state_ref, *, nc: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (C, dk)
    k = k_ref[0, 0].astype(jnp.float32)  # (C, dk)
    v = v_ref[0, 0].astype(jnp.float32)  # (C, dv)
    a = a_ref[0, 0].astype(jnp.float32)  # (1, C) lane-dense row
    C = q.shape[0]

    tri = (
        jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    )
    # L_i = sum_{r<=i} a_r as a column and as a row: two small matmuls
    # against the lower-triangular ones matrix (Mosaic has no cumsum).
    trif = tri.astype(jnp.float32)
    exact = jax.lax.Precision.HIGHEST
    cum = jax.lax.dot_general(  # (C, 1)
        trif, a, (((1,), (1,)), ((), ())), precision=exact,
        preferred_element_type=jnp.float32)
    cum_row = jax.lax.dot_general(  # (1, C)
        a, trif, (((1,), (1,)), ((), ())), precision=exact,
        preferred_element_type=jnp.float32)
    total = jnp.sum(a, axis=1, keepdims=True)  # (1, 1)
    # Within-chunk decay matrix, masked BEFORE exp (no inf * 0).
    diff = cum - cum_row
    D = jnp.exp(jnp.where(tri, diff, -jnp.inf))

    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * D  # (C, C)
    y = jax.lax.dot_general(
        scores, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    # Cross-chunk: contribution of the state entering this chunk.
    q_dec = q * jnp.exp(cum)
    y = y + jax.lax.dot_general(
        q_dec, state_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[0, 0, ...] = y.astype(o_ref.dtype)

    k_dec = k * jnp.exp(total - cum)
    state_ref[...] = state_ref[...] * jnp.exp(total) + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def gla_bhsd(
    q: jnp.ndarray,  # (B, H, S, dk)
    k: jnp.ndarray,  # (B, H, S, dk)
    v: jnp.ndarray,  # (B, H, S, dv)
    log_a: jnp.ndarray,  # (B, H, S)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Chunkwise gated linear attention over (batch, head)-major layout.

    Shapes: ``q``/``k`` are (B, H, S, dk), ``v`` is (B, H, S, dv),
    ``log_a`` is (B, H, S) per-step log decay (must be ≤ 0 for a stable
    recurrence); returns (B, H, S, dv) in ``q.dtype``. S must be a
    multiple of ``chunk``, and a compiled (TPU) ``chunk`` a multiple of
    128 or all of S — ``ops.gla`` pads with identity steps
    (log_a = 0, k/v = 0, which neither read nor write the state). Inputs
    may be bf16/f32; the (dk, dv) recurrent state and all matmuls run in
    f32 VMEM scratch. The chunk axis of the grid is sequential, so the
    state carries across grid steps per (b, h). Reference implementation:
    ``kernels/ref.py::gla_chunk_ref`` (exact per-step recurrence).
    """
    B, H, S, dk = q.shape
    dv = v.shape[-1]
    nc = S // chunk
    grid = (B, H, nc)
    return pl.pallas_call(
        functools.partial(_gla_kernel, nc=nc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, dk), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, dk), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, dv), lambda b, h, c: (b, h, c, 0)),
            # log_a rides as (B, H, 1, S): its block's last two dims are
            # (1, chunk) — full and lane-aligned — as the TPU tiling needs.
            pl.BlockSpec((1, 1, 1, chunk), lambda b, h, c: (b, h, 0, c)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, dv), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, dv), q.dtype),
        scratch_shapes=[pl_scratch((dk, dv))],
        interpret=interpret,
    )(q, k, v, log_a.reshape(B, H, 1, S))
