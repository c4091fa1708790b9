"""Flash attention as a Pallas TPU kernel.

TPU adaptation (DESIGN.md §5.4): the grid is (B, H, n_q, n_kv) with the
innermost kv dimension marked "arbitrary" (sequential) so the online-softmax
state (acc, m, l) lives in VMEM scratch across kv steps — the TPU analogue
of a CUDA flash kernel's shared-memory tile loop. Block shapes default to
(128, 128): multiples of the (8, 128) sublane x lane tile and of the 128-wide
MXU systolic dims. GQA is handled in the K/V index maps (kv head = h // G),
so KV tiles are fetched once per group, not repeated H times — this is where
a TPU kernel saves HBM bandwidth over the naive jnp path.

Causal masking skips fully-masked kv blocks with ``pl.when`` (block-level
sparsity); sliding windows additionally skip blocks left of the window.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref,  # (1, 1, bq, hd), (1, 1, bk, hd) x2
    o_ref,  # (1, 1, bq, hd)
    acc_ref, m_ref, l_ref,  # VMEM scratch: (bq, hd) f32, (bq, 1), (bq, 1)
    *,
    sq: int,
    skv: int,
    causal: bool,
    window: Optional[int],
    q_offset: int,
    scale: float,
):
    iq = pl.program_id(2)
    ikv = pl.program_id(3)
    n_kv = pl.num_programs(3)
    bq = q_ref.shape[2]
    bk = k_ref.shape[2]

    @pl.when(ikv == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_offset
    kv_pos = ikv * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # Block-level skip: run only if some (q, kv) pair in this tile is live.
    q_max = iq * bq + bq - 1 + q_offset
    kv_min = ikv * bk
    live = jnp.asarray(True)
    if causal:
        live = live & (kv_min <= q_max)
    if window is not None:
        q_min = iq * bq + q_offset
        kv_max = ikv * bk + bk - 1
        live = live & (kv_max > q_min - window)

    @pl.when(live)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        mask = kv_pos < skv  # kv padding
        mask &= q_pos < sq + q_offset  # q padding (never attends garbage)
        if causal:
            mask &= kv_pos <= q_pos
        if window is not None:
            mask &= kv_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)  # rows with all-masked history stay 0
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ikv == n_kv - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0, ...] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_bhsd(
    q: jnp.ndarray,  # (B, H, Sq_padded, hd)
    k: jnp.ndarray,  # (B, KV, Skv_padded, hd)
    v: jnp.ndarray,  # (B, KV, Skv_padded, hd)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
    true_sq: Optional[int] = None,
    true_skv: Optional[int] = None,
) -> jnp.ndarray:
    """Flash attention over (batch, head)-major layout with online softmax.

    Shapes: ``q`` is (B, H, Sq, hd); ``k``/``v`` are (B, KV, Skv, hd)
    with KV ≤ H and H % KV == 0 (GQA: query head h reads kv head
    h // (H // KV)); returns (B, H, Sq, hd) in ``q.dtype``. Sq/Skv must
    be multiples of ``block_q``/``block_kv`` — ``ops.flash_attention``
    pads and passes the unpadded lengths as ``true_sq``/``true_skv`` for
    masking. Inputs may be bf16/f32; scores, the running max/normalizer
    and the accumulator are f32 (VMEM scratch), cast back on the final
    flush. ``causal``/``window`` masking skips fully-dead kv blocks at
    block granularity. Reference implementation:
    ``kernels/ref.py::flash_attention_ref``.
    """
    B, H, Sq, hd = q.shape
    KV = k.shape[1]
    Skv = k.shape[2]
    true_sq = Sq if true_sq is None else true_sq
    true_skv = Skv if true_skv is None else true_skv
    group = H // KV
    n_q = Sq // block_q
    n_kv = Skv // block_kv
    grid = (B, H, n_q, n_kv)

    kernel = functools.partial(
        _flash_kernel,
        sq=true_sq, skv=true_skv, causal=causal, window=window,
        q_offset=q_offset, scale=1.0 / math.sqrt(hd),
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ikv: (b, h, iq, 0)),
            pl.BlockSpec(
                (1, 1, block_kv, hd), lambda b, h, iq, ikv: (b, h // group, ikv, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_kv, hd), lambda b, h, iq, ikv: (b, h // group, ikv, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, hd), lambda b, h, iq, ikv: (b, h, iq, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            # (bq, hd) f32 accumulator + (bq, 1) running max / normalizer
            pl_scratch((block_q, hd)),
            pl_scratch((block_q, 1)),
            pl_scratch((block_q, 1)),
        ],
        interpret=interpret,
    )(q, k, v)


def pl_scratch(shape):
    """VMEM f32 scratch (interpret mode emulates the TPU memory space)."""
    return pltpu.VMEM(shape, jnp.float32)
