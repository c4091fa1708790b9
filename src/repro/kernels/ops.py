"""Jit'd public wrappers around the Pallas kernels.

On this CPU container the kernels run in ``interpret=True`` mode (the
kernel body executes in Python per grid cell — bit-accurate to the TPU
lowering's semantics); on a TPU runtime ``interpret=False`` compiles to
Mosaic. The default is resolved LAZILY per call (``interpret_default``)
so importing this module never initializes the XLA backend — tests that
force host device counts (``--xla_force_host_platform_device_count``)
must be able to import kernels before touching a device.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import wire as _wire
from repro.kernels.attention import flash_attention_bhsd
from repro.kernels.gla import gla_bhsd
from repro.kernels.reparam import reparam_stl as _reparam_stl
from repro.kernels.rmsnorm import rmsnorm_rows


def interpret_default() -> bool:
    """True when the kernels must run in interpret mode (non-TPU host)."""
    return jax.default_backend() == "cpu"


@partial(jax.jit, static_argnames=("causal", "window", "q_offset", "block_q",
                                   "block_kv", "interpret"))
def flash_attention(
    q: jnp.ndarray,  # (B, Sq, H, hd)
    k: jnp.ndarray,  # (B, Skv, KV, hd)
    v: jnp.ndarray,  # (B, Skv, KV, hd)
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Flash attention for (batch, seq, head)-major activations.

    Shapes: ``q`` is (B, Sq, H, hd); ``k``/``v`` are (B, Skv, KV, hd)
    with H % KV == 0 (GQA groups of H/KV query heads share a kv head);
    returns (B, Sq, H, hd) in ``q.dtype`` (bf16/f32; softmax state is
    f32 inside the kernel). ``window`` enables sliding-window masking
    and ``q_offset`` positions the query block for decode. Pads Sq/Skv
    to block multiples (masked inside the kernel) and adapts the layout
    to the (B, H, S, hd) kernel. Reference implementation:
    ``kernels/ref.py::flash_attention_ref``.
    """
    interpret = interpret_default() if interpret is None else interpret
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    block_q = min(block_q, _round_up(Sq, 8))
    block_kv = min(block_kv, _round_up(Skv, 8))
    qt = jnp.moveaxis(q, 1, 2)
    kt = jnp.moveaxis(k, 1, 2)
    vt = jnp.moveaxis(v, 1, 2)
    pq = (-Sq) % block_q
    pkv = (-Skv) % block_kv
    if pq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pkv:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pkv), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pkv), (0, 0)))
    out = flash_attention_bhsd(
        qt, kt, vt, causal=causal, window=window, q_offset=q_offset,
        block_q=block_q, block_kv=block_kv, interpret=interpret,
        true_sq=Sq, true_skv=Skv,
    )
    return jnp.moveaxis(out[:, :, :Sq], 2, 1)


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


@partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6,
            block_rows: int = 256, interpret: Optional[bool] = None) -> jnp.ndarray:
    """RMSNorm over the last axis for arbitrary leading shape.

    Shapes: ``x`` is (..., D) with any leading dims, ``weight`` is (D,);
    returns (..., D) in ``x.dtype`` (bf16/f32; mean-of-squares in f32).
    Flattens leading dims to rows and halves ``block_rows`` until it
    divides the row count. Reference implementation:
    ``kernels/ref.py::rmsnorm_ref``.
    """
    interpret = interpret_default() if interpret is None else interpret
    lead = x.shape[:-1]
    D = x.shape[-1]
    R = 1
    for s in lead:
        R *= s
    xf = x.reshape(R, D)
    br = block_rows
    while R % br:
        br //= 2
    br = max(br, 1)
    out = rmsnorm_rows(xf, weight, eps=eps, block_rows=br, interpret=interpret)
    return out.reshape(*lead, D)


@partial(jax.jit, static_argnames=("block", "interpret"))
def reparam_stl(mu, log_sigma, eps, block: int = 4096,
                interpret: Optional[bool] = None):
    """Fused z = mu + exp(log_sigma)·eps and STL log q, one HBM pass.

    Shapes: ``mu``/``log_sigma``/``eps`` are (N,) flattened latents of
    equal length (f32; bf16 inputs are upcast per-block inside the
    kernel); returns ``(z, logq)`` with z (N,) in ``mu.dtype`` and logq
    a f32 scalar. Differentiable (fused custom VJP). Reference
    implementation: ``kernels/ref.py::reparam_stl_ref``.
    """
    interpret = interpret_default() if interpret is None else interpret
    return _reparam_stl(mu, log_sigma, eps, block=block, interpret=interpret)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def gla(q, k, v, log_a, chunk: int = 128, interpret: Optional[bool] = None):
    """Gated linear attention (Mamba2-SSD/mLSTM recurrence).

    Shapes: ``q``/``k`` are (B, S, H, dk); ``v`` is (B, S, H, dv);
    ``log_a`` is (B, S, H) per-step log decay (≤ 0); returns
    (B, S, H, dv) in ``q.dtype`` (bf16/f32; the recurrent state is f32).
    Pads S to a chunk multiple with identity steps (log_a = 0, k/v = 0 →
    the padded steps neither read nor write the state) and adapts the
    layout to the (B, H, S, ·) kernel. Reference implementation:
    ``kernels/ref.py::gla_chunk_ref``.
    """
    interpret = interpret_default() if interpret is None else interpret
    B, S, H, dk = q.shape
    chunk = min(chunk, _round_up(S, 8))
    pad = (-S) % chunk
    qt = jnp.moveaxis(q, 1, 2)
    kt = jnp.moveaxis(k, 1, 2)
    vt = jnp.moveaxis(v, 1, 2)
    at = jnp.moveaxis(log_a, 1, 2)
    if pad:
        zpad = ((0, 0), (0, 0), (0, pad), (0, 0))
        qt = jnp.pad(qt, zpad)
        kt = jnp.pad(kt, zpad)
        vt = jnp.pad(vt, zpad)
        at = jnp.pad(at, ((0, 0), (0, 0), (0, pad)))
    out = gla_bhsd(qt, kt, vt, at, chunk=chunk, interpret=interpret)
    return jnp.moveaxis(out[:, :, :S], 2, 1)


@partial(jax.jit, static_argnames=("clip_norm", "noise_multiplier", "quantize",
                                   "block_rows", "interpret"))
def wire_upload(
    x: jnp.ndarray,  # (J, P) stacked wire matrix
    mask: jnp.ndarray,  # (J,) participation mask
    keys: Optional[jnp.ndarray] = None,  # (J, 2) uint32 per-row noise keys
    reference: Optional[jnp.ndarray] = None,  # (P,) broadcast row
    clip_norm: Optional[float] = None,
    noise_multiplier: float = 0.0,
    quantize: bool = False,
    block_rows: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Fused per-silo upload: clip + DP noise + mask + int8 quantize.

    One pass over the (J, P) wire matrix; noise drawn in-kernel from the
    per-row ``keys`` (pass ``fold_in(policy.upload_key(rk, t, j), 0)``
    per row for bit-exactness with ``PrivacyPolicy``'s stream). Returns
    the privatized f32 matrix, or ``(q, scales)`` with one scale per
    silo row when ``quantize``. Reference implementation:
    ``kernels/ref.py::wire_upload_ref``.
    """
    interpret = interpret_default() if interpret is None else interpret
    return _wire.fused_upload(
        x, mask=mask, keys=keys, reference=reference, clip_norm=clip_norm,
        noise_multiplier=noise_multiplier, quantize=quantize,
        block_rows=block_rows, interpret=interpret,
    )


@partial(jax.jit, static_argnames=("trim_frac", "block_cols", "interpret"))
def wire_combine(
    x: jnp.ndarray,  # (J, P) gathered wire matrix (f32, or int8 + scales)
    weights: jnp.ndarray,  # (J,) 0/1 or fractional async weights
    scales: Optional[jnp.ndarray] = None,  # (J,) int8 scales (fused dequant)
    trim_frac: Optional[float] = None,
    block_cols: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused masked/weighted (trimmed-)mean over the silo axis.

    ``trim_frac=None`` is ``MeanAggregator`` semantics, a float is
    ``TrimmedMeanAggregator`` semantics; int8 payloads dequantize inside
    the same pass when ``scales`` is given. Returns the (P,) combined
    row. Reference implementations:
    ``kernels/ref.py::masked_weighted_mean_ref`` /
    ``masked_trimmed_mean_ref``.
    """
    interpret = interpret_default() if interpret is None else interpret
    return _wire.fused_combine(
        x, weights, scales=scales, trim_frac=trim_frac,
        block_cols=block_cols, interpret=interpret,
    )


@partial(jax.jit, static_argnames=("num_iters", "interpret"))
def sqrtm_ns(mat: jnp.ndarray, num_iters: int = 25,
             interpret: Optional[bool] = None) -> jnp.ndarray:
    """PSD matrix sqrt via the fused Newton–Schulz step kernel.

    Shapes: ``mat`` is (d, d) symmetric PSD; returns (d, d) in
    ``mat.dtype``. Same normalization/iteration as
    ``core.barycenter.sqrtm_newton_schulz``. Reference implementation:
    ``kernels/ref.py::newton_schulz_sqrtm_ref``.
    """
    interpret = interpret_default() if interpret is None else interpret
    return _wire.sqrtm_newton_schulz_fused(mat, num_iters=num_iters,
                                           interpret=interpret)
