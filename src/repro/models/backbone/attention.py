"""Attention: GQA with optional qk-norm, causal/bidirectional/sliding-window
masking, chunked (flash-style) softmax for long prefill, KV-cache decode with
ring-buffer sliding windows, and cross-attention for the enc-dec path.

The chunked implementation is the pure-JAX analogue of the Pallas flash
kernel in repro/kernels/attention.py (which is the TPU-target hot path);
both share the same oracle (kernels/ref.py).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.backbone.layers import (
    apply_mrope,
    apply_rope,
    dense_init,
    rmsnorm,
    rmsnorm_init,
)

NEG_INF = -1e30


def attn_init(key, cfg):
    hd = cfg.head_dim_
    dtype = jnp.dtype(cfg.dtype)
    k = jax.random.split(key, 6)
    params = {
        "wq": dense_init(k[0], cfg.d_model, cfg.num_heads * hd, dtype),
        "wk": dense_init(k[1], cfg.d_model, cfg.num_kv_heads * hd, dtype),
        "wv": dense_init(k[2], cfg.d_model, cfg.num_kv_heads * hd, dtype),
        "wo": dense_init(k[3], cfg.num_heads * hd, cfg.d_model, dtype),
    }
    if cfg.qk_norm:
        params["q_norm"] = rmsnorm_init(hd, dtype)
        params["k_norm"] = rmsnorm_init(hd, dtype)
    return params


def _project_qkv(params, cfg, x, positions):
    B, S, _ = x.shape
    hd = cfg.head_dim_
    q = (x @ params["wq"]).reshape(B, S, cfg.num_heads, hd)
    k = (x @ params["wk"]).reshape(B, S, cfg.num_kv_heads, hd)
    v = (x @ params["wv"]).reshape(B, S, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, params["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, params["k_norm"], cfg.norm_eps)
    if positions is not None and cfg.attn_rope:
        if cfg.mrope:
            q = apply_mrope(q, positions, cfg.rope_theta)
            k = apply_mrope(k, positions, cfg.rope_theta)
        else:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _repeat_kv(k: jnp.ndarray, num_heads: int) -> jnp.ndarray:
    """(B, S, KV, hd) -> (B, S, H, hd) by repeating each KV group."""
    B, S, KV, hd = k.shape
    rep = num_heads // KV
    return jnp.repeat(k, rep, axis=2)


def _pad_heads(cfg, q, kf, vf):
    """§Perf lever 6 (pad_heads): zero-pad the head axis of the attention
    ACTIVATIONS to a multiple of the model-axis size. When num_heads does
    not divide the tensor-parallel degree (llama3.2: 24 heads on 16-way),
    GSPMD falls back to sharding head_dim, and the QK^T contraction over
    the sharded hd emits a partial-sum ALL-REDUCE of the full (B,H,S,S)
    score tensor per layer. With padded heads the contraction is local.
    The padded heads' outputs are sliced away before w_o — mathematically
    exact (params unchanged, gradients of real heads unchanged)."""
    m = cfg.perf.pad_heads
    H = q.shape[2]
    if not m or H % m == 0:
        return q, kf, vf, H
    Hp = -(-H // m) * m
    pad = ((0, 0), (0, 0), (0, Hp - H), (0, 0))
    q = jnp.pad(q, pad)
    kf = jnp.pad(kf, pad)
    vf = jnp.pad(vf, pad)
    # Hint GSPMD to shard the padded head axis over the active mesh's
    # model axis; without such a mesh there is nothing to shard over.
    if "model" in jax.sharding.get_abstract_mesh().axis_names:
        spec = jax.sharding.PartitionSpec(None, None, "model", None)
        q = jax.lax.with_sharding_constraint(q, spec)
        kf = jax.lax.with_sharding_constraint(kf, spec)
        vf = jax.lax.with_sharding_constraint(vf, spec)
    return q, kf, vf, H


def chunked_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    sliding_window: Optional[int] = None,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    q_offset: int = 0,
) -> jnp.ndarray:
    """Flash-style online-softmax attention, O(S·W) memory.

    q: (B, Sq, H, hd); k, v: (B, Skv, H, hd) (already GQA-expanded).
    ``q_offset`` is the absolute position of q[0] relative to k[0]
    (decode: Skv-1; prefill: 0).
    """
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    nq = -(-Sq // q_chunk)
    nkv = -(-Skv // kv_chunk)
    # Pad to multiples (padding keys are masked out).
    q_pad = nq * q_chunk - Sq
    kv_pad = nkv * kv_chunk - Skv
    qp = jnp.pad(q, ((0, 0), (0, q_pad), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, kv_pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, kv_pad), (0, 0), (0, 0)))
    qp = qp.reshape(B, nq, q_chunk, H, hd)
    kp = kp.reshape(B, nkv, kv_chunk, H, hd)
    vp = vp.reshape(B, nkv, kv_chunk, H, hd)

    q_pos_base = jnp.arange(nq)[:, None] * q_chunk + jnp.arange(q_chunk)[None]  # (nq, qc)
    kv_pos_base = jnp.arange(nkv)[:, None] * kv_chunk + jnp.arange(kv_chunk)[None]

    def q_block(qi, q_blk):
        # Online softmax over kv blocks.
        q_pos = q_pos_base[qi] + q_offset  # (qc,)

        def kv_step(carry, kv_idx):
            acc, m, l = carry
            k_blk = kp[:, kv_idx]  # (B, kc, H, hd)
            v_blk = vp[:, kv_idx]
            kv_pos = kv_pos_base[kv_idx]  # (kc,)
            s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k_blk).astype(jnp.float32) * scale
            mask = kv_pos[None, :] < Skv  # mask kv padding
            if causal:
                mask = mask & (kv_pos[None, :] <= q_pos[:, None])
            if sliding_window is not None:
                mask = mask & (kv_pos[None, :] > q_pos[:, None] - sliding_window)
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(v_blk.dtype), v_blk
            ).astype(jnp.float32)
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((B, H, q_chunk, hd), jnp.float32)
        m0 = jnp.full((B, H, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, q_chunk), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(kv_step, (acc0, m0, l0), jnp.arange(nkv))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out  # (B, H, qc, hd)

    outs = jax.lax.map(lambda qi: q_block(qi, qp[:, qi]), jnp.arange(nq))
    # (nq, B, H, qc, hd) -> (B, nq*qc, H, hd)
    out = jnp.transpose(outs, (1, 0, 3, 2, 4)).reshape(B, nq * q_chunk, H, hd)
    return out[:, :Sq].astype(q.dtype)


def full_attention(q, k, v, causal=True, sliding_window=None, q_offset=0):
    """Naive reference attention (small S only; used by smoke tests/oracles)."""
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / math.sqrt(hd)
    q_pos = jnp.arange(Sq) + q_offset
    kv_pos = jnp.arange(Skv)
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kv_pos[None, :] <= q_pos[:, None]
    if sliding_window is not None:
        mask &= kv_pos[None, :] > q_pos[:, None] - sliding_window
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# ---------------------------------------------------------------------------
# Block-level entry points
# ---------------------------------------------------------------------------

def attention_block(params, cfg, x, positions, causal=True, use_chunked=None):
    """Self-attention over a full sequence (train / prefill). Returns output
    of shape (B, S, D)."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(params, cfg, x, positions)
    window = cfg.sliding_window
    if cfg.use_pallas:
        # TPU hot path: the Pallas flash kernel takes UNEXPANDED KV heads
        # (GQA handled in its index maps — KV tiles fetched once per group).
        from repro.kernels import ops as kops

        out = kops.flash_attention(q, k, v, causal=causal, window=window)
        return out.reshape(B, S, cfg.num_heads * cfg.head_dim_) @ params["wo"]
    k = _repeat_kv(k, cfg.num_heads)
    v = _repeat_kv(v, cfg.num_heads)
    q, k, v, h_real = _pad_heads(cfg, q, k, v)
    if use_chunked is None:
        use_chunked = S > 2048 and not cfg.analysis_mode
    if use_chunked:
        out = chunked_attention(q, k, v, causal=causal, sliding_window=window)
    else:
        out = full_attention(q, k, v, causal=causal, sliding_window=window)
    out = out[:, :, :h_real]  # drop padded heads (exact)
    return out.reshape(B, S, cfg.num_heads * cfg.head_dim_) @ params["wo"]


def attention_prefill(params, cfg, x, positions):
    """Prefill: like attention_block but also returns the KV cache
    (B, S, KV, hd) pair for subsequent decode."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(params, cfg, x, positions)
    kf = _repeat_kv(k, cfg.num_heads)
    vf = _repeat_kv(v, cfg.num_heads)
    qp, kf, vf, h_real = _pad_heads(cfg, q, kf, vf)
    if cfg.analysis_mode:
        out = full_attention(qp, kf, vf, causal=True, sliding_window=cfg.sliding_window)
    else:
        out = chunked_attention(qp, kf, vf, causal=True, sliding_window=cfg.sliding_window)
    out = out[:, :, :h_real]
    y = out.reshape(B, S, cfg.num_heads * cfg.head_dim_) @ params["wo"]
    return y, {"k": k, "v": v, "pos": jnp.asarray(S, jnp.int32)}


def init_kv_cache(cfg, batch: int, max_len: int, dtype) -> dict:
    hd = cfg.head_dim_
    window = cfg.sliding_window
    cache_len = min(window, max_len) if window else max_len
    return {
        "k": jnp.zeros((batch, cache_len, cfg.num_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, cache_len, cfg.num_kv_heads, hd), dtype),
        "pos": jnp.zeros((), jnp.int32),  # absolute position of next token
    }


def attention_decode(params, cfg, x, cache, positions=None):
    """One-token decode with KV cache. x: (B, 1, D).

    Sliding-window archs keep a ring buffer of ``window`` entries — O(1)
    memory in sequence length, which is what makes long_500k lowerable.
    ``positions`` overrides the rope position (needed for M-RoPE, whose
    text positions differ from the raw cache counter).
    """
    B, _, _ = x.shape
    hd = cfg.head_dim_
    pos = cache["pos"]
    if positions is None:
        positions = jnp.full((B, 1), pos, jnp.int32)
        if cfg.mrope:
            positions = jnp.broadcast_to(positions[None], (3, B, 1))
    q, k_new, v_new = _project_qkv(params, cfg, x, positions)

    cache_len = cache["k"].shape[1]
    slot = jnp.mod(pos, cache_len)  # ring-buffer index (== pos when no window)
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, slot, axis=1)

    kf = _repeat_kv(k, cfg.num_heads)
    vf = _repeat_kv(v, cfg.num_heads)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kf).astype(jnp.float32) / math.sqrt(hd)
    # Valid entries: absolute positions (pos - age) with age < cache_len,
    # i.e. every slot written so far.
    idx = jnp.arange(cache_len)
    written = jnp.where(pos + 1 >= cache_len, cache_len, pos + 1)
    valid = idx < written
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(vf.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vf)
    y = out.reshape(B, 1, cfg.num_heads * hd) @ params["wo"]
    new_cache = {"k": k, "v": v, "pos": pos + 1}
    return y, new_cache


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder)
# ---------------------------------------------------------------------------

def cross_attn_init(key, cfg):
    hd = cfg.head_dim_
    dtype = jnp.dtype(cfg.dtype)
    k = jax.random.split(key, 4)
    return {
        "wq": dense_init(k[0], cfg.d_model, cfg.num_heads * hd, dtype),
        "wk": dense_init(k[1], cfg.d_model, cfg.num_kv_heads * hd, dtype),
        "wv": dense_init(k[2], cfg.d_model, cfg.num_kv_heads * hd, dtype),
        "wo": dense_init(k[3], cfg.num_heads * hd, cfg.d_model, dtype),
    }


def cross_attention(params, cfg, x, memory):
    """x: (B, Sq, D) queries; memory: (B, Skv, D) encoder states."""
    B, Sq, _ = x.shape
    Skv = memory.shape[1]
    hd = cfg.head_dim_
    q = (x @ params["wq"]).reshape(B, Sq, cfg.num_heads, hd)
    k = (memory @ params["wk"]).reshape(B, Skv, cfg.num_kv_heads, hd)
    v = (memory @ params["wv"]).reshape(B, Skv, cfg.num_kv_heads, hd)
    kf = _repeat_kv(k, cfg.num_heads)
    vf = _repeat_kv(v, cfg.num_heads)
    out = full_attention(q, kf, vf, causal=False)
    return out.reshape(B, Sq, cfg.num_heads * hd) @ params["wo"]
