"""Mamba2 (SSD) blocks + a generic chunked gated-linear-attention scan.

Mamba2's state-space duality (SSD) recurrence

    S_t = exp(a_t) S_{t-1} + k_t v_t^T          (state: (H, dk, dv))
    y_t = q_t . S_t

is shared by every gated linear-attention family (Mamba2, mLSTM, GLA);
``chunked_gla`` implements it once with the standard chunked algorithm:
quadratic *within* a chunk (MXU-friendly matmuls) and a ``lax.scan`` of
states *across* chunks — O(S·C) instead of O(S²) work, O(S) memory.

TPU adaptation: the chunk length is a multiple of the MXU tile (128) so
the within-chunk matmuls are hardware-aligned, and the scan carries only
the (H, dk, dv) state — it never materializes per-step decay products
along the full sequence.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.backbone.layers import dense_init, rmsnorm, rmsnorm_init


def chunked_gla(
    q: jnp.ndarray,  # (B, S, H, dk)
    k: jnp.ndarray,  # (B, S, H, dk)
    v: jnp.ndarray,  # (B, S, H, dv)
    log_a: jnp.ndarray,  # (B, S, H) per-step log decay (<= 0)
    chunk: int = 256,
) -> jnp.ndarray:
    """y_t = q_t^T ( sum_{s<=t} (prod_{r=s+1..t} exp(log_a_r)) k_s v_s^T ).

    All accumulation in f32. Returns (B, S, H, dv) in q.dtype.
    """
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        log_a = jnp.pad(log_a, ((0, 0), (0, pad), (0, 0)))
    n = (S + pad) // chunk
    # (B, n, C, H, ...)
    qc = q.reshape(B, n, chunk, H, dk).astype(jnp.float32)
    kc = k.reshape(B, n, chunk, H, dk).astype(jnp.float32)
    vc = v.reshape(B, n, chunk, H, dv).astype(jnp.float32)
    ac = log_a.reshape(B, n, chunk, H).astype(jnp.float32)

    # Cumulative log-decay within each chunk: L_t = sum_{r<=t} log_a_r.
    cum = jnp.cumsum(ac, axis=2)  # (B, n, C, H)
    total = cum[:, :, -1]  # (B, n, H) — full-chunk decay

    # Within-chunk (intra) term: y_t += sum_{s<=t} exp(L_t - L_s) q_t.k_s v_s
    # Decay matrix D[t, s] = exp(L_t - L_s) for s <= t else 0.
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,n,C_t,C_s,H)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))[None, None, :, :, None]
    # Mask *before* exp so no inf ever materializes (NaN-safe gradients).
    D = jnp.exp(jnp.where(tri, diff, -jnp.inf))
    scores = jnp.einsum("bnthd,bnshd->bntsh", qc, kc) * D
    y_intra = jnp.einsum("bntsh,bnshv->bnthv", scores, vc)

    # Cross-chunk (inter) term via scan of the state.
    # State entering chunk i is S_i; contribution y_t += exp(L_t) q_t . S_i.
    # State update: S_{i+1} = exp(total_i) S_i + sum_s exp(total_i - L_s) k_s v_s.
    k_dec = kc * jnp.exp(total[:, :, None] - cum)[..., None]  # (B,n,C,H,dk)
    chunk_kv = jnp.einsum("bnshd,bnshv->bnhdv", k_dec, vc)  # (B,n,H,dk,dv)

    def scan_body(state, inp):
        chunk_kv_i, total_i = inp  # (B,H,dk,dv), (B,H)
        new_state = state * jnp.exp(total_i)[..., None, None] + chunk_kv_i
        return new_state, state  # emit state *entering* the chunk

    init = jnp.zeros((B, H, dk, dv), jnp.float32)
    _, states = jax.lax.scan(
        scan_body,
        init,
        (jnp.moveaxis(chunk_kv, 1, 0), jnp.moveaxis(total, 1, 0)),
    )
    states = jnp.moveaxis(states, 0, 1)  # (B, n, H, dk, dv)
    q_dec = qc * jnp.exp(cum)[..., None]
    y_inter = jnp.einsum("bnthd,bnhdv->bnthv", q_dec, states)

    y = (y_intra + y_inter).reshape(B, n * chunk, H, dv)
    return y[:, :S].astype(q.dtype)


def gla_final_state(k, v, log_a, chunk: int = 256) -> jnp.ndarray:
    """The recurrent state after the last position (for prefill -> decode).

    Returns (B, H, dk, dv) f32.
    """
    B, S, H, dk = k.shape
    dv = v.shape[-1]
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:  # padded steps must be identity: decay 1 (log 0), kv 0
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        log_a = jnp.pad(log_a, ((0, 0), (0, pad), (0, 0)))
    n = (S + pad) // chunk
    kc = k.reshape(B, n, chunk, H, dk).astype(jnp.float32)
    vc = v.reshape(B, n, chunk, H, dv).astype(jnp.float32)
    ac = log_a.reshape(B, n, chunk, H).astype(jnp.float32)
    cum = jnp.cumsum(ac, axis=2)
    total = cum[:, :, -1]
    k_dec = kc * jnp.exp(total[:, :, None] - cum)[..., None]
    chunk_kv = jnp.einsum("bnshd,bnshv->bnhdv", k_dec, vc)

    def body(state, inp):
        ckv, tot = inp
        return state * jnp.exp(tot)[..., None, None] + ckv, None

    init = jnp.zeros((B, H, dk, dv), jnp.float32)
    final, _ = jax.lax.scan(
        body, init, (jnp.moveaxis(chunk_kv, 1, 0), jnp.moveaxis(total, 1, 0))
    )
    return final


def gla_decode_step(state, q, k, v, log_a):
    """One recurrent step. state: (B,H,dk,dv) f32; q/k/v: (B,H,d*); log_a: (B,H)."""
    state = state * jnp.exp(log_a.astype(jnp.float32))[..., None, None] + jnp.einsum(
        "bhd,bhv->bhdv", k.astype(jnp.float32), v.astype(jnp.float32)
    )
    y = jnp.einsum("bhd,bhdv->bhv", q.astype(jnp.float32), state)
    return state, y


# ---------------------------------------------------------------------------
# Mamba2 block
# ---------------------------------------------------------------------------

def mamba2_init(key, cfg):
    """Mamba2 block parameters.

    d_inner = ``cfg.ssm_inner`` (heads x head_dim, or expand * d_model),
    H = d_inner / ssm_head_dim heads, N = ssm_state, G = ssm_groups B/C
    groups (heads split evenly over them; zamba2 has one, shared by every
    head), per-head scalar A (the SSD restriction), depthwise conv of
    width ssm_conv over the x/B/C streams, learned dt bias, and a gated
    RMSNorm (per group of d_inner / G channels) before out-proj.
    """
    d = cfg.d_model
    d_inner = cfg.ssm_inner
    N = cfg.ssm_state
    H = d_inner // cfg.ssm_head_dim
    G = cfg.ssm_groups
    conv_dim = d_inner + 2 * G * N
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    # in_proj emits [z (gate), x, B, C, dt] like the reference mamba2.
    return {
        "in_proj": dense_init(ks[0], d, 2 * d_inner + 2 * G * N + H, dtype),
        "conv_w": (0.1 * jax.random.normal(ks[1], (cfg.ssm_conv, conv_dim))).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32),  # A = -exp(A_log)
        "dt_bias": jnp.log(jnp.expm1(jnp.full((H,), 1e-2))).astype(jnp.float32),
        "D": jnp.ones((H,), jnp.float32),  # skip connection
        "out_norm": rmsnorm_init(d_inner, dtype),
        "out_proj": dense_init(ks[2], d_inner, d, dtype),
    }


def _mamba2_split(params, cfg, u):
    """Shared projection + causal conv. u: (B, S, D). Returns z, x, Bm, Cm, dt."""
    d_inner = cfg.ssm_inner
    N = cfg.ssm_state
    H = d_inner // cfg.ssm_head_dim
    proj = u @ params["in_proj"]  # (B,S,2*di+2GN+H)
    z, xBC, dt = jnp.split(
        proj, [d_inner, 2 * d_inner + 2 * cfg.ssm_groups * N], axis=-1)
    return z, xBC, dt, d_inner, N, H


def _causal_conv(xBC, conv_w, conv_b, conv_state=None):
    """Depthwise causal conv, width K. xBC: (B,S,C). conv_state: (B,K-1,C)."""
    K = conv_w.shape[0]
    if conv_state is None:
        pad = jnp.zeros((xBC.shape[0], K - 1, xBC.shape[-1]), xBC.dtype)
    else:
        pad = conv_state.astype(xBC.dtype)
    xp = jnp.concatenate([pad, xBC], axis=1)  # (B, S+K-1, C)
    out = sum(xp[:, i : i + xBC.shape[1]] * conv_w[i] for i in range(K))
    new_state = xp[:, xp.shape[1] - (K - 1) :]
    return jax.nn.silu(out + conv_b), new_state


def _mamba2_qkva(params, cfg, x_conv, dt_raw, d_inner, N, H):
    """Map conv output + dt to the GLA (q, k, v, log_a) views."""
    P = cfg.ssm_head_dim
    G = cfg.ssm_groups
    x, Bm, Cm = jnp.split(x_conv, [d_inner, d_inner + G * N], axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])  # (...,H)
    A = -jnp.exp(params["A_log"])  # (H,) negative
    log_a = dt * A  # (..., H)
    shape = x.shape[:-1]
    xh = x.reshape(*shape, H, P)
    v = xh * dt[..., None].astype(x.dtype)  # dt folds into v (SSD form)
    # Head h reads B/C group h // (H / G) (one group: every head reads it).
    k = _group_heads(Bm, G, H).astype(x.dtype)
    q = _group_heads(Cm, G, H).astype(x.dtype)
    return q, k, v, log_a, xh


def _group_heads(m, G: int, H: int):
    """(..., G*N) -> (..., H, N): each group's N channels for its heads."""
    shape = m.shape[:-1]
    m = m.reshape(*shape, G, 1, m.shape[-1] // G)
    return jnp.broadcast_to(m, (*shape, G, H // G, m.shape[-1])).reshape(
        *shape, H, m.shape[-1])


def _gated_norm(params, cfg, y, z):
    """RMSNorm of y * silu(z) over each of the G groups of channels."""
    g = y * jax.nn.silu(z)
    G = cfg.ssm_groups
    if G == 1:
        return rmsnorm(g, params["out_norm"], cfg.norm_eps)
    shape = g.shape[:-1]
    g = g.reshape(*shape, G, g.shape[-1] // G)
    w = params["out_norm"].reshape(G, -1)
    return rmsnorm(g, w, cfg.norm_eps).reshape(*shape, -1)


def _gla_dispatch(cfg, q, k, v, log_a):
    """jnp chunked scan (default) or the Pallas GLA kernel (TPU hot path)."""
    if cfg is not None and getattr(cfg, "use_pallas", False):
        from repro.kernels import ops as kops

        return kops.gla(q, k, v, log_a)
    return chunked_gla(q, k, v, log_a, chunk=cfg.ssm_chunk)


def mamba2_block(params, cfg, u):
    """Full-sequence Mamba2 (train / prefill). u: (B, S, D) -> (B, S, D)."""
    with jax.named_scope("mamba2"):
        z, xBC, dt_raw, d_inner, N, H = _mamba2_split(params, cfg, u)
        x_conv, _ = _causal_conv(xBC, params["conv_w"], params["conv_b"])
        q, k, v, log_a, xh = _mamba2_qkva(params, cfg, x_conv, dt_raw,
                                          d_inner, N, H)
        y = _gla_dispatch(cfg, q, k, v, log_a)
        y = y + xh * params["D"][:, None].astype(xh.dtype)
        y = y.reshape(*u.shape[:2], d_inner)
        return _gated_norm(params, cfg, y, z) @ params["out_proj"]


def mamba2_init_cache(params, cfg, batch: int, dtype):
    d_inner = cfg.ssm_inner
    N = cfg.ssm_state
    H = d_inner // cfg.ssm_head_dim
    conv_dim = d_inner + 2 * cfg.ssm_groups * N
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, conv_dim), dtype),
        "ssm": jnp.zeros((batch, H, N, cfg.ssm_head_dim), jnp.float32),
    }


def mamba2_prefill(params, cfg, u):
    """Like mamba2_block but also returns the decode cache."""
    z, xBC, dt_raw, d_inner, N, H = _mamba2_split(params, cfg, u)
    x_conv, conv_state = _causal_conv(xBC, params["conv_w"], params["conv_b"])
    q, k, v, log_a, xh = _mamba2_qkva(params, cfg, x_conv, dt_raw, d_inner, N, H)
    y = chunked_gla(q, k, v, log_a, chunk=cfg.ssm_chunk)
    ssm_state = gla_final_state(k, v, log_a, chunk=cfg.ssm_chunk)
    y = y + xh * params["D"][:, None].astype(xh.dtype)
    y = y.reshape(*u.shape[:2], d_inner)
    y = _gated_norm(params, cfg, y, z)
    return y @ params["out_proj"], {"conv": conv_state, "ssm": ssm_state}


def mamba2_decode(params, cfg, u, cache):
    """One-token step. u: (B, 1, D). O(1) state — enables long_500k."""
    z, xBC, dt_raw, d_inner, N, H = _mamba2_split(params, cfg, u)
    x_conv, conv_state = _causal_conv(
        xBC, params["conv_w"], params["conv_b"], conv_state=cache["conv"]
    )
    q, k, v, log_a, xh = _mamba2_qkva(params, cfg, x_conv, dt_raw, d_inner, N, H)
    # Squeeze the length-1 axis for the recurrent step.
    state, y = gla_decode_step(
        cache["ssm"], q[:, 0], k[:, 0], v[:, 0], log_a[:, 0]
    )
    y = y[:, None].astype(u.dtype) + xh * params["D"][:, None].astype(xh.dtype)
    y = y.reshape(u.shape[0], 1, d_inner)
    y = _gated_norm(params, cfg, y, z)
    return y @ params["out_proj"], {"conv": conv_state, "ssm": state}
