"""Plain float32 reference of Nemotron-H under the Bayesian head, for
the tests (the benchmark keeps its own copy, perfbench/models/nemotron_h.py).

Straightforward jax.numpy on the program's parameter layout, read with
the widths of a ``repro`` ArchConfig: the Mamba-2 recurrence step by
step (no chunking), every held expert on every token masked by the
router, full causal attention, and the head's log-softmax over the whole
vocabulary. Callers run it under ``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def mamba2(cfg, p, u):
    """u (B, S, d) -> (B, S, d), the recurrence one step at a time."""
    B, S, _ = u.shape
    H, P, N, G = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    di, K = H * P, cfg.ssm_conv
    z, xbc, dt = jnp.split(u @ p["in_proj"], [di, 2 * di + 2 * G * N], axis=-1)
    xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(xp[:, i:i + S] * p["conv_w"][i] for i in range(K))
                      + p["conv_b"])
    x, Bm, Cm = jnp.split(xbc, [di, di + G * N], axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = jnp.exp(dt * -jnp.exp(p["A_log"]))
    xh = x.reshape(B, S, H, P)
    Bh = jnp.repeat(Bm.reshape(B, S, G, N), H // G, axis=2)
    Ch = jnp.repeat(Cm.reshape(B, S, G, N), H // G, axis=2)
    state = jnp.zeros((B, H, P, N))
    ys = []
    for t in range(S):
        state = (state * a[:, t, :, None, None]
                 + (dt[:, t, :, None] * xh[:, t])[..., None] * Bh[:, t, :, None, :])
        ys.append(jnp.einsum("bhpn,bhn->bhp", state, Ch[:, t]))
    y = jnp.stack(ys, axis=1) + xh * p["D"][:, None]
    g = (y.reshape(B, S, di) * jax.nn.silu(z)).reshape(B, S, G, di // G)
    g = rmsnorm(g, p["out_norm"].reshape(G, -1), cfg.norm_eps)
    return g.reshape(B, S, di) @ p["out_proj"]


def route(cfg, p, xf):
    scores = jax.nn.sigmoid(xf @ p["router"])
    _, ids = jax.lax.top_k(scores + p["router_bias"], cfg.num_experts_per_tok)
    gates = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, gates / (gates.sum(-1, keepdims=True) + 1e-20) * cfg.routed_scaling


def moe(cfg, p, x, held=None, shared=True):
    """Every held expert (router ids ``held``) on every token, masked by
    the router, plus the shared expert."""
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    ids, gates = route(cfg, p, xf)
    held = range(p["w_up"].shape[0]) if held is None else held
    y = jnp.zeros_like(xf)
    for e, rid in enumerate(held):
        g = jnp.sum(jnp.where(ids == rid, gates, 0.0), axis=-1)
        y = y + g[:, None] * (jnp.square(jax.nn.relu(xf @ p["w_up"][e]))
                              @ p["w_down"][e])
    if shared:
        s = p["shared"]
        y = y + jnp.square(jax.nn.relu(xf @ s["w_up"])) @ s["w_down"]
    return y.reshape(B, S, d)


def attention(cfg, p, x):
    B, S, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = jnp.repeat((x @ p["wk"]).reshape(B, S, KV, hd), H // KV, axis=2)
    v = jnp.repeat((x @ p["wv"]).reshape(B, S, KV, hd), H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(B, S, H * hd) @ p["wo"]


def backbone(cfg, theta, tokens):
    x = theta["embed"]["tok"][tokens]
    for i in range(cfg.num_layers):
        p = theta["tail"][f"layer{i}"]
        h = rmsnorm(x, p["norm1"], cfg.norm_eps)
        kind = cfg.layer_pattern[i]
        if kind == "M":
            x = x + mamba2(cfg, p["mixer"], h)
        elif kind == "E":
            x = x + moe(cfg, p["moe"], h)
        else:
            x = x + attention(cfg, p["attn"], h)
    return rmsnorm(x, theta["final_norm"], cfg.norm_eps)


def log_prior_global(cfg, z_G):
    return -0.5 * jnp.sum(z_G * z_G)


def log_local(cfg, theta, z_G, z_L, data):
    """log p(Z_L | Z_G) + Σ next-token log-likelihood of the sequences."""
    d, V = cfg.d_model, cfg.vocab_size
    rg, rl = cfg.bayes.global_rank, cfg.bayes.local_rank
    tokens = data["tokens"]
    h = backbone(cfg, theta, tokens[:, :-1])
    A_G = z_G[:rg * d].reshape(rg, d)
    B_G = z_G[rg * d:rg * (d + V)].reshape(rg, V)
    A_L = z_L[:rl * d].reshape(rl, d)
    B_L = z_L[rl * d:rl * (d + V)].reshape(rl, V)
    logits = (h @ theta["lm_head"] + (h @ A_G.T) @ B_G / rg
              + (h @ A_L.T) @ B_L / rl + z_L[rl * (d + V):])
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.sum(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))
    omega = z_G[-1]
    return ll - 0.5 * jnp.sum(z_L * z_L) * jnp.exp(-2 * omega) - z_L.size * omega
