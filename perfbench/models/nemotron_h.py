"""Nemotron-3-Nano (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16) under the
Bayesian LM head: data, initial state, the plain reference of its
densities, and its matmul FLOP count. Nothing here imports the program
except ``program_model``.

The stack is the first ``num_hidden_layers`` blocks of
``hybrid_override_pattern`` (M = Mamba-2, E = MoE, * = attention), each
one sublayer behind its own RMSNorm and residual, then a final RMSNorm
and an untied head:

    Mamba-2   in_proj -> [z, x B C, dt]; causal depthwise conv (width 4,
              with bias) and silu on x B C; dt = softplus(dt + dt_bias),
              A = -exp(A_log); per head h (B/C group g = h // (H/G)):
              S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,  y_t = S_t C_t
              + D x_t; y * silu(z) RMS-normed per group; out_proj
    MoE       sigmoid router scores over all ``router_experts``; top-k by
              score + a selection-only bias; the k scores renormalised,
              times ``routed_scaling_factor``; this chip's experts
              (router ids 0 .. n_routed_experts - 1) on every token,
              masked by the router; plus the shared expert; relu^2 MLPs
    attention GQA, no rotary embedding, full causal softmax
    head      logits = h W + (h A_G^T) B_G / r_g + (h A_Lj^T) B_Lj / r_l
              + b_j, next-token log-likelihood over the vocabulary slice

    Z_G = (A_G, B_G, omega) ~ N(0, I);  Z_Lj | Z_G ~ N(0, exp(2 omega) I)

written the way the program states it (no normalising constants), so
the ELBOs compare number for number. The recurrence runs step by step,
in checkpointed blocks of ``SCAN_BLOCK`` steps; attention runs in
checkpointed blocks of ``QUERY_BLOCK`` query rows, each over every key;
each block is checkpointed, so the reference fits beside the optimizer
state on one chip.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

LOCAL_MEAN = "mu"  # q(Z_L) = N(mu, diag sigma^2), no coupling to Z_G
SCAN_BLOCK = 64
QUERY_BLOCK = 256
EPS = 1e-5


# -- shapes -------------------------------------------------------------------


def arch(cfg):
    """The widths the formulas below read, from the config file's keys."""
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    return {
        "d": cfg["hidden_size"], "V": cfg["vocab_size"],
        "pattern": cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]],
        "H": H, "P": P, "di": H * P, "N": cfg["ssm_state_size"],
        "G": cfg["n_groups"], "conv": cfg["conv_kernel"],
        "chunk": cfg["chunk_size"],
        "E": cfg["n_routed_experts"], "R": cfg["router_experts"],
        "k": cfg["num_experts_per_tok"], "f": cfg["moe_intermediate_size"],
        "fs": cfg["moe_shared_expert_intermediate_size"],
        "scale": cfg["routed_scaling_factor"],
        "Hq": cfg["num_attention_heads"], "KV": cfg["num_key_value_heads"],
        "hd": cfg["head_dim"],
        "rg": cfg["bayes"]["global_rank"], "rl": cfg["bayes"]["local_rank"],
        "S": cfg["seq_len"],
    }


def dims(cfg):
    a = arch(cfg)
    d, V = a["d"], a["V"]
    return {"global": a["rg"] * (d + V) + 1,
            "local": (a["rl"] * (d + V) + V,)}


def rows_per_silo(cfg):
    """N_j: the silo's sequences."""
    return cfg["seqs_per_silo"]


def program_model(cfg):
    """The system under test's model at this configuration's widths."""
    from repro.models.backbone import bayes

    return bayes.sfvi_problem(program_arch(cfg))


def program_arch(cfg):
    """The program's ArchConfig with this configuration's widths."""
    import dataclasses

    from repro.configs import get_config
    from repro.models.backbone.config import BayesConfig

    a = arch(cfg)
    return dataclasses.replace(
        get_config("nemotron3-nano-30b-a3b"),
        num_layers=cfg["num_hidden_layers"], d_model=a["d"],
        num_heads=a["Hq"], num_kv_heads=a["KV"], head_dim=a["hd"],
        d_ff=a["f"], vocab_size=a["V"], num_experts=a["E"],
        num_experts_per_tok=a["k"], d_expert=a["f"], router_experts=a["R"],
        routed_scaling=a["scale"], d_shared_expert=a["fs"],
        ssm_state=a["N"], ssm_conv=a["conv"], ssm_heads=a["H"],
        ssm_head_dim=a["P"], ssm_groups=a["G"], ssm_chunk=a["chunk"],
        layer_pattern=cfg["hybrid_override_pattern"], norm_eps=EPS,
        bayes=BayesConfig(global_rank=a["rg"], local_rank=a["rl"]))


# -- data and initial state ------------------------------------------------------


def _per_config(build):
    """One jitted generator per configuration (each run makes its inputs
    twice: for the program, and again for the reference)."""
    cached = functools.lru_cache(maxsize=4)(
        lambda text: jax.jit(build(json.loads(text))))
    return lambda key, cfg: cached(json.dumps(cfg, sort_keys=True))(key)


@_per_config
def make_data(cfg):
    """``{"tokens": (J, seqs, S + 1) int32}``: silo j draws ranks from
    Zipf(zipf_s) over the slice and maps them through its own seeded
    permutation of the slice's ids, so each silo's frequent tokens (and
    so its routing) differ. Called as ``make_data(key, cfg)``."""
    J, n, S, V = (cfg["num_silos"], cfg["seqs_per_silo"], cfg["seq_len"],
                  cfg["vocab_size"])

    def gen(key):
        kp, ku = jax.random.split(key)
        w = jnp.arange(1, V + 1, dtype=jnp.float32) ** -cfg["zipf_s"]
        cdf = jnp.cumsum(w / jnp.sum(w))
        u = jax.random.uniform(ku, (J, n, S + 1))
        rank = jnp.minimum(jnp.searchsorted(cdf, u), V - 1)
        perm = jax.vmap(lambda k: jax.random.permutation(k, V))(
            jax.random.split(kp, J))
        return {"tokens": jax.vmap(lambda p, r: p[r])(perm, rank)
                .astype(jnp.int32)}

    return gen


def _normal(key, shape, scale):
    return scale * jax.random.normal(key, shape, jnp.float32)


def init_layer(key, cfg, kind):
    a = arch(cfg)
    d = a["d"]
    ks = jax.random.split(key, 8)
    if kind == "M":
        di, G, N, H = a["di"], a["G"], a["N"], a["H"]
        conv_dim = di + 2 * G * N
        # dt log-uniform in [1e-3, 1e-1] (the config's time_step_min/max),
        # dt_bias its softplus inverse.
        dt = jnp.exp(jax.random.uniform(ks[3], (H,), jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        mixer = {
            "in_proj": _normal(ks[0], (d, 2 * di + 2 * G * N + H),
                               1 / math.sqrt(d)),
            "conv_w": _normal(ks[1], (a["conv"], conv_dim), 0.1),
            "conv_b": _normal(ks[2], (conv_dim,), 0.02),
            "A_log": jnp.log(jnp.linspace(1.0, 16.0, H, dtype=jnp.float32)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "D": jnp.ones((H,), jnp.float32),
            "out_norm": jnp.ones((di,), jnp.float32),
            "out_proj": _normal(ks[4], (di, d), 1 / math.sqrt(di)),
        }
        return {"norm1": jnp.ones((d,), jnp.float32), "mixer": mixer}
    if kind == "E":
        E, R, f, fs = a["E"], a["R"], a["f"], a["fs"]
        moe = {
            "router": _normal(ks[0], (d, R), 0.02),
            "router_bias": _normal(ks[1], (R,), 0.01),
            "w_up": _normal(ks[2], (E, d, f), 1 / math.sqrt(d)),
            "w_down": _normal(ks[3], (E, f, d), 1 / math.sqrt(f)),
            "shared": {"w_up": _normal(ks[4], (d, fs), 1 / math.sqrt(d)),
                       "w_down": _normal(ks[5], (fs, d), 1 / math.sqrt(fs))},
        }
        return {"norm1": jnp.ones((d,), jnp.float32), "moe": moe}
    hq, hk = a["Hq"] * a["hd"], a["KV"] * a["hd"]
    attn = {"wq": _normal(ks[0], (d, hq), 1 / math.sqrt(d)),
            "wk": _normal(ks[1], (d, hk), 1 / math.sqrt(d)),
            "wv": _normal(ks[2], (d, hk), 1 / math.sqrt(d)),
            "wo": _normal(ks[3], (hq, d), 1 / math.sqrt(hq))}
    return {"norm1": jnp.ones((d,), jnp.float32), "attn": attn}


@_per_config
def make_init(cfg):
    """(theta, eta_G, eta_L), on the device: theta in the program's
    layout (``embed``, ``tail/layer<i>``, ``final_norm``, ``lm_head``);
    variational means 0.01 * N(0, 1), log-scales -2. Called as
    ``make_init(key, cfg)``."""
    a = arch(cfg)
    d, V, J = a["d"], a["V"], cfg["num_silos"]
    dg, (dl,) = dims(cfg)["global"], dims(cfg)["local"]

    def gen(key):
        ke, kh, kl, kg, kz = jax.random.split(key, 5)
        theta = {
            "embed": {"tok": _normal(ke, (V, d), 0.02)},
            "final_norm": jnp.ones((d,), jnp.float32),
            "lm_head": _normal(kh, (d, V), 1 / math.sqrt(d)),
            "tail": {f"layer{i}": init_layer(jax.random.fold_in(kl, i), cfg, c)
                     for i, c in enumerate(a["pattern"])},
        }
        eta_G = {"mu": _normal(kg, (dg,), 0.01),
                 "log_sigma": jnp.full((dg,), -2.0, jnp.float32)}
        eta_L = {"mu": _normal(kz, (J, dl), 0.01),
                 "log_sigma": jnp.full((J, dl), -2.0, jnp.float32)}
        return theta, eta_G, eta_L

    return gen


# -- the reference densities ---------------------------------------------------


def rmsnorm(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * w


def _lin(mm, x, w):
    """x (..., k) @ w (k, n) through the 2-D ``mm``."""
    return mm(x.reshape(-1, x.shape[-1]), w).reshape(*x.shape[:-1], w.shape[-1])


def mamba2(a, p, u, mm):
    B, S, _ = u.shape
    di, G, N, H, P, K = a["di"], a["G"], a["N"], a["H"], a["P"], a["conv"]
    proj = _lin(mm, u, p["in_proj"])
    z, xbc, dt = jnp.split(proj, [di, 2 * di + 2 * G * N], axis=-1)
    xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(xp[:, i:i + S] * p["conv_w"][i] for i in range(K))
    xbc = jax.nn.silu(conv + p["conv_b"])
    x, Bm, Cm = jnp.split(xbc, [di, di + G * N], axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"])                  # (B, S, H)
    decay = jnp.exp(dt * -jnp.exp(p["A_log"]))                # (B, S, H)
    xh = x.reshape(B, S, H, P)
    rep = H // G
    Bh = jnp.repeat(Bm.reshape(B, S, G, N), rep, axis=2)      # (B, S, H, N)
    Ch = jnp.repeat(Cm.reshape(B, S, G, N), rep, axis=2)

    def step(state, inp):
        x_t, b_t, c_t, a_t, dt_t = inp
        state = (state * a_t[..., None, None]
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    @jax.checkpoint
    def block(state, inp):
        return jax.lax.scan(step, state, inp)

    nb = S // SCAN_BLOCK if S % SCAN_BLOCK == 0 else 1
    seq = [jnp.moveaxis(t, 1, 0) for t in (xh, Bh, Ch, decay, dt)]
    seq = [t.reshape(nb, S // nb, *t.shape[1:]) for t in seq]
    _, ys = jax.lax.scan(block, jnp.zeros((B, H, P, N), jnp.float32), seq)
    y = jnp.moveaxis(ys.reshape(S, B, H, P), 0, 1)
    y = (y + xh * p["D"][:, None]).reshape(B, S, di)
    g = (y * jax.nn.silu(z)).reshape(B, S, G, di // G)
    g = rmsnorm(g, p["out_norm"].reshape(G, di // G)).reshape(B, S, di)
    return _lin(mm, g, p["out_proj"])


def moe(a, p, x, mm):
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    scores = jax.nn.sigmoid(mm(xf, p["router"]))
    _, ids = jax.lax.top_k(scores + jax.lax.stop_gradient(p["router_bias"]),
                           a["k"])
    gates = jnp.take_along_axis(scores, ids, axis=-1)
    gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20) * a["scale"]
    y = mm(jnp.square(jax.nn.relu(mm(xf, p["shared"]["w_up"]))),
           p["shared"]["w_down"])
    for e in range(a["E"]):  # every held expert on every token, masked
        g_e = jnp.sum(jnp.where(ids == e, gates, 0.0), axis=-1)
        h = jnp.square(jax.nn.relu(mm(xf, p["w_up"][e])))
        y = y + g_e[:, None] * mm(h, p["w_down"][e])
    return y.reshape(B, S, d)


def attention(a, p, x, mm):
    B, S, _ = x.shape
    Hq, KV, hd = a["Hq"], a["KV"], a["hd"]
    q = _lin(mm, x, p["wq"]).reshape(B, S, Hq, hd).transpose(0, 2, 1, 3)
    k = _lin(mm, x, p["wk"]).reshape(B, S, KV, hd).transpose(0, 2, 1, 3)
    v = _lin(mm, x, p["wv"]).reshape(B, S, KV, hd).transpose(0, 2, 1, 3)
    k = jnp.repeat(k, Hq // KV, axis=1)                      # (B, Hq, S, hd)
    v = jnp.repeat(v, Hq // KV, axis=1)
    bmm = jax.vmap(jax.vmap(mm))
    qb = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S

    @jax.checkpoint
    def rows(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=2)
        s = bmm(qi, jnp.swapaxes(k, 2, 3)) / math.sqrt(hd)  # (B, Hq, qb, S)
        pos = i * qb + jnp.arange(qb)
        s = jnp.where(jnp.arange(S)[None, :] <= pos[:, None], s, -jnp.inf)
        return bmm(jax.nn.softmax(s, axis=-1), v)           # (B, Hq, qb, hd)

    o = jax.lax.map(rows, jnp.arange(S // qb))              # (nq, B, Hq, qb, hd)
    o = jnp.moveaxis(o, 0, 2).reshape(B, Hq, S, hd).transpose(0, 2, 1, 3)
    return _lin(mm, o.reshape(B, S, Hq * hd), p["wo"])


MIXERS = {"M": ("mixer", mamba2), "E": ("moe", moe), "*": ("attn", attention)}


def backbone(a, theta, tokens, mm):
    x = theta["embed"]["tok"][tokens]
    for i, kind in enumerate(a["pattern"]):
        name, fn = MIXERS[kind]

        @jax.checkpoint
        def block(p, x, fn=fn, name=name):
            return x + fn(a, p[name], rmsnorm(x, p["norm1"]), mm)

        x = block(theta["tail"][f"layer{i}"], x)
    return rmsnorm(x, theta["final_norm"])


def log_prior_global(cfg, theta, z_G, mm):
    del cfg, theta, mm
    return -0.5 * jnp.sum(z_G * z_G)


def log_local(cfg, theta, z_G, z_L, data_j, mm):
    a = arch(cfg)
    d, V, rg, rl = a["d"], a["V"], a["rg"], a["rl"]
    tokens = data_j["tokens"]
    h = backbone(a, theta, tokens[:, :-1], mm).reshape(-1, d)
    labels = tokens[:, 1:].reshape(-1)

    @jax.checkpoint
    def head(h, lm_head, z_G, z_L):
        A_G = z_G[:rg * d].reshape(rg, d)
        B_G = z_G[rg * d:rg * (d + V)].reshape(rg, V)
        A_L = z_L[:rl * d].reshape(rl, d)
        B_L = z_L[rl * d:rl * (d + V)].reshape(rl, V)
        logits = (mm(h, lm_head) + mm(mm(h, A_G.T), B_G) / rg
                  + mm(mm(h, A_L.T), B_L) / rl + z_L[rl * (d + V):])
        gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jnp.sum(gold - jax.nn.logsumexp(logits, axis=-1))

    omega = z_G[-1]
    prior = -0.5 * jnp.sum(z_L * z_L) * jnp.exp(-2.0 * omega) - z_L.size * omega
    return prior + head(h, theta["lm_head"], z_G, z_L)


# -- FLOPs ------------------------------------------------------------------------


def matmul_flops_per_silo_step(cfg, rows):
    """Matmul FLOPs of one forward and backward pass (3x the forward) of
    log_local over ``rows`` sequences, from shapes.

    Forward, per token: every projection (Mamba-2 in/out, router, shared
    expert, q/k/v/o, head and adapters); the routed experts at the
    expected held load k * E_held / router_experts per token; the SSD
    chunk matmuls of the program's chunked scan (intra-chunk scores and
    outputs, chunk states and their read-out); and the full S x S
    attention scores and weighted sum. Elementwise work, the recurrence
    and recomputation under remat are not counted.
    """
    a = arch(cfg)
    d, V, S = a["d"], a["V"], a["S"]
    T = rows * S
    di, G, N, H, P, C = a["di"], a["G"], a["N"], a["H"], a["P"], a["chunk"]
    mamba = (2 * T * d * (2 * di + 2 * G * N + H) + 2 * T * di * d
             + 2 * T * H * (C * N + C * P + 2 * N * P))
    experts = T * a["k"] * a["E"] / a["R"]
    moe_f = (2 * T * d * a["R"] + experts * 4 * d * a["f"]
             + 4 * T * d * a["fs"])
    hq, hk = a["Hq"] * a["hd"], a["KV"] * a["hd"]
    attn = (2 * T * d * (2 * hq + 2 * hk)
            + 4 * rows * S * S * hq)
    head = 2 * T * d * V + 2 * T * (a["rg"] + a["rl"]) * (d + V)
    counts = {"M": mamba, "E": moe_f, "*": attn}
    fwd = sum(counts[c] for c in a["pattern"]) + head
    return 3 * fwd


def expert_matmul_flops_per_silo_step(cfg, rows):
    """The routed experts' part of ``matmul_flops_per_silo_step``: up and
    down projections at the expected held load, forward and backward."""
    a = arch(cfg)
    T = rows * a["S"]
    per_layer = T * a["k"] * a["E"] / a["R"] * 4 * a["d"] * a["f"]
    return 3 * per_layer * a["pattern"].count("E")
