"""Mixture-of-Experts layers.

Two formulations:

* ``moe_block`` — top-k softmax routing with capacity-based einsum
  dispatch (the classic shard-friendly formulation of GShard / Switch /
  t5x), used by olmoe and phi3.5-moe;
* ``moe_dropless`` — the expert layer of an expert-parallel deployment,
  used by Nemotron-H: it is told which experts it holds, routes every
  token over ALL experts (DeepSeek-V3 rule: sigmoid scores, a
  selection-only correction bias, top-k renormalised, times
  ``routed_scaling``), and computes only its held experts' gated part,
  grouping tokens by held expert for ``jax.lax.ragged_dot`` and dropping
  none; plus the always-on shared expert. Experts are relu^2 MLPs.

Capacity path — the expert dimension E is sharded along the
``model`` mesh axis (expert parallelism); tokens arrive sharded along
``data``. The dispatch einsum reshards (groups@data, E, C, D) ->
(E@model, ...) — XLA SPMD lowers that resharding to the all-to-all that a
hand-written torch/NCCL MoE would issue explicitly. Router logits and
load-balance statistics are computed where the tokens live, so per-silo
routing information never crosses the silo boundary (the paper's privacy
structure extends to the router).

Groups are sequence chunks of ``group_size`` tokens; capacity is
``group_size * top_k / E * capacity_factor``. Tokens overflowing an
expert's capacity within their group are dropped (standard GShard
behaviour) — the residual path carries them unchanged.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from repro.models.backbone.layers import dense_init


def moe_init(key, cfg):
    d = cfg.d_model
    E = cfg.num_experts
    dff = cfg.d_expert if cfg.d_expert else cfg.d_ff
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    return {
        "router": dense_init(ks[0], d, E, jnp.float32, scale=0.02),
        "w_gate": (s * jax.random.normal(ks[1], (E, d, dff))).astype(dtype),
        "w_up": (s * jax.random.normal(ks[2], (E, d, dff))).astype(dtype),
        "w_down": ((1.0 / math.sqrt(dff)) * jax.random.normal(ks[3], (E, dff, d))).astype(dtype),
    }


def _route(router_w, x_flat, E: int, top_k: int):
    """Router probabilities + top-k assignment. x_flat: (T, D)."""
    logits = (x_flat.astype(jnp.float32) @ router_w).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)  # (T, k)
    # Renormalize the selected gates (standard for top-k > 1).
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    return probs, gate_vals, expert_idx


def load_balance_loss(probs: jnp.ndarray, expert_idx: jnp.ndarray, E: int) -> jnp.ndarray:
    """Switch-style auxiliary loss: E * <fraction routed to e> . <router prob e>."""
    T = probs.shape[0]
    counts = jnp.zeros((E,), jnp.float32).at[expert_idx.reshape(-1)].add(1.0)
    frac = counts / jnp.maximum(expert_idx.size, 1)
    mean_prob = probs.mean(axis=0)
    return E * jnp.sum(frac * mean_prob)


def _dispatch_masks(expert_idx, gate_vals, E: int, capacity: int):
    """Build (T, E, C) dispatch (bool->dtype) and combine (gated) tensors."""
    T, k = expert_idx.shape
    # Position of each (token, slot) in its expert's queue, computed per
    # expert via a masked cumulative sum over the flattened (T*k) order.
    flat_expert = expert_idx.reshape(-1)  # (T*k,)
    onehot = jax.nn.one_hot(flat_expert, E, dtype=jnp.float32)  # (T*k, E)
    pos_in_expert = (jnp.cumsum(onehot, axis=0) - onehot) * onehot  # (T*k, E)
    pos = pos_in_expert.sum(-1).astype(jnp.int32)  # (T*k,)
    keep = pos < capacity
    pos_oh = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)  # (T*k, C)
    disp = (onehot * keep[:, None].astype(jnp.float32))[:, :, None] * pos_oh[:, None, :]
    disp = disp.reshape(T, k, E, capacity).sum(axis=1)  # (T, E, C)
    comb = (
        (onehot * (gate_vals.reshape(-1)[:, None] * keep[:, None]))[:, :, None]
        * pos_oh[:, None, :]
    ).reshape(T, k, E, capacity).sum(axis=1)
    return disp, comb


def moe_block(params, cfg, x, group_size: int = 1024) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (y, aux_loss). Grouped capacity-based top-k MoE."""
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    gs = min(group_size, B * S)
    T = B * S
    assert T % gs == 0, f"tokens {T} not divisible by group {gs}"
    G = T // gs
    capacity = max(int(gs * k / E * cfg.capacity_factor), 1)
    xg = x.reshape(G, gs, D)

    probs, gate_vals, expert_idx = jax.vmap(
        lambda xf: _route(params["router"], xf, E, k)
    )(xg)
    aux = jax.vmap(lambda p, i: load_balance_loss(p, i, E))(probs, expert_idx).mean()

    disp, comb = jax.vmap(lambda ei, gv: _dispatch_masks(ei, gv, E, capacity))(
        expert_idx, gate_vals
    )  # (G, gs, E, C) each

    # Dispatch: (G,gs,E,C),(G,gs,D) -> (E, G, C, D). Expert-major layout so
    # the expert matmuls shard cleanly along the model axis.
    xe = jnp.einsum("gsec,gsd->egcd", disp.astype(x.dtype), xg)
    h = jnp.einsum("egcd,edf->egcf", xe, params["w_gate"])
    u = jnp.einsum("egcd,edf->egcf", xe, params["w_up"])
    y_e = jnp.einsum("egcf,efd->egcd", jax.nn.silu(h) * u, params["w_down"])
    y = jnp.einsum("gsec,egcd->gsd", comb.astype(x.dtype), y_e)
    return y.reshape(B, S, D), aux


def moe_block_dense(params, cfg, x) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Reference/decode path: every expert on every token, gate-weighted sum
    restricted to the top-k (no capacity drops). O(E/k) extra FLOPs — used
    for single-token decode (T = B is tiny) and as the correctness oracle
    for ``moe_block``."""
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    xf = x.reshape(-1, D)
    probs, gate_vals, expert_idx = _route(params["router"], xf, E, k)
    aux = load_balance_loss(probs, expert_idx, E)
    # Gate matrix (T, E): gate value where selected, else 0.
    gates = jnp.zeros_like(probs).at[
        jnp.arange(xf.shape[0])[:, None], expert_idx
    ].set(gate_vals)
    h = jnp.einsum("td,edf->etf", xf, params["w_gate"])
    u = jnp.einsum("td,edf->etf", xf, params["w_up"])
    y_e = jnp.einsum("etf,efd->etd", jax.nn.silu(h) * u, params["w_down"])
    y = jnp.einsum("te,etd->td", gates.astype(x.dtype), y_e)
    return y.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# Dropless expert layer (expert parallelism: this device's held experts)
# ---------------------------------------------------------------------------

def _batch_all(fn):
    """``fn`` with a vmap rule that maps it over the batch, one element
    after another.

    ``jax.lax.ragged_dot`` does not batch over operands that lack the batch
    axis (under the runtime's vmap over silos the expert weights are θ,
    shared by every silo), and the TPU compiler takes no batched ragged
    dot at all.
    """
    f = custom_vmap(fn)

    @f.def_vmap
    def rule(axis_size, in_batched, *args):
        args = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args, in_batched, strict=True)]
        out = jax.lax.map(lambda a: fn(*a), args)
        return out, jax.tree_util.tree_map(lambda _: True, out)

    return f


def _ragged(x, w, sizes):
    return jax.lax.ragged_dot(x, w, sizes, preferred_element_type=jnp.float32)


def _ragged_vjp(x, w, sizes, g):
    return jax.vjp(lambda x, w: _ragged(x, w, sizes), x, w)[1](g)


_ragged_fwd = _batch_all(_ragged)
_ragged_bwd = _batch_all(_ragged_vjp)


@jax.custom_vjp
def grouped_matmul(x, w, sizes):
    """(m, k) rows sorted by group x (G, k, n) -> (m, n): row block g of
    ``sizes[g]`` rows times ``w[g]``; rows past ``sum(sizes)`` are 0.

    ``jax.lax.ragged_dot`` with its own gradient, made batchable (the
    custom VJP keeps reverse mode under vmap, which a bare custom vmap
    rule does not).
    """
    return _ragged_fwd(x, w, sizes)


def _gm_fwd(x, w, sizes):
    return _ragged_fwd(x, w, sizes), (x, w, sizes)


def _gm_bwd(res, g):
    x, w, sizes = res
    dx, dw = _ragged_bwd(x, w, sizes, g)
    return dx, dw, None


grouped_matmul.defvjp(_gm_fwd, _gm_bwd)


def moe_dropless_init(key, cfg):
    """Router over ``cfg.num_router_experts`` experts, the weights of the
    ``cfg.num_experts`` held here, and the shared expert (if any)."""
    d, E, R = cfg.d_model, cfg.num_experts, cfg.num_router_experts
    f, fs = cfg.d_expert or cfg.d_ff, cfg.d_shared_expert
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 6)
    p = {
        "router": dense_init(ks[0], d, R, jnp.float32, scale=0.02),
        # The correction bias only steers selection; it gets no gradient
        # and stays at its seeded value.
        "router_bias": 0.01 * jax.random.normal(ks[1], (R,), jnp.float32),
        "w_up": ((1.0 / math.sqrt(d)) * jax.random.normal(ks[2], (E, d, f))).astype(dtype),
        "w_down": ((1.0 / math.sqrt(f)) * jax.random.normal(ks[3], (E, f, d))).astype(dtype),
    }
    if fs:
        p["shared"] = {"w_up": dense_init(ks[4], d, fs, dtype),
                       "w_down": dense_init(ks[5], fs, d, dtype)}
    return p


def route_sigmoid(params, cfg, xf):
    """Top-k experts of every token over all router experts.

    Returns (ids (T, k) int32, gates (T, k) f32): sigmoid scores, top-k
    by score + correction bias, the chosen scores renormalised to sum 1
    and times ``cfg.routed_scaling``.
    """
    scores = jax.nn.sigmoid(xf.astype(jnp.float32) @ params["router"])
    bias = jax.lax.stop_gradient(params["router_bias"])
    _, ids = jax.lax.top_k(scores + bias, cfg.num_experts_per_tok)
    gates = jnp.take_along_axis(scores, ids, axis=-1)
    gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return ids, gates * cfg.routed_scaling


def relu2_mlp(p, x):
    """down(relu(x up)^2): Nemotron-H's ungated MLP."""
    return jnp.square(jax.nn.relu(x @ p["w_up"])) @ p["w_down"]


def moe_dropless(params, cfg, x, held=None, shared: bool = True):
    """x (B, S, D) -> the held experts' gated part (+ the shared expert).

    ``held`` lists the router ids of the experts whose weights are in
    ``params`` (row e of ``w_up``/``w_down`` is expert ``held[e]``);
    default ``0 .. num_experts - 1``. Every (token, choice) pair routed to
    a held expert is computed — none is dropped — and pairs routed
    elsewhere contribute nothing here (their holders compute them).
    """
    B, S, D = x.shape
    xf = x.reshape(-1, D)
    E = params["w_up"].shape[0]
    k = cfg.num_experts_per_tok
    held = jnp.arange(E) if held is None else jnp.asarray(held, jnp.int32)
    with jax.named_scope("moe.route"):
        ids, gates = route_sigmoid(params, cfg, xf)
        hit = ids[..., None] == held  # (T, k, E)
        local = jnp.where(hit.any(-1), jnp.argmax(hit, axis=-1), E).reshape(-1)
        order = jnp.argsort(local, stable=True)
        sizes = jnp.sum(jax.nn.one_hot(local, E, dtype=jnp.int32), axis=0)
        tok = order // k
        live = (local[order] < E)[:, None]
    with jax.named_scope("moe.experts"):
        # Rows past the held groups are left undefined by the TPU kernel,
        # in the products and in their gradients: each is zeroed by a
        # select (never a multiply) before anything reads it.
        xs = jnp.where(live, xf[tok], 0.0)
        h = jnp.where(live, grouped_matmul(xs, params["w_up"], sizes), 0.0)
        h = jnp.square(jax.nn.relu(h)).astype(x.dtype)
        ys = jnp.where(live, grouped_matmul(h, params["w_down"], sizes), 0.0)
        ys = ys * gates.reshape(-1)[order][:, None]
        y = jnp.zeros((xf.shape[0], D), jnp.float32).at[tok].add(ys)
    if shared and "shared" in params:
        with jax.named_scope("moe.shared"):
            y = y + relu2_mlp(params["shared"], xf)
    return y.astype(x.dtype).reshape(B, S, D)
