"""Backbone assembly: one module covering all six assigned arch families.

Layer stacking uses **scan-over-units**: the layer list is grouped into its
repeating unit (dense: [attn]; zamba2: [mamba2 x5, attn]; xlstm:
[mlstm x7, slstm]); parameters are stacked with a leading ``n_units`` axis
and the stack is applied with ``jax.lax.scan`` (+ remat in training). This
keeps the HLO size O(unit) instead of O(num_layers) — essential for the
40 x 2-mesh dry-run compiles — and matches how MaxText-class frameworks
lower deep stacks.

Three entry modes share the block code:
  * ``forward``      — full-sequence teacher-forced logits (train).
  * ``prefill``      — full sequence, returns logits + decode cache.
  * ``decode_step``  — ONE token against the cache (serve_step for
                       decode_32k / long_500k).

Whisper (enc-dec) adds a bidirectional encoder over stub frame embeddings
and cross-attention in each decoder block; the cross K/V are computed once
at prefill and stored in the cache. Qwen2-VL prepends stub patch
embeddings and uses M-RoPE positions.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as _P

from repro.models.backbone.attention import (
    attention_block,
    attention_decode,
    attention_prefill,
    attn_init,
    cross_attention,
    cross_attn_init,
    init_kv_cache,
)
from repro.models.backbone.config import ArchConfig
from repro.models.backbone.layers import (
    dense_init,
    embed,
    embed_init,
    mlp,
    mlp_init,
    mrope_positions,
    mrope_text_start,
    rmsnorm,
    rmsnorm_init,
)
from repro.models.backbone.moe import (
    moe_block,
    moe_block_dense,
    moe_dropless,
    moe_dropless_init,
    moe_init,
)
from repro.models.backbone.ssm import (
    mamba2_block,
    mamba2_decode,
    mamba2_init,
    mamba2_init_cache,
    mamba2_prefill,
)
from repro.models.backbone.xlstm import (
    mlstm_block,
    mlstm_decode,
    mlstm_init,
    mlstm_init_cache,
    mlstm_prefill,
    slstm_block,
    slstm_decode,
    slstm_init,
    slstm_init_cache,
    slstm_prefill,
)

PyTree = Any


# ---------------------------------------------------------------------------
# Stacking structure
# ---------------------------------------------------------------------------

def unit_structure(cfg: ArchConfig) -> Tuple[Tuple[str, ...], int, Tuple[str, ...]]:
    """(unit_pattern, n_units, tail_pattern).

    A ``layer_pattern`` model has no repeating unit of equal blocks: every
    block is its own layer, applied unrolled (and, in training, remat'd
    one block at a time).
    """
    pattern = cfg.block_pattern
    if cfg.layer_pattern:
        return (), 0, pattern
    period = cfg.hybrid_attn_period or cfg.slstm_period or 1
    if period <= 1:
        return (pattern[0],), len(pattern), ()
    n_units = len(pattern) // period
    unit = pattern[:period]
    tail = pattern[n_units * period :]
    return unit, n_units, tail


def _block_init(key, cfg: ArchConfig, kind: str, decoder: bool = False) -> PyTree:
    ks = jax.random.split(key, 4)
    dtype = jnp.dtype(cfg.dtype)
    if kind == "moe":
        return {"norm1": rmsnorm_init(cfg.d_model, dtype),
                "moe": moe_dropless_init(ks[0], cfg)}
    if kind == "attn" and cfg.layer_pattern:
        return {"norm1": rmsnorm_init(cfg.d_model, dtype),
                "attn": attn_init(ks[0], cfg)}
    if kind == "attn":
        if cfg.arch_type == "hybrid" and cfg.shared_attn:
            # Weights live in params["shared_attn"]; block carries only norms.
            return {"norm1": rmsnorm_init(cfg.d_model, dtype)}
        p = {
            "norm1": rmsnorm_init(cfg.d_model, dtype),
            "attn": attn_init(ks[0], cfg),
            "norm2": rmsnorm_init(cfg.d_model, dtype),
        }
        if cfg.is_moe:
            p["moe"] = moe_init(ks[1], cfg)
        elif cfg.d_ff:
            p["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff, dtype)
        if decoder and cfg.is_encoder_decoder:
            p["norm_x"] = rmsnorm_init(cfg.d_model, dtype)
            p["xattn"] = cross_attn_init(ks[2], cfg)
        return p
    if kind == "mamba2":
        return {"norm1": rmsnorm_init(cfg.d_model, dtype), "mixer": mamba2_init(ks[0], cfg)}
    if kind == "mlstm":
        return {"norm1": rmsnorm_init(cfg.d_model, dtype), "mixer": mlstm_init(ks[0], cfg)}
    if kind == "slstm":
        return {"norm1": rmsnorm_init(cfg.d_model, dtype), "mixer": slstm_init(ks[0], cfg)}
    raise ValueError(kind)


def init_params(key, cfg: ArchConfig) -> PyTree:
    unit, n_units, tail = unit_structure(cfg)
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": embed_init(keys[0], cfg.padded_vocab, cfg.d_model, dtype),
        "final_norm": rmsnorm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[1], cfg.d_model, cfg.padded_vocab, dtype)
    decoder = cfg.is_encoder_decoder
    # Stacked unit params: vmap the initializer over n_units keys.
    unit_params = {}
    for s, kind in enumerate(unit):
        unit_keys = jax.random.split(jax.random.fold_in(keys[2], s), n_units)
        unit_params[f"slot{s}"] = jax.vmap(
            lambda k: _block_init(k, cfg, kind, decoder)
        )(unit_keys)
    params["units"] = unit_params
    params["tail"] = {
        f"layer{i}": _block_init(jax.random.fold_in(keys[3], i), cfg, kind, decoder)
        for i, kind in enumerate(tail)
    }
    if cfg.arch_type == "hybrid" and cfg.shared_attn:
        shared = {
            "attn": attn_init(keys[4], cfg),
            "norm2": rmsnorm_init(cfg.d_model, dtype),
        }
        if cfg.d_ff:
            shared["mlp"] = mlp_init(keys[5], cfg.d_model, cfg.d_ff, dtype)
        params["shared_attn"] = shared
    if cfg.is_encoder_decoder:
        enc_keys = jax.random.split(keys[6], cfg.num_encoder_layers)
        params["encoder"] = {
            "blocks": jax.vmap(lambda k: _block_init(k, cfg, "attn", decoder=False))(
                enc_keys
            ),
            "final_norm": rmsnorm_init(cfg.d_model, dtype),
        }
    if cfg.num_vision_tokens:
        # Projector from the (stubbed) vision encoder's embedding space.
        params["vision_proj"] = dense_init(keys[7], cfg.d_model, cfg.d_model, dtype)
    return params


def param_count(params: PyTree) -> int:
    return sum(int(jnp.size(x)) for x in jax.tree_util.tree_leaves(params))


# ---------------------------------------------------------------------------
# Block application (shared across modes)
# ---------------------------------------------------------------------------

def _apply_block(
    p: PyTree,
    shared: Optional[PyTree],
    cfg: ArchConfig,
    kind: str,
    x: jnp.ndarray,
    positions,
    mode: str,
    cache: Optional[PyTree],
    memory: Optional[jnp.ndarray],
    causal: bool = True,
):
    """Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache = cache
    if kind == "moe":
        h = rmsnorm(x, p["norm1"], cfg.norm_eps)
        return x + moe_dropless(p["moe"], cfg, h), new_cache, aux
    if kind == "attn":
        attn_p = shared if (shared is not None) else p
        h = rmsnorm(x, p["norm1"], cfg.norm_eps)
        with jax.named_scope("attn"):
            if mode == "train":
                y = attention_block(attn_p["attn"], cfg, h, positions,
                                    causal=causal)
            elif mode == "prefill":
                y, new_attn_cache = attention_prefill(attn_p["attn"], cfg, h,
                                                      positions)
            else:  # decode
                y, new_attn_cache = attention_decode(
                    attn_p["attn"], cfg, h, cache["attn"], positions
                )
        x = x + y
        if mode != "train":
            new_cache = dict(cache) if cache is not None else {}
            new_cache["attn"] = new_attn_cache
        if cfg.is_encoder_decoder and memory is not None:
            hx = rmsnorm(x, p["norm_x"], cfg.norm_eps)
            x = x + cross_attention(p["xattn"], cfg, hx, memory)
        ffn_p = attn_p if (shared is not None) else p
        if "moe" in ffn_p or "mlp" in ffn_p:
            h2 = rmsnorm(x, ffn_p["norm2"] if shared is None else shared["norm2"], cfg.norm_eps)
            if "moe" in ffn_p:
                if mode == "decode":
                    y2, a = moe_block_dense(ffn_p["moe"], cfg, h2)
                else:
                    y2, a = moe_block(ffn_p["moe"], cfg, h2)
                aux = aux + a
            else:
                y2 = mlp(ffn_p["mlp"], h2)
            x = x + y2
        return x, new_cache, aux

    h = rmsnorm(x, p["norm1"], cfg.norm_eps)
    mixer = p["mixer"]
    if kind == "mamba2":
        fns = (mamba2_block, mamba2_prefill, mamba2_decode)
    elif kind == "mlstm":
        fns = (mlstm_block, mlstm_prefill, mlstm_decode)
    elif kind == "slstm":
        fns = (slstm_block, slstm_prefill, slstm_decode)
    else:
        raise ValueError(kind)
    if mode == "train":
        y = fns[0](mixer, cfg, h)
    elif mode == "prefill":
        y, new_cache = fns[1](mixer, cfg, h)
    else:
        y, new_cache = fns[2](mixer, cfg, h, cache)
    return x + y, new_cache, aux


def _init_block_cache(params_block, cfg, kind, batch, max_len, dtype):
    if kind == "attn":
        return {"attn": init_kv_cache(cfg, batch, max_len, dtype)}
    if kind == "moe":
        return {}
    if kind == "mamba2":
        return mamba2_init_cache(params_block, cfg, batch, dtype)
    if kind == "mlstm":
        return mlstm_init_cache(params_block, cfg, batch, dtype)
    if kind == "slstm":
        return slstm_init_cache(params_block, cfg, batch, dtype)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Stack application: scan over units + unrolled tail
# ---------------------------------------------------------------------------

def _apply_stack(params, cfg, x, positions, mode, caches, memory, remat=False):
    """caches: {"units": {slotS: stacked cache}, "tail": {layerI: cache}} or None."""
    unit, n_units, tail = unit_structure(cfg)
    shared = params.get("shared_attn")
    aux_total = jnp.zeros((), jnp.float32)
    new_caches: Dict[str, Any] = {"units": {}, "tail": {}}

    def unit_fn(x, unit_params, unit_caches):
        aux = jnp.zeros((), jnp.float32)
        out_caches = {}
        for s, kind in enumerate(unit):
            c = unit_caches.get(f"slot{s}") if unit_caches else None
            sh = shared if (kind == "attn" and shared is not None) else None
            x, nc, a = _apply_block(
                unit_params[f"slot{s}"], sh, cfg, kind, x, positions, mode, c, memory
            )
            aux = aux + a
            if nc is not None:
                out_caches[f"slot{s}"] = nc
        if cfg.perf.act_shard and mode == "train":
            # §Perf lever 4 (Megatron sequence parallelism): activations
            # between units live sequence-sharded on the model axis, so the
            # per-unit tensor saved for backward is 1/model_size the size
            # and the TP all-reduce splits into reduce-scatter + all-gather.
            x = jax.lax.with_sharding_constraint(x, _P(None, "model", None))
        return x, out_caches, aux

    if n_units == 0:
        pass
    elif n_units == 1 or cfg.analysis_mode:
        # Unrolled path: exact per-layer FLOP counting for the roofline
        # analysis compiles (scan bodies are counted once by XLA cost
        # analysis), and trivially correct for single-unit stacks.
        uc_stacked = (caches or {}).get("units") if caches else None
        fn = jax.checkpoint(unit_fn) if remat else unit_fn
        outs = []
        for i in range(n_units):
            up = jax.tree_util.tree_map(lambda a: a[i], params["units"])
            ucc = (
                jax.tree_util.tree_map(lambda a: a[i], uc_stacked)
                if uc_stacked
                else None
            )
            x, out_c, aux = fn(x, up, ucc)
            aux_total += aux
            outs.append(out_c)
        new_caches["units"] = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *outs
        )
    else:
        def scan_body(carry, xs):
            x, aux = carry
            unit_params, unit_caches = xs
            fn = jax.checkpoint(unit_fn) if remat else unit_fn
            x, out_c, a = fn(x, unit_params, unit_caches)
            return (x, aux + a), out_c

        unit_caches_stacked = (caches or {}).get("units") if caches else None
        if unit_caches_stacked is None:
            # lax.scan needs a pytree with a leading axis; use per-unit None
            # via a dummy zeros array so the tree structure is static.
            unit_caches_stacked = {"_none": jnp.zeros((n_units,), jnp.float32)}

            def unit_fn_nocache(x, unit_params, _):
                return unit_fn(x, unit_params, None)

            def scan_body(carry, xs):  # noqa: F811 — cache-free variant
                x, aux = carry
                unit_params, _dummy = xs
                fn = jax.checkpoint(unit_fn_nocache) if remat else unit_fn_nocache
                x, out_c, a = fn(x, unit_params, None)
                return (x, aux + a), out_c

        (x, aux_total), out_caches = jax.lax.scan(
            scan_body, (x, aux_total), (params["units"], unit_caches_stacked)
        )
        new_caches["units"] = out_caches

    for i, kind in enumerate(tail):
        c = (caches or {}).get("tail", {}).get(f"layer{i}") if caches else None
        sh = shared if (kind == "attn" and shared is not None) else None

        def layer(p, x, c, sh=sh, kind=kind):
            return _apply_block(p, sh, cfg, kind, x, positions, mode, c, memory)

        if remat and cfg.layer_pattern:
            layer = jax.checkpoint(layer)
        x, nc, a = layer(params["tail"][f"layer{i}"], x, c)
        aux_total += a
        if nc is not None:
            new_caches["tail"][f"layer{i}"] = nc
    return x, new_caches, aux_total


# ---------------------------------------------------------------------------
# Inputs: embedding + positions (+ modality stubs)
# ---------------------------------------------------------------------------

def _embed_inputs(params, cfg, batch, pos_offset=0):
    """batch: {"tokens": (B,S), optional "vision": (B,nv,D)}.

    Returns (x, positions). For M-RoPE positions has shape (3,B,S')."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = embed(params["embed"], tokens)
    if cfg.num_vision_tokens and "vision" in batch:
        vis = batch["vision"].astype(x.dtype) @ params["vision_proj"]
        x = jnp.concatenate([vis, x], axis=1)
        S_total = x.shape[1]
        if cfg.mrope:
            positions = mrope_positions(B, S_total, cfg.num_vision_tokens)
        else:
            positions = jnp.broadcast_to(jnp.arange(S_total)[None], (B, S_total))
        return x, positions
    if cfg.mrope:
        positions = mrope_positions(B, S, 0)
    else:
        positions = jnp.broadcast_to(
            (pos_offset + jnp.arange(S))[None], (B, S)
        )
    return x, positions


def _logits(params, cfg, h):
    out = (h @ params["embed"]["tok"].T) if cfg.tie_embeddings else (
        h @ params["lm_head"])
    if cfg.padded_vocab != cfg.vocab_size:
        # Padding columns must never win softmax/argmax.
        col = jax.lax.broadcasted_iota(jnp.int32, out.shape, out.ndim - 1)
        out = jnp.where(col < cfg.vocab_size, out, -1e30)
    return out


def encode(params, cfg, frames):
    """Whisper encoder over stub frame embeddings (B, S_enc, D)."""
    x = frames.astype(jnp.dtype(cfg.dtype))
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    blocks = params["encoder"]["blocks"]

    def body(x, p):
        x, _, _ = _apply_block(
            p, None, cfg, "attn", x, positions, "train", None, None, causal=False
        )
        return x, None

    if cfg.analysis_mode:
        for i in range(cfg.num_encoder_layers):
            x, _ = body(x, jax.tree_util.tree_map(lambda a: a[i], blocks))
    else:
        x, _ = jax.lax.scan(body, x, blocks)
    return rmsnorm(x, params["encoder"]["final_norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def forward(params, cfg: ArchConfig, batch, remat: bool = True):
    """Teacher-forced logits. Returns (logits, aux_loss, h_final)."""
    memory = None
    if cfg.is_encoder_decoder:
        memory = encode(params, cfg, batch["frames"])
    x, positions = _embed_inputs(params, cfg, batch)
    x, _, aux = _apply_stack(params, cfg, x, positions, "train", None, memory, remat=remat)
    h = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if cfg.num_vision_tokens and "vision" in batch:
        h = h[:, cfg.num_vision_tokens :]  # loss only over text positions
    return _logits(params, cfg, h), aux, h


def init_cache(params, cfg: ArchConfig, batch: int, max_len: int):
    """Zero-initialized decode cache (for decode-only lowering)."""
    unit, n_units, tail = unit_structure(cfg)
    dtype = jnp.dtype(cfg.dtype)
    caches: Dict[str, Any] = {"units": {}, "tail": {}}
    for s, kind in enumerate(unit):
        one = _init_block_cache(
            jax.tree_util.tree_map(lambda a: a[0], params["units"][f"slot{s}"]),
            cfg, kind, batch, max_len, dtype,
        )
        caches["units"][f"slot{s}"] = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (n_units,) + a.shape), one
        )
    for i, kind in enumerate(tail):
        caches["tail"][f"layer{i}"] = _init_block_cache(
            params["tail"][f"layer{i}"], cfg, kind, batch, max_len, dtype
        )
    if cfg.is_encoder_decoder:
        caches["memory"] = jnp.zeros((batch, cfg.encoder_seq_len, cfg.d_model), dtype)
    caches["t"] = jnp.zeros((), jnp.int32)  # absolute token counter (incl. vision)
    return caches


def prefill(params, cfg: ArchConfig, batch, max_len: int):
    """Full-sequence prefill. Returns (last-position logits, cache)."""
    memory = None
    if cfg.is_encoder_decoder:
        memory = encode(params, cfg, batch["frames"])
    x, positions = _embed_inputs(params, cfg, batch)
    x, caches, _ = _apply_stack(params, cfg, x, positions, "prefill", None, memory)
    h = rmsnorm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    if cfg.is_encoder_decoder:
        caches["memory"] = memory
    # Right-size attention caches to max_len ring buffers.
    caches = _resize_attn_caches(params, cfg, caches, max_len)
    caches["t"] = jnp.asarray(x.shape[1], jnp.int32)
    return _logits(params, cfg, h), caches, h


def _resize_attn_caches(params, cfg, caches, max_len):
    """Pad prefill KV caches out to the serving ring-buffer length."""
    def fix(c):
        if not (isinstance(c, dict) and set(c) >= {"k", "v", "pos"}):
            return c
        window = cfg.sliding_window
        cur_len = c["k"].shape[-3]
        # Non-windowed caches must never truncate (e.g. vision-prefix tokens).
        target = min(window, max_len) if window else max(max_len, cur_len)
        def pad_to(a):
            cur = a.shape[-3]
            if cur >= target:
                # Keep the last ``target`` keys AND place each absolute
                # position p at ring slot p % target so subsequent decode
                # writes (slot = pos % target) overwrite the oldest entry.
                kept = a[..., cur - target :, :, :]
                shift = (cur - target) % target if target else 0
                return jnp.roll(kept, shift=shift, axis=-3)
            pad = [(0, 0)] * a.ndim
            pad[-3] = (0, target - cur)
            return jnp.pad(a, pad)
        return {"k": pad_to(c["k"]), "v": pad_to(c["v"]), "pos": c["pos"]}

    def walk(tree):
        if isinstance(tree, dict):
            if set(tree) >= {"k", "v", "pos"}:
                return fix(tree)
            return {k: walk(v) for k, v in tree.items()}
        return tree

    return walk(caches)


def decode_step(params, cfg: ArchConfig, tokens, caches):
    """One decode step. tokens: (B, 1). Returns (logits (B,1,V), new caches)."""
    memory = caches.get("memory") if cfg.is_encoder_decoder else None
    x = embed(params["embed"], tokens)
    t = caches["t"]
    if cfg.mrope:
        # Text M-RoPE position: start + (t - num_vision); all 3 channels equal.
        p = mrope_text_start(cfg.num_vision_tokens) + t - cfg.num_vision_tokens
        positions = jnp.broadcast_to(p, (3, tokens.shape[0], 1)).astype(jnp.int32)
    else:
        positions = None  # attention_decode derives positions from cache["pos"]
    x, new_caches, _ = _apply_stack(params, cfg, x, positions, "decode", caches, memory)
    if cfg.is_encoder_decoder:
        new_caches["memory"] = memory
    new_caches["t"] = t + 1
    h = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return _logits(params, cfg, h), new_caches, h
