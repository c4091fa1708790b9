"""Architecture configuration.

One ``ArchConfig`` instance per assigned architecture (see repro/configs/).
``reduced()`` derives the CPU smoke-test variant (≤2 layers, d_model ≤ 512,
≤4 experts) from the same family, per the assignment's requirements.
"""
from __future__ import annotations

import dataclasses
from typing import Literal, Optional, Tuple

ArchType = Literal["dense", "moe", "ssm", "hybrid", "audio", "vlm"]
BlockKind = Literal["attn", "mamba2", "mlstm", "slstm", "moe"]

# One character per block of a ``layer_pattern`` (Nemotron-H's
# ``hybrid_override_pattern``): each block holds ONE sublayer behind its
# own pre-norm and residual.
PATTERN_KINDS = {"M": "mamba2", "E": "moe", "*": "attn"}


@dataclasses.dataclass(frozen=True)
class BayesConfig:
    """SFVI latent decomposition for LLM-scale models (``bayes.py``).

    θ   = backbone weights;
    Z_G = global Gaussian latent over a rank-r LM-head adapter;
    Z_L = per-silo latents (rank-r_l head adapter + logit bias).
    """

    global_rank: int = 8
    local_rank: int = 2
    local_bias: bool = True

    def global_dim(self, d_model: int, vocab: int) -> int:
        return self.global_rank * (d_model + vocab)

    def local_dim(self, d_model: int, vocab: int) -> int:
        d = self.local_rank * (d_model + vocab)
        if self.local_bias:
            d += vocab
        return d


@dataclasses.dataclass(frozen=True)
class PerfConfig:
    """Beyond-paper performance levers (EXPERIMENTS.md §Perf). All default
    OFF — the paper-faithful baseline; the dry-run's --optimized flag and
    the §Perf iterations turn them on one at a time."""

    masked_nll: bool = False   # gold-logit gather -> masked sum (shards over vocab)
    pad_vocab: bool = False    # pad embed/head vocab dim to a multiple of 256
    zero_opt: bool = False     # ZeRO: shard Adam state over the data axes
    act_shard: bool = False    # sequence-sharded activations between units
    microbatch: int = 0        # gradient accumulation over k microbatches
    pad_heads: int = 0         # pad ATTENTION ACTIVATIONS to a multiple of
                               # this head count (0=off; 16 = model axis) so
                               # the QK contraction shards on heads, not hd

    @property
    def any(self) -> bool:
        return any((self.masked_nll, self.pad_vocab, self.zero_opt,
                    self.act_shard, self.microbatch > 1, self.pad_heads > 0))


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    arch_type: ArchType
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None  # default d_model // num_heads

    # attention details
    qk_norm: bool = False
    rope_theta: float = 1e4
    mrope: bool = False  # Qwen2-VL multimodal RoPE
    sliding_window: Optional[int] = None  # enables long_500k for dense archs

    # MoE
    num_experts: int = 0
    num_experts_per_tok: int = 0
    d_expert: int = 0  # per-expert FFN width (olmoe: 1024)
    capacity_factor: float = 1.25
    # Dropless expert layer (``moe.moe_dropless``, the "E" blocks of a
    # ``layer_pattern``; relu^2 experts): the router scores
    # ``router_experts`` experts (0 = num_experts) and this device holds
    # the first ``num_experts`` of them; sigmoid scores with a
    # selection-only bias, top-k renormalised, times ``routed_scaling``.
    router_experts: int = 0
    routed_scaling: float = 1.0
    d_shared_expert: int = 0  # one always-on shared expert (0 = none)

    # SSM (mamba2)
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_heads: int = 0  # 0 = ssm_expand * d_model / ssm_head_dim
    ssm_groups: int = 1  # B/C groups; heads split evenly over them
    ssm_chunk: int = 256  # SSD chunk length

    # Per-block pattern (one sublayer per block, see PATTERN_KINDS); the
    # first ``num_layers`` characters are the blocks built.
    layer_pattern: str = ""
    attn_rope: bool = True

    # hybrid (zamba2): attention block period; others are mamba2
    hybrid_attn_period: int = 0  # 0 = not hybrid; e.g. 6 = every 6th block is attn
    shared_attn: bool = False  # zamba2: ONE attn block's weights reused at every period
    # xLSTM: sLSTM block period; others are mLSTM
    slstm_period: int = 0

    # enc-dec (whisper)
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0
    encoder_seq_len: int = 1500  # whisper 30 s of audio frames

    # VLM stub frontend
    num_vision_tokens: int = 0  # prepended patch embeddings

    # training details
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"

    # SFVI
    bayes: BayesConfig = dataclasses.field(default_factory=BayesConfig)

    # Roofline-analysis mode (launch/roofline.py): unroll the unit stack and
    # use unblocked attention so XLA cost_analysis counts every FLOP (scan
    # bodies are otherwise counted ONCE, not x trip-count).
    analysis_mode: bool = False

    # Performance levers (all off = paper-faithful baseline)
    perf: PerfConfig = dataclasses.field(default_factory=PerfConfig)

    # Execute attention/GLA through the Pallas kernels (kernels/): the TPU
    # hot path. On CPU the kernels run in interpret mode (correct, slow) —
    # smoke tests exercise it on small shapes; default remains the jnp path.
    use_pallas: bool = False

    source: str = ""  # paper/model-card citation

    # ------------------------------------------------------------------

    @property
    def head_dim_(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.num_heads

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def ssm_inner(self) -> int:
        """Mamba-2 inner width: heads x head_dim."""
        if self.ssm_heads:
            return self.ssm_heads * self.ssm_head_dim
        return self.ssm_expand * self.d_model

    @property
    def num_router_experts(self) -> int:
        return self.router_experts or self.num_experts

    @property
    def padded_vocab(self) -> int:
        """Embed/head table rows. §Perf lever 2: padding the vocab to a
        multiple of 256 makes the head matmul and the (B,S,V) logits
        shardable on any model-axis size (whisper: 51865 -> 52096)."""
        if self.perf.pad_vocab:
            return -(-self.vocab_size // 256) * 256
        return self.vocab_size

    def block_kind(self, layer_idx: int) -> BlockKind:
        """Which block family does layer ``layer_idx`` use?"""
        if self.layer_pattern:
            return PATTERN_KINDS[self.layer_pattern[layer_idx]]
        if self.arch_type == "hybrid" and self.hybrid_attn_period:
            return "attn" if (layer_idx % self.hybrid_attn_period) == (self.hybrid_attn_period - 1) else "mamba2"
        if self.arch_type == "ssm" and self.slstm_period:
            return "slstm" if (layer_idx % self.slstm_period) == (self.slstm_period - 1) else "mlstm"
        if self.arch_type == "ssm":
            return "mlstm"
        return "attn"

    @property
    def block_pattern(self) -> Tuple[BlockKind, ...]:
        """The repeating unit of block kinds (for scan-over-layers grouping)."""
        kinds = tuple(self.block_kind(i) for i in range(self.num_layers))
        return kinds

    def supports_long_context(self) -> bool:
        """long_500k eligibility: recurrent state or sliding window."""
        if self.is_encoder_decoder:
            return False  # cross-attention over a fixed 30 s encoder window
        if self.arch_type in ("ssm", "hybrid"):
            return True
        return self.sliding_window is not None

    def long_context_variant(self) -> ArchConfig:
        """Sub-quadratic variant used for long_500k: dense archs get a
        sliding window (block-sparse-in-time attention); SSM/hybrid archs
        are already O(1)-state and return themselves."""
        if self.arch_type in ("ssm", "hybrid") or self.sliding_window is not None:
            return self
        return dataclasses.replace(
            self, name=self.name + "-swa", sliding_window=8192
        )

    def reduced(self) -> ArchConfig:
        """CPU smoke-test variant of the same family.

        A ``layer_pattern`` model keeps its pattern's first period (up to
        and including the first attention block), so every block kind is
        present; every width is cut to a CPU size.
        """
        if self.layer_pattern:
            return dataclasses.replace(
                self,
                name=self.name + "-smoke",
                num_layers=min(self.num_layers,
                               self.layer_pattern.index("*") + 1),
                d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
                vocab_size=256,
                num_experts=min(self.num_experts, 4),
                router_experts=min(self.num_router_experts, 16),
                num_experts_per_tok=min(self.num_experts_per_tok, 3),
                d_expert=32, d_shared_expert=48 if self.d_shared_expert else 0,
                ssm_state=16, ssm_heads=8, ssm_head_dim=8,
                ssm_groups=min(self.ssm_groups, 2), ssm_chunk=8,
                dtype="float32",
                bayes=BayesConfig(global_rank=2, local_rank=1),
            )
        return dataclasses.replace(
            self,
            name=self.name + "-smoke",
            num_layers=2,
            d_model=min(self.d_model, 128),
            num_heads=4,
            num_kv_heads=min(max(1, self.num_kv_heads * 4 // self.num_heads), 4),
            head_dim=32,
            d_ff=min(self.d_ff, 256) if self.d_ff else 0,
            vocab_size=min(self.vocab_size, 512),
            num_experts=min(self.num_experts, 4) if self.num_experts else 0,
            num_experts_per_tok=min(self.num_experts_per_tok, 2) if self.num_experts_per_tok else 0,
            d_expert=min(self.d_expert, 64) if self.d_expert else 0,
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            ssm_head_dim=32 if self.ssm_state else 64,
            sliding_window=min(self.sliding_window, 128) if self.sliding_window else None,
            hybrid_attn_period=min(self.hybrid_attn_period, 2) if self.hybrid_attn_period else 0,
            slstm_period=2 if self.slstm_period else 0,
            num_encoder_layers=min(self.num_encoder_layers, 2),
            encoder_seq_len=min(self.encoder_seq_len, 64),
            num_vision_tokens=min(self.num_vision_tokens, 16) if self.num_vision_tokens else 0,
            dtype="float32",
            bayes=BayesConfig(global_rank=2, local_rank=1),
        )


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: Literal["train", "prefill", "decode"]


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}
