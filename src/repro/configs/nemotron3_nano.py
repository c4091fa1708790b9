"""NVIDIA Nemotron-3-Nano-30B-A3B — hybrid Mamba-2 / MoE / GQA stack
[hf:nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, config.json].

52 single-sublayer blocks laid out by ``hybrid_override_pattern``
(M = Mamba-2, E = MoE, * = attention). d_model=2688, vocab=131072,
untied head, RMSNorm eps 1e-5. Mamba-2: 64 heads x 64 (inner width
4096), state 128, 8 B/C groups, conv 4 with bias, chunk 128. MoE: 128
routed relu^2 experts of width 1856, top-6, normalised top-k times 2.5,
one shared expert of width 3712. Attention: GQA 32 query / 2 KV heads of
128.

Two equations the config does not fix, chosen here (and by every
reference in the repository alike): the router follows DeepSeek-V3
(sigmoid scores, a selection-only correction bias, top-6 renormalised,
times ``routed_scaling_factor``), and attention applies no rotary
embedding (the config carries ``rope_theta`` but no switch; Nemotron-H's
attention blocks take their positions from the Mamba-2 blocks).
"""
from repro.models.backbone.config import ArchConfig

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

CONFIG = ArchConfig(
    name="nemotron3-nano-30b-a3b",
    arch_type="hybrid",
    num_layers=52,
    d_model=2688,
    num_heads=32,
    num_kv_heads=2,
    head_dim=128,
    d_ff=1856,
    vocab_size=131072,
    num_experts=128,
    num_experts_per_tok=6,
    d_expert=1856,
    router_experts=128,
    routed_scaling=2.5,
    d_shared_expert=3712,
    ssm_state=128,
    ssm_conv=4,
    ssm_heads=64,
    ssm_head_dim=64,
    ssm_groups=8,
    ssm_chunk=128,
    layer_pattern=PATTERN,
    attn_rope=False,
    norm_eps=1e-5,
    dtype="float32",
    source="hf:nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16",
)
