"""Roofline-term extraction from a compiled (dry-run) artifact.

    compute term    = HLO_FLOPs      / (chips * peak FLOP/s)
    memory term     = HLO_bytes      / (chips * HBM bytes/s)
    collective term = collective_bytes / (chips * ICI bytes/s)

with the peaks of the target chip (``launch.mesh.chip_peaks``, keyed by
``device_kind``).

``cost_analysis()`` reports per-partition (per-device) FLOPs/bytes for an
SPMD executable, so the per-chip terms divide by peak directly; the
"chips" division is kept explicit for the global view. Collective bytes
are NOT in cost_analysis — we parse the optimized HLO and sum the result
sizes of every collective op (a standard proxy: all-reduce moves ~2x its
operand over the ring, all-gather/reduce-scatter ~1x the full result;
we apply per-op multipliers below).
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, Optional

from repro.launch.mesh import chip_peaks

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

# op -> (regex fragment, ring-traffic multiplier per byte of result)
_COLLECTIVES = {
    "all-reduce": 2.0,  # reduce-scatter + all-gather equivalent
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"=\s+((?:\([^)]*\)|[a-z0-9]+\[[\d,]*\][^\s]*))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Ring-traffic bytes per collective kind from optimized HLO text."""
    out = {k: 0.0 for k in _COLLECTIVES}
    for m in _OP_RE.finditer(hlo_text):
        shape_str, kind = m.group(1), m.group(2)
        out[kind] += _shape_bytes(shape_str) * _COLLECTIVES[kind]
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_chip: float
    bytes_per_chip: float
    coll_bytes_per_chip: float
    coll_breakdown: Dict[str, float]
    peak_bytes_per_chip: float  # memory_analysis: peak HBM
    model_flops: float  # 6*N*D (active) — analytic useful work, GLOBAL
    device_kind: str  # the target chip, keys launch.mesh.CHIP_PEAKS

    @property
    def t_compute(self) -> float:
        return self.flops_per_chip / chip_peaks(self.device_kind).flops_bf16

    @property
    def t_memory(self) -> float:
        return self.bytes_per_chip / chip_peaks(self.device_kind).hbm_bw

    @property
    def t_collective(self) -> float:
        return self.coll_bytes_per_chip / chip_peaks(self.device_kind).ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        total = self.flops_per_chip * self.chips
        return self.model_flops / total if total else 0.0

    def to_dict(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips, "device_kind": self.device_kind,
            "flops_per_chip": self.flops_per_chip,
            "bytes_per_chip": self.bytes_per_chip,
            "coll_bytes_per_chip": self.coll_bytes_per_chip,
            "coll_breakdown": self.coll_breakdown,
            "peak_bytes_per_chip": self.peak_bytes_per_chip,
            "model_flops": self.model_flops,
            "t_compute": self.t_compute,
            "t_memory": self.t_memory,
            "t_collective": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
        }


def analyze(compiled, arch: str, shape: str, mesh_name: str, chips: int,
            model_flops: float, device_kind: str) -> Roofline:
    ca = compiled.cost_analysis() or {}
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    coll = collective_bytes(compiled.as_text())
    mem = compiled.memory_analysis()
    peak = 0.0
    if mem is not None:
        peak = float(
            getattr(mem, "temp_size_in_bytes", 0)
            + getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0)
            - getattr(mem, "alias_size_in_bytes", 0)
        )
    return Roofline(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        flops_per_chip=flops, bytes_per_chip=byts,
        coll_bytes_per_chip=sum(coll.values()),
        coll_breakdown=coll, peak_bytes_per_chip=peak,
        model_flops=model_flops, device_kind=device_kind,
    )


# ---------------------------------------------------------------------------
# Analytic MODEL_FLOPS: 6 * N_active * D_tokens (decode: D = batch tokens)
# ---------------------------------------------------------------------------

def active_param_count(cfg) -> int:
    """Parameter count with MoE experts counted at top-k/E (active share)."""
    d, V = cfg.d_model, cfg.vocab_size
    hd = cfg.head_dim_
    n = V * d  # embed
    if not cfg.tie_embeddings:
        n += d * V
    per_attn = d * (cfg.num_heads * hd) + 2 * d * (cfg.num_kv_heads * hd) + (
        cfg.num_heads * hd) * d
    if cfg.is_moe:
        per_ffn = 3 * d * (cfg.d_expert or cfg.d_ff) * cfg.num_experts_per_tok
    else:
        per_ffn = 3 * d * cfg.d_ff if cfg.d_ff else 0
    d_inner = cfg.ssm_expand * d
    N = cfg.ssm_state
    per_mamba = d * (2 * d_inner + 2 * N + (d_inner // max(cfg.ssm_head_dim, 1))) + d_inner * d
    per_mlstm = d * 4 * d + (2 * d) * (2 * d) * 3 + 2 * d * d
    per_slstm = d * 4 * d + d * (4 * d // 3) * 2
    for i in range(cfg.num_layers):
        kind = cfg.block_kind(i)
        if kind == "attn":
            n += per_attn + per_ffn
        elif kind == "mamba2":
            n += per_mamba
        elif kind == "mlstm":
            n += per_mlstm
        elif kind == "slstm":
            n += per_slstm
    if cfg.is_encoder_decoder:
        n += cfg.num_encoder_layers * (per_attn + per_ffn)
        n += cfg.num_layers * 2 * d * (cfg.num_kv_heads * hd + cfg.num_heads * hd // 2)
    return n


def model_flops(cfg, shape) -> float:
    """Global useful FLOPs for one step: 6ND train, 2ND forward-only."""
    n_active = active_param_count(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch


# ---------------------------------------------------------------------------
# Scan-aware measurement: XLA cost_analysis counts scan bodies ONCE, so the
# production (scan-over-units) compile undercounts per-layer work. We compile
# two ANALYSIS variants (units unrolled, attention unblocked) at k=1 and k=2
# units and extrapolate linearly to the full depth:
#     f(n_units) = f1 + (n_units - 1) * (f2 - f1)
# which is exact for homogeneous unit stacks (it captures both per-layer
# compute/collectives and depth-scaling gradient reductions). Remaining
# in-scan work (the GLA cross-chunk state scan, the sLSTM time scan) is
# documented as a small undercount in EXPERIMENTS.md.
# ---------------------------------------------------------------------------

def _unit_period(cfg) -> int:
    return cfg.hybrid_attn_period or cfg.slstm_period or 1


def analysis_variant(cfg, k_units: int):
    import dataclasses

    period = _unit_period(cfg)
    tail = cfg.num_layers % period
    return dataclasses.replace(
        cfg, num_layers=k_units * period + tail, analysis_mode=True,
        name=f"{cfg.name}-analysis{k_units}",
    )


def _extract(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll": sum(coll.values()),
        "coll_breakdown": coll,
    }


def extrapolate(m1: Dict, m2: Dict, n_units: int) -> Dict[str, float]:
    out = {}
    for k in ("flops", "bytes", "coll"):
        out[k] = m1[k] + (n_units - 1) * (m2[k] - m1[k])
    out["coll_breakdown"] = {
        kk: m1["coll_breakdown"][kk]
        + (n_units - 1) * (m2["coll_breakdown"][kk] - m1["coll_breakdown"][kk])
        for kk in m1["coll_breakdown"]
    }
    # Guard against tiny negative extrapolations from fusion differences.
    for k in ("flops", "bytes", "coll"):
        out[k] = max(out[k], 0.0)
    return out


def save_results(path: str, rows) -> None:
    with open(path, "w") as f:
        json.dump([r.to_dict() for r in rows], f, indent=1)


def load_results(path: str):
    with open(path) as f:
        return json.load(f)
