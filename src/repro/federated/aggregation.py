"""Pluggable server-side aggregation and wire compression.

Every round the runtime gathers one pytree per silo (gradients for SFVI,
locally-updated parameters for SFVI-Avg), stacked along a leading silo
axis of size J. An :class:`Aggregator` turns that stack plus the round's
participation mask into a single *mean-like* estimate over the active
silos; the runtime rescales by J where the paper's algebra needs the sum
Σ_j (unbiased under partial participation, §3 Remark).

A :class:`Compressor` sits on the silo→server edge: silos ``encode`` the
shipped pytree before the ``all_gather`` and the server ``decode``s after
it, so the collective itself moves the compressed representation — the
byte reduction is visible both in the host-side meter (``wire_bytes``)
and in the compiled HLO via ``launch.roofline.collective_bytes``.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.federated.metering import is_array

PyTree = Any


def _tree_elements(tree: PyTree) -> int:
    return sum(
        int(np.prod(x.shape))
        for x in jax.tree_util.tree_leaves(tree)
        if is_array(x)
    )


def _bcast_mask(mask: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    return mask.reshape(mask.shape[0], *([1] * (x.ndim - 1)))


@dataclasses.dataclass(frozen=True)
class MeanAggregator:
    """Weighted mean over the round's active silos.

    ``combine`` returns Σ_j m_j x_j / Σ_j m_j — for a binary
    participation mask m this is the paper's server reduction up to the
    J rescale applied by the runtime (J · mean over active =
    (J/|A|) Σ_active, the unbiased partial-participation estimator).
    The async engine passes fractional staleness-decay weights instead
    of a 0/1 mask, turning the same expression into the FedBuff-style
    staleness-weighted mean.

    The denominator guards only against an exactly-zero total weight
    (an empty round): clamping it to 1.0, as an earlier version did,
    silently shrank every aggregate whose fractional weights summed
    below 1 — e.g. a single stale async arrival with weight 0.25 was
    divided by 1.0 instead of 0.25, scaling the (parameter!) upload by
    4× toward zero.

    ``fused_reduction`` is the Aggregator protocol's *capability
    attribute*: the name of the fused Pallas reduction that computes
    this rule on the fused wire ("mean"/"trimmed"), or ``None``
    (the default the runtime assumes via ``getattr``) to fall back to
    :meth:`combine` on the dequantized (J, P) matrix.  Custom
    aggregators omit it; the runtime never type-probes.
    """

    fused_reduction = "mean"

    def combine(self, stacked: PyTree, mask: jnp.ndarray) -> PyTree:
        """Weighted mean over the leading silo axis of every leaf."""
        total = jnp.sum(mask)
        denom = jnp.where(total > 0.0, total, 1.0)

        def leaf(x):
            return jnp.sum(_bcast_mask(mask, x) * x, axis=0) / denom

        return jax.tree_util.tree_map(leaf, stacked)


@dataclasses.dataclass(frozen=True)
class TrimmedMeanAggregator:
    """Coordinate-wise trimmed mean over active silos (Yin et al., 2018).

    Drops the ``trim_frac`` fraction of smallest and largest values per
    coordinate among the *active* silos before averaging — a robust
    aggregation rule for straggler/Byzantine scenarios. Inactive silos
    are excluded by sorting them to the top (+inf sentinel) and masking
    by rank. Degenerates to :class:`MeanAggregator` at ``trim_frac=0``.
    """

    fused_reduction = "trimmed"

    trim_frac: float = 0.1

    def combine(self, stacked: PyTree, mask: jnp.ndarray) -> PyTree:
        """Per-coordinate trimmed mean over the active silos of every leaf.

        Any silo with weight > 0 counts as active; the trimmed mean
        itself is unweighted (rank statistics have no canonical
        fractional weighting), so under the async engine staleness
        affects only WHICH silos enter the trim, not their weight.
        """
        any_active = jnp.sum((mask > 0.0).astype(mask.dtype)) > 0.0
        n_active = jnp.maximum(jnp.sum((mask > 0.0).astype(mask.dtype)), 1.0)
        k = jnp.floor(self.trim_frac * n_active)
        k = jnp.minimum(k, jnp.floor((n_active - 1.0) / 2.0))

        def leaf(x):
            m = _bcast_mask(mask, x) > 0.0
            order = jnp.sort(jnp.where(m, x, jnp.inf), axis=0)
            rank = jnp.arange(x.shape[0]).reshape(-1, *([1] * (x.ndim - 1)))
            keep = (rank >= k) & (rank < n_active - k)
            total = jnp.sum(jnp.where(keep, order, 0.0), axis=0)
            mean = total / jnp.maximum(jnp.sum(keep, axis=0), 1)
            # Zero active silos would average the +inf sentinel; return
            # zeros instead, like MeanAggregator's zero-total guard.
            return jnp.where(any_active, mean, jnp.zeros_like(mean))

        return jax.tree_util.tree_map(leaf, stacked)


# ---------------------------------------------------------------------------
# Wire compression
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class NoCompression:
    """Identity codec: ships raw float leaves (4 bytes/element for f32).

    ``wire_codec`` is the Compressor protocol's capability attribute:
    the fused wire inlines the codecs it has Pallas kernels for
    ("identity" and "int8") and calls ``encode``/``decode`` per silo
    for anything else (``getattr`` default "custom").
    """

    wire_codec = "identity"

    def encode(self, tree: PyTree) -> PyTree:
        """Identity — the shipped tree is the wire format."""
        return tree

    def decode(self, enc: PyTree) -> PyTree:
        """Identity inverse of :meth:`encode`."""
        return enc

    def wire_bytes(self, tree: PyTree) -> int:
        """Wire size of the raw upload: the runtime packs the whole tree
        into ONE contiguous float32 row
        (:class:`~repro.core.flatten.TreeSpec`), so the wire carries 4
        bytes per element regardless of leaf dtypes."""
        return 4 * _tree_elements(tree)


@dataclasses.dataclass(frozen=True)
class Int8Compressor:
    """Per-leaf symmetric int8 quantization of shipped pytrees.

    Each leaf x is shipped as (round(x / s) : int8, s : f32) with
    s = max|x| / 127, a 4× wire reduction on f32 gradients at <1%
    relative error on the aggregate (quantization noise is zero-mean and
    averages down across silos). Because ``encode`` runs *before* the
    cross-silo ``all_gather``, the collective moves int8 payloads — the
    saving shows up in the optimized HLO's collective bytes, not just in
    the host-side meter.
    """

    wire_codec = "int8"

    def encode(self, tree: PyTree) -> PyTree:
        """Quantize every leaf to (int8 payload, f32 scale) wire format."""
        def leaf(x):
            scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
            q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
            return {"q": q, "scale": scale.astype(jnp.float32)}

        return {"leaves": [leaf(x) for x in jax.tree_util.tree_leaves(tree)],
                "treedef": _Static(jax.tree_util.tree_structure(tree))}

    def decode(self, enc: PyTree) -> PyTree:
        """Dequantize and rebuild the original pytree structure."""
        leaves = [d["q"].astype(jnp.float32) * d["scale"] for d in enc["leaves"]]
        return jax.tree_util.tree_unflatten(enc["treedef"].value, leaves)

    def wire_bytes(self, tree: PyTree) -> int:
        """Wire size of the quantized upload: the runtime packs it into a
        single (P,) row first, so the silo ships one int8 row and
        exactly ONE f32 scale, P + 4 bytes — what the compiled collective
        gathers (see ``launch.roofline.collective_bytes``)."""
        return _tree_elements(tree) + 4


@dataclasses.dataclass(frozen=True)
class _Static:
    """Wraps a treedef so it rides through pytree ops as a static leaf."""

    value: Any

    def __hash__(self):
        return hash(self.value)


jax.tree_util.register_pytree_node(
    _Static, lambda s: ((), s.value), lambda aux, _: _Static(aux)
)
