"""Per-silo differential privacy mechanism for federated uploads.

The unit of privacy is the silo→server message of one exchange (a
gradient pytree for SFVI, a locally-updated parameter pytree for
SFVI-Avg) — the same client→server surface that partitioned VI hardens
in Heikkilä et al. (2022) and that PVI (Ashman et al., 2022) frames as
the natural thing to clip and noise. :class:`PrivacyPolicy` implements
the Gaussian mechanism on that message *inside* the compiled round:

  1. the shipped pytree (or its delta from the round's public broadcast,
     for parameter uploads) is clipped to global L2 norm ``clip_norm``;
  2. i.i.d. Gaussian noise with per-coordinate std
     ``noise_multiplier * clip_norm`` is added, drawn from a PRNG key
     folded per (round, local step, silo) so every silo's noise is
     independent yet fully replayable from the round key;
  3. only then does the compression hook and the cross-silo
     ``all_gather`` run — the wire carries already-privatized bytes, so
     an honest-but-curious server (or wire observer) never sees a raw
     silo message.

All methods are pure jax functions over ANY pytree: the runtime's flat
wire format hands the mechanism one packed ``(P,)`` vector per silo
(``core.flatten.TreeSpec``), so the clip is a single norm and the noise
a single Gaussian draw — no per-leaf tree_map on the hot path (the
per-leaf fold-in below still applies verbatim to multi-leaf trees). The
mechanism lives in the same ``shard_map`` graph
as the round itself (verified by ``Server.compiled_collective_bytes`` /
the one-``all_gather``-per-wire-dtype HLO tests).
Accounting lives in :mod:`repro.federated.privacy.accountant`; the
threat model is spelled out in ``docs/privacy.md``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

PyTree = Any

# Fold-in tag separating DP noise draws from the runtime's ε_G / ε_{L_j}
# streams (which use offsets 0 and 100_003 of the same round key).
_DP_SALT = 777_013


@dataclasses.dataclass(frozen=True)
class PrivacyPolicy:
    """Clip-and-noise policy for one silo upload.

    Attributes:
      clip_norm: L2 bound C applied to the uploaded pytree (its global
        norm across all leaves). This is the mechanism's sensitivity
        under ADD/REMOVE adjacency — the DP-FedAvg convention the
        accountant (σ = z·C) assumes: a silo's contribution is either
        its clipped upload (norm ≤ C) or the data-independent zero
        upload the runtime ships for non-participants, so presence vs
        absence moves the gathered sum by at most C. (Replace-one-silo
        adjacency would double the sensitivity; account it by halving
        ``noise_multiplier``.)
      noise_multiplier: z — per-coordinate noise std is ``z * C``. Zero
        disables noising (clipping still applies), which is useful for
        isolating the utility cost of clipping alone.
      delta: target δ for (ε, δ) reports; threaded to the accountant by
        the runtime, not used by the mechanism itself.
    """

    clip_norm: float = 1.0
    noise_multiplier: float = 1.0
    delta: float = 1e-5

    def __post_init__(self):
        if self.clip_norm <= 0:
            raise ValueError(f"clip_norm must be > 0, got {self.clip_norm}")
        if self.noise_multiplier < 0:
            raise ValueError(
                f"noise_multiplier must be >= 0, got {self.noise_multiplier}"
            )
        if not (0.0 < self.delta < 1.0):
            raise ValueError(f"delta must be in (0, 1), got {self.delta}")

    # -- mechanism pieces (each jittable) -----------------------------------

    def global_norm(self, tree: PyTree) -> jnp.ndarray:
        """Global L2 norm over every leaf of ``tree`` (0 for empty trees)."""
        leaves = jax.tree_util.tree_leaves(tree)
        if not leaves:
            return jnp.zeros(())
        return jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in leaves))

    def clip(self, tree: PyTree) -> PyTree:
        """Scale ``tree`` so its global L2 norm is at most ``clip_norm``."""
        norm = self.global_norm(tree)
        factor = jnp.minimum(1.0, self.clip_norm / jnp.maximum(norm, 1e-12))
        return jax.tree_util.tree_map(lambda x: x * factor, tree)

    def noise(self, tree: PyTree, key: jnp.ndarray) -> PyTree:
        """Fresh N(0, (z·C)²) per coordinate; one folded subkey per leaf."""
        std = self.noise_multiplier * self.clip_norm
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        noised = [
            x + std * jax.random.normal(jax.random.fold_in(key, i), x.shape, x.dtype)
            for i, x in enumerate(leaves)
        ]
        return jax.tree_util.tree_unflatten(treedef, noised)

    def privatize(
        self, tree: PyTree, key: jnp.ndarray, reference: Optional[PyTree] = None
    ) -> PyTree:
        """Clip-and-noise ``tree`` (or its delta from ``reference``).

        ``reference`` handles parameter uploads (SFVI-Avg): the round's
        broadcast (θ, η_G) is public to the server, so the private
        quantity is the silo's *update* — the delta is clipped, noised,
        and added back so the wire format stays a parameter pytree and
        the downstream aggregator is untouched.
        """
        if reference is not None:
            delta = jax.tree_util.tree_map(jnp.subtract, tree, reference)
            priv = self.noise(self.clip(delta), key)
            return jax.tree_util.tree_map(jnp.add, reference, priv)
        return self.noise(self.clip(tree), key)

    def upload_key(
        self, round_key: jnp.ndarray, step: Any, silo_id: Any
    ) -> jnp.ndarray:
        """Noise key for (round, local step, silo) — disjoint from the
        runtime's shared-randomness streams via ``_DP_SALT``."""
        k = jax.random.fold_in(round_key, _DP_SALT)
        return jax.random.fold_in(jax.random.fold_in(k, step), silo_id)
