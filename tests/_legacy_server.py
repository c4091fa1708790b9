"""FROZEN pre-refactor Server snapshot — the bit-exactness oracle.

This file is a verbatim copy of ``repro/federated/runtime.py`` as of the
commit BEFORE the server-side update was factored into the pluggable
``ServerStrategy`` protocol (PR 7). The strategy-equivalence suite
(``tests/test_strategies.py``) runs the SAME configs through this legacy
``Server`` and the refactored registry-built one and asserts the
trajectories are bit-identical — including under DP + int8 + async and
across save/resume — on whatever machine the tests run, so the oracle
never suffers cross-platform float drift the way stored fixtures would.

Do not edit the algorithmic bodies here; the whole point is that they
stay what shipped. It only imports stable primitives (privacy policy,
aggregation, wire kernels, families, optimizers), none of which the
refactor touches semantically.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.barycenter import family_barycenter
from repro.core.family import eps_shape as family_eps_shape
from repro.core.family import supports_moments
from repro.core.flatten import TreeSpec
from repro.core.sfvi import SFVIProblem
from repro.federated.aggregation import (
    Int8Compressor,
    MeanAggregator,
    NoCompression,
    TrimmedMeanAggregator,
)
from repro.federated.metering import CommMeter, tree_bytes
from repro.kernels import wire as wire_kernels
from repro.federated.privacy import PrivacyPolicy, RdpAccountant
from repro.federated.scheduler import RoundScheduler
from repro.launch.mesh import make_silo_mesh
from repro.optim.base import GradientTransformation, apply_updates

PyTree = Any


# ---------------------------------------------------------------------------
# Shared-randomness helpers (exported: tests replay the exact draws)
# ---------------------------------------------------------------------------


def global_eps(problem: SFVIProblem, round_key: jnp.ndarray, t) -> jnp.ndarray:
    """ε_G for local step ``t`` of a round — identical on every silo."""
    return jax.random.normal(
        jax.random.fold_in(round_key, t),
        family_eps_shape(problem.global_family),
    )


def silo_eps(problem: SFVIProblem, round_key: jnp.ndarray, t, silo_id):
    """ε_{L_j} for local step ``t`` on silo ``silo_id`` (None if Z_L = ∅)."""
    if not problem.model.has_local:
        return None
    key = jax.random.fold_in(jax.random.fold_in(round_key, 100_003 + t), silo_id)
    return jax.random.normal(key, family_eps_shape(problem.local_family))


def stack_silos(datas: Sequence[PyTree]) -> PyTree:
    """Stack J per-silo data pytrees along a new leading silo axis.

    All silos must share leaf shapes (equal-sized shards — what the
    partitioners in ``repro.data.partition`` produce); ragged federations
    pad to the max and mask inside ``log_local``.
    """
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *datas)


def _neg(tree: PyTree) -> PyTree:
    return jax.tree_util.tree_map(lambda x: -x, tree)


def _add(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree_util.tree_map(jnp.add, a, b)


def _select(keep, new: PyTree, old: PyTree) -> PyTree:
    """Per-leaf ``where`` that preserves dtypes (masked silo-state update)."""
    return jax.tree_util.tree_map(lambda n, o: jnp.where(keep, n, o), new, old)


def _coalesced_all_gather(tree: PyTree, axis_name: str) -> PyTree:
    """Cross-silo gather as ONE ``all_gather`` per wire dtype.

    A naive per-leaf ``tree_map(all_gather)`` emits one collective per
    pytree leaf — more instructions (and collective launches) than the
    algorithm needs, and it makes the "one gather per exchange" claim of
    §3.2 unverifiable in the HLO. Instead: flatten every leaf of the
    (already encoded, already privatized) upload to ``(stack, size)``,
    concatenate per dtype into one contiguous buffer, gather that, and
    split back. Uncompressed float uploads produce exactly one
    ``all-gather`` instruction in the compiled round; int8 compression
    produces two (payload + scales), still independent of leaf count
    and of ``local_steps``.

    Leaves must share a leading stacked-silo axis (what the runtime's
    vmapped ``per_silo`` emits); the gather tiles along it.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    stack = leaves[0].shape[0]
    groups: Dict[Any, list] = {}
    for i, x in enumerate(leaves):
        groups.setdefault(jnp.dtype(x.dtype), []).append(i)
    out: list = [None] * len(leaves)
    for dt in sorted(groups, key=lambda d: d.name):
        idxs = groups[dt]
        flat = jnp.concatenate(
            [leaves[i].reshape(stack, -1) for i in idxs], axis=1
        )
        gathered = jax.lax.all_gather(flat, axis_name, axis=0, tiled=True)
        off = 0
        for i in idxs:
            size = int(np.prod(leaves[i].shape[1:], dtype=np.int64))
            piece = gathered[:, off : off + size]
            out[i] = piece.reshape((-1,) + leaves[i].shape[1:])
            off += size
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Fused-wire plumbing (wire="fused"): the upload pipeline and the server
# reduction run as the Pallas kernels of repro.kernels.wire, applied to the
# stacked (J, P) block AFTER the per-silo vmap instead of leaf-by-leaf
# inside it. Semantics match the flat path exactly (same op sequence, same
# PRNG stream); only the pass structure changes.
# ---------------------------------------------------------------------------


def _fused_keys(privacy, round_key, t, sids):
    """(J, 2) per-row DP noise keys: fold_in(upload_key(rk, t, j), 0).

    The trailing fold_in(·, 0) is ``PrivacyPolicy.noise``'s per-leaf
    fold for the single flat leaf — precomputing it per row makes the
    in-kernel draw bit-identical to the policy's stream.
    """
    if privacy is None or privacy.noise_multiplier <= 0.0:
        return None
    return jax.vmap(
        lambda s: jax.random.fold_in(privacy.upload_key(round_key, t, s), 0)
    )(sids)


def _fused_ship(mat, mask_sh, keys, reference, privacy, comp, int8):
    """Privatize + mask + encode a stacked (J, P) block in one fused pass."""
    out = wire_kernels.fused_upload(
        mat,
        mask=mask_sh,
        keys=keys,
        reference=reference,
        clip_norm=None if privacy is None else privacy.clip_norm,
        noise_multiplier=0.0 if privacy is None else privacy.noise_multiplier,
        quantize=int8,
    )
    if int8:
        q, scales = out
        return {"q": q, "scale": scales}
    if type(comp) is NoCompression:
        return out
    # Custom codec: fall back to the per-silo encode on the fused output.
    return jax.vmap(comp.encode)(out)


def _fused_decode(enc, comp, int8):
    """Gathered fused wire -> dequantized (J, P) float32 matrix."""
    if int8:
        return enc["q"].astype(jnp.float32) * enc["scale"][:, None]
    if type(comp) is NoCompression:
        return enc
    return jax.vmap(comp.decode)(enc)


class LegacyServer:
    """Round-based federation driver over a compiled multi-silo graph.

    Owns the replicated server state (θ, η_G, server optimizer) and the
    silo-sharded state (stacked η_{L_j} and local optimizer states), and
    advances them one *round* at a time through a jitted ``shard_map``
    graph. ``run(algorithm="sfvi")`` synchronizes every local step;
    ``run(algorithm="sfvi_avg")`` runs ``local_steps`` local VI steps on
    the N/N_j-rescaled objective and aggregates parameters once per round
    (FedAvg for θ, Wasserstein barycenter — or parameter-space mean —
    for η_G).

    Args:
      problem: the :class:`~repro.core.sfvi.SFVIProblem` to optimize.
      datas: list of J per-silo data pytrees with equal leaf shapes.
      theta: initial model parameters θ (``{}`` for fully-Bayesian).
      eta_G: initial global variational parameters η_G.
      num_obs: per-silo observation counts N_j (default: leading dim of
        each silo's first data leaf) — drives SFVI-Avg's N/N_j rescale.
      server_opt: optimizer for (θ, η_G). Descent convention; the runtime
        flips signs to ascend the ELBO.
      local_opt: optimizer for each η_{L_j} (state is stacked per silo).
      aggregator: cross-silo combine rule (mean / trimmed mean / custom).
      compressor: silo→server wire codec (identity / int8 quantization).
      eta_mode: ``"barycenter"`` (paper §3.2 — any family exposing the
        ``to_moments``/``from_moments`` bridge: analytic for diag-form
        families, the in-graph Newton–Schulz fixed point for
        full-covariance ones) or ``"param"`` (FedAvg in parameter
        space) for SFVI-Avg's η_G merge.
      wire: silo→server wire layout. ``"flat"`` (default) packs each
        upload into ONE contiguous float32 vector
        (:class:`~repro.core.flatten.TreeSpec`), so DP clip+noise,
        compression, the cross-silo gather and the aggregator all
        operate on a single (J, P) matrix — fewer HLO ops per round and
        one int8 scale per silo instead of one per leaf. ``"fused"``
        keeps the flat layout but runs the upload pipeline (clip + DP
        noise + mask + int8 quantize) and the server reduction as the
        fused Pallas kernels of :mod:`repro.kernels.wire` — identical
        semantics (bit-exact without DP/compression; the DP noise
        stream is bit-identical by construction), fewer memory passes.
        ``"legacy"`` keeps the per-leaf pytree wire (benchmark/debug
        reference).
      privacy: optional :class:`~repro.federated.privacy.PrivacyPolicy`.
        When set, every silo upload is L2-clipped and Gaussian-noised
        *inside* the compiled round — before the compression hook and
        the ``all_gather``, so the wire carries already-privatized bytes
        (SFVI privatizes the gradient tree; SFVI-Avg the parameter delta
        from the round's public broadcast). The Server then owns an
        :class:`~repro.federated.privacy.RdpAccountant` composing every
        exchange; ``run`` reports cumulative ε per round.
      mesh: optional silo mesh (default ``make_silo_mesh(J)``).
      seed: base seed for the round key stream.
    """

    def __init__(
        self,
        problem: SFVIProblem,
        datas: Sequence[PyTree],
        theta: PyTree,
        eta_G: PyTree,
        *,
        num_obs: Optional[Sequence[int]] = None,
        server_opt: GradientTransformation,
        local_opt: Optional[GradientTransformation] = None,
        aggregator=None,
        compressor=None,
        eta_mode: str = "barycenter",
        wire: str = "flat",
        privacy: Optional[PrivacyPolicy] = None,
        mesh=None,
        seed: int = 0,
    ):
        self.problem = problem
        self.J = len(datas)
        self.aggregator = aggregator or MeanAggregator()
        self.compressor = compressor or NoCompression()
        self.privacy = privacy
        self.accountant = RdpAccountant() if privacy is not None else None
        self.mesh = mesh if mesh is not None else make_silo_mesh(self.J)
        # The stacked silo axis is padded up to a multiple of the mesh
        # size with dummy silos (copies of silo 0's data, permanently
        # masked out), so ANY J shards over every device — a prime J on
        # a 4-device mesh no longer collapses the federation onto one
        # device. All masks/weights entering the compiled round carry
        # zeros for the padded tail; the J-rescales below always use the
        # real J. On divisible meshes J_pad == J and nothing changes.
        n_dev = int(self.mesh.shape["silo"])
        self.J_pad = ((self.J + n_dev - 1) // n_dev) * n_dev
        datas = list(datas)
        self.data = stack_silos(datas + [datas[0]] * (self.J_pad - self.J))
        self.seed = seed
        self._server_opt = server_opt
        self._local_opt = local_opt
        self._has_local = problem.model.has_local
        if eta_mode not in ("barycenter", "param"):
            raise ValueError(f"unknown eta_mode {eta_mode!r}")
        if eta_mode == "barycenter" and not supports_moments(
            problem.global_family
        ):
            raise ValueError(
                "eta_mode='barycenter' needs a global family exposing "
                "to_moments/from_moments (DiagGaussian, CholeskyGaussian, "
                "LowRankGaussian, ...); pass eta_mode='param' for "
                f"{type(problem.global_family).__name__}"
            )
        self.eta_mode = eta_mode
        if wire not in ("flat", "fused", "legacy"):
            raise ValueError(
                f"unknown wire layout {wire!r} (flat/fused/legacy)")
        self.wire = wire

        if num_obs is None:
            num_obs = [
                int(jax.tree_util.tree_leaves(d)[0].shape[0])
                for d in datas[: self.J]
            ]
        num_obs = list(num_obs) + [num_obs[0]] * (self.J_pad - self.J)
        self.num_obs = np.asarray(num_obs, np.float32)

        if self._has_local:
            if local_opt is None:
                raise ValueError("local_opt is required when the model has Z_L")
            # Real silos draw the same keys regardless of padding (the
            # split width is J, not J_pad) so trajectories agree across
            # device counts; the padded rows reuse silo 0's init and are
            # frozen by their permanent zero mask.
            keys = jax.random.split(jax.random.PRNGKey(seed + 1), self.J)
            eta_L = jax.vmap(problem.local_family.init)(keys)
            eta_L = self.pad_silo_axis(eta_L)
            opt_L = jax.vmap(local_opt.init)(eta_L)
        else:
            eta_L, opt_L = {}, {}
        self.state: Dict[str, PyTree] = {
            "theta": theta,
            "eta_G": eta_G,
            "eta_L": eta_L,
            "opt_server": server_opt.init({"theta": theta, "eta_G": eta_G}),
            "opt_local": opt_L,
        }
        self.comm = CommMeter()
        self._round_fns: Dict[tuple, Callable] = {}

    # -- convenience accessors (mirror the host runtime's attributes) -------

    @property
    def theta(self) -> PyTree:
        """Current model parameters θ (replicated)."""
        return self.state["theta"]

    @property
    def eta_G(self) -> PyTree:
        """Current global variational parameters η_G (replicated)."""
        return self.state["eta_G"]

    @property
    def eta_L(self) -> PyTree:
        """Stacked per-silo variational parameters η_{L_j}.

        Leading axis is ``J_pad`` (= J rounded up to the mesh size);
        rows ``J:`` are permanently-masked padding — slice ``[:J]`` for
        the real federation.
        """
        return self.state["eta_L"]

    # -- silo-axis padding ---------------------------------------------------

    def pad_silo_axis(self, tree: PyTree) -> PyTree:
        """Pad a J-leading stacked tree to ``J_pad`` rows (tile row 0).

        Padded rows never influence the run: every mask/weight vector
        carries zeros for them, so their state stays frozen and their
        uploads are masked out of the aggregation.
        """
        pad = self.J_pad - self.J
        if pad == 0:
            return tree
        return jax.tree_util.tree_map(
            lambda x: jnp.concatenate(
                [x, jnp.broadcast_to(x[:1], (pad,) + x.shape[1:])], axis=0
            ),
            tree,
        )

    def _pad_mask(self, mask: jnp.ndarray) -> jnp.ndarray:
        """Extend a (J,) mask/weight vector with zeros for padded silos."""
        pad = self.J_pad - self.J
        if pad == 0:
            return mask
        return jnp.concatenate([mask, jnp.zeros((pad,), mask.dtype)])

    # -- wire accounting -----------------------------------------------------

    def ship_template(self, algorithm: str) -> PyTree:
        """Shape-only pytree of one silo's upload (pre-compression)."""
        if algorithm == "sfvi":
            return {"g_theta": self.state["theta"], "g_eta": self.state["eta_G"]}
        return {"theta": self.state["theta"], "eta_G": self.state["eta_G"]}

    def wire_spec(self, algorithm: str) -> TreeSpec:
        """The flat wire bijection of one upload (static; P = its dim)."""
        return TreeSpec.of(self.ship_template(algorithm))

    def bytes_up_per_silo(self, algorithm: str) -> int:
        """Post-compression upload bytes for one silo, one gather.

        On the flat wire the compressor sees ONE (P,) float32 vector —
        an int8 codec therefore pays a single 4-byte scale per silo
        instead of one per pytree leaf.
        """
        template = self.ship_template(algorithm)
        if self.wire in ("flat", "fused"):
            template = np.zeros((self.wire_spec(algorithm).dim,), np.float32)
        return self.compressor.wire_bytes(template)

    def bytes_down_per_silo(self) -> int:
        """Broadcast bytes: (θ, η_G) raw; the round key is ~0 and elided."""
        return NoCompression().wire_bytes(
            {"theta": self.state["theta"], "eta_G": self.state["eta_G"]}
        )

    def compiled_collective_bytes(
        self, algorithm: str = "sfvi", local_steps: int = 1
    ) -> Dict[str, float]:
        """Ring-traffic bytes per collective kind in the compiled round.

        Lowers the jitted round function and applies
        ``launch.roofline.collective_bytes`` to the optimized HLO. On a
        single-device mesh XLA elides the collectives entirely (all
        entries 0); run under a multi-device mesh (or the forced-host-
        device trick of ``launch/comm.py``) for real numbers.
        """
        from repro.launch.roofline import collective_bytes

        fn = self._get_round(algorithm, local_steps)
        mask_shape = ((local_steps, self.J_pad) if algorithm == "sfvi"
                      else (self.J_pad,))
        ones = jnp.ones(mask_shape, jnp.float32)
        args = (
            self.state,
            self.data,
            jax.random.PRNGKey(0),
            ones,
            ones,
        )
        return collective_bytes(fn.lower(*args).compile().as_text())

    def compiled_roofline(
        self, algorithm: str = "sfvi", local_steps: int = 1
    ) -> Dict[str, float]:
        """Roofline terms of the compiled round: FLOPs + bytes moved.

        Lowers the jitted round function and reads XLA's
        ``cost_analysis`` (per-partition FLOPs and HBM bytes accessed)
        plus ``launch.roofline.collective_bytes`` on the optimized HLO.
        The ``bytes_accessed`` term is what the fused wire kernels
        attack: fewer memory passes over the (J, P) matrix per round.
        """
        from repro.launch.roofline import collective_bytes

        fn = self._get_round(algorithm, local_steps)
        mask_shape = ((local_steps, self.J_pad) if algorithm == "sfvi"
                      else (self.J_pad,))
        ones = jnp.ones(mask_shape, jnp.float32)
        compiled = fn.lower(
            self.state, self.data, jax.random.PRNGKey(0), ones, ones
        ).compile()
        ca = compiled.cost_analysis() or {}
        return {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "collective_bytes": float(
                sum(collective_bytes(compiled.as_text()).values())),
        }

    def _fused_trim(self):
        """Fused-reduction mode for the configured aggregator.

        ``(None,)`` → fused weighted mean, ``(frac,)`` → fused trimmed
        mean, ``None`` → aggregator not expressible as a fused kernel
        (custom subclass): the fused wire falls back to
        ``aggregator.combine`` on the dequantized matrix.
        """
        if type(self.aggregator) is MeanAggregator:
            return (None,)
        if type(self.aggregator) is TrimmedMeanAggregator:
            return (float(self.aggregator.trim_frac),)
        return None

    # -- the compiled round --------------------------------------------------

    def _get_round(self, algorithm: str, local_steps: int) -> Callable:
        key = (algorithm, local_steps)
        if key not in self._round_fns:
            if algorithm == "sfvi":
                body = self._sfvi_body(local_steps)
            elif algorithm == "sfvi_avg":
                body = self._avg_body(local_steps)
            else:
                raise ValueError(f"unknown algorithm {algorithm!r}")
            sharded = jax.shard_map(
                body,
                mesh=self.mesh,
                in_specs=(
                    P(), P(), P(),  # theta, eta_G, opt_server (replicated)
                    P("silo"), P("silo"),  # eta_L, opt_local
                    P("silo"), P("silo"), P("silo"),  # data, sids, n_j
                    # Participation mask rides ONCE, replicated; each block
                    # slices its silos' entries via sids. Passing it a
                    # second time with P("silo") made GSPMD reshard it with
                    # an extra 4-byte all-gather in the compiled round.
                    # ``weights`` are the aggregation weights (== mask on
                    # the sync path; staleness-decayed on the async path).
                    P(), P(), P(),  # full mask, full weights, round key
                ),
                out_specs=(P(), P(), P(), P("silo"), P("silo"), P()),
                check_vma=False,
            )

            def round_fn(state, data, round_key, mask, weights):
                sids = jnp.arange(self.J_pad, dtype=jnp.int32)
                n_j = jnp.asarray(self.num_obs)
                theta, eta_G, opt_server, eta_L, opt_L, elbos = sharded(
                    state["theta"], state["eta_G"], state["opt_server"],
                    state["eta_L"], state["opt_local"],
                    data, sids, n_j, mask, weights, round_key,
                )
                new_state = {
                    "theta": theta, "eta_G": eta_G, "eta_L": eta_L,
                    "opt_server": opt_server, "opt_local": opt_L,
                }
                return new_state, {"elbo": elbos}

            self._round_fns[key] = jax.jit(round_fn)
        return self._round_fns[key]

    def _sfvi_body(self, K: int) -> Callable:
        """Round = K synchronized steps: gather + server update every step."""
        problem, J = self.problem, self.J
        agg, comp = self.aggregator, self.compressor
        server_opt, local_opt = self._server_opt, self._local_opt
        has_local = self._has_local
        privacy = self.privacy
        # Flat wire: the whole upload is ONE (P,) f32 vector, so clip,
        # noise, quantization, the gather and the aggregation below all
        # see a single array per silo ((J, P) once stacked). The fused
        # wire keeps the same layout but runs those stages as the Pallas
        # kernels of repro.kernels.wire on the stacked block.
        wire = self.wire_spec("sfvi") if self.wire != "legacy" else None
        fused = self.wire == "fused"
        int8 = type(comp) is Int8Compressor
        trim = self._fused_trim()

        def body(theta, eta_G, opt_server, eta_L, opt_L,
                 data_sh, sids, n_j, masks_full, weights_full, round_key):
            # masks_full: (K, J) — SFVI samples participation PER EXCHANGE
            # (it synchronizes every step, so each gather is its own
            # subsampling event; this is what makes the accountant's
            # per-exchange amplification sound — one shared mask across
            # the K gathers would expose K correlated outputs per draw).
            # weights_full: (K, J) aggregation weights — identical to
            # masks_full on the sync path.
            del n_j  # SFVI needs no N/N_j rescale (likelihood_scale = 1)

            def sync_step(carry, step_xs):
                t, mask_full, w_full = step_xs
                mask_sh = mask_full[sids]  # this block's silos
                n_active = jnp.maximum(jnp.sum(mask_full), 1.0)
                theta, eta_G, opt_server, eta_L, opt_L = carry
                eps_G = global_eps(problem, round_key, t)

                def per_silo(eta_Lj, opt_Lj, data_j, sid, m_j):
                    el = eta_Lj if has_local else None
                    eps_L = silo_eps(problem, round_key, t, sid)
                    g_th, g_eta, g_loc, hatLj = problem.silo_grads(
                        theta, eta_G, el, eps_G, eps_L, data_j
                    )
                    if has_local:
                        upd, new_opt = local_opt.update(_neg(g_loc), opt_Lj, el)
                        eta_Lj = _select(m_j > 0.5, apply_updates(el, upd), el)
                        opt_Lj = _select(m_j > 0.5, new_opt, opt_Lj)
                    ship = {"g_theta": g_th, "g_eta": g_eta}
                    if wire is not None:
                        ship = wire.pack(ship)
                    if fused:
                        # Privatize/mask/quantize run as ONE fused pass
                        # over the stacked (J, P) block after the vmap.
                        return eta_Lj, opt_Lj, ship, hatLj * m_j
                    if privacy is not None:
                        # Clip + noise BEFORE compression and the gather:
                        # the wire never carries a raw silo gradient.
                        ship = privacy.privatize(
                            ship, privacy.upload_key(round_key, t, sid)
                        )
                    # Non-participating silos upload a data-independent
                    # zero tree (they "don't upload"; aggregation masks
                    # them anyway). This is what makes the accountant's
                    # subsampling amplification valid: an unsampled
                    # silo's data is absent from the wire, not merely
                    # down-weighted at the server.
                    ship = _select(
                        m_j > 0.5, ship,
                        jax.tree_util.tree_map(jnp.zeros_like, ship),
                    )
                    ship = comp.encode(ship)
                    return eta_Lj, opt_Lj, ship, hatLj * m_j

                eta_L, opt_L, enc, hatL = jax.vmap(per_silo)(
                    eta_L, opt_L, data_sh, sids, mask_sh
                )
                if fused:
                    enc = _fused_ship(
                        enc, mask_sh, _fused_keys(privacy, round_key, t, sids),
                        None, privacy, comp, int8)
                enc = _coalesced_all_gather(enc, "silo")
                hatL_sum = jax.lax.psum(jnp.sum(hatL), "silo")

                if fused and int8 and trim is not None:
                    # Dequantize inside the reduction kernel: the server
                    # never materializes the dequantized (J, P) matrix.
                    mean_g = wire_kernels.fused_combine(
                        enc["q"], w_full, scales=enc["scale"],
                        trim_frac=trim[0])
                elif fused:
                    mat = _fused_decode(enc, comp, int8)
                    mean_g = (wire_kernels.fused_combine(
                        mat, w_full, trim_frac=trim[0])
                        if trim is not None else agg.combine(mat, w_full))
                else:
                    shipped = jax.vmap(comp.decode)(enc)  # (J, P) | per leaf
                    mean_g = agg.combine(shipped, w_full)
                g_sum = jax.tree_util.tree_map(lambda x: x * float(J), mean_g)
                if wire is not None:
                    g_sum = wire.unpack(g_sum)
                g_th0, g_eta0, hatL0 = problem.server_grads(theta, eta_G, eps_G)
                g = {
                    "theta": _add(g_sum["g_theta"], g_th0),
                    "eta_G": _add(g_sum["g_eta"], g_eta0),
                }
                params = {"theta": theta, "eta_G": eta_G}
                updates, opt_server = server_opt.update(_neg(g), opt_server, params)
                merged = apply_updates(params, updates)
                elbo = hatL0 + (float(J) / n_active) * hatL_sum
                carry = (merged["theta"], merged["eta_G"], opt_server, eta_L, opt_L)
                return carry, elbo

            carry = (theta, eta_G, opt_server, eta_L, opt_L)
            carry, elbos = jax.lax.scan(
                sync_step, carry, (jnp.arange(K), masks_full, weights_full)
            )
            return (*carry, elbos)

        return body

    def _avg_body(self, K: int) -> Callable:
        """Round = K local VI steps per silo, ONE gather + parameter merge."""
        problem, J = self.problem, self.J
        agg, comp = self.aggregator, self.compressor
        server_opt, local_opt = self._server_opt, self._local_opt
        has_local = self._has_local
        eta_mode = self.eta_mode
        privacy = self.privacy
        wire = self.wire_spec("sfvi_avg") if self.wire != "legacy" else None
        fused = self.wire == "fused"
        int8 = type(comp) is Int8Compressor
        trim = self._fused_trim()
        # N = Σ_j N_j over the REAL federation — the padded tail repeats
        # silo 0's count purely to keep the dummy silos' per-silo scale
        # finite (their contribution is masked out regardless).
        total_obs = float(np.sum(self.num_obs[: self.J]))

        def body(theta, eta_G, opt_server, eta_L, opt_L,
                 data_sh, sids, n_j, mask_full, w_full, round_key):
            mask_sh = mask_full[sids]  # this block's silos
            n_active = jnp.maximum(jnp.sum(mask_full), 1.0)
            # The round's public broadcast in wire form: the DP delta
            # reference AND the data-independent upload of silos that
            # did not participate.
            broadcast = {"theta": theta, "eta_G": eta_G}
            if wire is not None:
                broadcast = wire.pack(broadcast)

            def per_silo(eta_Lj, opt_Lj, data_j, sid, m_j, n_obs_j):
                scale = total_obs / n_obs_j  # §3.2 point 2: N / N_j
                el0 = eta_Lj if has_local else None
                s_state = server_opt.init({"theta": theta, "eta_G": eta_G})

                def local_step(carry, t):
                    th, eg, el, s_st, l_st = carry
                    eps_G = global_eps(problem, round_key, t)
                    eps_L = silo_eps(problem, round_key, t, sid)

                    def objective(th_, eg_, el_):
                        val = problem.hat_L0(th_, eg_, eps_G)
                        return val + problem.hat_Lj(
                            th_, eg_, el_, eps_G, eps_L, data_j, scale
                        )

                    if has_local:
                        val, (g_th, g_eg, g_el) = jax.value_and_grad(
                            objective, argnums=(0, 1, 2)
                        )(th, eg, el)
                        upd_l, l_st = local_opt.update(_neg(g_el), l_st, el)
                        el = apply_updates(el, upd_l)
                    else:
                        val, (g_th, g_eg) = jax.value_and_grad(
                            lambda a, b: objective(a, b, None), argnums=(0, 1)
                        )(th, eg)
                    params = {"theta": th, "eta_G": eg}
                    upd_s, s_st = server_opt.update(
                        _neg({"theta": g_th, "eta_G": g_eg}), s_st, params
                    )
                    merged = apply_updates(params, upd_s)
                    return (merged["theta"], merged["eta_G"], el, s_st, l_st), val

                carry = (theta, eta_G, el0, s_state, opt_Lj)
                (th, eg, el, _, l_st), elbos = jax.lax.scan(
                    local_step, carry, jnp.arange(K)
                )
                if has_local:
                    eta_Lj = _select(m_j > 0.5, el, el0)
                    opt_Lj = _select(m_j > 0.5, l_st, opt_Lj)
                ship = {"theta": th, "eta_G": eg}
                if wire is not None:
                    ship = wire.pack(ship)
                if fused:
                    # Delta-clip/noise vs the broadcast, the broadcast
                    # fallback for non-participants, and quantization all
                    # run as ONE fused pass on the stacked block.
                    return eta_Lj, opt_Lj, ship, elbos * m_j
                if privacy is not None:
                    # Parameter upload: the private quantity is the delta
                    # from the round's broadcast (θ, η_G), which the server
                    # already knows. Clip + noise the delta, add it back —
                    # the wire format (flat vector or parameter pytree) is
                    # unchanged, and it is privatized before compression
                    # and the gather.
                    ship = privacy.privatize(
                        ship,
                        privacy.upload_key(round_key, 0, sid),
                        reference=broadcast,
                    )
                # Non-participating silos upload the round's public
                # broadcast — data-independent, so the subsampling
                # amplification in the accountant actually holds on the
                # wire (aggregation masks these rows regardless).
                ship = _select(m_j > 0.5, ship, broadcast)
                ship = comp.encode(ship)
                return eta_Lj, opt_Lj, ship, elbos * m_j

            eta_L, opt_L, enc, elbos = jax.vmap(per_silo)(
                eta_L, opt_L, data_sh, sids, mask_sh, n_j
            )
            if fused:
                enc = _fused_ship(
                    enc, mask_sh, _fused_keys(privacy, round_key, 0, sids),
                    broadcast, privacy, comp, int8)
            enc = _coalesced_all_gather(enc, "silo")
            elbo_t = jax.lax.psum(jnp.sum(elbos, axis=0), "silo") / n_active

            if fused:
                # The barycenter needs every silo's η_G anyway, so the
                # dequantized matrix is materialized here (unlike SFVI);
                # the reduction itself still runs as the fused kernel.
                shipped = _fused_decode(enc, comp, int8)
                vec = (wire_kernels.fused_combine(
                    shipped, w_full, trim_frac=trim[0])
                    if trim is not None else agg.combine(shipped, w_full))
                merged = wire.unpack(vec)
                eta_shipped = jax.vmap(lambda v: wire.unpack(v)["eta_G"])(
                    shipped)
            elif wire is not None:
                shipped = jax.vmap(comp.decode)(enc)  # (J, P)
                merged = wire.unpack(agg.combine(shipped, w_full))
                eta_shipped = jax.vmap(lambda v: wire.unpack(v)["eta_G"])(
                    shipped)
            else:
                shipped = jax.vmap(comp.decode)(enc)  # stacked pytree
                merged = {k: agg.combine(v, w_full)
                          for k, v in shipped.items()}
                eta_shipped = shipped["eta_G"]
            theta_new = merged["theta"]
            if eta_mode == "param":
                eta_new = merged["eta_G"]
            else:
                # W2 barycenter in moment space, generic over the
                # family's moment bridge: analytic (aggregator-
                # robustified) for diag-form families, the in-graph
                # Newton–Schulz fixed point for full-covariance ones
                # (the fused wire plugs in the fused-step kernel — same
                # iteration, one kernel per step instead of 3 matmuls).
                sqrtm_kw = (
                    {"sqrtm": wire_kernels.sqrtm_newton_schulz_fused}
                    if fused else {})
                eta_new = family_barycenter(
                    problem.global_family, eta_shipped, w_full, agg,
                    **sqrtm_kw)
            return theta_new, eta_new, opt_server, eta_L, opt_L, elbo_t

        return body

    # -- driver --------------------------------------------------------------

    def run(
        self,
        num_rounds: int,
        *,
        algorithm: str = "sfvi",
        local_steps: int = 1,
        scheduler: Optional[RoundScheduler] = None,
        callback: Optional[Callable[[int, dict], None]] = None,
        start_round: int = 0,
    ) -> Dict[str, list]:
        """Advance the federation ``num_rounds`` rounds; returns history.

        ``start_round`` is the absolute index of the first round: the
        round PRNG key, the scheduler's participation draws and the
        accountant's exchange indices are all functions of the absolute
        round, so ``run(a); run(b, start_round=a)`` replays exactly the
        same stream as one ``run(a + b)`` — the property
        ``federated.api.Experiment`` builds its bit-exact save/resume
        guarantee on.

        One round is ``local_steps`` optimizer steps: SFVI pays one
        up+down exchange per step, SFVI-Avg one per round — the meter
        (``self.comm``) records exactly that asymmetry. ``scheduler``
        injects partial participation / straggler masks: uninvited silos
        cost nothing; invited stragglers (dropout) receive the broadcast
        (download is billed) but never upload, and the aggregation is
        rescaled by the realized active count (unbiased, §3 Remark).

        With ``privacy`` set, each of the round's ``exchanges`` gathers
        is one (subsampled) Gaussian-mechanism invocation: the owned
        accountant composes them (q = the scheduler's invitation rate)
        and ``history["epsilon"]`` traces the cumulative ε at the
        policy's δ after each round. SFVI draws a FRESH participation
        mask for every local step (schedule index = exchange index
        ``r * local_steps + t``), so each gather is an independent
        subsampling event and the per-exchange amplification is sound;
        SFVI-Avg draws one mask per round (index ``r``).
        """
        if local_steps < 1:
            raise ValueError(f"local_steps must be >= 1, got {local_steps}")
        fn = self._get_round(algorithm, local_steps)
        sched = scheduler or RoundScheduler(self.J, seed=self.seed)
        up1 = self.bytes_up_per_silo(algorithm)
        down1 = self.bytes_down_per_silo()
        exchanges = local_steps if algorithm == "sfvi" else 1
        history: Dict[str, list] = {
            "elbo": [], "elbo_trace": [], "bytes_up": [], "bytes_down": [],
            "n_active": [],
        }
        if self.accountant is not None:
            history["epsilon"] = []
            # Poisson-q surrogate for the scheduler's fixed-size invitation
            # (docs/privacy.md §Accounting); custom schedulers without a
            # participation attribute are accounted at full participation.
            q = float(getattr(sched, "participation", 1.0))
        base_key = jax.random.PRNGKey(self.seed)
        for r in range(start_round, start_round + num_rounds):
            # SFVI synchronizes every local step, so each of the round's
            # `exchanges` gathers is its OWN participation draw (schedule
            # index = exchange index) — required for the accountant's
            # per-exchange subsampling amplification to be sound.
            # SFVI-Avg gathers once: one draw per round.
            ex_idx = ([r * local_steps + t for t in range(local_steps)]
                      if algorithm == "sfvi" else [r])
            ex_masks = [sched.mask(i) for i in ex_idx]
            active = [int(np.sum(np.asarray(m))) for m in ex_masks]
            # Stragglers received the broadcast before dropping: bill their
            # download. Custom schedulers without invited() bill reporters.
            invited = [
                max(int(np.sum(np.asarray(
                    sched.invited(i) if hasattr(sched, "invited")
                    else ex_masks[k]))), active[k])
                for k, i in enumerate(ex_idx)
            ]
            ex_masks = [self._pad_mask(m) for m in ex_masks]
            mask = (jnp.stack(ex_masks) if algorithm == "sfvi"
                    else ex_masks[0])
            round_key = jax.random.fold_in(base_key, r)
            # Sync rounds aggregate with the participation mask itself;
            # the async engine passes staleness-decayed weights instead.
            self.state, metrics = fn(self.state, self.data, round_key,
                                     mask, mask)
            elbos = np.asarray(metrics["elbo"])
            up = sum(active) * up1
            down = sum(invited) * down1
            n_active = active[-1]  # the round's final exchange
            self.comm.record(up, down)
            history["elbo"].append(float(elbos[-1]))
            history["elbo_trace"].extend(float(e) for e in elbos)
            history["bytes_up"].append(up)
            history["bytes_down"].append(down)
            history["n_active"].append(n_active)
            metrics_out = {
                "elbo": history["elbo"][-1], "bytes_up": up,
                "bytes_down": down, "n_active": n_active,
            }
            if self.accountant is not None:
                self.accountant.step(
                    noise_multiplier=self.privacy.noise_multiplier,
                    sampling_rate=q,
                    steps=exchanges,
                )
                eps = self.accountant.epsilon(self.privacy.delta)[0]
                history["epsilon"].append(eps)
                metrics_out["epsilon"] = eps
            if callback:
                callback(r, metrics_out)
        return history
