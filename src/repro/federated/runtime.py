"""Compiled federated orchestration: one sharded graph per round.

All J silos advance together inside a single ``shard_map`` over the
federated ``(silo[, model])`` mesh (``launch.mesh.build_mesh``), with the
server virtualized into collectives:

  * silo state (η_{L_j}, its optimizer, its data shard, and any per-silo
    strategy state such as PVI's site approximations λ_j) is stacked
    along a leading axis of size J and sharded over ``silo`` — privacy
    by placement, exactly as in ``launch/steps.py``;
  * the silo→server ship — whatever pytree the active
    :class:`~repro.federated.strategy.ServerStrategy` emits (gradients,
    locally-updated parameters, natural-parameter deltas) — is packed
    into ONE contiguous float32 vector per silo (the flat wire format,
    :class:`~repro.core.flatten.TreeSpec`), so DP clip+noise, the
    pluggable :mod:`~repro.federated.aggregation` compressor (applied
    *before* the collective — quantization reduces real bytes-on-wire,
    with a single int8 scale per silo), the ``all_gather`` over ``silo``
    and the server-side aggregation all operate on a single (J, P)
    matrix instead of per-leaf tree_maps;
  * the server reduction is a pluggable aggregator (mean, trimmed mean)
    evaluated redundantly on every device (standard SPMD replication);
  * on a 2-D ``(silo, model)`` mesh each row's P wire parameters are
    additionally sharded along ``model``: the whole upload pipeline
    (pack → DP clip+noise → mask → encode, or the fused kernel pass)
    runs on full rows — so noise streams and int8 row scales are
    bit-identical to the 1-D mesh — and each device then slices its
    model-column block, so the big gather over ``silo`` moves
    ``(J_pad, P/model)`` blocks; a second row-local ``all_gather`` over
    ``model`` rejoins the blocks before decode/aggregation, so the
    combine sees the exact (J_pad, P) matrix of the 1-D mesh and 2-D
    trajectories are bit-exact, reported ELBO included.
    ``model > 1`` requires an identity or int8 codec (custom codecs see
    arbitrary pytrees the runtime cannot column-slice).

Multi-process execution (``jax.distributed``) runs the same SPMD graph
over a global mesh: every process computes the identical control plane
(masks, keys, metering — pure functions of seed and round) while silo
state and data exist only on the owning process's devices
(:mod:`repro.federated.distributed`).

WHAT each silo computes and HOW the server folds the aggregate back into
(θ, η_G) is not this module's business: both live behind the
:class:`~repro.federated.strategy.ServerStrategy` registry. The runtime
only distinguishes the two *cadences* — step-cadence strategies gather
after every local optimizer step (``local_steps`` gathers per round);
round-cadence strategies run ``local_steps`` local VI steps and gather
once — which makes the paper's §3.2 communication claim directly
measurable and extends it unchanged to PVI / federated EP.

Randomness: the server broadcasts only a per-round PRNG key. ε_G at local
step t is derived from (round_key, t) and therefore *shared* by all silos
(common-random-numbers — replaces the ε_G broadcast of Algorithm 1 with
zero wire bytes); ε_{L_j} additionally folds in the silo id.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import debug
from repro.core.family import supports_moments
from repro.core.flatten import TreeSpec
from repro.core.sfvi import SFVIProblem
from repro.federated import graph_cache
from repro.federated.aggregation import MeanAggregator, NoCompression
from repro.federated.metering import CommMeter, tree_bytes
from repro.federated.strategy import (
    DEFAULT_STRATEGY,
    ServerStrategy,
    StrategyContext,
    _select,
    global_eps,
    resolve_strategy,
    silo_eps,
)
from repro.kernels import wire as wire_kernels
from repro.federated.privacy import PrivacyPolicy, RdpAccountant
from repro.federated.scheduler import RoundScheduler, draw_masks
from repro.launch.mesh import (
    MeshSpec,
    build_mesh,
    mesh_process_count,
    model_world,
)
from repro.optim.base import GradientTransformation

__all__ = [
    "Server", "global_eps", "silo_eps", "stack_silos",
]

PyTree = Any

# State groups stacked along the silo axis (sharded over ``silo``); the
# rest of the round state — θ, η_G, the server optimizer — replicates.
SILO_STATE = ("eta_L", "opt_local", "strategy")
SERVER_STATE = ("theta", "eta_G", "opt_server")


def stack_silos(datas: Sequence[PyTree]) -> PyTree:
    """Stack J per-silo data pytrees along a new leading silo axis.

    All silos must share leaf shapes (equal-sized shards — what the
    partitioners in ``repro.data.partition`` produce); ragged federations
    pad to the max and mask inside ``log_local``.
    """
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *datas)


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
    if _wire_codec(comp) == "identity":
        return out
    # Custom codec: fall back to the per-silo encode on the fused output.
    return jax.vmap(comp.encode)(out)


def _fused_decode(enc, comp, int8):
    """Gathered fused wire -> dequantized (J, P) float32 matrix."""
    if int8:
        return enc["q"].astype(jnp.float32) * enc["scale"][:, None]
    if _wire_codec(comp) == "identity":
        return enc
    return jax.vmap(comp.decode)(enc)


def _wire_codec(comp) -> str:
    """The compressor's fused-wire capability (Compressor protocol).

    "identity"/"int8" run as the fused Pallas kernels; "custom" (the
    default for compressors that don't declare the attribute) falls
    back to per-silo ``encode``/``decode`` around the same gather.
    """
    return getattr(comp, "wire_codec", "custom")


@dataclasses.dataclass(frozen=True)
class _RunSetup:
    """What ``Server.run`` places once per Server, beside its program.

    ``ctl`` holds the in-graph round's placed inputs (the base round key,
    the schedule seed, the membership vector); None when the schedule is
    drawn on the host. Per Server, not in the shared graph cache: the
    arrays live on this Server's mesh and carry its seed.
    """

    up1: int  # upload bytes of one silo, one exchange
    down1: int  # broadcast bytes of one silo, one exchange
    n_j: jax.Array  # the placed per-silo observation counts
    ctl: Optional[Dict[str, jax.Array]]


class Server:
    """Round-based federation driver over a compiled multi-silo graph.

    Owns the replicated server state (θ, η_G, server optimizer) and the
    silo-sharded state (stacked η_{L_j}, local optimizer states, and any
    per-silo strategy state), and advances them one *round* at a time
    through a jitted ``shard_map`` graph. The update rule is a
    :class:`~repro.federated.strategy.ServerStrategy` resolved from the
    registry by name: step-cadence strategies (SFVI) synchronize every
    local step; round-cadence strategies (SFVI-Avg, PVI, federated EP)
    run ``local_steps`` local VI steps and aggregate once per round.

    Args:
      problem: the :class:`~repro.core.sfvi.SFVIProblem` to optimize.
      datas: list of J per-silo data pytrees with equal leaf shapes.
      theta: initial model parameters θ (``{}`` for fully-Bayesian).
      eta_G: initial global variational parameters η_G.
      num_obs: per-silo observation counts N_j (default: leading dim of
        each silo's first data leaf) — drives SFVI-Avg's N/N_j rescale.
      server_opt: optimizer for (θ, η_G). Descent convention; the
        strategies flip signs to ascend the ELBO.
      local_opt: optimizer for each η_{L_j} (state is stacked per silo).
      aggregator: cross-silo combine rule (mean / trimmed mean / custom).
      compressor: silo→server wire codec (identity / int8 quantization).
      eta_mode: ``"barycenter"`` (paper §3.2 — any family exposing the
        ``to_moments``/``from_moments`` bridge: analytic for diag-form
        families, the in-graph Newton–Schulz fixed point for
        full-covariance ones) or ``"param"`` (FedAvg in parameter
        space) for SFVI-Avg's η_G merge.
      wire: how the silo→server upload runs. Every upload is packed
        into ONE contiguous float32 vector
        (:class:`~repro.core.flatten.TreeSpec`), so DP clip+noise,
        compression, the cross-silo gather and the aggregator all
        operate on a single (J, P) matrix with one int8 scale per silo.
        ``"flat"`` (default) runs those stages as jnp ops; ``"fused"``
        runs the upload pipeline (clip + DP noise + mask + int8
        quantize) and the server reduction on the same layout as the
        fused Pallas kernels of :mod:`repro.kernels.wire` — identical
        semantics (bit-exact without DP/compression; the DP noise
        stream is bit-identical by construction), fewer memory passes.
      privacy: optional :class:`~repro.federated.privacy.PrivacyPolicy`.
        When set, every silo upload is L2-clipped and Gaussian-noised
        *inside* the compiled round — before the compression hook and
        the ``all_gather``, so the wire carries already-privatized bytes
        (the clipped quantity is the strategy's upload measured against
        its wire reference: raw gradients / deltas for zero-reference
        strategies, the parameter delta from the round's public
        broadcast for broadcast-reference ones). The Server then owns an
        :class:`~repro.federated.privacy.RdpAccountant` composing every
        exchange; ``run`` reports cumulative ε per round.
      mesh: optional pre-built federated mesh (a 1-D ``(silo,)`` or 2-D
        ``(silo, model)`` :class:`jax.sharding.Mesh`). Mutually
        exclusive with ``mesh_spec``; default ``build_mesh`` over the
        spec (or ``MeshSpec()`` — the historical 1-D auto mesh).
      mesh_spec: declarative topology
        (:class:`~repro.launch.mesh.MeshSpec`) — what
        ``ExperimentSpec.runtime.mesh`` carries. ``model > 1`` shards
        each silo row's P wire parameters across the ``model`` axis
        (identity or int8 codec only);
        ``multiprocess=True`` builds the mesh over the global device
        list of a ``jax.distributed`` run and globalizes silo state,
        data and control inputs accordingly.
      seed: base seed for the round key stream.
      strategy: default update rule for :meth:`run` — a registry name,
        a :class:`~repro.federated.strategy.StrategySpec`, or a
        :class:`~repro.federated.strategy.ServerStrategy` instance.
        Per-silo strategy state (if any) is initialized here so it
        checkpoints alongside ``eta_L``.
      federation_size: the FULL federation width the estimators scale
        by (SFVI's ``J`` inflation, the ELBO's ``J/n_active`` rescale).
        Defaults to ``len(datas)``. A dynamic population sets this to
        the roster maximum so the estimator target — the full-roster
        ELBO — stays fixed while silos join through
        :meth:`grow_silos` (absent silos are just non-participants of
        the roster-wide federation, the §3 Remark).
      federation_obs: the full federation's N = Σ_j N_j (SFVI-Avg's
        N/N_j rescale). Defaults to the sum over ``datas``; a dynamic
        population passes the roster-wide total for the same reason.
    """

    def __init__(
        self,
        problem: SFVIProblem,  # repro-lint: allow[R5] — the seed's problem protocol (local ELBO interface), not a strategy branch
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
        mesh_spec: Optional[MeshSpec] = None,
        seed: int = 0,
        strategy: Union[str, ServerStrategy, None] = None,
        graph_cache_token: Optional[str] = None,
        federation_size: Optional[int] = None,
        federation_obs: Optional[float] = None,
    ):
        self.problem = problem
        self.J = len(datas)
        self.aggregator = aggregator or MeanAggregator()
        self.compressor = compressor or NoCompression()
        self.privacy = privacy
        self.accountant = RdpAccountant() if privacy is not None else None
        if mesh is not None and mesh_spec is not None:
            raise ValueError(
                "pass either a pre-built mesh or a MeshSpec, not both")
        self.mesh = (mesh if mesh is not None
                     else build_mesh(mesh_spec, num_silos=self.J))
        self.model_world = model_world(self.mesh)
        self.n_processes = mesh_process_count(self.mesh)
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
        if wire not in ("flat", "fused"):
            raise ValueError(f"unknown wire layout {wire!r} (flat/fused)")
        self.wire = wire
        if self.model_world > 1:
            # Model-sharding slices the (J, P) wire by columns, which
            # needs a codec whose payload IS that matrix (identity/int8);
            # custom codecs carry pytrees the runtime cannot column-slice.
            if _wire_codec(self.compressor) == "custom":
                raise ValueError(
                    f"compressor {type(self.compressor).__name__} has no "
                    "wire_codec capability; model-axis sharding supports "
                    "identity/int8 codecs only (or set model=1)")

        if num_obs is None:
            num_obs = [
                int(jax.tree_util.tree_leaves(d)[0].shape[0])
                for d in datas[: self.J]
            ]
        num_obs = list(num_obs) + [num_obs[0]] * (self.J_pad - self.J)
        # repro-lint: allow[R4] — host staging of a Python list at init, not a device pull
        self.num_obs = np.asarray(num_obs, np.float32)
        # Roster-wide constants the strategies' estimators scale by —
        # trace-time facts that must NOT change when a dynamic
        # population grows the live J (see class docstring).
        self.fed_J = self.J if federation_size is None else int(federation_size)
        self.fed_obs = (float(np.sum(self.num_obs[: self.J]))
                        if federation_obs is None else float(federation_obs))

        if self._has_local:
            if local_opt is None:
                raise ValueError("local_opt is required when the model has Z_L")
            # Real silos draw the same keys regardless of padding (the
            # split width is J, not J_pad) so trajectories agree across
            # device counts; the padded rows reuse silo 0's init and are
            # frozen by their permanent zero mask.
            # repro-lint: allow[R1] — init-time root of the η_L stream: a pure function of the spec seed, so resume re-derives it bit-exactly
            keys = jax.random.split(jax.random.PRNGKey(seed + 1), self.J)
            eta_L = jax.vmap(problem.local_family.init)(keys)
            eta_L = self.pad_silo_axis(eta_L)
            opt_L = jax.vmap(local_opt.init)(eta_L)
        else:
            eta_L, opt_L = {}, {}
        self._strategy = resolve_strategy(
            strategy if strategy is not None else DEFAULT_STRATEGY
        )
        self._strategy.validate(self)  # fail fast, not at first run()
        self.state: Dict[str, PyTree] = {
            "theta": theta,
            "eta_G": eta_G,
            "eta_L": eta_L,
            "opt_server": server_opt.init({"theta": theta, "eta_G": eta_G}),
            "opt_local": opt_L,
            "strategy": {},
        }
        self.state["strategy"] = self._strategy.init_silo_state(self)
        self.place()
        self.comm = CommMeter()
        # (r, the device scalar r) the last in-graph round handed back:
        # the next round's index, already on the device.
        self._next_round: Optional[Tuple[int, jax.Array]] = None
        # Shared across structurally-identical Servers (resume!) when the
        # builder hands in a token; private otherwise. See graph_cache.
        self._round_fns: Dict[tuple, Callable] = graph_cache.round_fns(
            graph_cache_token)

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

    @property
    def strategy(self) -> ServerStrategy:
        """The server's default update rule (overridable per ``run``)."""
        return self._strategy

    # -- strategy resolution -------------------------------------------------

    def _resolve(self, algorithm) -> ServerStrategy:
        """None / name / spec / instance → a ServerStrategy instance."""
        if algorithm is None:
            return self._strategy
        return resolve_strategy(algorithm)

    def _ensure_strategy_state(self, strat: ServerStrategy) -> None:
        """Lazily create per-silo strategy state when first needed.

        Restored checkpoints (and the constructor's default strategy)
        arrive with state already populated; this only fills the gap
        when ``run`` is pointed at a stateful strategy the Server was
        not built with.
        """
        if strat.has_silo_state and not jax.tree_util.tree_leaves(
            self.state.get("strategy", {})
        ):
            self.state["strategy"] = strat.init_silo_state(self)
            self.place()
        self.state.setdefault("strategy", {})

    # -- placement -----------------------------------------------------------

    def _shardings(self):
        """(state, data) shardings of the compiled round's inputs."""
        rep = NamedSharding(self.mesh, P())
        silo = NamedSharding(self.mesh, P("silo"))
        state = {k: rep for k in SERVER_STATE}
        state.update({k: silo for k in SILO_STATE})
        return state, silo

    def place(self) -> None:
        """Commit data and state to the compiled round's input shardings.

        Silo-stacked trees (data, η_L, local optimizer, strategy state)
        shard over ``silo``; the server state replicates. Runs at build,
        after :meth:`grow_silos`, and on resume: a round fed
        default-device inputs would trace a second graph once its own
        mesh-sharded outputs come back, and on several chips would copy
        every silo's data from the first chip each round. Leaves already
        placed are left as they are; a multi-process run globalizes
        host values (identical on every process) instead.
        """
        state_sh, data_sh = self._shardings()
        if self.n_processes > 1:
            from repro.federated import distributed

            def put(tree, sharding):
                return distributed.globalize(tree, self.mesh, sharding.spec)
        else:
            put = jax.device_put
        self.data = put(self.data, data_sh)
        self.state = {k: put(v, state_sh[k]) for k, v in self.state.items()}
        # Every change of J or of the state's shapes ends here (growth,
        # lazily created strategy state, warm starts, resume), and so
        # drops ``run``'s cached set-up, which reads both.
        self._setups: Dict[tuple, _RunSetup] = {}

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

    def _control(self, host: PyTree) -> PyTree:
        """Host control inputs → the round's replicated device arrays.

        One transfer for the whole tree. A multi-process run needs
        global arrays; every process computed the identical host values
        (scheduler and key stream are pure functions of seed and
        absolute round).
        """
        if self.n_processes > 1:
            from repro.federated import distributed

            return jax.tree_util.tree_map(
                lambda x: distributed.replicated(x, self.mesh), host)
        return jax.device_put(host, NamedSharding(self.mesh, P()))

    # -- dynamic population growth ------------------------------------------

    def grow_silos(self, datas: Sequence[PyTree],
                   num_obs: Optional[Sequence[int]] = None,
                   eta_rows: Optional[Sequence[PyTree]] = None) -> None:
        """Append joining silos to the stacked silo axis, in place.

        The population engine's join path: the new silos' data shards
        (equal leaf shapes with the existing federation) are appended,
        J and the mesh-chunked ``J_pad`` are recomputed, and every
        silo-stacked tree is rebuilt — existing real rows are copied
        bitwise, new rows are initialized, padding is re-tiled. The
        compiled round retraces only when ``J_pad`` steps (the
        round-fn cache is keyed by it); growth within the padded chunk
        reuses the compiled graph, with the new silo entering through
        the ``n_j`` argument and its mask column.

        ``eta_rows`` optionally supplies each new silo's initial
        ``η_L`` (the amortized warm start); ``None`` draws the cold
        family init from a deterministic per-silo key — a pure
        function of ``(seed, roster index)``, so a resumed run
        re-grows bit-exactly whenever the join replays. New silos'
        optimizer moments are fresh; per-silo strategy state rows are
        the strategy's init (zero sites — PVI's continual-learning
        join: the new silo's cavity is the current global posterior).
        """
        if not datas:
            return
        if self.n_processes > 1:
            raise NotImplementedError(
                "dynamic population growth is single-process for now "
                "(multi-process federations own silo rows per host)")
        old_J = self.J
        new = list(datas)
        if num_obs is None:
            num_obs = [int(jax.tree_util.tree_leaves(d)[0].shape[0])
                       for d in new]
        real_data = jax.tree_util.tree_map(
            lambda x: x[:old_J], self.data)
        self.J = old_J + len(new)
        n_dev = int(self.mesh.shape["silo"])
        self.J_pad = ((self.J + n_dev - 1) // n_dev) * n_dev
        grown = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b], axis=0),
            real_data, stack_silos(new))
        self.data = self.pad_silo_axis(grown)
        self.num_obs = np.concatenate([
            self.num_obs[:old_J],
            # repro-lint: allow[R4] — host staging of a Python list at growth time, not a device pull
            np.asarray(list(num_obs), np.float32),
        ])
        self.num_obs = np.concatenate([
            self.num_obs,
            np.broadcast_to(self.num_obs[:1], (self.J_pad - self.J,)),
        ]).astype(np.float32)

        if self._has_local:
            if eta_rows is None:
                # repro-lint: allow[R1] — per-silo growth init root: a pure function of (seed, roster index), re-derived bit-exactly on resume
                root = jax.random.PRNGKey(self.seed + 1)
                keys = jnp.stack([
                    jax.random.fold_in(root, j)
                    for j in range(old_J, self.J)])
                new_eta = jax.vmap(self.problem.local_family.init)(keys)
            else:
                if len(eta_rows) != len(new):
                    raise ValueError(
                        f"eta_rows has {len(eta_rows)} entries for "
                        f"{len(new)} joining silos")
                new_eta = stack_silos(list(eta_rows))
            new_opt = jax.vmap(self._local_opt.init)(new_eta)
            for k, rows in (("eta_L", new_eta), ("opt_local", new_opt)):
                real = jax.tree_util.tree_map(
                    lambda x: x[:old_J], self.state[k])
                self.state[k] = self.pad_silo_axis(jax.tree_util.tree_map(
                    lambda a, b: jnp.concatenate([a, b], axis=0),
                    real, rows))

        old_strat = self.state.get("strategy", {})
        if jax.tree_util.tree_leaves(old_strat):
            fresh = self._strategy.init_silo_state(self)
            self.state["strategy"] = jax.tree_util.tree_map(
                lambda f, o: f.at[:old_J].set(o[:old_J]), fresh, old_strat)
        self.place()

    # -- model-axis wire sharding -------------------------------------------
    #
    # On a 2-D (silo, model) mesh each device uploads one model-column
    # block of its silo rows' wire. The upload pipeline runs on FULL
    # rows first (DP noise and int8 row scales stay bit-identical to
    # the 1-D mesh), then every device slices its P/model_world column
    # block, so the big gather over "silo" moves (J_pad, Pb) blocks —
    # 1/model_world of the 1-D mesh's per-device gather traffic. A
    # second, row-local gather over "model" reconstructs the full
    # (J_pad, P) matrix BEFORE decode/aggregation, so the combine
    # compiles against the exact shapes and values of the 1-D mesh.

    def _model_block(self, P_dim: int):
        """(Pb, pad): the column-block width and zero-pad up to mw·Pb."""
        mw = self.model_world
        Pb = -(-P_dim // mw)
        return Pb, Pb * mw - P_dim

    def _shard_model_cols(self, enc: PyTree, P_dim: int) -> PyTree:
        """Slice every (rows, P) wire leaf to this device's column block.

        Per-silo side leaves (the int8 scale vector) have no P trailing
        dim and stay replicated over ``model``.
        """
        if self.model_world == 1:
            return enc
        Pb, pad = self._model_block(P_dim)
        mi = jax.lax.axis_index("model")

        def leaf(x):
            if x.ndim < 2 or x.shape[-1] != P_dim:
                return x
            xp = jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),))
            return jax.lax.dynamic_slice_in_dim(
                xp, mi * Pb, Pb, axis=x.ndim - 1)

        return jax.tree_util.tree_map(leaf, enc)

    def _gather_model_cols(self, enc: PyTree, P_dim: int) -> PyTree:
        """Silo-gathered (J_pad, Pb) blocks -> the full (J_pad, P) wire.

        The inverse of :meth:`_shard_model_cols`, run BEFORE decode and
        aggregation: the combine then compiles against the exact shapes
        and values of the 1-D mesh, which is what keeps 2-D trajectories
        bit-exact. (XLA's axis-0 reductions are not bitwise invariant
        under column slicing — a columnwise combine + concat drifts at
        the last bit for some widths — so the blocks must be rejoined
        first.) The int8 wire gathers its quantized bytes here; per-row
        side leaves (the f32 scale vector) were never sliced and stay
        as gathered over ``silo``.
        """
        if self.model_world == 1:
            return enc
        Pb, pad = self._model_block(P_dim)

        def leaf(x):
            if x.ndim < 2 or x.shape[-1] != Pb:
                return x
            full = jax.lax.all_gather(x, "model", axis=x.ndim - 1,
                                      tiled=True)
            return full[..., :P_dim] if pad else full

        return jax.tree_util.tree_map(leaf, enc)

    # -- wire accounting -----------------------------------------------------

    def ship_template(self, algorithm=None) -> PyTree:
        """Shape-only pytree of one silo's upload (pre-compression)."""
        return self._resolve(algorithm).ship_template(self)

    def wire_spec(self, algorithm=None) -> TreeSpec:
        """The flat wire bijection of one upload (static; P = its dim)."""
        return TreeSpec.of(self.ship_template(algorithm))

    def bytes_up_per_silo(self, algorithm=None) -> int:
        """Post-compression upload bytes for one silo, one gather.

        The compressor sees ONE (P,) float32 vector — an int8 codec
        therefore pays a single 4-byte scale per silo — so the host
        meter matches what the compiled collective actually gathers.
        """
        return self.compressor.wire_bytes(self.ship_template(algorithm))

    def bytes_down_per_silo(self) -> int:
        """Broadcast bytes: (θ, η_G) raw; the round key is ~0 and elided."""
        return tree_bytes(
            {"theta": self.state["theta"], "eta_G": self.state["eta_G"]}
        )

    def compiled_collective_bytes(
        self, algorithm=None, local_steps: int = 1
    ) -> Dict[str, float]:
        """Ring-traffic bytes per collective kind in the compiled round.

        Lowers the jitted round function and applies
        ``launch.roofline.collective_bytes`` to the optimized HLO. On a
        single-device mesh XLA elides the collectives entirely (all
        entries 0); run under a multi-device mesh (or the forced-host-
        device trick of ``launch/comm.py``) for real numbers. On a 2-D
        ``(silo, model)`` mesh the total covers BOTH collectives: the
        silo gather of model-column blocks (1/model_world of the 1-D
        gather) plus the small reconstruction gather over ``model``.
        """
        from repro.launch.roofline import collective_bytes

        compiled = self._lower(algorithm, local_steps).compile()
        return collective_bytes(compiled.as_text())

    def compiled_roofline(
        self, algorithm=None, local_steps: int = 1
    ) -> Dict[str, float]:
        """Roofline terms of the compiled round: FLOPs + bytes moved.

        Lowers the jitted round function and reads XLA's
        ``cost_analysis`` (per-partition FLOPs and HBM bytes accessed)
        plus ``launch.roofline.collective_bytes`` on the optimized HLO.
        The ``bytes_accessed`` term is what the fused wire kernels
        attack: fewer memory passes over the (J, P) matrix per round.
        """
        from repro.launch.roofline import collective_bytes

        compiled = self._lower(algorithm, local_steps).compile()
        ca = compiled.cost_analysis() or {}
        return {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "collective_bytes": float(
                sum(collective_bytes(compiled.as_text()).values())),
        }

    def _lower(self, algorithm, local_steps: int):
        """Lower one compiled round with all-ones masks (for inspection)."""
        strat = self._resolve(algorithm)
        fn = self._get_round(strat, local_steps)
        mask_shape = ((local_steps, self.J_pad) if strat.cadence == "step"
                      else (self.J_pad,))
        ones = jnp.ones(mask_shape, jnp.float32)
        with debug.suspended_tracing():  # inspection traces are free
            return fn.lower(
                self.state, self.data, jnp.asarray(self.num_obs),
                # repro-lint: allow[R1] — dummy key for shape-only lowering; never executed
                jax.random.PRNGKey(0), ones, ones
            )

    def _fused_trim(self):
        """Fused-reduction mode for the configured aggregator.

        ``(None,)`` → fused weighted mean, ``(frac,)`` → fused trimmed
        mean, ``None`` → aggregator not expressible as a fused kernel
        (custom subclass): the fused wire falls back to
        ``aggregator.combine`` on the dequantized matrix.
        """
        fused = getattr(self.aggregator, "fused_reduction", None)
        if fused == "mean":
            return (None,)
        if fused == "trimmed":
            return (float(self.aggregator.trim_frac),)
        return None

    # -- the compiled round --------------------------------------------------

    def _get_round(self, algorithm, local_steps: int) -> Callable:
        """The compiled round of (strategy, K) at this ``J_pad``, its
        control inputs (round key, mask, weights) explicit arguments.

        The one source of the round body: ``run_buffered`` and schedules
        drawn on the host launch it, and the in-graph round of
        :meth:`_sync_round` traces it inline.
        """
        strat = self._resolve(algorithm)
        strat.validate(self)
        self._ensure_strategy_state(strat)
        # J_pad keys the entry: growing the silo axis past a mesh-chunk
        # boundary is a NEW graph (every silo-sharded shape changes),
        # while growth within the padded chunk reuses the compiled one
        # — per-silo counts ride the jit boundary as the n_j argument.
        key = (strat.cache_key(), local_steps, self.J_pad)
        if key not in self._round_fns:
            accumulate = self.accumulates(strat)
            if strat.cadence == "step":
                body = self._step_body(strat, local_steps, accumulate)
            elif strat.cadence == "round":
                body = self._round_body(strat, local_steps)
            else:
                raise ValueError(
                    f"strategy {strat.name!r} has unknown cadence "
                    f"{strat.cadence!r} (step/round)"
                )
            sharded = jax.shard_map(
                body,
                mesh=self.mesh,
                in_specs=(
                    P(), P(), P(),  # theta, eta_G, opt_server (replicated)
                    P("silo"), P("silo"),  # eta_L, opt_local
                    P("silo"),  # per-silo strategy state (λ_j, ...)
                    P("silo"), P("silo"), P("silo"),  # data, sids, n_j
                    # Participation mask rides ONCE, replicated; each block
                    # slices its silos' entries via sids. Passing it a
                    # second time with P("silo") made GSPMD reshard it with
                    # an extra 4-byte all-gather in the compiled round.
                    # ``weights`` are the aggregation weights (== mask on
                    # the sync path; staleness-decayed on the async path).
                    P(), P(), P(),  # full mask, full weights, round key
                ),
                out_specs=(
                    P(), P(), P(), P("silo"), P("silo"), P("silo"), P()
                ),
                check_vma=False,
            )

            # Mesh shape and J_pad ride the tag (a topology change or a
            # padded-chunk growth step is a legitimate new trace); the
            # wire stays LAST — that suffix is part of the watchdog-tag
            # contract (tests/test_sanitize).
            trace_tag = ("round", strat.cache_key(), local_steps,
                         self.J_pad,
                         tuple(sorted(self.mesh.shape.items())), self.wire)
            j_pad = self.J_pad

            def round_fn(state, data, n_j, round_key, mask, weights):
                # Trace-time only: the recompile watchdog's counter
                # (no-op unless repro.debug.sanitize is active).
                debug.trace_event(trace_tag)
                sids = jnp.arange(j_pad, dtype=jnp.int32)
                (theta, eta_G, opt_server, eta_L, opt_L, strat_state,
                 elbos) = sharded(
                    state["theta"], state["eta_G"], state["opt_server"],
                    state["eta_L"], state["opt_local"],
                    state.get("strategy", {}),
                    data, sids, n_j, mask, weights, round_key,
                )
                new_state = {
                    "theta": theta, "eta_G": eta_G, "eta_L": eta_L,
                    "opt_server": opt_server, "opt_local": opt_L,
                    "strategy": strat_state,
                }
                return new_state, {"elbo": elbos}

            # Explicit shardings: the outputs come back exactly as the
            # inputs went in (see ``place``), so round r+1 reuses round
            # r's compiled graph. Control inputs are small and replicate.
            state_sh, data_sh = self._shardings()
            rep = NamedSharding(self.mesh, P())
            # A round that accumulates holds no spare copy of the state
            # either: the new state is written over the old one.
            self._round_fns[key] = jax.jit(
                round_fn,
                in_shardings=(state_sh, data_sh, rep, rep, rep, rep),
                out_shardings=(state_sh, {"elbo": rep}),
                donate_argnums=(0,) if accumulate else (),
            )
        return self._round_fns[key]

    def _sync_round(self, strat: ServerStrategy, local_steps: int,
                    rule: tuple) -> Callable:
        """The synchronous round, its control inputs drawn in the graph.

        Cached per (strategy, K, J_pad, ``rule``) in ``_round_fns``,
        beside the explicit-input round of :meth:`_get_round`, whose body
        it traces inline: only this program compiles on the path. From
        the absolute round index ``r``, a traced int32 scalar, it derives
        what the host used to draw and send every round: the round key
        ``fold_in(base_key, r)``; the E exchange masks of
        :func:`~repro.federated.scheduler.draw_masks` under the static
        ``rule``, at schedule indices ``r * K + t`` (step cadence) or
        ``r``; their product with the membership and staleness vectors;
        and the meter's per-exchange active and invited counts. It hands
        back ``r + 1`` beside them, so a steady loop gives the next round
        its index without a transfer.
        """
        key = (strat.cache_key(), local_steps, self.J_pad, rule)
        if key in self._round_fns:
            return self._round_fns[key]
        body = self._get_round(strat, local_steps)
        step_cadence = strat.cadence == "step"
        j_pad = self.J_pad
        cols = min(rule[0], j_pad)

        def on_axis(m):
            # The scheduler is roster-wide: its first columns are the
            # stacked silos; the padded tail is never invited.
            return jnp.pad(m[:, :cols], ((0, 0), (0, j_pad - cols)))

        def round_fn(state, data, n_j, ctl):
            r = ctl["round"]
            # Step cadence gathers every local step, each gather its own
            # draw (schedule index = exchange index), which the
            # accountant's per-exchange amplification needs; round
            # cadence gathers once, one draw a round.
            idx = (r * local_steps + jnp.arange(local_steps, dtype=r.dtype)
                   if step_cadence else r[None])
            inv, rep = draw_masks(ctl["seed"], idx, rule)
            inv, rep = on_axis(inv), on_axis(rep)
            weights = rep * ctl["weight"]
            inv, mask = inv * ctl["present"], rep * ctl["present"]
            active = jnp.sum(mask, axis=1)
            invited = jnp.maximum(jnp.sum(inv, axis=1), active)
            if not step_cadence:
                mask, weights = mask[0], weights[0]
            round_key = jax.random.fold_in(ctl["key"], r)
            state, metrics = body(state, data, n_j, round_key, mask, weights)
            # What the host reads, as ONE array (one transfer): the ELBO
            # trace, then the E active and E invited counts (small
            # integers, exact in float32).
            report = jnp.concatenate([metrics["elbo"], active, invited])
            return state, {"report": report, "round": r + 1}

        state_sh, data_sh = self._shardings()
        rep_sh = NamedSharding(self.mesh, P())
        self._round_fns[key] = jax.jit(
            round_fn,
            in_shardings=(state_sh, data_sh, rep_sh, rep_sh),
            out_shardings=(state_sh, rep_sh),
            donate_argnums=(0,) if self.accumulates(strat) else (),
        )
        return self._round_fns[key]

    def _run_setup(self, strat: ServerStrategy, local_steps: int,
                   sched) -> _RunSetup:
        """``run``'s per-Server inputs for (strategy, K, J_pad, schedule).

        A miss also builds the round program, so a hit finds it in
        ``_round_fns``. ``place`` drops this cache, so growth and any
        change of the state's shapes rebuild it; a hit transfers nothing.
        """
        rule = getattr(sched, "graph_rule", None)
        key = (strat.cache_key(), local_steps, self.J_pad, rule,
               None if rule is None else sched.seed)
        setup = self._setups.get(key)
        if setup is not None:
            return setup
        covers = getattr(sched, "num_silos", self.J)
        if covers < self.J:
            raise ValueError(f"the scheduler covers {covers} silos; the "
                             f"federation has J={self.J}")
        # Graph construction and byte metering evaluate wire templates
        # eagerly on host, sanctioned under the transfer guard.
        with debug.host_bridge():
            self._get_round(strat, local_steps)  # may re-place
            ctl = None
            if rule is not None:
                self._sync_round(strat, local_steps, rule)
                members = np.zeros((self.J_pad,), np.float32)
                members[: self.J] = 1.0
                # repro-lint: allow[R1] — root of the round stream; the round program folds in the absolute round index, so resume replays it exactly
                base_key = jax.random.PRNGKey(self.seed)
                ctl = self._control({"key": base_key,
                                     "seed": sched.seed_u32,
                                     "present": members})
                ctl["weight"] = ctl["present"]
            setup = _RunSetup(self.bytes_up_per_silo(strat),
                              self.bytes_down_per_silo(),
                              self._control(self.num_obs), ctl)
        self._setups[key] = setup
        return setup

    def _ctx(self, K: int, wire) -> StrategyContext:
        """Static per-body facts handed to every strategy hook."""
        return StrategyContext(
            problem=self.problem,
            # The FULL federation width, not the currently-joined J: a
            # dynamic population's estimators target the roster-wide
            # ELBO, with absent silos as non-participants (§3 Remark).
            # Without a population the two coincide.
            J=self.fed_J,
            K=K,
            server_opt=self._server_opt,
            local_opt=self._local_opt,
            has_local=self._has_local,
            eta_mode=self.eta_mode,
            aggregator=self.aggregator,
            wire=wire,
            fused=self.wire == "fused",
            # N = Σ_j N_j over the full federation — the padded tail
            # repeats silo 0's count purely to keep the dummy silos'
            # per-silo scale finite (their contribution is masked out).
            total_obs=self.fed_obs,
        )

    def _ship_upload(self, ship, m_j, key, ref, wire, fused):
        """The strategy-independent upload pipeline for one silo.

        pack → (fused: defer to the stacked fused pass) → DP privatize
        against the strategy's wire reference → data-independent filler
        for non-participants (the reference itself, or zeros) → encode.
        Non-participating silos never put data-dependent bytes on the
        wire — they "don't upload"; aggregation masks them anyway — so
        the accountant's subsampling amplification holds on what is
        actually transmitted.
        """
        ship = wire.pack(ship)
        if fused:
            # Privatize/mask/quantize run as ONE fused pass over the
            # stacked (J, P) block after the per-silo vmap.
            return ship
        if self.privacy is not None:
            # Clip + noise BEFORE compression and the gather: the wire
            # never carries a raw silo quantity.
            ship = self.privacy.privatize(ship, key, reference=ref)
        idle = (ref if ref is not None
                else jax.tree_util.tree_map(jnp.zeros_like, ship))
        ship = _select(m_j > 0.5, ship, idle)
        return self.compressor.encode(ship)

    def _packed_reference(self, strat, ctx, wire, theta, eta_G):
        """The strategy's wire reference, packed to wire form (or None)."""
        ref = strat.reference_tree(ctx, theta, eta_G)
        return None if ref is None else wire.pack(ref)

    def accumulates(self, algorithm=None) -> bool:
        """Whether the round sums the silos' uploads one silo after another.

        The stacked round holds every local silo's upload twice — its
        gradient tree and its packed wire row — before one gather and one
        combine. When that stack would take more than a quarter of a
        device's memory (a backbone θ of hundreds of millions of floats),
        a step-cadence round with the plain mean combine — no DP, no
        compression, no robust aggregator, a 1-D silo mesh — instead runs
        each device's silos in turn into one running sum of the upload's
        P floats, then sums those over ``silo`` (the gather's place), and
        donates the round's state (arrays handed to the Server are
        consumed by its first round). The meter still bills every silo's
        row. Everything is read from the configuration and the device;
        no option selects it.
        """
        strat = self._resolve(algorithm)
        if (strat.cadence != "step" or self.privacy is not None or self.model_world > 1
                or _wire_codec(self.compressor) != "identity"
                or getattr(self.aggregator, "fused_reduction", None) != "mean"):
            return False
        per_device = self.J_pad // int(self.mesh.shape["silo"])
        stacked = 2 * per_device * self.wire_spec(strat).dim * 4
        stats = self.mesh.local_devices[0].memory_stats() or {}
        return stacked > stats.get("bytes_limit", float("inf")) / 4

    def _step_body(self, strat: ServerStrategy, K: int,
                   accumulate: bool = False) -> Callable:
        """Round = K synchronized steps: gather + server update every step
        (or, with ``accumulate``, a running sum over silos; see
        :meth:`accumulates`)."""
        problem = self.problem
        agg, comp = self.aggregator, self.compressor
        privacy = self.privacy
        # Flat wire: the whole upload is ONE (P,) f32 vector, so clip,
        # noise, quantization, the gather and the aggregation below all
        # see a single array per silo ((J, P) once stacked). The fused
        # wire keeps the same layout but runs those stages as the Pallas
        # kernels of repro.kernels.wire on the stacked block.
        wire = self.wire_spec(strat)
        fused = self.wire == "fused"
        int8 = _wire_codec(comp) == "int8"
        trim = self._fused_trim()
        ctx = self._ctx(K, wire)
        # Shapes only: a device array captured here would stay alive as
        # long as the cached round program.
        template = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32),
            strat.ship_template(self)) if accumulate else None

        def silo_sum(theta, eta_G, eta_L, opt_L, strat_state, data_sh,
                     sids, mask_sh, w_sh, n_j, round_key, t, eps_G, w_full):
            """This device's silos one after another into a running sum
            of their weighted uploads; the sum over ``silo`` over the
            total weight is the plain mean of the stacked combine."""
            def one(acc, xs):
                eta_Lj, opt_Lj, st_j, data_j, sid, m_j, w_j, n_obs_j = xs
                eta_Lj, opt_Lj, st_j, ship, hatLj = strat.silo_step(
                    ctx, theta, eta_G, eta_Lj, opt_Lj, st_j,
                    data_j, sid, m_j, n_obs_j, round_key, t, eps_G,
                )
                acc = jax.tree_util.tree_map(
                    lambda a, g: a + jnp.where(m_j > 0.5, w_j * g, 0.0),
                    acc, ship)
                return acc, (eta_Lj, opt_Lj, st_j, hatLj * m_j)

            with jax.named_scope("round.silo_sum"):
                acc = jax.tree_util.tree_map(
                    lambda x: jnp.zeros(x.shape, jnp.float32), template)
                acc, (eta_L, opt_L, strat_state, hatL) = jax.lax.scan(
                    one, acc, (eta_L, opt_L, strat_state, data_sh, sids,
                               mask_sh, w_sh, n_j))
                acc = jax.lax.psum(acc, "silo")
            total = jnp.sum(w_full)
            denom = jnp.where(total > 0.0, total, 1.0)
            mean_g = jax.tree_util.tree_map(lambda a: a / denom, acc)
            hatL_sum = jax.lax.psum(jnp.sum(hatL), "silo")
            return eta_L, opt_L, strat_state, mean_g, hatL_sum

        def body(theta, eta_G, opt_server, eta_L, opt_L, strat_state,
                 data_sh, sids, n_j, masks_full, weights_full, round_key):
            # masks_full: (K, J) — step-cadence strategies sample
            # participation PER EXCHANGE (each gather is its own
            # subsampling event; this is what makes the accountant's
            # per-exchange amplification sound — one shared mask across
            # the K gathers would expose K correlated outputs per draw).
            # weights_full: (K, J) aggregation weights — identical to
            # masks_full on the sync path.

            def sync_step(carry, step_xs):
                t, mask_full, w_full = step_xs
                mask_sh = mask_full[sids]  # this block's silos
                n_active = jnp.maximum(jnp.sum(mask_full), 1.0)
                (theta, eta_G, opt_server, eta_L, opt_L,
                 strat_state) = carry
                eps_G = global_eps(problem, round_key, t)
                if accumulate:
                    eta_L, opt_L, strat_state, mean_g, hatL_sum = silo_sum(
                        theta, eta_G, eta_L, opt_L, strat_state, data_sh,
                        sids, mask_sh, w_full[sids], n_j, round_key, t,
                        eps_G, w_full)
                    theta, eta_G, opt_server, elbo = strat.server_step(
                        ctx, theta, eta_G, opt_server, mean_g, hatL_sum,
                        n_active, eps_G,
                    )
                    return (theta, eta_G, opt_server, eta_L, opt_L,
                            strat_state), elbo
                ref = self._packed_reference(strat, ctx, wire, theta, eta_G)

                def per_silo(eta_Lj, opt_Lj, st_j, data_j, sid, m_j,
                             n_obs_j):
                    eta_Lj, opt_Lj, st_j, ship, hatLj = strat.silo_step(
                        ctx, theta, eta_G, eta_Lj, opt_Lj, st_j,
                        data_j, sid, m_j, n_obs_j, round_key, t, eps_G,
                    )
                    key = (None if privacy is None
                           else privacy.upload_key(round_key, t, sid))
                    ship = self._ship_upload(ship, m_j, key, ref, wire,
                                             fused)
                    return eta_Lj, opt_Lj, st_j, ship, hatLj * m_j

                eta_L, opt_L, strat_state, enc, hatL = jax.vmap(per_silo)(
                    eta_L, opt_L, strat_state, data_sh, sids, mask_sh, n_j
                )
                if fused:
                    enc = _fused_ship(
                        enc, mask_sh,
                        _fused_keys(privacy, round_key, t, sids),
                        ref, privacy, comp, int8)
                # 2-D mesh: slice AFTER the full-row pipeline so DP
                # noise / int8 scales match the 1-D mesh bit-exactly,
                # then rejoin the gathered blocks before decoding.
                enc = self._shard_model_cols(enc, wire.dim)
                enc = _coalesced_all_gather(enc, "silo")
                enc = self._gather_model_cols(enc, wire.dim)
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
                    shipped = jax.vmap(comp.decode)(enc)  # (J, P) matrix
                    mean_g = agg.combine(shipped, w_full)
                mean_g = wire.unpack(mean_g)
                theta, eta_G, opt_server, elbo = strat.server_step(
                    ctx, theta, eta_G, opt_server, mean_g, hatL_sum,
                    n_active, eps_G,
                )
                carry = (theta, eta_G, opt_server, eta_L, opt_L,
                         strat_state)
                return carry, elbo

            carry = (theta, eta_G, opt_server, eta_L, opt_L, strat_state)
            carry, elbos = jax.lax.scan(
                sync_step, carry, (jnp.arange(K), masks_full, weights_full)
            )
            return (*carry, elbos)

        return body

    def _round_body(self, strat: ServerStrategy, K: int) -> Callable:
        """Round = K local steps per silo, ONE gather + one server merge."""
        agg, comp = self.aggregator, self.compressor
        privacy = self.privacy
        wire = self.wire_spec(strat)
        fused = self.wire == "fused"
        int8 = _wire_codec(comp) == "int8"
        trim = self._fused_trim()
        ctx = self._ctx(K, wire)

        def body(theta, eta_G, opt_server, eta_L, opt_L, strat_state,
                 data_sh, sids, n_j, mask_full, w_full, round_key):
            mask_sh = mask_full[sids]  # this block's silos
            n_active = jnp.maximum(jnp.sum(mask_full), 1.0)
            # The strategy's wire reference — for broadcast-reference
            # strategies this is the round's public (θ, η_G) in wire
            # form: the DP delta reference AND the data-independent
            # upload of silos that did not participate.
            ref = self._packed_reference(strat, ctx, wire, theta, eta_G)

            def per_silo(eta_Lj, opt_Lj, st_j, data_j, sid, m_j, n_obs_j):
                eta_Lj, opt_Lj, st_j, ship, elbos = strat.local_run(
                    ctx, theta, eta_G, eta_Lj, opt_Lj, st_j,
                    data_j, sid, m_j, n_obs_j, round_key,
                )
                key = (None if privacy is None
                       else privacy.upload_key(round_key, 0, sid))
                ship = self._ship_upload(ship, m_j, key, ref, wire, fused)
                return eta_Lj, opt_Lj, st_j, ship, elbos * m_j

            eta_L, opt_L, strat_state, enc, elbos = jax.vmap(per_silo)(
                eta_L, opt_L, strat_state, data_sh, sids, mask_sh, n_j
            )
            if fused:
                enc = _fused_ship(
                    enc, mask_sh, _fused_keys(privacy, round_key, 0, sids),
                    ref, privacy, comp, int8)
            # 2-D mesh: slice AFTER the full-row pipeline so DP
            # noise / int8 scales match the 1-D mesh bit-exactly,
            # then rejoin the gathered blocks before decoding.
            enc = self._shard_model_cols(enc, wire.dim)
            enc = _coalesced_all_gather(enc, "silo")
            enc = self._gather_model_cols(enc, wire.dim)
            elbo_t = jax.lax.psum(jnp.sum(elbos, axis=0), "silo") / n_active

            if fused:
                # Round-cadence merges may need every silo's upload (the
                # barycenter), so the dequantized matrix is materialized
                # here (unlike the step cadence); the reduction itself
                # still runs as the fused kernel.
                shipped = _fused_decode(enc, comp, int8)
                vec = (wire_kernels.fused_combine(
                    shipped, w_full, trim_frac=trim[0])
                    if trim is not None else agg.combine(shipped, w_full))
                combined = wire.unpack(vec)
            else:
                shipped = jax.vmap(comp.decode)(enc)  # (J, P) matrix
                combined = wire.unpack(agg.combine(shipped, w_full))
            theta_new, eta_new, opt_server = strat.server_update(
                ctx, theta, eta_G, opt_server, combined, shipped,
                w_full, n_active,
            )
            return (theta_new, eta_new, opt_server, eta_L, opt_L,
                    strat_state, elbo_t)

        return body

    # -- driver --------------------------------------------------------------

    def _graph_control(self, setup: _RunSetup, r: int, present=None,
                       stale_w=None) -> tuple:
        """The in-graph round's control argument for absolute round ``r``.

        In a steady loop nothing moves host to device: the round index
        is the one the previous round handed back, and without a
        population the membership vector is the cached one. A fresh
        index (first call, resume, an explicit ``start_round``) and a
        population's per-round membership and staleness vectors go over
        in one transfer.
        """
        ctl = dict(setup.ctl)
        sent: Dict[str, np.ndarray] = {}
        if self._next_round is not None and self._next_round[0] == r:
            ctl["round"] = self._next_round[1]
        else:
            sent["round"] = np.int32(r)
        if present is not None:
            pad = (0, self.J_pad - self.J)
            sent["present"] = np.pad(present, pad)
            sent["weight"] = np.pad(stale_w, pad)
        if sent:
            with debug.host_bridge():
                ctl.update(self._control(sent))
        return (ctl,)

    def _host_control(self, sched, r: int, local_steps: int,
                      step_cadence: bool, present, stale_w):
        """Host-drawn control inputs, for a scheduler whose rule is host
        code (it overrides ``round_masks``): the round's E exchange masks
        in one draw and one pull, sent with the round key in one
        transfer. Returns the round's arguments and the meter's
        per-exchange (active, invited) counts."""
        ex_idx = ([r * local_steps + t for t in range(local_steps)]
                  if step_cadence else [r])
        with debug.host_bridge():
            inv, rep = jax.device_get(sched.round_masks(ex_idx))
            if present is not None:
                # The scheduler is roster-wide; slice to the joined J.
                wts = rep[:, : self.J] * stale_w
                rep = rep[:, : self.J] * present
                inv = inv[:, : self.J] * present
            else:
                wts = rep
            mask = np.zeros((len(ex_idx), self.J_pad), np.float32)
            weights = np.zeros_like(mask)
            mask[:, : self.J], weights[:, : self.J] = rep, wts
            if not step_cadence:
                mask, weights = mask[0], weights[0]
            # repro-lint: allow[R1] — root of the round stream, folded with the absolute round index on the same line
            round_key = jax.random.fold_in(jax.random.PRNGKey(self.seed), r)
            args = self._control((round_key, mask, weights))
        # Stragglers received the broadcast before dropping: bill their
        # download; an absent silo receives no broadcast.
        active = rep.sum(axis=1)
        return args, (active, np.maximum(inv.sum(axis=1), active))

    def run(
        self,
        num_rounds: int,
        *,
        algorithm=None,
        local_steps: int = 1,
        scheduler: Optional[RoundScheduler] = None,
        callback: Optional[Callable[[int, dict], None]] = None,
        start_round: int = 0,
        population=None,
    ) -> Dict[str, list]:
        """Advance the federation ``num_rounds`` rounds; returns history.

        ``algorithm`` selects the update rule — a registry name (any of
        :func:`repro.federated.strategy.strategy_names`), a
        ``StrategySpec``, or a ``ServerStrategy`` instance; None uses
        the Server's default strategy.

        ``start_round`` is the absolute index of the first round: the
        round PRNG key, the scheduler's participation draws and the
        accountant's exchange indices are all functions of the absolute
        round, so ``run(a); run(b, start_round=a)`` replays exactly the
        same stream as one ``run(a + b)`` — the property
        ``federated.api.Experiment`` builds its bit-exact save/resume
        guarantee on.

        One round is ``local_steps`` optimizer steps: a step-cadence
        strategy pays one up+down exchange per step, a round-cadence
        strategy one per round — the meter (``self.comm``) records
        exactly that asymmetry. ``scheduler`` injects partial
        participation / straggler masks: uninvited silos cost nothing;
        invited stragglers (dropout) receive the broadcast (download is
        billed) but never upload, and the aggregation is rescaled by
        the realized active count (unbiased, §3 Remark).

        With ``privacy`` set, each of the round's ``exchanges`` gathers
        is one (subsampled) Gaussian-mechanism invocation: the owned
        accountant composes them (q = the scheduler's invitation rate)
        and ``history["epsilon"]`` traces the cumulative ε at the
        policy's δ after each round. A step-cadence strategy draws a
        FRESH participation mask for every local step (schedule index =
        exchange index ``r * local_steps + t``), so each gather is an
        independent subsampling event and the per-exchange amplification
        is sound; a round-cadence strategy draws one mask per round
        (index ``r``).

        ``population`` optionally threads a
        :class:`~repro.federated.population.PopulationEngine` through
        the loop: its ``begin_round`` hook processes the round's churn
        events first (joins may grow the silo axis, which re-fetches
        the compiled round for the new ``J_pad``), and the resulting
        membership mask multiplies the scheduler's participation mask
        — with a returning silo's first round back staleness-decayed
        in the aggregation weights. The scheduler stays roster-wide
        (its masks are sliced to the currently-joined J), so the
        participation schedule is independent of the churn schedule.

        Control plane: a :class:`RoundScheduler` (any scheduler whose
        ``graph_rule`` is set) is drawn inside the round program
        (:meth:`_sync_round`), so a round costs the host one launch
        and one pull (ELBO and the meter's counts), and in a steady loop
        without a population nothing moves host to device. The set-up
        (byte meter, placed inputs) is cached per (strategy, K, J_pad,
        schedule) until :meth:`place`; the programs stay in the graph
        cache.
        A scheduler that overrides ``round_masks`` keeps the host draw:
        one more pull and one transfer a round.
        """
        if local_steps < 1:
            raise ValueError(f"local_steps must be >= 1, got {local_steps}")
        strat = self._resolve(algorithm)
        # The default scheduler covers the FULL federation (fed_J == J
        # without a population): churn multiplies membership into the
        # roster-wide participation draws, it never re-shapes them.
        sched = scheduler or RoundScheduler(self.fed_J, seed=self.seed)
        covers = getattr(sched, "num_silos", self.J)
        if population is None and covers != self.J:
            raise ValueError(f"the scheduler covers {covers} silos; the "
                             f"federation has J={self.J}")
        rule = getattr(sched, "graph_rule", None)
        step_cadence = strat.cadence == "step"
        exchanges = local_steps if step_cadence else 1
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
        for r in range(start_round, start_round + num_rounds):
            with jax.profiler.TraceAnnotation("server.round_control"):
                present = stale_w = None
                if population is not None:
                    # Churn first: a join may grow J (and step J_pad,
                    # which drops the cached set-up); the membership and
                    # staleness vectors cover the post-growth J.
                    with debug.host_bridge():
                        present, stale_w = population.begin_round(self, r)
                setup = self._run_setup(strat, local_steps, sched)
                fn = (self._get_round(strat, local_steps) if rule is None
                      else self._sync_round(strat, local_steps, rule))
                if setup.ctl is not None:
                    args = self._graph_control(setup, r, present, stale_w)
                else:
                    args, counts = self._host_control(
                        sched, r, local_steps, step_cadence, present,
                        stale_w)
            with jax.profiler.TraceAnnotation("server.round_launch"):
                self.state, metrics = fn(self.state, self.data, setup.n_j,
                                         *args)
            # Explicit pulls, legal under the transfer guard: the in-graph
            # round's counts ride with its ELBO in one.
            with jax.profiler.TraceAnnotation("server.round_pull"):
                if setup.ctl is not None:
                    report = jax.device_get(metrics["report"])
                    self._next_round = (r + 1, metrics["round"])
                    elbos, active, invited = np.split(
                        report, [report.size - 2 * exchanges,
                                 report.size - exchanges])
                else:
                    elbos = jax.device_get(metrics["elbo"])
                    active, invited = counts
            up = int(active.sum()) * setup.up1
            down = int(invited.sum()) * setup.down1
            n_active = int(active[-1])  # the round's final exchange
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
