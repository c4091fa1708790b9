"""Pluggable server-side update rules: the ``ServerStrategy`` protocol.

The compiled :class:`~repro.federated.runtime.Server` advances all J silos
through one shard_map graph per round, but everything *algorithm-specific*
— what each silo computes locally, what it ships, and how the server folds
the aggregate back into (θ, η_G) — lives here, behind a name-keyed
registry mirroring :mod:`repro.core.family`'s ``VariationalFamily``:

  * ``register_strategy``/``get_strategy``/``strategy_names`` — the
    registry; :class:`StrategySpec` is the serializable handle that rides
    on ``ExperimentSpec`` (exactly like ``FamilySpec``).
  * :class:`ServerStrategy` — the protocol. Capability flags
    (``cadence``, ``has_silo_state``, ``wire_reference``) tell the
    runtime how to wire a strategy into the generic round bodies; the
    hooks supply the per-silo and server-side math.

Two cadences cover every federated-VI update rule in the zoo:

  * ``cadence == "step"`` — synchronize every local step (one gather per
    optimizer step). Hooks: :meth:`ServerStrategy.silo_step` +
    :meth:`ServerStrategy.server_step`. SFVI (paper Algorithm 1).
  * ``cadence == "round"`` — K local steps per silo, ONE gather, one
    server merge. Hooks: :meth:`ServerStrategy.local_run` +
    :meth:`ServerStrategy.server_update`. SFVI-Avg (§3.2), PVI
    (Ashman et al., arXiv:2202.12275) and federated EP (Guo et al.,
    arXiv:2302.04228).

Every strategy ships ONE pytree per silo per exchange, and the runtime
packs it over the same ``(J, P)`` wire regardless of what the
tree means (gradients, parameters, natural-parameter deltas) — so DP
clip+noise, int8 quantization, async staleness weights and the single
coalesced all_gather apply to PVI/EP exactly as they do to the paper's
two algorithms, and DP composition threads through the one
``RdpAccountant`` unchanged (one privatized flat upload per exchange).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, ClassVar, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.barycenter import family_barycenter
from repro.core.family import eps_shape as family_eps_shape
from repro.kernels import wire as wire_kernels
from repro.optim.base import apply_updates

PyTree = Any

DEFAULT_STRATEGY = "sfvi"


# ---------------------------------------------------------------------------
# Shared-randomness helpers (canonical definitions; runtime re-exports them
# so tests can replay the exact draws)
# ---------------------------------------------------------------------------


def global_eps(problem, round_key: jnp.ndarray, t) -> jnp.ndarray:
    """ε_G for local step ``t`` of a round — identical on every silo."""
    return jax.random.normal(
        jax.random.fold_in(round_key, t),
        family_eps_shape(problem.global_family),
    )


def silo_eps(problem, round_key: jnp.ndarray, t, silo_id):
    """ε_{L_j} for local step ``t`` on silo ``silo_id`` (None if Z_L = ∅)."""
    if not problem.model.has_local:
        return None
    key = jax.random.fold_in(jax.random.fold_in(round_key, 100_003 + t), silo_id)
    return jax.random.normal(key, family_eps_shape(problem.local_family))


def _neg(tree: PyTree) -> PyTree:
    return jax.tree_util.tree_map(lambda x: -x, tree)


def _add(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree_util.tree_map(jnp.add, a, b)


def _sub(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree_util.tree_map(jnp.subtract, a, b)


def _select(keep, new: PyTree, old: PyTree) -> PyTree:
    """Per-leaf ``where`` that preserves dtypes (masked silo-state update)."""
    return jax.tree_util.tree_map(lambda n, o: jnp.where(keep, n, o), new, old)


def _stop(tree: PyTree) -> PyTree:
    return jax.tree_util.tree_map(jax.lax.stop_gradient, tree)


# ---------------------------------------------------------------------------
# Diagonal-Gaussian natural parameters (PVI / EP site algebra)
# ---------------------------------------------------------------------------


def natural_from_eta(family, eta: PyTree) -> Dict[str, jnp.ndarray]:
    """η → natural parameters {h = Σ⁻¹μ, prec = Σ⁻¹} (diag form).

    Uses the family's moment bridge, so any ``moment_form == "diag"``
    family (DiagGaussian, BatchedDiagGaussian, ...) participates without
    knowing about PVI.
    """
    mu, sigma = family.to_moments(eta)
    prec = 1.0 / (sigma * sigma)
    return {"h": mu * prec, "prec": prec}


def eta_from_natural(
    family, nat: Dict[str, jnp.ndarray], prec_floor: float = 1e-6
) -> PyTree:
    """Natural parameters → η, with the precision floored for validity.

    Damped-delta and cavity subtractions can transiently drive a
    precision nonpositive; flooring keeps the resulting distribution
    proper (standard PVI practice) without touching the fixed point,
    where precisions are strictly positive.
    """
    prec = jnp.maximum(nat["prec"], prec_floor)
    sigma = prec ** -0.5
    mu = nat["h"] / prec
    return family.from_moments(mu, sigma)


# ---------------------------------------------------------------------------
# The protocol
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StrategyContext:
    """Static per-body facts the runtime hands every strategy hook.

    Frozen (hashable) so it rides into jitted closures as a constant.
    ``wire`` is the flat :class:`~repro.core.flatten.TreeSpec` bijection
    of one upload; ``shipped`` values passed to
    :meth:`ServerStrategy.server_update` are ``(J, P)`` matrices of
    uploads in that layout.
    """

    problem: Any
    J: int
    K: int
    server_opt: Any
    local_opt: Any
    has_local: bool
    eta_mode: str
    aggregator: Any
    wire: Any
    fused: bool
    total_obs: float


class ServerStrategy:
    """Base class for pluggable server-side update rules.

    Subclasses are frozen dataclasses (their fields are the strategy's
    hyperparameters, e.g. PVI's ``damping``) registered by name via
    :func:`register_strategy`. Capability flags:

    ``cadence``
        ``"step"`` — one gather per local optimizer step (implement
        :meth:`silo_step` / :meth:`server_step`); ``"round"`` — K local
        steps then one gather (implement :meth:`local_run` /
        :meth:`server_update`).
    ``has_silo_state``
        True when the strategy carries per-silo state beyond η_{L_j}
        (e.g. PVI's site approximations λ_j). The runtime stacks it on
        the silo axis, shards it through the round graph next to
        ``eta_L``, and the checkpoint layer rides it on the per-silo
        shards — so save/resume of strategy state is bit-exact for free.
    ``wire_reference``
        What a silo's upload is measured against on the wire:
        ``"zero"`` — ships an absolute quantity; DP privatizes the raw
        tree and non-participants ship zeros. ``"broadcast"`` — ships
        parameters; DP privatizes the delta from the round's public
        broadcast and non-participants ship the broadcast itself. Both
        keep every wire row data-independent for unsampled silos, which
        is what makes the accountant's subsampling amplification sound.
    """

    name: ClassVar[str] = ""
    cadence: ClassVar[str] = "round"
    has_silo_state: ClassVar[bool] = False
    wire_reference: ClassVar[str] = "zero"

    # -- identity ------------------------------------------------------------

    def cache_key(self) -> tuple:
        """Hashable identity for the runtime's compiled-round cache."""
        return (self.name,) + tuple(
            sorted(dataclasses.asdict(self).items())  # type: ignore[call-overload]
        )

    # -- capability / wiring hooks ------------------------------------------

    def validate(self, server) -> None:
        """Raise if the server's configuration cannot host this strategy."""

    def ship_template(self, server) -> PyTree:
        """Shape-only pytree of one silo's upload (pre-compression)."""
        raise NotImplementedError

    def reference_tree(self, ctx: StrategyContext, theta, eta_G):
        """The wire reference (see ``wire_reference``); None means zeros."""
        if self.wire_reference == "broadcast":
            return {"theta": theta, "eta_G": eta_G}
        return None

    def init_silo_state(self, server) -> PyTree:
        """Initial stacked (J_pad, ...) strategy state ({} if stateless)."""
        return {}

    # -- cadence == "step" hooks --------------------------------------------

    def silo_step(
        self, ctx, theta, eta_G, eta_Lj, opt_Lj, state_j,
        data_j, sid, m_j, n_obs_j, round_key, t, eps_G,
    ) -> Tuple[PyTree, PyTree, PyTree, PyTree, jnp.ndarray]:
        """One silo's work for one synchronized step.

        Returns ``(eta_Lj, opt_Lj, state_j, ship_tree, hatLj)``; the
        runtime packs/privatizes/masks/encodes ``ship_tree`` and gathers.
        """
        raise NotImplementedError

    def server_step(
        self, ctx, theta, eta_G, opt_server, mean_tree,
        hatL_sum, n_active, eps_G,
    ) -> Tuple[PyTree, PyTree, PyTree, jnp.ndarray]:
        """Fold one gathered aggregate into the server state.

        ``mean_tree`` is the aggregator's mean-like combine of the
        decoded uploads, unpacked back to ship_template structure.
        Returns ``(theta, eta_G, opt_server, elbo)``.
        """
        raise NotImplementedError

    # -- cadence == "round" hooks -------------------------------------------

    def local_run(
        self, ctx, theta, eta_G, eta_Lj, opt_Lj, state_j,
        data_j, sid, m_j, n_obs_j, round_key,
    ) -> Tuple[PyTree, PyTree, PyTree, PyTree, jnp.ndarray]:
        """One silo's K local steps for a round-cadence strategy.

        Returns ``(eta_Lj, opt_Lj, state_j, ship_tree, elbos)`` with
        ``elbos`` shaped (K,).
        """
        raise NotImplementedError

    def server_update(
        self, ctx, theta, eta_G, opt_server, combined, shipped,
        w_full, n_active,
    ) -> Tuple[PyTree, PyTree, PyTree]:
        """Merge the round's gathered uploads into the server state.

        ``combined`` is the aggregator's combine unpacked to
        ship_template structure; ``shipped`` is the full decoded
        (J, P) matrix for strategies that need every silo's upload (the
        barycenter). Returns ``(theta, eta_G, opt_server)``.
        """
        raise NotImplementedError


# ---------------------------------------------------------------------------
# Registry + spec
# ---------------------------------------------------------------------------

STRATEGIES: Dict[str, type] = {}


def register_strategy(name: str) -> Callable[[type], type]:
    """Class decorator: register a ServerStrategy subclass under ``name``."""

    def wrap(cls: type) -> type:
        if name in STRATEGIES:
            raise ValueError(f"strategy {name!r} already registered")
        cls.name = name
        STRATEGIES[name] = cls
        return cls

    return wrap


def get_strategy(name: str) -> type:
    """Look up a registered strategy class by name."""
    try:
        return STRATEGIES[name]
    except KeyError:
        raise KeyError(
            f"unknown strategy {name!r}; registered: {sorted(STRATEGIES)}"
        ) from None


def strategy_names() -> Tuple[str, ...]:
    """Names of all registered strategies (sorted)."""
    return tuple(sorted(STRATEGIES))


def resolve_strategy(algorithm) -> ServerStrategy:
    """Name / spec / instance → a ServerStrategy instance."""
    if isinstance(algorithm, ServerStrategy):  # repro-lint: allow[R6] — registry front door: input-KIND dispatch (instance | spec | name), not a capability probe
        return algorithm
    if isinstance(algorithm, StrategySpec):  # repro-lint: allow[R6] — registry front door: input-kind dispatch, see above
        return algorithm.build()
    return get_strategy(algorithm)()


@dataclasses.dataclass
class StrategySpec:
    """Serializable handle for a registry strategy (mirrors FamilySpec).

    ``kwargs`` feed the strategy dataclass's hyperparameter fields, e.g.
    ``StrategySpec("pvi", {"damping": 0.2})``.
    """

    name: str = DEFAULT_STRATEGY
    kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def build(self) -> ServerStrategy:
        """Instantiate the registered strategy with this spec's kwargs."""
        cls = get_strategy(self.name)
        fields = {f.name for f in dataclasses.fields(cls)}
        unknown = set(self.kwargs) - fields
        if unknown:
            raise ValueError(
                f"strategy {self.name!r} got unknown kwargs {sorted(unknown)}; "
                f"accepted: {sorted(fields)}"
            )
        return cls(**self.kwargs)

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> StrategySpec:
        return StrategySpec(
            name=d.get("name", DEFAULT_STRATEGY),
            kwargs=dict(d.get("kwargs", {})),
        )


# ---------------------------------------------------------------------------
# The paper's two algorithms as registry entries (bit-exact re-expressions
# of the pre-refactor round bodies — the equivalence suite in
# tests/test_strategies.py holds them to the frozen legacy Server)
# ---------------------------------------------------------------------------


@register_strategy("sfvi")
@dataclasses.dataclass(frozen=True)
class SFVIStrategy(ServerStrategy):
    """Paper Algorithm 1: synchronize (g_j^θ, g_j^η) every local step."""

    cadence: ClassVar[str] = "step"
    has_silo_state: ClassVar[bool] = False
    wire_reference: ClassVar[str] = "zero"

    def ship_template(self, server) -> PyTree:
        return {"g_theta": server.state["theta"], "g_eta": server.state["eta_G"]}

    def silo_step(self, ctx, theta, eta_G, eta_Lj, opt_Lj, state_j,
                  data_j, sid, m_j, n_obs_j, round_key, t, eps_G):
        problem = ctx.problem
        el = eta_Lj if ctx.has_local else None
        eps_L = silo_eps(problem, round_key, t, sid)
        g_th, g_eta, g_loc, hatLj = problem.silo_grads(
            theta, eta_G, el, eps_G, eps_L, data_j
        )
        if ctx.has_local:
            upd, new_opt = ctx.local_opt.update(_neg(g_loc), opt_Lj, el)
            eta_Lj = _select(m_j > 0.5, apply_updates(el, upd), el)
            opt_Lj = _select(m_j > 0.5, new_opt, opt_Lj)
        return eta_Lj, opt_Lj, state_j, {"g_theta": g_th, "g_eta": g_eta}, hatLj

    def server_step(self, ctx, theta, eta_G, opt_server, mean_tree,
                    hatL_sum, n_active, eps_G):
        # J × mean over active = (J/|A|) Σ_active — the unbiased
        # partial-participation estimator of Σ_j (§3 Remark). Scaling
        # after the unpack is bit-identical to scaling the packed
        # vector (elementwise ops commute with reshape/slice).
        J = float(ctx.J)
        g_sum = jax.tree_util.tree_map(lambda x: x * J, mean_tree)
        g_th0, g_eta0, hatL0 = ctx.problem.server_grads(theta, eta_G, eps_G)
        g = {
            "theta": _add(g_sum["g_theta"], g_th0),
            "eta_G": _add(g_sum["g_eta"], g_eta0),
        }
        params = {"theta": theta, "eta_G": eta_G}
        updates, opt_server = ctx.server_opt.update(_neg(g), opt_server, params)
        merged = apply_updates(params, updates)
        elbo = hatL0 + (J / n_active) * hatL_sum
        return merged["theta"], merged["eta_G"], opt_server, elbo


@register_strategy("sfvi_avg")
@dataclasses.dataclass(frozen=True)
class SFVIAvgStrategy(ServerStrategy):
    """§3.2: K local VI steps on the N/N_j-rescaled objective, one merge.

    Ships locally-updated (θ^(j), η_G^(j)); the server FedAvgs θ and
    merges η_G by moment barycenter (or parameter mean, per the server's
    ``eta_mode``).
    """

    cadence: ClassVar[str] = "round"
    has_silo_state: ClassVar[bool] = False
    wire_reference: ClassVar[str] = "broadcast"

    def ship_template(self, server) -> PyTree:
        return {"theta": server.state["theta"], "eta_G": server.state["eta_G"]}

    def local_run(self, ctx, theta, eta_G, eta_Lj, opt_Lj, state_j,
                  data_j, sid, m_j, n_obs_j, round_key):
        problem = ctx.problem
        scale = ctx.total_obs / n_obs_j  # §3.2 point 2: N / N_j
        el0 = eta_Lj if ctx.has_local else None
        s_state = ctx.server_opt.init({"theta": theta, "eta_G": eta_G})

        def local_step(carry, t):
            th, eg, el, s_st, l_st = carry
            eps_G = global_eps(problem, round_key, t)
            eps_L = silo_eps(problem, round_key, t, sid)

            def objective(th_, eg_, el_):
                val = problem.hat_L0(th_, eg_, eps_G)
                return val + problem.hat_Lj(
                    th_, eg_, el_, eps_G, eps_L, data_j, scale
                )

            if ctx.has_local:
                val, (g_th, g_eg, g_el) = jax.value_and_grad(
                    objective, argnums=(0, 1, 2)
                )(th, eg, el)
                upd_l, l_st = ctx.local_opt.update(_neg(g_el), l_st, el)
                el = apply_updates(el, upd_l)
            else:
                val, (g_th, g_eg) = jax.value_and_grad(
                    lambda a, b: objective(a, b, None), argnums=(0, 1)
                )(th, eg)
            params = {"theta": th, "eta_G": eg}
            upd_s, s_st = ctx.server_opt.update(
                _neg({"theta": g_th, "eta_G": g_eg}), s_st, params
            )
            merged = apply_updates(params, upd_s)
            return (merged["theta"], merged["eta_G"], el, s_st, l_st), val

        carry = (theta, eta_G, el0, s_state, opt_Lj)
        (th, eg, el, _, l_st), elbos = jax.lax.scan(
            local_step, carry, jnp.arange(ctx.K)
        )
        if ctx.has_local:
            eta_Lj = _select(m_j > 0.5, el, el0)
            opt_Lj = _select(m_j > 0.5, l_st, opt_Lj)
        return eta_Lj, opt_Lj, state_j, {"theta": th, "eta_G": eg}, elbos

    def server_update(self, ctx, theta, eta_G, opt_server, combined,
                      shipped, w_full, n_active):
        theta_new = combined["theta"]
        if ctx.eta_mode == "param":
            eta_new = combined["eta_G"]
        else:
            # W2 barycenter in moment space, generic over the family's
            # moment bridge (the fused wire plugs in the fused
            # Newton–Schulz step kernel for full-covariance families).
            eta_shipped = jax.vmap(
                lambda v: ctx.wire.unpack(v)["eta_G"]
            )(shipped)
            sqrtm_kw = (
                {"sqrtm": wire_kernels.sqrtm_newton_schulz_fused}
                if ctx.fused else {})
            eta_new = family_barycenter(
                ctx.problem.global_family, eta_shipped, w_full,
                ctx.aggregator, **sqrtm_kw)
        return theta_new, eta_new, opt_server


# ---------------------------------------------------------------------------
# Partitioned VI and federated EP: damped natural-parameter deltas
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _NaturalDeltaStrategy(ServerStrategy):
    """Shared machinery for PVI and federated EP.

    Both maintain per-silo site approximations λ_j in natural-parameter
    space with q_G ∝ p(Z_G) Π_j exp⟨λ_j, T(Z_G)⟩, refine silo j's site
    against the cavity q_G^{\\j} ∝ q_G / exp⟨λ_j, ·⟩ by local VI on the
    tilted objective, and ship the natural-parameter delta
    Δ_j = λ(q_j) − λ(q_G); the server applies the damped sum
    λ(q_G) ← λ(q_G) + ρ Σ_j Δ_j and each silo folds ρ Δ_j into its own
    λ_j. They differ only in where the local VI starts (see subclasses):
    same fixed points, genuinely different finite-K trajectories.

    θ (the model's point parameters) is updated FedAvg-style alongside:
    silos ship θ^(j) − θ and the server applies ρ × the aggregated mean.

    Requires a ``moment_form == "diag"`` global family — the site
    algebra runs through the family's moment bridge.
    """

    damping: float = 0.25
    prec_floor: float = 1e-6

    cadence: ClassVar[str] = "round"
    has_silo_state: ClassVar[bool] = True
    wire_reference: ClassVar[str] = "zero"
    # Where silo j's local VI over η starts: "posterior" (PVI — damped
    # delta from the current broadcast) or "cavity" (EP — refine the
    # site from scratch against the cavity).
    local_init: ClassVar[str] = "posterior"

    def validate(self, server) -> None:
        fam = server.problem.global_family
        if getattr(fam, "moment_form", None) != "diag":
            raise ValueError(
                f"strategy {self.name!r} needs a moment_form='diag' global "
                f"family (DiagGaussian, BatchedDiagGaussian, ...); got "
                f"{type(fam).__name__}"
            )

    def _nat_template(self, server) -> Dict[str, jnp.ndarray]:
        return natural_from_eta(
            server.problem.global_family, server.state["eta_G"]
        )

    def ship_template(self, server) -> PyTree:
        return {"theta": server.state["theta"],
                "eta": self._nat_template(server)}

    def init_silo_state(self, server) -> PyTree:
        """λ_j = 0 for every silo: q_G starts as the unrefined prior fit."""
        nat = self._nat_template(server)
        return {
            "lam": jax.tree_util.tree_map(
                lambda x: jnp.zeros((server.J_pad,) + x.shape, x.dtype), nat
            )
        }

    def local_run(self, ctx, theta, eta_G, eta_Lj, opt_Lj, state_j,
                  data_j, sid, m_j, n_obs_j, round_key):
        problem = ctx.problem
        fam = problem.global_family
        lam = state_j["lam"]
        nat_G = natural_from_eta(fam, eta_G)
        cav_eta = eta_from_natural(fam, _sub(nat_G, lam), self.prec_floor)
        init_eta = eta_G if self.local_init == "posterior" else cav_eta
        el0 = eta_Lj if ctx.has_local else None
        s_state = ctx.server_opt.init({"theta": theta, "eta_G": init_eta})

        def local_step(carry, t):
            th, eg, el, s_st, l_st = carry
            eps_G = global_eps(problem, round_key, t)
            eps_L = silo_eps(problem, round_key, t, sid)

            def objective(th_, eg_, el_):
                # Tilted local ELBO: E_q[log q_cav(Z_G)] + H(q) replaces
                # hat_L0's prior/entropy pair — the cavity is silo j's
                # effective prior. STL-stopped log q, like hat_L0.
                z_G = fam.sample(eg_, eps_G)
                val = fam.log_prob(cav_eta, z_G) - fam.log_prob(
                    _stop(eg_), z_G
                )
                return val + problem.hat_Lj(
                    th_, eg_, el_, eps_G, eps_L, data_j, 1.0
                )

            if ctx.has_local:
                val, (g_th, g_eg, g_el) = jax.value_and_grad(
                    objective, argnums=(0, 1, 2)
                )(th, eg, el)
                upd_l, l_st = ctx.local_opt.update(_neg(g_el), l_st, el)
                el = apply_updates(el, upd_l)
            else:
                val, (g_th, g_eg) = jax.value_and_grad(
                    lambda a, b: objective(a, b, None), argnums=(0, 1)
                )(th, eg)
            params = {"theta": th, "eta_G": eg}
            upd_s, s_st = ctx.server_opt.update(
                _neg({"theta": g_th, "eta_G": g_eg}), s_st, params
            )
            merged = apply_updates(params, upd_s)
            return (merged["theta"], merged["eta_G"], el, s_st, l_st), val

        carry = (theta, init_eta, el0, s_state, opt_Lj)
        (th, eg, el, _, l_st), elbos = jax.lax.scan(
            local_step, carry, jnp.arange(ctx.K)
        )
        if ctx.has_local:
            eta_Lj = _select(m_j > 0.5, el, el0)
            opt_Lj = _select(m_j > 0.5, l_st, opt_Lj)
        # Site delta Δ_j = λ(q_j) − λ(q_G): identical for both inits
        # (λ_j^new − λ_j = [λ(q_j) − cav] − λ_j = λ(q_j) − λ(q_G)).
        delta_nat = _sub(natural_from_eta(fam, eg), nat_G)
        delta_th = _sub(th, theta)
        # The silo folds the CLEAN damped delta into its own site; the
        # server only ever sees the privatized aggregate (the DP-PVI
        # convention: local state is exact, the wire is noised).
        new_lam = jax.tree_util.tree_map(
            lambda val, d: val + self.damping * d, lam, delta_nat
        )
        state_j = {"lam": _select(m_j > 0.5, new_lam, lam)}
        ship = {"theta": delta_th, "eta": delta_nat}
        return eta_Lj, opt_Lj, state_j, ship, elbos

    def server_update(self, ctx, theta, eta_G, opt_server, combined,
                      shipped, w_full, n_active):
        fam = ctx.problem.global_family
        rho = self.damping
        # θ: damped FedAvg of the per-silo moves (mean over active).
        theta_new = jax.tree_util.tree_map(
            lambda p, d: p + rho * d, theta, combined["theta"]
        )
        # η_G: the posterior is the product of sites, so the update is
        # the damped SUM of deltas — n_active × the aggregated mean.
        nat_G = natural_from_eta(fam, eta_G)
        nat_new = jax.tree_util.tree_map(
            lambda n, d: n + rho * n_active * d, nat_G, combined["eta"]
        )
        eta_new = eta_from_natural(fam, nat_new, self.prec_floor)
        return theta_new, eta_new, opt_server


@register_strategy("pvi")
@dataclasses.dataclass(frozen=True)
class PVIStrategy(_NaturalDeltaStrategy):
    """Partitioned Variational Inference (Ashman et al., 2202.12275).

    Local VI starts at the current broadcast posterior, so each silo
    computes a small refinement against its cavity and the exchange is a
    damped natural-parameter *delta step*. ``damping=0`` is an exact
    fixed point (nothing moves) — the sanity anchor in the tests.
    """

    local_init: ClassVar[str] = "posterior"


@register_strategy("fed_ep")
@dataclasses.dataclass(frozen=True)
class FedEPStrategy(_NaturalDeltaStrategy):
    """Federated EP-style site refinement (Guo et al., 2302.04228).

    Identical site algebra to PVI, but each silo re-derives its site
    from scratch: local VI starts at the CAVITY (the posterior with the
    silo's own site removed), the classic EP refinement view. Same
    fixed points as PVI; different finite-K trajectories — at the fixed
    point the tilted optimum equals the posterior either way.
    """

    local_init: ClassVar[str] = "cavity"
