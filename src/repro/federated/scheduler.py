"""Round scheduling and scenario construction for the federated runtime.

The scheduler is the scenario knob of the runtime: full participation
reproduces the paper's Algorithms 1–2 exactly; ``participation < 1``
samples a random subset per round (cross-device FL); ``dropout > 0``
models stragglers that accept the round but fail to report back. Masks
are deterministic functions of (seed, round index) so a schedule can be
replayed — and so ``Server.run``'s round program draws them in its own
graph from a traced round index (:func:`draw_masks`).

:class:`Scenario` bundles every orthogonal knob — sync cadence,
participation, stragglers, wire compression, differential privacy —
into one named configuration, and :func:`scenario_matrix` crosses the
axes into a grid so one CLI/benchmark invocation sweeps the whole
scenario space (``python -m repro.federated.run --sweep``).
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.federated.aggregation import (
    Int8Compressor,
    MeanAggregator,
    NoCompression,
    TrimmedMeanAggregator,
)
from repro.federated.privacy import PrivacyPolicy
from repro.federated.strategy import get_strategy, strategy_names

# Human-readable labels for registry strategies (fallback: upper-cased name).
_ALGO_LABELS = {
    "sfvi": "SFVI",
    "sfvi_avg": "SFVI-Avg",
    "pvi": "PVI",
    "fed_ep": "FedEP",
}


def algorithm_label(algorithm: str) -> str:
    """Human-readable label for a registry strategy name."""
    return _ALGO_LABELS.get(algorithm, algorithm.upper())


@dataclasses.dataclass(frozen=True)
class RoundScheduler:
    """Samples a per-round participation mask over J silos.

    Attributes:
      num_silos: J, the federation width.
      participation: fraction of silos the server *invites* each round
        (at least one silo is always invited).
      dropout: probability that an invited silo straggles and drops out
        of the round after receiving the broadcast (its upload never
        arrives; the server rescales by the realized active count).
      seed: PRNG seed for the schedule.
    """

    num_silos: int
    participation: float = 1.0
    dropout: float = 0.0
    seed: int = 0

    def round_masks(self, indices: Sequence[int]):
        """(invited, reported): two (E, J) float32 masks for E indices.

        One jitted, vmapped draw per call (:func:`draw_masks`), so a
        round of K synchronised exchanges costs one dispatch, not K.
        Row ``e`` is the draw of schedule index ``indices[e]``:
        ``invited`` is who the server broadcasts to, ``reported`` who
        uploads (stragglers are invited but absent).
        """
        return draw_masks(self.seed_u32, np.asarray(indices, np.int32),
                          self._rule)

    @property
    def seed_u32(self) -> np.uint32:
        """The schedule seed as :func:`draw_masks` takes it.

        PRNGKey reads its seed modulo 2**32; the uint32 keeps every
        seed in range of the traced argument.
        """
        return np.uint32(self.seed % 2 ** 32)

    @property
    def _rule(self) -> tuple:
        return (self.num_silos, float(self.participation),
                float(self.dropout))

    @property
    def graph_rule(self) -> Optional[tuple]:
        """``(J, participation, dropout)``, the static half of the draw,
        for a round program that runs :func:`draw_masks` in its own
        graph from (seed, index) inputs; None when a subclass overrides
        :meth:`round_masks`, whose rule is host code the graph cannot
        reproduce."""
        if type(self).round_masks is not RoundScheduler.round_masks:
            return None
        return self._rule

    def invited(self, round_idx: int) -> jnp.ndarray:
        """(J,) float32 mask of silos the server *broadcasts to* this round.

        Stragglers (``dropout``) are invited — they receive (θ, η_G) and
        cost download bytes — but may still be absent from :meth:`mask`.
        """
        return self.round_masks([round_idx])[0][0]

    def mask(self, round_idx: int) -> jnp.ndarray:
        """(J,) float32 mask: 1.0 = silo reports this round, 0.0 = absent."""
        return self.round_masks([round_idx])[1][0]

    def masks(self, num_rounds: int) -> jnp.ndarray:
        """(num_rounds, J) stacked schedule (for logging / tests)."""
        return self.round_masks(range(num_rounds))[1]


@functools.partial(jax.jit, static_argnums=2)
def draw_masks(seed, indices, rule: tuple):
    """The schedule rule, vmapped over schedule indices (see round_masks).

    ``seed`` (uint32) and ``indices`` (int32) may be traced: the round
    program of ``Server.run`` calls this inside its own graph with the
    absolute round index; ``rule`` is the static ``(J, participation,
    dropout)``.
    """
    J, participation, dropout = rule

    def one(i):
        # repro-lint: allow[R1] — participation stream root, folded with the absolute schedule index on the same line
        key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
        k_inv, k_drop = jax.random.split(key)
        invited = jnp.ones((J,), jnp.float32)
        if participation < 1.0:
            # Half-up, not Python's round(): banker's rounding resolves
            # the .5 tie to the nearest EVEN count, so participation=0.5
            # with J=5 would invite round(2.5) = 2 silos instead of the
            # documented "fraction of silos" (3).
            n_inv = max(1, int(participation * J + 0.5))
            chosen = jax.random.choice(k_inv, J, shape=(n_inv,),
                                       replace=False)
            invited = jnp.zeros((J,), jnp.float32).at[chosen].set(1.0)
        reported = invited
        if dropout > 0.0:
            survive = jax.random.bernoulli(
                k_drop, 1.0 - dropout, (J,)).astype(jnp.float32)
            dropped = invited * survive
            # Never lose the whole round: keep the lowest-index invitee.
            first = jax.nn.one_hot(jnp.argmax(invited), J,
                                   dtype=jnp.float32)
            reported = jnp.where(jnp.any(dropped), dropped, first)
        return invited, reported

    return jax.vmap(one)(indices)


# ---------------------------------------------------------------------------
# Asynchronous execution block (consumed by repro.federated.async_engine)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AsyncConfig:
    """Declarative knobs of the buffered-asynchronous execution mode.

    All fields are JSON-native so the block round-trips inside
    :class:`Scenario` / ``ExperimentSpec``. The semantics (FedBuff-style
    buffer, staleness-decayed weights, deterministic latency models) are
    implemented by :mod:`repro.federated.async_engine`.

    Attributes:
      buffer_size: B — the server applies one aggregate ("flush") as
        soon as B silo contributions have arrived (1 ≤ B ≤ J;
        ``B == J`` with constant latency reproduces the synchronous
        SFVI-Avg trajectory bit-exactly).
      staleness_decay: exponent d of the weight ``(1 + s)^-d`` applied
        to a contribution that is ``s`` server versions behind
        (0 disables staleness weighting).
      latency: per-task silo latency model — ``"constant"`` (every task
        takes ``latency_scale``), ``"lognormal"`` (median
        ``latency_scale``, log-sd ``latency_sigma``), or
        ``"straggler"`` (constant, but a ``straggler_frac`` fraction of
        tasks run ``straggler_slowdown``× slower — the heavy-tail
        regime). Every draw is a pure function of
        (seed, silo, task index), so schedules replay bit-exactly.
      latency_scale: median simulated seconds per silo task.
      latency_sigma: log-normal spread (``"lognormal"`` only).
      straggler_frac: probability a task straggles (``"straggler"``).
      straggler_slowdown: multiplier for straggling tasks.
    """

    buffer_size: int = 2
    staleness_decay: float = 0.5
    latency: str = "lognormal"
    latency_scale: float = 1.0
    latency_sigma: float = 0.5
    straggler_frac: float = 0.1
    straggler_slowdown: float = 10.0

    @property
    def name(self) -> str:
        """Compact label fragment for scenario tables."""
        bits = [f"B={self.buffer_size}", self.latency]
        if self.staleness_decay:
            bits.append(f"d={self.staleness_decay:g}")
        return f"async({','.join(bits)})"


# ---------------------------------------------------------------------------
# Scenario matrix: participation × stragglers × compression × DP [× async]
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Scenario:
    """One named point in the runtime's scenario space.

    A Scenario is declarative: it records the knob settings and builds
    the concrete runtime pieces on demand (:meth:`scheduler`,
    :meth:`compressor`, :meth:`privacy`), so grids stay cheap to
    enumerate and trivially serializable for logs.

    Attributes:
      algorithm: any registered server-strategy name
        (:func:`repro.federated.strategy.strategy_names`): ``"sfvi"``
        (sync every local step), ``"sfvi_avg"``, ``"pvi"``,
        ``"fed_ep"``, ...
      participation: fraction of silos invited per round.
      dropout: per-round straggler probability for invited silos.
      compression: ``"none"`` or ``"int8"`` wire codec.
      dp_noise: Gaussian noise multiplier z; 0 disables DP.
      dp_clip: L2 clip norm C for the upload (used when ``dp_noise > 0``
        or ``dp_clip_only``).
      dp_delta: target δ for (ε, δ) reports.
      dp_clip_only: apply clipping without noise (isolates the utility
        cost of clipping; ε stays ∞).
      aggregator: ``"mean"`` or ``"trimmed"`` server combine rule.
      trim_frac: trim fraction for the ``"trimmed"`` aggregator.
      async_cfg: buffered-asynchronous execution block
        (:class:`AsyncConfig`), or None for synchronous rounds. Async
        scenarios require a round-cadence algorithm (SFVI-Avg, PVI,
        FedEP) with full participation and no dropout — the latency
        model owns the arrival dynamics (:meth:`validate`).
    """

    algorithm: str = "sfvi_avg"
    participation: float = 1.0
    dropout: float = 0.0
    compression: str = "none"
    dp_noise: float = 0.0
    dp_clip: float = 1.0
    dp_delta: float = 1e-5
    dp_clip_only: bool = False
    aggregator: str = "mean"
    trim_frac: float = 0.1
    async_cfg: Optional[AsyncConfig] = None

    @property
    def name(self) -> str:
        """Compact human-readable label for tables and logs."""
        bits = [_ALGO_LABELS.get(self.algorithm, self.algorithm.upper())]
        if self.async_cfg is not None:
            bits.append(self.async_cfg.name)
        if self.participation < 1.0:
            bits.append(f"part={self.participation:g}")
        if self.dropout > 0.0:
            bits.append(f"drop={self.dropout:g}")
        if self.compression != "none":
            bits.append(self.compression)
        if self.dp_noise > 0.0:
            bits.append(f"dp(z={self.dp_noise:g},C={self.dp_clip:g})")
        elif self.dp_clip_only:
            bits.append(f"clip(C={self.dp_clip:g})")
        if self.aggregator != "mean":
            bits.append(f"{self.aggregator}({self.trim_frac:g})")
        return " ".join(bits)

    def validate(self, num_silos: Optional[int] = None) -> Scenario:
        """Reject physically-meaningless knob combinations (returns self).

        Async mode composes with compression, aggregation and DP, but
        not with the synchronous scheduler's participation/straggler
        knobs (the latency model subsumes them) and only under a
        round-cadence strategy (step-cadence strategies synchronize
        every local step — there is no round-granular contribution to
        buffer).
        """
        try:
            strategy_cls = get_strategy(self.algorithm)
        except KeyError:
            raise ValueError(
                f"unknown algorithm {self.algorithm!r}; registered "
                f"strategies: {list(strategy_names())}") from None
        if self.async_cfg is None:
            return self
        if strategy_cls.cadence != "round":
            raise ValueError(
                f"async execution requires a round-cadence strategy "
                f"(sfvi_avg, pvi, fed_ep, ...); {self.algorithm!r} "
                "synchronizes every local step and has no round-granular "
                "contribution to buffer")
        if self.participation < 1.0 or self.dropout > 0.0:
            raise ValueError(
                "async scenarios model arrival dynamics with the latency "
                "model; set participation=1.0 and dropout=0.0 (got "
                f"participation={self.participation}, dropout={self.dropout})")
        if self.async_cfg.buffer_size < 1:
            raise ValueError("async buffer_size must be >= 1")
        if num_silos is not None and self.async_cfg.buffer_size > num_silos:
            raise ValueError(
                f"async buffer_size={self.async_cfg.buffer_size} exceeds "
                f"the federation width J={num_silos}")
        if self.async_cfg.latency not in ("constant", "lognormal", "straggler"):
            raise ValueError(
                f"unknown latency model {self.async_cfg.latency!r} "
                "(constant/lognormal/straggler)")
        return self

    @classmethod
    def from_dict(cls, d: dict) -> Scenario:
        """Inverse of ``dataclasses.asdict`` (rebuilds the async block).

        Validates on deserialization: a hand-edited spec JSON combining
        contradictory knobs (e.g. ``async_cfg`` with a step-cadence
        algorithm) fails HERE, not rounds into a silently-wrong run.
        The federation width is not known yet, so the J-dependent
        checks re-run in ``api.build``.
        """
        d = dict(d)
        if d.get("async_cfg") is not None:
            d["async_cfg"] = AsyncConfig(**d["async_cfg"])
        return cls(**d).validate()

    def scheduler(self, num_silos: int, seed: int = 0) -> RoundScheduler:
        """The participation/straggler schedule for this scenario."""
        return RoundScheduler(
            num_silos, participation=self.participation,
            dropout=self.dropout, seed=seed,
        )

    def compressor(self):
        """The wire codec for this scenario."""
        if self.compression == "int8":
            return Int8Compressor()
        if self.compression == "none":
            return NoCompression()
        raise ValueError(f"unknown compression {self.compression!r}")

    def make_aggregator(self):
        """The server combine rule for this scenario."""
        if self.aggregator == "trimmed":
            return TrimmedMeanAggregator(self.trim_frac)
        if self.aggregator == "mean":
            return MeanAggregator()
        raise ValueError(f"unknown aggregator {self.aggregator!r}")

    def privacy(self) -> Optional[PrivacyPolicy]:
        """The DP policy, or None when this scenario is non-private."""
        if self.dp_noise > 0.0 or self.dp_clip_only:
            return PrivacyPolicy(
                clip_norm=self.dp_clip,
                noise_multiplier=self.dp_noise,
                delta=self.dp_delta,
            )
        return None


def scenario_matrix(
    *,
    algorithms: Sequence[str] = ("sfvi", "sfvi_avg"),
    participation: Sequence[float] = (1.0, 0.5),
    dropout: Sequence[float] = (0.0, 0.2),
    compression: Sequence[str] = ("none", "int8"),
    dp_noise: Sequence[float] = (0.0, 1.0),
    dp_clip: float = 1.0,
    dp_delta: float = 1e-5,
    async_cfgs: Sequence[Optional[AsyncConfig]] = (None,),
) -> list:
    """Cross participation × stragglers × compression × DP × async.

    The full cartesian product, minus physically-meaningless rows:
    dropout without partial participation is kept (stragglers exist
    under full invitation too), but async rows are emitted only for
    round-cadence algorithms under full participation (see
    :meth:`Scenario.validate`). ``algorithms`` accepts any registered
    strategy name — e.g. ``("sfvi", "sfvi_avg", "pvi", "fed_ep")``
    sweeps the whole zoo. One invocation of
    ``python -m repro.federated.run --sweep`` walks the returned list.
    """
    grid = []
    for algo, part, drop, comp, z, acfg in itertools.product(
        algorithms, participation, dropout, compression, dp_noise, async_cfgs
    ):
        if acfg is not None and (
            get_strategy(algo).cadence != "round" or part < 1.0 or drop > 0.0
        ):
            continue
        grid.append(Scenario(
            algorithm=algo, participation=part, dropout=drop,
            compression=comp, dp_noise=z, dp_clip=dp_clip, dp_delta=dp_delta,
            async_cfg=acfg,
        ))
    return grid
