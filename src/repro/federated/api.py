"""One declarative experiment API: spec → build → run → resume.

The paper's experiments are a grid of (model × algorithm × participation
× compression × DP) runs. Instead of wiring that grid by hand at every
call site, this module gives the repo ONE serializable experiment
object:

  * :class:`ExperimentSpec` — a frozen dataclass tree (model reference +
    kwargs, silos, rounds × local steps, optimizers, a
    :class:`~repro.federated.scheduler.Scenario` carrying participation /
    stragglers / compression / aggregation / differential privacy, eval
    cadence, seed) with a lossless ``to_dict()`` / ``from_dict()`` JSON
    round trip;
  * :func:`build` — resolves the model through the registry
    (:mod:`repro.models.paper.registry`) and assembles the compiled
    :class:`~repro.federated.runtime.Server`, scheduler, privacy policy
    and accountant into an :class:`Experiment`;
  * :class:`Experiment` — owns the run loop (`run`), evaluation cadence,
    and checkpointing: ``save(dir)`` persists the FULL round state
    (θ, η_G, stacked η_{L_j}, both optimizer states, the RDP ledger, the
    communication meter, and the absolute round index) through
    :class:`~repro.checkpoint.CheckpointManager`; ``Experiment.resume(dir)``
    rebuilds from ``spec.json`` and restores that state. Because every
    random stream in the runtime (round keys, participation masks, DP
    noise) is a function of (seed, absolute round index), a resumed run
    replays the uninterrupted run's remaining rounds **bit-exactly** —
    asserted in ``tests/test_api.py``.

This is the single construction path the CLI
(``python -m repro.federated.run``), the examples, and the benchmark
suite all build on; the legacy eager ``SFVIServer``/``SFVIAvgServer``
are deprecated adapters over the same compiled runtime. See
``docs/api.md``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import warnings
from typing import Any, Callable, Dict, List, Optional, Union

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.core.family import FamilySpec
from repro.federated.population import PopulationEngine, PopulationSpec, PopulationState
from repro.federated.runtime import SERVER_STATE
from repro.federated.scheduler import RoundScheduler, Scenario
from repro.federated.strategy import StrategySpec
from repro.launch.mesh import MeshSpec, build_mesh

PyTree = Any

_SPEC_FILE = "spec.json"

# The deprecated out-of-band wire kwarg warns ONCE per process — sweeps
# over many specs shouldn't drown their output in repeats.
_WIRE_KWARG_WARNED = False


def _warn_wire_kwarg(where: str) -> None:
    global _WIRE_KWARG_WARNED
    if not _WIRE_KWARG_WARNED:
        warnings.warn(
            f"the wire= kwarg on {where} is deprecated; set it on the spec "
            "instead: ExperimentSpec(runtime=RuntimeSpec(wire=...)). The "
            "kwarg still overrides the spec for now.",
            DeprecationWarning, stacklevel=3)
        _WIRE_KWARG_WARNED = True


# ---------------------------------------------------------------------------
# Spec tree
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class OptimizerSpec:
    """Declarative optimizer: resolved by name at build time.

    Attributes:
      name: ``"adam"``, ``"adamw"`` or ``"sgd"``.
      learning_rate: step size.
      kwargs: extra keyword arguments for the optimizer factory
        (JSON-native values only: betas, momentum, weight decay, ...).
    """

    name: str = "adam"
    learning_rate: float = 1e-2
    kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def build(self):
        """Instantiate the :class:`~repro.optim.base.GradientTransformation`."""
        if self.name == "adam":
            from repro.optim.adam import adam
            return adam(self.learning_rate, **self.kwargs)
        if self.name == "adamw":
            from repro.optim.adam import adamw
            return adamw(self.learning_rate, **self.kwargs)
        if self.name == "sgd":
            from repro.optim.sgd import sgd
            return sgd(self.learning_rate, **self.kwargs)
        raise ValueError(f"unknown optimizer {self.name!r} (adam/adamw/sgd)")

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> OptimizerSpec:
        return cls(name=d.get("name", "adam"),
                   learning_rate=d.get("learning_rate", 1e-2),
                   kwargs=dict(d.get("kwargs", {})))


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """Reference to a registered model plus its build kwargs.

    ``name`` resolves through :mod:`repro.models.paper.registry`;
    ``kwargs`` are forwarded to the registered builder and must be
    JSON-native (the spec round-trips through ``json.dumps``).

    ``global_family`` / ``local_family`` optionally override the staged
    problem's variational families with a
    :class:`~repro.core.family.FamilySpec` — ``null`` keeps the model's
    default (the paper's choice). Structural dimensions are filled from
    the model at build time, so ``FamilySpec("cholesky")`` upgrades any
    model's η_G to a full unitriangular factor and
    ``FamilySpec("lowrank", {"rank": 2})`` to diag + rank-2.
    """

    name: str
    kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    global_family: Optional[FamilySpec] = None
    local_family: Optional[FamilySpec] = None

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> ModelSpec:
        return cls(
            name=d["name"],
            kwargs=dict(d.get("kwargs", {})),
            global_family=(FamilySpec.from_dict(d["global_family"])
                           if d.get("global_family") is not None else None),
            local_family=(FamilySpec.from_dict(d["local_family"])
                          if d.get("local_family") is not None else None),
        )


@dataclasses.dataclass(frozen=True)
class RuntimeSpec:
    """Execution topology and wire layout — spec-carried, JSON-native.

    Historically the wire layout rode an out-of-band ``wire=`` kwarg on
    :func:`build` and the mesh was whatever ``make_silo_mesh`` decided;
    both now live on the spec so a run's topology serializes, resumes
    and sweeps like every other knob.

    Attributes:
      wire: silo→server wire layout — ``"flat"`` (packed (J, P)
        matrix, the default), ``"fused"`` (same layout, Pallas-kernel
        pipeline) or ``"legacy"`` (per-leaf reference).
      mesh: the federated mesh topology
        (:class:`~repro.launch.mesh.MeshSpec`): ``silo`` devices × a
        ``model`` axis sharding each row's P wire parameters, plus the
        ``multiprocess`` flag for ``jax.distributed`` runs.
      sanitize: default for :meth:`Experiment.run`'s runtime sanitizer
        (transfer guard + NaN checks + recompile watchdog); an explicit
        ``run(sanitize=...)`` still overrides.
    """

    wire: str = "flat"
    mesh: MeshSpec = MeshSpec()
    sanitize: bool = False

    def __post_init__(self):
        if self.wire not in ("flat", "fused", "legacy"):
            raise ValueError(
                f"unknown wire layout {self.wire!r} (flat/fused/legacy)")

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> RuntimeSpec:
        return cls(wire=d.get("wire", "flat"),
                   mesh=MeshSpec.from_dict(d.get("mesh") or {}),
                   sanitize=d.get("sanitize", False))


@dataclasses.dataclass(frozen=True)
class ExperimentSpec:
    """The complete, serializable description of one federated run.

    Attributes:
      model: registry reference + kwargs (:class:`ModelSpec`).
      scenario: the runtime scenario — algorithm (any registered
        :class:`~repro.federated.strategy.ServerStrategy` name:
        ``sfvi``/``sfvi_avg``/``pvi``/``fed_ep``), participation,
        stragglers, wire compression, aggregation rule and the
        differential-privacy policy (dp_noise/dp_clip/dp_delta) — as
        one :class:`~repro.federated.scheduler.Scenario`.
      strategy: optional
        :class:`~repro.federated.strategy.StrategySpec` carrying the
        strategy's hyperparameters (e.g. PVI's ``damping``). ``None``
        builds the scenario's algorithm with registry defaults; when
        set, its name must match ``scenario.algorithm``.
      num_silos: J, the federation width.
      rounds: total rounds the experiment runs (``Experiment.run()`` with
        no argument runs whatever remains of this budget).
      local_steps: K optimizer steps per round (step-cadence strategies
        sync after each, round-cadence ones once per round).
      server_opt: optimizer for (θ, η_G).
      local_opt: optimizer for each η_{L_j}; None mirrors ``server_opt``
        when the model has local latents.
      eta_mode: SFVI-Avg's η_G merge — ``"barycenter"`` (paper §3.2,
        DiagGaussian) or ``"param"`` (parameter-space FedAvg).
      eval_every: evaluate the registry's eval_fn every this many rounds
        (0 disables the cadence; ``Experiment.evaluate()`` is always
        available on demand).
      seed: base seed for initialization, round keys and the
        participation schedule (and data staging, unless ``data_seed``
        overrides it).
      data_seed: seed the registry stages data with; None mirrors
        ``seed``. Separate so one dataset can be crossed with many run
        seeds while the spec still rebuilds the exact data on resume.
      runtime: execution topology — wire layout, federated mesh
        (:class:`~repro.launch.mesh.MeshSpec`) and the sanitizer
        default, as one :class:`RuntimeSpec`. A resume may change the
        topology (device or process count): silo re-padding and
        resharding keep the REAL silos' trajectory bit-exact.
      population: optional dynamic-population churn
        (:class:`~repro.federated.population.PopulationSpec`). When
        set, ``num_silos`` is the ROSTER maximum (the registry stages
        every shard up front); only ``population.initial`` silos are
        live at round 0 and the rest join, depart and return through
        the deterministic event process of
        :mod:`repro.federated.population`. ``None`` (the default) is
        the paper's fixed-J federation, byte-for-byte unchanged.
    """

    model: ModelSpec
    scenario: Scenario = Scenario()
    strategy: Optional[StrategySpec] = None
    num_silos: int = 4
    rounds: int = 10
    local_steps: int = 1
    server_opt: OptimizerSpec = OptimizerSpec()
    local_opt: Optional[OptimizerSpec] = None
    eta_mode: str = "barycenter"
    eval_every: int = 0
    seed: int = 0
    data_seed: Optional[int] = None
    runtime: RuntimeSpec = RuntimeSpec()
    population: Optional[PopulationSpec] = None

    @property
    def algorithm(self) -> str:
        """The sync cadence, carried by the scenario."""
        return self.scenario.algorithm

    @property
    def name(self) -> str:
        """Human-readable label: model + the scenario's knob summary."""
        return f"{self.model.name} {self.scenario.name}"

    # -- serialization ------------------------------------------------------

    def to_dict(self) -> Dict[str, Any]:
        """Plain-dict form, JSON-ready (nested dataclasses flattened)."""
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> ExperimentSpec:
        """Inverse of :meth:`to_dict`: ``from_dict(to_dict(s)) == s``."""
        return cls(
            model=ModelSpec.from_dict(d["model"]),
            scenario=Scenario.from_dict(d.get("scenario", {})),
            strategy=(StrategySpec.from_dict(d["strategy"])
                      if d.get("strategy") is not None else None),
            num_silos=d.get("num_silos", 4),
            rounds=d.get("rounds", 10),
            local_steps=d.get("local_steps", 1),
            server_opt=OptimizerSpec.from_dict(d.get("server_opt", {})),
            local_opt=(OptimizerSpec.from_dict(d["local_opt"])
                       if d.get("local_opt") is not None else None),
            eta_mode=d.get("eta_mode", "barycenter"),
            eval_every=d.get("eval_every", 0),
            seed=d.get("seed", 0),
            data_seed=d.get("data_seed"),
            runtime=RuntimeSpec.from_dict(d.get("runtime") or {}),
            population=(PopulationSpec.from_dict(d["population"])
                        if d.get("population") is not None else None),
        )

    def to_json(self, indent: int = 2) -> str:
        """JSON text of :meth:`to_dict` (what ``--dump-spec`` prints)."""
        return json.dumps(self.to_dict(), indent=indent)

    @classmethod
    def from_json(cls, text: str) -> ExperimentSpec:
        return cls.from_dict(json.loads(text))

    def save(self, path: str) -> None:
        """Write the spec as JSON (atomically) to ``path``."""
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write(self.to_json() + "\n")
        os.replace(tmp, path)

    @classmethod
    def load(cls, path: str) -> ExperimentSpec:
        with open(path) as f:
            return cls.from_json(f.read())


# ---------------------------------------------------------------------------
# build: spec -> Experiment
# ---------------------------------------------------------------------------


def build(spec: ExperimentSpec, bundle=None, *,
          wire: Optional[str] = None) -> Experiment:
    """Assemble the compiled runtime for ``spec``.

    Resolves the model through the registry (unless a pre-staged
    ``bundle`` is supplied — benchmarks reuse one dataset across many
    scenario specs that way), applies the spec's family overrides
    (``ModelSpec.global_family`` / ``local_family``), instantiates
    optimizers, aggregation, compression, the privacy policy AND the
    execution topology — wire layout and federated mesh, from
    ``spec.runtime`` — and returns a ready-to-run :class:`Experiment`.

    ``wire`` is a DEPRECATED override of ``spec.runtime.wire`` (warns
    once); topology belongs on the spec so it serializes and resumes
    with everything else.
    """
    if wire is not None:
        _warn_wire_kwarg("build()")
    return _build(spec, bundle, wire)


def _bundle_num_obs(bundle) -> List[int]:
    """Per-silo N_j for the FULL staged roster (inferring when absent)."""
    if bundle.num_obs is not None:
        return [int(n) for n in bundle.num_obs]
    return [int(jax.tree_util.tree_leaves(d)[0].shape[0])
            for d in bundle.datas]


def _build(spec: ExperimentSpec, bundle=None,
           wire: Optional[str] = None,
           joined: Optional[int] = None) -> Experiment:
    """The warning-free core of :func:`build` (resume calls this).

    ``joined`` is the resume path's population head-count: a churn
    run's Server is built with exactly the silos that had joined at
    the checkpoint (their shards restore on top), and the engine's
    state is restored right after. A fresh population build starts at
    ``spec.population.initial``.
    """
    from repro.federated import graph_cache
    from repro.federated.runtime import Server
    from repro.models.paper.registry import apply_family_spec, get_model

    wire = wire if wire is not None else spec.runtime.wire
    spec.scenario.validate(spec.num_silos)
    strat_spec = (spec.strategy if spec.strategy is not None
                  else StrategySpec(spec.scenario.algorithm))
    if strat_spec.name != spec.scenario.algorithm:
        raise ValueError(
            f"spec.strategy names {strat_spec.name!r} but "
            f"scenario.algorithm is {spec.scenario.algorithm!r}; they must "
            f"agree (the scenario label drives scheduling/validation, the "
            f"StrategySpec only adds hyperparameters)")
    strategy = strat_spec.build()
    mesh = build_mesh(spec.runtime.mesh, num_silos=spec.num_silos)
    pop = spec.population
    # The live federation at build time: the full roster, or — under a
    # population — the silos joined so far (churn grows the rest).
    if pop is None:
        j_live = spec.num_silos
    else:
        j_live = int(joined) if joined is not None else min(
            pop.initial, spec.num_silos)
    n_dev = int(mesh.shape["silo"])
    j_pad = ((j_live + n_dev - 1) // n_dev) * n_dev
    token = None
    if bundle is None:
        entry = get_model(spec.model.name)
        data_seed = spec.data_seed if spec.data_seed is not None else spec.seed
        bundle = entry.build(data_seed, spec.num_silos, **spec.model.kwargs)
        # Registry-staged builds are pure functions of the spec, so
        # structurally-equal Servers may share compiled round graphs —
        # resume then re-traces nothing. A caller-supplied bundle is
        # opaque to the token and opts out. J_pad rides the token: the
        # compiled shapes are functions of the PADDED silo axis, which
        # a population grows in mesh-sized chunks.
        token = graph_cache.build_token(
            spec.to_json(indent=0), wire, spec.num_silos,
            mesh_shape=tuple(sorted(mesh.shape.items())), j_pad=j_pad)
    if len(bundle.datas) != spec.num_silos:
        raise ValueError(
            f"bundle stages {len(bundle.datas)} silos, spec.num_silos is "
            f"{spec.num_silos}")
    bundle = apply_family_spec(
        bundle, spec.model.global_family, spec.model.local_family)

    problem = bundle.problem
    has_local = problem.model.has_local
    local_spec = spec.local_opt if spec.local_opt is not None else spec.server_opt
    num_obs_full = _bundle_num_obs(bundle)
    server = Server(
        problem,
        bundle.datas[:j_live],
        bundle.theta0,
        # repro-lint: allow[R1] — η_G init root: a pure function of spec.seed, re-derived bit-exactly by resume
        problem.global_family.init(jax.random.PRNGKey(spec.seed)),
        num_obs=num_obs_full[:j_live],
        server_opt=spec.server_opt.build(),
        local_opt=local_spec.build() if has_local else None,
        aggregator=spec.scenario.make_aggregator(),
        compressor=spec.scenario.compressor(),
        eta_mode=spec.eta_mode,
        wire=wire,
        mesh=mesh,
        privacy=spec.scenario.privacy(),
        seed=spec.seed,
        strategy=strategy,
        graph_cache_token=token,
        # The estimators scale by the ROSTER width and total N: absent
        # silos are non-participants of the full federation, so the
        # optimization target is fixed while the population churns.
        federation_size=spec.num_silos,
        federation_obs=float(sum(num_obs_full)),
    )
    population = None
    if pop is not None:
        if server.n_processes > 1:
            raise ValueError(
                "population churn is single-process for now (dynamic "
                "growth re-shards silo rows, which multi-process "
                "federations pin to their owning host)")
        population = PopulationEngine(pop, bundle, spec.num_silos)
    scheduler = spec.scenario.scheduler(spec.num_silos, seed=spec.seed)
    return Experiment(spec, bundle, server, scheduler,
                      population=population)


# ---------------------------------------------------------------------------
# Experiment: run / evaluate / save / resume
# ---------------------------------------------------------------------------


class Experiment:
    """A built federated run: owns the Server, scheduler and round index.

    Construct through :func:`build` (or :meth:`resume`); drive with
    :meth:`run`. ``history`` accumulates across calls, ``round`` is the
    absolute number of rounds completed so far.
    """

    def __init__(self, spec: ExperimentSpec, bundle, server, scheduler: RoundScheduler,
                 population: Optional[PopulationEngine] = None):
        self.spec = spec
        self.bundle = bundle
        self.server = server
        self.scheduler = scheduler
        # Churn driver (spec.population): joins/departures/returns fire
        # between rounds; None for a fixed federation.
        self.population = population
        self.round = 0
        self.history: Dict[str, list] = {}
        # Buffered-async event-loop state (None until the first async
        # flush, or restored by resume); rounds count flushes in async
        # mode, so `self.round` needs no second counter.
        self.async_state = None

    # -- delegation conveniences -------------------------------------------

    @property
    def theta(self) -> PyTree:
        return self.server.theta

    @property
    def eta_G(self) -> PyTree:
        return self.server.eta_G

    @property
    def eta_L(self) -> PyTree:
        return self.server.eta_L

    @property
    def comm(self):
        return self.server.comm

    @property
    def accountant(self):
        return self.server.accountant

    @property
    def remaining_rounds(self) -> int:
        return max(self.spec.rounds - self.round, 0)

    def warm_start(self, theta: Optional[PyTree] = None,
                   eta_G: Optional[PyTree] = None) -> Experiment:
        """Override the initial (θ, η_G) — e.g. from a previous fit
        (the paper's Figure S2 warm-starting protocol). Optimizer
        moments are left at their fresh init."""
        if theta is not None:
            self.server.state["theta"] = theta
        if eta_G is not None:
            self.server.state["eta_G"] = eta_G
        self.server.place()
        return self

    # -- running ------------------------------------------------------------

    def run(self, rounds: Optional[int] = None,
            callback: Optional[Callable[[int, dict], None]] = None,
            sanitize: Union[None, bool, Dict[str, Any]] = None
            ) -> Dict[str, list]:
        """Advance ``rounds`` rounds (default: the spec's remaining budget).

        Returns the accumulated history. ``callback(r, metrics)`` fires
        per round with the ABSOLUTE round index; when the spec sets
        ``eval_every``, the registry's eval metrics are merged into the
        round's metrics (and recorded under ``history["eval"]``) at that
        cadence.

        ``sanitize=True`` wraps the loop in :func:`repro.debug.sanitize`
        — transfer guard, NaN debugging and the recompile watchdog (a
        dict passes keyword options through, e.g.
        ``sanitize={"debug_nans": False}``). The default (``None``)
        defers to ``spec.runtime.sanitize``. See docs/dev.md.

        When the scenario carries an async block, "rounds" are buffered
        flushes driven by :func:`repro.federated.async_engine.run_buffered`
        over the same compiled graph; the engine's
        :class:`~repro.federated.async_engine.BufferState` lives on
        ``self.async_state`` and is checkpointed with everything else.
        """
        n = self.remaining_rounds if rounds is None else rounds
        if n <= 0:
            return self.history
        spec = self.spec
        if sanitize is None:
            sanitize = spec.runtime.sanitize
        start = self.round

        def cb(r: int, metrics: dict) -> None:
            # Keep the absolute round index current DURING the run, so a
            # callback may checkpoint mid-run (``save`` stamps the state
            # with ``self.round``) and the resume replays from the right
            # absolute round.
            self.round = r + 1
            if (spec.eval_every and self.bundle.eval_fn is not None
                    and (r + 1) % spec.eval_every == 0):
                scores = self.bundle.eval_fn(self.server)
                metrics = dict(metrics, **scores)
                self.history.setdefault("eval", []).append(
                    {"round": r + 1, **scores})
            if callback is not None:
                callback(r, metrics)

        if sanitize:
            from repro import debug as _debug

            guard = _debug.sanitize(
                **(sanitize if isinstance(sanitize, dict) else {}))
        else:
            guard = contextlib.nullcontext()
        with guard:
            if spec.scenario.async_cfg is not None:
                from repro.federated.async_engine import (BufferState,
                                                          run_buffered)

                # Materialize the event-loop state BEFORE the loop: the
                # engine mutates it in place, so a callback that saves
                # mid-run checkpoints the live clock/tasks/buffer (and a
                # resume replays the remaining flushes bit-exactly).
                if self.async_state is None:
                    self.async_state = BufferState.init(
                        self.server.J, spec.scenario.async_cfg,
                        self.server.seed)
                chunk, self.async_state = run_buffered(
                    self.server, n, spec.scenario.async_cfg,
                    local_steps=spec.local_steps,
                    start_flush=start,
                    state=self.async_state,
                    callback=cb,
                    population=self.population,
                )
            else:
                # algorithm=None: the Server already carries the built
                # strategy INSTANCE (spec.strategy hyperparameters
                # included); passing spec.algorithm's NAME would rebuild
                # it with registry defaults.
                chunk = self.server.run(
                    n,
                    local_steps=spec.local_steps,
                    scheduler=self.scheduler,
                    callback=cb,
                    start_round=start,
                    population=self.population,
                )
        for k, v in chunk.items():
            self.history.setdefault(k, []).extend(v)
        self.round = start + n
        return self.history

    def evaluate(self) -> Dict[str, float]:
        """Run the registry's eval hook on the current state ({} if none)."""
        if self.bundle.eval_fn is None:
            return {}
        return dict(self.bundle.eval_fn(self.server))

    # -- checkpointing -------------------------------------------------------

    def _meta_dict(self) -> Dict[str, Any]:
        meta: Dict[str, Any] = {
            "round": self.round,
            "comm": self.comm.state_dict(),
            # The wire layout is an execution knob, not spec state — but
            # DP noise keys and int8 scales depend on it, so a resume
            # must rebuild with the SAME layout to stay bit-exact.
            "wire": self.server.wire,
        }
        if self.accountant is not None:
            acct = self.accountant.state_dict()
            # JSON, not the msgpack/jnp path: the RDP ledger is float64
            # and jnp.asarray would silently downcast it to float32
            # (x64 disabled), breaking the bit-exact epsilon trace.
            # Python's repr-based JSON floats round-trip doubles exactly.
            meta["acct"] = {"rdp": [float(x) for x in np.asarray(acct["rdp"])],
                            "steps": int(acct["steps"])}
        if self.async_state is not None:
            # Buffered-async event loop: simulated clock, in-flight tasks
            # and the partially-filled buffer (JSON doubles are exact, so
            # the arrival schedule resumes bit-exactly).
            meta["async_state"] = self.async_state.state_dict()
        if self.population is not None:
            # Roster head-count + per-silo status/last-present: resume
            # rebuilds the Server at the saved width and replays the
            # event stream from the saved index, mid-event included.
            meta["population"] = self.population.state.state_dict()
        return meta

    @staticmethod
    def _meta_path(directory: str, step: int) -> str:
        return os.path.join(directory, f"step_{step:08d}.meta.json")

    @staticmethod
    def _silo_state_tree(state: Dict[str, Any]) -> Dict[str, Any]:
        """The per-silo shard contents: every stacked-(J, ...) state group
        with any leaves. η_{L_j}/opt_local exist when the model has local
        latents; ``strategy`` when the strategy keeps per-silo state
        (e.g. PVI/FedEP site parameters λ_j) — a stateful strategy on a
        global-only model still gets its shards."""
        silo_state: Dict[str, Any] = {}
        if jax.tree_util.tree_leaves(state["eta_L"]):
            silo_state["eta_L"] = state["eta_L"]
            silo_state["opt_local"] = state["opt_local"]
        if jax.tree_util.tree_leaves(state.get("strategy", {})):
            silo_state["strategy"] = state["strategy"]
        return silo_state

    def save(self, directory: str, keep: int = 3) -> str:
        """Persist the full round state under ``directory``.

        Layout (all through :class:`~repro.checkpoint.CheckpointManager`,
        ``keep`` most recent steps retained):

          * ``spec.json`` — the experiment spec (written once);
          * ``step_NNNNNNNN.msgpack`` — server state (θ, η_G, server
            optimizer);
          * ``step_NNNNNNNN.silo_JJJJ.msgpack`` — silo J's private state
            (η_{L_J} + its optimizer moments, plus per-silo strategy
            state such as PVI/FedEP site parameters λ_J), one file per
            silo so the server checkpoint never contains local
            variational parameters (the paper's privacy boundary, see
            ``repro.checkpoint.io``);
          * ``step_NNNNNNNN.meta.json`` — round index, communication
            counters, RDP ledger (JSON so the float64 ledger round-trips
            exactly).

        On a multi-process run, host I/O is routed through silo
        ownership: process 0 writes the spec, the replicated server
        state and the meta sidecar, and each process writes ONLY the
        silo shards it owns (its addressable rows of the stacked silo
        axis — reading another host's rows would dispatch a cross-host
        collective). Every process must call ``save``; the shared
        ``directory`` must be visible to all of them.

        Returns the directory.
        """
        from repro.federated import distributed

        multi = self.server.n_processes > 1
        lead = (not multi) or jax.process_index() == 0
        os.makedirs(directory, exist_ok=True)
        mgr = CheckpointManager(directory, keep=keep)
        state = self.server.state
        if lead:
            self.spec.save(os.path.join(directory, _SPEC_FILE))
            mgr.save(self.round, {k: state[k] for k in SERVER_STATE})
        silo_state = self._silo_state_tree(state)
        if silo_state:
            if multi:
                rows = [r for r in distributed.owned_rows(
                    self.server.mesh, self.server.J_pad)
                    if r < self.server.J]
                for j in rows:
                    mgr.save(
                        self.round,
                        jax.tree_util.tree_map(
                            lambda x, jj=j: distributed.host_rows(
                                x, [jj])[jj],
                            silo_state),
                        shard=f"silo_{j:04d}",
                    )
            else:
                for j in range(self.server.J):
                    mgr.save(
                        self.round,
                        jax.tree_util.tree_map(lambda x: x[j], silo_state),
                        shard=f"silo_{j:04d}",
                    )
        if lead:
            tmp = self._meta_path(directory, self.round) + ".tmp"
            with open(tmp, "w") as f:
                json.dump(self._meta_dict(), f)
            os.replace(tmp, self._meta_path(directory, self.round))
            # Retention for the JSON sidecars mirrors the msgpack GC.
            live = set(mgr.steps())
            for fn in os.listdir(directory):
                if fn.startswith("step_") and fn.endswith(".meta.json"):
                    s = fn[len("step_"):-len(".meta.json")]
                    if s.isdigit() and int(s) not in live:
                        os.remove(os.path.join(directory, fn))
        if multi:
            # All shards on disk before ANY process proceeds — a resume
            # right after save must never read a half-written step.
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices(f"repro_save_{self.round}")
        return directory

    @classmethod
    def resume(cls, directory: str, spec: Optional[ExperimentSpec] = None,
               step: Optional[int] = None, bundle=None,
               wire: Optional[str] = None) -> Experiment:
        """Rebuild from ``directory`` and restore the saved round state.

        Reads ``spec.json`` (unless ``spec`` overrides it), rebuilds the
        experiment with :func:`build` — the registry re-stages the data
        deterministically from the spec's seed — then restores θ, η_G,
        stacked η_{L_j}, both optimizer states, the communication meter,
        the RDP ledger and the round index from the latest (or ``step``)
        checkpoint. Continuing with :meth:`run` reproduces the
        uninterrupted run bit-exactly.

        ``wire`` is a DEPRECATED override (warns once; prefer
        ``spec.runtime.wire``) of the checkpoint's recorded layout —
        switching between ``"flat"`` and ``"fused"`` mid-run is safe
        (the fused kernels replay the identical op sequence and DP
        noise stream, so the continued trajectory is unchanged);
        switching to/from ``"legacy"`` changes per-leaf DP fold-ins and
        int8 scale granularity and will diverge under DP/compression.

        A resume may land on a DIFFERENT topology than the run that
        saved (device count, ``MeshSpec`` shape, process count):
        checkpoints hold the J real silos one file each, so the stacked
        axis is re-padded and resharded for the new mesh and the real
        silos' trajectory stays bit-exact. On a multi-process resume
        every process calls this; each reads only the silo shards it
        owns on the new mesh.
        """
        if wire is not None:
            _warn_wire_kwarg("Experiment.resume()")
        if spec is None:
            spec = ExperimentSpec.load(os.path.join(directory, _SPEC_FILE))
        mgr = CheckpointManager(directory)
        if step is None:
            step = mgr.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {directory!r}")
        # Meta first: the run's wire layout must be restored before the
        # Server is built (DP keys / int8 scales are layout-dependent,
        # so resuming a wire='legacy' run as 'flat' would diverge).
        with open(cls._meta_path(directory, step)) as f:
            meta = json.load(f)
        pop_meta = meta.get("population")
        exp = _build(spec, bundle,
                     wire if wire is not None
                     else meta.get("wire", spec.runtime.wire),
                     joined=(int(pop_meta["joined"])
                             if pop_meta is not None else None))
        if exp.population is not None and pop_meta is not None:
            exp.population.state = PopulationState.from_state(pop_meta)

        from repro.federated import distributed

        multi = exp.server.n_processes > 1
        state = exp.server.state
        like = {k: state[k] for k in SERVER_STATE}
        restored = mgr.restore(step, like)
        for k in SERVER_STATE:
            state[k] = restored[k]
        silo_like = cls._silo_state_tree(state)
        if silo_like and multi:
            J, J_pad = exp.server.J, exp.server.J_pad
            row_like = jax.tree_util.tree_map(
                lambda x: np.zeros(x.shape[1:], x.dtype), silo_like)
            loaded = {
                j: mgr.restore(step, row_like, shard=f"silo_{j:04d}")
                for j in distributed.owned_rows(exp.server.mesh, J_pad)
                if j < J
            }
            for k in silo_like:
                state[k] = distributed.silo_sharded_from_rows(
                    silo_like[k], exp.server.mesh,
                    {j: t[k] for j, t in loaded.items()})
        elif silo_like:
            # Shard-tolerant: a resume may rebuild with MORE silos than
            # the run that saved (e.g. a fixed-J spec override growing
            # the roster) — silos with no shard on disk keep their fresh
            # init row; every saved silo restores bit-exactly.
            slices = []
            for j in range(exp.server.J):
                row = jax.tree_util.tree_map(
                    lambda x, jj=j: x[jj], silo_like)
                if mgr.has(step, shard=f"silo_{j:04d}"):
                    row = mgr.restore(step, row, shard=f"silo_{j:04d}")
                slices.append(row)
            stacked = jax.tree_util.tree_map(
                lambda *xs: jax.numpy.stack(xs), *slices)
            # Checkpoints hold the J REAL silos; re-pad the stacked axis
            # to this mesh's J_pad (a resume may land on a different
            # device count — padded rows are masked and never read).
            for k in silo_like:
                state[k] = exp.server.pad_silo_axis(stacked[k])
        # Restored host trees -> the round's input shardings on the new
        # mesh (every process read the identical server files; silo rows
        # built above are already global).
        exp.server.place()

        exp.round = int(meta["round"])
        exp.comm.load_state(meta["comm"])
        if exp.accountant is not None and "acct" in meta:
            exp.accountant.load_state({
                "rdp": np.asarray(meta["acct"]["rdp"], np.float64),
                "steps": int(meta["acct"]["steps"]),
            })
        if "async_state" in meta:
            from repro.federated.async_engine import BufferState

            exp.async_state = BufferState.from_state(meta["async_state"])
        return exp


def run_spec(spec: ExperimentSpec,
             callback: Optional[Callable[[int, dict], None]] = None) -> Experiment:
    """One-shot convenience: ``build(spec)`` then run the full budget."""
    exp = build(spec)
    exp.run(callback=callback)
    return exp


def scenario_specs(base: ExperimentSpec, scenarios: List[Scenario]) -> List[ExperimentSpec]:
    """Cross one base spec with a scenario list (the --sweep expansion)."""
    return [dataclasses.replace(base, scenario=sc) for sc in scenarios]
