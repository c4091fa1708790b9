"""Federated posterior serving: answer ``q(Z_L | Z_G)`` queries from a
checkpoint.

Training ends with the structured posterior split across the privacy
boundary — the server's ``q_{η_G}(Z_G)`` plus one private
``q_{η_{L_j}}(Z_{L_j} | Z_G)`` per silo. This module turns a saved run
(:meth:`repro.federated.api.Experiment.save`) into a query endpoint:

  * :meth:`Posterior.global_sample` — draws from ``q_{η_G}(Z_G)``;
  * :meth:`Posterior.sample` — joint ``(Z_G, Z_{L_j})`` draws for one
    silo, routed through the same :class:`~repro.core.sfvi.SFVIProblem`
    sampling path training used (conditional families condition on the
    drawn ``Z_G``, so the serving-time posterior is exactly the
    variational family the paper optimizes);
  * :meth:`Posterior.predict` — posterior-predictive outputs for new
    inputs through the model's optional ``predict`` hook, averaged over
    posterior draws;
  * :meth:`Posterior.answer_batch` — a request batcher: queries are
    grouped by (kind, silo) and each group is served by ONE vectorized
    sampling call (the per-query draws are slices of a single
    ``num_samples = Σ n`` batch), then scattered back in request order.

Every query is deterministic in its ``seed`` — two replicas serving the
same checkpoint return bit-identical answers, the serving-side analogue
of the trainer's bit-exact resume contract.

Each group is one dispatch of one jitted program: the serving key
(``fold_in(PRNGKey(salt + seed), silo + 1)``) and the silo's row of the
stacked ``η_L`` are taken inside it from a traced ``(seed, silo)``.
Samplers compile per row bucket, the next power of two at or above the
rows asked for, not per row count: a group of ``Σ n`` draws takes the
first ``Σ n`` rows of its bucket. Draws are prefix-consistent (with
JAX's default partitionable threefry, ``normal(k, (N,) + s)[:n]`` equals
``normal(k, (n,) + s)``, and the families sample row by row), so the
bucket changes no answer, and a total above every bucket served so far
compiles one new power of two, once. ``predict`` averages over exactly
``n`` draws, so its programs stay keyed on ``n`` and the shape of ``x``.

CLI::

    python -m repro.federated.serve --ckpt-dir runs/demo --silo 0 --n 3
    python -m repro.federated.serve --ckpt-dir runs/demo --global-sample 5
    python -m repro.federated.serve --ckpt-dir runs/demo \
        --queries '[{"kind": "sample", "silo": 1, "n": 2}]'

Latency under open-loop traffic is measured by the chip benchmark's
``hier_bnn-serve-poisson-j64`` cell (``BENCHMARK.json``,
``perfbench/drivers/serve.py``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

# Fold-in salt separating the serving key stream from training's
# round keys (fold_in(seed, round)) and the population/latency salts.
_SERVE_SALT = 0x53E7


def _bucket(n: int) -> int:
    """Rows a group of ``n`` draws is served from: the next power of two."""
    return 1 << (n - 1).bit_length()


def _stream(seed: int, silo: int) -> np.ndarray:
    """``[_SERVE_SALT + seed, silo]``, the traced argument that keys a
    serving program.

    ``PRNGKey`` of a Python int goes through int64 to JAX's default int
    (int32 unless x64 is on), wrapping; the same cast here keeps the
    in-program key equal to :meth:`Posterior._key`'s for every seed it
    accepts, and raises the same ``OverflowError`` for the rest.
    """
    return np.array([_SERVE_SALT + seed, silo], np.int64).astype(
        jax.dtypes.canonicalize_dtype(np.int64))


def _stream_key(stream: jax.Array) -> jax.Array:
    """The serving key of ``stream``, inside a program."""
    # repro-lint: allow[R1] — serving key root: pure function of the query seed, disjoint from training streams
    return jax.random.fold_in(jax.random.PRNGKey(stream[0]), stream[1] + 1)


def _silo_row(eta_L: PyTree, silo: jax.Array) -> PyTree:
    """Row ``silo`` of the stacked ``η_L``, inside a program."""
    if eta_L is None:
        return None
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, silo, keepdims=False),
        eta_L)


@dataclasses.dataclass(frozen=True)
class Query:
    """One serving request.

    ``kind`` is ``"sample"`` (joint ``(Z_G, Z_{L_silo})`` draws),
    ``"global_sample"`` (``Z_G`` only; ``silo`` ignored) or
    ``"predict"`` (posterior-predictive outputs for inputs ``x``
    through the model's ``predict`` hook, averaged over ``n`` draws).
    """

    kind: str
    silo: Optional[int] = None
    n: int = 1
    x: Optional[Any] = None

    def __post_init__(self):
        if self.kind not in ("sample", "global_sample", "predict"):
            raise ValueError(f"unknown query kind {self.kind!r}")
        if self.n < 1:
            raise ValueError(f"n must be >= 1, got {self.n}")
        if self.kind != "global_sample" and self.silo is None:
            raise ValueError(f"{self.kind!r} queries need a silo index")
        if self.kind == "predict" and self.x is None:
            raise ValueError("predict queries need inputs x")

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Query":
        x = d.get("x")
        return cls(kind=d["kind"], silo=d.get("silo"), n=d.get("n", 1),
                   x=None if x is None else jnp.asarray(x))


class Posterior:
    """A checkpointed federated posterior, ready to answer queries.

    Wraps a restored :class:`~repro.federated.api.Experiment` —
    construct with :meth:`from_checkpoint` (the usual path) or directly
    from a live experiment (``Posterior(exp)``) to serve mid-training
    state without a disk round-trip.
    """

    def __init__(self, experiment):
        self.experiment = experiment
        self.server = experiment.server
        self.problem = self.server.problem
        # One jitted program per (kind, row bucket) for the samplers and
        # per (n, x-shape) for predict: a serving loop compiles a handful.
        self._compiled: Dict[tuple, Any] = {}

    @classmethod
    def from_checkpoint(cls, directory: str,
                        step: Optional[int] = None) -> "Posterior":
        """Restore the latest (or ``step``) checkpoint under ``directory``."""
        from repro.federated.api import Experiment

        return cls(Experiment.resume(directory, step=step))

    # -- state accessors -----------------------------------------------------

    @property
    def num_silos(self) -> int:
        """Live silos (a population checkpoint restores mid-roster)."""
        return self.server.J

    @property
    def round(self) -> int:
        return self.experiment.round

    def eta_row(self, silo: int) -> PyTree:
        """Silo ``silo``'s private ``η_{L_j}`` (row of the stacked axis)."""
        self._check_silo(silo)
        if not self.problem.model.has_local:
            return None
        return jax.tree_util.tree_map(
            lambda x: x[silo], self.server.state["eta_L"])

    # -- sampling ------------------------------------------------------------

    def _key(self, seed: int, silo: int) -> jax.Array:
        """The serving key of ``(seed, silo)``, derived eagerly: the
        reference that every program's in-graph key
        (:func:`_stream_key`) equals bit for bit."""
        with self._bridge():
            # silo + 1: fold_in data is uint32 and the global stream
            # uses silo = -1.
            # repro-lint: allow[R1] — serving key root: pure function of the query seed, disjoint from training streams
            root = jax.random.PRNGKey(_SERVE_SALT + seed)
            return jax.random.fold_in(root, silo + 1)

    @staticmethod
    def _bridge():
        from repro import debug

        return debug.host_bridge()

    def _local_state(self) -> PyTree:
        """The stacked ``η_L`` a program takes its silo's row from."""
        if not self.problem.model.has_local:
            return None
        return self.server.state["eta_L"]

    def _program(self, kind: str, rows: int, x_shape: tuple = ()):
        """The jitted program of one group: ``rows`` draws of ``kind``
        keyed by the traced ``_stream(seed, silo)``, which also picks the
        silo's row of the stacked ``η_L`` inside the program."""
        ck = (kind, rows, x_shape)
        if ck in self._compiled:
            return self._compiled[ck]
        prob = self.problem

        if kind == "global_sample":
            def run(eta_G, stream):
                return prob.sample_posterior(eta_G, None, _stream_key(stream),
                                             num_samples=rows)[0]
        elif kind == "sample":
            def run(eta_G, eta_L, stream):
                return prob.sample_posterior(
                    eta_G, _silo_row(eta_L, stream[1]), _stream_key(stream),
                    num_samples=rows)
        else:
            predict = prob.model.predict

            def run(theta, eta_G, eta_L, x, stream):
                z_G, z_L = prob.sample_posterior(
                    eta_G, _silo_row(eta_L, stream[1]), _stream_key(stream),
                    num_samples=rows)
                if z_L is None:
                    out = jax.vmap(lambda zg: predict(theta, zg, None, x))(z_G)
                else:
                    out = jax.vmap(
                        lambda zg, zl: predict(theta, zg, zl, x))(z_G, z_L)
                return jnp.mean(out, axis=0)

        self._compiled[ck] = jax.jit(run)
        return self._compiled[ck]

    def _check_silo(self, silo: int) -> None:
        if not 0 <= silo < self.server.J:
            raise IndexError(
                f"silo {silo} out of range: checkpoint serves "
                f"{self.server.J} silos")

    def global_sample(self, n: int = 1, seed: int = 0) -> jax.Array:
        """``n`` draws of ``Z_G`` from ``q_{η_G}`` — shape ``(n, d_G)``."""
        n = int(n)
        rows = _bucket(n)
        fn = self._program("global_sample", rows)
        with self._bridge():
            z = fn(self.server.state["eta_G"], _stream(seed, -1))
        return z if rows == n else z[:n]

    def sample(self, silo: int, n: int = 1,
               seed: int = 0) -> Dict[str, Optional[jax.Array]]:
        """``n`` joint draws for ``silo``: ``{"z_G": (n, d_G), "z_L": (n, d_L)}``.

        ``z_L`` is None for global-only models. Conditional local
        families draw ``Z_L | Z_G`` from the SAME ``Z_G`` realization
        returned, so the pair is a joint posterior draw.
        """
        self._check_silo(silo)
        n = int(n)
        rows = _bucket(n)
        fn = self._program("sample", rows)
        with self._bridge():
            z_G, z_L = fn(self.server.state["eta_G"], self._local_state(),
                          _stream(seed, silo))
        if rows != n:
            z_G = z_G[:n]
            z_L = None if z_L is None else z_L[:n]
        return {"z_G": z_G, "z_L": z_L}

    def predict(self, silo: int, x, n: int = 8, seed: int = 0) -> jax.Array:
        """Posterior-predictive output for inputs ``x`` at ``silo``.

        Averages the model's ``predict(θ, Z_G, Z_{L_silo}, x)`` over
        ``n`` joint posterior draws. Raises for models without a
        ``predict`` hook.
        """
        if self.problem.model.predict is None:
            raise ValueError(
                f"model {self.problem.model.name!r} has no predict hook; "
                f"only sample/global_sample queries are servable")
        self._check_silo(silo)
        x = jnp.asarray(x)
        # The mean is over exactly n draws: no row bucket here.
        fn = self._program("predict", int(n), tuple(x.shape))
        st = self.server.state
        with self._bridge():
            return fn(st["theta"], st["eta_G"], self._local_state(), x,
                      _stream(seed, silo))

    # -- request batching ----------------------------------------------------

    def answer_batch(self, queries: Sequence[Query],
                     seed: int = 0) -> List[Any]:
        """Serve ``queries``, batching draws per (kind, silo) group.

        All ``sample``/``global_sample`` queries hitting the same silo
        are served by ONE vectorized call that draws the bucket of
        ``Σ n`` rows, and the per-query answers are contiguous slices of
        its first ``Σ n`` rows, in request order — the amortization that
        makes many small queries as cheap as one big one. ``predict``
        queries keep one call per query (their ``x`` shapes differ).
        Answers are returned in request order; the batching is invisible
        in the results (same draws as issuing the grouped queries
        back-to-back with one shared key per group).
        """
        groups: Dict[Tuple[str, int], List[int]] = {}
        for i, q in enumerate(queries):
            silo = -1 if q.kind == "global_sample" else int(q.silo)
            groups.setdefault((q.kind, silo), []).append(i)
        answers: List[Any] = [None] * len(queries)
        for (kind, silo), idxs in groups.items():
            if kind == "predict":
                for i in idxs:
                    q = queries[i]
                    answers[i] = self.predict(silo, q.x, n=q.n, seed=seed)
                continue
            # The group's draws are the first Σ n rows of its bucket.
            rows = _bucket(sum(queries[i].n for i in idxs))
            if kind == "global_sample":
                z = self.global_sample(rows, seed=seed)
                batch = {"z_G": z, "z_L": None}
            else:
                batch = self.sample(silo, rows, seed=seed)
            off = 0
            for i in idxs:
                n = queries[i].n
                answers[i] = {
                    k: (None if v is None else v[off:off + n])
                    for k, v in batch.items()
                }
                off += n
        return answers


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _jsonable(x):
    if x is None:
        return None
    if isinstance(x, dict):
        return {k: _jsonable(v) for k, v in x.items()}
    return np.asarray(x).tolist()


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.federated.serve",
        description="Answer q(Z_L|Z_G) queries from a federated checkpoint.")
    ap.add_argument("--ckpt-dir", required=True, metavar="DIR",
                    help="checkpoint directory written by Experiment.save")
    ap.add_argument("--step", type=int, default=None,
                    help="checkpoint step (default: latest)")
    ap.add_argument("--silo", type=int, default=None,
                    help="serve n joint (Z_G, Z_L) draws for this silo")
    ap.add_argument("--n", type=int, default=1,
                    help="draws per query (with --silo / --global-sample)")
    ap.add_argument("--global-sample", type=int, default=None, metavar="N",
                    help="serve N draws of Z_G from q(Z_G)")
    ap.add_argument("--queries", default=None, metavar="JSON",
                    help='batched request list, e.g. \'[{"kind": "sample", '
                         '"silo": 0, "n": 2}]\' — grouped by silo and '
                         "served with one vectorized call per group")
    ap.add_argument("--seed", type=int, default=0,
                    help="query seed (same seed -> bit-identical answers)")
    args = ap.parse_args(argv)

    from repro import compile_cache

    compile_cache.enable()
    post = Posterior.from_checkpoint(args.ckpt_dir, step=args.step)
    out: Dict[str, Any] = {
        "round": post.round,
        "num_silos": post.num_silos,
    }
    if args.queries is not None:
        qs = [Query.from_dict(d) for d in json.loads(args.queries)]
        out["answers"] = [_jsonable(a) for a in post.answer_batch(
            qs, seed=args.seed)]
    elif args.global_sample is not None:
        out["z_G"] = _jsonable(post.global_sample(args.global_sample,
                                                  seed=args.seed))
    elif args.silo is not None:
        out["answer"] = _jsonable(post.sample(args.silo, args.n,
                                              seed=args.seed))
    else:
        ap.error("one of --silo, --global-sample or --queries is required")
    json.dump(out, sys.stdout)
    sys.stdout.write("\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
