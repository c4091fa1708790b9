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

CLI::

    python -m repro.federated.serve --ckpt-dir runs/demo --silo 0 --n 3
    python -m repro.federated.serve --ckpt-dir runs/demo --global-sample 5
    python -m repro.federated.serve --ckpt-dir runs/demo \
        --queries '[{"kind": "sample", "silo": 1, "n": 2}]'

Latency/throughput numbers live in ``benchmarks/bench_serving.py``
(the federated-posterior row).
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
        # Sampling shapes are static per (kind, n, x-shape); memoize the
        # jitted closures so a serving loop pays one trace per shape.
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
        if not 0 <= silo < self.server.J:
            raise IndexError(
                f"silo {silo} out of range: checkpoint serves "
                f"{self.server.J} silos")
        if not self.problem.model.has_local:
            return None
        return jax.tree_util.tree_map(
            lambda x: x[silo], self.server.state["eta_L"])

    # -- sampling ------------------------------------------------------------

    def _key(self, seed: int, silo: int) -> jax.Array:
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

    def _sampler(self, n: int):
        key = ("sample", n)
        if key not in self._compiled:
            prob = self.problem

            def draw(eta_G, eta_L, k):
                return prob.sample_posterior(eta_G, eta_L, k, num_samples=n)

            self._compiled[key] = jax.jit(draw)
        return self._compiled[key]

    def _global_sampler(self, n: int):
        key = ("global", n)
        if key not in self._compiled:
            prob = self.problem

            def draw(eta_G, k):
                return prob.sample_posterior(eta_G, None, k, num_samples=n)[0]

            self._compiled[key] = jax.jit(draw)
        return self._compiled[key]

    def _predictor(self, n: int, x_shape: tuple):
        key = ("predict", n, x_shape)
        if key not in self._compiled:
            prob = self.problem
            predict = prob.model.predict

            def run(theta, eta_G, eta_L, x, k):
                z_G, z_L = prob.sample_posterior(eta_G, eta_L, k,
                                                 num_samples=n)
                if z_L is None:
                    out = jax.vmap(lambda zg: predict(theta, zg, None, x))(z_G)
                else:
                    out = jax.vmap(
                        lambda zg, zl: predict(theta, zg, zl, x))(z_G, z_L)
                return jnp.mean(out, axis=0)

            self._compiled[key] = jax.jit(run)
        return self._compiled[key]

    def global_sample(self, n: int = 1, seed: int = 0) -> jax.Array:
        """``n`` draws of ``Z_G`` from ``q_{η_G}`` — shape ``(n, d_G)``."""
        fn = self._global_sampler(int(n))
        return fn(self.server.state["eta_G"], self._key(seed, -1))

    def sample(self, silo: int, n: int = 1,
               seed: int = 0) -> Dict[str, Optional[jax.Array]]:
        """``n`` joint draws for ``silo``: ``{"z_G": (n, d_G), "z_L": (n, d_L)}``.

        ``z_L`` is None for global-only models. Conditional local
        families draw ``Z_L | Z_G`` from the SAME ``Z_G`` realization
        returned, so the pair is a joint posterior draw.
        """
        eta_L = self.eta_row(silo)
        fn = self._sampler(int(n))
        z_G, z_L = fn(self.server.state["eta_G"], eta_L,
                      self._key(seed, silo))
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
        eta_L = self.eta_row(silo)
        x = jnp.asarray(x)
        fn = self._predictor(int(n), tuple(x.shape))
        return fn(self.server.state["theta"], self.server.state["eta_G"],
                  eta_L, x, self._key(seed, silo))

    # -- request batching ----------------------------------------------------

    def answer_batch(self, queries: Sequence[Query],
                     seed: int = 0) -> List[Any]:
        """Serve ``queries``, batching draws per (kind, silo) group.

        All ``sample``/``global_sample`` queries hitting the same silo
        are served by ONE vectorized ``num_samples = Σ n`` call and the
        per-query answers are contiguous slices of that batch, in
        request order — the amortization that makes many small queries
        as cheap as one big one. ``predict`` queries keep one call per
        query (their ``x`` shapes differ), but still share the group's
        compiled sampler. Answers are returned in request order; the
        batching is invisible in the results (same draws as issuing the
        grouped queries back-to-back with one shared key per group).
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
            total = sum(queries[i].n for i in idxs)
            if kind == "global_sample":
                z = self.global_sample(total, seed=seed)
                batch = {"z_G": z, "z_L": None}
            else:
                batch = self.sample(silo, total, seed=seed)
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
