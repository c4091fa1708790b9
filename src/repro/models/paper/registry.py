"""Model registry — the paper's experiment models as named, buildable entries.

The declarative experiment API (:mod:`repro.federated.api`) refers to a
model by name plus JSON-serializable kwargs; this registry resolves the
name to a builder that stages everything a federation run needs:

  * the :class:`~repro.core.sfvi.SFVIProblem` (model + variational
    families),
  * initial model parameters θ₀,
  * J per-silo data pytrees with equal leaf shapes (what the compiled
    :class:`~repro.federated.runtime.Server` stacks along the ``silo``
    mesh axis),
  * per-silo observation counts N_j (SFVI-Avg's N/N_j rescale),
  * an evaluation hook ``eval_fn(server) -> {metric: value}``,
  * model-specific extras (test splits, oracles, closed-form answers)
    that benchmarks and examples read.

This module imports nothing heavy at module level — listing names (e.g.
``repro.federated.run --list-models``) must work before JAX is imported
so the CLI can still set ``XLA_FLAGS`` from ``--devices``. Builders do
their imports lazily when called.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

PyTree = Any


@dataclasses.dataclass(frozen=True)
class ModelBundle:
    """Everything one federation run needs, staged and ready to serve.

    Attributes:
      problem: the SFVI problem (generative model + variational families).
      theta0: initial model parameters θ (``{}`` for fully-Bayesian).
      datas: J per-silo data pytrees with equal leaf shapes.
      num_obs: per-silo observation counts N_j, or None to infer from
        the leading data dimension.
      eval_fn: ``eval_fn(server) -> {name: float}`` evaluated on the
        live :class:`~repro.federated.runtime.Server`, or None.
      extras: model-specific artifacts (test splits, pooled data for
        oracles, closed-form posteriors) for benchmarks/examples.
    """

    problem: Any
    theta0: PyTree
    datas: List[PyTree]
    num_obs: Optional[List[int]] = None
    eval_fn: Optional[Callable[[Any], Dict[str, float]]] = None
    extras: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class ModelEntry:
    """One registered model: a name, a help string, and a builder."""

    name: str
    description: str
    build: Callable[..., ModelBundle]


_REGISTRY: Dict[str, ModelEntry] = {}


def register(name: str, description: str):
    """Decorator: register ``fn(seed, num_silos, **kwargs) -> ModelBundle``."""

    def deco(fn):
        if name in _REGISTRY:
            raise ValueError(f"model {name!r} registered twice")
        _REGISTRY[name] = ModelEntry(name=name, description=description, build=fn)
        return fn

    return deco


def get_model(name: str) -> ModelEntry:
    """Resolve a registry name; raises with the available names on miss."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown model {name!r}; registered models: "
            + ", ".join(sorted(_REGISTRY))
        ) from None


def list_models() -> List[Tuple[str, str]]:
    """Sorted (name, description) pairs — what ``--list-models`` prints."""
    return [(e.name, e.description) for _, e in sorted(_REGISTRY.items())]


def model_names() -> List[str]:
    """Sorted registered names (CLI ``choices``)."""
    return sorted(_REGISTRY)


def apply_family_spec(bundle: ModelBundle, global_family=None,
                      local_family=None) -> ModelBundle:
    """Swap the staged problem's variational families from FamilySpecs.

    Every registered model stages a default family pair (the paper's
    choice); a :class:`~repro.core.family.FamilySpec` on the
    ``ModelSpec`` overrides either side — the structural dimensions
    (``dim``, ``global_dim``) come from the staged model, so the same
    spec applies to any registry entry. Data, θ₀, counts and eval hooks
    are untouched (family choice never changes the generative model).

    Imports lazily: the registry module must stay importable before JAX
    (``--list-models`` runs pre-``XLA_FLAGS``).
    """
    if global_family is None and local_family is None:
        return bundle
    import dataclasses as _dc

    from repro.core.family import build_family

    problem = bundle.problem
    model = problem.model
    gfam, lfam = problem.global_family, problem.local_family
    if global_family is not None:
        gfam = build_family(global_family, dim=model.global_dim)
    if local_family is not None:
        lfam = build_family(local_family, dim=model.local_dim,
                            global_dim=model.global_dim)
    problem = _dc.replace(problem, global_family=gfam, local_family=lfam)
    return _dc.replace(bundle, problem=problem)


# ---------------------------------------------------------------------------
# Builders (imports deferred to call time; see module docstring)
# ---------------------------------------------------------------------------


@register("toy", "Hierarchical Gaussian with a closed-form posterior (quickstart)")
def _build_toy(seed: int, num_silos: int, *, num_obs: int = 40,
               true_mu: float = 2.0, use_coupling: bool = True) -> ModelBundle:
    """μ ~ N(0, 10²); b_j | μ ~ N(μ, 1); y_jk | b_j ~ N(b_j, 0.5²).

    Z_G = μ, Z_{L_j} = b_j, θ = ∅. The exact posterior of μ given the
    silo means is Gaussian; ``extras`` carries it for correctness checks.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.core import ConditionalGaussian, DiagGaussian, SFVIProblem, StructuredModel

    rng = np.random.default_rng(seed)
    true_b = rng.normal(true_mu, 1.0, num_silos)
    datas = [{"y": jnp.asarray(rng.normal(true_b[j], 0.5, num_obs))}
             for j in range(num_silos)]

    model = StructuredModel(
        global_dim=1, local_dim=1,
        log_prior_global=lambda th, zg: -0.5 * jnp.sum(zg**2) / 10.0**2,
        log_local=lambda th, zg, zl, d: (
            -0.5 * jnp.sum((zl - zg) ** 2)
            - 0.5 * jnp.sum((d["y"] - zl) ** 2) / 0.5**2
        ),
        name="toy_hier_gaussian",
    )
    problem = SFVIProblem(
        model, DiagGaussian(1),
        ConditionalGaussian(1, 1, use_coupling=use_coupling),
    )

    # Closed form: posterior of μ given silo means ȳ_j (b_j integrated out).
    ybar = np.array([float(np.mean(np.asarray(d["y"]))) for d in datas])
    var_j = 1.0 + 0.5**2 / num_obs  # var of ȳ_j | μ, identical across silos
    post_prec = 1.0 / 10.0**2 + num_silos / var_j
    post_mu = float(np.sum(ybar) / var_j / post_prec)

    def eval_fn(server):
        mu_hat = float(np.asarray(server.eta_G["mu"])[0])
        return {"abs_error_vs_exact": abs(mu_hat - post_mu)}

    return ModelBundle(
        problem=problem, theta0={}, datas=datas,
        num_obs=[num_obs] * num_silos, eval_fn=eval_fn,
        extras={"true_mu": true_mu, "posterior_mu": post_mu,
                "posterior_sd": float(np.sqrt(1.0 / post_prec))},
    )


def _bnn_bundle(seed: int, num_silos: int, *, fedpop: bool, kwargs) -> ModelBundle:
    from repro.models.paper.fixtures import bnn_posterior_accuracy, hier_bnn_federation

    bnn, train, test = hier_bnn_federation(
        seed=seed, num_silos=num_silos, fedpop=fedpop, **kwargs)

    def eval_fn(server):
        acc, std = bnn_posterior_accuracy(bnn, server.eta_G, server.eta_L, test)
        return {"test_acc": acc, "test_acc_std": std}

    return ModelBundle(
        problem=bnn.problem, theta0={}, datas=train,
        num_obs=[int(d["y"].shape[0]) for d in train], eval_fn=eval_fn,
        extras={"bnn": bnn, "test": test},
    )


@register("hier_bnn", "Hierarchical BNN on heterogeneous synthetic MNIST (§4.1)")
def _build_hier_bnn(seed: int, num_silos: int, **kwargs) -> ModelBundle:
    return _bnn_bundle(seed, num_silos, fedpop=False, kwargs=kwargs)


@register("fedpop_bnn", "Fully-Bayesian FedPop BNN variant (§4.1, Table 1 row 2)")
def _build_fedpop_bnn(seed: int, num_silos: int, **kwargs) -> ModelBundle:
    return _bnn_bundle(seed, num_silos, fedpop=True, kwargs=kwargs)


@register("prodlda", "Federated ProdLDA topic model on a synthetic corpus (§4.2)")
def _build_prodlda(seed: int, num_silos: int, **kwargs) -> ModelBundle:
    import numpy as np

    from repro.models.paper.fixtures import prodlda_federation
    from repro.models.paper.prodlda import init_theta, umass_coherence

    lda, datas, counts = prodlda_federation(seed=seed, num_silos=num_silos, **kwargs)

    def eval_fn(server):
        t = np.asarray(lda.topics(server.eta_G["mu"]))
        coh = umass_coherence(t, counts, top_n=8)
        return {"coherence_median": float(np.median(coh)),
                "coherence_mean": float(np.mean(coh))}

    return ModelBundle(
        problem=lda.problem, theta0=init_theta(), datas=datas,
        num_obs=[lda.docs_per_silo] * num_silos, eval_fn=eval_fn,
        extras={"lda": lda, "counts": counts},
    )


@register("glmm", "Bayesian logistic GLMM, six-cities protocol (supplement S3.1)")
def _build_glmm(seed: int, num_silos: int, *, num_children: int = 120) -> ModelBundle:
    """Even split of the six-cities children across silos.

    The compiled Server stacks silo data along a leading axis, so every
    silo carries ``num_children // num_silos`` children (the leftover
    children are dropped; the paper's uneven 300/237 split corresponds
    to the host-level protocol, not the stacked SPMD layout).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data import make_six_cities, sizes_partition
    from repro.models.paper.glmm import build_glmm

    per_silo = num_children // num_silos
    total = per_silo * num_silos
    data, truth = make_six_cities(jax.random.PRNGKey(seed + 3), num_children=total)
    rng = np.random.default_rng(seed)
    parts = sizes_partition(rng, total, [per_silo] * num_silos)
    datas = [{k: jnp.asarray(v[p]) for k, v in data.items()} for p in parts]
    glmm = build_glmm(num_children_j=per_silo)

    return ModelBundle(
        problem=glmm.problem, theta0={}, datas=datas,
        num_obs=[per_silo] * num_silos, eval_fn=None,
        extras={"pooled": {k: jnp.asarray(v) for k, v in data.items()},
                "truth": truth, "num_children": total},
    )


@register("hetero_mn",
          "Multinomial regression under Dirichlet non-IID silos "
          "(unequal N_j, label skew)")
def _build_hetero_mn(seed: int, num_silos: int, *, n_total: int = 240,
                     in_dim: int = 196, alpha: float = 0.5,
                     min_per_silo: int = 2, prototype_scale: float = 0.6,
                     noise_scale: float = 3.0) -> ModelBundle:
    """The heterogeneous-silo scenario generator.

    Stages the multinomial model over a Dirichlet(α) label partition
    (Hsu et al., 2019): each class's samples are split across silos by
    ``p ~ Dir(α·1_J)``, producing the two hallmarks of real federations
    — per-silo label skew AND unequal shard sizes N_j. Small α is
    extreme non-IID, large α approaches IID. Ragged shards are padded
    to the widest silo with a 0/1 row-weight vector consumed by the
    weighted likelihood, so the compiled stacked runtime runs unchanged
    and padded rows contribute exactly nothing; ``num_obs`` carries the
    TRUE unequal N_j, which is what SFVI-Avg's N/N_j rescale sees.
    Composes freely with async execution, DP and compression — one spec
    covers async × non-IID × DP × int8.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data import (dirichlet_label_partition, make_synthetic_mnist,
                            pad_ragged_silos)
    from repro.models.paper.multinomial import build_multinomial, init_theta

    tr, te = make_synthetic_mnist(
        jax.random.PRNGKey(seed), n_total, max(200, num_silos * 20),
        dim=in_dim, prototype_scale=prototype_scale, noise_scale=noise_scale,
    )
    rng = np.random.default_rng(seed)
    parts = dirichlet_label_partition(
        rng, tr.y, num_silos, alpha=alpha, min_per_silo=min_per_silo)
    num_obs = [len(p) for p in parts]
    ragged = [{"x": tr.x[p], "y": tr.y[p]} for p in parts]
    datas = [{k: jnp.asarray(v) for k, v in d.items()}
             for d in pad_ragged_silos(ragged)]
    test = {"x": jnp.asarray(te.x), "y": jnp.asarray(te.y)}
    train_all = {"x": jnp.asarray(tr.x), "y": jnp.asarray(tr.y)}
    model = build_multinomial(in_dim=in_dim)

    def eval_fn(server):
        return {
            "train_acc": float(model.accuracy(
                server.eta_G["mu"], train_all["x"], train_all["y"])),
            "test_acc": float(model.accuracy(
                server.eta_G["mu"], test["x"], test["y"])),
        }

    skew = float(np.std(num_obs) / np.mean(num_obs))
    return ModelBundle(
        problem=model.problem, theta0=init_theta(), datas=datas,
        num_obs=num_obs, eval_fn=eval_fn,
        extras={"model": model, "train_all": train_all, "test": test,
                "partitions": parts, "alpha": alpha, "size_skew": skew},
    )


@register("multinomial",
          "Empirically-Bayesian multinomial regression (supplement S3.2)")
def _build_multinomial(seed: int, num_silos: int, *, n_per: int = 60,
                       in_dim: int = 196, prototype_scale: float = 0.6,
                       noise_scale: float = 3.0) -> ModelBundle:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data import iid_partition, make_synthetic_mnist
    from repro.models.paper.multinomial import build_multinomial, init_theta

    tr, te = make_synthetic_mnist(
        jax.random.PRNGKey(seed), num_silos * n_per, max(200, num_silos * 20),
        dim=in_dim, prototype_scale=prototype_scale, noise_scale=noise_scale,
    )
    rng = np.random.default_rng(seed)
    parts = iid_partition(rng, len(tr.y), num_silos)
    datas = [{"x": jnp.asarray(tr.x[p]), "y": jnp.asarray(tr.y[p])} for p in parts]
    test = {"x": jnp.asarray(te.x), "y": jnp.asarray(te.y)}
    train_all = {"x": jnp.asarray(tr.x), "y": jnp.asarray(tr.y)}
    model = build_multinomial(in_dim=in_dim)

    def eval_fn(server):
        return {
            "train_acc": float(model.accuracy(
                server.eta_G["mu"], train_all["x"], train_all["y"])),
            "test_acc": float(model.accuracy(
                server.eta_G["mu"], test["x"], test["y"])),
        }

    return ModelBundle(
        problem=model.problem, theta0=init_theta(), datas=datas,
        num_obs=[len(p) for p in parts], eval_fn=eval_fn,
        extras={"model": model, "train_all": train_all, "test": test},
    )


@register("nemotron_h",
          "Nemotron-3-Nano hybrid Mamba-2 / MoE backbone under the Bayesian "
          "LM head, next-token on Zipf-skewed synthetic silos")
def _build_nemotron_h(seed: int, num_silos: int, *,
                      seq_len: int = 32) -> ModelBundle:
    """θ = the backbone (seeded random weights), Z_G / Z_Lj = the head's
    global and per-silo adapters (``models/backbone/bayes.py``).

    Builds the CPU-size variant of the config (its first pattern period
    at small widths). Each silo holds 2 sequences of ``seq_len + 1``
    tokens drawn from its own Zipf(1.1) over a silo-specific permutation
    of the vocabulary, so silos route their tokens unevenly over the
    experts.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.models.backbone import bayes, transformer

    seqs_per_silo = 2
    cfg = get_config("nemotron3-nano-30b-a3b").reduced()
    V = cfg.vocab_size
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, V + 1) ** 1.1
    p /= p.sum()
    datas = [{"tokens": jnp.asarray(rng.permutation(V)[rng.choice(
        V, size=(seqs_per_silo, seq_len + 1), p=p)], jnp.int32)}
        for _ in range(num_silos)]
    theta0 = jax.jit(lambda k: transformer.init_params(k, cfg))(
        jax.random.PRNGKey(seed))
    return ModelBundle(
        problem=bayes.sfvi_problem(cfg), theta0=theta0, datas=datas,
        num_obs=[seqs_per_silo] * num_silos, extras={"arch": cfg})
