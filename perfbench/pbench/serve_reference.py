"""The plain reference of a posterior query.

Imports nothing of the program. What it shares with it is the serving
stream the endpoint documents: a call served with ``seed`` draws silo
j's group from ``fold_in(PRNGKey(21479 + seed), j + 1)`` (the global
group uses j = -1), split into the keys of the global and the local
draw; a ``sample`` or ``global_sample`` group draws its summed ``n`` at
once and each query takes its rows in call order; Z_G = mu + sigma *
eps_G, Z_L = mean + sigma * eps_L (the conditional family without
coupling), and a prediction is the mean of the model's logits over its
own n joint draws.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from pbench.reference import make_mm

SERVE_SALT = 0x53E7


@functools.lru_cache(maxsize=None)
def _draw_fn(model, cfg_key, kind, n, dtype):
    cfg = dict(cfg_key)
    mm = make_mm(dtype)
    d = model.dims(cfg)

    def cast(tree):
        return jax.tree_util.tree_map(lambda a: a.astype(dtype), tree)

    @jax.jit
    def draw(eta_G, eta_L, pool, x_idx, seed, silo):
        eta_G = cast(eta_G)
        eta_Lj = cast(jax.tree_util.tree_map(
            lambda a: a[jnp.maximum(silo, 0)], eta_L))
        x = pool[x_idx].astype(dtype)
        key = jax.random.fold_in(jax.random.PRNGKey(SERVE_SALT + seed),
                                 silo + 1)
        k_g, k_l = jax.random.split(key)
        eps_G = jax.random.normal(k_g, (n, d["global"])).astype(dtype)
        z_G = eta_G["mu"] + jnp.exp(eta_G["log_sigma"]) * eps_G
        if kind == "global_sample":
            return {"z_G": z_G, "z_L": None}
        eps_L = jax.random.normal(k_l, (n,) + tuple(d["local"])).astype(dtype)
        z_L = eta_Lj[model.LOCAL_MEAN] + jnp.exp(eta_Lj["log_sigma"]) * eps_L
        if kind == "sample":
            return {"z_G": z_G, "z_L": z_L}
        out = jax.vmap(lambda g, l: model.logits(cfg, g, l, x, mm))(z_G, z_L)
        return jnp.mean(out, axis=0)

    return draw


def group_draw(model, cfg, eta_G, eta_L, pool, kind, silo, n, x_idx, seed,
               dtype=jnp.float32):
    """The reference's draw of ``n`` rows for one group of a call served
    with ``seed`` (or its answer to one ``predict`` query), on the host."""
    fn = _draw_fn(model, tuple(sorted((k, v) for k, v in cfg.items()
                                      if isinstance(v, (int, float, str)))),
                  kind, int(n), dtype)
    out = fn(eta_G, eta_L, pool, 0 if x_idx is None else int(x_idx),
             int(seed), -1 if silo is None else int(silo))
    return jax.tree_util.tree_map(np.asarray, out)


def gap(got, want) -> float:
    """max |got - want| / max |want|, in float32 on the host."""
    g = np.asarray(got).astype(np.float32)
    w = np.asarray(want).astype(np.float32)
    if g.shape != w.shape:
        return float("inf")
    return float(np.max(np.abs(g - w)) / max(float(np.max(np.abs(w))), 1e-30))
