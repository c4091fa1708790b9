"""Serving cells: an open-loop stream of posterior queries.

Set-up draws a posterior of the configuration's shapes from the seed
(the benchmark's own eta_G and eta_L, in place of weights; the silos'
data is blank, since no query reads it), writes it with
``Experiment.save`` and restores it through ``Posterior.from_checkpoint``,
as a replica does. It then warms every group a call can form: for each
kind of the mix, one ``answer_batch`` call per multiset of up to
``warm_group_queries`` of the mix's ``n`` values, which compiles the
sampler of every summed ``n`` and the slices of it; then the mix's own
traffic for ``warmup_seconds``, open-loop, from a stream disjoint from
the window's.

The window offers queries at the times a Poisson process fixed in the
traffic file gives: the arrival times and the sequence of queries are
drawn from the traffic's ``schedule_seed``, so every seed does the same
work in the same order (a shuffled order moved the tail by half from
seed to seed); ``--seed`` makes the posterior and the inputs. One thread
serves: it hands every query that is due and not yet served to one
``answer_batch`` call, and waits for the answers. A query's latency runs
from the time it was due to the time its answer is ready; queries due in
the window and unanswered ``drain_seconds`` after it count as missing.

A sample of the window's queries, drawn from the seed with the largest
ones in it, keeps its answers (the others are dropped once ready, as a
server hands them off); after the window the plain reference
(pbench/serve_reference.py) recomputes each and they are compared.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import itertools
import math
import shutil
import time
from typing import List, Optional

import jax
import numpy as np

from pbench import harness
from pbench import trace as tracing
from pbench.serve_reference import gap, group_draw

SEED_BASE = 1_000_000  # a call's seed is SEED_BASE + its first query's id


@dataclasses.dataclass
class Item:
    qid: int
    due: float
    kind: str
    silo: Optional[int]
    n: int
    x: Optional[int]  # index into the input pool (predict)
    start: float = math.nan
    done: float = math.nan
    batch_seed: int = -1
    total: int = 0  # rows of the draw that served it (its group's summed n)
    off: int = 0  # its rows' offset in that draw
    answer: object = None


def posterior_params(key, cfg, tr, model):
    """The posterior served: eta_G and the stacked eta_L, from the key."""
    d = model.dims(cfg)
    J = cfg["num_silos"]
    p = tr["posterior"]

    @jax.jit
    def gen(key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        eta_G = {"mu": p["mu_scale"] * jax.random.normal(k1, (d["global"],)),
                 "log_sigma": p["log_sigma_mean"] + p["log_sigma_spread"]
                 * jax.random.normal(k2, (d["global"],))}
        shape = (J,) + tuple(d["local"])
        eta_L = {model.LOCAL_MEAN: p["mu_scale"] * jax.random.normal(k3, shape),
                 "log_sigma": p["log_sigma_mean"] + p["log_sigma_spread"]
                 * jax.random.normal(k4, shape)}
        return eta_G, eta_L

    return gen(key)


def restore_posterior(run, eta_G, eta_L):
    """Save a federation holding this posterior; restore it as a replica."""
    from repro.federated.api import (ExperimentSpec, ModelSpec, OptimizerSpec,
                                     build)
    from repro.federated.scheduler import Scenario
    from repro.federated.serve import Posterior
    from repro.models.paper.registry import ModelBundle

    cfg, tr, model = run.cell.cfg, run.cell.traffic, run.cell.model
    J = cfg["num_silos"]
    spec = ExperimentSpec(
        model=ModelSpec(cfg["model"], model.registry_kwargs(cfg)),
        scenario=Scenario(algorithm=tr["trained_with"]), num_silos=J,
        rounds=1, server_opt=OptimizerSpec("adam", 0.02),
        seed=harness.run_seed(run.seed))
    blank = model.blank_silo(cfg)
    bundle = ModelBundle(problem=model.program_model(cfg), theta0={},
                         datas=[blank] * J,
                         num_obs=[model.rows_per_silo(cfg)] * J)
    exp = build(spec, bundle)
    exp.server.state["eta_L"] = exp.server.pad_silo_axis(eta_L)
    exp.warm_start(eta_G=eta_G)
    ckpt = harness.temp_dir("posterior_")
    try:
        exp.save(ckpt)
        del exp, bundle
        gc.collect()
        return Posterior.from_checkpoint(ckpt)
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)


def schedule(tr, J, horizon, stream=0) -> List[Item]:
    """The queries due before ``horizon``, from the traffic's
    ``schedule_seed`` and ``stream`` alone."""
    rate = tr["rate_per_s"]
    base = np.random.default_rng([tr["schedule_seed"], stream])
    dues = np.cumsum(base.exponential(
        1.0 / rate, int(math.ceil(rate * horizon * 1.5)) + 64))
    count = int(np.searchsorted(dues, horizon))
    kinds = base.choice(list(tr["mix"]), count, p=list(tr["mix"].values()))
    ns = base.choice(tr["n_values"], count, p=tr["n_probs"])
    zipf = 1.0 / np.arange(1, J + 1) ** tr["zipf_s"]
    silos = base.choice(J, count, p=zipf / zipf.sum())
    xs = base.integers(0, tr["input_pool"], count)
    return [Item(qid=i, due=float(dues[i]), kind=str(kinds[i]),
                 silo=None if kinds[i] == "global_sample" else int(silos[i]),
                 n=int(ns[i]), x=int(xs[i]) if kinds[i] == "predict" else None)
            for i in range(count)]


def query(item, pool):
    from repro.federated.serve import Query

    return Query(item.kind, silo=item.silo, n=item.n,
                 x=None if item.x is None else pool[item.x])


def lay_out(batch):
    """The draw that serves each query of one call, as ``answer_batch``
    documents it: a (kind, silo) group of ``sample`` or ``global_sample``
    queries shares one draw of the group's summed ``n``, each query its
    contiguous rows in call order; a ``predict`` query draws its own ``n``.
    Returns the largest group."""
    groups = collections.defaultdict(list)
    for it in batch:
        if it.kind == "predict":
            it.total, it.off = it.n, 0
        else:
            groups[(it.kind, it.silo)].append(it)
    for members in groups.values():
        off = 0
        for it in members:
            it.off = off
            off += it.n
        for it in members:
            it.total = off
    return max((len(m) for m in groups.values()), default=1)


def serve(post, items, pool, seconds, drain, keep=frozenset()):
    """Serve ``items`` open-loop; returns the (start, done) of each call
    and the most queries one (kind, silo) group held. The answers of the
    queries whose ``qid`` is in ``keep`` are kept."""
    pending = []
    calls = []
    widest = 0
    nxt = 0
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        while True:
            now = time.perf_counter() - t0
            while nxt < len(items) and items[nxt].due <= now:
                pending.append(items[nxt])
                nxt += 1
            if not pending:
                if nxt >= len(items) or now > seconds + drain:
                    break
                time.sleep(min(items[nxt].due - now, 0.002))
                continue
            if now > seconds + drain:
                break
            batch, pending = pending, []
            s = SEED_BASE + batch[0].qid
            start = time.perf_counter() - t0
            with jax.profiler.TraceAnnotation("bench.answer_batch"):
                answers = post.answer_batch([query(it, pool) for it in batch],
                                            seed=s)
                jax.block_until_ready(answers)
            done = time.perf_counter() - t0
            calls.append((start, done))
            widest = max(widest, lay_out(batch))
            for it, a in zip(batch, answers):
                it.start, it.done, it.batch_seed = start, done, s
                if it.qid in keep:
                    it.answer = a
    return calls, widest


def percentile(values, q):
    """Nearest-rank percentile."""
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)]


def warm_groups(post, pool, tr):
    """One call per multiset of up to ``warm_group_queries`` of the mix's
    ``n`` values, for each kind that shares a group's draw (one query of
    each ``n`` for ``predict``)."""
    for kind in tr["mix"]:
        sizes = [1] if kind == "predict" else \
            range(1, tr["warm_group_queries"] + 1)
        for size in sizes:
            for ns in itertools.combinations_with_replacement(
                    tr["n_values"], size):
                batch = [Item(0, 0.0, kind,
                              None if kind == "global_sample" else 0, n,
                              0 if kind == "predict" else None) for n in ns]
                jax.block_until_ready(
                    post.answer_batch([query(it, pool) for it in batch]))


def prepare(run, key):
    """Posterior, query inputs, and every query shape of the mix warm."""
    cfg, tr, model = run.cell.cfg, run.cell.traffic, run.cell.model
    eta_G, eta_L = posterior_params(jax.random.fold_in(key, 3), cfg, tr, model)
    pool_arr = model.make_inputs(jax.random.fold_in(key, 4), cfg,
                                 tr["input_pool"], tr["predict_rows"])
    pool = [pool_arr[i] for i in range(tr["input_pool"])]
    post = restore_posterior(run, eta_G, eta_L)
    warm_groups(post, pool, tr)
    warm = schedule(tr, cfg["num_silos"], tr["warmup_seconds"], stream=1)
    serve(post, warm, pool, tr["warmup_seconds"], tr["drain_seconds"])
    return eta_G, eta_L, pool_arr, pool, post


def run(run):
    cell, cfg, tr = run.cell, run.cell.cfg, run.cell.traffic
    model = cell.model
    seed = harness.run_seed(run.seed)
    key = jax.random.PRNGKey(seed)
    with harness.CompileClock() as setup_clock:
        eta_G, eta_L, pool_arr, pool, post = prepare(run, key)
    run.counters["compile_s"] = setup_clock.seconds
    run.counters["programs_warmed"] = setup_clock.programs
    seconds = min(run.seconds, tr["trace_seconds"]) if run.trace else run.seconds
    items = schedule(tr, cfg["num_silos"], seconds)
    chosen = sample_checked(items, seed, tr["check"]["sample"])
    keep = frozenset(it.qid for it in chosen)
    trace_dir = harness.temp_dir("trace_") if run.trace else None
    drain = tr["drain_seconds"]
    run.end_to_end["setup_s"] = time.perf_counter() - run.t0
    with harness.CompileClock() as window_clock:
        if trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            with jax.profiler.trace(trace_dir, profiler_options=opts):
                calls, widest = serve(post, items, pool, seconds, drain, keep)
        else:
            calls, widest = serve(post, items, pool, seconds, drain, keep)
    run.memory_peak = harness.memory_peak(run.devices)
    # A query never answered waited at least until the drain ended.
    lat = [(it.done if not math.isnan(it.done) else seconds + drain) - it.due
           for it in items]
    answered = [it for it in items if not math.isnan(it.done)]
    in_window = sum(1 for it in answered if it.done <= seconds)
    run.attempted = len(items)
    run.failed = len(items) - len(answered)
    run.end_to_end["query_p95_ms"] = percentile(lat, 0.95) * 1e3
    run.end_to_end["queries_per_s"] = in_window / seconds
    run.counters["compiles_in_window"] = window_clock.programs
    run.counters["widest_group"] = widest
    run.spans["wait_s"] = [it.start - it.due for it in answered]
    run.spans["call_s"] = [b - a for a, b in calls]
    if trace_dir:
        run.reduced = tracing.reduce_dir(trace_dir)
    del post
    gc.collect()
    t = time.perf_counter()
    checked = [it for it in chosen if not math.isnan(it.done)]
    run.detail = {"eta_G": eta_G, "eta_L": eta_L, "pool": pool_arr,
                  "checked": checked}
    run.checks = compare_sample(model, cfg, tr, eta_G, eta_L, pool_arr,
                                checked, run.failed)
    run.counters["check_s"] = time.perf_counter() - t


def sample_checked(items, seed, size):
    """A sample drawn from the seed, with the largest queries in it."""
    if len(items) <= size:
        return list(items)
    by_n = sorted(items, key=lambda it: (-it.n, it.qid))
    head = by_n[: size // 4]
    taken = {it.qid for it in head}
    rest = [it for it in items if it.qid not in taken]
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(rest), size - len(head), replace=False)
    return head + [rest[i] for i in sorted(pick)]


def reference_answers(model, cfg, eta_G, eta_L, pool, checked, dtype):
    """The reference's answer to each checked query: one draw per group
    (kind, silo, call seed, summed n), each query's rows sliced from it."""
    out = {}
    for it in checked:
        g = (it.kind, it.silo, it.batch_seed, it.total, it.x)
        if g not in out:
            out[g] = group_draw(model, cfg, eta_G, eta_L, pool, it.kind,
                                it.silo, it.total, it.x, it.batch_seed, dtype)
    answers = []
    for it in checked:
        draw = out[(it.kind, it.silo, it.batch_seed, it.total, it.x)]
        if it.kind == "predict":
            answers.append(draw)
        else:
            answers.append({k: None if v is None else v[it.off:it.off + it.n]
                            for k, v in draw.items()})
    return answers


def compare_sample(model, cfg, tr, eta_G, eta_L, pool, checked, missing,
                   answers=None):
    """The gaps of the sampled answers to the float32 reference's.
    ``answers`` stands in for the program's (the control passes the
    reference's own in ``dtype``)."""
    import jax.numpy as jnp

    refs = reference_answers(model, cfg, eta_G, eta_L, pool, checked,
                             jnp.float32)
    if answers is None:
        answers = [it.answer for it in checked]
    draws, preds = [], []
    for it, got, ref in zip(checked, answers, refs):
        if it.kind == "predict":
            preds.append(gap(got, ref))
        else:
            draws.append(max(gap(got[k], ref[k]) for k in ref
                             if ref[k] is not None))
    lim = tr["check"]["limits"]
    return {
        "draw_max_gap": {"value": max(draws, default=math.nan),
                         "limit": lim["draw_max_gap"]},
        "predict_max_gap": {"value": max(preds, default=math.nan),
                            "limit": lim["predict_max_gap"]},
        "missing": {"value": float(missing), "limit": 0.0},
    }
