"""The chip benchmark on the CPU, at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest -q tests/chip_benchmark

What a CPU run can check: the trace reduction on a hand-built trace, the
FLOP counters against the dot instructions XLA compiles, the drivers
end to end at tiny widths with the chip check skipped, the control and
the planted faults coming out not correct, the refusals, and that a new
cell needs only new files. No number here is a device measurement.
"""
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "perfbench"))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from pbench import harness  # noqa: E402
from pbench import trace as T  # noqa: E402
from pbench.reference import Reference  # noqa: E402

TRAIN = "hier_bnn-avg-k40-j64"
LDA = "prodlda-sfvi-k25-j3"
SERVE = "hier_bnn-serve-poisson-j64"
TINY_CFG = {"in_dim": 16, "hidden": 8, "num_silos": 4, "train_per_silo": 21,
            "vocab_size": 50, "num_topics": 4, "docs_per_silo": 10}
# On the CPU the program's float32 matmuls run at full precision while
# the reference rounds their operands to bfloat16, as the chip does, so
# sound CPU runs read wider gaps than the chip's limits allow.
CPU_LIMITS = {"loss_rel_gap": 1e-2, "grad_norm_gap": 5e-2,
              "change_norm_gap": 5e-2, "draw_max_gap": 1e-4,
              "predict_max_gap": 5e-2}


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    jax.config.update("jax_enable_compilation_cache", False)
    yield


def tiny_cell(name, limits=True, **traffic):
    cell = harness.find_cell(name)
    cell.cfg.update(TINY_CFG)
    cell.traffic.update({"local_steps": 4, "rate_per_s": 40.0,
                         "warmup_seconds": 0.5, "n_values": [1, 4],
                         "n_probs": [0.5, 0.5], "trace_seconds": 1.0})
    cell.traffic.update(traffic)
    if limits:
        lim = cell.traffic["check"]["limits"]
        cell.traffic["check"] = dict(
            cell.traffic["check"],
            limits={k: CPU_LIMITS[k] for k in lim})
    return cell


def drive(cell, seed=2 ** 31 + 11, seconds=1.0, trace=False):
    run = harness.Run(cell=cell, seed=seed, seconds=seconds, trace=trace,
                      t0=time.perf_counter(),
                      devices=jax.devices("cpu")[:cell.chips],
                      peaks={"bf16_flops": 1e12})
    kind = cell.traffic["kind"]
    drv = harness.load_module(harness.BENCH / "drivers" / f"{kind}.py",
                              f"test_driver_{kind}")
    drv.run(run)
    return run


# -- trace reduction ----------------------------------------------------------


def _ev(name, start, dur):
    return T.Event(name, float(start), float(start + dur))


def hand_trace():
    """Two rounds of 100 ns with a nested op, 50 ns of host work between."""
    dev = "/device:TPU:0"
    ops = [_ev("%while.1 = (s32[]) while(...)", 100, 100),
           _ev("%fusion.2 = f32[8,8]{1,0} fusion(...)", 120, 60),
           _ev("%while.1 = (s32[]) while(...)", 250, 100),
           _ev("%fusion.2 = f32[8,8]{1,0} fusion(...)", 260, 40),
           _ev("%copy.3 = f32[4]{0} copy(...)", 380, 10)]
    mods = [_ev("jit_round_fn(1)", 100, 100), _ev("jit_round_fn(1)", 250, 100),
            _ev("jit_fold_in(2)", 380, 10)]
    host = [_ev("bench.window", 50, 400), _ev("bench.experiment_run", 60, 300),
            _ev("np.asarray(jax.Array)", 205, 40)]
    return T.Trace(ops={dev: ops}, modules={dev: mods}, host=host)


def test_trace_busy_idle_and_programs():
    r = T.reduce(hand_trace())
    assert r.window_s == pytest.approx(400e-9)
    assert r.busy_s == pytest.approx(210e-9)  # union: 100 + 100 + 10
    dev_s, n, between = r.program("round_fn")
    assert n == 2 and dev_s == pytest.approx(200e-9)
    assert between == pytest.approx(50e-9)  # 200..250 idle
    assert r.program("fold_in")[1] == 1


def test_trace_self_time_and_attribution():
    r = T.reduce(hand_trace())
    ops = dict(r.breakdown()["device_ops"])
    assert ops["while.1 (s32[])"] == pytest.approx(100e-9)  # 200 - 100 nested
    assert ops["fusion.2 f32[8,8]"] == pytest.approx(100e-9)
    idle = dict(r.breakdown()["idle_gaps"])
    # 50..100 and 350..380 inside the chunk; 200..250 mostly in the pull;
    # 390..450 after the chunk ended.
    assert idle["np.asarray(jax.Array)"] == pytest.approx(50e-9)
    assert idle["bench.experiment_run"] == pytest.approx(80e-9)
    assert idle["host (no span)"] == pytest.approx(60e-9)
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s)


def test_trace_all_gather_time():
    """Sync ops and async spans of one all-gather count once, per chip."""
    tr = hand_trace()
    ag = "%all-gather-start.1 = (f32[16,8]{1,0}, f32[64,8]{1,0}) all-gather-start(...)"
    done = "%all-gather-done.1 = f32[64,8]{1,0} all-gather-done(...)"
    tr.ops["/device:TPU:0"] += [_ev(done, 170, 20)]
    tr.async_ops = {"/device:TPU:0": [_ev(ag, 150, 30)]}
    tr.ops["/device:TPU:1"] = list(tr.ops["/device:TPU:0"][:2])
    tr.async_ops["/device:TPU:1"] = [_ev(ag, 300, 10)]
    r = T.reduce(tr)
    assert r.collective_s["all-gather"] == pytest.approx((40 + 10) / 2 * 1e-9)
    assert "async-collective" not in r.collective_s or \
        r.collective_s["async-collective"] == 0
    # The v5e form: start, an overlapped continuation fusion, done.
    split = hand_trace()
    split.ops["/device:TPU:0"] += [
        _ev("%async-collective-start = (f32[16,8]) fusion(...)", 200, 5),
        _ev("%fusion.132 = (f32[16,8]) fusion(...)", 210, 20),
        _ev("%async-collective-done = f32[64,8] fusion(...)", 240, 6)]
    r = T.reduce(split)
    assert r.collective_s["async-collective"] == pytest.approx(46e-9)
    assert T.collective_kind("%fusion.2 = f32[8,8]{1,0} fusion(...)") is None
    assert T.collective_kind(
        "%all-reduce.3 = f32[] all-reduce(f32[] %x)") == "all-reduce"


def test_trace_without_device_reads_nothing(tmp_path):
    assert T.reduce_dir(str(tmp_path)) is None


# -- FLOP counters --------------------------------------------------------------

_DEF = re.compile(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]")


def dot_flops(hlo_text):
    """2 x output elements x contracted length, over every dot of the
    optimised HLO (operand shapes looked up by name)."""
    shapes, dots = {}, []
    for line in hlo_text.splitlines():
        m = _DEF.match(line)
        if not m:
            continue
        dims = [int(d) for d in m.group(2).split(",") if d]
        shapes[m.group(1)] = dims
        if " dot(" in line:
            lhs = re.search(r" dot\(%([^,)\s]+)", line).group(1)
            contract = re.search(r"lhs_contracting_dims=\{([\d,]*)\}",
                                 line).group(1)
            dots.append((dims, lhs, [int(c) for c in contract.split(",") if c]))
    return sum(2 * int(np.prod(out)) * int(np.prod([shapes[lhs][c] for c in cs]))
               for out, lhs, cs in dots)


@pytest.mark.parametrize("name", [TRAIN, LDA])
def test_flop_counter_matches_compiled_dots(name):
    cell = tiny_cell(name)
    cfg, model = cell.cfg, cell.model
    problem = model.program_model(cfg)
    key = jax.random.PRNGKey(0)
    data = jax.tree_util.tree_map(lambda x: x[0], model.make_data(key, cfg))
    theta, eta_G, eta_L = model.make_init(key, cfg)
    z_G = eta_G["mu"]
    z_L = jax.tree_util.tree_map(lambda x: x[0], eta_L)[model.LOCAL_MEAN]

    def step(theta, z_G, z_L):
        return jax.grad(problem.model.log_local, argnums=(0, 1, 2))(
            theta, z_G, z_L, data)

    compiled = jax.jit(step).lower(theta, z_G, z_L).compile()
    counted = model.matmul_flops_per_silo_step(cfg, model.rows_per_silo(cfg))
    assert dot_flops(compiled.as_text()) == counted
    total = float(compiled.cost_analysis()["flops"])
    # The rest of cost_analysis's count is elementwise work (relu, exp,
    # log-softmax, the priors), which the counter leaves out: on the CPU
    # backend hier_bnn 16-8-10 x 21 rows reads 24,174 FLOPs against
    # 20,832 of matmul, ProdLDA 4 x 50 x 10 documents 15,010 against 12,000.
    assert counted < total < 1.5 * counted


# -- drivers end to end -----------------------------------------------------------

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.mark.parametrize("name,trace", [(TRAIN, False), (LDA, True)])
def test_train_driver_tiny(name, trace):
    run = drive(tiny_cell(name), trace=trace)
    line = harness.result_line(run)
    assert list(line)[:5] == CONTRACT_KEYS[:5] and list(line)[-1] == "checks"
    assert run.correct, run.checks
    assert run.attempted > 0 and run.failed == 0
    if trace:
        assert "compile_s" in line["metrics"]
        assert line["metrics"]["compiles_in_window.train"]["value"] == 0
    else:
        assert set(line["metrics"]) == {"rounds_per_s", "setup_s"}
    assert json.loads(json.dumps(line)) == line


def serve_cell(**kw):
    cell = tiny_cell(TRAIN, limits=False)
    cell.name = SERVE
    cell.traffic = harness.find_cell(SERVE).traffic
    cell.traffic.update({"rate_per_s": 40.0, "warmup_seconds": 0.5,
                         "n_values": [1, 4], "n_probs": [0.5, 0.5],
                         "trace_seconds": 1.0, **kw})
    lim = cell.traffic["check"]["limits"]
    cell.traffic["check"] = dict(cell.traffic["check"],
                                 limits={k: CPU_LIMITS[k] for k in lim})
    return cell


def test_serve_driver_tiny():
    cell = serve_cell()
    run = drive(cell, seconds=1.5)
    line = harness.result_line(run)
    assert list(line)[-1] == "checks"
    assert run.correct, run.checks
    assert run.failed == 0 and run.attempted > 20
    assert set(line["metrics"]) == {"query_p95_ms", "queries_per_s", "setup_s"}
    assert 0 < line["metrics"]["query_p95_ms"]["value"] < 1e3
    assert run.counters["widest_group"] <= cell.traffic["warm_group_queries"]
    assert run.counters["compiles_in_window"] == 0


def test_serve_groups_match_the_reference():
    """Queries of one (kind, silo) group share one draw of their summed
    n; the reference lays each out as ``answer_batch`` serves it."""
    drv = harness.load_module(harness.BENCH / "drivers" / "serve.py", "grp")
    cell = serve_cell()
    run = harness.Run(cell=cell, seed=5, seconds=1.0, trace=False,
                      t0=time.perf_counter(), devices=jax.devices("cpu")[:1],
                      peaks={})
    cell.traffic["warm_group_queries"] = 1
    cell.traffic["warmup_seconds"] = 0.1
    eta_G, eta_L, pool_arr, pool, post = drv.prepare(run, jax.random.PRNGKey(5))
    kinds = [("sample", 1, 4, None), ("global_sample", None, 1, None),
             ("sample", 1, 1, None), ("predict", 2, 4, 3),
             ("sample", 0, 4, None), ("global_sample", None, 4, None),
             ("sample", 1, 4, None)]
    batch = [drv.Item(i, 0.0, k, s, n, x) for i, (k, s, n, x) in enumerate(kinds)]
    answers = post.answer_batch([drv.query(it, pool) for it in batch], seed=77)
    assert drv.lay_out(batch) == 3
    assert [(it.total, it.off) for it in batch] == [
        (9, 0), (5, 0), (9, 4), (4, 0), (4, 0), (5, 1), (9, 5)]
    for it, a in zip(batch, answers):
        it.batch_seed, it.answer = 77, a
    checks = drv.compare_sample(cell.model, cell.cfg, cell.traffic, eta_G,
                                eta_L, pool_arr, batch, 0)
    assert checks["draw_max_gap"]["value"] <= CPU_LIMITS["draw_max_gap"]
    assert checks["predict_max_gap"]["value"] <= CPU_LIMITS["predict_max_gap"]
    for it in batch:  # a draw of the query's own n alone is another draw
        it.total, it.off = it.n, 0
    checks = drv.compare_sample(cell.model, cell.cfg, cell.traffic, eta_G,
                                eta_L, pool_arr, batch, 0)
    assert checks["draw_max_gap"]["value"] > 0.1


def test_schedule_same_work_for_every_seed():
    drv = harness.load_module(harness.BENCH / "drivers" / "serve.py", "sched")
    tr = harness.find_cell(SERVE).traffic
    a = drv.schedule(tr, 64, 20.0)
    again = drv.schedule(tr, 64, 20.0)
    key = lambda it: (it.due, it.kind, it.n, it.silo, it.x)  # noqa: E731
    assert len(a) > 10 * tr["rate_per_s"]
    assert [key(x) for x in again] == [key(x) for x in a]
    assert {it.kind for it in a} == set(tr["mix"])
    warm = drv.schedule(tr, 64, 20.0, stream=1)
    assert [i.due for i in warm] != [i.due for i in a]


# -- the control and the faults come out not correct ----------------------------


@pytest.mark.parametrize("name", [TRAIN, LDA])
def test_control_fails_the_committed_limits(name):
    from pbench import compare

    cell = tiny_cell(name, limits=False)
    cfg, tr, model = cell.cfg, cell.traffic, cell.model
    key = jax.random.PRNGKey(3)
    data = model.make_data(jax.random.fold_in(key, 1), cfg)
    init = model.make_init(jax.random.fold_in(key, 2), cfg)
    args = (tr["algorithm"], init, data, 3, tr["check"]["rounds"],
            tr["local_steps"], tr["optimizer"]["learning_rate"])
    ref = Reference(model, cfg).run(*args)
    low = Reference(model, cfg, dtype=jnp.bfloat16).run(*args)
    th, eg, el = init
    init_h = jax.tree_util.tree_map(np.asarray, {"theta": th, "eta_G": eg,
                                                 "eta_L": el})
    gaps = compare.training_gaps(low, ref, init_h)
    lim = tr["check"]["limits"]
    assert any(gaps[k] > lim[k] for k in lim), (gaps, lim)


def test_serving_control_fails_the_committed_limits():
    drv = harness.load_module(harness.BENCH / "drivers" / "serve.py", "ctl")
    cell = serve_cell()
    run = drive(cell, seconds=1.0)
    d = run.detail
    lim = harness.find_cell(SERVE).traffic["check"]["limits"]
    low = drv.reference_answers(cell.model, cell.cfg, d["eta_G"], d["eta_L"],
                                d["pool"], d["checked"], jnp.bfloat16)
    tr = dict(cell.traffic, check={"sample": 50, "limits": lim})
    checks = drv.compare_sample(cell.model, cell.cfg, tr, d["eta_G"],
                                d["eta_L"], d["pool"], d["checked"], 0,
                                answers=low)
    assert any(c["value"] > c["limit"] for c in checks.values()), checks


def test_fault_state_left_unchanged(monkeypatch):
    from repro.federated import runtime

    real = runtime.Server._get_round

    def frozen(self, algorithm, local_steps):
        fn = real(self, algorithm, local_steps)
        return lambda state, *a: (state, fn(state, *a)[1])

    monkeypatch.setattr(runtime.Server, "_get_round", frozen)
    run = drive(tiny_cell(TRAIN))
    assert not run.correct
    assert run.checks["change_norm_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("name", [TRAIN, LDA])
def test_fault_half_batch(monkeypatch, name):
    from repro.federated import runtime

    real = runtime.stack_silos

    def half(datas):
        def leaf(x):
            n = x.shape[0]
            return x[jnp.arange(n) % ((n + 1) // 2)]
        return real([jax.tree_util.tree_map(leaf, d) for d in datas])

    monkeypatch.setattr(runtime, "stack_silos", half)
    run = drive(tiny_cell(name))
    assert not run.correct, run.checks


def test_fault_answer_altered(monkeypatch):
    from repro.federated import serve

    real = serve.Posterior.sample

    def altered(self, *a, **k):
        out = real(self, *a, **k)
        return {"z_G": out["z_G"], "z_L": out["z_L"] * 1.001}

    monkeypatch.setattr(serve.Posterior, "sample", altered)
    run = drive(serve_cell(), seconds=1.0)
    assert not run.correct, run.checks


def test_fault_half_of_each_call_answered(monkeypatch):
    from repro.federated import serve

    real = serve.Posterior.answer_batch

    def half(self, queries, seed=0):
        return real(self, queries, seed=seed)[: len(queries) // 2]

    monkeypatch.setattr(serve.Posterior, "answer_batch", half)
    cell = serve_cell(drain_seconds=0.5, rate_per_s=80.0)
    run = drive(cell, seconds=1.0)
    assert not run.correct, run.checks
    assert run.checks["missing"]["value"] > 0


# -- refusals and data-driven cells ---------------------------------------------


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_refuses_without_a_tpu():
    out = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", TRAIN, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=_env(), cwd=ROOT, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "need a tpu device" in out.stderr


def test_refuses_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for path in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", TRAIN, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=_env(), cwd=tmp_path, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_a_new_cell_needs_only_new_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    traffic = json.loads(
        (tmp_path / "perfbench/traffic/sfvi_avg_k40_full.json").read_text())
    traffic["local_steps"] = 10
    (tmp_path / "perfbench/traffic/sfvi_avg_k10_full.json").write_text(
        json.dumps(traffic))
    (tmp_path / "perfbench/metrics/new_metric.train.py").write_text(
        "def read(run):\n    return run.counters.get('rounds_in_window')\n")
    bench["workloads"].append({"name": "hier_bnn-avg-k10-j64",
                               "config": "hier_bnn_784x64_j64",
                               "traffic": "sfvi_avg_k10_full", "chips": 1,
                               "why": "a cell added by files alone"})
    bench["end_to_end"][0]["workloads"].append("hier_bnn-avg-k10-j64")
    bench["per_layer"].append({"name": "new_metric.train", "unit": "count",
                               "better": "higher", "source": "program_counter",
                               "layer": "host control plane",
                               "moves": "rounds_per_s",
                               "workloads": ["hier_bnn-avg-k10-j64"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    script = (
        "import sys; sys.path.insert(0, 'perfbench')\n"
        "from pbench import harness\n"
        "c = harness.find_cell('hier_bnn-avg-k10-j64')\n"
        "assert c.traffic['local_steps'] == 10\n"
        "names = [m['name'] for m in harness.metrics_for(c, True)]\n"
        "assert 'new_metric.train' in names, names\n"
        "for n in names:\n"
        "    harness.load_module(harness.BENCH / 'metrics' / (n + '.py'), n)\n"
        "print('OK')\n")
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=_env(), cwd=tmp_path, timeout=120)
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr


def test_benchmark_json_is_whole():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"] for w in bench["workloads"]}
    for w in bench["workloads"]:
        cell = harness.find_cell(w["name"])
        assert (harness.BENCH / "drivers" / f"{cell.traffic['kind']}.py").is_file()
        e2e = [m["name"] for m in harness.metrics_for(cell, False)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.metrics_for(cell, True)
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", cells)) <= cells
