"""The two cells added with Nemotron-3-Nano, on the CPU at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest -q tests/chip_benchmark/test_nemotron_cells.py

``nemotron3-sfvi-k2-j3-s2048`` (the ``train_large`` driver: accumulating
round, sequential reference) and ``hier_bnn-sfvi-k25-j64-silo4`` (the
``train`` driver over a 4-device silo mesh, in a subprocess with four
host devices). No number here is a device measurement.
"""
import json
import os
import pathlib
import re
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

from pbench import compare, harness  # noqa: E402
from pbench import trace as T  # noqa: E402

NEM = "nemotron3-sfvi-k2-j3-s2048"
SILO4 = "hier_bnn-sfvi-k25-j64-silo4"
# Published shapes cut to a CPU size: one block of each kind, the router
# wider than the experts held, several B/C groups and SSD chunks.
TINY = {"hybrid_override_pattern": "ME*", "num_hidden_layers": 3,
        "hidden_size": 32, "vocab_size": 64, "mamba_num_heads": 4,
        "mamba_head_dim": 8, "ssm_state_size": 8, "n_groups": 2,
        "chunk_size": 8, "n_routed_experts": 4, "router_experts": 16,
        "num_experts_per_tok": 3, "moe_intermediate_size": 16,
        "moe_shared_expert_intermediate_size": 24, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 8, "seq_len": 16,
        "bayes": {"global_rank": 2, "local_rank": 2}}
# On the CPU the program's float32 matmuls run at full precision while
# the reference rounds their operands to bfloat16, as the chip does.
CPU_LIMITS = {"loss_rel_gap": 1e-2, "grad_norm_gap": 5e-2,
              "change_norm_gap": 5e-2}


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    jax.config.update("jax_enable_compilation_cache", False)
    yield


def tiny_cell(limits=True, **cfg):
    cell = harness.find_cell(NEM)
    cell.cfg.update(TINY, **cfg)
    cell.traffic["trace_seconds"] = 1.0
    if limits:
        cell.traffic["check"] = dict(cell.traffic["check"], limits=CPU_LIMITS)
    return cell


def drive(cell, seed=2 ** 31 + 29, seconds=1.0, trace=False):
    run = harness.Run(cell=cell, seed=seed, seconds=seconds, trace=trace,
                      t0=time.perf_counter(), devices=jax.devices("cpu")[:1],
                      peaks={"bf16_flops": 1e12})
    drv = harness.load_module(
        harness.BENCH / "drivers" / f"{cell.traffic['kind']}.py",
        "test_driver_" + cell.traffic["kind"])
    drv.run(run)
    return run


@pytest.mark.parametrize("trace", [False, True])
def test_nemotron_driver_tiny(trace):
    run = drive(tiny_cell(), trace=trace)
    line = harness.result_line(run)
    assert run.correct, run.checks
    assert run.attempted > 0 and run.failed == 0
    assert set(run.checks) == set(CPU_LIMITS)
    if trace:  # the CPU's trace holds no device plane to read
        assert set(line["metrics"]) == {"compile_s"}
        assert run.counters["compiles_in_window"] == 0
    else:
        assert set(line["metrics"]) == {"rounds_per_s", "setup_s"}
    assert json.loads(json.dumps(line)) == line


def test_nemotron_control_and_fault_fail_the_committed_limits():
    """The bf16 control and the half-batch fault, each against the float32
    sequential reference, read above one of the cell's committed limits
    (what ``calibrate_large.py`` reads on the chip)."""
    drv = harness.load_module(harness.BENCH / "drivers" / "train_large.py",
                              "test_driver_large")
    cell = tiny_cell(limits=False)
    run = harness.Run(cell=cell, seed=5, seconds=1.0, trace=False,
                      t0=time.perf_counter(), devices=jax.devices("cpu")[:1],
                      peaks={})
    data, _ = drv.inputs(cell.model, cell.cfg, run.seed)
    init_h, ref = drv.reference(run, data)
    lim = cell.traffic["check"]["limits"]
    for kw in ({"dtype": jnp.bfloat16}, {"fault": "half_batch"}):
        gaps = compare.training_gaps(drv.reference(run, data, **kw)[1], ref,
                                     init_h)
        assert any(gaps[k] > lim[k] for k in lim), (kw, gaps, lim)


def test_sequential_reference_is_the_vmapped_reference():
    """The reference laid out for a large θ (silos in turn, donated state)
    computes the run of pbench/reference.py."""
    from pbench.reference import Reference
    from pbench.reference_seq import SequentialReference

    cell = tiny_cell()
    cfg, tr, model = cell.cfg, cell.traffic, cell.model
    key = jax.random.PRNGKey(11)
    data = model.make_data(jax.random.fold_in(key, 1), cfg)
    args = (tr["algorithm"], None, data, 11, 2, tr["local_steps"], 0.01)

    def init():
        return model.make_init(jax.random.fold_in(key, 2), cfg)

    a = Reference(model, cfg).run(args[0], init(), *args[2:])
    b = SequentialReference(model, cfg).run(args[0], init(), *args[2:])
    # Summing silos in turn rounds differently from J x their mean.
    np.testing.assert_allclose(b["elbo"], a["elbo"], rtol=1e-4)
    for k in a["grad0"]:
        assert b["grad0"][k] == pytest.approx(a["grad0"][k], rel=1e-4, abs=1e-6)
    gaps = compare.training_gaps(b, a, jax.tree_util.tree_map(
        np.asarray, dict(zip(("theta", "eta_G", "eta_L"), init()))))
    # Rounding alone: far under any limit a cell could set from its control.
    assert max(gaps.values()) < 1e-3, gaps


_DEF = re.compile(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]")


def dot_flops(hlo_text):
    """2 x output elements x contracted length over every dot."""
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


@pytest.mark.parametrize("pattern", ["M", "*", "M*M"])
def test_flop_counter_matches_compiled_dots(pattern):
    """Mamba-2 (projections and the SSD chunk matmuls), attention (the
    projections and the full S x S scores) and the head, forward and
    backward, against the dots XLA compiles for the program's stack
    without remat. The routed experts are counted at their expected held
    load, which no compiled graph shows: that term is checked apart."""
    from repro.models.backbone import bayes, transformer

    cell = tiny_cell(hybrid_override_pattern=pattern,
                     num_hidden_layers=len(pattern))
    cfg, model = cell.cfg, cell.model
    acfg = model.program_arch(cfg)
    rows = model.rows_per_silo(cfg)
    key = jax.random.PRNGKey(0)
    theta = model.make_init(key, cfg)[0]
    tokens = model.make_data(key, cfg)["tokens"][0]
    n_G, n_L = bayes.latent_dims(acfg)
    z_G, z_L = jnp.zeros((n_G,)), jnp.zeros((n_L,))

    def ll(theta, z_G, z_L):
        base, _, h = transformer.forward(theta, acfg,
                                         {"tokens": tokens[:, :-1]},
                                         remat=False)
        return bayes.silo_log_lik(acfg, base, h, z_G, z_L, tokens[:, 1:])

    step = jax.grad(ll, argnums=(0, 1, 2))
    compiled = jax.jit(step).lower(theta, z_G, z_L).compile()
    assert dot_flops(compiled.as_text()) == \
        model.matmul_flops_per_silo_step(cfg, rows)


def test_expert_flops_are_the_expected_held_load():
    cell = tiny_cell()
    cfg, model = cell.cfg, cell.model
    a = model.arch(cfg)
    T_ = model.rows_per_silo(cfg) * a["S"]
    per_layer = 3 * T_ * a["k"] * a["E"] / a["R"] * 4 * a["d"] * a["f"]
    two = dict(cfg, hybrid_override_pattern="MEMEM*", num_hidden_layers=6)
    assert model.expert_matmul_flops_per_silo_step(two, 2) == \
        pytest.approx(2 * per_layer)  # MEMEM* has two expert layers
    whole = model.matmul_flops_per_silo_step(two, 2)
    no_experts = model.matmul_flops_per_silo_step(dict(two, n_routed_experts=0), 2)
    assert whole - no_experts == pytest.approx(2 * per_layer)


def test_allgather_ms_reads_the_gather_of_a_hand_built_trace():
    reader = harness.load_module(
        harness.BENCH / "metrics" / "allgather_ms.train.py", "m_allgather")
    dev = "/device:TPU:0"
    ops = [T.Event("%while.1 = (s32[]) while(...)", 100.0, 200.0),
           T.Event("%async-collective-start = (f32[16,8]) fusion(...)", 120, 125),
           T.Event("%fusion.7 = (f32[16,8]) fusion(...)", 125, 140),
           T.Event("%async-collective-done = f32[64,8] fusion(...)", 150, 156),
           T.Event("%while.1 = (s32[]) while(...)", 300.0, 400.0),
           T.Event("%async-collective-start = (f32[16,8]) fusion(...)", 310, 312),
           T.Event("%async-collective-done = f32[64,8] fusion(...)", 330, 334),
           # the ELBO's scalar psum, a synchronous all-reduce on a v5e
           T.Event("%psum.11 = f32[] all-reduce(...)", 160, 170)]
    mods = [T.Event("jit_round_fn(1)", 100, 200), T.Event("jit_round_fn(1)", 300, 400)]
    host = [T.Event("bench.window", 50, 450)]
    red = T.reduce(T.Trace(ops={dev: ops}, modules={dev: mods}, host=host))

    class Run:
        reduced = red
        cell = harness.find_cell(SILO4)

    # (156 - 120) + (334 - 310) ns over 2 rounds
    assert reader.read(Run) == pytest.approx((36 + 24) / 2 * 1e-6)
    Run.reduced = T.reduce(T.Trace(ops={dev: ops[:1] + ops[4:5] + ops[7:]},
                                   modules={dev: mods}, host=host))
    assert reader.read(Run) is None  # no gather in the trace: nothing


def test_expert_roofline_reads_the_ragged_dot_kernels():
    reader = harness.load_module(
        harness.BENCH / "metrics" / "expert_mm_roofline.train.py", "m_expert")
    dev = "/device:TPU:0"
    ops = [T.Event("%ragged-dot-none.5 = f32[24576,2688]{1,0} custom-call(...)",
                   100, 140),
           T.Event("%fusion.3 = f32[8]{0} fusion(...)", 140, 160),
           T.Event("%ragged-dot-none.15 = f32[8,2688,1856]{2,1,0} custom-call(...)",
                   160, 220)]
    mods = [T.Event("jit_round_fn(1)", 100, 220)]
    red = T.reduce(T.Trace(ops={dev: ops}, modules={dev: mods},
                           host=[T.Event("bench.window", 50, 300)]))

    class Run:
        reduced = red
        cell = harness.find_cell(NEM)
        counters = {"expert_flops_per_round": 5e4}
        peaks = {"bf16_flops": 1e12}
        devices = [0]

    assert reader.read(Run) == pytest.approx(100 * 5e4 / 100e-9 / 1e12)
    Run.counters = {}
    assert reader.read(Run) is None


def test_silo4_driver_tiny_on_four_host_devices():
    """The silo4 cell's own traffic (SFVI K=25 over a silo=4 mesh) at tiny
    widths, in a process with four CPU devices."""
    script = r"""
import sys, time, json
sys.path.insert(0, 'perfbench'); sys.path.insert(0, 'src')
import jax
jax.config.update("jax_enable_compilation_cache", False)
from pbench import harness
cell = harness.find_cell('hier_bnn-sfvi-k25-j64-silo4')
cell.cfg.update({"in_dim": 16, "hidden": 8, "num_silos": 8, "train_per_silo": 21})
cell.traffic.update({"local_steps": 3, "trace_seconds": 1.0})
cell.traffic["check"] = dict(cell.traffic["check"], limits={
    "loss_rel_gap": 1e-2, "grad_norm_gap": 5e-2, "change_norm_gap": 5e-2})
run = harness.Run(cell=cell, seed=2**31 + 3, seconds=1.0, trace=False,
                  t0=time.perf_counter(), devices=jax.devices("cpu")[:4],
                  peaks={"bf16_flops": 1e12})
drv = harness.load_module(harness.BENCH / "drivers" / "train.py", "d")
drv.run(run)
print(json.dumps({"correct": run.correct, "checks": run.checks,
                  "devices": len(run.devices)}))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, cwd=ROOT, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["devices"] == 4, line
