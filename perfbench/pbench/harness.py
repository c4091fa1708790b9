"""The benchmark's entry: find the cell, check the chip, run its driver,
reduce what it recorded to metrics, and print the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by name:

* ``BENCHMARK.json``            cells, metrics, and the configuration files;
* ``perfbench/traffic/<traffic>.json``   the mix; its ``kind`` names the driver;
* ``perfbench/drivers/<kind>.py``        one driver per kind of traffic;
* ``perfbench/models/<model>.py``        data, reference math, FLOP count;
* ``perfbench/metrics/<metric>.py``      one reader per per-layer metric;
* ``perfbench/peaks.json``               published chip peaks by device kind.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import math
import os
import pathlib
import sys
import time
from typing import Any, Dict, Optional

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "perfbench"


class Refused(RuntimeError):
    """The run cannot measure this cell here (no chip, bad cell)."""


def load_module(path: pathlib.Path, name: str):
    if not path.is_file():
        raise Refused(f"missing benchmark file {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(path: pathlib.Path):
    if not path.is_file():
        raise Refused(f"missing benchmark file {path}")
    return json.loads(path.read_text())


@dataclasses.dataclass
class Cell:
    """One workload of BENCHMARK.json with its files read."""

    name: str
    chips: int
    config_name: str
    cfg: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    bench: Dict[str, Any]

    @property
    def model(self):
        return load_module(BENCH / "models" / f"{self.cfg['model']}.py",
                           f"pbench_model_{self.cfg['model']}")


def find_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json "
                      f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "perfbench" / "traffic" / f"{w['traffic']}.json")
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                cfg=cfg, traffic_name=w["traffic"], traffic=traffic,
                bench=bench)


def metrics_for(cell: Cell, trace: bool):
    """The metric entries this cell reports: its end-to-end metrics, or
    with ``trace`` its per-layer ones (by ``workloads``, else by
    ``moves``)."""
    e2e = [m for m in cell.bench["end_to_end"]
           if cell.name in m.get("workloads", [cell.name])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in cell.bench["per_layer"]
            if (cell.name in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


class CompileClock:
    """JAX compile events while active: seconds, and programs lowered."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        self._on = False

    def _listen(self, event: str, duration: float, **_):
        if self._on and event.startswith("/jax/core/compile/"):
            self.seconds += duration
            if event.endswith("jaxpr_to_mlir_module_duration"):
                self.programs += 1

    def __enter__(self):
        import jax

        self._on = True
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax

        self._on = False
        jax.monitoring.unregister_event_duration_listener(self._listen)


@dataclasses.dataclass
class Run:
    """What one run hands to the metric readers and the result line."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t0: float
    devices: list
    peaks: Dict[str, float]
    end_to_end: Dict[str, float] = dataclasses.field(default_factory=dict)
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    spans: Dict[str, list] = dataclasses.field(default_factory=dict)
    reduced: Any = None  # pbench.trace.Reduced of a --trace 1 run
    checks: Dict[str, Dict[str, float]] = dataclasses.field(
        default_factory=dict)
    attempted: int = 0
    failed: int = 0
    memory_peak: int = 0
    detail: Any = None  # what a serving run keeps for the readings

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            math.isfinite(c["value"]) and c["value"] <= c["limit"]
            for c in self.checks.values())


def setup_jax(root: pathlib.Path = ROOT):
    """JAX on the chip, with its persistent cache inside the checkout."""
    import jax

    cache = root / ".jax_cache"
    cache.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # No eviction: an evicting cache reads every entry's access-time file
    # on each write, and one missing file then fails every later write.
    jax.config.update("jax_compilation_cache_max_size", -1)
    return jax


def chip_devices(jax, chips: int, platform: str = "tpu"):
    devs = jax.devices()
    if devs[0].platform != platform:
        raise Refused(f"need a {platform} device, JAX found "
                      f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest device."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def peaks_for(kind: str) -> Dict[str, float]:
    table = load_json(BENCH / "peaks.json")["device_kinds"]
    if kind not in table:
        raise Refused(f"no published peaks for device kind {kind!r}")
    return table[kind]


def result_line(run: Run) -> Dict[str, Any]:
    metrics = {}
    for m in metrics_for(run.cell, run.trace):
        if run.trace:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                                 "pbench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(run)
        else:
            value = run.end_to_end.get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    d0 = run.devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(run.devices), "memory_peak_bytes": run.memory_peak}
    out = {"correct": run.correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": device}
    if run.trace and run.reduced is not None:
        device["busy_s"] = run.reduced.busy_s
        device["window_s"] = run.reduced.window_s
        out["breakdown"] = run.reduced.breakdown()
    out["checks"] = run.checks
    return out


def main(argv, t0: float) -> int:
    ap = argparse.ArgumentParser(prog="perfbench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = find_cell(args.workload)
        src = ROOT / "src"
        if not (src / "repro").is_dir():
            raise Refused("no program under src/ in this checkout")
        sys.path.insert(0, str(src))
        jax = setup_jax()
        devices = chip_devices(jax, cell.chips)
        peaks = peaks_for(devices[0].device_kind)
        d0 = devices[0]
        print(f"[bench] {cell.name}: platform={d0.platform} "
              f"device_kind={d0.device_kind!r} count={len(devices)} "
              f"seed={args.seed} seconds={args.seconds} trace={args.trace}",
              file=sys.stderr, flush=True)
        driver = load_module(BENCH / "drivers" / f"{cell.traffic['kind']}.py",
                             f"pbench_driver_{cell.traffic['kind']}")
    except Refused as e:
        print(f"[bench] refused: {e}", file=sys.stderr, flush=True)
        return 2
    run = Run(cell=cell, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), t0=t0, devices=devices, peaks=peaks)
    driver.run(run)
    line = result_line(run)
    print(f"[bench] set-up {run.end_to_end['setup_s']:.2f} s, comparison "
          f"{run.counters.get('check_s', 0.0):.2f} s", file=sys.stderr,
          flush=True)
    for name, c in run.checks.items():
        print(f"[check] {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


def run_seed(seed: int) -> int:
    """The seed as the program and the reference take it (31 bits)."""
    return int(seed) % (2 ** 31)


def temp_dir(prefix: str) -> str:
    """A fresh directory under TMPDIR (or the checkout when unset)."""
    import tempfile

    base = os.environ.get("TMPDIR") or str(ROOT / ".bench_tmp")
    os.makedirs(base, exist_ok=True)
    return tempfile.mkdtemp(prefix=prefix, dir=base)
