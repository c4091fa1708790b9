"""Reduce a JAX profiler trace to the benchmark's device numbers.

A trace is read into plain lists of :class:`Event` (name, start and end
in nanoseconds on one clock; the TPU planes of a JAX trace share the
host's clock):

* per device, the ``XLA Ops`` line (operations, nested inside the
  while-loops that contain them), the ``Async XLA Ops`` line (the spans
  of asynchronous copies and collectives) and the ``XLA Modules`` line
  (one event per program execution);
* every host event with a name (``TraceAnnotation`` spans and the
  runtime's own, such as ``CommonPjRtBuffer::ToLiteral``).

The window is the host span named ``bench.window``; busy time is the
union of operation intervals inside it, averaged over the devices.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import itertools
import re
import shutil
import sys
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
_OP_NAME = re.compile(r"^%?([^ =]+)(?: = (\S+?)(?:\{|\s|$))?")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


ASYNC = "async-collective"


def async_collective_spans(ops: Sequence["Event"]) -> List[Tuple[float, float]]:
    """From each ``async-collective-start`` to the next
    ``async-collective-done``: the span in which a collective that the
    TPU compiler split into start, overlapped continuation and done
    fusions is in flight. (A v5e compiles the silo gather so; the HLO
    names the all-gather only inside the fusions, which a trace does
    not show.)"""
    out, starts = [], collections.deque()
    for e in sorted(ops, key=lambda e: e.start):
        name = e.name.lstrip("%")
        if name.startswith(ASYNC + "-start"):
            starts.append(e.start)
        elif name.startswith(ASYNC + "-done") and starts:
            out.append((starts.popleft(), e.end))
    return out


def collective_kind(hlo: str) -> Optional[str]:
    """The collective an HLO op (or its async start/done) is, or None."""
    name = hlo.lstrip("%").split(" ", 1)[0]
    for kind in COLLECTIVES:
        if name.startswith(kind) or f" {kind}(" in hlo or \
                f" {kind}-start(" in hlo or f" {kind}-done(" in hlo:
            return kind
    return None


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]       # device -> XLA Ops events
    modules: Dict[str, List[Event]]   # device -> XLA Modules events
    host: List[Event]                 # named host events, any thread
    async_ops: Dict[str, List[Event]] = dataclasses.field(
        default_factory=dict)         # device -> Async XLA Ops events


def load(log_dir: str) -> Trace:
    """Read the ``.xplane.pb`` that ``jax.profiler.trace(log_dir)`` wrote."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(files[-1])
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    async_ops: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            for line in plane.lines:
                target = {"XLA Ops": ops, "XLA Modules": modules,
                          "Async XLA Ops": async_ops}.get(line.name)
                if target is not None:
                    target[plane.name] = [
                        Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns,
                                  e.start_ns + e.duration_ns)
                            for e in line.events if not e.name.startswith("$"))
    return Trace(ops=ops, modules=modules, host=host, async_ops=async_ops)


def reduce_dir(log_dir: str) -> Optional["Reduced"]:
    """Load, reduce and delete a trace directory; None (with a note on
    standard error) where the trace holds no device operation."""
    try:
        return reduce(load(log_dir))
    except (ValueError, FileNotFoundError) as e:
        print(f"[trace] nothing to read: {e}", file=sys.stderr, flush=True)
        return None
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted, disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy, lo: float, hi: float) -> List[Tuple[float, float]]:
    """The complement of disjoint sorted ``busy`` inside [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Exclusive time per op name: nested children are subtracted."""
    out: Dict[str, float] = collections.defaultdict(float)
    stack: List[List] = []  # [event, child time]
    for ev in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= ev.start:
            done, child = stack.pop()
            out[short_name(done.name)] += done.dur - child
        if stack:
            stack[-1][1] += min(ev.end, stack[-1][0].end) - ev.start
        stack.append([ev, 0.0])
    while stack:
        done, child = stack.pop()
        out[short_name(done.name)] += done.dur - child
    return out


def short_name(hlo: str) -> str:
    """``%fusion.9 = f32[64,937]{...} fusion(...)`` -> ``fusion.9 f32[64,937]``."""
    m = _OP_NAME.match(hlo)
    if not m:
        return hlo[:80]
    return m.group(1) + (f" {m.group(2)}" if m.group(2) else "")


@dataclasses.dataclass
class Reduced:
    """The numbers of one traced window."""

    window_s: float
    busy_s: float                          # mean over devices
    module_s: Dict[str, float]             # program name -> device seconds
    module_n: Dict[str, int]               # program name -> executions
    between_s: Dict[str, float]            # program -> idle between runs
    op_self_s: Dict[str, float]            # op -> exclusive seconds
    idle_by_host: Dict[str, float]         # host activity -> idle seconds
    devices: int
    collective_s: Dict[str, float] = dataclasses.field(
        default_factory=dict)              # collective -> seconds in flight

    def program(self, match: str) -> Tuple[float, int, float]:
        """(device seconds, executions, idle seconds between consecutive
        executions) of the programs whose name contains ``match``."""
        names = [k for k in self.module_s if match in k]
        return (sum(self.module_s[k] for k in names),
                sum(self.module_n[k] for k in names),
                sum(self.between_s[k] for k in names))

    def breakdown(self) -> Dict[str, list]:
        top_ops = sorted(self.op_self_s.items(), key=lambda kv: -kv[1])[:10]
        top_idle = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top_ops],
                "idle_gaps": [[k, v] for k, v in top_idle]}


def _program_name(name: str) -> str:
    return name.split("(")[0]


def reduce(trace: Trace, window: Optional[Tuple[float, float]] = None) -> Reduced:
    """Busy, idle, per-program and per-op time inside the window."""
    if window is None:
        spans = [e for e in trace.host if e.name == WINDOW]
        if not spans:
            raise ValueError(f"no {WINDOW!r} span in the trace")
        window = (spans[0].start, spans[0].end)
    lo, hi = window
    devices = sorted(trace.ops)
    if not devices:
        raise ValueError("no device operations in the trace")
    busy_total = 0.0
    module_s: Dict[str, float] = collections.defaultdict(float)
    module_n: Dict[str, int] = collections.defaultdict(int)
    between_s: Dict[str, float] = collections.defaultdict(float)
    op_self: Dict[str, float] = collections.defaultdict(float)
    idle_host: Dict[str, float] = collections.defaultdict(float)
    coll: Dict[str, float] = collections.defaultdict(float)
    index = _HostIndex([e for e in trace.host if e.name != WINDOW])
    for dev in devices:
        ops = [e for e in trace.ops[dev] if e.end > lo and e.start < hi]
        busy = union(clip([(e.start, e.end) for e in ops], lo, hi))
        busy_total += length(busy)
        for k, v in self_times(ops).items():
            op_self[k] += v / len(devices)
        by_prog: Dict[str, List[Event]] = collections.defaultdict(list)
        for m in trace.modules.get(dev, []):
            if m.start >= lo and m.end <= hi:
                by_prog[_program_name(m.name)].append(m)
        for prog, runs in by_prog.items():
            runs.sort(key=lambda e: e.start)
            module_s[prog] += sum(r.dur for r in runs) / len(devices)
            module_n[prog] += len(runs) if dev == devices[0] else 0
            idle = 0.0
            for a, b in zip(runs, runs[1:]):
                idle += length(gaps(clip(busy, a.end, b.start), a.end, b.start))
            between_s[prog] += idle / len(devices)
        for g0, g1 in gaps(busy, lo, hi):
            idle_host[index.activity(g0, g1)] += (g1 - g0) / len(devices)
        spans = collections.defaultdict(list)
        for e in ops + trace.async_ops.get(dev, []):
            kind = collective_kind(e.name)
            if kind:
                spans[kind].append((e.start, e.end))
        spans[ASYNC] = async_collective_spans(ops)
        for kind, iv in spans.items():
            coll[kind] += length(union(clip(iv, lo, hi))) / len(devices)
    return Reduced(
        window_s=(hi - lo) * 1e-9, busy_s=busy_total / len(devices) * 1e-9,
        module_s={k: v * 1e-9 for k, v in module_s.items()},
        module_n=dict(module_n),
        between_s={k: v * 1e-9 for k, v in between_s.items()},
        op_self_s={k: v * 1e-9 for k, v in op_self.items()},
        idle_by_host={k: v * 1e-9 for k, v in idle_host.items()},
        devices=len(devices),
        collective_s={k: v * 1e-9 for k, v in coll.items()})


class _HostIndex:
    """Find the innermost named host event overlapping most of a gap."""

    LONG = 1e6  # events over 1 ms are scanned in full; few exist

    def __init__(self, host: Sequence[Event]):
        self.long = [e for e in host if e.dur > self.LONG]
        self.short = sorted((e for e in host if e.dur <= self.LONG),
                            key=lambda e: e.start)
        self.starts = [e.start for e in self.short]

    def activity(self, g0: float, g1: float) -> str:
        """The shortest event covering at least half of the gap, else the
        one that covers most of it."""
        lo = bisect.bisect_left(self.starts, g0 - self.LONG)
        hi = bisect.bisect_left(self.starts, g1)
        best, best_key = "host (no span)", None
        for e in itertools.chain(self.long, self.short[lo:hi]):
            overlap = min(e.end, g1) - max(e.start, g0)
            if overlap <= 0:
                continue
            key = (overlap >= 0.5 * (g1 - g0), -e.dur if overlap >= 0.5 * (g1 - g0)
                   else overlap)
            if best_key is None or key > best_key:
                best, best_key = e.name, key
        return best
