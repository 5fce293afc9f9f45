"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics and the result's ``device`` and ``breakdown`` read.

A TPU trace has one plane per chip, ``/device:TPU:<n>``, whose line
``XLA Modules`` holds one event per execution of a compiled program and
whose line ``XLA Ops`` holds one event per HLO operation.  The host plane
``/host:CPU`` holds the benchmark's own annotations (``traffic.py`` and
``harness.py`` name them).  On the CPU (rehearsals only) the operations are
host events that carry an ``hlo_module`` stat; each program execution is
then the span of its operations.  All times are nanoseconds on the trace's
one clock.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

HOST_SPANS = ("window", "segment", "sync", "train_step", "data.batch",
              "maybe_reconfigure", "dmr.check_status", "layout")
COLLECTIVE = re.compile(r"all-reduce|reduce-scatter|all-gather", re.I)
STEP_PROGRAM = re.compile(r"train_step")
CONTROL = re.compile(r"(while|conditional|call)\b")
DEVICE = re.compile(r"/device:TPU:(\d+)\b")

Interval = Tuple[float, float]


@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float
    slices: Optional[int] = None


@dataclasses.dataclass
class Device:
    """One chip's operations in the window, reduced as they are read."""
    busy: List[Interval]              # union of operation intervals
    op_time: Dict[str, float]         # per operation name, ns
    collectives: List[Event]          # all-reduce, reduce-scatter, ...
    modules: List[Event]              # program executions


@dataclasses.dataclass
class Trace:
    devices: Dict[int, Device]
    host: List[Event]

    def spans(self, name: str) -> List[Event]:
        return [e for e in self.host if e.name == name]

    def window(self) -> Interval:
        w = self.spans("window")
        if len(w) != 1:
            raise ValueError(f"the trace holds {len(w)} windows, not 1")
        return w[0].start, w[0].end

    def window_s(self) -> float:
        lo, hi = self.window()
        return (hi - lo) * 1e-9


def _stats(e) -> dict:
    return {k: v for k, v in e.stats}


def short(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


class _Reducer:
    """Folds one chip's operation events, in start order, into a Device."""

    def __init__(self, lo: float, hi: float):
        self.lo, self.hi = lo, hi
        self.busy: List[Interval] = []
        self.op_time: Dict[str, float] = {}
        self.coll: List[Event] = []
        self.unsorted = False

    def device(self, modules: List[Event]) -> Device:
        busy = union(self.busy) if self.unsorted else self.busy
        return Device(busy, self.op_time, self.coll, modules)

    def add(self, name: str, start: float, end: float) -> None:
        if end <= self.lo or start >= self.hi:
            return
        b = self.busy
        if b and start < b[-1][0]:          # out of order: merge at the end
            self.unsorted = True
            b.append((start, end))
        elif b and start <= b[-1][1]:
            if end > b[-1][1]:
                b[-1] = (b[-1][0], end)
        else:
            b.append((start, end))
        op = short(name)
        if not CONTROL.match(op):
            self.op_time[op] = self.op_time.get(op, 0.0) + (end - start)
        if COLLECTIVE.search(op):
            self.coll.append(Event(op, start, end))


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    tpu = any(DEVICE.match(p.name) for p in planes)
    host: List[Event] = []
    cpu_ops: Dict[int, list] = {}
    for plane in planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            scan_ops = not tpu and not line.name.startswith("tf_XLAEigen")
            for e in line.events:
                name = e.name
                if name in HOST_SPANS:
                    sl = _stats(e).get("slices")
                    host.append(Event(name, e.start_ns, e.end_ns,
                                      slices=int(sl) if sl is not None
                                      else None))
                elif scan_ops and e.duration_ns > 0 and \
                        not name.startswith("ThreadpoolListener"):
                    st = _stats(e)
                    if "hlo_module" in st:
                        cpu_ops.setdefault(
                            int(st.get("device_ordinal", 0)), []).append(
                            (e.start_ns, e.end_ns, name, str(st["hlo_module"]),
                             st.get("run_id")))
    host.sort(key=lambda e: e.start)
    trace = Trace(devices={}, host=host)
    lo, hi = trace.window()
    for plane in planes:
        m = DEVICE.match(plane.name)
        if not m:
            continue
        red, modules = _Reducer(lo, hi), []
        for line in plane.lines:
            if line.name == "XLA Ops":
                for e in line.events:
                    red.add(e.name, e.start_ns, e.end_ns)
            elif line.name == "XLA Modules":
                modules = [Event(e.name, e.start_ns, e.end_ns)
                           for e in line.events
                           if lo < e.end_ns and e.start_ns < hi]
        trace.devices[int(m.group(1))] = red.device(modules)
    for dev, ops in cpu_ops.items():          # the CPU: rehearsals only
        ops.sort()
        red, runs = _Reducer(lo, hi), {}
        for start, end, name, mod, run in ops:
            red.add(name, start, end)
            r = runs.setdefault((mod, run), [start, end])
            r[1] = max(r[1], end)
        modules = sorted((Event(mod, a, b) for (mod, _), (a, b)
                          in runs.items()), key=lambda e: e.start)
        trace.devices[dev] = red.device([e for e in modules
                                         if lo < e.end and e.start < hi])
    return trace


# -- interval arithmetic ------------------------------------------------------


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def overlap(a: List[Interval], b: List[Interval]) -> float:
    """Total length of the intersection of two unions."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def busy(trace: Trace, dev: int) -> List[Interval]:
    d = trace.devices.get(dev)
    return d.busy if d else []


def held_intervals(trace: Trace, slices0: int) -> Dict[int, List[Interval]]:
    """For each chip, when the job held it in the window: chip i is held
    while the job has more than i slices (a mesh takes the first chips).
    The layout changes at the benchmark's ``layout`` markers."""
    lo, hi = trace.window()
    marks = [(e.start, e.slices) for e in trace.spans("layout")
             if lo <= e.start <= hi]
    cuts = [(lo, slices0)] + marks
    devs = sorted(trace.devices)
    held: Dict[int, List[Interval]] = {}
    for k, (t, n) in enumerate(cuts):
        end = cuts[k + 1][0] if k + 1 < len(cuts) else hi
        for rank, dev in enumerate(devs):
            if rank < n:
                held.setdefault(dev, []).append((t, end))
    return {d: union(v) for d, v in held.items()}


def busy_s(trace: Trace, held: Dict[int, List[Interval]]) -> float:
    """Seconds in which an operation ran, within the window, averaged over
    the chips the job used."""
    lo, hi = trace.window()
    per = [overlap(busy(trace, d), [(lo, hi)]) for d in held]
    return sum(per) / len(per) * 1e-9 if per else 0.0


def idle_share(trace: Trace, held: Dict[int, List[Interval]]) -> float:
    """1 - busy time over held time, summed over the chips."""
    b = sum(overlap(busy(trace, d), h) for d, h in held.items())
    t = sum(hi - lo for h in held.values() for lo, hi in h)
    return 1.0 - b / t


def step_executions(trace: Trace) -> Dict[int, List[Event]]:
    return {d: [e for e in dev.modules if STEP_PROGRAM.search(e.name)]
            for d, dev in trace.devices.items()}


def within(evs: List[Event], spans: List[Event]) -> List[Event]:
    """The events that start inside one of the (sorted) spans."""
    out, j = [], 0
    for e in evs:
        while j < len(spans) and spans[j].end < e.start:
            j += 1
        if j < len(spans) and spans[j].start <= e.start <= spans[j].end:
            out.append(e)
    return out


def collective_s(trace: Trace) -> Tuple[float, float]:
    """(collective op time, step program time), device-seconds over all
    chips, both within the train step's executions."""
    steps = step_executions(trace)
    coll = tot = 0.0
    for dev, execs in steps.items():
        tot += sum(e.end - e.start for e in execs)
        ops = trace.devices[dev].collectives
        coll += sum(e.end - e.start for e in within(ops, execs))
    return coll * 1e-9, tot * 1e-9


# -- breakdown ----------------------------------------------------------------


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device operations that took most time (seconds a chip, averaged
    over the chips that ran any) and the longest idle gaps of the first
    chip in the window, each named by the innermost host span around it."""
    lo, hi = trace.window()
    per: Dict[str, float] = {}
    devs = [d for d, dev in trace.devices.items() if dev.busy]
    for d in devs:
        for name, t in trace.devices[d].op_time.items():
            per[name] = per.get(name, 0.0) + t
    n = max(len(devs), 1)
    ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    if devs:
        b = [(max(a, lo), min(z, hi)) for a, z in busy(trace, min(devs))
             if z > lo and a < hi]
        edges = [lo] + [x for iv in b for x in iv] + [hi]
        for k in range(0, len(edges), 2):
            g0, g1 = edges[k], edges[k + 1]
            if g1 > g0:
                gaps.append((g1 - g0, g0, g1))
        gaps.sort(reverse=True)
    named = []
    for length, g0, g1 in gaps[:top]:
        mid = (g0 + g1) / 2
        around = [e for e in trace.host
                  if e.start <= mid <= e.end and e.name != "window"]
        name = min(around, key=lambda e: e.end - e.start).name \
            if around else "outside any span"
        named.append([name, length * 1e-9])
    return {"device_ops": [[k, v * 1e-9 / n] for k, v in ops],
            "idle_gaps": named}
