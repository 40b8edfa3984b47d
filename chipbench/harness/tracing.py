"""Reduce a profiler trace to device busy time, per-program device time,
the longest device operations and the device's idle gaps labelled by what
the host was doing.

Two stages, so the second can be tested on a recorded trace without the
profiler: ``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes
into plain interval lists (``Trace``); the functions below it work on
those lists only. All times inside a ``Trace`` are seconds on the trace's
own clock, shared by its host and device planes.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[str, float, float]          # (name, start_s, end_s)


@dataclasses.dataclass
class Device:
    name: str
    modules: List[Interval]                   # one per program execution
    ops: List[Interval]                       # one per device operation


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    host: List[Interval]                      # host-side events (any thread)
    lo: float                                 # extent of the trace
    hi: float

    def to_json(self) -> dict:
        return {"devices": [dataclasses.asdict(d) for d in self.devices],
                "host": self.host, "lo": self.lo, "hi": self.hi}

    @staticmethod
    def from_json(d: dict) -> "Trace":
        devs = [Device(x["name"], [tuple(e) for e in x["modules"]],
                       [tuple(e) for e in x["ops"]]) for x in d["devices"]]
        return Trace(devs, [tuple(e) for e in d["host"]], d["lo"], d["hi"])

    def save(self, path: str) -> None:
        with gzip.open(path, "wt") as f:
            json.dump(self.to_json(), f)

    @staticmethod
    def read(path: str) -> "Trace":
        with gzip.open(path, "rt") as f:
            return Trace.from_json(json.load(f))

    def clip(self, lo: float, hi: float) -> "Trace":
        """The part of the trace inside [lo, hi) (events cut at the edges)."""
        def cut(evs):
            return [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                    if e > lo and s < hi]
        return Trace([Device(d.name, cut(d.modules), cut(d.ops))
                      for d in self.devices], cut(self.host), lo, hi)


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    devices: List[Device] = []
    host: List[Interval] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            mods: List[Interval] = []
            ops: List[Interval] = []
            for line in plane.lines:
                dst = mods if line.name == "XLA Modules" else \
                    ops if line.name == "XLA Ops" else None
                if dst is None:
                    continue
                for e in line.events:
                    s = e.start_ns * 1e-9
                    dst.append((e.name, s, s + e.duration_ns * 1e-9))
            devices.append(Device(plane.name, mods, ops))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        s = e.start_ns * 1e-9
                        host.append((e.name, s, s + e.duration_ns * 1e-9))
    every = [t for d in devices for evs in (d.modules, d.ops)
             for _, s, e in evs for t in (s, e)]
    every += [t for _, s, e in host for t in (s, e)]
    lo, hi = (min(every), max(every)) if every else (0.0, 0.0)
    return Trace(devices, host, lo, hi)


def describe(trace_dir: str) -> List[str]:
    """One line per plane and line of the raw trace (for a first look)."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for f in files:
        pd = ProfileData.from_file(f)
        for plane in pd.planes:
            for line in plane.lines:
                evs = list(line.events)
                if not evs:
                    continue
                names = defaultdict(float)
                for e in evs:
                    names[e.name] += e.duration_ns * 1e-9
                top = sorted(names.items(), key=lambda kv: -kv[1])[:4]
                out.append(f"{plane.name} | {line.name} | {len(evs)} events "
                           f"| {evs[0].start_ns} .. {evs[-1].start_ns} | "
                           f"{top}")
    return out


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    """Merge overlapping (start, end) intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


CONTAINERS = ("while", "conditional", "call")


def op_name(name: str) -> str:
    """A device op's short name: the HLO instruction name (the trace gives
    the whole instruction text)."""
    return name.split(" = ", 1)[0].lstrip("%")


def _leaves(dev: Device) -> List[Interval]:
    """The device's ops without control-flow containers (a while loop's
    event spans every op it runs and any gap between them), or whole
    programs where the trace has no op line."""
    if not dev.ops:
        return dev.modules
    return [ev for ev in dev.ops
            if op_name(ev[0]).split(".")[0] not in CONTAINERS]


def busy_s(dev: Device) -> float:
    """Seconds in which some operation ran on the device."""
    return sum(e - s for s, e in union((s, e) for _, s, e in _leaves(dev)))


def module_time(dev: Device, patterns: Sequence[str]) -> Tuple[float, int]:
    """(seconds, executions) of the programs whose name contains one of
    ``patterns``."""
    t, n = 0.0, 0
    for name, s, e in dev.modules:
        if any(p in name for p in patterns):
            t += e - s
            n += 1
    return t, n


def top_ops(dev: Device, n: int = 10) -> List[Tuple[str, float]]:
    """Device time by operation, longest first. Control-flow ops (a while
    loop and the like) contain the ops they run and are left out, so no
    time is counted twice."""
    acc: Dict[str, float] = defaultdict(float)
    for name, s, e in _leaves(dev):
        acc[op_name(name)] += e - s
    return sorted(acc.items(), key=lambda kv: -kv[1])[:n]


def idle_gaps(dev: Device, lo: float, hi: float) -> List[Tuple[float,
                                                               float]]:
    """Intervals of [lo, hi) in which nothing ran on the device."""
    gaps, t = [], lo
    for s, e in union((s, e) for _, s, e in _leaves(dev)):
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    return [(s, e) for s, e in gaps if e > s]


def label_gaps(gaps: Sequence[Tuple[float, float]],
               host: Sequence[Interval]) -> List[str]:
    """For each gap, the host event that overlaps it most (the shorter one
    on a tie); "host idle" when none does. One sweep over both lists."""
    import heapq
    evs = sorted(host, key=lambda ev: ev[1])
    order = sorted(range(len(gaps)), key=lambda i: gaps[i][0])
    labels = ["host idle"] * len(gaps)
    active: List[Tuple[float, int]] = []          # (end, index into evs)
    j = 0
    for gi in order:
        s0, e0 = gaps[gi]
        while j < len(evs) and evs[j][1] < e0:
            heapq.heappush(active, (evs[j][2], j))
            j += 1
        while active and active[0][0] <= s0:
            heapq.heappop(active)
        best = None
        for _, k in active:
            name, s, e = evs[k]
            ov = min(e, e0) - max(s, s0)
            if ov <= 0:
                continue
            key = (ov, -(e - s))
            if best is None or key > best[0]:
                best = (key, name)
        if best is not None:
            labels[gi] = best[1]
    return labels


def gaps_by_label(dev: Device, host: Sequence[Interval], lo: float,
                  hi: float, n: int = 10) -> List[Tuple[str, float]]:
    """Idle seconds summed by the label of each gap, longest first."""
    gaps = idle_gaps(dev, lo, hi)
    acc: Dict[str, float] = defaultdict(float)
    for g, name in zip(gaps, label_gaps(gaps, host)):
        acc[name] += g[1] - g[0]
    return sorted(acc.items(), key=lambda kv: -kv[1])[:n]
