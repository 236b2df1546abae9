"""Reduction from a profiler trace to numbers.  Kept with the benchmark so
that every PR computes the same number the same way.

A trace is read once (``load``) into plain tuples, so the arithmetic below is
checked on small synthetic traces (``benchmarks/tests/test_trace.py``) and
never needs a chip to be tested.

Device time: on a TPU plane the ``XLA Ops`` line holds one event per executed
HLO instruction and ``XLA Modules`` one per executed program.  Busy time is
the UNION of the op intervals (nested or overlapping events count once); the
traced span runs from the first to the last event on the device, so a trace
that was started while the device was idle does not count the idle lead-in
against the program."""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

NAME_CHARS = 160  # an op's name is its whole HLO line: keep the start
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: int
    dur_ns: int
    meta: str = ""  # the event's string stats, joined: patterns match here too

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


@dataclass
class Plane:
    name: str
    lines: dict[str, list[Event]] = field(default_factory=dict)


def load(trace_dir: str | Path) -> list[Plane]:
    """Every plane of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    planes = []
    for p in data.planes:
        plane = Plane(p.name)
        for ln in p.lines:
            events = []
            for e in ln.events:
                meta = " ".join(f"{k}={v}" for k, v in e.stats
                                if isinstance(v, str))
                events.append(Event(e.name, int(e.start_ns),
                                    int(e.duration_ns), meta))
            plane.lines.setdefault(ln.name, []).extend(events)
        planes.append(plane)
    return planes


def device_planes(planes: list[Plane], platform: str = "tpu") -> list[Plane]:
    """Planes of accelerator devices that ran something.  On the CPU backend
    (tests) there is no device plane; callers get an empty list and report
    nothing."""
    prefix = {"tpu": "/device:TPU:", "gpu": "/device:GPU:"}.get(platform)
    if prefix is None:
        return []
    return [p for p in planes if p.name.startswith(prefix)
            and any(p.lines.get(OPS_LINE, []))]


def union_ns(events: list[Event]) -> int:
    """Total length of the union of the events' intervals."""
    total, end = 0, None
    for e in sorted(events, key=lambda e: e.start_ns):
        if end is None or e.start_ns > end:
            total += e.dur_ns
            end = e.end_ns
        elif e.end_ns > end:
            total += e.end_ns - end
            end = e.end_ns
    return total


def span_ns(events: list[Event]) -> tuple[int, int]:
    return (min(e.start_ns for e in events), max(e.end_ns for e in events))


def gaps(events: list[Event]) -> list[tuple[int, int]]:
    """Idle intervals between the events' union, longest first."""
    out, end = [], None
    for e in sorted(events, key=lambda e: e.start_ns):
        if end is not None and e.start_ns > end:
            out.append((end, e.start_ns))
        end = e.end_ns if end is None else max(end, e.end_ns)
    return sorted(out, key=lambda g: g[0] - g[1])


def top_ops(events: list[Event], n: int = 10) -> list[list]:
    """``[[name, seconds], ...]``: the operations that took most device time,
    summed by name (a name nested inside another is still listed: the list is
    for reading, the union is for arithmetic)."""
    by_name: dict[str, int] = {}
    for e in events:
        by_name[e.name] = by_name.get(e.name, 0) + e.dur_ns
    best = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[k[:NAME_CHARS], v / 1e9] for k, v in best]


def matching(events: list[Event], pattern: str) -> list[Event]:
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e.name) or rx.search(e.meta)]


def main_module(plane: Plane) -> tuple[str, list[Event]]:
    """The program that took most device time (the train step) and its
    executions."""
    by_name: dict[str, list[Event]] = {}
    for e in plane.lines.get(MODULES_LINE, []):
        by_name.setdefault(e.name, []).append(e)
    if not by_name:
        return "", []
    name = max(by_name, key=lambda k: sum(e.dur_ns for e in by_name[k]))
    return name, by_name[name]


def attribute_gaps(idle: list[tuple[int, int]], host: list[Plane],
                   n: int = 10) -> list[list]:
    """For each of the ``n`` longest device-idle gaps, the host event (any
    thread of any host plane) that overlaps it longest.  Only what the
    profiler's own host lines say: a gap no host event overlaps is
    ``unattributed``.  Summed by name."""
    host_events = [e for p in host for evs in p.lines.values() for e in evs]
    by_name: dict[str, int] = {}
    for a, b in idle[:3 * n]:
        best, best_ov = "unattributed", 0
        for e in host_events:
            ov = min(b, e.end_ns) - max(a, e.start_ns)
            if ov > best_ov:
                best, best_ov = e.name, ov
        by_name[best] = by_name.get(best, 0) + (b - a)
    best = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in best]


@dataclass
class DeviceSummary:
    busy_s: float       # union of op intervals, mean over device planes
    window_s: float     # first to last device event, mean over planes
    steps: int          # executions of the main module inside the window
    module: str
    ops: list[Event]    # the first device plane's op events
    idle: list[tuple[int, int]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def summarise(planes: list[Plane], platform: str = "tpu") -> DeviceSummary | None:
    devs = device_planes(planes, platform)
    if not devs:
        return None
    busy, window = [], []
    for p in devs:
        ops = p.lines[OPS_LINE]
        a, b = span_ns(ops)
        busy.append(union_ns(ops) / 1e9)
        window.append((b - a) / 1e9)
    first = devs[0]
    module, runs = main_module(first)
    return DeviceSummary(
        busy_s=sum(busy) / len(busy), window_s=sum(window) / len(window),
        steps=len(runs), module=module, ops=first.lines[OPS_LINE],
        idle=gaps(first.lines[OPS_LINE]))
