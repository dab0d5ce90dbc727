"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
small ``Trace``: the events of each TPU plane's op and module lines, and
the host spans this benchmark annotated (``chipbench.*``). Everything else
works on a ``Trace``, so tests run on a small trace recorded on the chip
and kept as JSON.

Busy time is the union of the intervals in which an op ran on a device,
inside the traced window (the ``chipbench.wave`` span of the traced
wave); the idle share is one minus busy over the window. Each idle gap is
labelled by the innermost benchmark span the host was in at the gap's
midpoint, and idle time is summed per label.
"""
from __future__ import annotations

import bisect
import glob
import json
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

Event = Tuple[str, float, float]          # name, start_ns, duration_ns

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.wave"


@dataclass
class Trace:
    #: per device plane: line name -> events
    devices: List[Dict[str, List[Event]]] = field(default_factory=list)
    #: the benchmark's host spans
    host: List[Event] = field(default_factory=list)

    def to_json(self) -> dict:
        return {"devices": self.devices, "host": self.host}

    @staticmethod
    def from_json(obj: dict) -> "Trace":
        devs = [{k: [tuple(e) for e in v] for k, v in d.items()}
                for d in obj["devices"]]
        return Trace(devs, [tuple(e) for e in obj["host"]])


def load(trace_dir: str) -> Trace:
    """Read the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} xplane files in {trace_dir}")
    data = ProfileData.from_file(paths[0])
    out = Trace()
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            out.devices.append({
                line.name: [(e.name, e.start_ns, e.duration_ns)
                            for e in line.events]
                for line in plane.lines
                if line.name in (OPS_LINE, MODULES_LINE)})
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out.host += [(e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX)]
    out.host.sort(key=lambda e: e[1])
    return out


def window(tr: Trace) -> Optional[Tuple[float, float]]:
    """(start_ns, end_ns) of the traced wave, or None."""
    spans = [(s, s + d) for n, s, d in tr.host if n == WINDOW_SPAN]
    if not spans:
        return None
    return min(s for s, _ in spans), max(e for _, e in spans)


def _clip(events: List[Event], w: Tuple[float, float]):
    w0, w1 = w
    for name, s, d in events:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            yield name, a, b


def busy_intervals(events: List[Event], w: Tuple[float, float])\
        -> List[Tuple[float, float]]:
    """Union of the event intervals inside ``w``, merged and sorted."""
    merged: List[List[float]] = []
    for _, a, b in sorted(_clip(events, w), key=lambda e: e[1]):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def idle_gaps(busy: List[Tuple[float, float]], w: Tuple[float, float])\
        -> List[Tuple[float, float]]:
    gaps, t = [], w[0]
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if w[1] > t:
        gaps.append((t, w[1]))
    return gaps


class Labeller:
    """The innermost benchmark span covering a host time: of the spans
    that cover it, the one that started last."""

    def __init__(self, host: List[Event]):
        self.by_name: Dict[str, Tuple[List[float], List[float]]] = {}
        for name, s, d in sorted(host, key=lambda e: e[1]):
            starts, ends = self.by_name.setdefault(name, ([], []))
            starts.append(s)
            ends.append(s + d)

    def __call__(self, t_ns: float) -> str:
        best, best_start = "outside", None
        for name, (starts, ends) in self.by_name.items():
            i = bisect.bisect_right(starts, t_ns) - 1
            if i >= 0 and ends[i] >= t_ns and (best_start is None
                                               or starts[i] > best_start):
                best, best_start = name[len(SPAN_PREFIX):], starts[i]
        return best


def device_ops(tr: Trace) -> List[List[Event]]:
    return [d.get(OPS_LINE, []) for d in tr.devices]


def device_modules(tr: Trace) -> List[List[Event]]:
    return [d.get(MODULES_LINE, []) for d in tr.devices]


@dataclass
class Reduced:
    window_s: float
    busy_s: float                          # mean over the devices
    modules_s: Dict[str, float]            # device seconds per program
    ops_s: Dict[str, float]                # device seconds per op name
    idle_by_label: Dict[str, float]        # idle seconds per host span


def reduce(tr: Trace) -> Optional[Reduced]:
    w = window(tr)
    ops = device_ops(tr)
    if w is None or not any(ops):
        return None
    busy = [busy_intervals(ev, w) for ev in ops]
    busy_s = sum(b - a for bs in busy for a, b in bs) / len(busy) / 1e9
    modules: Dict[str, float] = defaultdict(float)
    for ev in device_modules(tr):
        for name, a, b in _clip(ev, w):
            modules[name] += (b - a) / 1e9
    per_op: Dict[str, float] = defaultdict(float)
    for ev in ops:
        for name, a, b in _clip(ev, w):
            per_op[name] += (b - a) / 1e9
    label = Labeller(tr.host)
    idle: Dict[str, float] = defaultdict(float)
    for bs in busy:
        for a, b in idle_gaps(bs, w):
            idle[label((a + b) / 2)] += (b - a) / 1e9 / len(busy)
    return Reduced(window_s=(w[1] - w[0]) / 1e9, busy_s=busy_s,
                   modules_s=dict(modules), ops_s=dict(per_op),
                   idle_by_label=dict(idle))


def breakdown(r: Reduced, n: int = 10) -> dict:
    """The device ops that took most time, and the idle time summed by
    the benchmark span the host was in, longest first."""
    ops = sorted(r.ops_s.items(), key=lambda kv: -kv[1])[:n]
    gaps = sorted(r.idle_by_label.items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


def save(tr: Trace, path: str) -> None:
    with open(path, "w") as f:
        json.dump(tr.to_json(), f)
