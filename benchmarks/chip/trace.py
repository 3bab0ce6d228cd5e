"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

Device planes (``/device:TPU:<n>``) hold the operations that ran on each
chip, on the line ``XLA Ops``; the host plane holds the harness spans
(``serve.*``, ``train.*``), which are TraceAnnotations and so share the
trace's clock.  From these:

- busy: the union of the operation intervals of a device; idle = window -
  busy, where the window runs from the first harness span to the last;
- device time inside a span: busy time of each device within the span's
  interval, averaged over the devices;
- exposed collectives: time in all-gather / reduce-scatter / all-reduce /
  all-to-all / collective-permute operations during which no other
  operation runs on that device;
- the breakdown: the operations that took most device time, and the
  longest idle gaps, each named by the innermost harness span the host was
  in at the gap's middle.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

OPS_LINE = "XLA Ops"
SPAN_PREFIXES = ("serve.", "train.")
COLLECTIVE = re.compile(
    r"all-gather|reduce-scatter|all-reduce|all-to-all|collective-permute")
# operations that contain others (a loop's body runs as operations of its
# own): counted in busy time, left out of the per-operation breakdown
CONTAINERS = ("while", "conditional", "call")


def op_kind(name: str) -> str:
    """An operation's kind from its HLO text: ``%fusion.16 = ...`` ->
    ``fusion``; ``%copy_bitcast_fusion.3 = ...`` -> ``copy_bitcast_fusion``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.(\d|clone).*$", "", head)


@dataclass
class Span:
    name: str
    start: float
    end: float
    stats: Dict[str, object] = field(default_factory=dict)


def merge(iv: np.ndarray) -> np.ndarray:
    """Union of (n, 2) intervals, sorted and disjoint."""
    if len(iv) == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def covered(merged: np.ndarray, a: float, b: float) -> float:
    """Length of [a, b] that the disjoint sorted intervals cover."""
    if len(merged) == 0 or b <= a:
        return 0.0
    s = np.clip(merged[:, 0], a, b)
    e = np.clip(merged[:, 1], a, b)
    return float(np.sum(e - s))


@dataclass
class Device:
    name: str
    ops: List[Tuple[float, float, str]]
    busy: np.ndarray = None          # merged intervals of every operation
    collective: np.ndarray = None    # merged intervals of collectives
    compute: np.ndarray = None       # merged intervals of the rest

    def __post_init__(self):
        iv = lambda keep: np.asarray([(s, e) for s, e, n in self.ops
                                      if keep(n)]).reshape(-1, 2)
        self.busy = merge(iv(lambda n: True))
        self.collective = merge(iv(lambda n: COLLECTIVE.search(n)))
        self.compute = merge(iv(lambda n: not COLLECTIVE.search(n)))


class Trace:
    def __init__(self, devices: List[Device], spans: List[Span]):
        self.devices = devices
        self.spans = sorted(spans, key=lambda s: s.start)
        self.t0 = self.spans[0].start if spans else 0.0
        self.t1 = max((s.end for s in self.spans), default=0.0)

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def busy_in(self, a: float, b: float) -> float:
        """Device busy seconds within [a, b], mean over the devices."""
        return float(np.mean([covered(d.busy, a, b) for d in self.devices]))

    @property
    def busy_s(self) -> float:
        return self.busy_in(self.t0, self.t1)

    def spans_named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def exposed_collective_s(self) -> float:
        """Collective seconds with no other operation running, mean over
        the devices, inside the window."""
        out = []
        for d in self.devices:
            t = 0.0
            for s, e in d.collective:
                s, e = max(s, self.t0), min(e, self.t1)
                if e > s:
                    t += (e - s) - covered(d.compute, s, e)
            out.append(t)
        return float(np.mean(out))

    def host_span_at(self, t: float) -> str:
        inside = [s for s in self.spans if s.start <= t <= s.end]
        if not inside:
            return "host.outside_spans"
        return min(inside, key=lambda s: s.end - s.start).name

    def breakdown(self, top: int = 10) -> dict:
        """Top device operation kinds by seconds (mean over devices;
        containers left out), and the longest idle gaps of the first device
        by the host span at their middle."""
        per_op = defaultdict(float)
        for d in self.devices:
            for s, e, n in d.ops:
                s, e = max(s, self.t0), min(e, self.t1)
                k = op_kind(n)
                if e > s and k not in CONTAINERS:
                    per_op[k] += (e - s) / len(self.devices)
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
        b = self.devices[0].busy
        edges = np.concatenate([[self.t0], b.reshape(-1), [self.t1]])
        starts, ends = edges[0::2], edges[1::2]
        gaps = []
        for s, e in zip(starts, ends):
            s, e = max(s, self.t0), min(e, self.t1)
            if e > s:
                gaps.append((self.host_span_at((s + e) / 2), e - s))
        gaps.sort(key=lambda g: -g[1])
        return {"device_ops": [[n, float(t)] for n, t in ops],
                "idle_gaps": [[n, float(t)] for n, t in gaps[:top]]}


def load(path: str, span_prefixes=SPAN_PREFIXES) -> Trace:
    from jax._src.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = [(e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if ops:
                devices.append(Device(plane.name, ops))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(span_prefixes):
                        spans.append(Span(e.name, e.start_ns * 1e-9,
                                          e.end_ns * 1e-9, dict(e.stats)))
    devices.sort(key=lambda d: d.name)
    return Trace(devices, spans)


def find_xplane(directory: str) -> Optional[str]:
    import glob
    found = sorted(glob.glob(f"{directory}/**/*.xplane.pb", recursive=True))
    return found[-1] if found else None
