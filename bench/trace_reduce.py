"""Reduce a JAX profiler trace (``.xplane.pb``) to device time.

Read with ``jax.profiler.ProfileData``. A device is a plane named
``/device:<KIND>:<n>``; its ``XLA Ops`` line holds one event per operation
run on it and its ``XLA Modules`` line one event per program run (named
after the jitted function, ``jit_<name>(<id>)``). The traced span is the
host annotation ``bench.traced_window``; the host annotations ``bench.*``
say what the host was doing in a gap.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.traced_window"
OPS, MODULES = "XLA Ops", "XLA Modules"
_DEVICE = re.compile(r"^/device:([A-Z]+):(\d+)$")


@dataclasses.dataclass
class DeviceTime:
    name: str
    busy: List[Tuple[float, float]]            # merged (start, end), s
    modules: List[Tuple[str, float, float]]    # (name, start, duration), s
    ops: Dict[str, float]                      # op name -> seconds

    @property
    def busy_s(self) -> float:
        return sum(e - s for s, e in self.busy)


@dataclasses.dataclass
class Reduction:
    span: Tuple[float, float]                  # traced window, s
    devices: List[DeviceTime]
    host: List[Tuple[str, float, float]]       # bench.* (name, start, end)

    @property
    def span_s(self) -> float:
        return self.span[1] - self.span[0]

    @property
    def busy_s(self) -> float:
        """Device-busy seconds inside the span, averaged over devices."""
        return sum(_clip_total(d.busy, self.span) for d in self.devices) \
            / len(self.devices)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.span_s

    def module_runs(self, pattern: str) -> List[Tuple[float, float]]:
        """(start, duration) of each run of the programs whose name matches
        ``pattern``, on the first device, in order. Every run in the trace
        counts: the trace starts before the traced span and stops after it,
        both between steps, so the device ran nothing else in it; and the
        device's clock need not agree with the host's to the millisecond
        that would decide a run at the span's edge."""
        rx = re.compile(pattern)
        return [(s, d) for n, s, d in self.devices[0].modules
                if rx.search(n)]

    def top_ops(self, n: int = 10) -> List[list]:
        tot: Dict[str, float] = collections.Counter()
        for d in self.devices:
            for k, v in d.ops.items():
                tot[k] += v / len(self.devices)
        return [[k, v] for k, v in tot.most_common(n)]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """Device-idle seconds in the span by the host annotation open at
        each gap's middle (``host`` when none is)."""
        tot: Dict[str, float] = collections.Counter()
        d = self.devices[0]
        edges = [self.span[0]] + [x for iv in d.busy for x in iv] + \
            [self.span[1]]
        for a, b in zip(edges[::2], edges[1::2]):
            a, b = max(a, self.span[0]), min(b, self.span[1])
            if b <= a:
                continue
            mid = (a + b) / 2
            owner = "host"
            for name, s, e in self.host:
                if s <= mid <= e and name != WINDOW:
                    owner = name
            tot[owner] += b - a
        return [[k, v] for k, v in tot.most_common(n)]


def _merge(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip_total(iv, span) -> float:
    return sum(max(0.0, min(e, span[1]) - max(s, span[0])) for s, e in iv)


def find_xplane(trace_dir: str) -> Optional[str]:
    got = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                        "*.xplane.pb")))
    return got[-1] if got else None


def reduce(path: str) -> Reduction:
    from jax.profiler import ProfileData
    try:
        return reduce_planes(ProfileData.from_file(path).planes)
    except ValueError as e:
        raise ValueError(f"{path}: {e}") from None


def reduce_planes(planes) -> Reduction:
    """The reduction of a trace's planes: objects with ``name`` and
    ``lines``, whose lines have ``name`` and ``events`` (``name``,
    ``start_ns``, ``duration_ns``), as ``ProfileData`` gives them."""
    devices, host, span = [], [], None
    for plane in planes:
        if _DEVICE.match(plane.name):
            busy, modules, ops = [], [], collections.Counter()
            for line in plane.lines:
                if line.name == OPS:
                    for e in line.events:
                        s, d = e.start_ns * 1e-9, e.duration_ns * 1e-9
                        busy.append((s, s + d))
                        ops[e.name] += d
                elif line.name == MODULES:
                    modules += [(e.name, e.start_ns * 1e-9,
                                 e.duration_ns * 1e-9) for e in line.events]
            if busy or modules:
                devices.append(DeviceTime(plane.name, _merge(busy),
                                          sorted(modules, key=lambda m: m[1]),
                                          dict(ops)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        s = e.start_ns * 1e-9
                        host.append((e.name, s, s + e.duration_ns * 1e-9))
                        if e.name == WINDOW:
                            span = (s, s + e.duration_ns * 1e-9)
    if not devices:
        raise ValueError("no device plane with operations")
    if span is None:
        raise ValueError(f"no {WINDOW!r} annotation")
    devices.sort(key=lambda d: d.name)
    return Reduction(span, devices, host)
