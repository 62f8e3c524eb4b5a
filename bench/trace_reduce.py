"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy time, the device time of a jitted program or
a kernel found by its stable name, the costliest device operations and
the longest idle gaps.

Devices are the planes named ``/device:TPU:<i>``; their ``XLA Ops``
line holds one event per executed operation and their ``XLA Modules``
line one per executed program (``jit_<function name>``).  The traced
window is the host annotation :data:`WINDOW` that the harness opens
around the timed call; device events are clipped to it.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

#: the host annotation the harness opens around the measured window
WINDOW = "bench.window"

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float, str]  # (start ns, end ns, name)


@dataclass
class DeviceTrace:
    """The device events of one traced window, in nanoseconds on the
    profiler's clock."""

    window: Tuple[float, float]
    #: per device plane: its op events and its program events
    ops: List[List[Interval]] = field(default_factory=list)
    modules: List[List[Interval]] = field(default_factory=list)
    #: the host's own profiler events (transfers, dispatches), all threads
    host: List[Interval] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def ndevices(self) -> int:
        return len(self.ops)

    def busy_intervals(self, device: int) -> List[Tuple[float, float]]:
        """The union of the device's op intervals inside the window."""
        lo, hi = self.window
        spans = sorted((max(s, lo), min(e, hi))
                       for s, e, _ in self.ops[device] if e > lo and s < hi)
        merged: List[Tuple[float, float]] = []
        for s, e in spans:
            if merged and s <= merged[-1][1]:
                if e > merged[-1][1]:
                    merged[-1] = (merged[-1][0], e)
            else:
                merged.append((s, e))
        return merged

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.ops:
            return 0.0
        total = sum(e - s for d in range(self.ndevices)
                    for s, e in self.busy_intervals(d))
        return total * 1e-9 / self.ndevices

    def _sum(self, events: Sequence[List[Interval]], match) -> Tuple[float, int]:
        lo, hi = self.window
        secs, count = 0.0, 0
        for dev in events:
            for s, e, name in dev:
                if e > lo and s < hi and match(name):
                    secs += (min(e, hi) - max(s, lo)) * 1e-9
                    count += 1
        n = max(1, len(events))
        return secs / n, count // n

    def program_time(self, prefix: str) -> Tuple[float, int]:
        """(device seconds, executions) of the programs whose name starts
        with ``prefix`` (``jit_step`` matches ``jit_step(12)``), per
        device."""
        return self._sum(self.modules, lambda name: name.startswith(prefix))

    def op_time(self, substring: str) -> Tuple[float, int]:
        """(device seconds, executions) of the ops whose name contains
        ``substring``, per device."""
        return self._sum(self.ops, lambda name: substring in name)

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """The ``n`` ops that took the most device time, each named
        ``<program>/<op>`` (``jit_step/%fusion.3``), per device."""
        totals: Dict[str, float] = {}
        lo, hi = self.window
        for dev, mods in zip(self.ops, self.modules):
            mods = sorted(mods)
            starts = [m[0] for m in mods]
            for s, e, name in dev:
                if e > lo and s < hi:
                    i = bisect.bisect_right(starts, s) - 1
                    prog = (mods[i][2].split("(")[0]
                            if i >= 0 and s < mods[i][1] else "?")
                    key = f"{prog}/{name.split(' = ')[0]}"
                    totals[key] = totals.get(key, 0.0) + (
                        min(e, hi) - max(s, lo)) * 1e-9 / self.ndevices
        return sorted(totals.items(), key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, device: int = 0) -> List[Tuple[float, float]]:
        """The device's idle intervals inside the window, longest first."""
        lo, hi = self.window
        gaps, t = [], lo
        for s, e in self.busy_intervals(device):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        return sorted(gaps, key=lambda g: g[0] - g[1])


def find_xplane(log_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` the profiler wrote under ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def _events(line) -> List[Interval]:
    return [(float(e.start_ns), float(e.start_ns) + float(e.duration_ns),
             e.name) for e in line.events]


def load(path: str) -> DeviceTrace:
    """Read the device planes and the window annotation of one trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: List[List[Interval]] = []
    modules: List[List[Interval]] = []
    host: List[Interval] = []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            lines = {line.name: _events(line) for line in plane.lines}
            ops.append(lines.get(OPS_LINE, []))
            modules.append(lines.get(MODULES_LINE, []))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
    windows = [(s, e) for s, e, name in host if name == WINDOW]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW!r} annotation on a host plane")
    return DeviceTrace(window=windows[0], ops=ops, modules=modules,
                       host=[h for h in host if h[2] != WINDOW])


def label_gaps(gaps: List[Tuple[float, float]],
               host: List[Interval], n: int = 10) -> List[Tuple[str, float]]:
    """Name the ``n`` longest gaps by what the host was doing: the host
    event or span that overlaps a gap most, else ``after <name>`` of the
    last one that ended before it.  ``host`` holds (start, end, name) on
    the trace's clock (the profiler's host events and the program's
    spans); an instant event has start == end."""
    out = []
    for g0, g1 in gaps[:n]:
        best, best_overlap = None, 0.0
        last, last_end = None, float("-inf")
        for s, e, name in host:
            overlap = min(e, g1) - max(s, g0)
            if e > s and overlap > best_overlap:
                best, best_overlap = name, overlap
            if e <= g0 and e >= last_end:
                last, last_end = name, e
        label = best if best is not None else (
            f"after {last}" if last is not None else "before the first step")
        out.append((label, (g1 - g0) * 1e-9))
    return out
