"""Reduction of a profiler trace of the measured window to device numbers.

A trace is read into plain data, ``[(plane, [(line, [(name, start_ns,
duration_ns), ...]), ...]), ...]``, by :func:`load`
(``jax.profiler.ProfileData``), and reduced by :func:`reduce`:

* the window is the host span named ``window``;
* a device is a plane named ``/device:<kind>:<n>`` other than the CPU's;
  its operations are the events of its ``XLA Ops`` line, named
  ``<program>/<instruction>`` by the ``XLA Modules`` event they start in;
* busy time is the union of a device's operation intervals inside the
  window, averaged over the devices; idle time is the rest of the window;
* ``device_ops`` sums operation time inside the window by name on the
  first device (a ``while`` holds the operations of its body, which are
  listed too);
* ``idle_gaps`` are the longest stretches of the window in which no
  operation ran on the first device, each named by the innermost host
  span open at its middle (``-`` when none was).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]
Line = Tuple[str, List[Event]]
Plane = Tuple[str, List[Line]]

WINDOW_SPAN = "window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
_DEVICE = re.compile(r"^/device:(?!CPU:)[A-Za-z_]+:\d+$")


@dataclass
class Reduced:
    window_ns: float
    busy_ns: float                     # mean over devices
    devices: int
    device_ops: List[Tuple[str, float]]   # (name, seconds), longest first
    idle_gaps: List[Tuple[str, float]]    # (host span, seconds)


def short_name(name: str) -> str:
    """``%fusion.7 = s32[..] fusion(..), ...`` -> ``fusion.7``;
    ``jit_run_program(123)`` -> ``jit_run_program``."""
    if name.startswith("%"):
        return name[1:].split(" ", 1)[0]
    return name.split("(", 1)[0]


def load(trace_dir: str) -> List[Plane]:
    """Planes of the newest ``*.xplane.pb`` under ``trace_dir``: every host
    event, and each device's ``XLA Modules`` and ``XLA Ops`` lines with
    short names."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    planes: List[Plane] = []
    for plane in data.planes:
        device = _is_device(plane.name)
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            names: Dict[str, str] = {}
            events = []
            for e in line.events:
                name = e.name
                if device:
                    name = names.get(name) or names.setdefault(
                        name, short_name(name))
                events.append((name, float(e.start_ns),
                               float(e.duration_ns)))
            lines.append((line.name, events))
        planes.append((plane.name, lines))
    return planes


def _is_device(plane: str) -> bool:
    return bool(_DEVICE.match(plane))


def _union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def _clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def _host_spans(planes: Sequence[Plane]) -> List[Tuple[float, float, str]]:
    return [(start, start + dur, name)
            for plane, lines in planes if not _is_device(plane)
            for _, events in lines for name, start, dur in events]


def reduce(planes: Sequence[Plane], spans: Sequence[str]) -> Optional[Reduced]:
    """Device numbers of the window, or ``None`` when the trace holds no
    window span or no device plane.  ``spans`` are the host span names
    that may label an idle gap."""
    host = _host_spans(planes)
    windows = [(a, b) for a, b, name in host if name == WINDOW_SPAN]
    devices = [lines for plane, lines in planes
               if _is_device(plane) and any(ev for _, ev in lines)]
    if not windows or not devices:
        return None
    w0, w1 = windows[0]
    labels = [(a, b, name) for a, b, name in host if name in spans]
    busy, op_ns = [], {}
    first_busy = None
    for lines in devices:
        by_line = dict(lines)
        events = by_line.get(OPS_LINE) or [e for _, ev in lines for e in ev]
        merged = _union(_clip([(s, s + d) for _, s, d in events], w0, w1))
        busy.append(sum(b - a for a, b in merged))
        if first_busy is None:
            first_busy = merged
            modules = sorted((s, s + d, short_name(n))
                             for n, s, d in by_line.get(MODULES_LINE, ()))
            starts = [m[0] for m in modules]
            for name, s, d in events:
                if s + d <= w0 or s >= w1:
                    continue
                name = short_name(name)
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s < modules[i][1]:
                    name = f"{modules[i][2]}/{name}"
                op_ns[name] = op_ns.get(name, 0.0) + min(s + d, w1) - max(s, w0)
    gaps, edge = [], w0
    for a, b in first_busy + [[w1, w1]]:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    named = []
    for a, b in gaps:
        mid = (a + b) / 2
        open_spans = [(s1 - s0, name) for s0, s1, name in labels
                      if s0 <= mid <= s1]
        named.append((min(open_spans)[1] if open_spans else "-",
                      (b - a) / 1e9))
    named.sort(key=lambda g: -g[1])
    ops = sorted(((n, t / 1e9) for n, t in op_ns.items()), key=lambda o: -o[1])
    return Reduced(window_ns=w1 - w0, busy_ns=sum(busy) / len(busy),
                   devices=len(devices), device_ops=ops[:TOP],
                   idle_gaps=named[:TOP])
