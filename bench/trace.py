"""Reduction of a profiler trace to device busy time, idle gaps and the
time of named programs and kernels.

``load`` reads the newest ``*.xplane.pb`` under a directory with JAX's own
reader and keeps the device planes (``/device:TPU:<n>``) as plain
``Event`` tuples, so ``reduce`` is a pure function that tests can feed a
constructed trace.  On a TPU plane, line ``XLA Ops`` holds one event per
executed operation and line ``XLA Modules`` one per executed program.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import NamedTuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


class Event(NamedTuple):
    device: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


def load(log_dir: str) -> list[Event]:
    """Device events of the newest trace under ``log_dir``."""
    from jax._src.lib import _profile_data
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    data = _profile_data.ProfileData.from_file(max(paths,
                                                   key=os.path.getmtime))
    out = []
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


_HLO = re.compile(r"^(%[\w.\-]+) = (\S+?)(?:\{[^}]*\})? ([\w\-]+)\(")


def short_name(name: str) -> str:
    """``%pad.4 pad f32[12288,8448]`` for an HLO op's full text; other
    names as they are."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(3)} {m.group(2)}" if m else name


def _union(intervals: list[tuple[float, float, str]]):
    """Merged busy intervals; each keeps the names of its first and last
    operation, so a gap can be named by what bracketed it."""
    merged: list[list] = []
    for s, e, name in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
                merged[-1][3] = name
        else:
            merged.append([s, e, name, name])
    return merged


def reduce(events: list[Event], window_s: float, *,
           module: str | None = None, kernel: str | None = None,
           top: int = 10) -> dict:
    """Busy and idle time of the devices over a traced window.

    ``busy_s`` is the union of the operation intervals of each device,
    averaged over the devices that ran anything; ``idle_share`` is one less
    busy over ``window_s``.  ``module_s``/``module_calls`` sum the executions
    of programs whose name contains ``module``; ``kernel_s``/
    ``kernel_calls`` the operations whose full HLO text matches the regular
    expression ``kernel``.  ``device_ops`` lists the operations by total
    time, ``idle_gaps`` the longest gaps between operations, named by the
    operations on either side; both name an HLO op by ``short_name``.
    """
    per_dev: dict[str, list] = defaultdict(list)
    op_time: dict[str, float] = defaultdict(float)
    module_s = kernel_s = 0.0
    module_calls = kernel_calls = 0
    kern = re.compile(kernel) if kernel else None
    for ev in events:
        if ev.line == MODULES_LINE:
            if module and module in ev.name:
                module_s += ev.dur_ns * 1e-9
                module_calls += 1
            continue
        name = short_name(ev.name)
        per_dev[ev.device].append((ev.start_ns, ev.start_ns + ev.dur_ns,
                                   name))
        op_time[name] += ev.dur_ns * 1e-9
        if kern and kern.search(ev.name):
            kernel_s += ev.dur_ns * 1e-9
            kernel_calls += 1
    busy, gaps = [], []
    for dev, iv in per_dev.items():
        merged = _union(iv)
        busy.append(sum(e - s for s, e, _, _ in merged) * 1e-9)
        for a, b in zip(merged, merged[1:]):
            gaps.append((f"{a[3]} -> {b[2]}", (b[0] - a[1]) * 1e-9))
    busy_s = sum(busy) / len(busy) if busy else 0.0
    gaps.sort(key=lambda g: -g[1])
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": (1.0 - busy_s / window_s) if window_s > 0 else None,
            "devices": len(busy),
            "module_s": module_s, "module_calls": module_calls,
            "kernel_s": kernel_s, "kernel_calls": kernel_calls,
            "device_ops": [[n, s] for n, s in ops[:top]],
            "idle_gaps": [[n, s] for n, s in gaps[:top]]}
