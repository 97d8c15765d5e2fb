"""The program's host spans on the device trace's clock.

The served path records ``repro.spans`` spans as profiler annotations, so
a trace taken with ``host_tracer_level`` 1 holds them on the host plane,
one line per OS thread.  ``load`` reads the newest ``*.xplane.pb`` under a
directory and keeps those spans as ``Span`` tuples and the device events
as ``bench.trace.Event`` tuples; the rest are pure functions that tests
can feed a constructed trace:

  * ``worker_line``: the batcher worker's line, the one that holds its
    ``serve.form`` spans (Python names no OS thread, so the trace does not
    name it either);
  * ``attribute``: each second the device was idle, given to the
    innermost and to the outermost span on that line that covers it, or
    to ``unattributed``;
  * ``self_times``: count, seconds and self seconds per span name on that
    line (self: not covered by a span inside it);
  * ``programs_inside``: how many executions of the engine's program on
    the device lie inside an ``engine.run`` span on the host.

Run as a script on a run's trace directory (``--trace 1`` leaves it in
``bench/out/trace``), it prints one line for each and then a JSON object:

  python3 bench/spans.py [bench/out/trace]
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import NamedTuple

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace as btrace  # noqa: E402

HOST_PLANE = "/host:CPU"
SPAN = re.compile(r"^(serve|store|engine)\.")
FORM = "serve.form"          # only the batcher worker forms groups
RUN = "engine.run"
UNATTRIBUTED = "unattributed"
SLACK_NS = 1e6            # a program may start or end this far outside


class Span(NamedTuple):
    line: int
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def span_name(name: str) -> str:
    """An annotation's name without its ``#k=v,...#`` arguments."""
    return name.split("#", 1)[0]


def load(log_dir: str) -> tuple[list[Span], list[btrace.Event]]:
    """Program spans of every host line and device events of the newest
    trace under ``log_dir``."""
    from jax._src.lib import _profile_data
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    data = _profile_data.ProfileData.from_file(max(paths,
                                                   key=os.path.getmtime))
    spans, device = [], []
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    name = span_name(ev.name)
                    if SPAN.match(name):
                        spans.append(Span(i, name, float(ev.start_ns),
                                          float(ev.duration_ns)))
        elif btrace.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name not in (btrace.OPS_LINE, btrace.MODULES_LINE):
                    continue
                for ev in line.events:
                    device.append(btrace.Event(
                        plane.name, line.name, ev.name, float(ev.start_ns),
                        float(ev.duration_ns)))
    return spans, device


def worker_line(spans: list[Span]) -> int | None:
    """The line with the most ``serve.form`` spans; None without one."""
    forms = defaultdict(int)
    for s in spans:
        if s.name == FORM:
            forms[s.line] += 1
    return max(forms, key=forms.get) if forms else None


def _segments(spans: list[Span], outermost: bool):
    """The line's time cut where spans open and close, each piece named by
    the innermost (or outermost) span open over it.  Spans of one thread
    nest; an end past its parent's is cut to the parent's."""
    spans = sorted(spans, key=lambda s: (s.start_ns, -s.dur_ns))
    out, stack, cur = [], [], 0.0

    def close():
        nonlocal cur
        top = stack.pop()
        name = stack[0][2] if outermost and stack else top[2]
        if top[1] > cur:
            out.append((cur, top[1], name))
        cur = max(cur, top[1])

    for s in spans:
        while stack and stack[-1][1] <= s.start_ns:
            close()
        if stack and s.start_ns > cur:
            out.append((cur, s.start_ns,
                        stack[0][2] if outermost else stack[-1][2]))
        end = min(s.end_ns, stack[-1][1]) if stack else s.end_ns
        stack.append((s.start_ns, end, s.name))
        cur = s.start_ns
    while stack:
        close()
    return out


def _busy(device: list[btrace.Event]) -> list[tuple[float, float]]:
    """Union of the operation intervals of every device."""
    merged: list[list[float]] = []
    for s, e in sorted((ev.start_ns, ev.start_ns + ev.dur_ns)
                       for ev in device if ev.line == btrace.OPS_LINE):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def idle_intervals(device: list[btrace.Event], t0: float, t1: float):
    """Intervals of ``[t0, t1]`` in which no operation ran."""
    out, cur = [], t0
    for s, e in _busy(device):
        if s > cur:
            out.append((cur, min(s, t1)))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        out.append((cur, t1))
    return [(a, b) for a, b in out if b > a]


def _overlap(intervals, segments) -> dict[str, float]:
    """Length of each named segment's overlap with the intervals (both
    sorted and disjoint), in ns."""
    out: dict[str, float] = defaultdict(float)
    i = j = 0
    while i < len(intervals) and j < len(segments):
        a = max(intervals[i][0], segments[j][0])
        b = min(intervals[i][1], segments[j][1])
        if b > a:
            out[segments[j][2]] += b - a
        if intervals[i][1] < segments[j][1]:
            i += 1
        else:
            j += 1
    return out


def _seconds(ns: dict[str, float], total_ns: float) -> dict[str, float]:
    out = {k: v * 1e-9 for k, v in ns.items()}
    out[UNATTRIBUTED] = max(total_ns - sum(ns.values()), 0.0) * 1e-9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def attribute(spans: list[Span], device: list[btrace.Event],
              window: tuple[float, float] | None = None) -> dict | None:
    """Device-idle seconds of the window by the worker's span over them.

    ``window`` is ``(t0, t1)`` in ns; by default from the first to the last
    event of the spans and the device.  ``by_span`` names the innermost
    span, ``by_root`` the outermost; each has ``unattributed``.  None
    without a worker line."""
    line = worker_line(spans)
    if line is None:
        return None
    if window is None:
        ends = [(s.start_ns, s.end_ns) for s in spans] + [
            (e.start_ns, e.start_ns + e.dur_ns) for e in device]
        window = (min(a for a, _ in ends), max(b for _, b in ends))
    idle = idle_intervals(device, *window)
    idle_ns = sum(b - a for a, b in idle)
    mine = [s for s in spans if s.line == line]
    return {"idle_s": idle_ns * 1e-9,
            "window_s": (window[1] - window[0]) * 1e-9,
            "by_span": _seconds(_overlap(idle, _segments(mine, False)),
                                idle_ns),
            "by_root": _seconds(_overlap(idle, _segments(mine, True)),
                                idle_ns)}


def self_times(spans: list[Span]) -> dict[str, dict]:
    """``{name: {"n", "s", "self_s"}}`` over the worker line's spans."""
    line = worker_line(spans)
    mine = [s for s in spans if s.line == line]
    out: dict[str, dict] = {}
    for s in mine:
        row = out.setdefault(s.name, {"n": 0, "s": 0.0, "self_s": 0.0})
        row["n"] += 1
        row["s"] += s.dur_ns * 1e-9
    for a, b, name in _segments(mine, False):
        out[name]["self_s"] += (b - a) * 1e-9
    return out


def program_offsets(spans: list[Span], device: list[btrace.Event], *,
                    module: str = "_program", name: str = RUN):
    """Per execution of a program named like ``module`` on the devices,
    ``(t_s, offset_ns)``: its start in seconds since the first one, and
    how far it lies outside the nearest ``name`` span on any host line:
    0 inside, positive by how much it ends after the span, negative by
    how much it starts before it.  The spans of one thread do not
    overlap, so the nearest is one of the three around its start."""
    runs = sorted((s.start_ns, s.end_ns) for s in spans if s.name == name)
    starts = [a for a, _ in runs]
    progs = sorted((e.start_ns, e.start_ns + e.dur_ns) for e in device
                   if e.line == btrace.MODULES_LINE and module in e.name)
    out = []
    for a, b in progs:
        k = bisect.bisect_right(starts, a)
        best = None
        for j in (k - 2, k - 1, k):
            if 0 <= j < len(runs):
                lead, lag = runs[j][0] - a, b - runs[j][1]
                off = lag if lag > 0 else (-lead if lead > 0 else 0.0)
                if best is None or abs(off) < abs(best):
                    best = off
        out.append(((a - progs[0][0]) * 1e-9,
                    float("inf") if best is None else best))
    return out


def programs_inside(spans: list[Span], device: list[btrace.Event], *,
                    slack_ns: float = SLACK_NS, **kw) -> tuple[int, int]:
    """``(inside, total)``: program executions, and how many lie inside
    an ``engine.run`` span within ``slack_ns`` (``program_offsets``)."""
    offs = program_offsets(spans, device, **kw)
    return sum(abs(o) <= slack_ns for _, o in offs), len(offs)


def change(ctx: dict, name: str) -> dict | None:
    """``{"n", "s"}``: the change of span or counter ``name`` in the
    ``stats`` RPC's ``spans`` block across the window.  None where the
    program has no such block."""
    before, after = ctx["before"].get("spans"), ctx["after"].get("spans")
    if before is None or after is None:
        return None
    zero = {"n": 0, "s": 0.0}
    b, a = before.get(name, zero), after.get(name, zero)
    return {"n": a["n"] - b["n"], "s": a["s"] - b["s"]}


def per_group_ms(ctx: dict, *names: str) -> float | None:
    """Seconds of the spans ``names`` per fused group (``serve.group``)
    over the window, in ms."""
    groups = change(ctx, "serve.group")
    if groups is None or groups["n"] <= 0:
        return None
    return 1e3 * sum(change(ctx, n)["s"] for n in names) / groups["n"]


def report(log_dir: str) -> dict:
    """Every reduction of one trace, as printed by the script."""
    spans, device = load(log_dir)
    offsets = program_offsets(spans, device)
    return {"spans": len(spans), "device_events": len(device),
            "idle": attribute(spans, device),
            "programs_inside": [sum(abs(o) <= SLACK_NS for _, o in offsets),
                                len(offsets)],
            "program_offsets_ms": [[t, o * 1e-6] for t, o in offsets],
            "self_times": self_times(spans)}


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    log_dir = args[0] if args else str(Path(__file__).resolve().parent
                                       / "out" / "trace")
    rep = report(log_dir)
    idle = rep["idle"]
    if idle is None:
        print(f"spans: {rep['spans']} program spans, none of the batcher "
              f"worker (no {FORM})")
    else:
        share = {k: round(100.0 * v / idle["idle_s"], 3) if idle["idle_s"]
                 else None for k, v in idle["by_span"].items()}
        print(f"idle by span: {idle['idle_s']:.6f} s idle of "
              f"{idle['window_s']:.6f} s; percent by innermost span "
              f"{json.dumps(share)}")
        root = {k: round(100.0 * v / idle["idle_s"], 3) if idle["idle_s"]
                else None for k, v in idle["by_root"].items()}
        print(f"idle by root span: percent {json.dumps(root)}")
    times = rep["self_times"]
    groups = times.get("serve.group", {}).get("n", 0)
    if groups:
        print(f"per group of {groups}, ms (total, self): " + json.dumps(
            {k: [round(1e3 * v["s"] / groups, 3),
                 round(1e3 * v["self_s"] / groups, 3)]
             for k, v in times.items()}))
    inside, total = rep["programs_inside"]
    print(f"programs inside {RUN}: {inside} of {total} "
          f"({100.0 * inside / total if total else 0.0:.3f} %, slack "
          f"{SLACK_NS * 1e-6:g} ms)")
    out = [(t, o) for t, o in rep["program_offsets_ms"] if o != 0.0]
    if out:
        print(f"programs outside {RUN}, ms by start time (s): " + ", ".join(
            f"{t:.1f}: {o:+.3f}" for t, o in out[:40]))
    print(json.dumps(rep))
    return 0


if __name__ == "__main__":
    sys.exit(main())
