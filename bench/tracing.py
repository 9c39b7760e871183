"""Reduction of a JAX profiler trace (``.xplane.pb``) to per-device busy
time, per-operation device time, idle gaps and the host activity in them.

The harness brackets its measured window with a ``bench.window``
``TraceAnnotation`` and each job with ``bench.job.<i>``; everything is
clipped to the window.  Device planes are ``/device:TPU:<n>``, and their
operations are the events of the ``XLA Ops`` line; each is keyed
``<XLA module>/<instruction base name>:<opcode>`` and charged its self
time (a ``while`` is charged only what its body's operations leave).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

WINDOW = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class DeviceSummary:
    name: str
    busy_ns: float
    op_ns: dict            # op key -> device self ns inside the window
    gaps: list             # (start_ns, end_ns) idle intervals in the window


@dataclasses.dataclass
class TraceSummary:
    window_ns: float
    devices: list          # list[DeviceSummary]
    host: list             # (start_ns, end_ns, name) host events

    def busy_s(self) -> float:
        return sum(d.busy_ns for d in self.devices) / len(self.devices) / 1e9

    def window_s(self) -> float:
        return self.window_ns / 1e9

    def op_share(self, match) -> float:
        """Mean over devices of the device time of the operations whose
        name ``match`` accepts, as a share of the window."""
        per = [sum(ns for op, ns in d.op_ns.items() if match(op))
               for d in self.devices]
        return sum(per) / len(per) / self.window_ns

    def breakdown(self, top: int = 10) -> dict:
        totals: dict = {}
        for d in self.devices:
            for op, ns in d.op_ns.items():
                totals[op] = totals.get(op, 0.0) + ns / len(self.devices)
        ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(((e - s, s, e) for d in self.devices[:1]
                       for s, e in d.gaps), reverse=True)[:top]
        return {"device_ops": [[op, ns / 1e9] for op, ns in ops],
                "idle_gaps": [[self.host_label(s, e), ln / 1e9]
                              for ln, s, e in gaps]}

    def host_label(self, start: float, end: float) -> str:
        """Innermost host event covering the middle of [start, end],
        under the job that holds it."""
        mid = (start + end) / 2
        covering = [(e - s, name) for s, e, name in self.host
                    if s <= mid <= e and name != WINDOW]
        if not covering:
            return "outside any host event"
        covering.sort()
        inner = covering[0][1]
        jobs = [n for _, n in covering if n.startswith("bench.")]
        return inner if not jobs or jobs[0] == inner else \
            f"{jobs[0]} > {inner}"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


_HLO = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)* = .*?\b([a-z][a-z\-]*)\(")


def op_key(event_name: str) -> str:
    """``<instruction base name>:<opcode>`` of one ``XLA Ops`` event, whose
    name is the instruction's HLO text: ``%fusion.56 = f32[...] fusion(...)``
    gives ``fusion:fusion``, a Pallas kernel ``frontier_expand:custom-call``.
    Numbering suffixes are dropped, so keys stay stable across compiles."""
    m = _HLO.match(event_name)
    if not m:
        return event_name[:64]
    return f"{m.group(1)}:{m.group(2)}"


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _exclusive(events):
    """Self time of nested intervals (a ``while`` holds its body's ops):
    [(start, end, key)] -> {key: ns not covered by a nested event}."""
    out: dict = {}
    stack: list = []        # [end, key, child_ns, start]

    def close(item):
        end, key, child, start = item
        out[key] = out.get(key, 0.0) + (end - start) - child
        if stack:
            stack[-1][2] += end - start

    for s, e, key in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        stack.append([e, key, 0.0, s])
    while stack:
        close(stack.pop())
    return out


def summarize(path: str) -> TraceSummary:
    """Read one ``.xplane.pb`` and reduce it to a :class:`TraceSummary`."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host, window = [], None
    device_planes = []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            device_planes.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = float(ev.start_ns)
                    e = s + float(ev.duration_ns)
                    host.append((s, e, ev.name))
                    if ev.name == WINDOW:
                        window = (s, e)
    if window is None:
        raise ValueError(f"no {WINDOW!r} annotation in {path}")
    w0, w1 = window
    host = [h for h in host if h[1] >= w0 and h[0] <= w1]
    devices = []
    for plane in sorted(device_planes, key=lambda p: p.name):
        events, modules = [], []
        for line in plane.lines:
            if line.name not in (_OPS_LINE, _MODULES_LINE):
                continue
            for ev in line.events:
                s = max(float(ev.start_ns), w0)
                e = min(float(ev.start_ns) + float(ev.duration_ns), w1)
                if e <= s:
                    continue
                if line.name == _MODULES_LINE:
                    modules.append((s, e, ev.name.split("(")[0]))
                else:
                    events.append((s, e, op_key(ev.name)))
        if not events:
            continue
        modules.sort()
        starts = [m[0] for m in modules]

        def module_of(t):
            i = bisect.bisect_right(starts, t) - 1
            return modules[i][2] if i >= 0 and modules[i][1] >= t else "?"

        events = [(s, e, f"{module_of(s)}/{k}") for s, e, k in events]
        busy = _union([(s, e) for s, e, _ in events])
        gaps, cur = [], w0
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < w1:
            gaps.append((cur, w1))
        devices.append(DeviceSummary(plane.name,
                                     sum(e - s for s, e in busy),
                                     _exclusive(events), gaps))
    if not devices:
        raise ValueError(f"no device plane with {_OPS_LINE!r} in {path}")
    return TraceSummary(w1 - w0, devices, host)
