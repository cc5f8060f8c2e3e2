"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

* busy: the union of the intervals in which a program ran on a device,
  inside the traced window, averaged over the devices that ran any.
* device time by program: summed durations of each jitted program
  (``XLA Modules`` line of a device plane), names without their
  trailing ``(id)``.
* idle gaps: the stretches of the window in which no program ran, each
  named by the harness's host annotation (``engine.step``,
  ``idle.wait_arrival``, ``engine.submit``) that covers most of it,
  ``other`` where none does.

The window is the span of the ``bench.window`` annotation on the host.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

WINDOW = "bench.window"
HOST_SPANS = ("engine.step", "idle.wait_arrival", "engine.submit")
_ID = re.compile(r"\(\d+\)$")


def program_name(name: str) -> str:
    return _ID.sub("", name).strip()


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str):
    """Planes of a trace as plain data: {"device": {plane: [(name, s,
    e)]}, "host": [(name, s, e)]}, times in ns."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            line = lines.get("XLA Modules")
            if line is None:
                continue
            device[plane.name] = [(ev.name, ev.start_ns, ev.end_ns)
                                  for ev in line.events]
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == WINDOW or ev.name in HOST_SPANS:
                        host.append((ev.name, ev.start_ns, ev.end_ns))
    return {"device": device, "host": host}


def reduce(planes, top: int = 10) -> dict:
    """busy_s, window_s, by_program {name: s}, idle_gaps [[name, s]]
    (top ``top`` by seconds, summed by name), device_ops [[name, s]]."""
    win = [(s, e) for n, s, e in planes["host"] if n == WINDOW]
    if not win:
        raise ValueError(f"no {WINDOW} annotation in the trace")
    lo, hi = win[0]
    by_prog = defaultdict(float)
    busy_per_dev = []
    union_all = []
    for _plane, evs in planes["device"].items():
        iv = []
        for n, s, e in evs:
            c = _clip([(s, e)], lo, hi)
            if c:
                by_prog[program_name(n)] += (c[0][1] - c[0][0]) * 1e-9
                iv.append(c[0])
        if iv:
            u = _union(iv)
            busy_per_dev.append(sum(e - s for s, e in u) * 1e-9)
            union_all.extend(u)
    busy = sum(busy_per_dev) / len(busy_per_dev) if busy_per_dev else 0.0
    # idle gaps of the union of devices, named by the host span
    gaps, t = [], lo
    for s, e in _union(union_all):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    spans = [(n, s, e) for n, s, e in planes["host"] if n in HOST_SPANS]
    idle = defaultdict(float)
    for gs, ge in gaps:
        cover = defaultdict(int)
        for n, s, e in spans:
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                cover[n] += ov
        name = max(cover, key=cover.get) if cover else "other"
        idle[name] += (ge - gs) * 1e-9
    rank = lambda d: [[k, v] for k, v in sorted(d.items(),
                                                key=lambda kv: -kv[1])][:top]
    return {"busy_s": busy, "window_s": (hi - lo) * 1e-9,
            "by_program": dict(by_prog), "device_ops": rank(by_prog),
            "idle_gaps": rank(idle)}
