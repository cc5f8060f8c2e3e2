"""Idle gaps of a profiler trace named by the program's own spans.

``trace_reduce.reduce`` names each idle gap of the device by the
harness's host annotation; a gap under ``engine.step`` is host work the
harness cannot split.  The program marks its phases with ``koios.*``
annotations (``repro.runtime.instrument.span``).  ``idle_by_phase``
gives each instant of such a gap to the innermost program span over it
(the shortest one covering it; ``koios.step``, the whole step, is not a
phase); what no span covers stays ``engine.step``.  Every other gap
keeps the name ``trace_reduce`` gives it.

    planes = trace_reduce.load(path)
    gaps = phases.idle_by_phase(planes, phases.load_program(path))

``run.py`` does not call this yet: the result line's breakdown is
``trace_reduce.reduce``'s.
"""
from __future__ import annotations

from collections import defaultdict

import trace_reduce as tr

PROGRAM = "koios."               # prefix of the program's spans
PROGRAM_STEP = "koios.step"      # the whole step: not a phase
STEP = "engine.step"             # the harness's span that phases split


def load_program(path: str) -> list:
    """The host events named ``koios.*`` of a trace: [(name, s, e)] in
    ns, on the clock of ``trace_reduce.load``'s planes."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for ln in plane.lines:
                out.extend((ev.name, ev.start_ns, ev.end_ns)
                           for ev in ln.events
                           if ev.name.startswith(PROGRAM))
    return out


def idle_gaps(planes) -> list:
    """[(start, end, name)] of the window's idle stretches, named as
    ``trace_reduce.reduce`` names them."""
    lo, hi = next((s, e) for n, s, e in planes["host"] if n == tr.WINDOW)
    busy = []
    for evs in planes["device"].values():
        busy.extend(tr._clip([(s, e) for _, s, e in evs], lo, hi))
    gaps, t = [], lo
    for s, e in tr._union(busy):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    spans = [(n, s, e) for n, s, e in planes["host"] if n in tr.HOST_SPANS]
    named = []
    for gs, ge in gaps:
        cover = defaultdict(int)
        for n, s, e in spans:
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                cover[n] += ov
        named.append((gs, ge, max(cover, key=cover.get) if cover
                      else "other"))
    return named


def idle_by_phase(planes, program, top: int = 30) -> list:
    """[[name, seconds]] of the idle time, largest first (top ``top``):
    ``engine.step`` gaps split by the innermost program span."""
    lo, hi = next((s, e) for n, s, e in planes["host"] if n == tr.WINDOW)
    phases = sorted((s, e, n) for n, s, e in program
                    if n != PROGRAM_STEP and e > lo and s < hi)
    idle = defaultdict(float)
    for gs, ge, name in idle_gaps(planes):
        if name != STEP:
            idle[name] += (ge - gs) * 1e-9
            continue
        inside = [(s, e, n) for s, e, n in phases if s < ge and e > gs]
        cuts = sorted({gs, ge} | {min(max(t, gs), ge)
                                 for s, e, _ in inside for t in (s, e)})
        for a, b in zip(cuts, cuts[1:]):
            over = [(e - s, -s, n) for s, e, n in inside
                    if s <= a and e >= b]
            idle[min(over)[2] if over else STEP] += (b - a) * 1e-9
    return [[k, v] for k, v in sorted(idle.items(),
                                      key=lambda kv: -kv[1])][:top]
