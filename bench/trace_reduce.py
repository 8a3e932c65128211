"""Reduce a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy time, per-op and per-program device time, per-step
device time, the longest device idle gaps with the host span that was open
during each, and the window's idle time summed by the innermost host span.

Sources inside the trace:

* device events: on a TPU, every plane ``/device:TPU:<n>``; its ``XLA Ops``
  line holds one event per executed operation, its ``XLA Modules`` line one
  event per executed program (jit name plus an id, e.g.
  ``jit_paged_decode_step(42)``). On the CPU backend (used to check this
  module), ops run on the ``tf_XLAPjRtCpuClient`` host threads and programs
  show as the ``PjitFunction(<name>)`` dispatch spans of the host thread.
* host spans: the benchmark's own ``jax.profiler.TraceAnnotation`` spans,
  all named ``bench.*`` (``bench.step`` carries the step's ``index``), and
  the serving engine's, named ``engine.*``, nested inside ``bench.step``.

Times are seconds; the traced window is the ``bench.window`` span.
"""
from __future__ import annotations

import bisect
import dataclasses
import re

Interval = tuple[float, float]          # (start_s, end_s)
# what idle time open under no host span is put down to
OUTSIDE = "outside bench spans"


@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float
    stats: dict


@dataclasses.dataclass
class Profile:
    """Events of one trace, split by source; ``ops`` and ``modules`` are per
    device (index = device number)."""
    ops: list[list[Event]]
    modules: list[list[Event]]
    spans: list[Event]


_CPU_NOISE = ("ThreadpoolListener", "SlinkyThreadPool", "ThunkExecutor",
              "end: ")


def load(path: str) -> Profile:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, spans = [], [], []
    cpu_ops: list[Event] = []
    cpu_modules: list[Event] = []

    def ev(e) -> Event:
        return Event(e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9, dict(e.stats))

    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            ops.append([ev(e) for e in lines["XLA Ops"].events]
                       if "XLA Ops" in lines else [])
            modules.append([ev(e) for e in lines["XLA Modules"].events]
                           if "XLA Modules" in lines else [])
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(("bench.", "engine.")):
                        spans.append(ev(e))
                    elif e.name.startswith("PjitFunction("):
                        cpu_modules.append(ev(e))
                    elif (ln.name.startswith("tf_XLAPjRtCpuClient")
                          and e.duration_ns > 0
                          and not e.name.startswith(_CPU_NOISE)):
                        cpu_ops.append(ev(e))
    if not ops:                          # CPU backend: the host is the device
        ops, modules = [cpu_ops], [cpu_modules]
    for lst in ops + modules:
        lst.sort(key=lambda e: e.start)
    spans.sort(key=lambda e: e.start)
    return Profile(ops, modules, spans)


def label(e: Event) -> str:
    """An op's name with its string stats (on a TPU these carry the jax name
    stack, where a Pallas kernel shows by its own name)."""
    return "|".join([e.name] + [v for v in e.stats.values()
                                if isinstance(v, str)])


def program_name(event_name: str) -> str:
    """``jit_paged_decode_step(42)`` / ``PjitFunction(paged_decode_step)``
    -> ``paged_decode_step``."""
    m = re.fullmatch(r"PjitFunction\((.*)\)", event_name)
    name = m.group(1) if m else re.sub(r"\(\d+\)$", "", event_name)
    return name[4:] if name.startswith("jit_") else name


def union(intervals: list[Interval]) -> list[Interval]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: list[Interval], lo: float, hi: float) -> list[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(intervals: list[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def covered_between(u: list[Interval], starts: list[float], lo: float,
                    hi: float) -> float:
    """Seconds of the sorted disjoint union ``u`` (``starts`` = its start
    times) that lie inside ``[lo, hi]``."""
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    j = bisect.bisect_left(starts, hi)
    return covered(clip(u[i:j], lo, hi))


@dataclasses.dataclass
class Reduced:
    window: Interval
    busy_s: float                        # mean over devices
    op_s: dict[str, float]               # device seconds per op name
    label_s: dict[str, float]            # per op name + its string stats
    program_s: dict[str, float]          # device seconds per program
    program_n: dict[str, int]
    steps: list[dict]                    # per bench.step span in the window
    gaps: list[tuple[str, float]]        # ``top`` longest idle gaps
    idle_by_span: dict[str, float]       # idle seconds by innermost span

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def _inner_name(open_spans) -> str:
    """Name of the innermost of the host spans open at one instant: the
    shortest (the earliest started among equals), ``OUTSIDE`` where none
    is."""
    best = min(open_spans, key=lambda e: (e.end - e.start, e.start),
               default=None)
    return best.name if best is not None else OUTSIDE


def innermost(spans: list[Event], t: float) -> str:
    """Name of the innermost host span (``bench.*`` or ``engine.*``) open
    at ``t``."""
    n = bisect.bisect_right([s.start for s in spans], t)  # sorted by start
    return _inner_name(s for s in spans[:n] if s.end >= t)


def idle_by_span(profile: Profile, lo: float, hi: float) -> dict[str, float]:
    """Device idle seconds in ``[lo, hi]`` (first device, as the idle gaps
    are read), each instant put down to the innermost host span open then,
    by ``innermost``'s rule. Sums to the window's idle seconds."""
    busy = union(clip([(e.start, e.end) for e in profile.ops[0]], lo, hi))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    idle_starts = [a for a, _ in idle]
    marks = []                      # (time, 0 = close / 1 = open, span)
    for s in profile.spans:
        if s.end > lo and s.start < hi:
            marks += [(max(s.start, lo), 1, s), (min(s.end, hi), 0, s)]
    marks.sort(key=lambda m: (m[0], m[1]))
    out: dict[str, float] = {}
    opened: list[Event] = []
    t_prev = lo
    for t, is_open, s in marks + [(hi, 0, None)]:
        if t > t_prev:
            name = _inner_name(opened)
            out[name] = out.get(name, 0.0) + covered_between(
                idle, idle_starts, t_prev, t)
            t_prev = t
        if s is not None:
            if is_open:
                opened.append(s)
            else:
                opened.remove(s)
    return {k: v for k, v in out.items() if v > 0}


def reduce(profile: Profile, min_gap_s: float = 1e-4,
           top: int = 10) -> Reduced:
    wins = [s for s in profile.spans if s.name == "bench.window"]
    if not wins:
        raise ValueError("trace holds no bench.window span")
    lo, hi = wins[0].start, wins[0].end
    busy, op_s, label_s, prog_s, prog_n = [], {}, {}, {}, {}
    dev_unions = []
    for dev_ops, dev_mods in zip(profile.ops, profile.modules):
        u = union(clip([(e.start, e.end) for e in dev_ops], lo, hi))
        dev_unions.append(u)
        busy.append(covered(u))
        for e in dev_ops:
            if lo <= e.start < hi:
                op_s[e.name] = op_s.get(e.name, 0.0) + e.end - e.start
                lab = label(e)
                label_s[lab] = label_s.get(lab, 0.0) + e.end - e.start
        for e in dev_mods:
            if lo <= e.start < hi:
                p = program_name(e.name)
                prog_s[p] = prog_s.get(p, 0.0) + e.end - e.start
                prog_n[p] = prog_n.get(p, 0) + 1
    starts = [[s for s, _ in u] for u in dev_unions]
    mod_starts = [[e.start for e in m] for m in profile.modules]
    steps = []
    for s in profile.spans:
        if s.name != "bench.step" or s.start < lo or s.end > hi:
            continue
        inside = [covered_between(u, st, s.start, s.end)
                  for u, st in zip(dev_unions, starts)]
        progs: dict[str, float] = {}
        for dev_mods, st in zip(profile.modules, mod_starts):
            for e in dev_mods[bisect.bisect_left(st, s.start):
                              bisect.bisect_left(st, s.end)]:
                p = program_name(e.name)
                progs[p] = progs.get(p, 0.0) + e.end - e.start
        steps.append({"index": s.stats.get("index"),
                      "host_s": s.end - s.start,
                      "device_busy_s": sum(inside) / len(inside),
                      "programs": progs})
    spans_ = []                          # (mid, length) of each idle gap
    for u in dev_unions[:1]:             # gaps are read on the first device
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        spans_ = [((a + b) / 2, b - a)
                  for a, b in zip(edges[0::2], edges[1::2])
                  if b - a >= min_gap_s]
    spans_.sort(key=lambda g: -g[1])
    gaps = [(innermost(profile.spans, mid), length)
            for mid, length in spans_[:top]]
    return Reduced((lo, hi), sum(busy) / max(len(busy), 1), op_s, label_s,
                   prog_s, prog_n, steps, gaps,
                   idle_by_span(profile, lo, hi))
