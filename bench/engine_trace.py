"""Read the serving engine's own spans and step records: the ``engine.*``
host spans of a profiler trace, the device idle time of a traced window
summed by the innermost host span open over it, and the engine's iteration
records of the traced window.

The engine opens a ``jax.profiler.TraceAnnotation`` named ``engine.<phase>``
around each phase of ``ServingEngine.step`` (``engine.step`` outermost;
chunk spans carry ``rid``, ``start`` and ``end``), and stamps each
``IterationRecord`` with the step's wall start and end (``wall_t0_s`` /
``wall_t1_s``, ``time.perf_counter``), the self seconds of each phase
(``host_s``) and the bytes that crossed (``h2d_bytes`` / ``d2h_bytes``).
A program that lacks them leaves every reader here with nothing to read.
"""
from __future__ import annotations

from bench.trace_reduce import Event, Profile, clip, covered_between, union

OUTSIDE = "outside bench spans"


def load_spans(path: str) -> list[Event]:
    """The ``engine.*`` host spans of an ``.xplane.pb`` trace, by start."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name.startswith("engine."):
                    out.append(Event(e.name, e.start_ns * 1e-9,
                                     (e.start_ns + e.duration_ns) * 1e-9,
                                     dict(e.stats)))
    return sorted(out, key=lambda e: e.start)


def idle_by_span(profile: Profile, lo: float, hi: float) -> dict[str, float]:
    """Device idle seconds in ``[lo, hi]`` (first device, as the idle gaps
    are read), each instant put down to the innermost host span open then:
    the latest-started, which for nested spans is the shortest. Sums to the
    window's idle seconds."""
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
            inner = max(opened, key=lambda e: (e.start, e.start - e.end),
                        default=None)
            name = inner.name if inner is not None else OUTSIDE
            out[name] = out.get(name, 0.0) + covered_between(
                idle, idle_starts, t_prev, t)
            t_prev = t
        if s is not None:
            if is_open:
                opened.append(s)
            else:
                opened.remove(s)
    return {k: v for k, v in out.items() if v > 0}


def window_steps(run) -> list | None:
    """The engine's iteration records whose step started in the traced part
    of the window, ``[run.w0, run.w1)``; None where the run kept no records
    or the program stamps no wall clock on them."""
    its = getattr(run, "iterations", None)
    if not its or not hasattr(its[0], "wall_t0_s"):
        return None
    return [r for r in its if run.in_window(r.wall_t0_s)]


def decode_steps(run) -> list | None:
    """``window_steps`` that ran a decode."""
    steps = window_steps(run)
    return None if steps is None else [r for r in steps if r.decode_batch]


def request_stamps(run) -> list | None:
    """Requests due in the window and not rejected (as ``ttft_p95_s``
    counts them), or None where the program stamps no wall clock on
    requests."""
    reqs = [r for r in run.due_in_window() if not r.rejected]
    if not reqs or getattr(reqs[0].req, "submitted_wall_s", None) is None:
        return None
    return reqs
