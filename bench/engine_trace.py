"""Read the serving engine's own records: its iteration records of the
traced window and the wall stamps on its requests. (Its ``engine.*`` spans
are loaded with the benchmark's own by ``bench/trace_reduce.py``.)

The engine opens a ``jax.profiler.TraceAnnotation`` named ``engine.<phase>``
around each phase of ``ServingEngine.step`` (``engine.step`` outermost;
chunk spans carry ``rid``, ``start`` and ``end``), and stamps each
``IterationRecord`` with the step's wall start and end (``wall_t0_s`` /
``wall_t1_s``, ``time.perf_counter``), the self seconds of each phase
(``host_s``) and the bytes that crossed (``h2d_bytes`` / ``d2h_bytes``).
A program that lacks them leaves every reader here with nothing to read.
"""
from __future__ import annotations


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


def by_end(t: float | None, end: float) -> float:
    """A wall stamp as seen at ``end``: ``end`` where it is later or
    missing."""
    return end if t is None else min(t, end)


def request_stamps(run) -> list | None:
    """The run's requests that were not rejected (as ``ttft_p95_s`` counts
    them), or None where there are none or the program stamps no wall clock
    on requests."""
    reqs = [r for r in run.requests if not r.rejected]
    if not reqs or getattr(reqs[0].req, "submitted_wall_s", None) is None:
        return None
    return reqs
