"""Named phases of the router's host work: profiler spans plus counters.

A phase is a ``jax.profiler.TraceAnnotation``: while a profiler runs it
lands on the profiler's host plane, on the same clock as the device
ops, so a trace says which phase the host was in while the device sat
idle.  With no profiler running it costs about as much as a
``nullcontext`` (under a microsecond), so phases are always on.

Each phase also adds to its ``PhaseCounters``: how many times it ran,
its total seconds, and how many runs took longer than ``SLOW_S``.  The
counters are cumulative and never reset; a reader takes the difference
of two ``snapshot()`` calls.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator

from jax.profiler import TraceAnnotation

# a routed batch takes milliseconds; a phase past this is a stall worth
# counting on its own
SLOW_S = 0.1


class PhaseCounters:
    """Cumulative count, seconds and slow runs per phase name.  Not
    locked: one owner thread runs the phases (the router's serving
    thread)."""

    def __init__(self):
        # phase name -> [runs, seconds, runs over SLOW_S]
        self._c: Dict[str, list] = {}

    @contextlib.contextmanager
    def phase(self, name: str, **meta) -> Iterator[None]:
        """Run the block as phase ``name``; ``meta`` rides the profiler
        span as its arguments."""
        t0 = time.perf_counter()
        try:
            with TraceAnnotation(name, **meta):
                yield
        finally:
            dt = time.perf_counter() - t0
            c = self._c.setdefault(name, [0, 0.0, 0])
            c[0] += 1
            c[1] += dt
            c[2] += dt > SLOW_S

    def count(self, name: str) -> int:
        """Finished runs of phase ``name`` so far."""
        return self._c.get(name, (0,))[0]

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """{phase: {"n", "seconds", "over_100ms"}} for every phase run."""
        return {k: {"n": n, "seconds": s, "over_100ms": slow}
                for k, (n, s, slow) in self._c.items()}
