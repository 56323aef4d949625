"""Device-idle time inside the program's own host spans.

With a profiler running, the training entry writes its phases as spans
named ``train.*`` (``repro.obs``, DESIGN.md §16) on the host line that holds
the benchmark's ``bench.*`` spans, so ``bench/trace.py:load`` puts them in
``Trace.host``, on the device ops' clock. The gap readers
(``bench/metrics/*_gap_ms_per_call.py``) ask how long the device idled
inside some of them.
"""
from __future__ import annotations

from typing import Iterable, Optional

from bench import trace


def idle_ms_per_call(tr: trace.Trace, lo: float, hi: float,
                     names: Iterable[str], minus: Iterable[str] = ()
                     ) -> Optional[float]:
    """Device-idle milliseconds inside the union of the host spans named
    ``names`` that start in ``[lo, hi]``, less the union of those named
    ``minus``, per ``bench.call`` span, averaged over devices. None where
    no call or no such span is in the window."""
    def union(which):
        which = set(which)
        return trace.merge((s, e) for n, s, e in tr.host
                           if n in which and lo <= s < hi)

    calls = [s for n, s, _ in tr.spans if n == "bench.call" and lo <= s < hi]
    inside = trace.subtract(union(names), union(minus))
    if not calls or not inside:
        return None
    idle = 0.0
    for d in tr.devices:
        merged = trace.busy(tr, d)
        idle += sum((e - s) - trace.covered(merged, s, e) for s, e in inside)
    return idle / len(tr.devices) / len(calls) / 1e6
