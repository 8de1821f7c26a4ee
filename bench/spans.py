"""The program's own host spans in a compact trace record: how long each
traced request spent in one named step of the host loop.

The program names its host steps with ``repro.obs.profiling.span``
(``fl.run``, ``runtime.prologue``, ``runtime.batches``, ...), recorded on
the driving thread beside ``bench.request``, so ``trace.compact`` keeps
them.  A program without these spans yields no values, and its readers
report nothing.
"""
from __future__ import annotations

from typing import List

from bench.trace import REQUEST_SPAN


def per_request_ms(trace: dict, name: str) -> List[float]:
    """Milliseconds spent in spans named ``name`` inside each traced
    request, summed per request, for the requests that hold one; a span
    outside every request is not counted."""
    reqs = [(s, s + d) for n, s, d in trace["host"] if n == REQUEST_SPAN]
    spans = [(s, s + d) for n, s, d in trace["host"] if n == name]
    out = []
    for lo, hi in reqs:
        inside = [e - s for s, e in spans if lo <= s and e <= hi]
        if inside:
            out.append(sum(inside) / 1e6)
    return out
