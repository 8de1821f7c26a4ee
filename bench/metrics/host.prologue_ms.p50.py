"""host.prologue_ms.p50: median over traced requests of the milliseconds
spent in the program's ``runtime.prologue`` span: ``fed/runtime.py::run``
from entry to its first chunk (the channel and gain state's upload, the
PRNG keys, the donation copies of params and optimizer state)."""
import statistics

from bench import spans


def read(run):
    if run.trace is None or run.trace["window"] is None:
        return None
    ms = spans.per_request_ms(run.trace, "runtime.prologue")
    return statistics.median(ms) if ms else None
