"""host.batches_ms.p50: median over traced requests of the milliseconds
spent in the program's ``runtime.batches`` spans: the chunk's batch draw
in ``fed/runtime.py::run`` (the index draw, its host sync, the gather of
the devices' samples, the upload)."""
import statistics

from bench import spans


def read(run):
    if run.trace is None or run.trace["window"] is None:
        return None
    ms = spans.per_request_ms(run.trace, "runtime.batches")
    return statistics.median(ms) if ms else None
