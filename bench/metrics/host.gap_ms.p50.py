"""host.gap_ms.p50: median device-idle milliseconds between consecutive
runs of the round program in the traced stretch: the host loop's cost
per segment (batch indices, dispatch, the history ``device_get``)."""
import statistics

from bench import trace


def read(run):
    if run.trace is None or run.trace["window"] is None:
        return None
    gaps = trace.gaps_between_round_programs(run.trace)
    return statistics.median(gaps) / 1e6 if gaps else None
