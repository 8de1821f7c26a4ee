"""device_idle_share: percent of the traced stretch in which no operation
ran on the device (1 - union of busy intervals / window)."""
from bench import trace


def read(run):
    if run.trace is None or run.trace["window"] is None or not run.trace["ops"]:
        return None
    return 100.0 * (1.0 - trace.busy_seconds(run.trace)
                    / trace.window_seconds(run.trace))
