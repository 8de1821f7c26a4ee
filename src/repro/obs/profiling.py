"""Profiling hooks: the program's host spans, env-gated ``jax.profiler``
traces, and /proc RSS sampling.

:func:`span` is the program's one span mechanism: a
``jax.profiler.TraceAnnotation`` (a ``StepTraceAnnotation`` when given a
step number), recorded on the profiler's clock whenever any profiler trace
is active, whoever started it (this module, the benchmark harness,
TensorBoard's remote capture), and about a microsecond when none is.  The
engine names its host steps with it (``fl.run``, ``runtime.prologue``,
``runtime.chunk``, ``runtime.batches``, ``runtime.dispatch``,
``runtime.history_get``, ``runtime.eval``, ``runtime.epilogue``), so a
trace puts device-idle time down to the step the host was in.  Spans sit
at chunk granularity, outside traced code.

``REPRO_OBS_PROFILE`` starts a trace: set to a directory, it turns every
``Experiment.run`` into a profiler trace (``start_trace``/``stop_trace``
around the run) viewable in TensorBoard/Perfetto.  Unset,
``start_profile`` returns ``None``.

The RSS readers are the ``benchmarks/kscale_case.py`` /proc pattern promoted
to a library: ``VmHWM`` (peak) is a property of the current mm — exec-fresh,
unlike the fork-inherited ``ru_maxrss`` — and ``VmRSS`` (current) is the
per-chunk sample the recorder's ``chunk`` events carry.
"""
from __future__ import annotations

import os
import resource
from typing import Optional

PROFILE_ENV = "REPRO_OBS_PROFILE"

# one trace at a time: nested Experiment.run calls (sweep fallbacks) must
# not try to re-enter jax.profiler.start_trace
_ACTIVE = False


def profile_dir() -> Optional[str]:
    """The profiler output directory, or None when profiling is off."""
    return os.environ.get(PROFILE_ENV) or None


def _proc_status_mb(field: str) -> Optional[float]:
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith(field + ":"):
                    return float(line.split()[1]) / 1024.0
    except OSError:
        pass
    return None


def rss_mb() -> Optional[float]:
    """Current resident set (VmRSS) in MB; None off-/proc platforms."""
    return _proc_status_mb("VmRSS")


def peak_rss_mb() -> float:
    """This process's peak resident set in MB: exec-fresh ``VmHWM`` when
    /proc exists (fork-inherited ``ru_maxrss`` would report the launcher's
    high-water mark), ``ru_maxrss`` as the non-/proc fallback."""
    hwm = _proc_status_mb("VmHWM")
    if hwm is not None:
        return hwm
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def start_profile() -> Optional[str]:
    """Begin a ``jax.profiler`` trace when ``REPRO_OBS_PROFILE`` names a
    directory (and no trace is already active).  Returns the directory as
    the handle for :func:`stop_profile`, else None."""
    global _ACTIVE
    out = profile_dir()
    if out is None or _ACTIVE:
        return None
    import jax

    jax.profiler.start_trace(out)
    _ACTIVE = True
    return out


def stop_profile(handle: Optional[str]) -> None:
    """End the trace started by :func:`start_profile` (no-op on None)."""
    global _ACTIVE
    if handle is None:
        return
    import jax

    jax.profiler.stop_trace()
    _ACTIVE = False


def span(name: str, step: Optional[int] = None):
    """A host span named ``name`` (with ``step`` as its step number), recorded
    whenever a profiler trace is active.  Use as a context manager around
    host code only, never inside a traced function."""
    import jax

    if step is None:
        return jax.profiler.TraceAnnotation(name)
    return jax.profiler.StepTraceAnnotation(name, step_num=int(step))


def annotate_chunk(index: int):
    """The ``runtime.chunk`` span of one engine chunk (step number
    ``index``)."""
    return span("runtime.chunk", step=index)
