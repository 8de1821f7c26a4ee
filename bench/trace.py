"""Profiler traces: recording a traced stretch, reducing the profile to a
compact record, and the arithmetic over it (busy and idle time, gaps
between round programs, kernel calls, the breakdown).

The compact record is a plain dict, so a trace recorded on the chip can be
kept as a test fixture:

    {"window": [t0, t1],                      # ns, the traced requests
     "ops": [[name, start, dur, chip], ...],  # device "XLA Ops" events
     "modules": [[name, start, dur], ...],    # device "XLA Modules" events
     "host": [[name, start, dur], ...],       # the driving thread's spans
     "hlo": {name: instruction text},         # custom calls only
     "chips": n}

Times are nanoseconds on the profiler's clock, which host and device
events share.
"""
from __future__ import annotations

import glob
import gzip
import json
import re
import shutil
import tempfile
from typing import Callable, Dict, List, Optional, Sequence, Tuple

REQUEST_SPAN = "bench.request"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")

Interval = Tuple[float, float]


# ------------------------------------------------------------- recording


def record(body: Callable[[], None]) -> dict:
    """Run ``body`` under a profiler trace (Python tracer off, no HLO protos)
    and return the compact record.  The raw trace goes to a temporary
    directory that is removed afterwards."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            body()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
        return compact(jax.profiler.ProfileData.from_file(path))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _short(name: str) -> str:
    """``%fusion.3 = f32[..] fusion(..)`` -> ``fusion.3``."""
    if name.startswith("%") and " = " in name:
        return name[1:name.index(" = ")]
    return name


def compact(profile) -> dict:
    """Reduce a ``jax.profiler.ProfileData`` to the compact record."""
    ops: List[list] = []
    modules: List[list] = []
    hlo: Dict[str, str] = {}
    chips = 0
    host_lines = []
    for plane in profile.planes:
        if _DEVICE_PLANE.match(plane.name):
            chip = chips
            chips += 1
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        long = ev.name
                        name = _short(long)
                        if "custom-call" in long and name not in hlo:
                            hlo[name] = long
                        ops.append([name, ev.start_ns, ev.duration_ns, chip])
                elif line.name == "XLA Modules" and chip == 0:
                    modules.extend([ev.name, ev.start_ns, ev.duration_ns]
                                   for ev in line.events)
        elif plane.name.startswith("/host:CPU"):
            host_lines.extend(plane.lines)
    host: List[list] = []
    for line in host_lines:
        evs = [[ev.name, ev.start_ns, ev.duration_ns] for ev in line.events]
        if any(e[0] == REQUEST_SPAN for e in evs):
            host.extend(evs)
    reqs = [e for e in host if e[0] == REQUEST_SPAN]
    window = ([min(e[1] for e in reqs), max(e[1] + e[2] for e in reqs)]
              if reqs else None)
    return {"window": window, "ops": ops, "modules": modules, "host": host,
            "hlo": hlo, "chips": chips}


def trim(trace: dict, requests: int) -> dict:
    """The same record cut to its first ``requests`` traced requests (for a
    small fixture)."""
    reqs = sorted(e for e in trace["host"] if e[0] == REQUEST_SPAN)[:requests]
    t0, t1 = reqs[0][1], reqs[-1][1] + reqs[-1][2]

    def inside(evs):
        return [e for e in evs if e[1] >= t0 and e[1] + e[2] <= t1]
    ops = inside(trace["ops"])
    names = {e[0] for e in ops}
    return {"window": [t0, t1], "ops": ops,
            "modules": inside(trace["modules"]),
            "host": inside(trace["host"]),
            "hlo": {k: v for k, v in trace["hlo"].items() if k in names},
            "chips": trace["chips"]}


def save(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# ------------------------------------------------------------- arithmetic


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy(trace: dict, chip: int = 0) -> List[Interval]:
    """Busy intervals of one chip inside the traced window."""
    lo, hi = trace["window"]
    return clip(union([(s, s + d) for _, s, d, c in trace["ops"]
                       if c == chip]), lo, hi)


def busy_seconds(trace: dict) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    chips = max(trace["chips"], 1)
    return sum(e - s for c in range(chips)
               for s, e in busy(trace, c)) / 1e9 / chips


def window_seconds(trace: dict) -> float:
    lo, hi = trace["window"]
    return (hi - lo) / 1e9


def idle(trace: dict) -> List[Interval]:
    """Idle intervals of the first chip inside the traced window."""
    lo, hi = trace["window"]
    out, t = [], lo
    for s, e in busy(trace):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def round_program(trace: dict) -> Optional[str]:
    """The module that takes the most device time: the round program."""
    total: Dict[str, float] = {}
    for name, _, d in trace["modules"]:
        total[name] = total.get(name, 0.0) + d
    return max(total, key=total.get) if total else None


def gaps_between_round_programs(trace: dict) -> List[float]:
    """Device-idle nanoseconds between consecutive runs of the round
    program."""
    name = round_program(trace)
    if name is None:
        return []
    runs = sorted((s, s + d) for n, s, d in trace["modules"] if n == name)
    spans = busy(trace)
    out = []
    for (_, end), (start, _) in zip(runs, runs[1:]):
        inner = sum(e - s for s, e in clip(spans, end, start))
        out.append(max(start - end - inner, 0.0))
    return out


def request_count(trace: dict) -> int:
    """Traced requests (each ``bench.request`` span)."""
    return sum(1 for e in trace["host"] if e[0] == REQUEST_SPAN)


def kernel_calls(trace: dict, kernel: str) -> List[Tuple[float, str]]:
    """(duration ns, instruction text) of every call of the kernel whose
    stable name is ``kernel`` (the jitted wrapper's name)."""
    pat = re.compile(rf"^{re.escape(kernel)}(\.\d+)?$")
    return [(d, trace["hlo"][n]) for n, _, d, _ in trace["ops"]
            if pat.match(n) and n in trace["hlo"]]


def _self_times(ops: Sequence[list]) -> Dict[str, float]:
    """Per-name device time not covered by ops nested inside it."""
    out: Dict[str, float] = {}
    stack: List[list] = []          # [name, end, self time so far]
    for name, s, d, _ in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            n, _, own = stack.pop()
            out[n] = out.get(n, 0.0) + own
        if stack:
            stack[-1][2] -= d
        stack.append([name, s + d, d])
    while stack:
        n, _, own = stack.pop()
        out[n] = out.get(n, 0.0) + own
    return out


def _host_activity(trace: dict, t: float) -> str:
    """Innermost span of the driving thread that covers time ``t``."""
    best = None
    for name, s, d in trace["host"]:
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "between requests"


def breakdown(trace: dict, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle gaps
    named by what the host was doing in them; seconds."""
    lo, hi = trace["window"]
    ops = [o for o in trace["ops"] if o[3] == 0 and lo <= o[1] <= hi]
    selfs = sorted(_self_times(ops).items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle(trace), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, t / 1e9] for n, t in selfs],
            "idle_gaps": [[_host_activity(trace, (s + e) / 2), (e - s) / 1e9]
                          for s, e in gaps]}
