"""CPU tests of the readers of the program's host spans
(``host.prologue_ms.p50``, ``host.batches_ms.p50``): exact medians on a
synthetic trace, nothing on a trace without the spans (the program before
them), and, on traces recorded on the chip with the spans
(``bench/fixtures/<cell>.spans.trace.json.gz``), a value from every reader
and program spans under nearly all of the device-idle time of a request."""
from __future__ import annotations

import math

import pytest

from bench import cells, run, spans
from bench import trace as tracelib

KIND = "TPU v5 lite"
CELLS = ("k20.fixed", "k1024.fading", "k1024.fixed")
READERS = ("host.prologue_ms.p50", "host.batches_ms.p50")
MS = 1_000_000            # ns


def _run(name: str, trace: dict) -> run.Run:
    return run.Run(cells.resolve(name), KIND,
                   {"task_s": 1.5, "compile_s": 30.0}, 150.0, trace)


def _fixture(name: str) -> dict:
    return tracelib.load(str(cells.HERE / "fixtures"
                             / f"{name}.spans.trace.json.gz"))


def _synthetic() -> dict:
    # three requests; the third holds no program span.  Prologues of 7 and
    # 4 ms, batches of 2 + 3 and 6 ms; one span of each outside every
    # request, which must not count
    host = [["bench.request", 0, 100 * MS], ["bench.request", 200 * MS,
                                             100 * MS],
            ["bench.request", 400 * MS, 50 * MS],
            ["runtime.prologue", 10 * MS, 7 * MS],
            ["runtime.prologue", 210 * MS, 4 * MS],
            ["runtime.prologue", 150 * MS, 40 * MS],
            ["runtime.batches", 20 * MS, 2 * MS],
            ["runtime.batches", 30 * MS, 3 * MS],
            ["runtime.batches", 220 * MS, 6 * MS],
            ["runtime.batches", 310 * MS, 50 * MS]]
    return {"window": [0, 450 * MS], "chips": 1, "ops": [], "modules": [],
            "host": host, "hlo": {}}


def test_readers_take_the_median_over_requests_holding_the_span():
    t = _synthetic()
    assert spans.per_request_ms(t, "runtime.prologue") == [7.0, 4.0]
    assert spans.per_request_ms(t, "runtime.batches") == [5.0, 6.0]
    r = _run("k1024.fixed", t)
    assert cells.metric_reader("host.prologue_ms.p50").read(r) == 5.5
    assert cells.metric_reader("host.batches_ms.p50").read(r) == 5.5


@pytest.mark.parametrize("reader", READERS)
def test_readers_report_nothing_without_the_spans(reader):
    t = _synthetic()
    t["host"] = [e for e in t["host"] if e[0] == "bench.request"]
    assert cells.metric_reader(reader).read(_run("k1024.fixed", t)) is None
    assert cells.metric_reader(reader).read(_run("k1024.fixed", None)) is None


@pytest.mark.parametrize("name", CELLS)
def test_every_reader_reads_the_trace_with_spans(name):
    r = _run(name, _fixture(name))
    for m in r.cell.per_layer:
        value = cells.metric_reader(m["name"]).read(r)
        assert value is not None and math.isfinite(value), m["name"]
        if m["unit"] == "%":
            assert 0.0 < value <= 100.0, (m["name"], value)
    gap = cells.metric_reader("host.gap_ms.p50").read(r)
    for reader in READERS:
        value = cells.metric_reader(reader).read(r)
        assert value is not None and 0.0 < value < gap, (reader, value, gap)


def _idle_in_requests(trace: dict):
    reqs = [(s, s + d) for n, s, d in trace["host"]
            if n == tracelib.REQUEST_SPAN]
    return [iv for lo, hi in reqs
            for iv in tracelib.clip(tracelib.idle(trace), lo, hi)]


def _minus(intervals, cover):
    """The parts of ``intervals`` that no interval of ``cover`` holds."""
    out = []
    for s, e in intervals:
        t = s
        for cs, ce in tracelib.clip(cover, s, e):
            if cs > t:
                out.append((t, cs))
            t = max(t, ce)
        if e > t:
            out.append((t, e))
    return out


@pytest.mark.parametrize("name", CELLS)
def test_program_spans_cover_the_idle_time_of_a_request(name):
    t = _fixture(name)
    idle = _idle_in_requests(t)
    total = sum(e - s for s, e in idle)
    assert total > 0
    steps = tracelib.union([(s, s + d) for n, s, d in t["host"]
                            if n.startswith("runtime.")])
    uncovered = sum(e - s for s, e in _minus(idle, steps))
    assert uncovered / total <= 0.10
    program = tracelib.union([(s, s + d) for n, s, d in t["host"]
                              if n == "fl.run" or n.startswith("runtime.")])
    assert max((e - s for s, e in _minus(idle, program)), default=0) < MS
