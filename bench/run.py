"""Run one benchmark cell once on the chip this process finds.

    python3 -m bench.run --workload k20.fixed --seed 7 --seconds 20 --trace 0

Set-up builds the experiment from the seed, compiles its round program and
drives it through the first segments (which the reference then follows);
the window sends segment requests back to back for ``--seconds``; with
``--trace 1`` a short traced stretch follows the window.  After the
window the program's state is freed and the plain reference re-runs the
first segments: ``correct`` says whether every compared number lies within
its limit.  The last line of standard output is the result as one JSON
object; the compared numbers and their limits are also the last lines of
standard error.
"""
from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

from bench import cells, compare, workload  # noqa: E402
from bench import trace as tracelib  # noqa: E402

if str(cells.ROOT / "src") not in sys.path:
    sys.path.insert(0, str(cells.ROOT / "src"))



class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Run:
    """What a per-layer metric reader may read."""
    cell: cells.Cell
    device_kind: str
    setup: Dict[str, float]
    rounds_per_s: float
    trace: Optional[dict]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def configure_jax(config: dict):
    """The program's persistent compilation cache (``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` names another), every program
    cached, and the configuration's matmul precision."""
    import jax
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_default_matmul_precision",
                      config["matmul_precision"])
    return jax


def check_device(jax, chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoAccelerator(
            f"the cell needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform!r} device(s)")
    return devs


class CompileCounter:
    """While open, counts the programs XLA compiles: JAX's compile requests
    less those served by the persistent compilation cache."""

    def __init__(self):
        self.requests = 0
        self.cache_hits = 0

    @property
    def n(self) -> int:
        return self.requests - self.cache_hits

    def _on_duration(self, event: str, duration: float, **kwargs) -> None:
        if event.endswith("/backend_compile_duration"):
            self.requests += 1

    def _on_event(self, event: str, **kwargs) -> None:
        if event.endswith("/compilation_cache/cache_hits"):
            self.cache_hits += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


class GcWatch:
    """While open, records every collection of Python's garbage collector
    as (generation, start, end) on the harness's clock."""

    def __init__(self):
        self.collections: List[tuple] = []
        self._start: Dict[int, float] = {}

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._start[info["generation"]] = time.perf_counter()
        else:
            gen = info["generation"]
            self.collections.append((gen, self._start.pop(gen),
                                     time.perf_counter()))

    def __enter__(self):
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._on_gc)

    def summary(self, spans: List[tuple]) -> str:
        """Full collections in the window, and the slowest requests (over
        twice the median, at most three): their place in the window, wall
        and main-thread CPU milliseconds, and whether a full collection ran
        inside them.  ``spans`` holds (start, end, CPU seconds) per
        request."""
        full = [(s, e) for g, s, e in self.collections if g == 2]
        med = statistics.median(e - s for s, e, _ in spans)
        slow = sorted((sp for sp in enumerate(spans)
                       if sp[1][1] - sp[1][0] > 2 * med),
                      key=lambda sp: sp[1][0] - sp[1][1])[:3]
        pause = max((e - s for s, e in full), default=0.0)
        out = [f"{len(self.collections)} collections, {len(full)} full "
               f"(longest {1e3 * pause:.3f} ms)"]
        for i, (s, e, cpu) in slow:
            gc_in = any(s <= gs and ge <= e for gs, ge in full)
            out.append(f"request {i} at +{s - spans[0][0]:.3f} s: "
                       f"{1e3 * (e - s):.3f} ms, CPU {1e3 * cpu:.3f} ms"
                       f"{', full collection' if gc_in else ''}")
        return "; ".join(out)


def check_program(exp, config: dict) -> None:
    """Refuse a program that does not run the configuration as stated: its
    parameters' type and count."""
    import jax
    leaves = jax.tree_util.tree_leaves(exp.state.params)
    dtypes = sorted({str(x.dtype) for x in leaves})
    if dtypes != [config["dtype"]]:
        raise ValueError(f"parameters are {dtypes}, the configuration "
                         f"states {config['dtype']}")
    count = sum(x.size for x in leaves)
    if count != config["model"]["num_params"]:
        raise ValueError(f"{count} parameters, the configuration states "
                         f"{config['model']['num_params']}")


def _temp_bytes(client, before: set) -> int:
    """Largest scratch allocation of the programs compiled since
    ``before``: what the round program needs on top of the live buffers."""
    new = [e for e in client.live_executables() if id(e) not in before]
    return max((e.get_compiled_memory_stats().temp_size_in_bytes
                for e in new), default=0)


def start_checked(cell: cells.Cell, data_seed: int, fl_seed: int, dev):
    """Set-up: build the experiment, compile its round program and drive
    it through the checked first segments with the window's own request.
    Returns the experiment, the program's record of those segments (see
    ``compare``), the set-up times and the round program's temporaries."""
    import jax
    from repro.fl import Experiment

    traffic = cell.traffic
    rounds = traffic["segment_rounds"]
    spec = workload.make_spec(cell.config, traffic, data_seed, fl_seed)
    client = dev.client
    t0 = time.perf_counter()
    exp = Experiment(spec).setup()
    task_s = time.perf_counter() - t0
    check_program(exp, cell.config)
    before = {id(e) for e in client.live_executables()}
    t0 = time.perf_counter()
    prog = {"params0": jax.device_get(exp.state.params), "params": [],
            "diag": {}}
    for _ in range(traffic["check_segments"]):
        hist = exp.run(rounds, evaluate=traffic["eval"])
        prog["params"].append(jax.device_get(exp.state.params))
        for k in compare.GNORM_KEYS + ("update_norm",):
            prog["diag"].setdefault(k, []).extend(hist[k])
    compile_s = time.perf_counter() - t0
    return (exp, prog, {"task_s": task_s, "compile_s": compile_s},
            _temp_bytes(client, before))


def free_program() -> None:
    """Drop the program's cached tasks and round executables (the caller
    has dropped its experiment)."""
    from repro.fed import runtime
    from repro.fl.tasks import build_task
    runtime.clear_compile_caches()
    build_task.cache_clear()
    gc.collect()


def follow(ref, cell: cells.Cell, **kw) -> dict:
    """The reference's record of the checked segments."""
    t = cell.traffic
    return ref.follow(t["check_segments"] * t["segment_rounds"],
                      t["segment_rounds"],
                      t["spec"], **kw)


def execute(cell: cells.Cell, seed: int, seconds: float, trace: bool, *,
            require_accelerator: bool = True,
            start: Optional[float] = None,
            save_trace: Optional[str] = None) -> dict:
    """One run of ``cell``; returns the result object."""
    start = _PROCESS_START if start is None else start
    cfg, traffic = cell.config, cell.traffic
    jax = configure_jax(cfg)
    if require_accelerator:
        devs = check_device(jax, cell.chips)
    else:
        devs = jax.devices()
    dev = devs[0]
    from repro.fed import runtime

    data_seed, fl_seed = workload.derive_seeds(seed)
    rounds = traffic["segment_rounds"]
    in_use = [0]

    def read_memory() -> None:
        stats = dev.memory_stats()
        if stats:
            in_use[0] = max(in_use[0], stats["bytes_in_use"])

    with CompileCounter() as compiles:
        exp, prog, setup, temp = start_checked(cell, data_seed, fl_seed, dev)

        spans: List[tuple] = []

        def request() -> None:
            t0, c0 = time.perf_counter(), time.thread_time()
            exp.run(rounds, evaluate=traffic["eval"])
            spans.append((t0, time.perf_counter(), time.thread_time() - c0))

        # ---- the measured window
        setup_compiles = compiles.n
        traces0 = dict(runtime.TRACE_COUNTS)
        setup_s = time.perf_counter() - start
        with GcWatch() as collections:
            lat, window = workload.closed_loop(request, seconds, read_memory)
        retraces = runtime.trace_deltas(traces0)
    rounds_per_s = len(lat) * rounds / window
    med = statistics.median(lat)
    log(f"bench: setup {setup_s:.3f} s {setup}, {setup_compiles} compiles; "
        f"window {len(lat)} requests {window:.3f} s, compiles "
        f"{compiles.n - setup_compiles}, retraces {retraces}; request ms "
        f"p50 {1e3 * med:.3f} max {1e3 * max(lat):.3f}; garbage collector "
        f"and slowest requests: {collections.summary(spans)}")

    # ---- the traced stretch
    profile = None
    if trace:
        import jax.profiler

        def traced() -> None:
            def one() -> None:
                with jax.profiler.TraceAnnotation(tracelib.REQUEST_SPAN):
                    request()
            workload.closed_loop(one, traffic["trace_seconds"])
        profile = tracelib.record(traced)
        if save_trace:
            tracelib.save(tracelib.trim(profile, 3), save_trace)

    stats = dev.memory_stats() or {}
    peak_bytes = stats.get("peak_bytes_in_use")
    log(f"bench: bytes_in_use max {in_use[0]}, round program temporaries "
        f"{temp}, runtime peak {peak_bytes}")

    # ---- free the program, then the reference
    del exp, request
    free_program()
    t0 = time.perf_counter()
    ref = cells.reference(cfg).Reference(cfg, data_seed, fl_seed)
    nums = compare.numbers(prog, follow(ref, cell), ref.eval_loss)
    correct = compare.verdict(nums, cell.limits)
    log(f"bench: reference check {time.perf_counter() - t0:.3f} s, "
        f"numbers {json.dumps(nums)} (compared: {sorted(cell.limits)})")

    # ---- the result
    if trace:
        run = Run(cell, dev.device_kind, setup, rounds_per_s, profile)
        metrics = {}
        for m in cell.per_layer:
            value = cells.metric_reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = {"rounds_per_s": rounds_per_s,
               "segment_ms.p95": 1e3 * _p95(lat),
               "peak_hbm_gb": (in_use[0] + temp) / 1e9 if stats else None,
               "setup_s": setup_s}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if e2e[m["name"]] is not None}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": len(lat), "failed": 0,
              "metrics": metrics, "device": device}
    if trace and profile["window"] is not None:
        device["busy_s"] = tracelib.busy_seconds(profile)
        device["window_s"] = tracelib.window_seconds(profile)
        result["breakdown"] = tracelib.breakdown(profile)
    result["checks"] = {k: {"value": nums[k], "limit": lim}
                        for k, lim in cell.limits.items()}
    for k, c in result["checks"].items():
        log(f"check {k}: {c['value']!r} <= {c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}")
    return result


def _p95(values) -> float:
    """95th percentile (the inclusive quantile of Python's statistics)."""
    if len(values) < 2:
        return values[0]
    return statistics.quantiles(values, n=20, method="inclusive")[18]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--save-trace", default=None,
                    help="write the first traced requests' compact trace "
                         "(gzip JSON) here")
    args = ap.parse_args(argv)
    cell = cells.resolve(args.workload)
    try:
        result = execute(cell, args.seed, args.seconds, bool(args.trace),
                         save_trace=args.save_trace)
    except NoAccelerator as e:
        log(f"bench: {e}")
        return 3
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
