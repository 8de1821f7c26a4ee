"""CPU tests of the benchmark's definition: every cell resolves its files by
name, ``BENCHMARK.json`` keeps the contract's shape, seeds derive, and the
measurement path refuses to run without a TPU."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import types

import numpy as np
import pytest

from bench import cells, run, workload

BENCH = cells.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_its_files(name):
    cell = cells.resolve(name)
    assert cell.chips in (1, 4)
    assert cell.traffic["segment_rounds"] >= 1
    assert cell.limits and all(v > 0 for v in cell.limits.values())
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    ref = cells.reference(cell.config)
    assert hasattr(ref.Reference, "follow")
    for m in cell.per_layer:
        assert callable(cells.metric_reader(m["name"]).read)


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError, match="no workload"):
        cells.resolve("no.such.cell")


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/")
        with open(cells.ROOT / c["file"]) as f:
            data = json.load(f)
        assert all(k in data["spec"] for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert w["config"] in configs and len(w["why"]) <= 200
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    bounds = {m["name"]: m["bound"] for m in BENCH["end_to_end"]}
    assert 0.01 <= min(bounds.values()) and max(bounds.values()) <= 0.25


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5, 2 ** 40 + 5, -3])
def test_seeds_derive_within_key_range(seed):
    a, b = workload.derive_seeds(seed)
    assert 0 <= a < workload.SEED_LIMIT and 0 <= b < workload.SEED_LIMIT
    assert (a, b) == workload.derive_seeds(seed)


def test_large_seeds_stay_distinct():
    assert workload.derive_seeds(2 ** 31 + 5) != workload.derive_seeds(5)


@pytest.mark.parametrize("name", CELLS)
def test_every_spec_field_reaches_the_experiment(name):
    from repro.fl.spec import resolve_axis
    cell = cells.resolve(name)
    spec = workload.make_spec(cell.config, cell.traffic, 11, 12)
    fl = spec.fl_config()
    scopes = {"fl": fl, "channel": fl.channel, "data": spec.data,
              "model": spec.model}
    for key, value in {**cell.config["spec"], **cell.traffic["spec"]}.items():
        scope, field = resolve_axis(key)
        assert getattr(scopes[scope], field) == value, key
    assert (fl.seed, spec.data.seed) == (12, 11)
    assert spec.chunk_size == cell.traffic["segment_rounds"]
    assert spec.eval.enabled == cell.traffic["eval"]


@pytest.mark.parametrize("where, key", [("config", "no_such_field"),
                                        ("traffic", "no_such_field"),
                                        ("traffic", "num_devices")])
def test_a_spec_field_is_never_dropped(where, key):
    cell = cells.resolve(CELLS[0])
    config, traffic = dict(cell.config), dict(cell.traffic)
    part = config if where == "config" else traffic
    part["spec"] = dict(part["spec"], **{key: 1})
    with pytest.raises(ValueError):
        workload.make_spec(config, traffic, 1, 2)


@pytest.mark.parametrize("traffic", [{"clients": 4}, {"loop": "open"},
                                     {"rate_per_s": 10.0}])
def test_traffic_the_generator_cannot_send_is_refused(traffic):
    cell = cells.resolve(CELLS[0])
    with pytest.raises(ValueError):
        workload.make_spec(cell.config, dict(cell.traffic, **traffic), 1, 2)


@pytest.mark.parametrize("field", [{"participation": 0.25},
                                   {"local_steps": 8},
                                   {"server_opt": "adamw"},
                                   {"client.algo": "fedprox"}])
def test_reference_refuses_what_it_does_not_implement(field):
    cell = cells.resolve(CELLS[0])
    ref = cells.reference(cell.config)
    with pytest.raises(ValueError, match="reference"):
        ref.check_spec(dict(cell.config["spec"], **field))


@pytest.mark.parametrize("change, refused", [
    ({}, False), ({"dtype": np.float16}, True), ({"extra": 1}, True)])
def test_program_that_departs_from_the_configuration_is_refused(change,
                                                                 refused):
    config = cells.resolve(CELLS[0]).config
    n = config["model"]["num_params"]
    leaf = np.zeros(n + change.get("extra", 0),
                    change.get("dtype", np.float32))
    exp = types.SimpleNamespace(state=types.SimpleNamespace(
        params={"w": leaf}))
    if refused:
        with pytest.raises(ValueError):
            run.check_program(exp, config)
    else:
        run.check_program(exp, config)


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cells.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_gc_watch_records_collections():
    import gc
    with run.GcWatch() as watch:
        gc.collect()
    assert watch.collections and watch.collections[-1][0] == 2
    gen, start, end = watch.collections[-1]
    assert start <= end


def test_gc_watch_names_slow_requests_around_a_full_collection():
    watch = run.GcWatch()
    watch.collections = [(0, 0.5, 0.6), (2, 10.1, 10.4)]
    spans = [(0.0, 1.0, 0.9), (1.0, 2.0, 0.9), (2.0, 3.0, 0.9),
             (10.0, 14.0, 0.5), (14.0, 16.5, 2.4)]
    assert watch.summary(spans) == (
        "2 collections, 1 full (longest 300.000 ms); "
        "request 3 at +10.000 s: 4000.000 ms, CPU 500.000 ms, full "
        "collection; request 4 at +14.000 s: 2500.000 ms, CPU 2400.000 ms")
