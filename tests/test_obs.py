"""Flight-recorder contract tests (PR 10).

The load-bearing contract is **trajectory invisibility**: attaching any
telemetry sink to a run must leave params and history bitwise-identical —
the parity classes pin that on ``repro.obs.params_sha256`` digests across
both drivers, both single-host backends, ``k_block`` streaming, and
``device_mesh`` sharded streaming (emulated on this 1-device host).  Around
it: the sink registry and event schema, the post-hoc ``dump_history`` ==
live-JSONL equivalence, ``SweepResult.dump``/``curves``/``manifest``, the
``TRACE_KINDS`` retrace accounting, the live-metrics HTTP endpoint, the
mesh train-step instrumentation wrapper, and the ``benchmarks.compare
--manifest`` structural-signature cross-check (CLI, like test_lint's
self-test checks).
"""
import glob
import json
import pathlib
import re
import subprocess
import sys
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.channel import ChannelConfig
from repro.fed import runtime as rt
from repro.fl import (DataSpec, EvalSpec, Experiment, ExperimentSpec,
                      ModelSpec, SweepSpec, run_sweep)

ROOT = pathlib.Path(__file__).resolve().parent.parent
K = 4
ROUNDS = 8


def ridge_spec(**fl_kw):
    fl = dict(num_devices=K, scheme="normalized", case="II", eta=0.01,
              channel=ChannelConfig(num_devices=K, channel_mean=1e-3),
              grad_bound=25.0, s_target=0.995, smoothness_L=2.0,
              strong_convexity_M=0.5, seed=0)
    fl.update(fl_kw)
    return ExperimentSpec(
        fl=rt.FLConfig(**fl),
        data=DataSpec(dataset="ridge", split="iid", num_train=200, dim=8,
                      batch_size=16, seed=3),
        model=ModelSpec(kind="ridge"),
        eval=EvalSpec(every=5), chunk_size=3)


def run_pair(spec, rounds=ROUNDS, **run_kw):
    """(experiment, history) without a recorder, then the same spec with a
    MemoryRecorder attached."""
    e_off = Experiment(spec)
    h_off = e_off.run(rounds, **run_kw)
    rec = obs.MemoryRecorder()
    e_on = Experiment(spec)
    h_on = e_on.run(rounds, recorder=rec, **run_kw)
    return e_off, h_off, e_on, h_on, rec


def assert_invisible(spec, rounds=ROUNDS, **run_kw):
    e_off, h_off, e_on, h_on, rec = run_pair(spec, rounds, **run_kw)
    assert (obs.params_sha256(e_on.state.params)
            == obs.params_sha256(e_off.state.params))
    assert h_on == h_off
    return rec


class TestBitwiseInvisibility:
    """Recorder on vs off: identical params digests and history, across
    every driver/backend/streaming combination."""

    @pytest.mark.parametrize("driver", ("scan", "python"))
    @pytest.mark.parametrize("backend", ("vmap", "kernels"))
    def test_driver_backend_matrix(self, driver, backend):
        rec = assert_invisible(ridge_spec(backend=backend), driver=driver)
        assert rec.select("manifest") and rec.select("chunk")
        assert len(rec.select("round")) == ROUNDS

    def test_k_block_streaming(self):
        rec = assert_invisible(ridge_spec(k_block=2))
        assert len(rec.select("round")) == ROUNDS

    def test_device_mesh_sharded(self):
        # 1 local device -> the engine's emulated sharded path (bitwise-
        # identical to the physical one by its own contract)
        rec = assert_invisible(ridge_spec(k_block=2, device_mesh=2))
        assert len(rec.select("round")) == ROUNDS

    def test_sink_choice_invisible(self, tmp_path):
        # jsonl/csv/null produce the same trajectory as recorder-off
        e0 = Experiment(ridge_spec())
        e0.run(ROUNDS)
        d0 = obs.params_sha256(e0.state.params)
        for rec in (obs.make("null"),
                    obs.make("jsonl", path=str(tmp_path / "r.jsonl")),
                    obs.make("csv", path=str(tmp_path / "r.csv"))):
            e = Experiment(ridge_spec())
            with rec:
                e.run(ROUNDS, recorder=rec)
            assert obs.params_sha256(e.state.params) == d0

    def test_batched_sweep_invisible(self):
        sweep = SweepSpec(ridge_spec(), {"eta": (0.01, 0.02),
                                         "seed": (0, 1)})
        res_off = run_sweep(sweep, ROUNDS)
        rec = obs.MemoryRecorder()
        res_on = run_sweep(sweep, ROUNDS, recorder=rec)
        assert res_off.params_sha256() is not None
        assert res_on.params_sha256() == res_off.params_sha256()
        # batched rounds carry one [E] lane list per diagnostic
        row = rec.select("round")[0]
        assert isinstance(row["grad_norm_mean"], list)
        assert len(row["grad_norm_mean"]) == sweep.size

    def test_sequential_sweep_invisible(self):
        sweep = SweepSpec(ridge_spec(), {"eta": (0.01, 0.02)})
        res_off = run_sweep(sweep, ROUNDS, vectorized=False)
        rec = obs.MemoryRecorder()
        res_on = run_sweep(sweep, ROUNDS, vectorized=False, recorder=rec)
        assert res_on.params_sha256() == res_off.params_sha256()
        # batched and sequential agree on the combined digest too
        assert (run_sweep(sweep, ROUNDS).params_sha256()
                == res_off.params_sha256())


class TestEventStream:
    def test_chunk_events_cover_all_rounds(self):
        rec = obs.MemoryRecorder()
        e = Experiment(ridge_spec())
        e.run(ROUNDS, recorder=rec)
        chunks = rec.select("chunk")
        covered = []
        for c in chunks:
            assert c["round_end"] >= c["round_start"]
            assert c["dispatches"] >= 1
            assert c["wall_time_s"] > 0
            assert isinstance(c["retraces"], dict)
            assert set(c["retraces"]) == set(rt.TRACE_KINDS)
            covered.extend(range(c["round_start"], c["round_end"] + 1))
        assert covered == [r["round"] for r in rec.select("round")]
        assert covered == list(range(1, ROUNDS + 1))

    def test_eval_events_follow_schedule(self):
        rec = obs.MemoryRecorder()
        Experiment(ridge_spec()).run(10, recorder=rec)
        # the engine evaluates at the first round, then every `every` rounds
        assert [ev["round"] for ev in rec.select("eval")] == [1, 5, 10]
        assert "gap" in rec.select("eval")[0]

    def test_round_events_match_history(self):
        rec = obs.MemoryRecorder()
        e = Experiment(ridge_spec())
        hist = e.run(ROUNDS, recorder=rec)
        rows = rec.select("round")
        for k in rt.DIAG_KEYS:
            assert [r[k] for r in rows] == [float(v) for v in hist[k]]

    def test_dump_history_matches_live_jsonl(self, tmp_path):
        live, post = tmp_path / "live.jsonl", tmp_path / "post.jsonl"
        e = Experiment(ridge_spec())
        with obs.JsonlRecorder(str(live)) as rec:
            e.run(ROUNDS, recorder=rec)
        e.dump_history(str(post))
        lv = [json.loads(s) for s in open(live)]
        pv = [json.loads(s) for s in open(post)]
        for kind in ("round", "eval"):
            assert ([x for x in lv if x["event"] == kind]
                    == [x for x in pv if x["event"] == kind])
        assert pv[0]["event"] == "manifest"
        # the post-hoc manifest reflects the run's END state
        assert pv[0]["manifest"]["round"] == ROUNDS


class TestSinks:
    def test_registry(self):
        assert {"null", "memory", "jsonl", "csv"} <= set(obs.names())
        assert isinstance(obs.make("memory"), obs.MemoryRecorder)
        with pytest.raises(KeyError, match="unknown recorder"):
            obs.get("nope")

    def test_memory_latest(self):
        rec = obs.MemoryRecorder()
        rec.on_manifest({"manifest_version": 1})
        rec.on_round(1, {"grad_norm_mean": 2.0})
        rec.on_round(2, {"grad_norm_mean": 1.0})
        snap = rec.latest()
        assert snap["events"] == 3
        assert snap["round"]["round"] == 2
        assert snap["eval"] is None

    def test_jsonl_buffers_until_flush(self, tmp_path):
        path = tmp_path / "r.jsonl"
        rec = obs.JsonlRecorder(str(path), flush_every=1000)
        for t in range(5):
            rec.on_round(t, {"x": float(t)})
        assert path.read_text() == ""          # still buffered
        rec.close()
        lines = [json.loads(s) for s in path.read_text().splitlines()]
        assert [ln["x"] for ln in lines] == [0.0, 1.0, 2.0, 3.0, 4.0]

    def test_csv_round_table(self, tmp_path):
        path = tmp_path / "r.csv"
        with obs.CsvRecorder(str(path)) as rec:
            rec.on_manifest({"manifest_version": 1})   # ignored by csv
            rec.on_round(1, {"grad_norm_mean": 2.5})
            rec.on_round(2, {"grad_norm_mean": 1.5})
        lines = path.read_text().splitlines()
        assert lines[0] == "round,grad_norm_mean"
        assert len(lines) == 3

    def test_chunk_fanout_batched_lanes(self):
        rec = obs.MemoryRecorder()
        rec.on_chunk(0, [1, 2], {"g": np.arange(6.0).reshape(3, 2)})
        rows = rec.select("round")
        assert rows[0]["g"] == [0.0, 2.0, 4.0]      # [E] lanes of round 1
        assert rows[1]["g"] == [1.0, 3.0, 5.0]


class TestSweepResult:
    def test_dump_and_curves(self, tmp_path):
        sweep = SweepSpec(ridge_spec(), {"s_target": (0.98, 0.995),
                                         "seed": (0, 1)})
        res = run_sweep(sweep, 10)
        assert all(len(d) == 64 for d in res.params_digests)
        curves = res.curves("s_target", "gap")
        assert set(curves) == {"0.98", "0.995"}
        c = curves["0.98"]
        assert c["round"] == list(res.eval_rounds)
        assert len(c["gap"]) == len(c["gap_std"]) == len(res.eval_rounds)
        assert c["seeds"] == 2

        path = tmp_path / "sweep.json"
        res.dump(str(path))
        d = json.load(open(path))
        assert d["manifest"]["structural_signature"]
        assert d["manifest"]["params_sha256"] == res.params_sha256()
        assert d["shape"] == [2, 2]
        assert d["params_digests"] == res.params_digests
        assert set(d["bands"]) == set(res.history)
        mean, _ = res.band("gap", over="seed")
        assert d["bands"]["gap"]["mean"] == mean.tolist()


class TestManifest:
    def test_experiment_manifest_fields(self):
        e = Experiment(ridge_spec())
        m = e.manifest()
        for key in ("manifest_version", "jax_version", "numpy_version",
                    "platform", "backend", "local_devices", "spec",
                    "config_sha256", "structural_signature", "params_sha256",
                    "round"):
            assert key in m, key
        assert m["round"] == 0
        assert m["spec"]["fl"]["num_devices"] == K

    def test_structural_signature_collapses_batched_fields(self):
        def sig(spec):
            return obs.structural_signature(spec.fl_config())
        # batched lanes (seed, eta) keep the signature; structural knobs
        # (k_block) change it
        assert sig(ridge_spec(seed=0)) == sig(ridge_spec(seed=7))
        assert sig(ridge_spec(eta=0.01)) == sig(ridge_spec(eta=0.05))
        assert sig(ridge_spec()) != sig(ridge_spec(k_block=2))

    def test_config_sha_deterministic_and_sensitive(self):
        assert obs.config_sha256(ridge_spec()) == obs.config_sha256(
            ridge_spec())
        assert obs.config_sha256(ridge_spec()) != obs.config_sha256(
            ridge_spec(eta=0.02))

    def test_params_digest_tracks_training(self):
        e = Experiment(ridge_spec())
        d0 = obs.params_sha256(e.params)
        assert d0 == obs.params_sha256(e.params)
        e.run(4)
        assert obs.params_sha256(e.state.params) != d0


class TestTraceAccounting:
    def test_count_trace_rejects_unknown_kind(self):
        with pytest.raises(ValueError, match="unknown trace kind"):
            rt._count_trace("mystery_builder")

    def test_counts_stay_within_kinds(self):
        assert set(rt.TRACE_COUNTS) <= set(rt.TRACE_KINDS)

    def test_cache_info_reports_deltas_since_last_call(self):
        rt.clear_compile_caches()
        Experiment(ridge_spec()).run(4)
        info = rt.cache_info()
        assert set(info["traces_delta"]) == set(rt.TRACE_KINDS)
        assert info["traces_delta"]["run_chunk"] >= 1
        again = rt.cache_info()
        assert all(v == 0 for v in again["traces_delta"].values())


class TestProfiling:
    def test_rss_sampling(self):
        assert obs.profiling.rss_mb() > 0
        assert obs.profiling.peak_rss_mb() >= obs.profiling.rss_mb() * 0.5

    def test_disabled_without_env(self, monkeypatch):
        monkeypatch.delenv(obs.profiling.PROFILE_ENV, raising=False)
        assert obs.profiling.profile_dir() is None
        assert obs.profiling.start_profile() is None
        with obs.profiling.annotate_chunk(0):
            pass


def traced_host_spans(tmp_path, body):
    """Run ``body`` under a profiler trace started here (as an outside tool
    would) and return the host plane's events as (name, start, end) ns."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    prof = jax.profiler.ProfileData.from_file(path)
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in prof.planes if plane.name.startswith("/host:CPU")
            for line in plane.lines for ev in line.events]


def assert_nested(spans, outer, inner, every=True):
    """Every ``inner`` span lies inside some ``outer`` span, and (``every``)
    every ``outer`` span holds at least one ``inner`` span."""
    outs = [(s, e) for n, s, e in spans if n == outer]
    ins = [(s, e) for n, s, e in spans if n == inner]
    assert outs and ins, (outer, inner)
    assert all(any(os <= s and e <= oe for os, oe in outs) for s, e in ins)
    if every:
        assert all(any(os <= s and e <= oe for s, e in ins)
                   for os, oe in outs)


class TestProgramSpans:
    """The engine's host spans are recorded under any active profiler trace,
    with ``REPRO_OBS_PROFILE`` unset, nested as the host loop runs them."""

    @pytest.mark.parametrize("driver", ("scan", "python"))
    def test_experiment_run_spans(self, tmp_path, monkeypatch, driver):
        monkeypatch.delenv(obs.profiling.PROFILE_ENV, raising=False)
        e = Experiment(ridge_spec())
        e.setup()
        spans = traced_host_spans(tmp_path,
                                  lambda: e.run(ROUNDS, driver=driver))
        for inner in ("runtime.prologue", "runtime.chunk",
                      "runtime.epilogue"):
            assert_nested(spans, "fl.run", inner)
        for inner in ("runtime.batches", "runtime.dispatch",
                      "runtime.history_get"):
            assert_nested(spans, "runtime.chunk", inner)
        # eval at t = 1 and every 5th round, inside the chunk that ends there
        assert sum(n == "runtime.eval" for n, _, _ in spans) == 2
        assert_nested(spans, "runtime.chunk", "runtime.eval", every=False)
        chunks = sum(n == "runtime.chunk" for n, _, _ in spans)
        assert chunks == (ROUNDS if driver == "python" else len(
            rt._plan_chunks(0, ROUNDS, 5, 3)))

    def test_run_batched_spans(self, tmp_path, monkeypatch):
        monkeypatch.delenv(obs.profiling.PROFILE_ENV, raising=False)
        sweep = SweepSpec(ridge_spec(), {"eta": (0.01, 0.02)})
        spans = traced_host_spans(tmp_path, lambda: run_sweep(sweep, ROUNDS))
        names = {n for n, _, _ in spans}
        assert {"runtime.prologue", "runtime.epilogue"} <= names
        for inner in ("runtime.batches", "runtime.dispatch",
                      "runtime.history_get"):
            assert_nested(spans, "runtime.chunk", inner)
        assert_nested(spans, "runtime.chunk", "runtime.eval", every=False)

    @pytest.mark.parametrize("driver", ("scan", "python"))
    def test_trace_is_trajectory_invisible(self, tmp_path, driver):
        spec = ridge_spec(channel=ChannelConfig(
            num_devices=K, channel_mean=1e-3, block_fading=True))
        e_off = Experiment(spec)
        h_off = e_off.run(ROUNDS, driver=driver)
        e_on = Experiment(spec)
        e_on.setup()
        h_on = {}
        traced_host_spans(tmp_path, lambda: h_on.update(
            e_on.run(ROUNDS, driver=driver)))
        assert (obs.params_sha256(e_on.state.params)
                == obs.params_sha256(e_off.state.params))
        assert h_on == h_off


STAGES = ("fading_refresh", "local_step", "aggregate", "server_update")


def round_program_hlo(monkeypatch, spec) -> str:
    """The optimized HLO text of the round program that ``Experiment.run``
    compiles for ``spec`` (lowered from the same arguments)."""
    texts = []
    build = rt._make_run_chunk

    def spy(*a, **kw):
        fn = build(*a, **kw)

        def call(*args):
            texts.append(fn.lower(*args).compile().as_text())
            return fn(*args)
        return call

    monkeypatch.setattr(rt, "_make_run_chunk", spy)
    Experiment(spec).run(3, evaluate=False)
    return texts[0]


class TestStageScopes:
    """Each stage of the round program carries its ``jax.named_scope`` in
    the compiled ops' ``op_name`` metadata, on the dense and streamed
    paths."""

    @pytest.mark.parametrize("k_block", (None, 2))
    def test_every_stage_names_ops(self, monkeypatch, k_block):
        spec = ridge_spec(k_block=k_block, channel=ChannelConfig(
            num_devices=K, channel_mean=1e-3, block_fading=True))
        hlo = round_program_hlo(monkeypatch, spec)
        op_names = re.findall(r'op_name="([^"]*)"', hlo)
        for stage in STAGES:
            assert any(f"/{stage}/" in n for n in op_names), stage
        if k_block is not None:
            # the K-block scan is the aggregation's: its loop carries it
            loops = re.findall(r'= [^\n]* while\([^\n]*op_name="([^"]*)"',
                               hlo)
            assert any("/aggregate/" in n for n in loops)


class TestLiveMetrics:
    def test_serve_metrics_endpoint(self):
        from repro.launch.serve import serve_metrics
        rec = obs.MemoryRecorder()
        Experiment(ridge_spec()).run(ROUNDS, recorder=rec)
        server = serve_metrics(rec)
        try:
            host, port = server.server_address
            body = json.loads(urllib.request.urlopen(
                f"http://{host}:{port}/metrics", timeout=10).read())
            assert body["round"]["round"] == ROUNDS
            assert body["events"] == len(rec.events)
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(f"http://{host}:{port}/other",
                                       timeout=10)
        finally:
            server.shutdown()
            server.server_close()


class TestTrainInstrumentation:
    def test_wrapper_is_passthrough_and_records(self):
        from repro.launch.train import instrument_train_step

        def step(params, opt_state, batch, rng):
            return params + 1, opt_state, {"loss": jnp.float32(0.5)}

        rec = obs.MemoryRecorder()
        wrapped = instrument_train_step(step, rec,
                                        manifest={"manifest_version": 1})
        p, _, m = wrapped(jnp.zeros(()), None, None, None)
        p, _, m = wrapped(p, None, None, None)
        assert float(p) == 2.0
        assert float(m["loss"]) == 0.5
        assert [e["event"] for e in rec.events] == [
            "manifest", "chunk", "round", "chunk", "round"]
        assert rec.select("round")[1] == {"event": "round", "round": 1,
                                          "loss": 0.5}


class TestCompareManifest:
    def _compare(self, tmp_path, base, fresh, *flags):
        bdir, fdir = tmp_path / "baselines", tmp_path / "results"
        bdir.mkdir(exist_ok=True)
        fdir.mkdir(exist_ok=True)
        (bdir / "bench_x.json").write_text(json.dumps(base))
        (fdir / "bench_x.json").write_text(json.dumps(fresh))
        return subprocess.run(
            [sys.executable, "-m", "benchmarks.compare",
             "--baseline", str(bdir), "--fresh", str(fdir), *flags],
            capture_output=True, text=True, cwd=ROOT,
            env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})

    def test_equal_signatures_pass(self, tmp_path):
        doc = {"rounds": 4, "rounds_per_sec": 10.0,
               "manifest": {"structural_signature": "a" * 64}}
        r = self._compare(tmp_path, doc, doc, "--manifest")
        assert r.returncode == 0, r.stdout + r.stderr

    def test_signature_mismatch_is_a_regression(self, tmp_path):
        base = {"rounds": 4, "rounds_per_sec": 10.0,
                "manifest": {"structural_signature": "a" * 64}}
        fresh = {"rounds": 4, "rounds_per_sec": 10.0,
                 "manifest": {"structural_signature": "b" * 64}}
        r = self._compare(tmp_path, base, fresh, "--manifest")
        assert r.returncode == 1
        assert "structurally different" in r.stdout
        # without --manifest the same pair passes (opt-in check)
        assert self._compare(tmp_path, base, fresh).returncode == 0

    def test_missing_fresh_manifest_is_a_regression(self, tmp_path):
        base = {"rounds": 4, "rounds_per_sec": 10.0,
                "manifest": {"structural_signature": "a" * 64}}
        fresh = {"rounds": 4, "rounds_per_sec": 10.0}
        r = self._compare(tmp_path, base, fresh, "--manifest")
        assert r.returncode == 1
        assert "no longer writes its manifest" in r.stdout

    def test_manifestless_baseline_skips_with_note(self, tmp_path):
        base = {"rounds": 4, "rounds_per_sec": 10.0}
        fresh = {"rounds": 4, "rounds_per_sec": 10.0,
                 "manifest": {"structural_signature": "a" * 64}}
        r = self._compare(tmp_path, base, fresh, "--manifest")
        assert r.returncode == 0
        assert "no run manifest" in r.stdout
