"""One benchmark per paper figure (Sec. V), on the vectorized sweep engine:
each figure is ONE batched sweep (its comparison axis x seed replicates),
so every curve in the dumped JSON carries a mean and a std band across
channel/noise seeds.  Rows are CSV ``name,us_per_call,derived`` where
``us_per_call`` is aggregate wall time per (grid point x round) and
``derived`` the figure's headline quantity; full trajectories go to
results/bench_<name>.json.
"""
from __future__ import annotations

import json
import os
from typing import List, Tuple

import numpy as np

from benchmarks.common import (CaseIExperiment, CaseIIExperiment,
                               SEED_REPLICATES, timed_sweep)

RESULTS_DIR = os.environ.get("REPRO_RESULTS", "results")


def _dump(name: str, payload, manifest=None) -> None:
    """Write ``results/bench_<name>.json``; ``manifest`` (a
    ``repro.obs.run_manifest`` dict) rides along under the ``"manifest"``
    key so the file is self-describing and ``compare.py --manifest`` can
    cross-check the producing program's structural signature."""
    if manifest is not None:
        payload = dict(payload)
        payload["manifest"] = manifest
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, f"bench_{name}.json"), "w") as f:
        json.dump(payload, f, indent=2, default=str)


def _banded_rows(fig: str, res, us: float, axis: str, metric: str,
                 seeds: int, row_metric: str = None, value_prefix: str = "",
                 ) -> Tuple[List[Tuple[str, float, str]], dict]:
    """CSV rows + JSON payload for a (axis x seed) sweep: one curve per axis
    value, mean +- std across the seed replicates (the curve payload is
    ``SweepResult.curves`` — one assembly path for live dumps and tests).
    ``row_metric`` picks the headline quantity of the CSV row when it
    differs from the dumped curve metric (the ridge figures plot ``loss``
    but report ``gap``)."""
    curves = res.curves(axis, metric, over="seed")
    row_metric = row_metric or metric
    rmean, rstd = res.band(row_metric, over="seed")
    rows = []
    for i, value in enumerate(res.sweep.values(axis)):
        rows.append((f"{fig}/{value_prefix}{value}", us,
                     f"final_{row_metric}={rmean[i][-1]:.5f}"
                     f"+-{rstd[i][-1]:.5f}"))
    return rows, curves


def fig1a_opt_benefit(rounds: int = 300,
                      seeds: int = SEED_REPLICATES) -> List[Tuple[str, float, str]]:
    """Fig. 1(a): Case I test accuracy — optimized (a, b) vs b_k = b_k^max.
    One sweep: amplification (structural) x seed (batchable)."""
    exp = CaseIExperiment()
    sweep = exp.sweep({"amplification": ("optimal", "bmax")},
                      eval_every=max(rounds // 12, 5), seeds=seeds)
    res, us = timed_sweep(sweep, rounds)
    rows, curves = _banded_rows("fig1a", res, us, "amplification",
                                "test_acc", seeds)
    _dump("fig1a", curves, manifest=res.manifest())
    return rows


def fig1b_benchmarks(rounds: int = 300,
                     seeds: int = SEED_REPLICATES) -> List[Tuple[str, float, str]]:
    """Fig. 1(b): Case I — proposed vs Benchmark I [7] / II [13] (+ one-bit
    [12] as the extra ablation the intro argues against).  One sweep:
    scheme (structural, 4 sub-batches) x seed (batchable)."""
    exp = CaseIExperiment()
    sweep = exp.sweep(
        {"scheme": ("normalized", "benchmark1", "benchmark2", "onebit")},
        eval_every=25, seeds=seeds)
    res, us = timed_sweep(sweep, rounds)
    rows, curves = _banded_rows("fig1b", res, us, "scheme", "test_acc", seeds)
    _dump("fig1b", curves, manifest=res.manifest())
    return rows


def fig2a_opt_benefit_ridge(rounds: int = 400,
                            seeds: int = SEED_REPLICATES) -> List[Tuple[str, float, str]]:
    """Fig. 2(a): Case II loss — optimized (a, b) vs b_k = b_k^max."""
    exp = CaseIIExperiment()
    sweep = exp.sweep({"amplification": ("optimal", "bmax")}, eval_every=40,
                      seeds=seeds)
    res, us = timed_sweep(sweep, rounds)
    rows, curves = _banded_rows("fig2a", res, us, "amplification", "loss",
                                seeds, row_metric="gap")
    _dump("fig2a", curves, manifest=res.manifest())
    return rows


def fig2b_benchmarks_ridge(rounds: int = 400,
                           seeds: int = SEED_REPLICATES) -> List[Tuple[str, float, str]]:
    """Fig. 2(b): Case II — proposed vs Benchmark I / II."""
    exp = CaseIIExperiment()
    sweep = exp.sweep({"scheme": ("normalized", "benchmark1", "benchmark2")},
                      eval_every=40, seeds=seeds)
    res, us = timed_sweep(sweep, rounds)
    rows, curves = _banded_rows("fig2b", res, us, "scheme", "loss", seeds,
                                row_metric="gap")
    _dump("fig2b", curves, manifest=res.manifest())
    return rows


def fig3a_case1_vs_case2(rounds: int = 400,
                         seeds: int = SEED_REPLICATES) -> List[Tuple[str, float, str]]:
    """Fig. 3(a): on the strongly-convex task, Case-II parameters converge
    faster than Case-I parameters (the benefit of exploiting convexity).
    The case axis is a composite (several fields per value) and structural;
    seeds ride along batched within each sub-batch."""
    exp = CaseIIExperiment()
    sweep = exp.sweep(
        {"case_setup": (
            ("caseI", {"case": "I", "p": 0.75, "expected_loss_drop": 20.0,
                       "s_target": None}),
            # paper tunes Case II for speed in Fig. 3(a)
            ("caseII", {"case": "II", "s_target": 0.98}),
        )},
        eval_every=40, seeds=seeds)
    res, us = timed_sweep(sweep, rounds)
    rows, curves = _banded_rows("fig3a", res, us, "case_setup", "loss",
                                seeds)
    _dump("fig3a", curves, manifest=res.manifest())
    return rows


def fig3b_tradeoff(rounds: int = 600,
                   seeds: int = SEED_REPLICATES) -> List[Tuple[str, float, str]]:
    """Fig. 3(b): the q_max <-> epsilon tradeoff — larger s gives a lower
    floor but slower approach.  ``s_target`` only moves the setup-time
    receiver gain, so the WHOLE figure (3 targets x seeds) is one batched
    program."""
    exp = CaseIIExperiment()
    sweep = exp.sweep({"s_target": (0.9779, 0.9890, 0.9945)}, eval_every=60,
                      seeds=seeds)
    res, us = timed_sweep(sweep, rounds)
    rows, curves = _banded_rows("fig3b", res, us, "s_target", "loss", seeds,
                                row_metric="gap", value_prefix="s=")
    _dump("fig3b", curves, manifest=res.manifest())
    return rows


def engine_rounds_per_sec(rounds: int = 64,
                          repeats: int = 3) -> List[Tuple[str, float, str]]:
    """Compiled-engine headline: rounds/sec of the ``lax.scan`` driver vs the
    per-round Python-loop driver (K=20, noiseless channel, ``kernels``
    backend) on both benchmark tasks — the Case-I MLP (compute-bound rounds:
    the engine's win is the removed host round-trips) and the Case-II ridge
    model (driver-overhead-bound rounds: the engine's win is the round rate
    itself).  The facade's task cache keeps one ``grad_fn`` identity per
    experiment, so the runtime's compiled executables persist across the
    ``Experiment`` resets; one warm-up run per driver removes jit compile
    from the timed runs, and the reported rate is the best of ``repeats``
    full runs.

    A third lane re-times the scan driver with a live JSONL flight recorder
    and asserts the telemetry overhead stays within
    ``OBS_OVERHEAD_BUDGET`` (1.05x) — the recorder's whole design (host-side
    chunk-boundary emission, buffered writes) exists to keep this number
    flat, and this guard keeps it kept."""
    import time

    from repro import obs
    from repro.core.channel import ChannelConfig
    from repro.fl import Experiment
    from benchmarks.common import (CHANNEL_MEAN, CaseIExperiment,
                                   CaseIIExperiment, K, OBS_OVERHEAD_BUDGET)

    rows, dump = [], {}
    for task, exp in (("mlp", CaseIExperiment()), ("ridge", CaseIIExperiment())):
        n = rounds if task == "mlp" else rounds * 8   # tiny model: longer run
        cfg = exp.config(scheme="normalized", backend="kernels",
                         channel=ChannelConfig(num_devices=K,
                                               channel_mean=CHANNEL_MEAN,
                                               noise_var=0.0))
        e = Experiment(exp.spec(cfg, evaluate=False))
        rps = {}
        for driver in ("python", "scan"):
            # compute-bound MLP rounds prefer small chunks (batch-buffer
            # locality); overhead-bound ridge rounds prefer one maximal chunk
            kw = dict(driver=driver, chunk_size=8 if task == "mlp" else n)
            e.reset()
            e.run(n, **kw)                                       # warm-up
            dt = float("inf")
            for _ in range(repeats):
                e.reset()
                t0 = time.perf_counter()
                e.run(n, **kw)
                dt = min(dt, time.perf_counter() - t0)
            rps[driver] = n / dt
            rows.append((f"engine/{task}/{driver}", dt / n * 1e6,
                         f"rounds_per_sec={rps[driver]:.2f}"))
        speedup = rps["scan"] / rps["python"]
        if speedup < 1.0:
            # the engine's whole premise: the compiled scan must never lose
            # to the host loop.  The MLP (compute-bound) side regressed once
            # when chunk batches were host-gathered feature rows; the
            # index-batch providers (repro.fl.tasks) fixed it — this guard
            # keeps it fixed
            raise AssertionError(
                f"scan driver is {speedup:.2f}x the python driver on "
                f"{task} (< 1.0x) — the compiled engine regressed")
        rows.append((f"engine/{task}/speedup", 0.0,
                     f"scan_over_python={speedup:.2f}x"))
        # flight-recorder overhead lane: same scan timing, JSONL sink on
        # (fresh file per repeat so every run pays the full write path)
        kw = dict(driver="scan", chunk_size=8 if task == "mlp" else n)
        os.makedirs(RESULTS_DIR, exist_ok=True)
        obs_path = os.path.join(RESULTS_DIR, f"obs_engine_{task}.jsonl")
        dt = float("inf")
        for _ in range(repeats):
            e.reset()
            with obs.make("jsonl", path=obs_path) as rec:
                t0 = time.perf_counter()
                e.run(n, recorder=rec, **kw)
                dt = min(dt, time.perf_counter() - t0)
        rps["scan_jsonl"] = n / dt
        overhead = rps["scan"] / rps["scan_jsonl"]
        if overhead > OBS_OVERHEAD_BUDGET:
            raise AssertionError(
                f"JSONL flight recorder costs {overhead:.3f}x the bare scan "
                f"driver on {task} (> {OBS_OVERHEAD_BUDGET}x budget) — "
                "telemetry leaked onto the dispatch critical path")
        rows.append((f"engine/{task}/scan_jsonl", dt / n * 1e6,
                     f"rounds_per_sec={rps['scan_jsonl']:.2f};"
                     f"obs_overhead={overhead:.3f}x"))
        dump[task] = {"rounds_per_sec": rps, "speedup": speedup, "rounds": n,
                      "obs_overhead": overhead,
                      "obs_overhead_budget": OBS_OVERHEAD_BUDGET,
                      "manifest": e.manifest()}
    _dump("engine", dump)
    return rows


def sweep_rounds_per_sec(rounds: int = 256, grid: int = 8,
                         repeats: int = 2) -> List[Tuple[str, float, str]]:
    """Vectorized-sweep headline: aggregate rounds/sec of ONE batched
    program over a (seed x noise) grid vs the same grid as N sequential
    ``runtime.run`` dispatches (both on the compiled scan engine, both warm).
    The grid point is the Case-II ridge task — the overhead-bound regime
    sweeps live in — and the batched program runs the whole grid per
    dispatch, so the expected win is ~grid-size.  Also asserts the
    compiled-executable caches report ZERO re-traces across the timed
    repeats (the ``cache_info`` satellite)."""
    import dataclasses
    import time

    from repro.fed import runtime
    from repro.fl import SweepSpec
    from benchmarks.common import CaseIIExperiment, run_sweep, seed_axis

    exp = CaseIIExperiment()
    seeds = max(grid // 2, 1)
    base = dataclasses.replace(exp.spec(exp.config(), evaluate=False),
                               chunk_size=rounds)        # one scan per run
    nv = base.fl.channel.noise_var
    sweep = SweepSpec(base, {"noise_var": (nv, 2.0 * nv),
                             "seed": seed_axis(seeds)})
    g = sweep.size

    times = {}
    res_batched = None
    for mode, vectorized in (("batched", True), ("sequential", False)):
        run_sweep(sweep, rounds, vectorized=vectorized)      # warm-up
        traces0 = dict(runtime.TRACE_COUNTS)
        dt = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            res = run_sweep(sweep, rounds, vectorized=vectorized)
            dt = min(dt, time.perf_counter() - t0)
            if vectorized:
                res_batched = res
        retraces = sum(runtime.TRACE_COUNTS.values()) - sum(traces0.values())
        if mode == "batched" and retraces:
            # the README/ROADMAP contract: a warm batched grid re-traces
            # NOTHING (cache eviction or an unhashable config would show up
            # here long before it shows up as a perf regression)
            raise AssertionError(
                f"warm batched sweep re-traced {retraces} time(s); "
                f"cache_info={runtime.cache_info()}")
        times[mode] = dt
        times[f"{mode}_retraces"] = retraces
    rows = []
    for mode in ("batched", "sequential"):
        dt, retraces = times[mode], times[f"{mode}_retraces"]
        rows.append((f"sweep/{mode}", dt / (g * rounds) * 1e6,
                     f"agg_rounds_per_sec={g * rounds / dt:.1f};grid={g};"
                     f"retraces={retraces}"))
    speedup = times["sequential"] / times["batched"]
    rows.append((f"sweep/speedup", 0.0,
                 f"batched_over_sequential={speedup:.2f}x;grid={g}"))
    _dump("sweep", {
        "grid": g, "rounds": rounds,
        "agg_rounds_per_sec": {m: g * rounds / times[m]
                               for m in ("batched", "sequential")},
        "speedup": speedup,
        "retraces": {m: times[f"{m}_retraces"]
                     for m in ("batched", "sequential")},
        "cache_info": runtime.cache_info(),
    }, manifest=res_batched.manifest())
    return rows


def scenario_axes(rounds: int = 120) -> List[Tuple[str, float, str]]:
    """The new spec axes on the Case-I task, each a one-field change to the
    baseline ``ExperimentSpec`` (the point of the declarative redesign):
    adamw server optimizer, H = 4 local steps, and 50% Bernoulli
    participation — reported as final accuracy plus the measured eq.-8
    transmit-energy total, which partial participation cuts roughly in
    half."""
    import dataclasses
    import time

    import numpy as np

    from repro.fl import Experiment
    from benchmarks.common import CaseIExperiment

    exp = CaseIExperiment()
    base_spec = exp.spec(exp.config(scheme="normalized"),
                         eval_every=max(rounds // 4, 5))
    variants = {
        "baseline": base_spec,
        "adamw": dataclasses.replace(base_spec, server_opt="adamw"),
        "local_steps4": dataclasses.replace(base_spec, local_steps=4,
                                            local_lr=0.05),
        "participation50": dataclasses.replace(base_spec, participation=0.5),
    }
    rows, dump = [], {}
    for name, spec in variants.items():
        e = Experiment(spec)
        t0 = time.perf_counter()
        e.run(rounds)
        us = (time.perf_counter() - t0) / rounds * 1e6
        acc = e.history["test_acc"][-1]
        energy = float(np.sum(e.history["tx_energy"]))
        parts = float(np.mean(e.history["num_participants"]))
        dump[name] = {"round": e.history["eval_round"],
                      "acc": e.history["test_acc"],
                      "total_tx_energy": energy,
                      "mean_participants": parts,
                      "manifest": e.manifest()}
        rows.append((f"scenario/{name}", us,
                     f"final_acc={acc:.4f};total_tx_energy={energy:.1f}"))
    _dump("scenarios", dump)
    return rows


def channel_rounds_per_sec(rounds: int = 256,
                           repeats: int = 2) -> List[Tuple[str, float, str]]:
    """Wireless-environment engine overhead: scan rounds/sec of the ridge
    task (overhead-bound rounds — the regime where channel work shows)
    across radio environments: fixed channel, i.i.d. block fading, AR(1)
    correlated fading, and AR(1) + imperfect CSI.  Every time-varying
    variant redraws the channel AND re-solves Problem 3 (on ``h_hat``)
    inside the scan, so this measures the in-scan re-solve + estimation
    cost directly; the CSI variant must stay within 2x of plain fading
    (asserted — a regression in the scan-safe refresh shows up here before
    it shows up in a sweep)."""
    import dataclasses
    import time

    from repro.fl import Experiment
    from benchmarks.common import CaseIIExperiment

    exp = CaseIIExperiment()
    base = dataclasses.replace(exp.spec(exp.config(), evaluate=False),
                               chunk_size=rounds)       # one scan per run
    def env(**chkw):
        channel = dataclasses.replace(base.fl.channel, **chkw)
        return dataclasses.replace(
            base, fl=dataclasses.replace(base.fl, channel=channel))

    variants = {
        "fixed": base,
        "iid_fading": env(block_fading=True),
        "ar1": env(model="ar1", rho=0.9),
        "ar1_csi": env(model="ar1", rho=0.9, csi_error=0.2),
    }
    rows, dump = [], {}
    rps, manifests = {}, {}
    for name, spec in variants.items():
        e = Experiment(spec)
        e.run(rounds)                                    # warm-up + compile
        dt = float("inf")
        for _ in range(repeats):
            e.reset()
            t0 = time.perf_counter()
            e.run(rounds)
            dt = min(dt, time.perf_counter() - t0)
        rps[name] = rounds / dt
        manifests[name] = e.manifest()
        rows.append((f"channel/{name}", dt / rounds * 1e6,
                     f"rounds_per_sec={rps[name]:.1f}"))
    overhead = rps["iid_fading"] / rps["ar1_csi"]
    if overhead > 2.0:
        raise AssertionError(
            "in-scan AR(1)+CSI refresh costs "
            f"{overhead:.2f}x plain block fading (> 2x budget)")
    rows.append(("channel/csi_overhead", 0.0,
                 f"fading_over_ar1_csi={overhead:.2f}x"))
    _dump("channel", {"rounds": rounds, "rounds_per_sec": rps,
                      "csi_overhead_vs_fading": overhead,
                      "manifests": manifests})
    return rows


def csi_robustness(rounds: int = 400,
                   seeds: int = SEED_REPLICATES) -> List[Tuple[str, float, str]]:
    """CSI-robustness figure: the proposed normalized-gradient scheme vs
    the max-norm Benchmark I across CSI-error levels (block fading, so the
    per-round re-solve runs on every round's noisy estimate).  One sweep:
    scheme (structural, 2 sub-batches) x csi_error (batchable) x seed
    (batchable), dumped with seed-replicate bands via ``SweepResult.band``."""
    import dataclasses

    from benchmarks.common import CaseIIExperiment, seed_axis, timed_sweep
    from repro.fl import SweepSpec

    exp = CaseIIExperiment()
    base = exp.spec(exp.config(), eval_every=max(rounds // 10, 5))
    channel = dataclasses.replace(base.fl.channel, block_fading=True)
    base = dataclasses.replace(
        base, fl=dataclasses.replace(base.fl, channel=channel))
    sweep = SweepSpec(base, {"scheme": ("normalized", "benchmark1"),
                             "csi_error": (0.0, 0.1, 0.3, 0.6),
                             "seed": seed_axis(seeds)})
    res, us = timed_sweep(sweep, rounds)
    mean, std = res.band("gap", over="seed")      # [scheme, csi, evals]
    err_mean, _ = res.band("csi_gain_err", over="seed")
    rows, curves = [], {}
    for i, scheme in enumerate(res.sweep.values("scheme")):
        for j, err in enumerate(res.sweep.values("csi_error")):
            curves[f"{scheme}/csi={err}"] = {
                "round": res.eval_rounds,
                "gap": mean[i, j].tolist(),
                "gap_std": std[i, j].tolist(),
                "mean_abs_csi_gain_err": float(
                    np.abs(err_mean[i, j]).mean()),
                "seeds": seeds,
            }
            rows.append((f"csi_robustness/{scheme}/csi={err}", us,
                         f"final_gap={mean[i, j][-1]:.5f}"
                         f"+-{std[i, j][-1]:.5f}"))
    _dump("csi_robustness", curves, manifest=res.manifest())
    return rows


def client_algorithms(rounds: int = 200,
                      seeds: int = SEED_REPLICATES
                      ) -> List[Tuple[str, float, str]]:
    """Client-algorithm registry deliverable: FedProx, FedDyn, and SCAFFOLD
    through the air vs plain local SGD, on dirichlet splits of the Case-I
    task with H = 4 local steps (the client-drift regime the corrections
    target).  One sweep: algorithm (structural composite — FedDyn's and
    SCAFFOLD's refreshed correction states ride a second OTA slot) x
    dirichlet alpha (structural, new split per value) x participation
    (structural) x seed (batchable), dumped with seed-replicate bands on
    the train loss.

    The sweep runs at noise_var = 1e-10, the drift-dominated operating
    point: the stateful correctors learn their server state from the
    DE-GAINED slot-2 aggregate, which amplifies channel noise by
    ~1/(a sum h b) — at the repo-default 1e-7 that amplified noise swamps
    the variates, the corrections inject it into every local step, and
    plain SGD (which never de-gains) inverts the ranking.  The separation
    below is therefore asserted where client drift, not variate-channel
    noise, is the binding error source.

    Two guards asserted: the two-slot correctors' eq.-8 transmit energy is
    ~2x SGD's under full participation (the second slot pays the same
    unit-norm budget as the first), and on the alpha = 0.1 non-IID split
    both stateful correctors beat plain SGD on final train loss with
    non-overlapping seed bands."""
    import dataclasses

    from benchmarks.common import CaseIExperiment, seed_axis, timed_sweep
    from repro.fl import SweepSpec

    exp = CaseIExperiment()
    cfg = exp.config()
    cfg = dataclasses.replace(
        cfg, channel=dataclasses.replace(cfg.channel, noise_var=1e-10))
    base = exp.spec(cfg, eval_every=max(rounds // 10, 5))
    base = dataclasses.replace(base, local_steps=4, local_lr=0.05)
    algos = (("sgd", {"client.algo": "sgd"}),
             ("fedprox", {"client.algo": "fedprox", "client.mu": 0.1}),
             ("feddyn", {"client.algo": "feddyn", "client.alpha": 0.1}),
             ("scaffold", {"client.algo": "scaffold"}))
    sweep = SweepSpec(base, {"algo": algos,
                             "alpha": (0.1, 100.0),
                             "participation": (1.0, 0.5),
                             "seed": seed_axis(seeds)})
    res, us = timed_sweep(sweep, rounds)
    mean, std = res.band("train_loss", over="seed")  # [algo, alpha, part, E]
    emean, _ = res.band("tx_energy", over="seed")    # [algo, alpha, part, T]
    names = res.sweep.values("algo")
    rows, curves, energy, final = [], {}, {}, {}
    for i, name in enumerate(names):
        for j, al in enumerate(res.sweep.values("alpha")):
            for k, part in enumerate(res.sweep.values("participation")):
                tot_e = float(np.sum(emean[i, j, k]))
                energy[(name, al, part)] = tot_e
                final[(name, al, part)] = (mean[i, j, k][-1],
                                           std[i, j, k][-1])
                curves[f"{name}/alpha={al}/part={part}"] = {
                    "round": res.eval_rounds,
                    "train_loss": mean[i, j, k].tolist(),
                    "train_loss_std": std[i, j, k].tolist(),
                    "total_tx_energy": tot_e,
                    "seeds": seeds,
                }
                rows.append((f"clients/{name}/alpha={al}/part={part}", us,
                             f"final_train_loss={mean[i, j, k][-1]:.4f}"
                             f"+-{std[i, j, k][-1]:.4f};"
                             f"total_tx_energy={tot_e:.1f}"))
    # second OTA slot: the two-slot correctors pay exactly twice the
    # per-round unit-norm energy of the single-slot algorithms under full
    # participation
    a0 = res.sweep.values("alpha")[0]
    for name in ("feddyn", "scaffold"):
        ratio = energy[(name, a0, 1.0)] / energy[("sgd", a0, 1.0)]
        if not 1.95 <= ratio <= 2.05:
            raise AssertionError(
                f"{name}/sgd transmit-energy ratio {ratio:.3f} is not ~2 — "
                "the second OTA slot's eq.-8 accounting drifted")
    # algorithm separation on the non-IID split (full participation):
    # each stateful corrector's final band sits strictly below SGD's
    sm, ss = final[("sgd", 0.1, 1.0)]
    for name in ("feddyn", "scaffold"):
        am, as_ = final[(name, 0.1, 1.0)]
        if am + as_ >= sm - ss:
            raise AssertionError(
                f"{name} final train loss {am:.4f}+-{as_:.4f} does not "
                f"separate from sgd {sm:.4f}+-{ss:.4f} on dirichlet(0.1)")
    rows.append(("clients/energy_ratio", 0.0,
                 f"two_slot_over_sgd_tx_energy={ratio:.3f}"))
    _dump("clients", curves, manifest=res.manifest())
    return rows


def kscale_flat_memory(quick: bool = False) -> List[Tuple[str, float, str]]:
    """Streaming K-scale headline (the PR-6 tentpole deliverable): a
    100,000-device OTA round on the ``k_block`` streaming engine, with peak
    RSS held FLAT against the dense path's linear growth in K.

    Three subprocess cases (``benchmarks.kscale_case``; each owns a process
    because peak RSS is a lifetime high-water mark — measured via the
    exec-fresh ``VmHWM``, not the fork-inherited ``ru_maxrss``): the dense
    engine at
    two small K to fit its MB-per-device slope, then the streaming engine at
    the target K.  Asserted: streaming peak RSS < 0.5x the dense
    extrapolation at the same K (the measured ratio is ~0.04) AND under an
    absolute pin that catches an accidental [K, N] / [K, B, d]
    materialization even if the extrapolation is noisy.  Quick mode shrinks
    every K by 5x for the CI smoke — same shape, same guards.

    The PR-9 sharded entry runs the SAME streamed round under
    ``device_mesh=4`` twice — once on 4 forced host devices (the physical
    ``shard_map`` path) and once without them (the emulated fallback) — and
    asserts the two trajectories are bitwise-identical by params digest:
    sharding is just another blocking, so where it runs is invisible in the
    math.  The >= 2x rounds/sec speedup over the single-device stream is
    asserted only when the host has >= 4 cores (forced host devices on one
    core are concurrency, not parallelism) and the stream ran on the CPU
    too; the measured ratio is always recorded so a skipped assertion is
    visible, never silent.  Forced host devices exist only on the CPU
    backend, so both sharded cases run with ``JAX_PLATFORMS=cpu``: on an
    accelerator host they are a CPU rehearsal of the mesh path, and their
    rows say so.

    Every case is a child process, and an accelerator belongs to one
    process: this entry refuses to run in a process that already holds a
    non-CPU backend (``benchmarks.run`` runs it before anything touches
    JAX)."""
    import json as _json
    import os
    import subprocess
    import sys

    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        import jax
        if jax.default_backend() != "cpu":
            raise RuntimeError(
                "kscale starts JAX child processes, but this process already "
                f"holds the {jax.default_backend()} backend, so the children "
                "cannot reach the chip — run it before any in-process JAX "
                "work (python -m benchmarks.run --only kscale)")

    # the dense slope is fit from the SAME two K in both modes: smaller
    # points would shave seconds but leave the fit inside RSS noise (tens of
    # MB) — only the streaming K shrinks for the CI smoke
    if quick:
        rounds, dense_ks, stream_k, stream_kb = 2, (1000, 2000), 20_000, 500
    else:
        rounds, dense_ks, stream_k, stream_kb = 4, (1000, 2000), 100_000, 1000
    RSS_PIN_MB = 2048.0

    def case(devices: int, k_block: int, device_mesh: int = 0,
             force_host_devices: int = 0, cpu: bool = False) -> dict:
        env = dict(os.environ)
        if cpu:
            env["JAX_PLATFORMS"] = "cpu"
        if force_host_devices:
            flag = (f"--xla_force_host_platform_device_count="
                    f"{force_host_devices}")
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + flag).strip()
        out = subprocess.run(
            [sys.executable, "-m", "benchmarks.kscale_case",
             "--devices", str(devices), "--k-block", str(k_block),
             "--device-mesh", str(device_mesh), "--rounds", str(rounds)],
            capture_output=True, text=True, env=env)
        if out.returncode != 0:
            raise AssertionError(
                f"kscale case K={devices} k_block={k_block} "
                f"device_mesh={device_mesh} failed:\n{out.stderr[-2000:]}")
        return _json.loads(out.stdout.strip().splitlines()[-1])

    rows, dense = [], []
    for kdev in dense_ks:
        r = case(kdev, 0)
        dense.append(r)
        rows.append((f"kscale/dense/K={kdev}", 1e6 / r["rounds_per_sec"],
                     f"peak_rss_mb={r['peak_rss_mb']:.0f};"
                     f"rounds_per_sec={r['rounds_per_sec']:.2f}"))
    stream = case(stream_k, stream_kb)
    rows.append((f"kscale/streaming/K={stream_k}",
                 1e6 / stream["rounds_per_sec"],
                 f"peak_rss_mb={stream['peak_rss_mb']:.0f};"
                 f"rounds_per_sec={stream['rounds_per_sec']:.2f};"
                 f"k_block={stream_kb}"))

    (k1, m1), (k2, m2) = [(r["devices"], r["peak_rss_mb"]) for r in dense]
    slope = (m2 - m1) / (k2 - k1)                   # MB per device, dense
    extrapolated = m2 + slope * (stream_k - k2)
    ratio = stream["peak_rss_mb"] / extrapolated
    if stream["peak_rss_mb"] > 0.5 * extrapolated:
        raise AssertionError(
            f"streaming peak RSS {stream['peak_rss_mb']:.0f} MB at "
            f"K={stream_k} exceeds half the dense extrapolation "
            f"{extrapolated:.0f} MB — the K axis is leaking into memory")
    if stream["peak_rss_mb"] > RSS_PIN_MB:
        raise AssertionError(
            f"streaming peak RSS {stream['peak_rss_mb']:.0f} MB exceeds the "
            f"{RSS_PIN_MB:.0f} MB pin — something materializes O(K)")
    rows.append(("kscale/memory_ratio", 0.0,
                 f"stream_over_dense_extrapolated={ratio:.3f};"
                 f"dense_extrapolated_mb={extrapolated:.0f}"))

    # ---- PR-9 sharded streaming: device_mesh=4, physical vs emulated, as
    # a CPU rehearsal on forced host devices
    mesh_d = 4
    sharded = case(stream_k, stream_kb, device_mesh=mesh_d,
                   force_host_devices=mesh_d, cpu=True)
    if sharded["local_devices"] < mesh_d:
        raise AssertionError(
            f"forced-host-device case saw {sharded['local_devices']} local "
            f"devices (wanted {mesh_d}) — XLA_FLAGS did not reach the "
            "subprocess")
    sharded_emu = case(stream_k, stream_kb, device_mesh=mesh_d, cpu=True)
    if sharded["params_sha256"] != sharded_emu["params_sha256"]:
        raise AssertionError(
            "sharded streaming trajectory is NOT bitwise-identical across "
            f"physical/emulated execution: {sharded['params_sha256']} vs "
            f"{sharded_emu['params_sha256']} — the device_mesh math spec "
            "leaked an execution-dependent reduction")
    if sharded["peak_rss_mb"] > RSS_PIN_MB:
        raise AssertionError(
            f"sharded streaming peak RSS {sharded['peak_rss_mb']:.0f} MB "
            f"exceeds the {RSS_PIN_MB:.0f} MB pin — the mesh re-materialized "
            "the K axis")
    speedup = sharded["rounds_per_sec"] / stream["rounds_per_sec"]
    cores = os.cpu_count() or 1
    assert_speedup = cores >= mesh_d and stream["platform"] == "cpu"
    if assert_speedup and speedup < 2.0:
        raise AssertionError(
            f"sharded streaming speedup {speedup:.2f}x < 2x over the "
            f"single-device stream at K={stream_k} on {cores} cores")
    rows.append((f"kscale/sharded/K={stream_k}",
                 1e6 / sharded["rounds_per_sec"],
                 f"peak_rss_mb={sharded['peak_rss_mb']:.0f};"
                 f"rounds_per_sec={sharded['rounds_per_sec']:.2f};"
                 f"device_mesh={mesh_d};speedup={speedup:.2f}x;"
                 f"bitwise_phys_vs_emulated=ok;platform=cpu_rehearsal;"
                 + ("speedup_assert=on" if assert_speedup else
                    f"speedup_assert=SKIPPED(cores={cores},"
                    f"stream_platform={stream['platform']})")))

    _dump("kscale", {
        "rounds": rounds,
        "dense": dense,
        "streaming": stream,
        "sharded": sharded,
        "sharded_emulated": sharded_emu,
        "sharded_speedup_over_stream": speedup,
        "sharded_speedup_asserted": assert_speedup,
        "sharded_bitwise_phys_vs_emulated": True,
        "dense_slope_mb_per_device": slope,
        "dense_extrapolated_mb_at_stream_k": extrapolated,
        "stream_over_dense_extrapolated": ratio,
        "rss_pin_mb": RSS_PIN_MB,
    })
    return rows


def grad_norm_fluctuation(rounds: int = 200,
                          seeds: int = SEED_REPLICATES) -> List[Tuple[str, float, str]]:
    """Sec. I motivating claim: the local gradient norm fluctuates over
    iterations (so provisioning b_k for the max norm G wastes headroom).
    Reported on both experiment tasks (one seed-batched sweep each); ridge
    (whose norms collapse as the iterate approaches w*) shows the effect
    most starkly."""
    rows, dump = [], {}
    for name, exp in (("mnist", CaseIExperiment()), ("ridge", CaseIIExperiment())):
        sweep = exp.sweep({}, eval_every=rounds, evaluate=False, seeds=seeds)
        res, us = timed_sweep(sweep, rounds)
        mean = res.history["grad_norm_mean"].mean(axis=0)
        ratio = float(mean.max() / max(mean.min(), 1e-9))
        dump[name] = {
            "round": res.rounds,
            "mean": mean.tolist(),
            "mean_std": res.history["grad_norm_mean"].std(axis=0).tolist(),
            "min": res.history["grad_norm_min"].mean(axis=0).tolist(),
            "max": res.history["grad_norm_max"].mean(axis=0).tolist(),
            "seeds": seeds,
            "manifest": res.manifest(),
        }
        rows.append((f"grad_norm_fluctuation/{name}", us,
                     f"max_over_min={ratio:.2f};final_mean={mean[-1]:.4f}"))
    _dump("grad_norm_fluctuation", dump)
    return rows
