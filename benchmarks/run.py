"""Benchmark harness entry point (deliverable (d)): one function per paper
table/figure.  Prints ``name,us_per_call,derived`` CSV.

    PYTHONPATH=src python -m benchmarks.run             # full set
    PYTHONPATH=src python -m benchmarks.run --quick     # CI-speed subset
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced round counts (smoke)")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    ap.add_argument("--backend", default=None,
                    choices=("vmap", "kernels", "mesh"),
                    help="aggregation backend for the FL figure benchmarks "
                         "(default: the fused Pallas kernel path)")
    ap.add_argument("--driver", default=None, choices=("scan", "python"),
                    help="FL round-loop driver (default: the compiled "
                         "lax.scan engine)")
    args = ap.parse_args()

    from benchmarks import common, figures
    from benchmarks.roofline_table import roofline_rows
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.backend:
        common.DEFAULT_BACKEND = args.backend
    if args.driver:
        common.DEFAULT_DRIVER = args.driver

    r = (lambda full, quick: quick if args.quick else full)
    # figure benchmarks run as batched sweeps with seed-replicate error
    # bands; quick mode trims rounds AND replicates
    s = r(3, 2)
    benches = [
        # the streaming K-scale engine: a 100,000-device round (k_block
        # lax.scan superposition) vs the dense path's linear peak-RSS growth
        # — subprocess cases, flat-memory + absolute-pin guards asserted.
        # FIRST: its children need the accelerator, which this process
        # must not hold yet (one process per chip)
        ("kscale", lambda: figures.kscale_flat_memory(quick=args.quick)),
        ("fig1a", lambda: figures.fig1a_opt_benefit(r(300, 60), s)),
        ("fig1b", lambda: figures.fig1b_benchmarks(r(300, 60), s)),
        ("fig2a", lambda: figures.fig2a_opt_benefit_ridge(r(400, 80), s)),
        ("fig2b", lambda: figures.fig2b_benchmarks_ridge(r(400, 80), s)),
        ("fig3a", lambda: figures.fig3a_case1_vs_case2(r(400, 80), s)),
        ("fig3b", lambda: figures.fig3b_tradeoff(r(600, 120), s)),
        ("grad_norm", lambda: figures.grad_norm_fluctuation(r(200, 50), s)),
        ("engine", lambda: figures.engine_rounds_per_sec(r(48, 16))),
        # the vectorized sweep engine: one compiled program for a whole
        # experiment grid vs the same grid dispatched sequentially (quick
        # keeps enough rounds that the per-run host assembly amortizes —
        # the measurement targets the engine, not the stacking)
        ("sweep", lambda: figures.sweep_rounds_per_sec(r(256, 128))),
        # the wireless-environment subsystem: in-scan channel refresh
        # overhead (fixed vs fading vs AR(1) vs AR(1)+imperfect-CSI; the
        # CSI re-solve must stay within 2x of plain fading, asserted) and
        # the CSI-robustness figure (scheme x csi_error x seed bands)
        ("channel", lambda: figures.channel_rounds_per_sec(r(256, 96))),
        ("csi_robustness", lambda: figures.csi_robustness(r(400, 60))),
        # the client-algorithm registry: FedProx / FedDyn / SCAFFOLD vs
        # local SGD on dirichlet splits with H=4 local steps — the
        # correctors' two-slot energy ratio and the non-IID separation
        # (drift-dominated noise regime) are asserted
        ("clients", lambda: figures.client_algorithms(r(200, 60), s)),
        # the declarative spec axes: server optimizer / local steps /
        # partial participation, each one field on the baseline spec
        ("scenarios", lambda: figures.scenario_axes(r(120, 30))),
        ("roofline", roofline_rows),
    ]
    if args.only:
        keep = set(args.only.split(","))
        benches = [b for b in benches if b[0] in keep]

    print("name,us_per_call,derived")
    failed = []
    for name, fn in benches:
        t0 = time.time()
        try:
            rows = fn()
        except Exception as e:  # keep the harness alive; report the failure
            print(f"{name},0,ERROR={e!r}", flush=True)
            failed.append(name)
            continue
        for row in rows:
            print(",".join(str(c) for c in row), flush=True)
        print(f"# {name} took {time.time()-t0:.1f}s", file=sys.stderr)

    # harness-level run manifest: which benchmarks ran, with which knobs, on
    # which jax/platform — makes a whole results/ directory self-describing
    # (each bench_*.json additionally embeds its own per-config manifests).
    # Written last: it asks JAX for the backend, which takes the chip
    import json
    import os

    from repro import obs

    os.makedirs(figures.RESULTS_DIR, exist_ok=True)
    with open(os.path.join(figures.RESULTS_DIR, "run_manifest.json"),
              "w") as f:
        json.dump(obs.run_manifest(extra={
            "harness": "benchmarks.run",
            "quick": args.quick,
            "benchmarks": [name for name, _ in benches],
            "backend": common.DEFAULT_BACKEND,
            "driver": common.DEFAULT_DRIVER,
        }), f, indent=2, default=str)
    if failed:
        # the CI smoke relies on in-benchmark assertions (e.g. the channel
        # benchmark's 2x CSI-refresh budget) actually failing the job — a
        # swallowed error must not exit 0
        print(f"# FAILED: {','.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
