"""End-to-end driver (paper Case I): federated 10-digit classification with
over-the-air normalized-gradient aggregation — a few hundred rounds, all
aggregation schemes, with resumable ``Experiment`` checkpoints.  Rounds run
on the compiled ``lax.scan`` engine by default (``--driver python`` for the
host loop).

    PYTHONPATH=src python examples/fl_mnist_ota.py [--rounds 300] [--scheme all]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.compile_cache import enable_compile_cache
from repro.fl import Experiment


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=300)
    ap.add_argument("--scheme", default="all",
                    help="normalized|benchmark1|benchmark2|onebit|mean|all")
    ap.add_argument("--driver", default="scan", choices=("scan", "python"),
                    help="round-loop driver: the compiled lax.scan engine "
                         "(default) or the per-round host loop")
    ap.add_argument("--ckpt-dir", default="results/ckpt_mnist")
    args = ap.parse_args()

    from benchmarks import common
    from benchmarks.common import CaseIExperiment
    common.DEFAULT_DRIVER = args.driver
    exp = CaseIExperiment()
    print(f"K=20 devices, non-IID Dirichlet split, model dim {exp.dim}, "
          f"calibrated G = {exp.calibrate_G():.2f}")

    schemes = (["normalized", "benchmark1", "benchmark2", "onebit"]
               if args.scheme == "all" else [args.scheme])
    for scheme in schemes:
        cfg = exp.config(scheme=scheme)
        e = exp.experiment(cfg, eval_every=max(1, args.rounds // 10))
        e.run(args.rounds)
        hist = e.history
        accs = ", ".join(f"{t}:{a:.3f}" for t, a in
                         zip(hist["eval_round"], hist["test_acc"]))
        print(f"[{scheme:12s}] test acc over rounds: {accs}")
        # full resumable checkpoint: params + server-opt state + channel/round
        os.makedirs(args.ckpt_dir, exist_ok=True)
        path = e.save(os.path.join(args.ckpt_dir, f"{scheme}.msgpack"))
        resumed = Experiment(e.spec).load(path)
        assert resumed.round == args.rounds
        print(f"             checkpoint -> {path} "
              f"(resumes at round {resumed.round})")


if __name__ == "__main__":
    main()
