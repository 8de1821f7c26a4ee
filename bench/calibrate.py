"""Readings behind a cell's limits, in one process (the benchmark's own
runs never run this):

    python3 -m bench.calibrate --workload k20.fixed --seeds 101 102 ... \
        --controls 3

For every seed: the program's checked segments against the reference
(the sound reading).  For the first ``--controls`` seeds also the control
(the reference in the program's place, its matmuls at the next precision
down, ``high``) and the planted faults (the reference in the program's
place with half of each batch left out, and with the output bias's update
negated).  One JSON line per reading on standard output.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from bench import cells, compare, run, workload

READINGS = ({"precision": "high"}, {"fault": "half_batch"},
            {"fault": "negate_leaf"})


def _numbers(got, want, ref) -> dict:
    """The compared numbers plus, for the record, every leaf's change gap."""
    leaves = {f"{name}.{leaf}": gap
              for name, step in (("update1", 0), ("change3", -1))
              for leaf, gap in compare.leaf_change_gaps(
                  got["params0"], got["params"][step], want["params0"],
                  want["params"][step]).items()}
    return {**compare.numbers(got, want, ref.eval_loss), "leaves": leaves}


def read(cell: cells.Cell, seed: int, controls: bool, dev) -> list:
    data_seed, fl_seed = workload.derive_seeds(seed)
    t0 = time.perf_counter()
    exp, prog, setup, _ = run.start_checked(cell, data_seed, fl_seed, dev)
    del exp
    run.free_program()
    ref = cells.reference(cell.config).Reference(cell.config, data_seed,
                                                 fl_seed)
    want = run.follow(ref, cell)
    out = [{"reading": "program", **_numbers(prog, want, ref)}]
    if controls:
        for kw in READINGS:
            got = run.follow(ref, cell, **kw)
            out.append({"reading": kw.get("fault", "control_high"),
                        **_numbers(got, want, ref)})
    for o in out:
        o.update(cell=cell.name, seed=seed)
    run.log(f"calibrate: seed {seed} {time.perf_counter() - t0:.1f} s "
            f"setup {setup}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3)
    args = ap.parse_args(argv)
    cell = cells.resolve(args.workload)
    jax = run.configure_jax(cell.config)
    try:
        dev = run.check_device(jax, cell.chips)[0]
    except run.NoAccelerator as e:
        run.log(f"calibrate: {e}")
        return 3
    for i, seed in enumerate(args.seeds):
        for line in read(cell, seed, i < args.controls, dev):
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
