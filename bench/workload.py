"""The one traffic generator: a configuration and a traffic mix (both plain
JSON) plus ``--seed`` become the experiment the window drives.

The traffic is a closed loop of segment requests from one client: each
request runs ``segment_rounds`` FL rounds through ``Experiment.run`` and
ends when the host holds that segment's history (the engine's per-chunk
``device_get``), as a researcher's loop receives it.  Every input (data,
shards, initial weights, batch draws, channel draws, noise keys) derives
from two seeds drawn from ``--seed``.
"""
from __future__ import annotations

import time
from typing import Callable, List, Tuple

import numpy as np

# both derived seeds stay below 2**31 with room for the simulator's
# seed + 3 offsets, so every PRNG key keeps all of their bits
SEED_LIMIT = 2 ** 31 - 16


def derive_seeds(seed: int) -> Tuple[int, int]:
    """(data seed, channel seed) from any whole number."""
    a, b = np.random.SeedSequence(abs(int(seed))).generate_state(2)
    return int(a) % SEED_LIMIT, int(b) % SEED_LIMIT


# what the one generator implements; any other key or value is refused
TRAFFIC_KEYS = {"loop", "clients", "segment_rounds", "check_segments", "eval",
                "trace_seconds", "spec", "why"}


def check_traffic(traffic: dict) -> None:
    """Refuse a mix this generator cannot send as written."""
    unknown = set(traffic) - TRAFFIC_KEYS
    if unknown:
        raise ValueError(f"traffic keys {sorted(unknown)} are not read; "
                         f"known: {sorted(TRAFFIC_KEYS)}")
    if traffic["loop"] != "closed" or traffic["clients"] != 1:
        raise ValueError("the generator sends a closed loop from one client")


def make_spec(config: dict, traffic: dict, data_seed: int, fl_seed: int):
    """The ``ExperimentSpec`` of a configuration under a traffic mix: the
    configuration's and the mix's ``spec`` mappings and the two seeds folded
    in by the program's own sweep-axis resolution, which raises on a name
    the spec does not have."""
    from repro.fl import ExperimentSpec
    from repro.fl.spec import EvalSpec, apply_axes

    check_traffic(traffic)
    clash = set(config["spec"]) & set(traffic["spec"])
    if clash:
        raise ValueError(f"configuration and traffic both set {sorted(clash)}")
    base = ExperimentSpec(eval=EvalSpec(enabled=traffic["eval"]),
                          chunk_size=traffic["segment_rounds"])
    return apply_axes(base, {**config["spec"], **traffic["spec"],
                             "seed": fl_seed, "data.seed": data_seed})


def closed_loop(request: Callable[[], None], seconds: float,
                after: Callable[[], None] = lambda: None
                ) -> Tuple[List[float], float]:
    """Send requests back to back until ``seconds`` have passed; the last
    one started inside the window completes.  Returns the latency of every
    request and the window's length (first send to last completion)."""
    lat: List[float] = []
    start = time.perf_counter()
    deadline = start + seconds
    while True:
        t0 = time.perf_counter()
        request()
        t1 = time.perf_counter()
        lat.append(t1 - t0)
        after()
        if t1 >= deadline:
            return lat, time.perf_counter() - start
