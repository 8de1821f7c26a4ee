"""Quickstart: the paper's system as one declarative spec.

An ``ExperimentSpec`` names what the paper iterates on — aggregation scheme,
channel, amplification policy, learning-rate case — plus the data split and
model; ``Experiment`` compiles it into the fused OTA round loop.  This file
builds the Case-II ridge experiment (smooth + strongly convex, so Lemma 2's
linear-convergence bound is computable exactly), prints the Problem-3 /
Algorithm-1 solution it runs on, and compares the measured optimality gap
with the bound.

    PYTHONPATH=src python examples/quickstart.py

The core of it is ~15 lines:

    spec = ExperimentSpec(
        fl=FLConfig(num_devices=20, scheme="normalized", case="II",
                    eta=0.01, channel=ChannelConfig(num_devices=20,
                                                    channel_mean=1e-3),
                    grad_bound=25.0, s_target=0.995),
        data=DataSpec(dataset="ridge", num_train=2000, dim=30),
        eval=EvalSpec(every=50),
    )
    e = Experiment(spec)
    e.run(300)
    print(e.history["gap"])

Scenario axes are one-field changes on the same spec:
``dataclasses.replace(spec, server_opt='adamw')``, ``local_steps=4``, or
``participation=0.5``.
"""
import dataclasses

import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core import case2_bound, solve_problem3, solve_problem3_jax
from repro.core.channel import ChannelConfig
from repro.fl import (DataSpec, EvalSpec, Experiment, ExperimentSpec,
                      FLConfig, build_task)

K, DIM = 20, 30


def main() -> None:
    enable_compile_cache()
    chan = ChannelConfig(num_devices=K, channel_mean=1e-3)
    spec = ExperimentSpec(
        fl=FLConfig(num_devices=K, scheme="normalized", case="II", eta=0.01,
                    channel=chan, grad_bound=25.0, s_target=0.995),
        data=DataSpec(dataset="ridge", num_train=2000, dim=DIM),
        eval=EvalSpec(every=50),
    )
    # the ridge task computes its exact smoothness/strong-convexity
    # constants; fold them into the spec (spec construction already
    # validated scheme/case/amplification against the registries)
    c = build_task(spec.data, spec.model, K).constants
    spec = dataclasses.replace(
        spec, fl=dataclasses.replace(spec.fl, smoothness_L=c["smoothness_L"],
                                     strong_convexity_M=c["strong_convexity_M"]))
    # setup() draws the channel and solves Problem 3 (Algorithm 1)
    e = Experiment(spec).setup()

    # --- the paper's parameter optimization, standalone -------------------
    state = e.state
    sol = solve_problem3(state.h, chan.noise_var, DIM, chan.b_max)
    print(f"Problem 3: Z = {sol.Z:.4f}  (optimal b in "
          f"[{sol.b.min():.3f}, {sol.b.max():.3f}], {sol.iterations} bisection steps)")
    sol_jax = solve_problem3_jax(jnp.asarray(state.h, jnp.float32),
                                 chan.noise_var, DIM, chan.b_max)
    print(f"jax-native Algorithm 1 (runs inside the compiled round loop): "
          f"Z = {float(sol_jax.Z):.4f}, {int(sol_jax.iterations)} bisection steps")
    print(f"receiver gain a*eta = {state.a * state.eta0:.4f}, "
          f"contraction q_max = {spec.fl.s_target}")

    # --- run FL rounds ----------------------------------------------------
    e.run(300)
    hist, cfg = e.history, e.cfg

    print(f"\n{'round':>6s} {'empirical gap':>14s} {'Lemma-2 bound':>14s}")
    for t, gap in zip(hist["eval_round"], hist["gap"]):
        bound = case2_bound(t, state.eta0, state.a, state.h, state.b,
                            c["smoothness_L"], c["strong_convexity_M"],
                            cfg.grad_bound, cfg.theta_th, chan.noise_var, DIM,
                            w1_dist_sq=4.0 * hist["gap"][0])
        print(f"{t:6d} {gap:14.5f} {bound:14.5f}")
    print(f"\nfinal gap {hist['gap'][-1]:.5f} (f* = {c['f_star']:.4f}) — "
          "linear convergence to the epsilon-ball, as Lemma 2 promises.")


if __name__ == "__main__":
    main()
