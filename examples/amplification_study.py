"""Study of the paper's system-parameter optimization (Sec. IV).

Sweeps channel realizations and noise levels, solving Problem 3 optimally for
each, and prints how the solution structure changes — from the waterfilling-
like interior regime (low noise: equalize h_k b_k) to the corner regime
(high noise: every b_k at its cap), plus the Case-I S* (eq. 26) and the
Case-II rate/bias frontier.

    PYTHONPATH=src python examples/amplification_study.py
"""
import math

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import (case2_bias_floor, optimal_S, solve_problem3)
from repro.core.channel import ChannelConfig, draw_channel

K = 20
B_MAX = math.sqrt(5.0)


def main() -> None:
    enable_compile_cache()
    print("=== Problem 3 solution structure vs noise level ===")
    print(f"{'n*sigma^2':>12s} {'Z':>12s} {'#b at cap':>10s} {'cv(h*b)':>10s}")
    cfg = ChannelConfig(num_devices=K, channel_mean=1e-3)
    h = np.asarray(draw_channel(jax.random.PRNGKey(0), cfg))
    for log_c in (-12, -9, -7, -5, -3):
        c = 10.0 ** log_c
        sol = solve_problem3(h, c, 1, B_MAX)     # n*sigma^2 folded into c
        at_cap = int(np.sum(sol.b > B_MAX - 1e-6))
        hb = h * sol.b
        cv = float(np.std(hb) / np.mean(hb))
        print(f"{c:12.0e} {sol.Z:12.4f} {at_cap:10d} {cv:10.4f}")
    print("\nlow noise -> interior solution equalizing h_k b_k "
          "(cv ~ 0, few at cap);\nhigh noise -> corner solution "
          "(all b_k = b_max: maximize received power).")

    print("\n=== Case-I optimal S (eq. 26) vs expected loss drop ===")
    sol = solve_problem3(h, 1e-7 * 1000, 1, B_MAX)
    for drop in (0.5, 2.0, 10.0):
        s = optimal_S(sol.Z, L=2.0, p=0.75, expected_loss_drop=drop)
        print(f"  E[F(w1)-F(wT)] = {drop:5.1f}  ->  S* = {s:8.3f} "
              f"(a = {1.0 / (s * float(np.sum(h * sol.b))):10.1f})")

    print("\n=== Case-II rate/bias frontier (Remark 2) ===")
    print(f"{'q_max=s':>8s} {'bias floor eps':>16s} {'rounds to 2*eps':>16s}")
    for s in (0.95, 0.99, 0.999):
        eps = case2_bias_floor(sol.Z, L=2.0, G=10.0, M=0.5,
                               theta_th=math.pi / 3, s=s)
        import math as m
        rounds = m.ceil(m.log(0.5) / m.log(s))  # halve the linear term
        print(f"{s:8.3f} {eps:16.4f} {rounds:16d}")
    print("\nthe tradeoff: pushing the floor down (s -> 1) slows the "
          "geometric term — choose s for your tolerance (Fig. 3(b)).")

    # --- the same study end-to-end, declaratively -------------------------
    # amplification policy is a spec field: the Fig. 2(a)-style comparison is
    # one dataclasses.replace away from the baseline spec
    import dataclasses

    from repro.fl import DataSpec, EvalSpec, Experiment, ExperimentSpec, FLConfig

    print("\n=== optimal (a, b) vs b_k = b_k^max, via ExperimentSpec ===")
    base = ExperimentSpec(
        fl=FLConfig(num_devices=K, scheme="normalized", case="II", eta=0.01,
                    channel=cfg, grad_bound=25.0, s_target=0.995),
        data=DataSpec(dataset="ridge", num_train=2000),
        eval=EvalSpec(every=100))
    for policy in ("optimal", "bmax"):
        spec = dataclasses.replace(
            base, fl=dataclasses.replace(base.fl, amplification=policy))
        e = Experiment(spec)
        e.run(200)
        print(f"  amplification={policy:8s} -> final gap "
              f"{e.history['gap'][-1]:10.5f}  (tx energy/round "
              f"{e.history['tx_energy'][-1]:8.2f})")


if __name__ == "__main__":
    main()
