#!/usr/bin/env python3
"""On-chip smoke test of the OTA-FL round, through the user's entry points
(``repro.fl.ExperimentSpec`` -> ``Experiment``).

    python chip_smoke.py               # one TPU chip: phases A, B, C + Mosaic proof
    python chip_smoke.py --four-chips  # four TPU chips: the device_mesh phase only

Phases (one chip):

A. The paper's Sec.-V system at full width: the 784-64-64-10 MLP on
   synthetic MNIST (60,000 train / 10,000 test, dirichlet alpha=1, batch
   50), K=20, ``normalized`` scheme, Case I, Algorithm-1 amplification,
   ``backend="kernels"``, scan driver, 30 rounds.  Checks finite values,
   a falling training loss, and the params against ``backend="vmap"``.
B. The streamed round with block fading (Problem 3 re-solved inside the
   scan): K=1024, ``k_block=128``, 10 rounds, kernels vs vmap.
C. Resume: save after 5 rounds of A's spec, load, run 5 more; the params
   must equal 10 straight rounds.

Mosaic proof: the aggregation kernels, and the compiled round of A's and
B's specs, contain ``tpu_custom_call`` — the chip ran the Pallas kernels,
not the XLA oracles of ``repro.kernels.ref``.

``--four-chips`` runs B's spec with ``device_mesh=4`` on the physical mesh
and again emulated on one device (``REPRO_FL_MESH=emulate``), and compares.

Both backends run under ``jax_default_matmul_precision="highest"``: the vmap
reference superposes with ``tensordot`` and the MLP uses matmuls, which the
TPU otherwise rounds through bfloat16; fp32 is the paper's arithmetic and
the tolerance below is an fp32 tolerance.

Times are host clocks around work that ends in ``block_until_ready``; they
are informational, not metrics.  Any failed check exits non-zero.  The last
line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
CKPT = ROOT / "results" / "chip_smoke" / "resume.msgpack"

# tests/test_backends.py::assert_trees_close (vmap vs kernels)
BACKEND_TOL = dict(rtol=2e-4, atol=2e-5)
# tests/test_streaming.py::STREAM_TOL (blocked fp32 re-association)
STREAM_TOL = dict(rtol=3e-4, atol=1e-6)

N_TRAIN, N_TEST = 60_000, 10_000
ROUNDS_A, ROUNDS_B, ROUNDS_C = 30, 10, 5
K_A, K_B, K_BLOCK = 20, 1024, 128
MESH = 4


class SmokeFailure(Exception):
    """A check of the smoke test failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def device_info(want: int) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no accelerator — JAX found platform "
            f"{d.platform!r} ({len(devs)} device(s)); this test runs only on "
            "a TPU and has no CPU fallback")
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    if len(devs) < want:
        raise SystemExit(f"chip_smoke: needs {want} TPU devices, found "
                         f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def mnist_spec(num_devices: int, *, num_train: int = N_TRAIN,
               num_test: int = N_TEST, block_fading: bool = False, **fl):
    """The repo's Case-I experiment (benchmarks/common.py) at MNIST's own
    train/test sizes."""
    from repro.core.channel import ChannelConfig
    from repro.fl import (DataSpec, EvalSpec, ExperimentSpec, FLConfig,
                          ModelSpec)
    return ExperimentSpec(
        fl=FLConfig(num_devices=num_devices, scheme="normalized", case="I",
                    p=0.75, amplification="optimal", backend="kernels",
                    channel=ChannelConfig(num_devices=num_devices,
                                          channel_mean=1e-3,
                                          block_fading=block_fading),
                    smoothness_L=5.0, expected_loss_drop=2.0, seed=0, **fl),
        data=DataSpec(dataset="synthetic_mnist", split="dirichlet",
                      alpha=1.0, batch_size=50, num_train=num_train,
                      num_test=num_test),
        model=ModelSpec(kind="mlp", hidden=64),
        eval=EvalSpec(every=10),
        driver="scan")


def with_backend(spec, backend: str):
    return dataclasses.replace(
        spec, fl=dataclasses.replace(spec.fl, backend=backend))


def leaves(params):
    import jax
    import numpy as np
    return [np.asarray(l) for l in jax.tree_util.tree_leaves(params)]


def run_timed(spec, rounds: int, label: str):
    """Run a fresh experiment; returns it and the wall time of ``run``."""
    import jax
    from repro.fl import Experiment
    e = Experiment(spec)
    e.setup()
    t0 = time.perf_counter()
    e.run(rounds)
    jax.block_until_ready(e.params)
    dt = time.perf_counter() - t0
    print(f"  [info] {label}: {rounds} rounds in {dt:.3f} s", flush=True)
    return e, dt


def report_times(label: str, first: float, steady: float) -> None:
    print(f"  [info, not a metric] {label}: first run {first:.3f} s, "
          f"steady run {steady:.3f} s, compile ~{first - steady:.3f} s",
          flush=True)


def assert_finite(e, label: str) -> None:
    import numpy as np
    for l in leaves(e.params):
        check(bool(np.all(np.isfinite(l))), f"{label}: non-finite params")
    for k, v in e.history.items():
        check(bool(np.all(np.isfinite(np.asarray(v, np.float64)))),
              f"{label}: non-finite history {k!r}")


def assert_close(got, want, tol: dict, label: str) -> bool:
    """allclose per leaf; returns whether the trees are bitwise-equal."""
    import numpy as np
    bitwise = True
    for i, (g, w) in enumerate(zip(leaves(got), leaves(want))):
        err = float(np.max(np.abs(g - w)))
        bitwise &= bool(np.array_equal(g, w))
        check(bool(np.allclose(g, w, **tol)),
              f"{label}: leaf {i} differs beyond {tol} (max abs {err:.3e})")
    return bitwise


def phase_a(spec, rounds: int = ROUNDS_A) -> None:
    print(f"phase A: dense round, K={spec.fl.num_devices}, "
          f"{rounds} rounds, kernels vs vmap", flush=True)
    e, first = run_timed(spec, rounds, "kernels, first")
    assert_finite(e, "phase A")
    loss = e.history["train_loss"]
    print(f"  train_loss at rounds {e.history['eval_round']}: {loss}",
          flush=True)
    check(loss[-1] < loss[0], f"phase A: train_loss did not fall "
          f"({loss[0]} -> {loss[-1]})")
    again, steady = run_timed(spec, rounds, "kernels, steady")
    report_times("phase A kernels", first, steady)
    print(f"  [info] rerun bitwise-equal: "
          f"{assert_close(again.params, e.params, BACKEND_TOL, 'rerun')}",
          flush=True)
    ref, _ = run_timed(with_backend(spec, "vmap"), rounds, "vmap reference")
    bitwise = assert_close(e.params, ref.params, BACKEND_TOL,
                           "phase A kernels vs vmap")
    print(f"phase A: PASS (kernels vs vmap within {BACKEND_TOL}; "
          f"bitwise={bitwise})", flush=True)


def phase_b(spec, rounds: int = ROUNDS_B) -> None:
    print(f"phase B: streamed round with block fading, "
          f"K={spec.fl.num_devices}, k_block={spec.fl.k_block}, "
          f"{rounds} rounds, kernels vs vmap", flush=True)
    e, first = run_timed(spec, rounds, "kernels, first")
    assert_finite(e, "phase B")
    _, steady = run_timed(spec, rounds, "kernels, steady")
    report_times("phase B kernels", first, steady)
    ref, _ = run_timed(with_backend(spec, "vmap"), rounds, "vmap reference")
    bitwise = assert_close(e.params, ref.params, STREAM_TOL,
                           "phase B kernels vs vmap")
    print(f"phase B: PASS (kernels vs vmap within {STREAM_TOL}; "
          f"bitwise={bitwise})", flush=True)


def phase_c(spec, rounds: int = ROUNDS_C, ckpt: Path = CKPT) -> None:
    print(f"phase C: save after {rounds} rounds, load, {rounds} more "
          f"== {2 * rounds} straight", flush=True)
    from repro.fl import Experiment
    first = Experiment(spec)
    first.run(rounds)
    ckpt.parent.mkdir(parents=True, exist_ok=True)
    first.save(str(ckpt))
    resumed = Experiment(spec).load(str(ckpt))
    check(resumed.round == rounds,
          f"phase C: loaded round {resumed.round}, saved {rounds}")
    resumed.run(rounds)
    straight, _ = run_timed(spec, 2 * rounds, "straight")
    for i, (g, w) in enumerate(zip(leaves(resumed.params),
                                   leaves(straight.params))):
        check(g.shape == w.shape and bool((g == w).all()),
              f"phase C: resumed leaf {i} differs from the straight run")
    print("phase C: PASS (resumed == straight, bitwise)", flush=True)


def round_program(spec) -> str:
    """Compiled text of one engine round of ``spec`` — the program the scan
    driver runs each round — lowered with the experiment's own state."""
    import jax
    import jax.numpy as jnp
    from repro.fed import runtime
    from repro.fl import Experiment
    e = Experiment(spec).setup()
    st = e.state
    step = runtime.make_round_step(e.cfg, e.task.grad_fn)
    opt_state = runtime.server_optimizer(e.cfg).init(st.params)
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    return step.lower(st.params, opt_state, None, e.task.batch_provider(1),
                      f32(st.h), None, f32(st.b), f32(st.a), f32(st.eta0),
                      jnp.asarray(1), jax.random.PRNGKey(0)
                      ).compile().as_text()


def mosaic_proof(n: int) -> None:
    """The aggregation kernels at A's and B's shapes, and each phase's
    compiled round, contain the Mosaic custom call."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    print("mosaic proof: interpret=None resolves to Mosaic", flush=True)
    programs = {}
    for k, kb in ((K_A, None), (K_B, K_BLOCK), (K_BLOCK, None)):
        g = jax.ShapeDtypeStruct((k, n), jnp.float32)
        v = jax.ShapeDtypeStruct((k,), jnp.float32)
        z = jax.ShapeDtypeStruct((n,), jnp.float32)
        programs[f"batched_moments K={k} k_block={kb}"] = jax.jit(
            lambda g, kb=kb: ops.batched_moments(g, k_block=kb)).lower(g)
        programs[f"ota_superpose K={k} k_block={kb}"] = jax.jit(
            lambda g, s, z, kb=kb: ops.ota_superpose(g, s, z, 1.0,
                                                     k_block=kb)
        ).lower(g, v, z)
    for name, lowered in programs.items():
        found = "tpu_custom_call" in lowered.compile().as_text()
        print(f"  {name}: tpu_custom_call={found}", flush=True)
        check(found, f"mosaic proof: {name} compiled without the kernel")
    for name, spec in (("phase A round", mnist_spec(K_A)),
                       ("phase B round", phase_b_spec())):
        found = "tpu_custom_call" in round_program(spec)
        print(f"  {name}: tpu_custom_call={found}", flush=True)
        check(found, f"mosaic proof: {name} compiled without the kernel")
    print("mosaic proof: PASS", flush=True)


def phase_b_spec(**kw):
    return mnist_spec(K_B, k_block=K_BLOCK, block_fading=True, **kw)


def four_chips(spec, rounds: int = ROUNDS_B) -> None:
    import jax
    from repro.distribution import sharding
    from repro.fed import runtime
    print(f"four chips: device_mesh={MESH}, K={spec.fl.num_devices}, "
          f"k_block={spec.fl.k_block}, {rounds} rounds, physical vs "
          "emulated", flush=True)
    check(len(jax.devices()) >= MESH,
          f"four chips: {len(jax.devices())} devices present")
    check(sharding.device_mesh(MESH) is not None,
          "four chips: the physical mesh did not engage")
    spec = dataclasses.replace(spec, device_mesh=MESH)
    phys, first = run_timed(spec, rounds, "physical mesh, first")
    assert_finite(phys, "four chips physical")
    _, steady = run_timed(spec, rounds, "physical mesh, steady")
    report_times("physical mesh", first, steady)
    os.environ[sharding._EMULATE_ENV] = "emulate"
    runtime.clear_compile_caches()
    check(sharding.device_mesh(MESH) is None,
          "four chips: emulation did not disengage the mesh")
    emu, _ = run_timed(spec, rounds, "emulated on one device")
    bitwise = assert_close(phys.params, emu.params, STREAM_TOL,
                           "four chips physical vs emulated")
    print(f"four chips: PASS (physical vs emulated within {STREAM_TOL}; "
          f"bitwise={bitwise})", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the device_mesh=4 phase (needs 4 chips)")
    args = ap.parse_args()

    device = device_info(MESH if args.four_chips else 1)
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    jax.config.update("jax_default_matmul_precision", "highest")

    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(phase_b_spec())
    else:
        phase_a(mnist_spec(K_A))
        phase_b(phase_b_spec())
        phase_c(mnist_spec(K_A))
        e = mnist_spec(K_A)
        from repro.fl import build_task
        n = build_task(e.data, e.model, K_A).model_dim
        mosaic_proof(n)
    print(f"[info] total {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    try:
        main()
    except SmokeFailure as e:
        raise SystemExit(f"chip_smoke: FAIL: {e}")
