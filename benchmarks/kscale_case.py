"""One (devices, k_block) case of the K-scale streaming benchmark, run as a
SUBPROCESS: peak RSS is a process-lifetime high-water mark, so the
dense-vs-streaming peak-memory comparison is only meaningful when every case
owns a fresh process.

    PYTHONPATH=src python -m benchmarks.kscale_case \
        --devices 100000 --k-block 1000 --rounds 4

Prints one JSON line: peak RSS (MB), rounds/sec, and a final diagnostic.

The task is a shared-pool linear regression: every device's minibatch is B
rows gathered from one [pool, dim] example matrix by a (round, device)-keyed
index draw, so the DATA working set is O(pool * dim) no matter how many
devices exist — the device axis carries only PRNG folds.  That isolates what
this benchmark measures: the engine's own per-device memory (batch gather,
gradient stack, superposition), which the dense path materializes at
O(K * (B + 1) * dim) and the streaming path at O(k_block * (B + 1) * dim).

The radio environment comes from the lazy per-block samplers
(``draw_channel_block`` / ``relative_gains_block``) — the 100k-device path
never holds more than one K-block of geometry or fading draws in flight.
Problem 3's interior-point solve assembles a [K+1, K] system (itself
O(K^2) memory), so at this scale ``b`` rides at ``b_max`` and the receiver
gain normalizes the designed effective gain ``a * sum(h b)`` to 1 — the
paper's Case-I shape with the server optimization held out of the loop.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import sys
import time

# the exec-fresh VmHWM reader this benchmark pioneered, promoted to the
# observability library (see its docstring for why ru_maxrss lies under
# fork and VmHWM does not)
from repro.obs.profiling import peak_rss_mb


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, required=True)
    ap.add_argument("--k-block", type=int, default=0,
                    help="streaming K-block size; 0 = the dense path")
    ap.add_argument("--device-mesh", type=int, default=0,
                    help="sharded streaming mesh width (requires --k-block);"
                         " 0 = plain stream.  Launch with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=D for the"
                         " physical path; without the forced devices the"
                         " engine runs its (bitwise-identical) emulated path")
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--dim", type=int, default=2048)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--pool", type=int, default=4096)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.channels.geometry import GeometryConfig, relative_gains_block
    from repro.core.channel import ChannelConfig, draw_channel_block
    from repro.fed import runtime

    K, d, B = args.devices, args.dim, args.batch
    kb = args.k_block or None

    key = jax.random.PRNGKey(0)
    X = jax.random.normal(jax.random.fold_in(key, 1), (args.pool, d))
    w_true = jax.random.normal(jax.random.fold_in(key, 2), (d,))
    y = X @ w_true + 0.1 * jax.random.normal(jax.random.fold_in(key, 3),
                                             (args.pool,))

    def grad_fn(params, batch):
        xb, yb = batch
        r = xb @ params["w"] - yb
        return {"w": xb.T @ r / r.shape[0]}

    bk = jax.random.fold_in(key, 4)

    def device_batch(t, dev):
        # B pool rows keyed by (round, device index): the SAME draw whether
        # it is materialized dense or one K-block at a time
        dk = jax.random.fold_in(jax.random.fold_in(bk, t), dev)
        idx = jax.random.randint(dk, (B,), 0, args.pool)
        return X[idx], y[idx]

    def block_batch_provider(t, dev_idx):
        return jax.vmap(lambda i: device_batch(t, i))(dev_idx)

    dense_batch = jax.jit(
        lambda t: jax.vmap(lambda i: device_batch(t, i))(jnp.arange(K)))

    ccfg = ChannelConfig(num_devices=K, channel_mean=1e-3, noise_var=1e-7)
    geo = GeometryConfig(shadowing_std_db=4.0)
    ck = jax.random.PRNGKey(7)
    step = kb or min(K, 10_000)
    blocks = []
    for lo in range(0, K, step):
        devs = jnp.arange(lo, min(lo + step, K))
        scale = ccfg.rayleigh_scale() * relative_gains_block(ck, geo, devs)
        blocks.append(draw_channel_block(ck, ccfg, devs, scale))
    h = np.asarray(jnp.concatenate(blocks), np.float64)
    b = np.full(K, ccfg.b_max)
    a = 1.0 / float(np.sum(h * b))

    cfg = runtime.FLConfig(
        num_devices=K, case="I", p=0.75, channel=ccfg, scheme="normalized",
        backend="kernels", smoothness_L=5.0, expected_loss_drop=2.0,
        grad_bound=10.0, seed=0, k_block=kb,
        device_mesh=args.device_mesh if args.device_mesh > 1 else None)
    params0 = {"w": jnp.zeros((d,), jnp.float32)}
    state = runtime.FLState(params0, h, b, a, eta0=1.0, model_dim=d)

    if kb is None:
        provider, block_provider = dense_batch, None
    else:
        provider, block_provider = None, block_batch_provider

    def go(rounds):
        return runtime.run(cfg, state, grad_fn, provider, rounds,
                           driver="scan", chunk_size=1,
                           block_batch_provider=block_provider)

    go(1)                                          # compile warm-up
    t0 = time.perf_counter()
    _, hist = go(args.rounds)
    dt = time.perf_counter() - t0

    # bitwise trajectory fingerprint: the sharded benchmark compares the
    # physical and emulated runs of the same spec by digest, not tolerance
    params_sha = hashlib.sha256(
        np.ascontiguousarray(np.asarray(state.params["w"],
                                        np.float32)).tobytes()).hexdigest()

    from repro import obs

    json.dump({
        "devices": K, "k_block": args.k_block,
        "device_mesh": args.device_mesh, "dim": d, "batch": B,
        "rounds": args.rounds,
        "rounds_per_sec": args.rounds / dt,
        "peak_rss_mb": peak_rss_mb(),
        "grad_norm_mean_final": float(hist["grad_norm_mean"][-1]),
        "params_sha256": params_sha,
        "local_devices": jax.local_device_count(),
        "platform": jax.default_backend(),
        # self-describing identity block: config hash + structural signature
        # + the digest above (compare.py --manifest cross-checks signatures)
        "manifest": obs.run_manifest(cfg=cfg, params_digest=params_sha),
    }, sys.stdout)
    print()


if __name__ == "__main__":
    main()
