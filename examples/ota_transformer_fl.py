"""The paper's technique at framework scale: OTA normalized-gradient
aggregation as the data-parallel collective of a *transformer* LM train step
on a JAX device mesh — the same code path the 256/512-chip dry-run lowers,
executed for real on forced host devices.

Each of the 4 data shards is one FL "mobile device" with its own data shard;
the gradient all-reduce is the over-the-air superposition (ota_psum).

    PYTHONPATH=src python examples/ota_transformer_fl.py [--steps 30]
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compile_cache import enable_compile_cache
from repro.configs.registry import get_config, reduce_config
from repro.core import amplification as amp
from repro.core.channel import ChannelConfig, draw_channel
from repro.data.datasets import token_stream
from repro.launch import train as train_lib
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.optim.optimizers import sgd, inverse_power_schedule


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-moe-1b-a400m")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--scheme", default="normalized")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    args = ap.parse_args()

    mesh = make_host_mesh(4, 2)   # 4 FL clients x 2-way tensor parallel
    k_clients = mesh.shape["data"]
    cfg = reduce_config(get_config(args.arch), seq_len=args.seq)
    print(f"mesh {dict(mesh.shape)}; arch {cfg.name}; "
          f"params ~{cfg.param_count()/1e6:.1f}M; scheme {args.scheme}")

    # the paper's channel + Algorithm 1
    chan = ChannelConfig(num_devices=k_clients, channel_mean=1e-3)
    h = np.asarray(draw_channel(jax.random.PRNGKey(0), chan))
    sol = amp.solve_problem3(h, chan.noise_var, cfg.param_count(), chan.b_max)
    ota = train_lib.OTARunParams(h=h, b=sol.b,
                                 a=1.0 / float(np.sum(h * sol.b)),
                                 noise_var=chan.noise_var,
                                 grad_bound=5.0)
    print(f"Problem 3 -> Z={sol.Z:.3f}, a={ota.a:.1f}")

    params = T.init_params(cfg, jax.random.PRNGKey(1))
    opt = sgd(inverse_power_schedule(0.75, eta0=0.5))
    opt_state = opt.init(params)
    step, in_sh = train_lib.build_train_step(
        cfg, mesh, scheme=args.scheme, aggregation_axes=("data",),
        ota=ota, optimizer=opt)

    tokens = token_stream(jax.random.PRNGKey(2), args.batch, args.seq + 1,
                          cfg.vocab_size)
    batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
    ps, os_, bs = in_sh(params, opt_state, batch)
    with jax.set_mesh(mesh):
        params = jax.device_put(params, ps)
        opt_state = jax.device_put(opt_state, os_)
        batch_s = jax.device_put(batch, bs)
        jitted = jax.jit(step, in_shardings=(ps, os_, bs, NamedSharding(mesh, P())),
                         out_shardings=(ps, os_, None))
        t0 = time.time()
        for i in range(args.steps):
            params, opt_state, m = jitted(
                params, opt_state, batch_s,
                jax.random.fold_in(jax.random.PRNGKey(3), i))
            if i % 5 == 0 or i == args.steps - 1:
                print(f"step {i:4d}  loss {float(m['loss']):.4f}  "
                      f"grad_norm {float(m['grad_norm']):.3f}")
        dt = time.time() - t0
    print(f"{args.steps} steps in {dt:.1f}s "
          f"({dt/args.steps*1e3:.0f} ms/step on CPU-mesh simulation)")


if __name__ == "__main__":
    main()
