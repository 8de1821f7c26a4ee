"""Serving example: batched greedy decode of a reduced model on a device
mesh — the serve_step the decode_32k / long_500k dry-run shapes lower.

    PYTHONPATH=src python examples/serve_decode.py [--arch xlstm-1.3b]
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs.registry import get_config, reduce_config
from repro.launch.mesh import make_host_mesh
from repro.launch import serve as serve_lib
from repro.models import transformer as T


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-1.3b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    args = ap.parse_args()

    mesh = make_host_mesh(4, 2)
    cfg = reduce_config(get_config(args.arch))
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    max_len = args.prompt_len + args.new_tokens

    decode_step, in_sh = serve_lib.build_decode_step(cfg, mesh)
    prefill_step, pre_in_sh = serve_lib.build_prefill_cache_step(cfg, mesh, max_len)
    cache = T.init_cache(cfg, args.batch, max_len)
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    tokens_like = {"tokens": prompts[:, :1], "pos": jnp.asarray(0)}
    ps, cs, bs = in_sh(params, cache, tokens_like)
    pps, pbs = pre_in_sh(params, {"tokens": prompts})
    with jax.set_mesh(mesh):
        params = jax.device_put(params, ps)
        prompts = jax.device_put(prompts, pbs["tokens"])
        from jax.sharding import NamedSharding, PartitionSpec as P
        tok_out = NamedSharding(mesh, P("data"))
        # real prefill: one forward pass writes the whole decode cache
        prefill = jax.jit(prefill_step, in_shardings=(ps, pbs),
                          out_shardings=(tok_out, cs))
        nxt, cache = prefill(params, {"tokens": prompts})
        step = jax.jit(decode_step, in_shardings=(ps, cs, bs["tokens"], bs["pos"]),
                       out_shardings=(tok_out, cs))
        generated = [nxt]
        t0 = time.time()
        for pos in range(args.prompt_len, max_len - 1):
            nxt, cache = step(params, cache, generated[-1][:, None],
                              jnp.asarray(pos))
            generated.append(nxt)
        dt = time.time() - t0
    out = jnp.stack(generated, axis=1)
    print(f"arch {cfg.name}: generated {out.shape} tokens for "
          f"{args.batch} requests")
    print(f"first request: {out[0].tolist()}")
    print(f"decode throughput {args.batch * (len(generated)-1) / dt:.1f} tok/s "
          "(CPU-mesh simulation)")


if __name__ == "__main__":
    main()
