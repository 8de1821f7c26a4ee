"""Pallas TPU kernel: fused OTA superposition for every norm-scaling scheme.

Computes, for a block of the flat gradient dimension,

    y[j] = a * ( sum_k scale_k * pre(g[k, j]) + z[j] )

in one HBM pass: the K stacked device gradients stream through VMEM
``(K, block)`` tiles, the optional element-wise pre-transform (``sign`` for
the one-bit scheme) and the per-device scale vector are applied in-register,
the K-way reduction happens in VMEM, and the channel noise + receiver gain
fuse into the same tile before write-back.

``scale`` is a free per-device vector — the caller composes it as
``h_k * b_k * scheme.device_scale(stats)`` — so the SAME kernel serves
``normalized`` (h b / ||g||), ``benchmark1`` (h b / G), ``clipped``
(h b / max(||g||, G)), ``onebit`` (h b / sqrt(N), pre='sign'), and the
per-tensor variant (pre-scaled leaves, scale = h b).  An unfused
implementation reads the K gradients once for the scale, once for the sum and
touches y three times; this kernel is the paper's entire eq. (10) as a single
memory-bound sweep.

VMEM: the dense kernel holds a double-buffered ``(K, block)`` tile, so
``ops.ota_superpose`` shrinks the N-block as K grows to stay inside the
scoped VMEM limit; the per-column K-way sums, and so the result, do not
depend on the N-block.

Target: TPU VPU (8x128 lanes); validated on CPU via interpret=True against
``ref.ota_superpose_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

PRE_KINDS = ("identity", "sign")


def _ota_kernel(g_ref, scale_ref, noise_ref, a_ref, out_ref, *, pre):
    g = g_ref[...].astype(jnp.float32)              # [K, blk]
    if pre == "sign":
        g = jnp.sign(g)
    scale = scale_ref[...].astype(jnp.float32)      # [K, 1]
    acc = jnp.sum(g * scale, axis=0)                # superposition
    z = noise_ref[...].astype(jnp.float32)[0]       # [blk]
    out_ref[0, :] = a_ref[0, 0] * (acc + z)


def ota_aggregate_blocked(g: jax.Array, scale: jax.Array, noise: jax.Array,
                          a: jax.Array, *, block: int = 2048,
                          interpret: bool = True,
                          pre: str = "identity") -> jax.Array:
    """g: [K, N] stacked flat device gradients; scale: [K] per-device
    composite scale (h_k b_k x scheme scale); noise: [N]; a: scalar receiver
    gain; pre: element-wise pre-transform applied in-register.  Returns y [N].
    """
    if pre not in PRE_KINDS:
        raise ValueError(f"unknown pre-transform {pre!r}; one of {PRE_KINDS}")
    k, n = g.shape
    blk = min(block, n)
    if n % blk != 0:
        raise ValueError(f"N={n} must be divisible by block={blk}")
    grid = (n // blk,)
    out = pl.pallas_call(
        functools.partial(_ota_kernel, pre=pre),
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, blk), lambda i: (0, i)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, blk), lambda i: (0, i)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
    )(g, scale.reshape(k, 1), noise.reshape(1, n), a.reshape(1, 1))
    return out[0]


def _ota_stream_kernel(g_ref, scale_ref, noise_ref, a_ref, out_ref, *,
                       pre, num_k_blocks):
    """One (N-block, K-block) grid step: the device axis is the FAST grid
    dimension, so the output tile is revisited ``num_k_blocks`` times in a
    row and serves as the fp32 accumulator — only a ``(k_block, blk)`` tile
    of the stacked gradients is ever resident."""
    kb = pl.program_id(1)
    g = g_ref[...].astype(jnp.float32)              # [kb, blk]
    if pre == "sign":
        g = jnp.sign(g)
    scale = scale_ref[...].astype(jnp.float32)      # [kb, 1]
    partial = jnp.sum(g * scale, axis=0)            # this K-block's share

    @pl.when(kb == 0)
    def _init():
        out_ref[0, :] = partial

    @pl.when(kb > 0)
    def _accumulate():
        out_ref[0, :] += partial

    @pl.when(kb == num_k_blocks - 1)
    def _finish():
        z = noise_ref[...].astype(jnp.float32)[0]
        out_ref[0, :] = a_ref[0, 0] * (out_ref[0, :] + z)


def ota_aggregate_streaming(g: jax.Array, scale: jax.Array, noise: jax.Array,
                            a: jax.Array, *, k_block: int,
                            block: int = 2048, interpret: bool = True,
                            pre: str = "identity") -> jax.Array:
    """Streaming variant of ``ota_aggregate_blocked``: the K-way reduction
    itself is gridded, so VMEM holds ``(k_block, block)`` tiles instead of
    full-K columns — the kernel-level half of the 100k-device path.  The
    accumulation order (K-blocks summed sequentially per N-block) differs
    from the dense kernel's single K-way sum by float-associativity only
    (documented-ulp parity, tests/test_streaming.py)."""
    if pre not in PRE_KINDS:
        raise ValueError(f"unknown pre-transform {pre!r}; one of {PRE_KINDS}")
    k, n = g.shape
    kb = min(k_block, k)
    if k % kb != 0:
        raise ValueError(f"K={k} must be divisible by k_block={kb}")
    blk = min(block, n)
    if n % blk != 0:
        raise ValueError(f"N={n} must be divisible by block={blk}")
    nk = k // kb
    grid = (n // blk, nk)
    out = pl.pallas_call(
        functools.partial(_ota_stream_kernel, pre=pre, num_k_blocks=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((kb, blk), lambda i, j: (j, i)),
            pl.BlockSpec((kb, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((1, blk), lambda i, j: (0, i)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
    )(g, scale.reshape(k, 1), noise.reshape(1, n), a.reshape(1, 1))
    return out[0]
