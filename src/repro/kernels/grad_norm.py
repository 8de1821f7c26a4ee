"""Pallas TPU kernels: blocked global reductions over flat gradients.

The paper's device-side transforms need per-device statistics over the
*entire* flat gradient (millions of elements) before any element can be
scaled — HBM-bandwidth-bound reductions.  Two kernels:

``blocked_sumsq``          single-device [R, C] -> per-block sum-of-squares
                           partials (the original kernel, kept for the
                           single-vector ``ops.grad_norm``).
``batched_blocked_moments`` the registry-refactor kernel: ALL K devices in one
                           ``pallas_call`` over a ``(K, blocks)`` grid on a
                           [K, R, C] view of the stacked flat gradients,
                           emitting per-(device, block) sum-of-squares AND sum
                           partials.  One launch replaces the old Python loop
                           of K ``grad_norm`` calls, and the sum output gives
                           the moments schemes (benchmark2) their mean/std
                           from the same HBM pass.

The (tiny) final block-sum + sqrt happens in the jitted wrappers
(``ops.batched_moments`` / ``ops.batched_grad_norms``).

Output layout: Mosaic stores vectors, not scalars, to VMEM, and every block's
last two dims must be (8, 128)-tiled or span the whole array.  So each
partial is written as a lane row — the block's scalar broadcast over
``LANE`` lanes, in a trailing ``(1, LANE)`` that spans the array's last two
dims — and the wrappers read lane 0.

Target: TPU (MXU/VPU 8x128 tiling); validated on CPU via interpret=True
against ``ref.grad_norm_ref`` / ``ref.batched_moments_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128  # one vreg row: the width of every partial-sum output row


def _sumsq_kernel(x_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)              # [br, cols]
    sq = jnp.sum(x * x, axis=(0, 1), keepdims=True)
    out_ref[...] = jnp.broadcast_to(sq[None], out_ref.shape)


def blocked_sumsq(x: jax.Array, *, block_rows: int = 256,
                  interpret: bool = True) -> jax.Array:
    """Partial sums of squares of a [R, 128k]-shaped view of the flat vector.

    x must be 2-D with a lane-aligned trailing dim; returns [num_blocks] f32.
    """
    rows, cols = x.shape
    br = min(block_rows, rows)
    if rows % br != 0:
        raise ValueError(f"block_rows {br} must divide rows {rows}")
    grid = (rows // br,)
    out = pl.pallas_call(
        _sumsq_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((br, cols), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, 1, LANE), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((grid[0], 1, LANE), jnp.float32),
        interpret=interpret,
    )(x)
    return out[:, 0, 0]


def _moments_kernel(x_ref, sq_ref, s_ref):
    x = x_ref[0].astype(jnp.float32)          # [br, cols] tile of device i
    sq = jnp.sum(x * x, axis=(0, 1), keepdims=True)
    s = jnp.sum(x, axis=(0, 1), keepdims=True)
    sq_ref[...] = jnp.broadcast_to(sq[None, None], sq_ref.shape)
    s_ref[...] = jnp.broadcast_to(s[None, None], s_ref.shape)


def batched_blocked_moments(x: jax.Array, *, block_rows: int = 256,
                            interpret: bool = True):
    """Per-(device, block) partial moments of stacked flat gradients.

    x: [K, R, C] (C lane-aligned; zero padding is moment-neutral).  One
    ``pallas_call`` over a (K, R // block_rows) grid.  Returns
    ``(sumsq, sums)`` each [K, num_blocks] f32.
    """
    k, rows, cols = x.shape
    br = min(block_rows, rows)
    if rows % br != 0:
        raise ValueError(f"block_rows {br} must divide rows {rows}")
    grid = (k, rows // br)
    out_shape = jax.ShapeDtypeStruct((k, grid[1], 1, LANE), jnp.float32)
    out_spec = pl.BlockSpec((1, 1, 1, LANE), lambda i, j: (i, j, 0, 0))
    sumsq, sums = pl.pallas_call(
        _moments_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, br, cols), lambda i, j: (i, j, 0))],
        out_specs=[out_spec, out_spec],
        out_shape=[out_shape, out_shape],
        interpret=interpret,
    )(x)
    return sumsq[:, :, 0, 0], sums[:, :, 0, 0]


def _stream_moments_kernel(x_ref, sq_ref, s_ref, *, num_blocks):
    """(K-block, N-block) grid step: the N-block axis is the fast grid
    dimension, so the per-device output tiles are revisited ``num_blocks``
    times and act as fp32 accumulators — the final block-sum happens
    in-kernel instead of materializing [K, num_blocks] partials."""
    j = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)            # [kb, br, cols]
    # [kb, LANE]: each device's partial broadcast over one lane row
    sq = jnp.broadcast_to(
        jnp.sum(jnp.sum(x * x, axis=2), axis=1, keepdims=True), sq_ref.shape)
    s = jnp.broadcast_to(
        jnp.sum(jnp.sum(x, axis=2), axis=1, keepdims=True), s_ref.shape)

    @pl.when(j == 0)
    def _init():
        sq_ref[...] = sq
        s_ref[...] = s

    @pl.when(j > 0)
    def _accumulate():
        sq_ref[...] += sq
        s_ref[...] += s


def streaming_blocked_moments(x: jax.Array, *, k_block: int,
                              block_rows: int = 256,
                              interpret: bool = True):
    """Per-device moments over a (K-block, N-block) grid with in-kernel
    accumulation: only a ``(k_block, block_rows, C)`` tile is resident and
    the outputs come back fully reduced.

    x: [K, R, C].  Returns ``(sumsq, sums)`` each [K] f32.  Accumulation
    order differs from ``batched_blocked_moments`` + wrapper block-sum by
    float associativity only (documented-ulp).  Mosaic needs ``k_block``
    to be a multiple of 8 unless it spans K (``ops.batched_moments`` pads
    the K-blocks)."""
    k, rows, cols = x.shape
    kb = min(k_block, k)
    if k % kb != 0:
        raise ValueError(f"k_block {kb} must divide K {k}")
    br = min(block_rows, rows)
    if rows % br != 0:
        raise ValueError(f"block_rows {br} must divide rows {rows}")
    nb = rows // br
    grid = (k // kb, nb)
    out_shape = jax.ShapeDtypeStruct((k, LANE), jnp.float32)
    out_spec = pl.BlockSpec((kb, LANE), lambda i, j: (i, 0))
    sumsq, sums = pl.pallas_call(
        functools.partial(_stream_moments_kernel, num_blocks=nb),
        grid=grid,
        in_specs=[pl.BlockSpec((kb, br, cols), lambda i, j: (i, j, 0))],
        out_specs=[out_spec, out_spec],
        out_shape=[out_shape, out_shape],
        interpret=interpret,
    )(x)
    return sumsq[:, 0], sums[:, 0]
