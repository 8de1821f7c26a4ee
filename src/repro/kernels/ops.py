"""Jitted public wrappers around the Pallas kernels.

``interpret`` semantics (the flat-reduction/OTA kernels):

* ``None`` (default) — Mosaic on TPU; on hosts without a TPU the wrapper
  routes to the mathematically-identical XLA oracle in ``repro.kernels.ref``.
  The Pallas *interpreter* costs ~1 ms per grid step on CPU, which made the
  kernels FL backend ~10x slower than vmap for no extra coverage; the oracle
  keeps non-TPU callers (the compiled FL engine, benchmarks on this
  container) at full XLA speed.
* ``True`` — force the Pallas interpreter: the correctness-validation path
  every kernel test pins explicitly (tests/test_kernels.py,
  tests/test_backends.py).
* ``False`` — force Mosaic compilation.

``flash_attention`` / ``selective_scan`` keep the old behaviour (interpreter
when no TPU): their CPU call sites are numerics-validation only.

Sharded streaming (``FLConfig.device_mesh``): the streaming variants
(``k_block != None``) are also the per-shard launch — inside the engine's
``shard_map`` each mesh device calls them on its OWN [k_block, N] tiles, so
the grid, VMEM working set, and in-kernel fp32 accumulation are all
shard-local and identical to the single-device stream over the same blocks.
The kernels never see the mesh: cross-shard closure is the runtime's
deterministic accumulator fold (``distribution.ota_collectives``), which is
what keeps the kernels backend bitwise across physical/emulated execution
(tests/test_sharded_streaming.py).
"""
from __future__ import annotations

import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_blocked
from repro.kernels.grad_norm import (LANE, batched_blocked_moments,
                                     blocked_sumsq, streaming_blocked_moments)
from repro.kernels.ota_aggregate import (ota_aggregate_blocked,
                                         ota_aggregate_streaming)


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _resolve_interpret(interpret: Optional[bool]) -> Union[bool, str]:
    """None -> Mosaic on TPU, 'ref' (XLA oracle) elsewhere; explicit bools
    force the Pallas path (True = interpreter, False = Mosaic)."""
    if interpret is None:
        return False if jax.default_backend() == "tpu" else "ref"
    return interpret


LANES = 1024  # trailing-dim packing for flat-vector kernels (8x128-aligned)
SUBLANES = 8  # Mosaic tiles a block's second-to-last dim in multiples of 8
# bytes of one fp32 input tile of the device-axis kernels.  Double-buffered
# plus the kernel's temporaries this stays inside the 16 MiB scoped VMEM
# limit of a v5e core (the dense superpose compiles at (8192, 128) and
# (4096, 256) tiles, and not at (4096, 1024)).
TILE_BYTES = 4 * 1024 * 1024


def _fit_tile(limit: int, unit_bytes: int, align: int) -> int:
    """Units per block of a tile with ``unit_bytes`` per unit: ``limit``
    itself when that tile fits ``TILE_BYTES``, else the largest multiple of
    ``align`` that fits.  Raises when not even ``align`` units fit: the
    device axis must then be streamed with a smaller ``k_block``."""
    if limit * unit_bytes <= TILE_BYTES:
        return limit
    units = TILE_BYTES // unit_bytes // align * align
    if units < align:
        raise ValueError(
            f"a {align}-unit tile of {unit_bytes} bytes per unit exceeds the "
            f"{TILE_BYTES}-byte VMEM tile budget — stream the device axis "
            "with a smaller k_block")
    return units


def _aligned_k_block(k: int, kb: int) -> int:
    """``kb`` rounded up to Mosaic's 8-row tiling; a single K-block that
    spans all of K needs no rounding."""
    return kb if kb == k else -(-kb // SUBLANES) * SUBLANES


def _pad_k_blocks(x: jax.Array, kb: int, kb8: int) -> jax.Array:
    """Zero-pad every K-block of ``x`` ([K, ...], K a multiple of ``kb``)
    from ``kb`` to ``kb8`` rows, so a streaming kernel's ``(k_block, ...)``
    block fits Mosaic's tiling.  The padded rows are zero (and get zero
    scale), so they add exact zeros to every sum."""
    if kb8 == kb:
        return x
    k = x.shape[0]
    xb = x.reshape((k // kb, kb) + x.shape[1:])
    xb = jnp.pad(xb, [(0, 0), (0, kb8 - kb)] + [(0, 0)] * (x.ndim - 1))
    return xb.reshape((k // kb * kb8,) + x.shape[1:])


def _unpad_k_blocks(x: jax.Array, k: int, kb: int, kb8: int) -> jax.Array:
    """Inverse of ``_pad_k_blocks`` on a per-device [K'] result."""
    if kb8 == kb:
        return x
    return x.reshape(k // kb, kb8)[:, :kb].reshape(k)


def _pack_flat(x: jax.Array, lanes: int = LANES,
               block_rows: Optional[int] = None):
    """Flatten + zero-pad a vector to [rows, lanes] (padding is norm- and
    moment-neutral).  With ``block_rows``, rows are further padded to a
    multiple of ``min(block_rows, rows)`` so the blocked kernels keep full
    tiles for ANY N (instead of degrading the block size to a divisor);
    returns (packed, n, effective_block_rows)."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    rows = max(1, -(-n // lanes))
    br = rows if block_rows is None else min(block_rows, rows)
    rows = -(-rows // br) * br
    pad = rows * lanes - n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(rows, lanes), n, br


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def grad_norm(x: jax.Array, *, block_rows: int = 256,
              interpret: Optional[bool] = None) -> jax.Array:
    """Global L2 norm of a gradient vector via the blocked Pallas reduction."""
    interpret = _resolve_interpret(interpret)
    if interpret == "ref":
        return ref.grad_norm_ref(x)
    x2, _, br = _pack_flat(x, block_rows=block_rows)
    partials = blocked_sumsq(x2, block_rows=br, interpret=interpret)
    return jnp.sqrt(jnp.sum(partials))


def _pack_flat_batched(g: jax.Array, lanes: int = LANES,
                       block_rows: int = 256):
    """[K, N] -> zero-padded [K, rows, lanes] with rows a multiple of the
    effective block size (padding is moment-neutral); returns
    (packed, n, effective_block_rows)."""
    k, n = g.shape
    rows = max(1, -(-n // lanes))
    br = min(block_rows, rows)
    rows = -(-rows // br) * br
    pad = rows * lanes - n
    if pad:
        g = jnp.concatenate([g, jnp.zeros((k, pad), g.dtype)], axis=1)
    return g.reshape(k, rows, lanes), n, br


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret", "k_block"))
def batched_moments(g: jax.Array, *, block_rows: int = 256,
                    interpret: Optional[bool] = None,
                    k_block: Optional[int] = None):
    """Per-device (sum of squares, sum) of stacked flat gradients.

    g: [K, N].  One batched Pallas reduction over a (K, blocks) grid — this
    replaces K separate ``grad_norm`` launches.  Returns ([K], [K]) f32.

    ``k_block`` selects the streaming variant: a (K-block, N-block) grid
    with in-kernel fp32 accumulation (the oracle is a ``lax.scan`` over
    K-blocks), so the working set is one (k_block, N) tile — the 100k-device
    path.  ``None`` keeps the dense kernel bitwise-unchanged.
    """
    interpret = _resolve_interpret(interpret)
    if k_block is not None:
        kb = min(k_block, g.shape[0])
        if g.shape[0] % kb != 0:
            raise ValueError(f"k_block {kb} must divide K {g.shape[0]}")
        if interpret == "ref":
            return ref.streaming_moments_ref(g, kb)
        k, n = g.shape
        kb8 = _aligned_k_block(k, kb)
        g = _pad_k_blocks(g, kb, kb8)
        rows = -(-n // LANES)
        br = _fit_tile(min(block_rows, rows), kb8 * LANES * 4, SUBLANES)
        g3, _, br = _pack_flat_batched(g, block_rows=br)
        sumsq, sums = streaming_blocked_moments(g3, k_block=kb8,
                                                block_rows=br,
                                                interpret=interpret)
        return (_unpad_k_blocks(sumsq, k, kb, kb8),
                _unpad_k_blocks(sums, k, kb, kb8))
    if interpret == "ref":
        return ref.batched_moments_ref(g)
    g3, _, br = _pack_flat_batched(g, block_rows=block_rows)
    sumsq, sums = batched_blocked_moments(g3, block_rows=br, interpret=interpret)
    return jnp.sum(sumsq, axis=1), jnp.sum(sums, axis=1)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def batched_grad_norms(g: jax.Array, *, block_rows: int = 256,
                       interpret: Optional[bool] = None) -> jax.Array:
    """[K] global L2 norms of stacked flat gradients, one pallas_call."""
    sumsq, _ = batched_moments(g, block_rows=block_rows, interpret=interpret)
    return jnp.sqrt(sumsq)


@functools.partial(jax.jit,
                   static_argnames=("block", "interpret", "pre", "k_block"))
def ota_superpose(g: jax.Array, scale: jax.Array, noise: jax.Array, a, *,
                  pre: str = "identity", block: int = LANES,
                  interpret: Optional[bool] = None,
                  k_block: Optional[int] = None) -> jax.Array:
    """Fused superposition y = a (sum_k scale_k pre(g_k) + z) (paper eq. 10).

    g: [K, N]; scale: [K] composite per-device scale (h_k b_k x scheme
    scale); noise: [N]; a: scalar; pre: 'identity' | 'sign'.  Every
    norm-scaling scheme in the registry lowers to this one kernel.
    Returns y [N] f32.

    ``k_block`` selects the streaming kernel: the K-way reduction runs over
    an (N-block, K-block) grid whose output tile is the fp32 accumulator
    (oracle: sequential ``lax.scan`` over K-blocks), so VMEM holds
    (k_block, block) tiles instead of full-K columns.  ``None`` keeps the
    dense kernel bitwise-unchanged.  The N-block is ``block`` shrunk until
    the ``(K or k_block, N-block)`` tile fits ``TILE_BYTES`` of VMEM.
    """
    interpret = _resolve_interpret(interpret)
    a = jnp.asarray(a, jnp.float32)
    if k_block is not None:
        kb = min(k_block, g.shape[0])
        if g.shape[0] % kb != 0:
            raise ValueError(f"k_block {kb} must divide K {g.shape[0]}")
        if interpret == "ref":
            return ref.ota_superpose_streaming_ref(g, scale, noise, a,
                                                   pre=pre, k_block=kb)
    elif interpret == "ref":
        return ref.ota_superpose_ref(g, scale, noise, a, pre=pre)
    k, n = g.shape
    scale = scale.astype(jnp.float32)
    if k_block is None:
        blk = _fit_tile(block, k * 4, LANE)
    else:
        kb8 = _aligned_k_block(k, kb)
        scale = _pad_k_blocks(scale, kb, kb8)
        g = _pad_k_blocks(g, kb, kb8)
        blk = _fit_tile(block, kb8 * 4, LANE)
    pad_cols = -(-n // blk) * blk - n
    if pad_cols:
        g = jnp.pad(g, ((0, 0), (0, pad_cols)))
        noise = jnp.pad(noise, (0, pad_cols))
    if k_block is not None:
        y = ota_aggregate_streaming(g, scale, noise, a, k_block=kb8,
                                    block=blk, interpret=interpret, pre=pre)
    else:
        y = ota_aggregate_blocked(g, scale, noise, a, block=blk,
                                  interpret=interpret, pre=pre)
    return y[:n]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def ota_aggregate(g: jax.Array, hb: jax.Array, norms: jax.Array,
                  noise: jax.Array, a, *, block: int = LANES,
                  interpret: Optional[bool] = None) -> jax.Array:
    """Fused normalize-amplify-superpose (eq. 10 with eq. 12) — the
    ``normalized``-scheme specialization of ``ota_superpose``, kept for
    callers that already hold per-device norms.
    """
    scale = hb.astype(jnp.float32) / (norms.astype(jnp.float32) + 1e-12)
    return ota_superpose(g, scale, noise, a, block=block, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "block_q", "block_k",
                                    "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Flash attention over [B, H, S, d] (kv head-expanded)."""
    interpret = _default_interpret() if interpret is None else interpret
    return flash_attention_blocked(q, k, v, causal=causal, window=window,
                                   block_q=block_q, block_k=block_k,
                                   interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_d", "chunk", "interpret"))
def selective_scan(u, dt, a, bmat, cmat, *, block_d: int = 128,
                   chunk: int = 256, interpret: Optional[bool] = None):
    """Fused Mamba selective scan (see kernels/selective_scan.py)."""
    from repro.kernels.selective_scan import selective_scan_blocked
    interpret = _default_interpret() if interpret is None else interpret
    d, s = u.shape[2], u.shape[1]
    bd = block_d
    while d % bd != 0:
        bd //= 2
    cs = chunk
    while s % cs != 0:
        cs //= 2
    return selective_scan_blocked(u, dt, a, bmat, cmat, block_d=max(bd, 1),
                                  chunk=max(cs, 1), interpret=interpret)
