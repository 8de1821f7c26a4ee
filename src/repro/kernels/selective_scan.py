"""Pallas TPU kernel: fused Mamba (S6) selective scan.

The §Perf hillclimb showed jamba's train memory term is structural in the
XLA path: the chunked associative scan materializes O(log chunk) levels of
[B, chunk, D_inner, N] fp32 intermediates, and no chunk-size / dtype /
sharding lever moves it more than a few percent (EXPERIMENTS.md §Perf).
This kernel is the TPU analogue of the CUDA reference's fused scan: the
discretization (``da = exp(dt*A)``, ``dbu = dt*u*B``) and the recurrence

    h_t = da_t * h_{t-1} + dbu_t ;    y_t = <h_t, C_t>

happen *in registers/VMEM*, so HBM traffic is just u/dt/B/C in and y out —
the [S, D, N] state never exists in memory.  The grid is
(batch, d-blocks, seq-chunks) with the seq axis innermost-sequential and the
carried state h [bd, N] in VMEM scratch (same idiom as flash attention's
running softmax).

Validated on CPU via interpret=True against ``ref.selective_scan_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _selective_scan_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, h_scr,
                           *, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    u = u_ref[0].astype(jnp.float32)          # [cs, bd]
    dt = dt_ref[0].astype(jnp.float32)        # [cs, bd]
    a = a_ref[...].astype(jnp.float32)        # [bd, N]
    bmat = b_ref[0].astype(jnp.float32)       # [cs, N]
    cmat = c_ref[0].astype(jnp.float32)       # [cs, N]

    def body(t, h):
        da = jnp.exp(dt[t][:, None] * a)                       # [bd, N]
        dbu = (dt[t] * u[t])[:, None] * bmat[t][None, :]       # [bd, N]
        h = da * h + dbu
        y_t = jnp.sum(h * cmat[t][None, :], axis=1)            # [bd]
        y_ref[0, pl.ds(t, 1), :] = y_t[None, :]
        return h

    h = jax.lax.fori_loop(0, chunk, body, h_scr[...])
    h_scr[...] = h


def selective_scan_blocked(u: jax.Array, dt: jax.Array, a: jax.Array,
                           bmat: jax.Array, cmat: jax.Array, *,
                           block_d: int = 128, chunk: int = 256,
                           interpret: bool = True) -> jax.Array:
    """u/dt: [B, S, D]; a: [D, N] (= -exp(A_log)); bmat/cmat: [B, S, N].

    Returns y [B, S, D] f32 with y_t = <h_t, C_t>, h_t = exp(dt_t a) h_{t-1}
    + dt_t u_t B_t (h_0 = 0).
    """
    b, s, d = u.shape
    n = a.shape[1]
    bd = min(block_d, d)
    cs = min(chunk, s)
    if d % bd or s % cs:
        raise ValueError("D and S must divide block_d / chunk")
    grid = (b, d // bd, s // cs)
    kernel = functools.partial(_selective_scan_kernel, chunk=cs)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, cs, bd), lambda ib, id_, ic: (ib, ic, id_)),
            pl.BlockSpec((1, cs, bd), lambda ib, id_, ic: (ib, ic, id_)),
            pl.BlockSpec((bd, n), lambda ib, id_, ic: (id_, 0)),
            pl.BlockSpec((1, cs, n), lambda ib, id_, ic: (ib, ic, 0)),
            pl.BlockSpec((1, cs, n), lambda ib, id_, ic: (ib, ic, 0)),
        ],
        out_specs=pl.BlockSpec((1, cs, bd), lambda ib, id_, ic: (ib, ic, id_)),
        out_shape=jax.ShapeDtypeStruct((b, s, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bd, n), jnp.float32)],
        interpret=interpret,
    )(u, dt, a, bmat, cmat)
