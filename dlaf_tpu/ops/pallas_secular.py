"""Pallas TPU kernel: fused secular-equation bisection for the D&C merge.

The distributed tridiagonal D&C solves, for every eigenvalue slot, the
secular equation  f(x) = 1 + rho * sum_s z2[s] / (d[s] - anchor - x) = 0
by ``iters`` rounds of bisection (algorithms/tridiag_dc_dist.py `bisect`;
reference: src/eigensolver/tridiag_solver's laed4 calls + kernels.cu).
Under XLA the (K, S) pole tables stream from HBM on EVERY bisection round;
this kernel keeps a K-block of the tables resident in VMEM across all
rounds — one HBM read instead of ``iters``, turning a memory-bound loop
into a VPU-bound one.

Default OFF (tune.dc_secular_pallas) pending an on-hardware A/B;
interpret-mode parity tests pin it to the XLA formulation
(tests/test_pallas_kernels.py).  f32 only (TPU Pallas has no f64).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

_VMEM_TABLES_BYTES = 8 << 20
# int32 block index: under jax_enable_x64 a Python 0 is int64, which
# Mosaic cannot return from an index map
_I0 = np.int32(0)


def _kernel(dw_ref, z2_ref, rho_ref, anchor_ref, lo_ref, hi_ref, o_ref, *, iters: int):
    # per-row scalars arrive as (kb, 1) columns: a 1-D (kb,) block would
    # not match XLA's tiling of the (K,) operand whenever kb < K
    ag = dw_ref[...] - anchor_ref[...]  # (kb, S) pole gaps, resident
    z2 = z2_ref[...]
    rho = rho_ref[...]
    tiny = jnp.finfo(ag.dtype).tiny

    def body(_, lh):
        lo, hi = lh
        mid = 0.5 * (lo + hi)
        diff = ag - mid
        safe = jnp.where(diff == 0, tiny, diff)
        fm = 1.0 + rho * jnp.sum(z2 / safe, axis=1, keepdims=True)
        return jnp.where(fm < 0, mid, lo), jnp.where(fm < 0, hi, mid)

    lo, hi = lax.fori_loop(
        jnp.int32(0), jnp.int32(iters), body, (lo_ref[...], hi_ref[...]))
    o_ref[...] = 0.5 * (lo + hi)


@functools.partial(jax.jit, static_argnums=(6, 7))
def secular_bisect(dw, z2w, rho, anchor, lo0, hi0, iters: int, interpret: bool = False):
    """Roots (offsets from ``anchor``) of the secular function, one per row:
    ``dw``/``z2w`` are (K, S) pole/weight tables, ``rho``/``anchor``/``lo0``/
    ``hi0`` are (K,).  Bit-matches tridiag_dc_dist's XLA bisection (same
    mid/bracket updates in the same order)."""
    k, s = dw.shape
    # K-block: the two (kb, S) tables are double-buffered in VMEM, so keep
    # those four buffers within _VMEM_TABLES_BYTES (a top-level merge has
    # S = N poles per root)
    kb = k
    for cand in (512, 256, 128, 64, 32, 16, 8):
        if k % cand == 0 and 4 * cand * s * dw.dtype.itemsize <= _VMEM_TABLES_BYTES:
            kb = cand
            break
    grid = (k // kb,)
    col = pl.BlockSpec((kb, 1), lambda i: (i, _I0))
    out = pl.pallas_call(
        functools.partial(_kernel, iters=iters),
        grid=grid,
        in_specs=[
            pl.BlockSpec((kb, s), lambda i: (i, _I0)),
            pl.BlockSpec((kb, s), lambda i: (i, _I0)),
            col, col, col, col,
        ],
        out_specs=col,
        out_shape=jax.ShapeDtypeStruct((k, 1), dw.dtype),
        interpret=interpret,
    )(dw, z2w, *(v.reshape(k, 1) for v in (rho, anchor, lo0, hi0)))
    return out.reshape(k)
