"""Pallas TPU kernel: Cholesky of a single nb x nb tile in VMEM.

Replaces the reference's cuSOLVER potrf tile dispatch (lapack/tile.h potrf)
on the hot path of the distributed factorizations: XLA's generic blocked
Cholesky costs ~5 ms for a 256-tile on v5e (latency-bound recursion), while
the whole tile fits in VMEM and an unblocked right-looking sweep is a
``fori_loop`` of vectorized rank-1 updates.

f32 tiles only (other dtypes take the XLA path in ops/tile.py).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


def _potrf_kernel(a_ref, o_ref):
    a = a_ref[...]
    n = a.shape[-1]
    r2 = lax.broadcasted_iota(jnp.int32, (n, n), 0)
    c2 = lax.broadcasted_iota(jnp.int32, (n, n), 1)

    def body(j, a):
        # all accesses are masked full-tile ops (Mosaic has no value-level
        # dynamic slicing); each step is a handful of VPU sweeps
        dj = jnp.sum(jnp.where((r2 == j) & (c2 == j), a, 0.0))
        inv = 1.0 / jnp.sqrt(dj)
        col = jnp.sum(jnp.where(c2 == j, a, 0.0), axis=1)
        col = jnp.where(r2[:, 0] >= j, col * inv, 0.0)
        a = jnp.where(c2 == j, col[:, None], a)
        upd = col[:, None] * col[None, :]
        a = a - jnp.where(c2 > j, upd, 0.0)
        return a

    # int32 bounds: under jax_enable_x64 a Python-int loop index is int64,
    # which Mosaic cannot lower
    o_ref[...] = lax.fori_loop(jnp.int32(0), jnp.int32(n), body, a)


@partial(jax.jit, static_argnums=())
def potrf_tile(a):
    """Lower-Cholesky of one (n, n) real tile; only the lower triangle of
    ``a`` is referenced (it is hermitized first).  Upper triangle of the
    result is zero (jnp.linalg.cholesky semantics)."""
    herm = jnp.tril(a) + jnp.tril(a, -1).T
    return pl.pallas_call(
        _potrf_kernel, out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype)
    )(herm)


# VMEM guard: the tile and its output are resident; 1024^2 f32 is 4 MiB each
MAX_NB = 1024


def supported(a) -> bool:
    """f32 only: Mosaic has no f64 (the emulated-f64 and complex tiles take
    XLA's blocked Cholesky)."""
    import numpy as np

    return (
        np.dtype(a.dtype) == np.dtype(np.float32)
        and a.ndim == 2
        and a.shape[-1] == a.shape[-2]
        and a.shape[-1] % 8 == 0
        and a.shape[-1] <= MAX_NB
    )
