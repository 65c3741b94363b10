"""Pallas TPU kernel: column-blocked panel triangular solve X op(L) = B.

The panel-critical op of the distributed Cholesky/TRSM: after the diagonal
tile factors, every panel row block solves against the SAME nb x nb lower
factor (reference: the cuBLAS trsm dispatch under factorization/cholesky,
and src/lapack/gpu's 'vendor op too slow' custom-kernel layer).  XLA's
generic ``triangular_solve`` runs a latency-bound blocked recursion per
call; here the whole factor sits in VMEM and the solve is column-blocked
(docs/ROADMAP.md item 3's scoped design):

    for each W-wide column block j:                   (nb/W blocks)
        B_j -= X_{<j} @ op(L)_{<j, j}                 (MXU GEMM, [bm x jW x W])
        X_j  = B_j / triangular sweep of op(L)_{jj}   (W masked VPU steps)

Rows of X are independent, so the kernel grids over row blocks of B with
L resident; ``iters`` of HBM re-reads become one.  Real dtypes, RIGHT /
LOWER / {T, C} / non-unit — exactly the Cholesky panel case; everything
else falls back to XLA (ops/tile.py).

Default OFF (tune.panel_trsm_pallas) pending an on-hardware A/B —
interpret-mode parity tests keep it correct until then
(tests/test_pallas_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

# int32 block index: under jax_enable_x64 a Python 0 is int64, which
# Mosaic cannot return from an index map
_I0 = np.int32(0)
W = 32  # sub-triangle sweep width (one MXU tile side)


def _kernel(l_ref, b_ref, o_ref, *, nb: int):
    # l_ref: (nb, nb) factor, already op()-resolved to U = L^T form;
    # b_ref/o_ref: (bm, nb).  Column blocks are read and written through
    # ref slices (``pl.ds``): Mosaic lowers no value-level dynamic_slice.
    bm = b_ref.shape[0]
    dtype = o_ref.dtype
    r2 = lax.broadcasted_iota(jnp.int32, (bm, W), 1)  # column index within block
    cw = lax.broadcasted_iota(jnp.int32, (W, W), 1)
    rw = lax.broadcasted_iota(jnp.int32, (W, W), 0)
    for j in range(nb // W):  # static: nb/W blocks
        c0 = j * W
        bj = b_ref[:, pl.ds(c0, W)]
        if j:
            # MXU update: B_j -= X_{<j} @ U[<j, j]
            bj = bj - jax.lax.dot_general(
                o_ref[:, pl.ds(0, c0)], l_ref[pl.ds(0, c0), pl.ds(c0, W)],
                (((1,), (0,)), ((), ())),
                preferred_element_type=dtype,  # keep f64 accumulation f64
            )
        # W-step masked triangular sweep against the diagonal block
        # (upper-triangular W x W: ljj[s, t] multiplies solved col s into t)
        ljj = l_ref[pl.ds(c0, W), pl.ds(c0, W)]

        def step(t, xj):
            # contribution of solved columns s < t
            lcol = jnp.sum(jnp.where((cw == t) & (rw < t), ljj, 0.0), axis=1)
            dt_ = jnp.sum(jnp.where((cw == t) & (rw == t), ljj, 0.0))
            contrib = jnp.sum(xj * lcol[None, :], axis=1)
            bcol = jnp.sum(jnp.where(r2 == t, bj, 0.0), axis=1)
            newcol = (bcol - contrib) / dt_
            return jnp.where(r2 == t, newcol[:, None], xj)

        o_ref[:, pl.ds(c0, W)] = lax.fori_loop(
            jnp.int32(0), jnp.int32(W), step, jnp.zeros((bm, W), dtype))


@functools.partial(jax.jit, static_argnums=(2, 3))
def panel_trsm_right_lower_t(ell, b, conj: bool = False, interpret: bool = False):
    """X with X @ op(L) = B: op = L^T (conj=False) or L^H; ``ell`` is the
    (nb, nb) lower factor, ``b`` is (m, nb).  Real dtypes only."""
    nb = ell.shape[-1]
    if conj:
        ell = ell.conj()
    # pre-resolve op: the kernel consumes U = L^T (upper), laid out so that
    # U[:, j-block] are the GEMM operands
    u = jnp.tril(ell).T
    bm = min(512, b.shape[0]) if b.shape[0] % 512 == 0 or b.shape[0] < 512 else 256
    m = b.shape[0]
    if m % bm:
        bm = m  # single block for ragged heights (panel stacks are regular)
    grid = (m // bm,)
    return pl.pallas_call(
        functools.partial(_kernel, nb=nb),
        grid=grid,
        in_specs=[
            pl.BlockSpec((nb, nb), lambda i: (_I0, _I0)),
            pl.BlockSpec((bm, nb), lambda i: (i, _I0)),
        ],
        out_specs=pl.BlockSpec((bm, nb), lambda i: (i, _I0)),
        out_shape=jax.ShapeDtypeStruct(b.shape, b.dtype),
        interpret=interpret,
    )(u, b)


# VMEM guard: the factor (nb^2) plus a row block must fit comfortably; a
# 1024^2 f32 factor is 4 MiB of ~16 MiB VMEM.  Bigger nb means the caller
# is solving a whole matrix (the single-device path), not a panel.
MAX_NB = 1024


def supported(side, uplo, op, diag, a, b) -> bool:
    """The Cholesky-panel case this kernel covers: Right/Lower/{T,C},
    non-unit, real, tile-sized factor; ``b`` may be a batched panel stack
    ([L, mb, nb] — the distributed kernels' shape) or a flat (m, nb)."""
    import jax as _jax

    from dlaf_tpu.ops import tile as t

    rows = int(np.prod(b.shape[:-1])) if b.ndim >= 2 else 0
    # TPU Pallas has no f64: compiled runs are f32-only (CPU runs go
    # through interpret mode, where f64 parity tests are valid)
    dtype_ok = np.dtype(a.dtype) == np.dtype(np.float32) or (
        np.dtype(a.dtype).kind == "f" and _jax.default_backend() == "cpu"
    )
    return (
        side == t.RIGHT
        and uplo == t.LOWER
        and op in (t.TRANS, t.CONJ_TRANS)
        and diag == t.NON_UNIT
        and dtype_ok
        and a.ndim == 2
        and b.ndim in (2, 3)
        and b.shape[-1] == a.shape[-1]
        and a.shape[-1] % W == 0
        and 0 < a.shape[-1] <= MAX_NB
        and rows % 8 == 0
    )
