"""Distributed matrix inversion: triangular inverse (TRTRI) and inverse from
Cholesky factor (POTRI).

TPU-native re-design of the reference inverse algorithms
(reference: include/dlaf/inverse/triangular.h:38-64 + inverse/triangular/
impl.h, and inverse/cholesky.h:38-67 + inverse/cholesky/impl.h).

Triangular inverse, lower: backward loop over tile columns k,

    inv[k,k]    = L[k,k]^-1
    inv[k+1:,k] = -inv[k+1:,k+1:] @ L[k+1:,k] @ inv[k,k]

where the trailing block inverse is already final (backward order).  Each
step: broadcast original column k, transpose-redistribute it, one batched
einsum against the local trailing-inverse tiles, psum over the row of grid
columns, scale by the inverted diagonal tile, masked write-back.  Upper is
the row-wise mirror.

POTRI: A^-1 = L^-H L^-1 computed as trtri followed by a triangular
multiplication of the inverse against its own conjugate transpose (the
reference's lauum-style product, inverse/cholesky/impl.h).  Full Hermitian
storage is returned.
"""
from __future__ import annotations

from dlaf_tpu.algorithms._origin import origin_transparent

from functools import partial

import jax.numpy as jnp
from jax import lax

from dlaf_tpu.algorithms import _spmd
from dlaf_tpu.comm import collectives as coll
from dlaf_tpu.comm.grid import COL_AXIS, ROW_AXIS
from dlaf_tpu.matrix import util as mutil
from dlaf_tpu.matrix.matrix import DistributedMatrix
from dlaf_tpu.obs.trace import scope as _scope
from dlaf_tpu.ops import pallas_trailing_update as ptu
from dlaf_tpu.ops import tile as t
from dlaf_tpu.plan import core as _plan


def _trtri_lower_kernel(x, g: _spmd.Geometry, diag):
    x = coll.local(x)
    myr, myc = coll.my_rank()
    x = _spmd.pad_diag_identity(x, g, myr, myc)
    gi = _spmd.local_row_tiles(g, myr)
    gj = _spmd.local_col_tiles(g, myc)
    eye = jnp.eye(g.mb, dtype=x.dtype)

    def body(s, x):
        k = g.mt - 1 - s
        kr, kc = k % g.pr, k % g.pc
        lkc = k // g.pc
        akk = _spmd.bcast_diag_tile(x, k, g, myr, myc)
        tkk = t.trsm(t.LEFT, t.LOWER, t.NO_TRANS, diag, 1.0, akk, eye)
        # original column k below diagonal, to every rank column
        xc = _spmd.take_col(x, lkc, g)
        below = (gi > k)[:, None, None]
        cp = coll.bcast(jnp.where(below, xc, jnp.zeros_like(xc)), kc, COL_AXIS)
        rp = coll.transpose_panel(cp, g.mt, g.ltc)  # L[j,k] at local cols j>k
        # S[i] = sum_j inv[i,j] L[j,k] over trailing cols (inv cols > k final);
        # tiles above the diagonal are never referenced (may hold garbage)
        keep_cols = ((gj > k)[None, :] & (gi[:, None] >= gj[None, :]))[:, :, None, None]
        s_part = t.contract("ijab,jbc->iac", jnp.where(keep_cols, x, jnp.zeros_like(x)), rp)
        s_full = coll.psum_axis(s_part, COL_AXIS)
        newcol = -t.contract("iab,bc->iac", s_full, tkk)
        newcol = jnp.where(
            (gi == k)[:, None, None], tkk[None], jnp.where(below, newcol, xc)
        )
        return _spmd.put_col(x, jnp.where(myc == kc, newcol, xc), lkc)

    x = lax.fori_loop(0, g.mt, body, x)
    x = _spmd.pad_diag_identity(x, g, myr, myc, remove=True)
    return coll.relocal(x)


def _trtri_lower_bucketed_kernel(x, g: _spmd.Geometry, diag):
    """Bucketed variant of _trtri_lower_kernel: the trailing-inverse slab
    {i >= k+1} x {j >= k+1} is dynamic-sliced with static per-segment
    sizes.  The loop runs BACKWARD (k = mt-1 .. 0), so windows GROW with
    the step index — segments size their bucket for the segment's LAST
    step."""
    x = coll.local(x)
    myr, myc = coll.my_rank()
    x = _spmd.pad_diag_identity(x, g, myr, myc)
    eye = jnp.eye(g.mb, dtype=x.dtype)
    mt = g.mt
    fused_tier = _spmd.trailing_update_trace_key() == "fused"

    def step(s, x, L, C):
        k = mt - 1 - s
        kr, kc = k % g.pr, k % g.pc
        lkr, lkc = k // g.pr, k // g.pc
        with _scope("trtri.diag"):
            akk = _spmd.bcast_diag_tile(x, k, g, myr, myc)
            tkk = t.trsm(t.LEFT, t.LOWER, t.NO_TRANS, diag, 1.0, akk, eye)
        # window of rows/cols >= k+1
        rs = jnp.clip((k + g.pr - myr) // g.pr, 0, max(g.ltr - L, 0)).astype(lkr.dtype)
        cs = jnp.clip((k + g.pc - myc) // g.pc, 0, max(g.ltc - C, 0)).astype(lkr.dtype)
        gi_w = (rs + jnp.arange(L)) * g.pr + myr
        gj_w = (cs + jnp.arange(C)) * g.pc + myc
        below = (gi_w > k)[:, None, None]
        # original column k below the diagonal, to every rank column
        with _scope("trtri.panel_bcast"):
            xc = lax.dynamic_slice(x, (rs, lkc, 0, 0), (L, 1, g.mb, g.mb))[:, 0]
            cp = coll.bcast(
                jnp.where(below, xc, jnp.zeros_like(xc)), kc, COL_AXIS,
                consumed=fused_tier,
            )
            if fused_tier:
                taken, have = coll.transpose_panel_windowed_parts(
                    cp, gj_w, rs, g.mt
                )
                rp = ptu.consume_exchange(taken, have, ROW_AXIS)
            else:
                rp = coll.transpose_panel_windowed(cp, gj_w, rs, g.mt)  # L[j,k]
        # S[i] = sum_j inv[i,j] L[j,k] over the trailing slab (inv final there)
        with _scope("trtri.update"):
            xs = lax.dynamic_slice(x, (rs, cs, 0, 0), (L, C, g.mb, g.mb))
            keep = ((gj_w > k)[None, :] & (gi_w[:, None] >= gj_w[None, :]))[:, :, None, None]
            xk = jnp.where(keep, xs, jnp.zeros_like(xs))
            if fused_tier and ptu.update_kernel_ok(xs.dtype):
                # the contraction sums over j: one-shot in-VMEM kernel, not
                # per-hop consumption (see panel_contract's docstring)
                s_part = ptu.panel_contract(xk, rp, "ijab,jbc->iac")
            else:
                s_part = t.contract("ijab,jbc->iac", xk, rp)
            s_full = coll.psum_axis(s_part, COL_AXIS)
            newcol = -t.contract("iab,bc->iac", s_full, tkk)
        newcol = jnp.where(below & (myc == kc), newcol, xc)
        x = lax.dynamic_update_slice(x, newcol[:, None], (rs, lkc, 0, 0))
        # diagonal tile write (outside the window)
        mine_d = (myr == kr) & (myc == kc)
        dtile = jnp.where(mine_d, tkk, x[lkr, lkc])[None, None]
        return lax.dynamic_update_slice(x, dtile.astype(x.dtype), (lkr, lkc, 0, 0))

    for s0, s1 in _spmd.halving_segments(mt):
        # backward loop: largest window inside the segment is at its LAST
        # step s1-1 (k = mt - s1, trailing extent s1 - 1 tiles... + 1 slack)
        rem = s1 - 1
        L = max(min(g.ltr, (rem + g.pr - 1) // g.pr + 1), 1)
        C = max(min(g.ltc, (rem + g.pc - 1) // g.pc + 1), 1)
        x = lax.fori_loop(s0, s1, partial(step, L=L, C=C), x)

    x = _spmd.pad_diag_identity(x, g, myr, myc, remove=True)
    return coll.relocal(x)


def _trtri_upper_bucketed_kernel(x, g: _spmd.Geometry, diag):
    """Row-wise mirror of _trtri_lower_bucketed_kernel (upper triangle)."""
    x = coll.local(x)
    myr, myc = coll.my_rank()
    x = _spmd.pad_diag_identity(x, g, myr, myc)
    eye = jnp.eye(g.mb, dtype=x.dtype)
    mt = g.mt
    fused_tier = _spmd.trailing_update_trace_key() == "fused"

    def step(s, x, L, C):
        k = mt - 1 - s
        kr, kc = k % g.pr, k % g.pc
        lkr, lkc = k // g.pr, k // g.pc
        with _scope("trtri.diag"):
            akk = _spmd.bcast_diag_tile(x, k, g, myr, myc)
            tkk = t.trsm(t.LEFT, t.UPPER, t.NO_TRANS, diag, 1.0, akk, eye)
        rs = jnp.clip((k + g.pr - myr) // g.pr, 0, max(g.ltr - L, 0)).astype(lkr.dtype)
        cs = jnp.clip((k + g.pc - myc) // g.pc, 0, max(g.ltc - C, 0)).astype(lkr.dtype)
        gi_w = (rs + jnp.arange(L)) * g.pr + myr
        gj_w = (cs + jnp.arange(C)) * g.pc + myc
        right = (gj_w > k)[:, None, None]
        # windowed row panel of U[k, cs:cs+C] (covers all trailing cols > k)
        with _scope("trtri.panel_bcast"):
            xr = lax.dynamic_slice(x, (lkr, cs, 0, 0), (1, C, g.mb, g.mb))[0]
            rp = coll.bcast(
                jnp.where(right, xr, jnp.zeros_like(xr)), kr, ROW_AXIS,
                consumed=fused_tier,
            )
            # row panel U[k, v] -> windowed col panel indexed by window rows i
            if fused_tier:
                taken, have = coll.transpose_panel_rows_windowed_parts(
                    rp, gi_w, cs, g.nt
                )
                cp = ptu.consume_exchange(taken, have, COL_AXIS)
            else:
                cp = coll.transpose_panel_rows_windowed(rp, gi_w, cs, g.nt)
        with _scope("trtri.update"):
            xs = lax.dynamic_slice(x, (rs, cs, 0, 0), (L, C, g.mb, g.mb))
            keep = ((gi_w > k)[:, None] & (gi_w[:, None] <= gj_w[None, :]))[:, :, None, None]
            xk = jnp.where(keep, xs, jnp.zeros_like(xs))
            if fused_tier and ptu.update_kernel_ok(xs.dtype):
                s_part = ptu.panel_contract(cp, xk, "iab,ijbc->jac")
            else:
                s_part = t.contract("iab,ijbc->jac", cp, xk)
            s_full = coll.psum_axis(s_part, ROW_AXIS)
            newrow = -t.contract("ab,jbc->jac", tkk, s_full)
        newrow = jnp.where(right & (myr == kr), newrow, xr)
        x = lax.dynamic_update_slice(x, newrow[None, :], (lkr, cs, 0, 0))
        mine_d = (myr == kr) & (myc == kc)
        dtile = jnp.where(mine_d, tkk, x[lkr, lkc])[None, None]
        return lax.dynamic_update_slice(x, dtile.astype(x.dtype), (lkr, lkc, 0, 0))

    for s0, s1 in _spmd.halving_segments(mt):
        rem = s1 - 1
        L = max(min(g.ltr, (rem + g.pr - 1) // g.pr + 1), 1)
        C = max(min(g.ltc, (rem + g.pc - 1) // g.pc + 1), 1)
        x = lax.fori_loop(s0, s1, partial(step, L=L, C=C), x)

    x = _spmd.pad_diag_identity(x, g, myr, myc, remove=True)
    return coll.relocal(x)


def _trtri_upper_kernel(x, g: _spmd.Geometry, diag):
    x = coll.local(x)
    myr, myc = coll.my_rank()
    x = _spmd.pad_diag_identity(x, g, myr, myc)
    gi = _spmd.local_row_tiles(g, myr)
    gj = _spmd.local_col_tiles(g, myc)
    eye = jnp.eye(g.mb, dtype=x.dtype)

    def body(s, x):
        k = g.mt - 1 - s
        kr, kc = k % g.pr, k % g.pc
        lkr = k // g.pr
        akk = _spmd.bcast_diag_tile(x, k, g, myr, myc)
        tkk = t.trsm(t.LEFT, t.UPPER, t.NO_TRANS, diag, 1.0, akk, eye)
        # original row k right of diagonal, to every rank row
        xr = _spmd.take_row(x, lkr, g)
        right = (gj > k)[:, None, None]
        rp = coll.bcast(jnp.where(right, xr, jnp.zeros_like(xr)), kr, ROW_AXIS)
        cp = coll.transpose_panel_rows(rp, g.nt, g.ltr)  # U[k,i] at local rows i>k
        # S[j] = sum_i U[k,i] inv[i,j] over trailing rows (inv rows > k final);
        # tiles below the diagonal are never referenced (may hold garbage)
        keep_rows = ((gi > k)[:, None] & (gi[:, None] <= gj[None, :]))[:, :, None, None]
        s_part = t.contract("iab,ijbc->jac", cp, jnp.where(keep_rows, x, jnp.zeros_like(x)))
        s_full = coll.psum_axis(s_part, ROW_AXIS)
        newrow = -t.contract("ab,jbc->jac", tkk, s_full)
        newrow = jnp.where(
            (gj == k)[:, None, None], tkk[None], jnp.where(right, newrow, xr)
        )
        return _spmd.put_row(x, jnp.where(myr == kr, newrow, xr), lkr)

    x = lax.fori_loop(0, g.mt, body, x)
    x = _spmd.pad_diag_identity(x, g, myr, myc, remove=True)
    return coll.relocal(x)


def _trtri_single_device(uplo: str, diag: str, mat_a: DistributedMatrix) -> DistributedMatrix:
    """1x1-grid fast path: dense triangular solve against the identity."""
    import jax

    from dlaf_tpu.matrix import layout

    from dlaf_tpu.tune import blas3_precision

    dist = mat_a.dist

    def build():
        def run(x):
            g_ = layout.unpad_global(layout.unpack(x, dist), dist)
            eye = jnp.eye(g_.shape[0], dtype=g_.dtype)
            inv = t.trsm(t.LEFT, uplo, t.NO_TRANS, diag, 1.0, g_, eye)
            # keep the unreferenced triangle as the caller stored it
            if uplo == t.LOWER:
                out = jnp.tril(inv) + jnp.triu(g_, 1)
            else:
                out = jnp.triu(inv) + jnp.tril(g_, -1)
            return layout.pack(layout.pad_global(out, dist), dist)

        return _plan.jit("trtri_local", run)

    fn = _plan.cached("trtri_local", (dist, str(mat_a.dtype), uplo, diag), build)
    with blas3_precision():
        return mat_a._inplace(fn(mat_a.data))


@origin_transparent
def triangular_inverse(uplo: str, diag: str, mat_a: DistributedMatrix) -> DistributedMatrix:
    """In-place triangular inverse of the ``uplo`` triangle of A (the other
    triangle is not referenced and returned unchanged structure-wise)."""
    if mat_a.size.rows != mat_a.size.cols or mat_a.block_size.rows != mat_a.block_size.cols:
        raise ValueError("trtri: A must be square with square tiles")
    g = _spmd.Geometry.of(mat_a.dist)
    if g.mt == 0:
        return mat_a
    if mat_a.grid.grid_size.count() == 1:
        return _trtri_single_device(uplo, diag, mat_a)
    from dlaf_tpu.tune import blas3_precision

    def build():
        kern_fn = (
            _trtri_lower_bucketed_kernel if uplo == t.LOWER else _trtri_upper_bucketed_kernel
        )
        return coll.spmd(
            mat_a.grid, partial(kern_fn, g=g, diag=diag), donate_argnums=(0,),
            name="trtri",
        )

    fn = _plan.cached("trtri", (mat_a.grid.cache_key, uplo, diag, g), build)
    with blas3_precision():
        return mat_a._inplace(fn(mat_a.data))


@origin_transparent
def inverse_from_cholesky_factor(uplo: str, mat_a: DistributedMatrix) -> DistributedMatrix:
    """Given the Cholesky factor in the ``uplo`` triangle of A (as produced by
    cholesky_factorization), return A^-1 with FULL Hermitian storage
    (reference: inverse_from_cholesky_factor, inverse/cholesky.h:38)."""
    from dlaf_tpu.algorithms.multiplication import general_multiplication

    tinv = triangular_inverse(uplo, t.NON_UNIT, mat_a)
    tri = mutil.extract_triangle(tinv, uplo)
    out = DistributedMatrix(tinv.dist, tinv.grid, jnp.zeros_like(tinv.data))
    if uplo == t.LOWER:
        # A^-1 = L^-H L^-1
        return general_multiplication(t.CONJ_TRANS, t.NO_TRANS, 1.0, tri, tri, 0.0, out)
    # A^-1 = U^-1 U^-H
    return general_multiplication(t.NO_TRANS, t.CONJ_TRANS, 1.0, tri, tri, 0.0, out)
