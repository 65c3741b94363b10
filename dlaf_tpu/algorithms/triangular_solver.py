"""Distributed triangular solve (TRSM), all side/uplo/op/diag combinations.

TPU-native re-design of the reference distributed TRSM
(reference: include/dlaf/solver/triangular.h:31-83 and
solver/triangular/impl.h, 1205 lines covering the 16 combos with lookahead
panels).  Same SPMD skeleton as cholesky.py: one jitted fori_loop over the
triangular matrix's tile diagonal; each step broadcasts the diagonal tile,
solves one tile row (Left) / tile column (Right) of B in a batched trsm, and
applies one batched-einsum rank-nb update to the remaining rows/cols.
Direction (forward/backward) and panel source (A column vs transposed A row)
are resolved statically per combo; transposed panels reuse the
transpose_panel collectives rather than the reference's StoreTransposed
Panel workspaces (matrix/panel.h:571-616).
"""
from __future__ import annotations

from dlaf_tpu.algorithms._origin import origin_transparent

from functools import partial

import jax.numpy as jnp
import numpy as np
from jax import lax

from dlaf_tpu.algorithms import _spmd
from dlaf_tpu.comm import collectives as coll
from dlaf_tpu.comm.grid import COL_AXIS, ROW_AXIS
from dlaf_tpu.matrix.matrix import DistributedMatrix
from dlaf_tpu.obs.trace import scope as _scope
from dlaf_tpu.ops import tile as t
from dlaf_tpu.plan import core as _plan


def _trsm_left_kernel(a, b, g_a: _spmd.Geometry, g_b: _spmd.Geometry, uplo, op, diag, alpha):
    """Solve op(A) X = alpha B in place of B.  A: mt x mt tiles, B: mt x nt."""
    a = coll.local(a)
    b = coll.local(b)
    myr, myc = coll.my_rank()
    a = _spmd.pad_diag_identity(a, g_a, myr, myc)  # keep padded diag tiles non-singular
    lower = uplo == t.LOWER
    forward = lower == (op == t.NO_TRANS)
    mt = g_a.mt
    b = (jnp.asarray(alpha, b.dtype) * b).astype(b.dtype)
    gi = _spmd.local_row_tiles(g_b, myr)

    def body(s, b):
        k = s if forward else mt - 1 - s
        kr, kc = k % g_a.pr, k % g_a.pc
        lkr = k // g_a.pr
        with _scope("trsm.panel_solve"):
            akk = _spmd.bcast_diag_tile(a, k, g_a, myr, myc)
            # solve tile-row k of B (batched over this rank's local cols)
            brow = _spmd.take_row(b, lkr, g_b)
            solved = t.trsm(t.LEFT, uplo, op, diag, 1.0, akk, brow)
            xr = coll.bcast(solved, kr, ROW_AXIS)
        b = _spmd.put_row(b, jnp.where(myr == kr, solved, brow), lkr)
        # panel of op(A)[i, k] for remaining rows i
        remaining = (gi > k) if forward else (gi < k)
        if op == t.NO_TRANS:
            ac = _spmd.take_col(a, k // g_a.pc, g_a)
            cp = coll.bcast(
                jnp.where(remaining[:, None, None], ac, jnp.zeros_like(ac)),
                kc, COL_AXIS,
            )
        else:
            ar = _spmd.take_row(a, lkr, g_a)  # tiles A[k, j] for local cols j
            gj = _spmd.local_col_tiles(g_a, myc)
            rem_j = (gj > k) if forward else (gj < k)
            rp = coll.bcast(
                jnp.where(rem_j[:, None, None], ar, jnp.zeros_like(ar)),
                kr, ROW_AXIS,
            )
            cp = t.op_tile(coll.transpose_panel_rows(rp, g_a.mt, g_b.ltr), op)
            cp = jnp.where(remaining[:, None, None], cp, jnp.zeros_like(cp))
        # B[i, :] -= op(A)[i,k] @ X[k, :]
        with _scope("trsm.update"):
            return b - t.contract("iab,jbc->ijac", cp, xr)

    b = lax.fori_loop(0, mt, body, b)
    return coll.relocal(b)


def _trsm_right_kernel(a, b, g_a: _spmd.Geometry, g_b: _spmd.Geometry, uplo, op, diag, alpha):
    """Solve X op(A) = alpha B in place of B.  A: nt x nt tiles, B: mt x nt."""
    a = coll.local(a)
    b = coll.local(b)
    myr, myc = coll.my_rank()
    a = _spmd.pad_diag_identity(a, g_a, myr, myc)  # keep padded diag tiles non-singular
    lower = uplo == t.LOWER
    forward = lower != (op == t.NO_TRANS)
    nt = g_a.nt
    b = (jnp.asarray(alpha, b.dtype) * b).astype(b.dtype)
    gj = _spmd.local_col_tiles(g_b, myc)

    def body(s, b):
        k = s if forward else nt - 1 - s
        kr, kc = k % g_a.pr, k % g_a.pc
        lkc = k // g_a.pc
        with _scope("trsm.panel_solve"):
            akk = _spmd.bcast_diag_tile(a, k, g_a, myr, myc)
            # solve tile-col k of B (batched over this rank's local rows)
            bcol = _spmd.take_col(b, lkc, g_b)
            solved = t.trsm(t.RIGHT, uplo, op, diag, 1.0, akk, bcol)
            xc = coll.bcast(solved, kc, COL_AXIS)
        b = _spmd.put_col(b, jnp.where(myc == kc, solved, bcol), lkc)
        # panel of op(A)[k, j] for remaining cols j
        remaining = (gj > k) if forward else (gj < k)
        if op == t.NO_TRANS:
            ar = _spmd.take_row(a, k // g_a.pr, g_a)
            rp = coll.bcast(
                jnp.where(remaining[:, None, None], ar, jnp.zeros_like(ar)),
                kr, ROW_AXIS,
            )
        else:
            ac = _spmd.take_col(a, lkc, g_a)  # tiles A[i, k] for local rows i
            gi = _spmd.local_row_tiles(g_a, myr)
            rem_i = (gi > k) if forward else (gi < k)
            cp = coll.bcast(
                jnp.where(rem_i[:, None, None], ac, jnp.zeros_like(ac)),
                kc, COL_AXIS,
            )
            rp = t.op_tile(coll.transpose_panel(cp, g_a.nt, g_b.ltc), op)
            rp = jnp.where(remaining[:, None, None], rp, jnp.zeros_like(rp))
        # B[:, j] -= X[:, k] @ op(A)[k, j]
        with _scope("trsm.update"):
            return b - t.contract("iab,jbc->ijac", xc, rp)

    b = lax.fori_loop(0, nt, body, b)
    return coll.relocal(b)


def _trsm_left_bucketed_kernel(a, b, g_a, g_b, uplo, op, diag, alpha):
    """Bucketed variant of _trsm_left_kernel: the remaining-rows window of B
    (and the A panel) is dynamic-sliced with a static per-segment size, like
    cholesky's bucketed trailing update.  Masked panels make clamped window
    overlap a no-op."""
    a = coll.local(a)
    b = coll.local(b)
    myr, myc = coll.my_rank()
    a = _spmd.pad_diag_identity(a, g_a, myr, myc)
    lower = uplo == t.LOWER
    forward = lower == (op == t.NO_TRANS)
    mt = g_a.mt
    b = (jnp.asarray(alpha, b.dtype) * b).astype(b.dtype)

    def step(s, b, L):
        k = s if forward else mt - 1 - s
        kr, kc = k % g_a.pr, k % g_a.pc
        lkr = k // g_a.pr
        with _scope("trsm.panel_solve"):
            akk = _spmd.bcast_diag_tile(a, k, g_a, myr, myc)
            brow = _spmd.take_row(b, lkr, g_b)
            solved = t.trsm(t.LEFT, uplo, op, diag, 1.0, akk, brow)
            xr = coll.bcast(solved, kr, ROW_AXIS)
        b = _spmd.put_row(b, jnp.where(myr == kr, solved, brow), lkr)
        # remaining-rows window
        if forward:
            rs = jnp.clip((k + g_a.pr - myr) // g_a.pr, 0, max(g_b.ltr - L, 0))
            rs = rs.astype(jnp.asarray(k).dtype)
        else:
            rs = jnp.asarray(k) * 0  # start at 0, only the size shrinks
        gi_w = (rs + jnp.arange(L)) * g_a.pr + myr
        remaining = (gi_w > k) if forward else (gi_w < k)
        if op == t.NO_TRANS:
            ac = lax.dynamic_slice(
                a, (rs, k // g_a.pc, 0, 0), (L, 1, g_a.mb, g_a.mb)
            )[:, 0]
            cp = coll.bcast(
                jnp.where(remaining[:, None, None], ac, jnp.zeros_like(ac)),
                kc, COL_AXIS,
            )
        else:
            ar = _spmd.take_row(a, lkr, g_a)
            gj = _spmd.local_col_tiles(g_a, myc)
            rem_j = (gj > k) if forward else (gj < k)
            rp = coll.bcast(
                jnp.where(rem_j[:, None, None], ar, jnp.zeros_like(ar)),
                kr, ROW_AXIS,
            )
            # row panel -> windowed col panel: tiles indexed by A's col j
            cp = t.op_tile(coll.transpose_panel_rows_windowed(rp, gi_w, 0, g_a.mt), op)
            cp = jnp.where(remaining[:, None, None], cp, jnp.zeros_like(cp))
        with _scope("trsm.update"):
            bs = lax.dynamic_slice(b, (rs, 0, 0, 0), (L, g_b.ltc, g_b.mb, g_b.nb))
            bs = bs - t.contract("iab,jbc->ijac", cp, xr)
            return lax.dynamic_update_slice(b, bs, (rs, 0, 0, 0))

    for s0, s1 in _spmd.halving_segments(mt):
        rem = mt - 1 - s0  # max remaining tiles within the segment
        L = max(min(g_b.ltr, (rem + g_a.pr - 1) // g_a.pr + 1), 1)
        b = lax.fori_loop(s0, s1, partial(step, L=L), b)
    return coll.relocal(b)


def _trsm_right_bucketed_kernel(a, b, g_a, g_b, uplo, op, diag, alpha):
    """Bucketed variant of _trsm_right_kernel: the remaining-COLS window of
    B (and the op(A)[k, :] panel) is dynamic-sliced with a static
    per-segment size — the column-axis mirror of the left bucketed kernel
    (halves the einsum flops vs the full-stack masked form)."""
    a = coll.local(a)
    b = coll.local(b)
    myr, myc = coll.my_rank()
    a = _spmd.pad_diag_identity(a, g_a, myr, myc)
    lower = uplo == t.LOWER
    forward = lower != (op == t.NO_TRANS)
    nt = g_a.nt
    b = (jnp.asarray(alpha, b.dtype) * b).astype(b.dtype)

    def step(s, b, C):
        k = s if forward else nt - 1 - s
        kr, kc = k % g_a.pr, k % g_a.pc
        lkc = k // g_a.pc
        with _scope("trsm.panel_solve"):
            akk = _spmd.bcast_diag_tile(a, k, g_a, myr, myc)
            bcol = _spmd.take_col(b, lkc, g_b)
            solved = t.trsm(t.RIGHT, uplo, op, diag, 1.0, akk, bcol)
            xc = coll.bcast(solved, kc, COL_AXIS)
        b = _spmd.put_col(b, jnp.where(myc == kc, solved, bcol), lkc)
        # remaining-cols window
        if forward:
            cs = jnp.clip((k + g_a.pc - myc) // g_a.pc, 0, max(g_b.ltc - C, 0))
            cs = cs.astype(jnp.asarray(k).dtype)
        else:
            cs = jnp.asarray(k) * 0  # start at 0, only the size shrinks
        gj_w = (cs + jnp.arange(C)) * g_a.pc + myc
        remaining = (gj_w > k) if forward else (gj_w < k)
        if op == t.NO_TRANS:
            ar = lax.dynamic_slice(
                a, (k // g_a.pr, cs, 0, 0), (1, C, g_a.mb, g_a.mb)
            )[0]
            rp = coll.bcast(
                jnp.where(remaining[:, None, None], ar, jnp.zeros_like(ar)),
                kr, ROW_AXIS,
            )
        else:
            ac = _spmd.take_col(a, lkc, g_a)  # tiles A[i, k] for local rows i
            gi = _spmd.local_row_tiles(g_a, myr)
            rem_i = (gi > k) if forward else (gi < k)
            cp = coll.bcast(
                jnp.where(rem_i[:, None, None], ac, jnp.zeros_like(ac)),
                kc, COL_AXIS,
            )
            # col panel -> windowed row panel: tiles indexed by A's row j
            rp = t.op_tile(coll.transpose_panel_windowed(cp, gj_w, 0, g_a.nt), op)
            rp = jnp.where(remaining[:, None, None], rp, jnp.zeros_like(rp))
        with _scope("trsm.update"):
            bs = lax.dynamic_slice(b, (0, cs, 0, 0), (g_b.ltr, C, g_b.mb, g_b.nb))
            bs = bs - t.contract("iab,jbc->ijac", xc, rp)
            return lax.dynamic_update_slice(b, bs, (0, cs, 0, 0))

    for s0, s1 in _spmd.halving_segments(nt):
        rem = nt - 1 - s0  # max remaining tiles within the segment
        C = max(min(g_b.ltc, (rem + g_a.pc - 1) // g_a.pc + 1), 1)
        b = lax.fori_loop(s0, s1, partial(step, C=C), b)
    return coll.relocal(b)


def _trsm_left_lookahead_kernel(a, b, g_a, g_b, uplo, op, diag, alpha):
    """Lookahead variant of _trsm_left_kernel (reference: the next-panel
    high-priority tasks of solver/triangular/impl.h): each iteration writes
    back row k, applies the NARROW update to row k+1 only, immediately
    solves row k+1 (its psum rides alongside the bulk einsum — XLA can
    overlap the independent collective with the trailing update), then
    runs the bulk update excluding row k+1.  The solved row flows through
    the loop carry, exactly like cholesky's lookahead panel."""
    a = coll.local(a)
    b = coll.local(b)
    myr, myc = coll.my_rank()
    a = _spmd.pad_diag_identity(a, g_a, myr, myc)
    lower = uplo == t.LOWER
    forward = lower == (op == t.NO_TRANS)
    mt = g_a.mt
    b = (jnp.asarray(alpha, b.dtype) * b).astype(b.dtype)
    gi = _spmd.local_row_tiles(g_b, myr)

    def a_tile(k, i):
        """op(A)[i, k] broadcast to every rank (one tile)."""
        if op == t.NO_TRANS:
            src_r, src_c = i, k
        else:
            src_r, src_c = k, i
        rr, cc = src_r % g_a.pr, src_c % g_a.pc
        tile = _spmd.take_tile(_spmd.take_col(a, src_c // g_a.pc, g_a), src_r // g_a.pr)
        tile = coll.bcast2d(
            jnp.where((myr == rr) & (myc == cc), tile, jnp.zeros_like(tile)), rr, cc
        )
        return t.op_tile(tile, op)

    def solve_row(b, k):
        with _scope("trsm.panel_solve"):
            kr = k % g_a.pr
            akk = _spmd.bcast_diag_tile(a, k, g_a, myr, myc)
            brow = _spmd.take_row(b, k // g_a.pr, g_b)
            solved = t.trsm(t.LEFT, uplo, op, diag, 1.0, akk, brow)
            return coll.bcast(solved, kr, ROW_AXIS)

    def write_row(b, k, xr):
        lkr = k // g_a.pr
        brow = _spmd.take_row(b, lkr, g_b)
        return _spmd.put_row(b, jnp.where(myr == k % g_a.pr, xr, brow), lkr)

    def panel(k):
        """cp[i] = op(A)[i, k] for local rows i beyond k (bulk update)."""
        remaining = (gi > k) if forward else (gi < k)
        if op == t.NO_TRANS:
            kc = k % g_a.pc
            ac = _spmd.take_col(a, k // g_a.pc, g_a)
            return coll.bcast(
                jnp.where(remaining[:, None, None], ac, jnp.zeros_like(ac)),
                kc, COL_AXIS,
            )
        kr = k % g_a.pr
        ar = _spmd.take_row(a, k // g_a.pr, g_a)
        gj = _spmd.local_col_tiles(g_a, myc)
        rem_j = (gj > k) if forward else (gj < k)
        rp = coll.bcast(
            jnp.where(rem_j[:, None, None], ar, jnp.zeros_like(ar)),
            kr, ROW_AXIS,
        )
        cp = t.op_tile(coll.transpose_panel_rows(rp, g_a.mt, g_b.ltr), op)
        return jnp.where(remaining[:, None, None], cp, jnp.zeros_like(cp))

    def body(s, carry):
        b, xr = carry
        k = s if forward else mt - 1 - s
        k1 = k + 1 if forward else k - 1
        b = write_row(b, k, xr)
        # narrow update: row k1 only, so its solve can start immediately
        a1 = a_tile(k, k1)
        lk1 = k1 // g_a.pr
        brow1 = _spmd.take_row(b, lk1, g_b)
        upd1 = t.contract("ab,jbc->jac", a1, xr)
        brow1 = jnp.where(myr == k1 % g_a.pr, brow1 - upd1, brow1)
        b = _spmd.put_row(b, brow1, lk1)
        xr1 = solve_row(b, k1)  # lookahead: overlaps with the bulk below
        # bulk update, row k1 excluded (already updated)
        with _scope("trsm.update"):
            cp = panel(k)
            cp = jnp.where((gi == k1)[:, None, None], jnp.zeros_like(cp), cp)
            if _spmd.trailing_update_trace_key() == "fused":
                # fused tier: the bulk update as ONE VMEM-resident Pallas
                # kernel (in-kernel split-GEMM decomposition); compiled
                # TPU keeps the XLA einsum for complex payloads (Mosaic
                # has no complex arithmetic)
                from dlaf_tpu.ops import pallas_trailing_update as ptu

                if ptu.update_kernel_ok(b.dtype):
                    b = ptu.trailing_update(b, cp, xr, "iab,jbc->ijac")
                else:
                    b = b - t.contract("iab,jbc->ijac", cp, xr)
            else:
                b = b - t.contract("iab,jbc->ijac", cp, xr)
        return b, xr1

    k0 = 0 if forward else mt - 1
    xr0 = solve_row(b, k0)
    b, xr = lax.fori_loop(0, mt - 1, body, (b, xr0))
    b = write_row(b, mt - 1 if forward else 0, xr)
    return coll.relocal(b)


# dense-solve geometries the backend compiler refused (not executables —
# a retry memo, so the SPMD fallback is remembered per shape)
_dense_fail: set = set()


def _trsm_single_device(side, uplo, op, diag, alpha, mat_a, mat_b):
    """1x1-grid fast path: one XLA triangular_solve on the dense operands
    (~1.4x the SPMD loop on one chip at N=8k)."""
    import jax

    from dlaf_tpu.matrix import layout

    from dlaf_tpu.tune import blas3_precision

    da, db = mat_a.dist, mat_b.dist

    def build():
        def run(xa, xb):
            ga = layout.unpad_global(layout.unpack(xa, da), da)
            gb = layout.unpad_global(layout.unpack(xb, db), db)
            out = t.trsm(side, uplo, op, diag, jnp.asarray(alpha, gb.dtype), ga, gb)
            return layout.pack(layout.pad_global(out, db), db)

        return _plan.jit("trsm_local", run)

    fn = _plan.cached(
        "trsm_local",
        (da, db, np.dtype(mat_b.dtype), side, uplo, op, diag, complex(alpha)),
        build,
    )
    with blas3_precision():
        return mat_b._inplace(fn(mat_a.data, mat_b.data))


@origin_transparent
def triangular_solver(
    side: str, uplo: str, op: str, diag: str, alpha, mat_a: DistributedMatrix,
    mat_b: DistributedMatrix, backend: str = "auto",
    refine_to: str | None = None, refine_sweeps: int = 2,
) -> DistributedMatrix:
    """B := solution X of op(A) X = alpha B (Left) / X op(A) = alpha B (Right).

    A is triangular (only the ``uplo`` triangle is read).  Returns the
    updated B matrix (functional in-place).  ``backend='auto'`` uses one
    dense XLA triangular_solve on 1x1 grids, the distributed SPMD kernel
    otherwise; 'distributed' forces the kernel.

    ``refine_to='input'`` appends up to ``refine_sweeps`` residual
    corrections (``algorithms.refine``; companion of the bf16 split-GEMM
    tiers): r = alpha B - op(A)-apply(X) at full precision, correction
    d = solve(r) at the ambient tier, X += d.  Needs a pre-solve snapshot
    of B (the solve donates it)."""
    if refine_to is not None:
        from dlaf_tpu.algorithms.refine import validate_refine_to

        validate_refine_to(refine_to)
        b_snap = mat_b.astype(mat_b.dtype)  # fresh buffer: solve donates B
        x = triangular_solver(side, uplo, op, diag, alpha, mat_a, mat_b,
                              backend=backend)
        return _trsm_refined(side, uplo, op, diag, alpha, mat_a, x, b_snap,
                             backend, refine_sweeps)
    if mat_a.size.rows != mat_a.size.cols:
        raise ValueError("trsm: A must be square")
    if mat_a.block_size.rows != mat_a.block_size.cols:
        raise ValueError("trsm: A tiles must be square")
    need = mat_b.size.rows if side == t.LEFT else mat_b.size.cols
    need_b = mat_b.block_size.rows if side == t.LEFT else mat_b.block_size.cols
    if mat_a.size.rows != need or mat_a.block_size.rows != need_b:
        raise ValueError(f"trsm: A size {mat_a.size} incompatible with B {mat_b.size} for side {side}")
    if mat_a.grid is not mat_b.grid and mat_a.grid.grid_size != mat_b.grid.grid_size:
        raise ValueError("trsm: A and B must share the grid")
    g_a = _spmd.Geometry.of(mat_a.dist)
    g_b = _spmd.Geometry.of(mat_b.dist)
    if g_b.mt == 0 or g_b.nt == 0 or g_a.mt == 0:
        return mat_b
    if backend == "auto" and mat_b.grid.grid_size.count() == 1:
        fail_key = (mat_b.size, np.dtype(mat_b.dtype))
        if fail_key not in _dense_fail:
            try:
                return _trsm_single_device(side, uplo, op, diag, alpha, mat_a, mat_b)
            except Exception:
                # e.g. backend compiler limits on very large dense solves —
                # remember and use the tiled SPMD kernel instead
                _dense_fail.add(fail_key)
    from dlaf_tpu.tune import get_tune_parameters

    lookahead = side == t.LEFT and get_tune_parameters().trsm_lookahead and g_a.mt > 1
    if side == t.LEFT:
        kern_fn = _trsm_left_lookahead_kernel if lookahead else _trsm_left_bucketed_kernel
    else:
        kern_fn = _trsm_right_bucketed_kernel
    from dlaf_tpu.tune import blas3_precision

    def build():
        kern = partial(kern_fn, g_a=g_a, g_b=g_b, uplo=uplo, op=op, diag=diag, alpha=alpha)
        return coll.spmd(mat_b.grid, kern, donate_argnums=(1,), name="trsm")

    fn = _plan.cached(
        "trsm",
        (mat_b.grid.cache_key, side, uplo, op, diag, complex(alpha), g_a, g_b,
         lookahead),
        build,
    )
    with blas3_precision():
        return mat_b._inplace(fn(mat_a.data, mat_b.data))


def _trsm_refined(side, uplo, op, diag, alpha, mat_a, x, b_snap, backend,
                  refine_sweeps):
    """The ``refine_to='input'`` tail of ``triangular_solver``: residual
    r = alpha B - op(A)-apply(X) via ``triangular_multiplication`` (full
    precision), correction d = solve(r) at the ambient tier."""
    from dlaf_tpu.algorithms.multiplication import triangular_multiplication
    from dlaf_tpu.algorithms.norm import max_norm
    from dlaf_tpu.algorithms.refine import refine_tolerance, residual_refine

    anorm = max_norm(mat_a, uplo)

    def residual(xc):
        # trmm treats X as a summa operand (never donated) and returns a
        # fresh matrix; the subtraction is elementwise, no contraction
        ax = triangular_multiplication(side, uplo, op, diag, 1.0, mat_a, xc)
        return ax.like(alpha * b_snap.data.astype(ax.dtype) - ax.data)

    x, _ = residual_refine(
        x,
        residual,
        lambda r: triangular_solver(side, uplo, op, diag, 1.0, mat_a, r,
                                    backend=backend),
        tol=refine_tolerance(anorm, mat_a.size.rows, x.dtype),
        anorm=anorm,
        max_sweeps=refine_sweeps,
    )
    return x
