"""Distributed matrix multiplication family: GEMM (general), TRMM
(triangular), HEMM (Hermitian) on the 2D block-cyclic grid.

TPU-native re-design of the reference multiplication algorithms
(reference: include/dlaf/multiplication/{general,triangular,hermitian}.h and
their impl.h files).  All three share ONE SUMMA-style SPMD kernel: a jitted
fori_loop over the contraction tile index k where each step

  1. broadcasts column k of op(A)-tiles along 'c' (owner rank-column) and
     row k of op(B)-tiles along 'r' (owner rank-row) — for transposed
     operands the panel is fetched from the transposed storage direction and
     re-distributed with the transpose_panel collectives,
  2. accumulates C += panel_outer_product as one batched einsum.

Triangular/Hermitian structure is applied by masking the broadcast A panels
(tril/triu of diagonal tiles, zero/mirrored off-triangle tiles) instead of
the reference's per-case tile loops (multiplication/triangular/impl.h: 726
lines over 16 combos).  The reference computes TRMM in place; we return a
fresh C (functional), letting XLA alias buffers where legal.

This replaces, in one file: `triangular_multiplication`
(multiplication/triangular.h:48), `hermitian_multiplication`
(multiplication/hermitian.h:29), and internal `GeneralSub::callNN`
(multiplication/general/api.h:28).
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

# A-panel structure masks
_FULL = "full"
_LOWER_TRI = "ltri"  # A triangular-lower: tiles above diag zero, diag tril
_UPPER_TRI = "utri"
_HERM_LOWER = "herm_l"  # Hermitian, lower stored: upper tiles = mirror^H
_HERM_UPPER = "herm_u"


def _a_col_panel(a, k, g_a, myr, myc, op, structure, diag, ltr_out, mt_out):
    """Tiles op(A)[i, k] for this rank's local rows i, broadcast to all rank
    columns.  [ltr_out, mb, mb]."""
    gi = jnp.arange(ltr_out) * g_a.pr + myr

    def direct_col():
        # column k of A, masked by structure
        kc = k % g_a.pc
        ac = _spmd.take_col(a, k // g_a.pc, g_a)
        ac = _structure_mask_col(ac, gi, k, structure, diag)
        return coll.bcast(ac, kc, COL_AXIS)

    def from_row():
        # row k of A (tiles A[k, j]), op-transposed into a column panel
        kr = k % g_a.pr
        ar = _spmd.take_row(a, k // g_a.pr, g_a)
        gj = jnp.arange(g_a.ltc) * g_a.pc + myc
        ar = _structure_mask_col(
            jnp.swapaxes(ar, -1, -2), gj, k, _transpose_structure(structure), diag
        )
        ar = jnp.swapaxes(ar, -1, -2)
        rp = coll.bcast(ar, kr, ROW_AXIS)
        cp = coll.transpose_panel_rows(rp, mt_out, ltr_out)
        return t.op_tile(cp, op)

    if structure in (_HERM_LOWER, _HERM_UPPER):
        # Hermitian: column k assembled from BOTH the stored triangle's column
        # and the conj-transposed stored row (diagonal-crossing mirror).
        lower = structure == _HERM_LOWER
        kc, kr = k % g_a.pc, k % g_a.pr
        ac = _spmd.take_col(a, k // g_a.pc, g_a)
        keep_col = (gi >= k) if lower else (gi <= k)
        ac = jnp.where(keep_col[:, None, None], ac, jnp.zeros_like(ac))
        # make the diagonal tile exactly Hermitian from its stored triangle
        dmask = (gi == k)[:, None, None]
        ac = jnp.where(dmask, _hermitize_tile(ac, lower), ac)
        cp1 = coll.bcast(ac, kc, COL_AXIS)
        ar = _spmd.take_row(a, k // g_a.pr, g_a)
        gj = jnp.arange(g_a.ltc) * g_a.pc + myc
        keep_row = (gj < k) if lower else (gj > k)  # strict mirror: diag from col
        ar = jnp.where(keep_row[:, None, None], ar, jnp.zeros_like(ar))
        rp = coll.bcast(ar, kr, ROW_AXIS)
        cp2 = t.op_tile(coll.transpose_panel_rows(rp, mt_out, ltr_out), t.CONJ_TRANS)
        return cp1 + cp2
    if op == t.NO_TRANS:
        return direct_col()
    return from_row()


def _transpose_structure(structure):
    return {_FULL: _FULL, _LOWER_TRI: _UPPER_TRI, _UPPER_TRI: _LOWER_TRI}[structure]


def _hermitize_tile(tiles, lower: bool):
    """Build the full Hermitian tile from its stored triangle."""
    if lower:
        tri = jnp.tril(tiles)
        return tri + jnp.swapaxes(jnp.tril(tiles, -1), -1, -2).conj()
    tri = jnp.triu(tiles)
    return tri + jnp.swapaxes(jnp.triu(tiles, 1), -1, -2).conj()


def _structure_mask_col(ac, gi, k, structure, diag):
    """Mask a column-k panel [lt, mb, nb] of A by triangular structure."""
    if structure == _FULL:
        return ac
    lower = structure == _LOWER_TRI
    keep = (gi >= k) if lower else (gi <= k)
    ac = jnp.where(keep[:, None, None], ac, jnp.zeros_like(ac))
    dmask = (gi == k)[:, None, None]
    dtile = jnp.tril(ac) if lower else jnp.triu(ac)
    if diag == t.UNIT:
        eye = jnp.eye(ac.shape[-2], ac.shape[-1], dtype=ac.dtype)
        dtile = dtile - dtile * eye + eye
    return jnp.where(dmask, dtile, ac)


def _b_row_panel(b, k, g_b, myr, myc, op, ltc_out, nt_out):
    """Tiles op(B)[k, j] for this rank's local cols j, broadcast to all rank
    rows.  [ltc_out, mb, nb]."""
    if op == t.NO_TRANS:
        kr = k % g_b.pr
        br = _spmd.take_row(b, k // g_b.pr, g_b)
        return coll.bcast(br, kr, ROW_AXIS)
    kc = k % g_b.pc
    bc = _spmd.take_col(b, k // g_b.pc, g_b)
    cp = coll.bcast(bc, kc, COL_AXIS)
    rp = coll.transpose_panel(cp, nt_out, ltc_out)
    return t.op_tile(rp, op)


def _summa_kernel(
    a, b, c, g_a, g_b, g_c, opa, opb, alpha, beta, structure, diag, kt
):
    a, b, c = coll.local(a), coll.local(b), coll.local(c)
    myr, myc = coll.my_rank()
    c = (jnp.asarray(beta, c.dtype) * c).astype(c.dtype)
    al = jnp.asarray(alpha, c.dtype)

    def body(k, c):
        with _scope("summa.panel_bcast"):
            cp = _a_col_panel(a, k, g_a, myr, myc, opa, structure, diag, g_c.ltr, g_c.mt)
            rp = _b_row_panel(b, k, g_b, myr, myc, opb, g_c.ltc, g_c.nt)
        with _scope("summa.update"):
            return c + al * t.contract("iab,jbc->ijac", cp, rp)

    c = lax.fori_loop(0, kt, body, c)
    return coll.relocal(c)


def _dense_structured_a(ga, structure, diag):
    """Materialize the structured operand on a 1x1 grid (dense fast path)."""
    if structure == _FULL:
        return ga
    if structure in (_LOWER_TRI, _UPPER_TRI):
        tri = jnp.tril(ga) if structure == _LOWER_TRI else jnp.triu(ga)
        if diag == t.UNIT:
            eye = jnp.eye(tri.shape[-1], dtype=tri.dtype)
            tri = tri - tri * eye + eye
        return tri
    lower = structure == _HERM_LOWER
    if lower:
        return jnp.tril(ga) + jnp.swapaxes(jnp.tril(ga, -1), -1, -2).conj()
    return jnp.triu(ga) + jnp.swapaxes(jnp.triu(ga, 1), -1, -2).conj()


def _run_dense_local(mat_a, mat_b, mat_c, opa, opb, alpha, beta, structure, diag, a_right):
    """1x1-grid fast path: one dense GEMM instead of the SUMMA loop."""
    import jax

    from dlaf_tpu.tune import blas3_precision

    da, db, dc = mat_a.dist, mat_b.dist, mat_c.dist
    def build():
        from dlaf_tpu.matrix import layout

        def run(xa, xb, xc):
            ga = layout.unpad_global(layout.unpack(xa, da), da)
            gb = layout.unpad_global(layout.unpack(xb, db), db)
            gc = layout.unpad_global(layout.unpack(xc, dc), dc)
            ga = t.op_tile(_dense_structured_a(ga, structure, diag), opa)
            gb = t.op_tile(gb, opb)
            prod = (
                t.contract("...ab,...bc->...ac", gb, ga)
                if a_right
                else t.contract("...ab,...bc->...ac", ga, gb)
            )
            out = jnp.asarray(alpha, gc.dtype) * prod + jnp.asarray(beta, gc.dtype) * gc
            return layout.pack(layout.pad_global(out.astype(gc.dtype), dc), dc)

        return _plan.jit("gemm_local", run)

    fn = _plan.cached(
        "gemm_local",
        (da, db, dc, np.dtype(mat_c.dtype), opa, opb, complex(alpha),
         complex(beta), structure, diag, a_right),
        build,
    )
    with blas3_precision():
        return mat_c._inplace(fn(mat_a.data, mat_b.data, mat_c.data))


def _run_summa(mat_a, mat_b, mat_c, opa, opb, alpha, beta, structure, diag, kt):
    from dlaf_tpu.tune import blas3_precision

    g_a = _spmd.Geometry.of(mat_a.dist)
    g_b = _spmd.Geometry.of(mat_b.dist)
    g_c = _spmd.Geometry.of(mat_c.dist)
    if g_c.mt == 0 or g_c.nt == 0:
        return mat_c
    if mat_c.grid.grid_size.count() == 1:
        return _run_dense_local(mat_a, mat_b, mat_c, opa, opb, alpha, beta, structure, diag, False)
    def build():
        kern = partial(
            _summa_kernel, g_a=g_a, g_b=g_b, g_c=g_c, opa=opa, opb=opb,
            alpha=alpha, beta=beta, structure=structure, diag=diag, kt=kt,
        )
        return coll.spmd(mat_c.grid, kern, donate_argnums=(2,), name="summa")

    fn = _plan.cached(
        "summa",
        (mat_c.grid.cache_key, opa, opb, complex(alpha), complex(beta),
         structure, diag, kt, g_a, g_b, g_c),
        build,
    )
    with blas3_precision():
        return mat_c._inplace(fn(mat_a.data, mat_b.data, mat_c.data))


@origin_transparent
def general_multiplication(
    opa: str, opb: str, alpha, mat_a, mat_b, beta, mat_c
) -> DistributedMatrix:
    """C := alpha op(A) op(B) + beta C (reference GeneralSub::callNN extended
    to transposed operands)."""
    g_a = _spmd.Geometry.of(mat_a.dist)
    kt = g_a.nt if opa == t.NO_TRANS else g_a.mt
    _check_mult_shapes(opa, opb, mat_a, mat_b, mat_c)
    return _run_summa(mat_a, mat_b, mat_c, opa, opb, alpha, beta, _FULL, t.NON_UNIT, kt)


@origin_transparent
def triangular_multiplication(
    side: str, uplo: str, op: str, diag: str, alpha, mat_a, mat_b
) -> DistributedMatrix:
    """B := alpha op(A) B (Left) or alpha B op(A) (Right), A triangular
    (reference multiplication/triangular.h:48).  Returns new B."""
    structure = _LOWER_TRI if uplo == t.LOWER else _UPPER_TRI
    out = DistributedMatrix(
        mat_b.dist, mat_b.grid, jnp.zeros_like(mat_b.data)
    )
    if side == t.LEFT:
        g_a = _spmd.Geometry.of(mat_a.dist)
        kt = g_a.nt
        return _run_summa(mat_a, mat_b, out, op, t.NO_TRANS, alpha, 0.0, structure, diag, kt)
    # Right: B op(A) — swap roles via (B op(A)) = (op(A)^T B^T)^T; instead use
    # the same SUMMA with A as the B-side row panel: C = alpha B op(A)
    return _run_summa_right(mat_a, mat_b, out, op, alpha, structure, diag)


@origin_transparent
def hermitian_multiplication(
    side: str, uplo: str, alpha, mat_a, mat_b, beta, mat_c
) -> DistributedMatrix:
    """C := alpha A B + beta C with A Hermitian, only ``uplo`` triangle stored
    (reference multiplication/hermitian.h:29; side=R mapped via the
    conj/transpose trick there — here both sides are native)."""
    structure = _HERM_LOWER if uplo == t.LOWER else _HERM_UPPER
    if side == t.LEFT:
        g_a = _spmd.Geometry.of(mat_a.dist)
        return _run_summa(
            mat_a, mat_b, mat_c, t.NO_TRANS, t.NO_TRANS, alpha, beta, structure, t.NON_UNIT, g_a.nt
        )
    return _run_summa_right(mat_a, mat_b, mat_c, t.NO_TRANS, alpha, structure, t.NON_UNIT, beta=beta)


def _summa_right_kernel(a, b, c, g_a, g_b, g_c, opa, alpha, beta, structure, diag, kt):
    """C := alpha B op(A) + beta C — contraction over B cols / op(A) rows.
    Panels: column k of op(B)... i.e. row panel comes from op(A) rows, col
    panel from B columns."""
    a, b, c = coll.local(a), coll.local(b), coll.local(c)
    myr, myc = coll.my_rank()
    c = (jnp.asarray(beta, c.dtype) * c).astype(c.dtype)
    al = jnp.asarray(alpha, c.dtype)

    def body(k, c):
        with _scope("summa.panel_bcast"):
            # col panel: B[:, k] broadcast along 'c'
            kc = k % g_b.pc
            bc = _spmd.take_col(b, k // g_b.pc, g_b)
            cp = coll.bcast(bc, kc, COL_AXIS)
            # row panel: op(A)[k, :] — use the col-panel machinery on the
            # transposed problem: op(A)[k, j] = opT(op(A)^T[j, k])
            rp = _a_row_panel(a, k, g_a, myr, myc, opa, structure, diag, g_c.ltc, g_c.nt)
        with _scope("summa.update"):
            return c + al * t.contract("iab,jbc->ijac", cp, rp)

    c = lax.fori_loop(0, kt, body, c)
    return coll.relocal(c)


def _a_row_panel(a, k, g_a, myr, myc, op, structure, diag, ltc_out, nt_out):
    """Tiles op(A)[k, j] for this rank's local cols j, broadcast to all rank
    rows.  Mirror of _a_col_panel."""
    gj = jnp.arange(ltc_out) * g_a.pc + myc
    if structure in (_HERM_LOWER, _HERM_UPPER):
        lower = structure == _HERM_LOWER
        kr, kc = k % g_a.pr, k % g_a.pc
        ar = _spmd.take_row(a, k // g_a.pr, g_a)
        keep_row = (gj <= k) if lower else (gj >= k)
        ar = jnp.where(keep_row[:, None, None], ar, jnp.zeros_like(ar))
        dmask = (gj == k)[:, None, None]
        ar = jnp.where(dmask, _hermitize_tile(ar, lower), ar)
        rp1 = coll.bcast(ar, kr, ROW_AXIS)
        ac = _spmd.take_col(a, k // g_a.pc, g_a)
        gi = jnp.arange(g_a.ltr) * g_a.pr + myr
        keep_col = (gi > k) if lower else (gi < k)
        ac = jnp.where(keep_col[:, None, None], ac, jnp.zeros_like(ac))
        cp = coll.bcast(ac, kc, COL_AXIS)
        rp2 = t.op_tile(coll.transpose_panel(cp, nt_out, ltc_out), t.CONJ_TRANS)
        return rp1 + rp2
    if op == t.NO_TRANS:
        kr = k % g_a.pr
        ar = _spmd.take_row(a, k // g_a.pr, g_a)
        ar = jnp.swapaxes(
            _structure_mask_col(
                jnp.swapaxes(ar, -1, -2), gj, k, _transpose_structure(structure), diag
            ),
            -1,
            -2,
        )
        return coll.bcast(ar, kr, ROW_AXIS)
    # transposed: op(A)[k, j] = op(A[j, k]): fetch A column k, redistribute
    kc = k % g_a.pc
    ac = _spmd.take_col(a, k // g_a.pc, g_a)
    gi = jnp.arange(g_a.ltr) * g_a.pr + myr
    ac = _structure_mask_col(ac, gi, k, structure, diag)
    cp = coll.bcast(ac, kc, COL_AXIS)
    return t.op_tile(coll.transpose_panel(cp, nt_out, ltc_out), op)


def _run_summa_right(mat_a, mat_b, mat_c, opa, alpha, structure, diag, beta=0.0):
    from dlaf_tpu.tune import blas3_precision

    g_a = _spmd.Geometry.of(mat_a.dist)
    g_b = _spmd.Geometry.of(mat_b.dist)
    g_c = _spmd.Geometry.of(mat_c.dist)
    if g_c.mt == 0 or g_c.nt == 0:
        return mat_c
    if mat_c.grid.grid_size.count() == 1:
        return _run_dense_local(mat_a, mat_b, mat_c, opa, t.NO_TRANS, alpha, beta, structure, diag, True)
    kt = g_b.nt
    def build():
        kern = partial(
            _summa_right_kernel, g_a=g_a, g_b=g_b, g_c=g_c, opa=opa,
            alpha=alpha, beta=beta, structure=structure, diag=diag, kt=kt,
        )
        return coll.spmd(mat_c.grid, kern, donate_argnums=(2,), name="summa_right")

    fn = _plan.cached(
        "summa_right",
        (mat_c.grid.cache_key, opa, complex(alpha), complex(beta), structure,
         diag, kt, g_a, g_b, g_c),
        build,
    )
    with blas3_precision():
        return mat_c._inplace(fn(mat_a.data, mat_b.data, mat_c.data))


def _sub_gemm_kernel(
    a, b, c, g_a, g_b, g_c,
    ai0, ak0, bk0, bj0, ci0, cj0,  # tile origins of the three views
    Ri, Rj, Rk,  # view extents in tiles
    L, Cw,  # static C-window sizes (local row/col slots)
    alpha, beta,
):
    """C[view] := alpha A[view] B[view] + beta C[view], all views tile-index
    ranges into full stacked matrices (reference: GeneralSub::callNN,
    multiplication/general/api.h:28, generalized to independent per-operand
    origins a la MatrixRef).  Tiles outside the C view are untouched.

    Row alignment: when (ai0 - ci0) % pr == 0 the A-panel tiles this rank
    needs are locally owned (taken by index); otherwise the panel is
    all-gathered along 'r' first.  Mirrored for B along 'c'."""
    a, b, c = coll.local(a), coll.local(b), coll.local(c)
    myr, myc = coll.my_rank()
    al = jnp.asarray(alpha, c.dtype)
    pr, pc = g_c.pr, g_c.pc
    aligned_r = (ai0 - ci0) % pr == 0
    aligned_c = (bj0 - cj0) % pc == 0

    # C window: first local row slot with global tile >= ci0 (clipped so the
    # static window fits; out-of-range tiles are masked)
    rs = jnp.clip((ci0 + pr - 1 - myr) // pr, 0, max(g_c.ltr - L, 0))
    cs = jnp.clip((cj0 + pc - 1 - myc) // pc, 0, max(g_c.ltc - Cw, 0))
    gi_w = (rs + jnp.arange(L)) * pr + myr  # global C row tiles in window
    gj_w = (cs + jnp.arange(Cw)) * pc + myc
    rel_i = gi_w - ci0  # row index within the view
    rel_j = gj_w - cj0
    valid_i = (rel_i >= 0) & (rel_i < Ri)
    valid_j = (rel_j >= 0) & (rel_j < Rj)

    def body(k, acc):
        # --- A panel: tiles A[ai0 + rel_i, ak0 + k], broadcast along 'c'
        gka = ak0 + k
        ac = _spmd.take_col(a, gka // pc, g_a)  # [ltr_a, mb, nb]
        ac = coll.bcast(ac, gka % pc, COL_AXIS)
        if aligned_r:
            la = jnp.clip((ai0 + rel_i) // pr, 0, g_a.ltr - 1)
            ap = jnp.take(ac, la, axis=0)
        else:
            # gather only the Lg-slot window covering rows [ai0, ai0+Ri):
            # per-source-rank slot starts are static (ai0, Ri are)
            Lg = min(g_a.ltr, -(-Ri // pr) + 1)
            sA = jnp.asarray(
                [min(max((ai0 + pr - 1 - r) // pr, 0), g_a.ltr - Lg) for r in range(pr)]
            )
            my_s = sA[myr]
            zz = jnp.asarray(0, my_s.dtype)
            acw = lax.dynamic_slice(ac, (my_s, zz, zz), (Lg, g_a.mb, g_a.nb))
            gat = coll.all_gather_axis(acw, ROW_AXIS)  # [pr, Lg, mb, nb]
            flat = gat.reshape(pr * Lg, g_a.mb, g_a.nb)
            gt = ai0 + rel_i
            r_idx = gt % pr
            s_idx = gt // pr - sA[r_idx]
            ap = jnp.take(flat, jnp.clip(r_idx * Lg + s_idx, 0, pr * Lg - 1), axis=0)
        ap = jnp.where(valid_i[:, None, None], ap, jnp.zeros_like(ap))
        # --- B panel: tiles B[bk0 + k, bj0 + rel_j], broadcast along 'r'
        gkb = bk0 + k
        br = _spmd.take_row(b, gkb // pr, g_b)  # [ltc_b, mb, nb]
        br = coll.bcast(br, gkb % pr, ROW_AXIS)
        if aligned_c:
            lb = jnp.clip((bj0 + rel_j) // pc, 0, g_b.ltc - 1)
            bp = jnp.take(br, lb, axis=0)
        else:
            Lg = min(g_b.ltc, -(-Rj // pc) + 1)
            sB = jnp.asarray(
                [min(max((bj0 + pc - 1 - q) // pc, 0), g_b.ltc - Lg) for q in range(pc)]
            )
            my_s = sB[myc]
            zz = jnp.asarray(0, my_s.dtype)
            brw = lax.dynamic_slice(br, (my_s, zz, zz), (Lg, g_b.mb, g_b.nb))
            gat = coll.all_gather_axis(brw, COL_AXIS)  # [pc, Lg, mb, nb]
            flat = gat.reshape(pc * Lg, g_b.mb, g_b.nb)
            gt = bj0 + rel_j
            q_idx = gt % pc
            s_idx = gt // pc - sB[q_idx]
            bp = jnp.take(flat, jnp.clip(q_idx * Lg + s_idx, 0, pc * Lg - 1), axis=0)
        bp = jnp.where(valid_j[:, None, None], bp, jnp.zeros_like(bp))
        with _scope("summa.update"):
            return acc + t.contract("iab,jbc->ijac", ap, bp)

    acc = lax.fori_loop(
        0, Rk, body, jnp.zeros((L, Cw, g_c.mb, g_c.nb), c.dtype)
    )
    zero = jnp.asarray(0, rs.dtype)
    cw = lax.dynamic_slice(c, (rs, cs, zero, zero), (L, Cw, g_c.mb, g_c.nb))
    valid = (valid_i[:, None] & valid_j[None, :])[:, :, None, None]
    cw = jnp.where(valid, jnp.asarray(beta, c.dtype) * cw + al * acc, cw)
    c = lax.dynamic_update_slice(c, cw, (rs, cs, zero, zero))
    return coll.relocal(c)


def general_sub_multiplication(
    alpha, a_ref, b_ref, beta, c_ref
) -> DistributedMatrix:
    """C_view := alpha A_view B_view + beta C_view over tile-aligned
    sub-matrix views; tiles of C outside the view are untouched (reference:
    internal::GeneralSub::callNN, multiplication/general/api.h:28 — there
    one square diagonal tile range; here independent MatrixRef windows,
    matrix/matrix_ref.h:39).  Operands may be DistributedMatrix (whole) or
    MatrixRef.  Returns C's parent with the window updated (functional
    in-place; the parent's buffer is donated)."""
    from dlaf_tpu.matrix.ref import as_ref

    a_ref, b_ref, c_ref = as_ref(a_ref), as_ref(b_ref), as_ref(c_ref)
    mb, nb = c_ref.block_size
    for r in (a_ref, b_ref):
        if tuple(r.block_size) != (mb, nb):
            raise ValueError("general_sub_multiplication: block sizes must match")
    if not (a_ref.grid is c_ref.grid and b_ref.grid is c_ref.grid):
        raise ValueError("general_sub_multiplication: all operands on one grid")
    M, K = a_ref.size
    K2, N = b_ref.size
    if (M, N) != tuple(c_ref.size) or K != K2:
        raise ValueError(
            f"sub-gemm: A {M}x{K} B {K2}x{N} C {tuple(c_ref.size)}"
        )
    mat_a, mat_b, mat_c = a_ref.parent, b_ref.parent, c_ref.parent
    g_a = _spmd.Geometry.of(mat_a.dist)
    g_b = _spmd.Geometry.of(mat_b.dist)
    g_c = _spmd.Geometry.of(mat_c.dist)
    Ri, Rj = c_ref.nr_tiles
    Rk = a_ref.nr_tiles.cols
    if Ri == 0 or Rj == 0:
        return mat_c
    if mat_c.grid.grid_size.count() == 1:
        return _sub_gemm_local(alpha, a_ref, b_ref, beta, c_ref)
    if not (a_ref.aligned and b_ref.aligned and c_ref.aligned):
        # Non-tile-aligned distributed windows (reference: MatrixRef at any
        # element origin, matrix_ref.h:39): realign on device — O(window)
        # ppermute neighbor shifts (matrix/window.py), the SPMD equivalent
        # of the reference's in-tile SubTileSpec offsets — run the aligned
        # kernel, and write the C window back through its parent.
        from dlaf_tpu.matrix.window import window_extract, window_update

        wa = window_extract(mat_a, tuple(a_ref.origin), tuple(a_ref.size))
        wb = window_extract(mat_b, tuple(b_ref.origin), tuple(b_ref.size))
        wc = window_extract(mat_c, tuple(c_ref.origin), tuple(c_ref.size))
        out = general_multiplication(t.NO_TRANS, t.NO_TRANS, alpha, wa, wb, beta, wc)
        return window_update(mat_c, tuple(c_ref.origin), out)
    L = min(g_c.ltr, -(-Ri // g_c.pr))
    Cw = min(g_c.ltc, -(-Rj // g_c.pc))
    origins = (
        a_ref.tile_origin.row, a_ref.tile_origin.col,
        b_ref.tile_origin.row, b_ref.tile_origin.col,
        c_ref.tile_origin.row, c_ref.tile_origin.col,
    )
    # A/B windows may live in C's parent (the canonical MatrixRef use:
    # updating one window of a matrix from another) — donating C's buffer
    # would then alias a live operand, so compile a non-donating variant
    aliased = (mat_a.data is mat_c.data) or (mat_b.data is mat_c.data)
    from dlaf_tpu.tune import blas3_precision

    def build():
        kern = partial(
            _sub_gemm_kernel, g_a=g_a, g_b=g_b, g_c=g_c,
            ai0=origins[0], ak0=origins[1], bk0=origins[2], bj0=origins[3],
            ci0=origins[4], cj0=origins[5], Ri=Ri, Rj=Rj, Rk=Rk, L=L, Cw=Cw,
            alpha=alpha, beta=beta,
        )
        return coll.spmd(
            mat_c.grid, kern, donate_argnums=() if aliased else (2,), name="sub_gemm"
        )

    fn = _plan.cached(
        "sub_gemm",
        (mat_c.grid.cache_key, complex(alpha), complex(beta), origins,
         Ri, Rj, Rk, g_a, g_b, g_c, aliased),
        build,
    )
    with blas3_precision():
        return mat_c._inplace(fn(mat_a.data, mat_b.data, mat_c.data))


def _sub_gemm_local(alpha, a_ref, b_ref, beta, c_ref):
    """1x1-grid fast path: slice the three global windows, one dense GEMM."""
    import jax

    from dlaf_tpu.tune import blas3_precision

    da, db, dc = a_ref.parent.dist, b_ref.parent.dist, c_ref.parent.dist
    oa, ob, oc = tuple(a_ref.origin), tuple(b_ref.origin), tuple(c_ref.origin)
    sa, sb, sc = tuple(a_ref.size), tuple(b_ref.size), tuple(c_ref.size)
    def build():
        from dlaf_tpu.matrix import layout

        def run(xa, xb, xc):
            ga = layout.unpad_global(layout.unpack(xa, da), da)
            gb = layout.unpad_global(layout.unpack(xb, db), db)
            gc = layout.unpad_global(layout.unpack(xc, dc), dc)
            aw = ga[oa[0] : oa[0] + sa[0], oa[1] : oa[1] + sa[1]]
            bw = gb[ob[0] : ob[0] + sb[0], ob[1] : ob[1] + sb[1]]
            cw = gc[oc[0] : oc[0] + sc[0], oc[1] : oc[1] + sc[1]]
            new = jnp.asarray(alpha, gc.dtype) * t.contract(
                "...ab,...bc->...ac", aw, bw
            ) + jnp.asarray(beta, gc.dtype) * cw
            gc = lax.dynamic_update_slice(gc, new.astype(gc.dtype), oc)
            return layout.pack(layout.pad_global(gc, dc), dc)

        return _plan.jit("sub_gemm_local", run)

    fn = _plan.cached(
        "sub_gemm_local",
        (da, db, dc, oa, ob, oc, sa, sb, sc, np.dtype(c_ref.dtype),
         complex(alpha), complex(beta)),
        build,
    )
    with blas3_precision():
        return c_ref.parent._inplace(
            fn(a_ref.parent.data, b_ref.parent.data, c_ref.parent.data)
        )


def _check_mult_shapes(opa, opb, mat_a, mat_b, mat_c):
    am, an = mat_a.size
    if opa != t.NO_TRANS:
        am, an = an, am
    bm, bn = mat_b.size
    if opb != t.NO_TRANS:
        bm, bn = bn, bm
    if (am, bn) != tuple(mat_c.size) or an != bm:
        raise ValueError(
            f"gemm: op(A) {am}x{an} op(B) {bm}x{bn} C {tuple(mat_c.size)}"
        )
