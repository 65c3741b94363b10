"""Generalized-to-standard eigenproblem reduction (HEGST type 1).

TPU-native analogue of the reference gen_to_std
(reference: include/dlaf/eigensolver/gen_to_std.h:50-101 and
eigensolver/gen_to_std/impl.h, 769 lines of tiled hegst/trsm/hemm/her2k).
Given B = L L^H (factor in ``mat_b``), transforms A of A x = lambda B x into
the standard form  A_std := L^-1 A L^-H.

Two backends (``tune.gen_to_std_backend``):

- ``composed`` (default, MEASURED faster): hermitize(A) then two full
  triangular solves A_std = L^-1 (A L^-H) — 2 N^3 nominal, but each trsm
  is one einsum-sweep whose windows over-approximate in ONE dimension
  only.  1.16 s at N=2048 f32 on the 8-device mesh.
- ``fused``: the LAPACK/reference hegst tile recursion with the
  per-panel trailing triangular solve DEFERRED.  Phase A is one SPMD
  fori_loop over tile panels doing the symmetric-aware updates only —
  diag hegst, panel right-trsm with the diag L tile, the two 1/2-hemm
  corrections, and the her2k trailing update on a bucketed window.  The
  reference applies ``inv(L_trail)`` to each panel inside the loop
  (impl.h / LAPACK zhegst step 5); because L is lower triangular,
  ``inv(L(k+1:, k+1:)) P = inv(L) P`` for any panel P supported strictly
  below its diagonal block, so ALL those solves commute into ONE full
  left-trsm on the strictly-lower-tile part afterwards (phase B).
  ~1.67 N^3 true flops, but the her2k windows over-approximate in BOTH
  grid dimensions (up to 4x) under the halving buckets and each step
  carries two extra panel transposes — measured 1.75 s at the same
  config, hence not the default.  Kept as the candidate for meshes where
  collectives (not flops) dominate.

Full Hermitian storage in, full Hermitian storage out (superset of the
reference's single-triangle result).
"""
from __future__ import annotations

from dlaf_tpu.algorithms._origin import origin_transparent

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dlaf_tpu.algorithms import _spmd
from dlaf_tpu.algorithms.triangular_solver import triangular_solver
from dlaf_tpu.comm import collectives as coll
from dlaf_tpu.comm.grid import COL_AXIS, ROW_AXIS
from dlaf_tpu.matrix import util as mutil
from dlaf_tpu.matrix.matrix import DistributedMatrix
from dlaf_tpu.obs.trace import scope as _scope
from dlaf_tpu.ops import tile as t
from dlaf_tpu.plan import core as _plan


def _hegst_phase_a_kernel(a, b, g: _spmd.Geometry):
    """Phase A of the fused hegst (lower): per tile panel k —

      akk := inv(lkk) akk inv(lkk)^H            (diag, redundant everywhere)
      P   := A[i>k, k] inv(lkk)^H               (panel right-trsm)
      P   -= 1/2 L[i>k, k] akk                  (first hemm correction)
      A[i>k, j>k] -= L_p P^H + P L_p^H          (her2k, bucketed window)
      P   -= 1/2 L[i>k, k] akk                  (second hemm correction)

    exactly LAPACK zhegst itype=1 lower with the trailing trsm deferred
    (see module docstring).  ``a`` holds FULL Hermitian storage, so the
    her2k updates both triangles (Hermitian-preserving)."""
    a = coll.local(a)
    b = coll.local(b)
    myr, myc = coll.my_rank()
    b = _spmd.pad_diag_identity(b, g, myr, myc)  # padded L tiles stay non-singular
    half = 0.5
    fused_tier = _spmd.trailing_update_trace_key() == "fused"

    def step(k, a, L, C):
        kr, kc = k % g.pr, k % g.pc
        lkr, lkc = k // g.pr, k // g.pc
        with _scope("hegst.diag"):
            lkk = _spmd.bcast_diag_tile(b, k, g, myr, myc)
            akk = _spmd.bcast_diag_tile(a, k, g, myr, myc)
            akk = t.trsm(t.LEFT, t.LOWER, t.NO_TRANS, t.NON_UNIT, 1.0, lkk, akk)
            akk = t.trsm(t.RIGHT, t.LOWER, t.CONJ_TRANS, t.NON_UNIT, 1.0, lkk, akk)
        # window of remaining rows (first slot with gi >= k+1)
        rs = jnp.clip((k + g.pr - myr) // g.pr, 0, max(g.ltr - L, 0)).astype(lkr.dtype)
        cs = jnp.clip((k + g.pc - myc) // g.pc, 0, max(g.ltc - C, 0)).astype(lkr.dtype)
        gi_w = (rs + jnp.arange(L)) * g.pr + myr
        jv = (cs + jnp.arange(C)) * g.pc + myc
        below = (gi_w > k)[:, None, None]
        with _scope("hegst.panel"):
            xa = lax.dynamic_slice(a, (rs, lkc, 0, 0), (L, 1, g.mb, g.mb))[:, 0]
            xl = lax.dynamic_slice(b, (rs, lkc, 0, 0), (L, 1, g.mb, g.mb))[:, 0]
            pan = t.trsm(t.RIGHT, t.LOWER, t.CONJ_TRANS, t.NON_UNIT, 1.0, lkk, xa)
            corr = jnp.asarray(half, a.dtype) * t.contract("iab,bc->iac", xl, akk)
            pan1 = pan - corr  # the value her2k uses
            mine_c = myc == kc
            cp_a = coll.bcast(
                jnp.where(below, pan1, jnp.zeros_like(pan1)), kc, COL_AXIS,
                consumed=fused_tier,
            )
            cp_l = coll.bcast(
                jnp.where(below, xl, jnp.zeros_like(xl)), kc, COL_AXIS,
                consumed=fused_tier,
            )
            if fused_tier:
                taken_a, have_a = coll.transpose_panel_windowed_parts(
                    cp_a, jv, rs, g.mt
                )
                taken_l, have_l = coll.transpose_panel_windowed_parts(
                    cp_l, jv, rs, g.mt
                )
            else:
                rp_a = coll.transpose_panel_windowed(cp_a, jv, rs, g.mt)
                rp_l = coll.transpose_panel_windowed(cp_l, jv, rs, g.mt)
        # write back the twice-corrected panel and the transformed diag tile
        pan2 = pan1 - corr
        new_col = jnp.where(below & mine_c, pan2, xa)
        a = lax.dynamic_update_slice(a, new_col[:, None], (rs, lkc, 0, 0))
        mine_d = (myr == kr) & mine_c
        dtile = jnp.where(mine_d, akk, a[lkr, lkc])[None, None]
        a = lax.dynamic_update_slice(a, dtile.astype(a.dtype), (lkr, lkc, 0, 0))
        # her2k on the trailing window: A -= L_p P^H + P L_p^H
        with _scope("hegst.her2k"):
            xs = lax.dynamic_slice(a, (rs, cs, 0, 0), (L, C, g.mb, g.mb))
            if fused_tier:
                from dlaf_tpu.ops import pallas_trailing_update as ptu

                # two consume rings, one per addend.  Slots at or left of
                # panel k are suppressed: under the xla tier they carry
                # exactly-zero exchanged panels (the below-mask zeroed
                # them at the bcast), and subtracting an exactly-zero
                # contraction is bitwise identity, so parity holds.
                suppress = jv <= k
                xs, _ = ptu.fused_transpose_update(
                    xs, cp_l, taken_a, have_a, suppress, ROW_AXIS
                )
                xs, _ = ptu.fused_transpose_update(
                    xs, cp_a, taken_l, have_l, suppress, ROW_AXIS
                )
            else:
                xs = xs - t.contract("iab,jcb->ijac", cp_l, rp_a.conj())
                xs = xs - t.contract("iab,jcb->ijac", cp_a, rp_l.conj())
            return lax.dynamic_update_slice(a, xs, (rs, cs, 0, 0))

    for k0, k1 in _spmd.halving_segments(g.mt):
        L = min(g.ltr, (g.mt - 1 - k0 + g.pr - 1) // g.pr + 1)
        C = min(g.ltc, (g.mt - 1 - k0 + g.pc - 1) // g.pc + 1)
        L, C = max(L, 1), max(C, 1)
        a = lax.fori_loop(k0, k1, partial(step, L=L, C=C), a)

    return coll.relocal(a)


def _tile_mask(mat: DistributedMatrix, rel: str) -> DistributedMatrix:
    """Keep only tiles with row-tile ``rel`` col-tile ('lt' = strictly
    lower, 'diag' = diagonal); zero the rest."""
    def build():
        d = mat.dist

        def run(x):
            gi, gj = mutil._global_element_grids(d)
            ti, tj = gi // d.block_size.rows, gj // d.block_size.cols
            keep = (ti > tj) if rel == "lt" else (ti == tj)
            return jnp.where(keep, x, jnp.zeros_like(x))

        return _plan.jit("hegst_tmask", run)

    fn = _plan.cached("hegst_tmask", (rel, mat.dist, np.dtype(mat.dtype)), build)
    return mat.like(fn(mat.data))


def _gen_to_std_fused(mat_a_full: DistributedMatrix, mat_b_l: DistributedMatrix):
    """Fused hegst, lower-factor form (A full Hermitian storage, L lower)."""
    from dlaf_tpu.tune import blas3_precision

    g = _spmd.Geometry.of(mat_a_full.dist)
    g_b = _spmd.Geometry.of(mat_b_l.dist)
    if g.mt == 0:
        return mat_a_full
    if (g.mb, g.pr, g.pc, g.mt) != (g_b.mb, g_b.pr, g_b.pc, g_b.mt):
        raise ValueError("gen_to_std: A and B distributions must match")

    def build():
        return coll.spmd(
            mat_a_full.grid,
            partial(_hegst_phase_a_kernel, g=g),
            donate_argnums=(0,),
            name="hegst_phase_a",
        )

    fn = _plan.cached("hegst_phase_a", (mat_a_full.grid.cache_key, g), build)
    with blas3_precision():
        ph_a = mat_a_full._inplace(fn(mat_a_full.data, mat_b_l.data))
        # phase B: the deferred per-panel inv(L_trail) solves = one full
        # left-trsm on the strictly-lower-tile part (supported below each
        # diagonal block, so inv(L) acts as the per-panel inv(L_trail))
        w = _tile_mask(ph_a, "lt")
        x = triangular_solver(
            t.LEFT, t.LOWER, t.NO_TRANS, t.NON_UNIT, 1.0, mat_b_l, w
        )
        lower = x.like(x.data + _tile_mask(ph_a, "diag").data)
    return mutil.hermitize(lower, "L")


@origin_transparent
def generalized_to_standard(
    uplo: str, mat_a: DistributedMatrix, mat_b: DistributedMatrix
) -> DistributedMatrix:
    """A := inv(fac) A inv(fac)^H with fac = L (uplo=L, B = L L^H) or
    fac = U^H ... (uplo=U, B = U^H U: A := U^-H A U^-1).

    ``mat_a``: Hermitian, ``uplo`` triangle valid.  ``mat_b``: Cholesky
    factor in the ``uplo`` triangle.  Returns A_std with FULL Hermitian
    storage (superset of the reference's single-triangle result).
    """
    from dlaf_tpu.tune import get_tune_parameters

    backend = get_tune_parameters().gen_to_std_backend
    a_full = mutil.hermitize(mat_a, uplo)
    if backend == "fused" and mat_a.grid.grid_size.count() > 1:
        # U case: B = U^H U with fac U given; with L := U^H (one conj
        # transpose) the transform is the same L^-1 A L^-H
        b_l = mat_b if uplo == t.LOWER else mutil.transpose(
            mutil.extract_triangle(mat_b, "U"), conj=True
        )
        return _gen_to_std_fused(a_full, b_l)
    if uplo == t.LOWER:
        a1 = triangular_solver(t.LEFT, t.LOWER, t.NO_TRANS, t.NON_UNIT, 1.0, mat_b, a_full)
        return triangular_solver(t.RIGHT, t.LOWER, t.CONJ_TRANS, t.NON_UNIT, 1.0, mat_b, a1)
    a1 = triangular_solver(t.LEFT, t.UPPER, t.CONJ_TRANS, t.NON_UNIT, 1.0, mat_b, a_full)
    return triangular_solver(t.RIGHT, t.UPPER, t.NO_TRANS, t.NON_UNIT, 1.0, mat_b, a1)
