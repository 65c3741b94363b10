"""Reduction of a Hermitian matrix to band form (band <= tile size).

TPU-native re-design of the reference reduction_to_band
(reference: include/dlaf/eigensolver/reduction_to_band.h:51-120 and
eigensolver/reduction_to_band/impl.h, ~2100 lines).  The reference runs a
cooperative multi-threaded panel factorization, computeTFactor, then W/X
two-sided updates with p2p reductions.  Here, per panel k (one jitted
fori_loop-free outer Python loop is avoided — everything is ONE jitted SPMD
fori_loop over panels):

  1. the band-wide panel strip (cols [p*band, (p+1)*band), rows below
     (p+1)*band — generally NOT tile-aligned) is all-gathered along 'r' and
     broadcast along 'c' so EVERY rank holds the full N x band panel; the
     band Householder reflectors are then computed redundantly everywhere
     (O(N band^2) flops, vectorized over rows — replaces the reference's
     nworkers+barriers panel tasks, impl.h:578-700),
  2. the compact-WY T factor is the nb x nb triangular inverse
     T = inv(diag(1/tau) + striu(V^H V)) (replaces computeTFactor,
     factorization/qr/t_factor_impl.h),
  3. the two-sided trailing update A := Q^H A Q with Q = I - V T V^H is
     computed as X = A V T (one local einsum + psum over 'c'),
     M = V^H X (psum over 'r'), W2 = X - 1/2 V T^H M, then the rank-2b
     update A -= W2 V^H + V W2^H as two batched einsums (replaces
     hemmComputeX / her2k trailing update, impl.h:453-576).

Householder convention matches LAPACK geqrf: H_j = I - tau_j v_j v_j^H,
reflectors applied as H^H from the left to produce R; zero-norm columns get
tau = 0 and v = 0 (NOT v = e1) so the T-factor inverse stays well defined.

On return, the matrix holds (like the reference): band in the diagonal +
first sub-diagonal tile (R triangles), Householder vector tails below, and
the function also returns taus[k, j] per panel.  Only the lower triangle is
meaningful afterwards.
"""
from __future__ import annotations

from dlaf_tpu.algorithms._origin import origin_transparent

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from dlaf_tpu.algorithms import _spmd
from dlaf_tpu.comm import collectives as coll
from dlaf_tpu.comm.grid import COL_AXIS, ROW_AXIS
from dlaf_tpu.matrix import util as mutil
from dlaf_tpu.matrix.matrix import DistributedMatrix
from dlaf_tpu.obs.trace import scope as _scope
from dlaf_tpu.ops import tile as t
from dlaf_tpu.plan import core as _plan


def _panel_block_size(nb: int) -> int:
    """Largest divisor of nb not above 32 — the inner sub-panel width.
    Bands whose divisors <= 32 are all tiny (e.g. primes > 32) fall back to
    one full-width block: unrolling nb/bs sub-panels each with its own
    T factor would cost more than the single sequential loop."""
    bs = min(32, nb)
    while nb % bs:
        bs -= 1
    return bs if bs >= 8 or bs == nb else nb


def _hh_panel(p, start_row, nb: int, np_: int, m: int):
    """Householder QR of the gathered panel ``p[np_, nb]``; active rows are
    ``start_row + j`` and below for column j, rows >= m are padding.

    Blocked (reference: the recursive larft idea of
    factorization/qr/t_factor_impl.h): the sequential rank-1 loop only ever
    touches a bs<=32-wide sub-panel; each completed sub-panel is applied to
    the remaining panel columns as ONE compact-WY GEMM update
    ``P -= V (T^H (V^H P))``, so the bandwidth-bound sequential work drops
    from O(np_*nb) to O(np_*bs) per step and the aggregation rides the MXU.

    Returns (p_out, v, taus): p_out has R on/above the reflector diagonal and
    v tails below (LAPACK layout); v[np_, nb] is the explicit V with unit
    heads; taus[nb]."""
    rows = jnp.arange(np_)
    rdtype = jnp.zeros((), p.dtype).real.dtype
    bs = _panel_block_size(nb)

    def col_body(jj, carry, j0):
        sp, v, taus = carry  # sp: [np_, bs] current sub-panel
        s = start_row + j0 + jj
        x = sp[:, jj]
        tail = (rows > s) & (rows < m)
        alpha = jnp.sum(jnp.where(rows == s, x, 0))
        tail_sq = jnp.sum(jnp.where(tail, jnp.abs(x) ** 2, 0)).astype(rdtype)
        norm = jnp.sqrt(jnp.abs(alpha) ** 2 + tail_sq)
        nonzero = norm > 0
        sign = jnp.where(alpha.real >= 0, 1.0, -1.0).astype(rdtype)
        beta = (-sign * norm).astype(p.dtype)  # real
        tau = jnp.where(nonzero, (beta - alpha) / beta, 0).astype(p.dtype)
        denom = jnp.where(nonzero, alpha - beta, 1).astype(p.dtype)
        vj = jnp.where(tail, x / denom, 0) + jnp.where(
            (rows == s) & nonzero, 1.0, 0.0
        ).astype(p.dtype)
        # apply H_j^H to the remaining sub-panel columns:
        # SP -= conj(tau) v (v^H SP)
        w = jnp.einsum("i,ik->k", vj.conj(), sp)
        colmask = jnp.arange(bs) > jj
        sp = sp - jnp.conj(tau) * jnp.einsum("i,k->ik", vj, jnp.where(colmask, w, 0))
        # store the factored column: R above, beta at s, v tail below
        newcol = jnp.where(rows == s, beta, jnp.where(tail, vj, x))
        sp = jnp.where((jnp.arange(bs) == jj)[None, :], newcol[:, None], sp)
        v = v.at[:, jj].set(vj)
        taus = taus.at[jj].set(tau)
        return sp, v, taus

    v_parts, tau_parts = [], []
    for j0 in range(0, nb, bs):
        sp = lax.slice_in_dim(p, j0, j0 + bs, axis=1)
        v0 = jnp.zeros((np_, bs), p.dtype)
        t0 = jnp.zeros((bs,), p.dtype)
        sp, v_sub, taus_sub = lax.fori_loop(
            0, bs, partial(col_body, j0=j0), (sp, v0, t0)
        )
        p = lax.dynamic_update_slice(p, sp, (0, j0))
        if j0 + bs < nb:
            # aggregated block apply of Q_sub^H = I - V T^H V^H to the
            # not-yet-factored panel columns
            tsub = _t_factor(v_sub, taus_sub, bs)
            trail = lax.slice_in_dim(p, j0 + bs, nb, axis=1)
            w = jnp.einsum("ia,ik->ak", v_sub.conj(), trail)
            trail = trail - jnp.einsum("ia,ba,bk->ik", v_sub, tsub.conj(), w)
            p = lax.dynamic_update_slice(p, trail, (0, j0 + bs))
        v_parts.append(v_sub)
        tau_parts.append(taus_sub)
    v = v_parts[0] if len(v_parts) == 1 else jnp.concatenate(v_parts, axis=1)
    taus = tau_parts[0] if len(tau_parts) == 1 else jnp.concatenate(tau_parts)
    return p, v, taus


def _t_factor(v, taus, nb: int):
    """T = inv(diag(1/tau) + striu(V^H V)); zero-tau columns yield zero
    columns (v is zero there)."""
    s = jnp.triu(jnp.einsum("ia,ib->ab", v.conj(), v), 1)
    dinv = jnp.where(taus == 0, 1.0, 1.0 / jnp.where(taus == 0, 1.0, taus))
    m = s + jnp.diag(dinv)
    tmat = lax.linalg.triangular_solve(
        m, jnp.eye(nb, dtype=v.dtype), left_side=True, lower=False
    )
    return jnp.where((taus == 0)[None, :], 0, tmat)


def _red2band_step(p, carry, g: _spmd.Geometry, band: int, myr, myc, L: int, C: int):
    """One band-panel step (gather -> Householder panel -> T factor ->
    two-sided trailing update on an L x C window -> write-back) on the
    shard_map-local tile stack.  Shared by the bucketed full-loop kernel
    (shrinking windows per segment) and the checkpointing range kernel
    (full windows — V is zero outside the trailing region, so the wider
    window is value-exact).  carry = (x, taus_all)."""
    np_ = g.ltr * g.pr * g.mb  # padded global rows
    mt_pad = np_ // g.mb
    x, taus_all = carry
    pb = p * band  # first panel column (global element)
    kt = pb // g.nb  # tile column holding the panel
    co = pb % g.nb  # column offset inside that tile
    kc = kt % g.pc
    lkc = kt // g.pc
    # 1. gather the band-wide panel strip to every rank (O(N band) data)
    with _scope("red2band.panel_gather"):
        xc = _spmd.take_col(x, lkc, g)  # [ltr, mb, nb]
        xcb = lax.dynamic_slice(xc, (0, 0, co), (g.ltr, g.mb, band))
        gat = coll.all_gather_axis(xcb, ROW_AXIS)  # [pr, ltr, mb, band]
        col_tiles = jnp.transpose(gat, (1, 0, 2, 3)).reshape(mt_pad, g.mb, band)
        col_tiles = coll.bcast(col_tiles, kc, COL_AXIS)
        pnl = col_tiles.reshape(np_, band)
    start = (p + 1) * band  # first eliminated row
    with _scope("red2band.hh_panel"):
        p_out, v, taus = _hh_panel(pnl, start, band, np_, g.m)
        taus_all = lax.dynamic_update_slice(taus_all, taus[None, :], (p, 0))
    # 2. T factor (replicated)
    with _scope("red2band.t_factor"):
        tmat = _t_factor(v, taus, band)
    # 3. two-sided trailing update on the bucketed window (static L x C):
    # V is zero outside the trailing region, so clamped window overlap
    # contributes nothing — same safety argument as cholesky bucketing
    v_tiles = v.reshape(mt_pad, g.mb, band)
    t0 = start // g.mb  # first tile row/col with reflector data
    rs = jnp.clip((t0 + g.pr - 1 - myr) // g.pr, 0, max(g.ltr - L, 0)).astype(
        jnp.asarray(p).dtype
    )
    cs = jnp.clip((t0 + g.pc - 1 - myc) // g.pc, 0, max(g.ltc - C, 0)).astype(
        jnp.asarray(p).dtype
    )
    gi_w = (rs + jnp.arange(L)) * g.pr + myr
    gj_w = (cs + jnp.arange(C)) * g.pc + myc
    vr = jnp.take(v_tiles, gi_w, axis=0)  # [L, mb, band] (gi_w < mt_pad)
    valid_c = (gj_w < mt_pad)[:, None, None]
    vc = jnp.where(
        valid_c, jnp.take(v_tiles, jnp.clip(gj_w, 0, mt_pad - 1), axis=0), 0
    )  # [C, mb, band]
    with _scope("red2band.trailing_update"):
        xs = lax.dynamic_slice(x, (rs, cs, 0, 0), (L, C, g.mb, g.mb))
        xpart = t.contract("ijab,jbc->iac", xs, vc)
        xfull = coll.psum_axis(xpart, COL_AXIS)  # (A V) window rows
        xt = t.contract("iab,bc->iac", xfull, tmat)  # X = A V T
        mpart = t.contract("iab,iac->bc", vr.conj(), xt)
        mmat = coll.psum_axis(mpart, ROW_AXIS)  # M = V^H X
        w2 = xt - 0.5 * t.contract("iab,bc->iac", vr, tmat.conj().T @ mmat)
        # mask W2 to the trailing region (element rows >= start)
        ge = gi_w[:, None] * g.mb + jnp.arange(g.mb)[None, :]
        w2 = jnp.where((ge >= start)[:, :, None], w2, 0)
        if _spmd.trailing_update_trace_key() == "fused":
            from dlaf_tpu.ops import pallas_trailing_update as ptu

            # first addend: both operands local — one-shot in-VMEM kernel
            # (same jaxpr as the xla einsum; xla associates xs - c1 - c2 as
            # ((xs - c1) - c2), which sequential application reproduces)
            if ptu.update_kernel_ok(xs.dtype):
                xs = ptu.trailing_update(xs, w2, vc.conj())
            else:
                xs = xs - t.contract("iab,jcb->ijac", w2, vc.conj())
            # second addend: W2 crosses the diagonal — consume it out of
            # the ring landing slots (no suppressed slots here: every
            # window column takes its full contribution, matching xla)
            taken, have = coll.transpose_panel_windowed_parts(
                w2, gj_w, rs, g.mt
            )
            xs, _ = ptu.fused_transpose_update(
                xs, vr, taken, have, jnp.zeros_like(have), ROW_AXIS
            )
        else:
            w2c = coll.transpose_panel_windowed(w2, gj_w, rs, g.mt)
            xs = (
                xs
                - t.contract("iab,jcb->ijac", w2, vc.conj())
                - t.contract("iab,jcb->ijac", vr, w2c.conj())
            )
        x = lax.dynamic_update_slice(x, xs, (rs, cs, 0, 0))
    # 4. write the factored panel strip back (element rows >= start on
    # the owning tile column; start is generally NOT tile-aligned)
    p_tiles = p_out.reshape(mt_pad, g.mb, band)
    gi = _spmd.local_row_tiles(g, myr)
    newcol_b = jnp.take(p_tiles, gi, axis=0)  # [ltr, mb, band]
    ge_rows = gi[:, None] * g.mb + jnp.arange(g.mb)[None, :]
    write = (ge_rows >= start)[:, :, None] & (myc == kc)
    xc_now = _spmd.take_col(x, lkc, g)
    cur_b = lax.dynamic_slice(xc_now, (0, 0, co), (g.ltr, g.mb, band))
    new_b = jnp.where(write, newcol_b, cur_b)
    xc_new = lax.dynamic_update_slice(xc_now, new_b, (0, 0, co))
    x = _spmd.put_col(x, xc_new, lkc)
    return x, taus_all


def _red2band_kernel(x, g: _spmd.Geometry, n_panels: int, band: int):
    x = coll.local(x)
    myr, myc = coll.my_rank()
    taus_all = jnp.zeros((n_panels, band), x.dtype)

    carry = (x, taus_all)
    for p0, p1 in _spmd.halving_segments(n_panels):
        t0 = (p0 + 1) * band // g.mb
        L = max(min(g.ltr, (g.mt - 1 - t0 + g.pr - 1) // g.pr + 1), 1)
        C = max(min(g.ltc, (g.mt - 1 - t0 + g.pc - 1) // g.pc + 1), 1)
        body = partial(_red2band_step, g=g, band=band, myr=myr, myc=myc, L=L, C=C)
        carry = lax.fori_loop(p0, p1, body, carry)
    x, taus_all = carry
    return coll.relocal(x), coll.relocal(taus_all)


def _red2band_range_kernel(x, taus_all, p0, p1, g: _spmd.Geometry, band: int):
    """Checkpoint-segment kernel: band panels ``p0 <= p < p1`` with traced
    bounds, full L x C windows (L=ltr, C=ltc — V is zero outside the
    trailing region, so the wide window is value-exact), taus carried
    REPLICATED (every rank computes the panel QR redundantly from the
    broadcast strip, so the stack is identical everywhere and round-trips
    through checkpoints as a host array).  One compiled executable serves
    every segment and every resumed continuation — resumed and
    uninterrupted runs of the same cadence are bit-identical."""
    x = coll.local(x)
    myr, myc = coll.my_rank()
    body = partial(
        _red2band_step, g=g, band=band, myr=myr, myc=myc, L=g.ltr, C=g.ltc
    )
    # default-int bounds: the loop index feeds slice helpers that mix it
    # with python-int literals (same cast as cholesky._chol_L_range_kernel)
    idt = jnp.asarray(0).dtype
    x, taus_all = lax.fori_loop(p0.astype(idt), p1.astype(idt), body, (x, taus_all))
    return coll.relocal(x), taus_all


def _compiled_range(grid, g: _spmd.Geometry, band: int, prec: str):
    """Compiled checkpoint-segment executable:
    ``(x, taus_all, p0, p1) -> (x, taus_all)`` with traced panel bounds and
    a replicated taus carry.  Built on ``jax.shard_map`` directly — the
    scalar bounds and the replicated taus stack need ``P()`` in_specs that
    :func:`coll.spmd`'s uniform stacked specs cannot express."""
    def build():
        P = jax.sharding.PartitionSpec
        spec = P(ROW_AXIS, COL_AXIS)
        sm = jax.shard_map(
            partial(_red2band_range_kernel, g=g, band=band),
            mesh=grid.mesh,
            in_specs=(spec, P(), P(), P()),
            out_specs=(spec, P()),
            check_vma=False,
        )
        return _plan.jit("red2band_range", sm, donate_argnums=(0,))

    return _plan.cached("red2band_range", (grid.cache_key, g, band, prec), build)


def _reduce_checkpointed(full, g: _spmd.Geometry, band: int, n_panels: int,
                         checkpoint_every: int, checkpoint_path, resume_from,
                         prec: str):
    """Segmented band reduction mirroring cholesky._factor_checkpointed:
    ``checkpoint_every`` panels per range-kernel call, a
    ``resilience.panel_boundary`` before each segment, a checkpoint
    (matrix + taus stack + panel index + band) after each completed one.
    ``full`` is the hermitized working copy and is repointed every segment.
    Returns ``(data, taus_all)``."""
    import numpy as np

    from dlaf_tpu import resilience
    from dlaf_tpu.health import DistributionError
    from dlaf_tpu.tune import matmul_precision

    kern = _compiled_range(full.grid, g, band, prec)
    step = int(checkpoint_every) if checkpoint_every else n_panels
    p = 0
    taus = jnp.zeros((n_panels, band), full.dtype)
    if resume_from is not None:
        data, attrs, extras = resilience.load_checkpoint(
            resume_from, full, algo="reduction_to_band", extras=("taus", "band")
        )
        if int(extras["band"]) != band:
            raise DistributionError(
                f"{resume_from}: checkpoint band {int(extras['band'])} != "
                f"requested band {band}"
            )
        full._inplace(data)
        p = int(attrs.get("panel", 0))
        taus = jnp.asarray(extras["taus"].astype(np.dtype(full.dtype)))
    while p < n_panels:
        p1 = min(p + step, n_panels)
        resilience.panel_boundary("reduction_to_band", p, full.data)
        with matmul_precision(prec):
            data, taus = kern(full.data, taus, np.int32(p), np.int32(p1))
        full._inplace(data)
        p = p1
        if checkpoint_path is not None and p < n_panels:
            resilience.save_checkpoint(
                checkpoint_path, full, algo="reduction_to_band", panel=p,
                extras={"taus": np.asarray(taus), "band": np.asarray(band)},
            )
    return full.data, taus


def get_band_size(nb: int) -> int:
    """Band size used by the eigensolver: the smallest divisor of nb not
    below ``eigensolver_min_band`` — nb itself when nb is already small
    (reference: eigensolver/internal/get_band_size.h:20).  A band smaller
    than the tile decouples the O(N^2 b) host bulge-chasing cost from the
    MXU-shaped tile size.

    ``eigensolver_min_band`` -1 (the default) = auto: 33 (band 64 at
    nb=256) on CPU backends — HEEV 1.12-1.13x over band 128 at N=2048/4096
    on the 8-device mesh (the serial chase is O(N^2 b); band 32 loses it
    back in bt_band) — and the reference's 100 (band 128) on accelerators,
    where the SBR second stage absorbs the chase cost."""
    from dlaf_tpu.tune import get_tune_parameters

    b_min = int(get_tune_parameters().eigensolver_min_band)
    if b_min < 0:
        b_min = 33 if jax.default_backend() == "cpu" else 100
    b_min = max(2, b_min)
    for div in range(nb // b_min, 1, -1):
        if nb % div == 0:
            return nb // div
    return nb


@origin_transparent
def reduction_to_band(
    mat_a: DistributedMatrix,
    band: int | None = None,
    checkpoint_every: int = 0,
    checkpoint_path: str | None = None,
    resume_from: str | None = None,
) -> Tuple[DistributedMatrix, jax.Array]:
    """Reduce Hermitian ``mat_a`` (``uplo='L'`` storage) to band form with
    band size ``band`` (default: tile size; must divide the tile size —
    reference get_band_size.h).  Returns (matrix holding band + reflector
    tails in the lower triangle, taus[n_panels, band]); the band size is
    recoverable as ``taus.shape[1]``.

    Preemption safety (``dlaf_tpu.resilience``, same contract as
    ``cholesky_factorization``): ``checkpoint_every=k`` segments the panel
    loop and checkpoints matrix + taus stack + panel index to
    ``checkpoint_path`` after each completed segment (collective atomic
    rank-0 HDF5 write); ``resume_from=`` restores and re-enters at the
    stored panel, bit-identical to an uninterrupted run of the same
    cadence.  Segment boundaries enforce ambient ``resilience.deadline``
    budgets and host fault injection."""
    if mat_a.size.rows != mat_a.size.cols or mat_a.block_size.rows != mat_a.block_size.cols:
        raise ValueError("reduction_to_band: square matrix with square tiles required")
    g = _spmd.Geometry.of(mat_a.dist)
    if band is None:
        band = g.nb
    if band < 1 or g.nb % band:
        raise ValueError(f"reduction_to_band: band {band} must divide the tile size {g.nb}")
    n_panels = max(0, (g.m - 1) // band)
    full = mutil.hermitize(mat_a, "L")
    if n_panels == 0:
        return full, jnp.zeros((0, band), mat_a.dtype)
    from dlaf_tpu.tune import get_tune_parameters, matmul_precision

    prec = get_tune_parameters().eigensolver_matmul_precision
    ckpt = bool(checkpoint_every) or checkpoint_path is not None or resume_from is not None
    if ckpt:
        data, taus = _reduce_checkpointed(
            full, g, band, n_panels, checkpoint_every, checkpoint_path,
            resume_from, prec,
        )
        out = mat_a.like(data)
        out.band_size = band
        return out, taus
    def build():
        kern = partial(_red2band_kernel, g=g, n_panels=n_panels, band=band)
        return coll.spmd(mat_a.grid, kern, donate_argnums=(0,), name="red2band")

    fn = _plan.cached(
        "red2band", (mat_a.grid.cache_key, g, band, n_panels, prec), build
    )
    with matmul_precision(prec):
        data, taus_stack = fn(full.data)
    full.data = data  # the hermitized copy was donated
    out = mat_a.like(data)
    out.band_size = band  # consumed as the default by band_to_tridiagonal*
    return out, taus_stack[0, 0]
