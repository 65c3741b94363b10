"""Distributed tiled Cholesky factorization.

TPU-native re-design of the reference right-looking tiled POTRF
(reference: include/dlaf/factorization/cholesky.h:42-84 and
factorization/cholesky/impl.h:151-453).  The reference builds a task DAG per
step k: potrf(diag) -> column trsm panel -> col/row panel broadcasts ->
per-tile herk/gemm trailing update, with lookahead priorities and
communicator pipelines.  Here the whole factorization is ONE jitted SPMD
program: a ``lax.fori_loop`` over k where each iteration does

  1. psum-broadcast of the diagonal tile; every rank redundantly computes the
     nb x nb potrf (cheaper than a second broadcast — replaces the
     potrfDiagTile task, impl.h:228),
  2. batched panel trsm of this rank's local column tiles (impl.h:254-262),
  3. column-panel broadcast along 'c' + transposed row panel via
     ``transpose_panel`` (replaces broadcast_panel.h col+row broadcasts),
  4. trailing update as ONE batched einsum over the whole local tile stack
     (replaces the per-(i,j) herk/gemm task loop, impl.h:273-300); masks keep
     shapes static — tiles at or left of the pivot get zero contributions.

Lookahead/priorities/round-robin workspaces have no analogue: XLA schedules
the collectives against the einsum, and steps overlap through JAX async
dispatch.  Both triangles of the trailing matrix are updated (Hermitian
storage) — on the MXU the full-tile einsum is faster than triangle
bookkeeping; on exit only the requested triangle holds the factor, the other
is garbage exactly as in LAPACK potrf.
"""
from __future__ import annotations

from dlaf_tpu.algorithms._origin import origin_transparent

from contextlib import nullcontext as _nullcontext
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dlaf_tpu import obs
from dlaf_tpu.algorithms import _spmd
from dlaf_tpu.comm import collectives as coll
from dlaf_tpu.comm.grid import COL_AXIS, ROW_AXIS
from dlaf_tpu.common import stagetimer as st
from dlaf_tpu.matrix.matrix import DistributedMatrix
from dlaf_tpu.obs.comms import record as _rec_comms
from dlaf_tpu.obs.trace import scope as _scope
from dlaf_tpu.ops import tile as t
from dlaf_tpu.plan import core as _plan


def _diag_potrf(d):
    """Diagonal-tile Cholesky: the Pallas VMEM kernel for the tiles it
    supports on TPU, XLA's blocked Cholesky otherwise (the ``supported``
    gate decides; a kernel failure raises)."""
    from dlaf_tpu.ops import pallas_potrf

    if pallas_potrf.supported(d) and jax.default_backend() == "tpu":
        return pallas_potrf.potrf_tile(d)
    return t.potrf(d, lower=True)


_fused_decline_warned = False


def _warn_fused_decline(reason: str) -> None:
    """One-time visible signal that the fused pallas path disengaged for a
    reason other than the static gates — without it the tier could quietly
    never engage and an A/B would measure nothing."""
    global _fused_decline_warned
    if _fused_decline_warned:
        return
    _fused_decline_warned = True
    import warnings

    warnings.warn(
        f"pallas fused factor+bcast declined ({reason}); lookahead panels "
        "take the unfused pallas path (same math, exchange not fused under "
        "the factor)",
        RuntimeWarning,
        stacklevel=3,
    )


def _fused_panel_bcast(d, xc, below, root, overlap: bool,
                       consumed: bool = False):
    """Fused factor-and-send for the lookahead panel: one Pallas kernel
    composing the potrf sweep, the column-blocked panel trsm, and the
    remote-DMA ring broadcast (ops/pallas_panel_exchange.fused_factor_bcast)
    so the panel starts streaming the moment it is factored.  Engages only
    under the pallas collectives tier on a real TPU backend (the exchange
    needs ICI DMA); returns None to take the unfused path otherwise —
    identical math either way.

    Only the narrow kernel-unavailable declines (ImportError /
    NotImplementedError) fall back, and they warn once; any other
    trace-time failure propagates — a blanket fallback here would silently
    disengage the fused tier with no signal why.  A bad
    ``collectives_impl`` value raises ``ConfigurationError`` from the
    trace-key resolution, as everywhere else."""
    if (
        coll.collectives_trace_key() != "pallas"
        or jax.default_backend() != "tpu"
        or coll.axis_size(COL_AXIS) <= 1
    ):
        return None
    try:
        from dlaf_tpu.ops import pallas_panel_exchange as ppe
    except ImportError as e:
        _warn_fused_decline(repr(e))
        return None
    if not ppe.fusion_supported(d, xc):
        return None
    try:
        lkk, cp = ppe.fused_factor_bcast(d, xc, below, root, COL_AXIS)
    except NotImplementedError as e:
        _warn_fused_decline(repr(e))
        return None
    # under the fused trailing-update tier the ring's hops are drained by
    # the consume kernel — book the bytes as definitionally overlapped
    _rec_comms("bcast_fused" if consumed else "bcast_pallas", xc, COL_AXIS,
               overlapped=overlap)
    return lkk, cp


def _fused_lookahead_step(x, cp, k, g: _spmd.Geometry, gi, gj):
    """The whole lookahead body as ONE Pallas kernel
    (``ops.pallas_trailing_update.fused_step``): consume-update of panel k
    straight out of its ring landing slots, narrow update, diagonal
    broadcast, factor, panel solve, and panel k+1's ring send — nothing
    touches HBM between them.  TPU-only (remote DMA + Mosaic kernels);
    returns None to take the two-piece fused path otherwise.  Same decline
    discipline as :func:`_fused_panel_bcast`: only kernel-unavailable
    declines fall back (with a one-time warning), anything else raises."""
    if jax.default_backend() != "tpu" or not (
        coll.axis_size(ROW_AXIS) > 1 or coll.axis_size(COL_AXIS) > 1
    ):
        return None
    try:
        from dlaf_tpu.ops import pallas_trailing_update as ptu
    except ImportError as e:
        _warn_fused_decline(repr(e))
        return None
    if not ptu.fused_step_supported(x, cp):
        return None
    taken, have = coll.transpose_panel_parts(cp, g.mt, g.ltc)
    k1 = k + 1
    params = jnp.stack([
        k1 % g.pc, k1 % g.pr, k1 // g.pc, k1 // g.pr, k1 // g.pc,
        0 * k, 0 * k, 0 * k,
    ])
    try:
        out = ptu.fused_step(x, taken, have, gj == k1, cp, gi > k1, params)
    except NotImplementedError as e:
        _warn_fused_decline(repr(e))
        return None
    _rec_comms("transpose_panel_fused", taken, ROW_AXIS)
    _rec_comms("bcast_fused", cp, COL_AXIS)        # panel k+1's ring send
    _rec_comms("bcast_fused", x[0, 0], COL_AXIS)   # diag tile, 'c' ring
    _rec_comms("bcast_fused", x[0, 0], ROW_AXIS)   # diag tile, 'r' ring
    return out


def _pivot_scan(d):
    """First non-positive pivot of the Hermitian tile ``d``: int32 0 when
    every pivot is positive, else the 1-based within-tile index of the first
    pivot that is <= 0 or non-finite (LAPACK xPOTRF info semantics).

    An in-graph unblocked right-looking sweep (same shape of masked rank-1
    updates as ops/pallas_potrf._potrf_kernel) that carries the failure
    index instead of the factor.  It cannot be read off ``_diag_potrf``'s
    output: ``jnp.linalg.cholesky`` lowers to LAPACK potrf + a select that
    NaN-fills the WHOLE factor on failure, erasing the pivot position.
    Once a pivot fails the scale is forced to zero, freezing the trailing
    matrix so the recorded first index stays exact."""
    n = d.shape[-1]
    a = jnp.tril(d) + jnp.swapaxes(jnp.tril(d, -1), -1, -2).conj()
    r2 = lax.broadcasted_iota(jnp.int32, (n, n), 0)
    c2 = lax.broadcasted_iota(jnp.int32, (n, n), 1)

    def body(j, carry):
        a, bad = carry
        dj = jnp.sum(jnp.where((r2 == j) & (c2 == j), a, 0)).real
        ok = dj > 0  # False for NaN/Inf-poisoned pivots too
        bad = jnp.where((bad == 0) & ~ok, j + 1, bad)
        inv = jnp.where(ok, 1.0 / jnp.sqrt(jnp.where(ok, dj, 1.0)), 0.0)
        col = jnp.sum(jnp.where(c2 == j, a, 0), axis=1) * inv.astype(a.dtype)
        col = jnp.where(r2[:, 0] > j, col, 0)
        a = a - jnp.where((r2 > j) & (c2 > j), col[:, None] * col[None, :].conj(), 0)
        return a, bad

    _, bad = lax.fori_loop(0, n, body, (a, jnp.zeros((), jnp.int32)))
    return bad


def _chol_step(k, x, info, g: _spmd.Geometry, myr, myc, gi, want_info: bool):
    """One right-looking Cholesky panel step on the padded local tile stack
    ``x`` (diag potrf -> panel trsm -> broadcasts -> write-back -> trailing
    update).  Shared by the masked full-loop kernel and the checkpointing
    range kernel so both trace IDENTICAL per-step computation — the
    foundation of the resumed-run bit-exactness contract.  Returns
    ``(x, info)``; ``info`` is passed through untouched when ``want_info``
    is off (the caller drops it)."""
    kc = k % g.pc
    lkc = k // g.pc
    # 1. diagonal tile to everyone; redundant local potrf
    with _scope("chol.diag_potrf"):
        d = _spmd.bcast_diag_tile(x, k, g, myr, myc)
        lkk = _diag_potrf(d)
        if want_info:
            bad = _pivot_scan(d)
            # cast: k is the loop-index dtype (int64 in the range kernel
            # under x64), the info carry stays int32
            info = jnp.where(
                (info == 0) & (bad > 0), (k * g.mb + bad).astype(info.dtype), info
            )
    # 2. panel trsm: L[i,k] = A[i,k] @ L[k,k]^-H for local rows i > k
    with _scope("chol.panel_trsm"):
        xc = _spmd.take_col(x, lkc, g)
        pan = t.trsm(t.RIGHT, t.LOWER, t.CONJ_TRANS, t.NON_UNIT, 1.0, lkk, xc)
        below = (gi > k)[:, None, None]
        cp_own = jnp.where(below, pan, jnp.zeros_like(pan))
    # 3. column panel to all rank columns; transposed row panel
    # (one-contributor broadcast from rank column kc; the `below` mask
    # zeroes non-panel rows on the root before the wire)
    with _scope("chol.panel_bcast"):
        cp = coll.bcast(cp_own, kc, COL_AXIS)  # [ltr, mb, mb]
        rp = coll.transpose_panel(cp, g.mt, g.ltc)  # [ltc, mb, mb]
    # write back the factored column (pivot tile + sub-diagonal tiles)
    new_col = jnp.where(
        myc == kc,
        jnp.where((gi == k)[:, None, None], lkk[None], jnp.where(below, pan, xc)),
        xc,
    )
    x = _spmd.put_col(x, new_col, lkc)
    # 4. trailing update: A[i,j] -= L[i,k] L[j,k]^H  (one batched matmul)
    with _scope("chol.trailing_update"):
        x = x - t.contract("iab,jcb->ijac", cp, rp.conj())
    return x, info


def _chol_L_kernel(x, g: _spmd.Geometry, want_info: bool = False):
    """shard_map-local kernel: x is [1,1,ltr,ltc,mb,mb]; returns same — or,
    with ``want_info``, (same, info) with ``info`` the LAPACK-style 1-based
    first-failing-pivot index (0 = success) threaded through the fori_loop
    carry — every rank scans the same broadcast diagonal tile, so the scalar
    is replicated and costs zero extra collectives and zero host syncs.
    ``want_info`` is a STATIC trace-time switch: off, no pivot scan and no
    info carry are traced, so the plain path's HLO is unchanged."""
    x = coll.local(x)
    myr, myc = coll.my_rank()
    x = _spmd.pad_diag_identity(x, g, myr, myc)
    gi = _spmd.local_row_tiles(g, myr)

    def body(k, carry):
        x, info = carry if want_info else (carry, None)
        x, info = _chol_step(k, x, info, g, myr, myc, gi, want_info)
        return (x, info) if want_info else x

    init = (x, jnp.zeros((), jnp.int32)) if want_info else x
    out = lax.fori_loop(0, g.mt, body, init)
    x, info = out if want_info else (out, None)
    x = _spmd.pad_diag_identity(x, g, myr, myc, remove=True)
    return (coll.relocal(x), info) if want_info else coll.relocal(x)


def _chol_L_range_kernel(x, info, k0, k1, g: _spmd.Geometry):
    """Checkpoint-segment kernel: run panel steps ``k0 <= k < k1`` of the
    masked L factorization (``_chol_step``, info always carried).  ``k0``
    and ``k1`` are TRACED scalars — ``lax.fori_loop`` accepts dynamic
    bounds — so ONE compiled executable serves every segment of a
    ``checkpoint_every=`` run and every resumed continuation; resumed and
    uninterrupted runs of the same cadence replay the identical executable
    over identical panel ranges, which is what makes the restored factor
    bit-exact.  Padding is applied/removed per segment: padding tiles never
    feed real output entries (real tiles only read real panel entries), so
    segmenting is value-exact on the logical matrix."""
    x = coll.local(x)
    myr, myc = coll.my_rank()
    x = _spmd.pad_diag_identity(x, g, myr, myc)
    gi = _spmd.local_row_tiles(g, myr)

    def body(k, carry):
        return _chol_step(k, carry[0], carry[1], g, myr, myc, gi, True)

    # bounds cast to the DEFAULT int dtype so the loop index k matches the
    # full-loop kernels' weak-int index (int64 under x64) — the _spmd slice
    # helpers mix k-derived offsets with python-int literals
    idt = jnp.asarray(0).dtype
    x, info = lax.fori_loop(k0.astype(idt), k1.astype(idt), body, (x, info))
    x = _spmd.pad_diag_identity(x, g, myr, myc, remove=True)
    return coll.relocal(x), info


def _chol_L_bucketed_kernel(x, g: _spmd.Geometry, want_info: bool = False):
    """Bucketed variant of _chol_L_kernel: the trailing update runs on a
    dynamic-sliced window of the local tile stack whose STATIC size shrinks
    by segment — restoring the reference's 'only the trailing submatrix'
    flop count (impl.h:273-300) within static-shape constraints.  Windows
    are over-approximate and clamped; masked panels make overlap rows/cols
    no-ops, so clamping is always safe."""
    x = coll.local(x)
    myr, myc = coll.my_rank()
    x = _spmd.pad_diag_identity(x, g, myr, myc)

    def step(k, carry, L, C):
        x, info = carry if want_info else (carry, None)
        kr, kc = k % g.pr, k % g.pc
        lkr, lkc = k // g.pr, k // g.pc
        with _scope("chol.diag_potrf"):
            d = _spmd.bcast_diag_tile(x, k, g, myr, myc)
            lkk = _diag_potrf(d)
            if want_info:
                bad = _pivot_scan(d)
                info = jnp.where((info == 0) & (bad > 0), k * g.mb + bad, info)
        # local window starts (first slot with gi >= k+1 / gj >= k+1)
        rs = jnp.clip((k + g.pr - myr) // g.pr, 0, max(g.ltr - L, 0)).astype(lkr.dtype)
        cs = jnp.clip((k + g.pc - myc) // g.pc, 0, max(g.ltc - C, 0)).astype(lkr.dtype)
        gi_w = (rs + jnp.arange(L)) * g.pr + myr
        jv = (cs + jnp.arange(C)) * g.pc + myc
        # panel trsm on the row window only
        with _scope("chol.panel_trsm"):
            xc = lax.dynamic_slice(x, (rs, lkc, 0, 0), (L, 1, g.mb, g.mb))[:, 0]
            pan = t.trsm(t.RIGHT, t.LOWER, t.CONJ_TRANS, t.NON_UNIT, 1.0, lkk, xc)
            below = (gi_w > k)[:, None, None]
        with _scope("chol.panel_bcast"):
            cp = coll.bcast(jnp.where(below, pan, jnp.zeros_like(pan)), kc, COL_AXIS)
            rp = coll.transpose_panel_windowed(cp, jv, rs, g.mt)
        # write the factored panel (window rows) and the diagonal tile
        new_col = jnp.where(below & (myc == kc), pan, xc)
        x = lax.dynamic_update_slice(x, new_col[:, None], (rs, lkc, 0, 0))
        mine_d = (myr == kr) & (myc == kc)
        dtile = jnp.where(mine_d, lkk, x[lkr, lkc])[None, None]
        x = lax.dynamic_update_slice(x, dtile.astype(x.dtype), (lkr, lkc, 0, 0))
        # trailing update on the window
        with _scope("chol.trailing_update"):
            xs = lax.dynamic_slice(x, (rs, cs, 0, 0), (L, C, g.mb, g.mb))
            xs = xs - t.contract("iab,jcb->ijac", cp, rp.conj())
            out = lax.dynamic_update_slice(x, xs, (rs, cs, 0, 0))
            return (out, info) if want_info else out

    carry = (x, jnp.zeros((), jnp.int32)) if want_info else x
    for k0, k1 in _spmd.halving_segments(g.mt):
        L = min(g.ltr, (g.mt - 1 - k0 + g.pr - 1) // g.pr + 1)
        C = min(g.ltc, (g.mt - 1 - k0 + g.pc - 1) // g.pc + 1)
        L, C = max(L, 1), max(C, 1)
        carry = lax.fori_loop(k0, k1, partial(step, L=L, C=C), carry)

    x, info = carry if want_info else (carry, None)
    x = _spmd.pad_diag_identity(x, g, myr, myc, remove=True)
    return (coll.relocal(x), info) if want_info else coll.relocal(x)


def _chol_L_lookahead_kernel(x, g: _spmd.Geometry, want_info: bool = False):
    """Lookahead variant (reference: next-panel tasks at high priority while
    the trailing update runs, factorization/cholesky/impl.h:171-174,280-282).

    Each iteration k: write back panel k, apply the NARROW update to column
    k+1 only, immediately compute panel k+1 (potrf + trsm + broadcast), THEN
    run the bulk trailing update excluding column k+1.  Panel k+1's
    collectives are independent of the bulk einsum, so XLA can overlap them
    — panel broadcast latency hides under the trailing update on real
    meshes.  The panel flows through the loop carry.

    The steady-state panel exchanges (everything issued from the loop body;
    the prologue's panel-0 broadcast has nothing to hide under) run inside
    ``coll.overlap_window``: under the pallas collectives tier their DMA
    hops can drain beneath the bulk einsum and ``obs.comms`` books their
    modeled wire bytes as overlapped, and on TPU the panel factor+broadcast
    collapses into the fused Pallas step (``_fused_panel_bcast``).

    Under ``tune.trailing_update_impl == 'fused'`` the bulk trailing update
    routes through ``ops.pallas_trailing_update``: the row-panel exchange
    and the update become one consumer (per-hop application out of the ring
    landing slots on TPU; the one-shot in-kernel update on the interpret
    parity path), issued BEFORE the narrow update and panel k+1.  The
    reorder is bit-exact: the bulk excludes column k+1, whose slots enter
    the update as exact zeros, and every operand panel k+1 reads (its
    column, the diagonal tile, the broadcast selects) is either excluded
    from the bulk or root-selected off ranks the bulk touched — so
    ``(a - bulk) - narrow`` and ``(a - narrow) - bulk`` subtract the same
    two addends per element in both orders."""
    x = coll.local(x)
    myr, myc = coll.my_rank()
    x = _spmd.pad_diag_identity(x, g, myr, myc)
    gi = _spmd.local_row_tiles(g, myr)
    gj = _spmd.local_col_tiles(g, myc)
    fused_tier = _spmd.trailing_update_trace_key() == "fused"

    def compute_panel(x, k, overlap=False):
        # overlap=True: this is the lookahead panel — every collective in
        # its dependency chain (diag-tile bcast included) is independent of
        # the bulk einsum it is scheduled against, so the whole chain sits
        # inside the window
        win = coll.overlap_window if overlap else _nullcontext
        with _scope("chol.diag_potrf"), win():
            d = _spmd.bcast_diag_tile(x, k, g, myr, myc)
            bad = _pivot_scan(d) if want_info else None
        xc = _spmd.take_col(x, k // g.pc, g)
        fused = _fused_panel_bcast(d, xc, gi > k, k % g.pc, overlap,
                                   consumed=fused_tier)
        if fused is not None:
            return fused[0], fused[1], bad
        with _scope("chol.diag_potrf"):
            lkk = _diag_potrf(d)
        with _scope("chol.panel_trsm"):
            pan = t.trsm(t.RIGHT, t.LOWER, t.CONJ_TRANS, t.NON_UNIT, 1.0, lkk, xc)
            below = (gi > k)[:, None, None]
        with _scope("chol.panel_bcast"), win():
            cp = coll.bcast(
                jnp.where(below, pan, jnp.zeros_like(pan)), k % g.pc, COL_AXIS,
                consumed=fused_tier,
            )
        return lkk, cp, bad

    def write_back(x, k, lkk, cp):
        lkc = k // g.pc
        xc = _spmd.take_col(x, lkc, g)
        below = (gi > k)[:, None, None]
        new_col = jnp.where(
            myc == k % g.pc,
            jnp.where((gi == k)[:, None, None], lkk[None], jnp.where(below, cp, xc)),
            xc,
        )
        return _spmd.put_col(x, new_col, lkc)

    def body(k, carry):
        if want_info:
            x, lkk, cp, info = carry
        else:
            x, lkk, cp = carry
        x = write_back(x, k, lkk, cp)

        def two_piece(x, k, cp):
            if fused_tier:
                # two-piece fused path: the exchange-and-consume kernel
                # applies the bulk update (column k+1 excluded) BEFORE the
                # narrow update and panel k+1 — bit-exact reorder, see the
                # kernel docstring
                from dlaf_tpu.ops import pallas_trailing_update as ptu

                with _scope("chol.panel_bcast"), coll.overlap_window():
                    taken, have = coll.transpose_panel_parts(
                        cp, g.mt, g.ltc)
                with _scope("chol.trailing_update"):
                    x, rp = ptu.fused_transpose_update(
                        x, cp, taken, have, gj == k + 1, ROW_AXIS)
            else:
                with _scope("chol.panel_bcast"), coll.overlap_window():
                    rp = coll.transpose_panel(cp, g.mt, g.ltc)
            # narrow update: column k+1 only, so its panel starts now
            l_next = (k + 1) // g.pc
            xc1 = _spmd.take_col(x, l_next, g)
            rp1 = _spmd.take_tile(rp, l_next)
            upd1 = t.contract("iab,cb->iac", cp, rp1.conj())
            xc1 = jnp.where(myc == (k + 1) % g.pc, xc1 - upd1, xc1)
            x = _spmd.put_col(x, xc1, l_next)
            # lookahead: panel k+1 from the already-updated column
            lkk1, cp1, bad1 = compute_panel(x, k + 1, overlap=True)
            if not fused_tier:
                # bulk trailing update, column k+1 excluded (already done)
                with _scope("chol.trailing_update"):
                    rp_bulk = jnp.where(
                        (gj == k + 1)[:, None, None], jnp.zeros_like(rp), rp)
                    x = x - t.contract("iab,jcb->ijac", cp, rp_bulk.conj())
            return x, lkk1, cp1, bad1

        stepped = _fused_lookahead_step(x, cp, k, g, gi, gj) \
            if fused_tier else None
        if stepped is not None:
            # single-kernel path (TPU): consume-update + narrow + factor +
            # solve + send of panel k+1, one launch; the pivot scan reads
            # the kernel's broadcast diagonal tile.  ``stepped`` is decided
            # by trace-time static gates, identically on every rank.
            x, _rp, lkk1, cp1, d1 = stepped
            bad1 = _pivot_scan(d1) if want_info else None
        else:
            x, lkk1, cp1, bad1 = two_piece(x, k, cp)
        if want_info:
            info = jnp.where((info == 0) & (bad1 > 0), (k + 1) * g.mb + bad1, info)
        return (x, lkk1, cp1, info) if want_info else (x, lkk1, cp1)

    lkk0, cp0, bad0 = compute_panel(x, 0)
    if want_info:
        # pivot-0 tile: global 1-based index == within-tile index
        init = (x, lkk0, cp0, bad0)
        x, lkk, cp, info = lax.fori_loop(0, g.mt - 1, body, init)
    else:
        x, lkk, cp = lax.fori_loop(0, g.mt - 1, body, (x, lkk0, cp0))
        info = None
    x = write_back(x, g.mt - 1, lkk, cp)
    x = _spmd.pad_diag_identity(x, g, myr, myc, remove=True)
    return (coll.relocal(x), info) if want_info else coll.relocal(x)


def _compiled(grid, g: _spmd.Geometry, uplo: str, variant: str = "bucketed",
              want_info: bool = False):
    def build():
        kern_fn = {
            "bucketed": _chol_L_bucketed_kernel,
            "masked": _chol_L_kernel,
            "lookahead": _chol_L_lookahead_kernel,
        }[variant]
        if want_info:
            # kernels return (factor, info); the info scalar is computed
            # identically on every rank (replicated P() output)
            P = jax.sharding.PartitionSpec
            return coll.spmd(
                grid,
                partial(kern_fn, g=g, want_info=True),
                donate_argnums=(0,),
                out_specs=(P(ROW_AXIS, COL_AXIS), P()),
                name="cholesky",
            )
        return coll.spmd(grid, partial(kern_fn, g=g), donate_argnums=(0,), name="cholesky")

    return _plan.cached("cholesky", (grid.cache_key, g, uplo, variant, want_info),
                        build)


def _compiled_range(grid, g: _spmd.Geometry):
    """Compiled checkpoint-segment executable for the masked L kernel:
    ``(x, info, k0, k1) -> (x, info)`` with traced panel bounds, so the
    one executable serves every segment and every resumed continuation.
    Built directly on ``jax.shard_map`` (not :func:`coll.spmd`, whose
    uniform ``P('r','c')`` in_specs would shard the scalar bounds)."""
    def build():
        P = jax.sharding.PartitionSpec
        spec = P(ROW_AXIS, COL_AXIS)
        sm = jax.shard_map(
            partial(_chol_L_range_kernel, g=g),
            mesh=grid.mesh,
            in_specs=(spec, P(), P(), P()),
            out_specs=(spec, P()),
            check_vma=False,
        )
        return _plan.jit("cholesky_range", sm, donate_argnums=(0,))

    return _plan.cached("cholesky_range", (grid.cache_key, g), build)


def _factor_checkpointed(mat_a, g: _spmd.Geometry, checkpoint_every: int,
                         checkpoint_path, resume_from):
    """Segmented L factorization: run the range kernel ``checkpoint_every``
    panels at a time, crossing a ``resilience.panel_boundary`` (deadline
    check / fault-injection point) before each segment and writing a
    panel-granular checkpoint after each completed segment when
    ``checkpoint_path`` is set (no path: segmented execution only — how an
    uninterrupted reference run matches a resumed run's cadence).  With
    ``resume_from`` the matrix state and panel index are restored first and
    the loop re-enters at the stored panel.  Returns ``(data, info)``;
    ``mat_a`` is repointed at every segment so the caller's handle survives
    a preemption mid-loop."""
    from dlaf_tpu import resilience

    kern = _compiled_range(mat_a.grid, g)
    step = int(checkpoint_every) if checkpoint_every else g.mt
    k = 0
    info = jnp.zeros((), jnp.int32)
    if resume_from is not None:
        data, attrs, _ = resilience.load_checkpoint(
            resume_from, mat_a, algo="cholesky"
        )
        mat_a._inplace(data)
        k = int(attrs.get("panel", 0))
        info = jnp.asarray(np.int32(attrs.get("info", 0)))
    while k < g.mt:
        k1 = min(k + step, g.mt)
        resilience.panel_boundary("cholesky", k, mat_a.data)
        data, info = kern(mat_a.data, info, np.int32(k), np.int32(k1))
        mat_a._inplace(data)
        k = k1
        if checkpoint_path is not None and k < g.mt:
            resilience.save_checkpoint(
                checkpoint_path, mat_a, algo="cholesky", panel=k, info=int(info)
            )
    return mat_a.data, info


def _cholesky_single_device(uplo: str, mat_a: DistributedMatrix) -> DistributedMatrix:
    """1x1-grid fast path: XLA's built-in blocked Cholesky on the dense
    matrix (the TPU analogue of the reference dispatching tile potrf to
    cuSOLVER) — ~1.6x our SPMD loop at N=16k on one chip."""
    import jax
    import jax.numpy as jnp

    from dlaf_tpu.matrix import layout

    from dlaf_tpu.tune import blas3_precision

    dist = mat_a.dist

    def build():
        def run(x):
            g_ = layout.unpad_global(layout.unpack(x, dist), dist)
            if uplo == t.LOWER:
                herm = jnp.tril(g_) + jnp.swapaxes(jnp.tril(g_, -1), -1, -2).conj()
                fac = jnp.linalg.cholesky(herm)
                out = fac + jnp.triu(g_, 1)  # keep caller's upper triangle
            else:
                herm = jnp.triu(g_) + jnp.swapaxes(jnp.triu(g_, 1), -1, -2).conj()
                fac = jnp.swapaxes(jnp.linalg.cholesky(jnp.swapaxes(herm, -1, -2).conj()), -1, -2).conj()
                out = fac + jnp.tril(g_, -1)
            return layout.pack(layout.pad_global(out, dist), dist)

        return _plan.jit("cholesky_local", run)

    fn = _plan.cached("cholesky_local", (dist, np.dtype(mat_a.dtype), uplo), build)
    with blas3_precision():
        return mat_a._inplace(fn(mat_a.data))


def _factor_with_recovery(mat_a, g, variant, max_shift_attempts):
    """Escalating diagonal-shift retry (opt-in near-SPD recovery): factor
    A + shift*I with shift 0, then s0 = max(||A||_max, 1)*n*eps escalating
    x100 per attempt, at most ``max_shift_attempts`` retries.  Returns
    ``(data, info, shift)`` — info is the HOST int info of the LAST attempt
    (each retry costs one host sync by construction: the decision to retry
    depends on device data).  The kernel donates its input, so every
    attempt feeds a fresh buffer and the caller's original survives."""
    from dlaf_tpu import health
    from dlaf_tpu.matrix import util as mutil

    kern = _compiled(mat_a.grid, g, t.LOWER, variant, want_info=True)
    orig = mat_a.data
    data, info = kern(jnp.copy(orig))
    st.barrier(data)
    info_i = int(info)
    if info_i == 0:
        return data, 0, 0.0
    eps = float(np.finfo(np.dtype(mat_a.dtype).type(0).real.dtype).eps)
    anorm = float(jnp.max(jnp.abs(orig))) if orig.size else 1.0
    shift = max(anorm, 1.0) * max(mat_a.size.rows, 1) * eps
    eye = mutil.eye_like(mat_a).data
    for attempt in range(1, max_shift_attempts + 1):
        health.record(
            "cholesky_shift_retry", attempt=attempt, shift=shift, info=info_i
        )
        data, info = kern(orig + np.dtype(mat_a.dtype).type(shift) * eye)
        st.barrier(data)
        info_i = int(info)
        if info_i == 0:
            health.record("cholesky_shift_recovered", attempt=attempt, shift=shift)
            return data, 0, shift
        if attempt < max_shift_attempts:
            shift *= 100.0
    return data, info_i, shift


@origin_transparent
def cholesky_factorization(
    uplo: str,
    mat_a: DistributedMatrix,
    backend: str = "auto",
    _dump: bool = True,
    return_info: bool = False,
    raise_on_failure: bool = False,
    shift_recovery: bool = False,
    max_shift_attempts: int = 3,
    checkpoint_every: int = 0,
    checkpoint_path: str | None = None,
    resume_from: str | None = None,
) -> DistributedMatrix:
    """Factor the Hermitian positive-definite ``mat_a``: on return the
    ``uplo`` triangle holds the Cholesky factor.  Only the ``uplo`` triangle
    of the input is referenced (LAPACK semantics); the other triangle is
    returned unchanged (U path) or holds update residue (L path).  Async:
    returns immediately, the result materializes lazily (reference API:
    factorization/cholesky.h:72, also graph-building async).

    ``backend='auto'`` uses XLA's dense Cholesky on 1x1 grids and the
    distributed SPMD kernel otherwise; 'distributed' forces the kernel.

    Failure reporting (LAPACK xPOTRF conventions, 1-based):

    * ``return_info=True`` — returns ``(factor, info)``; ``info`` is 0 on
      success, else the index of the first non-positive pivot (the leading
      minor of order ``info`` is not positive definite).  Without
      ``shift_recovery``/``raise_on_failure`` the info stays a lazy device
      scalar — asynchrony is preserved, ``int(info)`` blocks.
    * ``raise_on_failure=True`` — syncs and raises
      :class:`~dlaf_tpu.health.NotPositiveDefiniteError` when info > 0.
    * ``shift_recovery=True`` — opt-in bounded recovery for near-SPD
      inputs: on failure, re-factor ``A + shift*I`` with an escalating
      shift (at most ``max_shift_attempts`` retries; each health-recorded
      with the shift used).  Implies host syncs; info/exceptions then
      report the LAST attempt.

    Info-code requests route 1x1 grids through the distributed kernel too:
    the dense XLA fast path NaN-fills its whole factor on failure and
    cannot name the pivot.

    Preemption safety (``dlaf_tpu.resilience``):

    * ``checkpoint_every=k`` — run the factorization in k-panel segments;
      after each completed segment write a panel-granular checkpoint to
      ``checkpoint_path`` (matrix state + panel index + tune/collectives
      snapshot, atomic rank-0 HDF5 write).  Collective-safe: on
      multi-process worlds every process must make the same call.  Without
      ``checkpoint_path`` the run is merely segmented — how an
      uninterrupted reference run matches a resumed run's cadence.
    * ``resume_from=path`` — restore a checkpoint and re-enter the panel
      loop at the stored panel.  A resumed run is BIT-IDENTICAL to an
      uninterrupted run of the same ``checkpoint_every`` cadence (both
      replay the one compiled range kernel over the same panel ranges).
    * Each segment boundary is a ``resilience.panel_boundary``: ambient
      ``resilience.deadline`` budgets are enforced there
      (:class:`~dlaf_tpu.health.DeadlineExceededError` instead of an
      unbounded block) and fault injection (simulated preemption) hooks in
      there.  Checkpointing forces the distributed kernel (the dense 1x1
      fast path has no panel loop) and excludes ``shift_recovery``.
    """
    from dlaf_tpu.health import DistributionError, NotPositiveDefiniteError

    want_info = return_info or raise_on_failure or shift_recovery
    ckpt = bool(checkpoint_every) or checkpoint_path is not None or resume_from is not None
    if ckpt and shift_recovery:
        raise DistributionError(
            "cholesky: checkpointing and shift_recovery are mutually exclusive "
            "(recovery restarts from the original matrix, not a checkpoint)"
        )
    if mat_a.size.rows != mat_a.size.cols:
        raise DistributionError("cholesky: matrix must be square")
    if mat_a.block_size.rows != mat_a.block_size.cols:
        raise DistributionError("cholesky: tiles must be square")
    from dlaf_tpu.common import checks

    checks.assert_hermitian_heavy(mat_a, uplo)
    g = _spmd.Geometry.of(mat_a.dist)
    if g.mt == 0:
        return (mat_a, 0) if return_info else mat_a
    if _dump:
        from dlaf_tpu.matrix.io import maybe_dump

        maybe_dump("debug_dump_cholesky_data", "dlaf_dump_cholesky_input.npz", mat_a)
    if (backend == "auto" and mat_a.grid.grid_size.count() == 1
            and not want_info and not ckpt):
        with obs.stage("potrf"):
            out = _cholesky_single_device(uplo, mat_a)
            st.barrier(out.data)
        return out
    if uplo == t.LOWER:
        from dlaf_tpu.tune import get_tune_parameters

        variant = "lookahead" if get_tune_parameters().cholesky_lookahead else "bucketed"
        from dlaf_tpu.tune import blas3_precision

        shift = 0.0
        with obs.stage("potrf"), blas3_precision():
            if ckpt:
                data, info = _factor_checkpointed(
                    mat_a, g, checkpoint_every, checkpoint_path, resume_from
                )
            elif shift_recovery:
                data, info, shift = _factor_with_recovery(
                    mat_a, g, variant, max_shift_attempts
                )
            elif want_info:
                data, info = _compiled(
                    mat_a.grid, g, uplo, variant, want_info=True
                )(mat_a.data)
            else:
                # plain path: the pre-health kernel trace, HLO unchanged
                data = _compiled(mat_a.grid, g, uplo, variant)(mat_a.data)
                info = 0
            st.barrier(data)
        out = mat_a._inplace(data)
        if raise_on_failure and int(info) > 0:
            raise NotPositiveDefiniteError(int(info), shift=shift)
        return (out, info) if return_info else out
    if uplo == t.UPPER:
        # A = U^H U with U = L^H: mirror the stored upper triangle to lower
        # storage, run the Lower kernel, conj-transpose the factor back
        # (reference implements a native call_U mirror-image loop,
        # factorization/cholesky/impl.h:316-453; the two transposes here are
        # single all-to-alls, negligible next to the N^3/3 factorization).
        # The mirrored matrix is conj(A) restricted to its stored triangle,
        # with the SAME leading minors — the L-path info carries over.
        from dlaf_tpu.matrix import util as mutil

        low = mutil.transpose(mutil.extract_triangle(mat_a, "U"), conj=True)
        res = cholesky_factorization(
            t.LOWER,
            low,
            _dump=False,
            return_info=want_info,
            raise_on_failure=raise_on_failure,
            shift_recovery=shift_recovery,
            max_shift_attempts=max_shift_attempts,
            checkpoint_every=checkpoint_every,
            checkpoint_path=checkpoint_path,
            resume_from=resume_from,
        )
        fac, info = res if want_info else (res, None)
        u = mutil.transpose(mutil.extract_triangle(fac, "L"), conj=True)
        # keep the caller's original lower triangle untouched (LAPACK-style);
        # _inplace (not like): the docstring promises in-place semantics, and
        # the L path repoints the caller's handle — U must match
        out = mat_a._inplace(
            mutil.extract_triangle(mat_a, "L", k=-1).data + mutil.extract_triangle(u, "U").data
        )
        return (out, info) if return_info else out
    raise DistributionError(f"bad uplo {uplo}")
