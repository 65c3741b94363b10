"""Back-transform of eigenvectors by the reduction-to-band reflectors:
E <- Q1 E with Q1 = prod_k (I - V_k T_k V_k^H).

TPU-native re-design of the reference bt_reduction_to_band
(reference: include/dlaf/eigensolver/bt_reduction_to_band.h:47-108 and
bt_reduction_to_band/impl.h — compact-WY applications with recomputed T
factors).  One jitted SPMD fori_loop over panels in REVERSE order; per panel:

  1. gather the stored reflector column from the band matrix (all_gather
     along 'r' + bcast along 'c'), rebuild V (unit heads, zero above),
  2. recompute the T factor (same _t_factor as reduction_to_band — the
     reference also recomputes T, impl.h:399),
  3. W = T^H? no — E := E - V T (V^H E): V^H E is a psum over 'r', the
     rank-nb update is one batched einsum.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dlaf_tpu.algorithms import _spmd
from dlaf_tpu.algorithms.reduction_to_band import _t_factor
from dlaf_tpu.comm import collectives as coll
from dlaf_tpu.comm.grid import COL_AXIS, ROW_AXIS
from dlaf_tpu.matrix.matrix import DistributedMatrix
from dlaf_tpu.ops import tile as t
from dlaf_tpu.plan import core as _plan


def _panel_v_tmat(a, taus, p, g_a: _spmd.Geometry, band: int):
    """Rebuild panel ``p``'s full reflector block V [np_, band] (replicated:
    all_gather along 'r' + bcast along 'c' of the stored strip, unit heads,
    zero above, tau==0 columns dropped) and its recomputed T factor — the
    shared core of the stacked and column-sharded kernels."""
    np_ = g_a.ltr * g_a.pr * g_a.mb
    rows = jnp.arange(np_)
    pb = p * band
    kt = pb // g_a.nb
    co = pb % g_a.nb
    kc = kt % g_a.pc
    lkc = kt // g_a.pc
    xc = _spmd.take_col(a, lkc, g_a)
    xcb = lax.dynamic_slice(xc, (0, 0, co), (g_a.ltr, g_a.mb, band))
    gat = coll.all_gather_axis(xcb, ROW_AXIS)
    col = jnp.transpose(gat, (1, 0, 2, 3)).reshape(np_ // g_a.mb, g_a.mb, band)
    col = coll.bcast(col, kc, COL_AXIS).reshape(np_, band)
    start = (p + 1) * band
    j_idx = jnp.arange(band)[None, :]
    head = rows[:, None] == start + j_idx
    below = rows[:, None] > start + j_idx
    v = jnp.where(head, 1.0, jnp.where(below, col, 0.0)).astype(col.dtype)
    tau_k = lax.dynamic_slice(taus, (p, 0), (1, band))[0]
    # zero columns whose tau is 0 (incl. padding columns)
    v = jnp.where((tau_k == 0)[None, :], 0.0, v)
    return v, _t_factor(v, tau_k, band)


def _bt_r2b_kernel(
    a, taus, e, g_a: _spmd.Geometry, g_e: _spmd.Geometry, n_panels: int, band: int
):
    a = coll.local(a)
    e = coll.local(e)
    taus = coll.local(taus)
    myr, myc = coll.my_rank()
    gi = _spmd.local_row_tiles(g_a, myr)
    np_ = g_a.ltr * g_a.pr * g_a.mb

    def body(s, e):
        p = n_panels - 1 - s
        v, tmat = _panel_v_tmat(a, taus, p, g_a, band)
        # E -= V T (V^H E): rows block-cyclic over 'r', W psum'd across it
        v_tiles = v.reshape(np_ // g_a.mb, g_a.mb, band)
        vr = jnp.take(v_tiles, gi, axis=0)  # [ltr, mb, band]
        w = coll.psum_axis(t.contract("iab,ijac->jbc", vr.conj(), e), ROW_AXIS)
        tw = t.contract("ab,jbc->jac", tmat, w)
        return e - t.contract("iab,jbc->ijac", vr, tw)

    e = lax.fori_loop(0, n_panels, body, e)
    return coll.relocal(e)


def _bt_r2b_cols_kernel(a, taus, e, g_a: _spmd.Geometry, n_panels: int, band: int):
    """Column-sharded variant: ``e`` is this device's [np_, kloc] slab of
    the column-panel layout (every device owns ALL rows of its columns), so
    the per-panel W = V^H E psum of the stacked kernel disappears — V is
    rebuilt replicated (same gather as the stacked kernel) and the update
    is three LOCAL matmuls.  Same per-device flop count (np_*band*k/P)."""
    a = coll.local(a)

    def body(s, e):
        p = n_panels - 1 - s
        v, tmat = _panel_v_tmat(a, taus, p, g_a, band)
        w = t.contract("ka,kb->ab", v.conj(), e)  # [band, kloc] — no psum: full rows are local
        return e - t.contract("ab,bc->ac", v, tmat @ w)

    return lax.fori_loop(0, n_panels, body, e)


def _bt_r2b_cols(cols, mat_band: DistributedMatrix, taus: jax.Array):
    """ColPanels entry: consume the column-sharded E of the fused
    back-transform chain, apply Q1, and perform the chain's single final
    pack to the stacked layout."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dlaf_tpu.matrix import colpanels as cpan
    from dlaf_tpu.matrix import layout
    from dlaf_tpu.tune import get_tune_parameters, matmul_precision

    g_a = _spmd.Geometry.of(mat_band.dist)
    g_e = _spmd.Geometry.of(cols.dist)
    if g_a.mb != g_e.mb or g_a.pr != g_e.pr or g_a.mt != g_e.mt:
        raise ValueError("bt_reduction_to_band: E row distribution must match A")
    n_panels = int(taus.shape[0])
    band = int(taus.shape[1])
    if n_panels == 0 or g_e.nt == 0:
        return cpan.pack_to_matrix(cols)
    grid = cols.grid
    dist = cols.dist
    n, k = cols.n, cols.k
    np_ = g_a.ltr * g_a.pr * g_a.mb
    mesh = grid.mesh
    colspec = P(None, (ROW_AXIS, COL_AXIS))
    prec = get_tune_parameters().eigensolver_matmul_precision
    def build():

        def kern(a, t, e):
            return _bt_r2b_cols_kernel(a, t, e, g_a=g_a, n_panels=n_panels, band=band)

        sm = jax.shard_map(
            kern,
            mesh=mesh,
            in_specs=(P(ROW_AXIS, COL_AXIS), P(), colspec),
            out_specs=colspec,
            check_vma=False,
        )

        def run(a, t, gp):
            # align rows to np_ (v's extent); rows beyond n are zero and
            # v has no support there, so slicing loses nothing
            r = gp.shape[0]
            if r < np_:
                gp = jnp.pad(gp, ((0, np_ - r), (0, 0)))
            elif r > np_:
                gp = gp[:np_]
            gp = jax.lax.with_sharding_constraint(gp, NamedSharding(mesh, colspec))
            gp = sm(a, t, gp)
            return layout.pack(layout.pad_global(gp[:n, :k], dist), dist)

        # no donation: the col-sharded input cannot alias the stacked output
        return _plan.jit("bt_r2b_cols", run, out_shardings=grid.stacked_sharding())

    fn = _plan.cached(
        "bt_r2b_cols",
        (grid.cache_key, g_a, dist, tuple(cols.data.shape), n_panels, band,
         prec, np.dtype(cols.data.dtype)),
        build,
    )
    with matmul_precision(prec):
        data = fn(mat_band.data, taus, cols.data)
    return DistributedMatrix(dist, grid, data)


def bt_reduction_to_band(
    mat_e, mat_band: DistributedMatrix, taus: jax.Array
) -> DistributedMatrix:
    """E := Q1 E where Q1 is the accumulated reduction_to_band transformation
    stored in ``mat_band`` (reflector tails below the band) + ``taus``.

    ``mat_e`` may be a stacked DistributedMatrix or the column-sharded
    :class:`~dlaf_tpu.matrix.colpanels.ColPanels` from the fused
    back-transform chain (then this stage does the chain's single pack)."""
    from dlaf_tpu.matrix import colpanels as cpan

    if isinstance(mat_e, cpan.ColPanels):
        return _bt_r2b_cols(mat_e, mat_band, taus)
    g_a = _spmd.Geometry.of(mat_band.dist)
    g_e = _spmd.Geometry.of(mat_e.dist)
    if g_a.mb != g_e.mb or g_a.pr != g_e.pr or g_a.mt != g_e.mt:
        raise ValueError("bt_reduction_to_band: E row distribution must match A")
    n_panels = int(taus.shape[0])
    band = int(taus.shape[1])
    if n_panels == 0 or g_e.nt == 0:
        return mat_e
    # taus replicated: stack to [Pr, Pc, n_panels, band].  Single-process
    # keeps the all-on-device broadcast (a host round-trip here would sync
    # on the tail of the reduction and serialize the pipeline); only the
    # multi-process world needs the host-staged placement (device_put cannot
    # reach other processes' devices).
    if jax.process_count() > 1:
        from dlaf_tpu.matrix.matrix import place

        taus_stacked = place(
            np.broadcast_to(np.asarray(taus), (g_a.pr, g_a.pc) + tuple(taus.shape)),
            mat_e.grid.stacked_sharding(),
        )
    else:
        taus_stacked = jnp.broadcast_to(
            taus[None, None], (g_a.pr, g_a.pc) + tuple(taus.shape)
        )
        taus_stacked = jax.device_put(taus_stacked, mat_e.grid.stacked_sharding())
    from dlaf_tpu.tune import get_tune_parameters, matmul_precision

    prec = get_tune_parameters().eigensolver_matmul_precision
    def build():
        kern = partial(_bt_r2b_kernel, g_a=g_a, g_e=g_e, n_panels=n_panels, band=band)
        return coll.spmd(mat_e.grid, kern, donate_argnums=(2,), name="bt_r2b")

    fn = _plan.cached(
        "bt_r2b", (mat_e.grid.cache_key, g_a, g_e, n_panels, band, prec), build
    )
    with matmul_precision(prec):
        return mat_e._inplace(fn(mat_band.data, taus_stacked, mat_e.data))
