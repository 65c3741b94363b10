"""Windowed sub-matrix extraction / write-back at ARBITRARY element origins.

TPU-native analogue of the reference's non-tile-aligned ``MatrixRef`` views
(reference: include/dlaf/matrix/matrix_ref.h:39-182 — sub-matrix at any
element origin; matrix/views.h:26-187 — per-tile SubTileSpec offsets).

Under SPMD there is no pointer aliasing, so "viewing" a window whose origin
sits inside a tile becomes a *realignment*: every output tile is the
concatenation of (parts of) two ADJACENT parent tiles, and block-cyclic
ownership maps that fixed tile shift to a fixed RANK shift on the mesh axis.
Extraction is therefore O(window) local work plus four neighbor
``ppermute``s (two per axis) — never an O(N^2) global repack and never a
host round-trip.  The same algebra run backwards gives the write-back
(``window_update``), i.e. write-through views.

Index algebra (columns; rows symmetric).  Window origin ``c0 = a*nb + d``:
output col-tile ``j'`` (owned by rank ``j' % Pc``) takes cols ``d..nb`` of
parent tile ``a + j'`` and cols ``0..d`` of parent tile ``a + j' + 1`` —
both owned at the constant rank offsets ``a % Pc`` / ``(a+1) % Pc`` from the
output owner, with local slot ``l + (a + myc) // Pc``.
"""
from __future__ import annotations

from functools import partial

import jax.numpy as jnp
from jax import lax

from dlaf_tpu.comm import collectives as coll
from dlaf_tpu.comm.grid import COL_AXIS, ROW_AXIS
from dlaf_tpu.matrix.distribution import Distribution
from dlaf_tpu.matrix.matrix import DistributedMatrix
from dlaf_tpu.plan import core as _plan


def _reshard_rolled(data, src_grid, dst_grid, roll):
    """Move a stacked array from ``src_grid``'s mesh onto the rolled
    ``dst_grid`` (same devices, rolled order): one jitted roll on the source
    mesh does the physical block ppermute, then the buffers are re-wrapped
    under the destination sharding (matrix._relabel) — jax's device_put
    cannot reshard across device orders directly."""
    import jax

    from dlaf_tpu.matrix.matrix import _relabel

    sr, sc = roll
    fn = _plan.cached(
        "window_reshard",
        (src_grid.cache_key, roll, data.shape, str(data.dtype)),
        lambda: _plan.jit(
            "window_reshard",
            lambda x: jnp.roll(x, (sr, sc), (0, 1)),
            out_shardings=src_grid.stacked_sharding(),
        ),
    )
    return _relabel(fn(data), dst_grid.stacked_sharding())


def _axis_extract(x, *, axis, a, d, lt_out, n_out, nt_parent):
    """One-axis window realign of a local tile stack ``x[ltr, ltc, mb, nb]``.

    ``axis``: 0 = rows (mesh axis 'r', slot axis 0, element axis 2),
    1 = cols.  ``a``/``d``: first parent tile / in-tile element offset.
    ``lt_out``: output local slots; ``n_out``: output element extent;
    ``nt_parent``: parent tile count on this axis (for validity masks)."""
    mesh_axis = ROW_AXIS if axis == 0 else COL_AXIS
    slot_ax = axis
    elem_ax = axis + 2
    blk = x.shape[elem_ax]
    P = coll.grid_shape()[axis]
    me = coll.my_rank()[axis]
    lt_in = x.shape[slot_ax]

    # neighbor realign: rank i receives the stack of rank (i + a) % P
    y1 = coll.shift(x, mesh_axis, (-a) % P)
    y2 = coll.shift(x, mesh_axis, (-(a + 1)) % P)

    def gather_slots(y, first_tile):
        # output slot l wants parent tile first_tile + l*P + me
        jt = first_tile + jnp.arange(lt_out) * P + me
        slot = jt // P  # == l + (first_tile + me) // P, always >= 0
        valid = jt < nt_parent
        taken = jnp.take(y, jnp.clip(slot, 0, lt_in - 1), axis=slot_ax)
        vshape = [1] * x.ndim
        vshape[slot_ax] = lt_out
        return jnp.where(valid.reshape(vshape), taken, 0)

    p1 = gather_slots(y1, a)
    if d:
        p2 = gather_slots(y2, a + 1)
        lo = lax.slice_in_dim(p1, d, blk, axis=elem_ax)
        hi = lax.slice_in_dim(p2, 0, d, axis=elem_ax)
        out = jnp.concatenate([lo, hi], axis=elem_ax)
    else:
        out = p1
    # zero the slack: elements at/after n_out (also kills whole slack slots)
    jt = jnp.arange(lt_out) * P + me
    eidx = jt[:, None] * blk + jnp.arange(blk)[None, :]
    vshape = [1] * x.ndim
    vshape[slot_ax] = lt_out
    vshape[elem_ax] = blk
    return jnp.where((eidx < n_out).reshape(vshape), out, 0)


def _axis_update(xp, w, *, axis, a, d, n_win, nt_win, c0):
    """Inverse of :func:`_axis_extract` on one axis: overwrite the window
    ``[c0, c0 + n_win)`` of the parent stack ``xp`` with the (origin-0
    tiled) window stack ``w``; elements outside the window keep their
    parent values.  Parent tile ``p`` takes cols ``d..nb`` from window tile
    ``p - a`` and cols ``0..d`` from window tile ``p - a - 1``."""
    mesh_axis = ROW_AXIS if axis == 0 else COL_AXIS
    slot_ax = axis
    elem_ax = axis + 2
    blk = xp.shape[elem_ax]
    P = coll.grid_shape()[axis]
    me = coll.my_rank()[axis]
    lt_par = xp.shape[slot_ax]
    lt_win = w.shape[slot_ax]

    # rank i's parent tiles p = l*P + i need window tiles p - a (owner
    # (i - a) % P) and p - a - 1: realign the window stack the other way
    y1 = coll.shift(w, mesh_axis, a % P)
    y2 = coll.shift(w, mesh_axis, (a + 1) % P)

    def gather_slots(y, tile_off):
        # parent slot l wants window tile l*P + me - tile_off (may be < 0)
        jt = jnp.arange(lt_par) * P + me - tile_off
        slot = jnp.floor_divide(jt, P)
        valid = (jt >= 0) & (jt < nt_win)
        taken = jnp.take(y, jnp.clip(slot, 0, lt_win - 1), axis=slot_ax)
        vshape = [1] * xp.ndim
        vshape[slot_ax] = lt_par
        return jnp.where(valid.reshape(vshape), taken, 0)

    w1 = gather_slots(y1, a)  # window tile p - a: its cols 0..nb-d land at d..nb
    if d:
        w2 = gather_slots(y2, a + 1)  # window tile p-a-1: cols nb-d..nb land at 0..d
        lo = lax.slice_in_dim(w2, blk - d, blk, axis=elem_ax)
        hi = lax.slice_in_dim(w1, 0, blk - d, axis=elem_ax)
        shifted = jnp.concatenate([lo, hi], axis=elem_ax)
    else:
        shifted = w1
    # merge: only parent elements inside [c0, c0 + n_win) are replaced
    pt = jnp.arange(lt_par) * P + me
    eidx = pt[:, None] * blk + jnp.arange(blk)[None, :]
    inside = (eidx >= c0) & (eidx < c0 + n_win)
    vshape = [1] * xp.ndim
    vshape[slot_ax] = lt_par
    vshape[elem_ax] = blk
    return jnp.where(inside.reshape(vshape), shifted, xp)


def _extract_kernel(x, *, a_r, d_r, a_c, d_c, ltr_out, ltc_out, m_out, n_out,
                    mt_par, nt_par):
    x = coll.local(x)
    x = _axis_extract(x, axis=1, a=a_c, d=d_c, lt_out=ltc_out, n_out=n_out,
                      nt_parent=nt_par)
    x = _axis_extract(x, axis=0, a=a_r, d=d_r, lt_out=ltr_out, n_out=m_out,
                      nt_parent=mt_par)
    return coll.relocal(x)


def _update_kernel(xp, w, *, a_r, d_r, a_c, d_c, r0, c0, m_win, n_win,
                   mt_win, nt_win, ltr_mid):
    xp = coll.local(xp)
    w = coll.local(w)
    # rows first: produce an intermediate window stack aligned to the
    # parent's ROW tiling but still origin-0 in columns...
    # Simpler and equivalent: realign the window fully onto the parent's
    # tile grid axis by axis, merging at the end of each axis pass is NOT
    # possible (the row pass needs full parent-tiled rows).  So: expand the
    # window to parent row alignment (extract-style inverse on rows into a
    # zero background), then merge columns into the parent with the row
    # range restricted by the element mask of the row pass.
    w_rows = _axis_update(
        jnp.zeros((ltr_mid,) + w.shape[1:], w.dtype), w,
        axis=0, a=a_r, d=d_r, n_win=m_win, nt_win=mt_win, c0=r0,
    )
    # column merge into the parent, restricted to window rows
    merged = _axis_update(xp, w_rows, axis=1, a=a_c, d=d_c, n_win=n_win,
                          nt_win=nt_win, c0=c0)
    # _axis_update(axis=1) replaced FULL columns of the window's column
    # range; rows outside [r0, r0+m_win) must keep parent values
    P = coll.grid_shape()[0]
    me = coll.my_rank()[0]
    mb = xp.shape[2]
    pt = jnp.arange(xp.shape[0]) * P + me
    ridx = pt[:, None] * mb + jnp.arange(mb)[None, :]
    row_inside = (ridx >= r0) & (ridx < r0 + m_win)
    keep = row_inside.reshape((xp.shape[0], 1, mb, 1))
    out = jnp.where(keep, merged, xp)
    return coll.relocal(out)


def window_extract(mat: DistributedMatrix, origin, size) -> DistributedMatrix:
    """Extract ``mat[r0:r0+m, c0:c0+n]`` into a fresh origin-(0,0)
    DistributedMatrix — any element origin, O(window) device work."""
    r0, c0 = (int(v) for v in origin)
    m, n = (int(v) for v in size)
    if tuple(mat.dist.source_rank) != (0, 0):
        # zero-traffic re-labeling to origin (0,0) on the rolled grid; the
        # extracted window is origin-(0,0) anyway, so nothing to undo
        mat = mat.to_origin()
    if (
        r0 < 0 or c0 < 0
        or r0 + m > mat.size.rows or c0 + n > mat.size.cols
    ):
        raise ValueError(f"window {origin}+{size} out of bounds {tuple(mat.size)}")
    out_dist = Distribution((m, n), tuple(mat.dist.block_size), tuple(mat.dist.grid_size))
    if m == 0 or n == 0:
        return DistributedMatrix.zeros(mat.grid, (m, n), tuple(mat.dist.block_size), mat.dtype)
    mb, nb = mat.dist.block_size
    def build():
        kern = partial(
            _extract_kernel,
            a_r=r0 // mb, d_r=r0 % mb, a_c=c0 // nb, d_c=c0 % nb,
            ltr_out=out_dist.local_slots.rows, ltc_out=out_dist.local_slots.cols,
            m_out=m, n_out=n,
            mt_par=mat.dist.nr_tiles.rows, nt_par=mat.dist.nr_tiles.cols,
        )
        return coll.spmd(mat.grid, kern, name="window_extract")

    fn = _plan.cached(
        "window_extract", (mat.grid.cache_key, mat.dist, r0, c0, m, n), build
    )
    return DistributedMatrix(out_dist, mat.grid, fn(mat.data))


def window_update(mat: DistributedMatrix, origin, win: DistributedMatrix) -> DistributedMatrix:
    """Write ``win`` (an origin-(0,0) tiled matrix) into the window of
    ``mat`` at ``origin`` — the write-through half of a non-aligned view.
    Returns the updated parent (functional in-place)."""
    r0, c0 = (int(v) for v in origin)
    m, n = win.size
    if tuple(win.dist.source_rank) != (0, 0):
        # window content is origin-indexed either way, but to_origin lands
        # on the ROLLED mesh — reshard the blocks back onto the caller's
        # mesh (O(window) ppermute) so the merge combines same-mesh data
        if win.grid.cache_key != mat.grid.cache_key:
            raise ValueError(
                "window_update: win and mat must live on the same mesh (got "
                "different grids — data would combine across device orders)"
            )
        sw = tuple(win.dist.source_rank)
        pr, pc = win.grid.grid_size
        w0 = win.to_origin()
        data = _reshard_rolled(
            w0.data, w0.grid, win.grid, ((-sw[0]) % pr, (-sw[1]) % pc)
        )
        win = DistributedMatrix(w0.dist, win.grid, data)
    if tuple(mat.dist.source_rank) != (0, 0):
        # run on the origin re-labeling (zero traffic), move the window onto
        # the rolled mesh (REAL O(window) ppermute — the block placements
        # differ), and relabel the result back into the caller's
        # distribution so the in-place contract holds
        src = tuple(mat.dist.source_rank)
        if win.grid.cache_key != mat.grid.cache_key:
            raise ValueError(
                "window_update: win and mat must live on the same mesh (got "
                "different grids — data would combine across device orders)"
            )
        parent0 = mat.to_origin()
        win0 = DistributedMatrix(
            win.dist, parent0.grid, _reshard_rolled(win.data, mat.grid, parent0.grid, src)
        )
        upd = window_update(parent0, origin, win0)
        return mat._inplace(upd.with_source_rank(src, mat.grid).data)
    if win.grid.cache_key != mat.grid.cache_key:
        raise ValueError(
            "window_update: win and mat must live on the same mesh (got "
            "different grids — data would combine across device orders)"
        )
    if (
        r0 < 0 or c0 < 0
        or r0 + m > mat.size.rows or c0 + n > mat.size.cols
    ):
        raise ValueError(f"window {origin}+{(m, n)} out of bounds {tuple(mat.size)}")
    if tuple(win.dist.block_size) != tuple(mat.dist.block_size):
        raise ValueError("window_update: block sizes must match")
    if m == 0 or n == 0:
        return mat
    mb, nb = mat.dist.block_size
    def build():
        kern = partial(
            _update_kernel,
            a_r=r0 // mb, d_r=r0 % mb, a_c=c0 // nb, d_c=c0 % nb,
            r0=r0, c0=c0, m_win=m, n_win=n,
            mt_win=win.dist.nr_tiles.rows, nt_win=win.dist.nr_tiles.cols,
            ltr_mid=mat.dist.local_slots.rows,
        )
        return coll.spmd(mat.grid, kern, donate_argnums=(0,), name="window_update")

    fn = _plan.cached(
        "window_update", (mat.grid.cache_key, mat.dist, win.dist, r0, c0), build
    )
    return mat._inplace(fn(mat.data, win.data))
