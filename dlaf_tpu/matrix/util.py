"""Matrix-level utilities on stacked block-cyclic storage.

Analogues of reference helpers scattered through matrix/util_matrix.h and
lapack laset/lacpy tile loops: triangle extraction, diagonal set, elementwise
masks expressed directly on the stacked [Pr, Pc, ltr, ltc, mb, nb] array
(pure elementwise XLA ops — they stay sharded, no communication).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from dlaf_tpu.matrix.distribution import Distribution
from dlaf_tpu.matrix.matrix import DistributedMatrix


def _global_element_grids(dist: Distribution):
    """Broadcastable global (row, col) element indices for the stacked shape."""
    pr, pc = dist.grid_size
    ltr, ltc = dist.local_slots
    mb, nb = dist.block_size
    sr, sc = dist.source_rank
    r = jnp.arange(pr).reshape(pr, 1, 1, 1, 1, 1)
    c = jnp.arange(pc).reshape(1, pc, 1, 1, 1, 1)
    li = jnp.arange(ltr).reshape(1, 1, ltr, 1, 1, 1)
    lj = jnp.arange(ltc).reshape(1, 1, 1, ltc, 1, 1)
    a = jnp.arange(mb).reshape(1, 1, 1, 1, mb, 1)
    b = jnp.arange(nb).reshape(1, 1, 1, 1, 1, nb)
    gi = (li * pr + (r - sr) % pr) * mb + a
    gj = (lj * pc + (c - sc) % pc) * nb + b
    return gi, gj


@partial(jax.jit, static_argnums=(1, 2, 3))
def _triangle_data(x, dist: Distribution, uplo: str, k: int):
    gi, gj = _global_element_grids(dist)
    # np convention: tril keeps i >= j - k, triu keeps i <= j - k
    keep = (gi >= gj - k) if uplo == "L" else (gi <= gj - k)
    return jnp.where(keep, x, jnp.zeros_like(x))


def extract_triangle(mat: DistributedMatrix, uplo: str, k: int = 0) -> DistributedMatrix:
    """Return a copy with only the ``uplo`` triangle kept (diagonal offset
    ``k`` as in np.tril/triu)."""
    return mat.like(_triangle_data(mat.data, mat.dist, uplo, k))


def _transpose_data(x, dist: Distribution, dist_t: Distribution, conj: bool):
    from dlaf_tpu.matrix import layout

    # unpad before transposing: source and target padded extents differ in
    # general (e.g. 8x16 padded vs 16x8) even though element counts match
    g = layout.unpad_global(layout.unpack(x, dist), dist)
    gt = jnp.swapaxes(g, 0, 1)
    if conj:
        gt = gt.conj()
    return layout.pack(layout.pad_global(gt, dist_t), dist_t)


def transpose(mat: DistributedMatrix, conj: bool = False) -> DistributedMatrix:
    """Distributed (conjugate) transpose.

    Expressed as unpack -> global transpose -> repack, all inside one jit:
    XLA lowers the resharding to an all-to-all over the mesh.  (The reference
    has no full transpose; its transposed panels are the per-step
    broadcast_panel trick — see collectives.transpose_panel.)"""
    d = mat.dist
    dist_t = Distribution(
        (d.size.cols, d.size.rows),
        (d.block_size.cols, d.block_size.rows),
        d.grid_size,
        (d.source_rank.col, d.source_rank.row),
    )
    if mat.data.size == 0:  # XLA overrides empty-output shardings to replicated
        return DistributedMatrix.zeros(
            mat.grid, dist_t.size, dist_t.block_size, mat.dtype, dist_t.source_rank
        )
    # out_shardings (not a post-hoc device_put): the compiled program ends in
    # the resharding collective itself, which also works on multi-process
    # worlds where device_put cannot reach non-addressable devices
    from dlaf_tpu.plan import core as _plan

    fn = _plan.jit(
        "transpose",
        partial(_transpose_data, dist=d, dist_t=dist_t, conj=conj),
        out_shardings=mat.grid.stacked_sharding(),
    )
    out = fn(mat.data)
    return DistributedMatrix(dist_t, mat.grid, out)


def hermitize(mat: DistributedMatrix, uplo: str) -> DistributedMatrix:
    """Build full Hermitian storage from the ``uplo`` triangle (the other
    triangle's stored values are ignored)."""
    if mat.size.rows != mat.size.cols:
        raise ValueError("hermitize: matrix must be square")
    tri = extract_triangle(mat, uplo)
    strict = extract_triangle(mat, uplo, k=-1 if uplo == "L" else 1)
    mirror = transpose(strict, conj=True)
    return mat.like(tri.data + mirror.data)


@partial(jax.jit, static_argnums=(1, 4))
def _set_diag_data(x, dist: Distribution, alpha, beta, overwrite_all: bool):
    gi, gj = _global_element_grids(dist)
    m, n = dist.size
    inside = (gi < m) & (gj < n)
    diag = (gi == gj) & inside
    if overwrite_all:
        off = jnp.where(inside, jnp.full_like(x, alpha), jnp.zeros_like(x))
        return jnp.where(diag, jnp.full_like(x, beta), off)
    return jnp.where(diag, jnp.full_like(x, beta), x)


def retile(mat: DistributedMatrix, new_block_size) -> DistributedMatrix:
    """Re-tile to a different block size (reference:
    Matrix::retiledSubPipeline, matrix/matrix.h:560-618 — there an in-place
    tile sub-split; here a relayout through the global form, one all-to-all
    under jit)."""
    from functools import partial as _p

    import jax as _jax

    from dlaf_tpu.matrix import layout
    from dlaf_tpu.matrix.distribution import Distribution as _D

    new_dist = _D(mat.size, new_block_size, mat.dist.grid_size, mat.dist.source_rank)
    if mat.data.size == 0 or not all(DistributedMatrix.stacked_shape(new_dist)):
        return DistributedMatrix.zeros(
            mat.grid, new_dist.size, new_dist.block_size, mat.dtype, new_dist.source_rank
        )

    @_p(_jax.jit, static_argnums=(1, 2), out_shardings=mat.grid.stacked_sharding())
    def _relayout(x, d_old, d_new):
        g = layout.unpad_global(layout.unpack(x, d_old), d_old)
        return layout.pack(layout.pad_global(g, d_new), d_new)

    data = _relayout(mat.data, mat.dist, new_dist)
    return DistributedMatrix(new_dist, mat.grid, data)


def sub_matrix(mat: DistributedMatrix, origin, size) -> DistributedMatrix:
    """Sub-matrix copy at ANY element origin (reference: MatrixRef sub-matrix
    view, matrix/matrix_ref.h:39).  Multi-device grids take the O(window)
    ppermute realignment of :mod:`dlaf_tpu.matrix.window` (nonzero source
    ranks are re-labeled to origin first — zero traffic,
    DistributedMatrix.to_origin); 1x1 grids slice the global form under
    jit."""
    from functools import partial as _p

    import jax as _jax

    from dlaf_tpu.matrix import layout
    from dlaf_tpu.matrix.distribution import Distribution as _D

    origin = tuple(int(v) for v in origin)
    size = tuple(int(v) for v in size)
    if (
        origin[0] < 0
        or origin[1] < 0
        or origin[0] + size[0] > mat.size.rows
        or origin[1] + size[1] > mat.size.cols
    ):
        raise ValueError(f"sub-matrix {origin}+{size} out of bounds {tuple(mat.size)}")
    if mat.grid.grid_size.count() > 1:
        # any source rank: window_extract re-labels to origin (0,0) first
        # (DistributedMatrix.to_origin, zero traffic)
        from dlaf_tpu.matrix.window import window_extract

        return window_extract(mat, origin, size)
    out_dist = _D(size, mat.dist.block_size, mat.dist.grid_size)
    if mat.data.size == 0 or not all(DistributedMatrix.stacked_shape(out_dist)):
        return DistributedMatrix.zeros(
            mat.grid, out_dist.size, out_dist.block_size, mat.dtype
        )

    @_p(
        _jax.jit,
        static_argnums=(1, 2, 3),
        static_argnames=(),
        out_shardings=mat.grid.stacked_sharding(),
    )
    def _slice(x, d_old, d_new, org):
        g = layout.unpad_global(layout.unpack(x, d_old), d_old)
        s = g[org[0] : org[0] + d_new.size.rows, org[1] : org[1] + d_new.size.cols]
        return layout.pack(layout.pad_global(s, d_new), d_new)

    data = _slice(mat.data, mat.dist, out_dist, tuple(origin))
    return DistributedMatrix(out_dist, mat.grid, data)


def laset(mat: DistributedMatrix, alpha, beta) -> DistributedMatrix:
    """Set all elements to alpha, diagonal to beta (lapack laset analogue)."""
    return mat.like(_set_diag_data(mat.data, mat.dist, alpha, beta, True))


def set_diagonal(mat: DistributedMatrix, beta) -> DistributedMatrix:
    return mat.like(_set_diag_data(mat.data, mat.dist, 0.0, beta, False))


def eye_like(mat: DistributedMatrix) -> DistributedMatrix:
    return laset(mat, 0.0, 1.0)
