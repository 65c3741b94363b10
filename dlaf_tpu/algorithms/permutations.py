"""Distributed row/column permutations.

TPU-native analogue of the reference permutations
(reference: include/dlaf/permutations/general/api.h:22-33 Permutations::call,
impl.h 659-LoC distributed all-to-all path + perms.cu:1-98 batched device
gather).  The distributed kernel here is a RING permutation inside
``shard_map``: a row permutation never moves data across the column axis,
so each device rotates the row-stacks of its grid COLUMN around the 'r'
ring (``lax.ppermute`` over ICI neighbor links, Pr-1 hops) and, at each
hop, gathers the rows whose source rank is currently resident into its
local output — per-device memory stays at 3 local blocks (own + rotating
buffer + output) regardless of N, and no global N x N intermediate ever
exists (asserted by the HLO test, tests/test_aux.py).  The permutation
vector is a traced operand: a new ordering does not recompile.

Used on real paths: refine_eigenpairs' final eigenvalue reorder
(eig_refine.py) and the partial-spectrum column selection.
"""
from __future__ import annotations

from dlaf_tpu.algorithms._origin import origin_transparent

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from dlaf_tpu.algorithms._spmd import Geometry
from dlaf_tpu.comm import collectives as coll
from dlaf_tpu.comm.grid import COL_AXIS, ROW_AXIS
from dlaf_tpu.matrix import layout
from dlaf_tpu.matrix.matrix import DistributedMatrix
from dlaf_tpu.plan import core as _plan


@partial(jax.jit, static_argnums=(2, 3))
def _permute_data_global(x, perm, dist, coord):
    """Single-device fallback: global take under jit (no mesh axes)."""
    g = layout.unpad_global(layout.unpack(x, dist), dist)
    g = jnp.take(g, perm, axis=0 if coord == "rows" else 1)
    return layout.pack(layout.pad_global(g, dist), dist)


def _permute_rows_kernel(x, perm, g: Geometry):
    """shard_map body: out rows gathered over a Pr-step ring rotation.

    ``x``: local [1, 1, ltr, ltc, mb, nb]; ``perm``: replicated [m]."""
    x = coll.local(x)
    myr, _ = coll.my_rank()
    li = jnp.arange(g.ltr)
    a = jnp.arange(g.mb)
    # global OUT row of local slot (li, a), and its source row perm[...]
    gout = (li * g.pr + myr)[:, None] * g.mb + a[None, :]  # [ltr, mb]
    valid = gout < g.m
    src = jnp.where(valid, perm[jnp.clip(gout, 0, max(g.m - 1, 0))], 0)
    st = src // g.mb  # source global tile row
    owner = st % g.pr  # rank whose stack holds it
    lrow = (st // g.pr) * g.mb + src % g.mb  # row index in that stack
    nrows = g.ltr * g.mb
    lrow = jnp.clip(lrow, 0, nrows - 1)

    # static unroll over the (small, compile-time) ring length: lets XLA
    # schedule gathers against the next hop's ppermute, and naturally drops
    # the final rotation (a fori_loop body would pay one dead collective)
    buf, out = x, jnp.zeros_like(x)
    for t in range(g.pr):
        rr = (myr + t) % g.pr
        # stack rows in global-row order within this rank: [ltr*mb, ltc, nb]
        rows = buf.transpose(0, 2, 1, 3).reshape(nrows, g.ltc, g.nb)
        got = rows[lrow]  # [ltr, mb, ltc, nb]
        take = (owner == rr) & valid
        out = out + jnp.where(take[:, :, None, None], got, 0).transpose(0, 2, 1, 3)
        if t < g.pr - 1:
            # rotate: device r receives rank r+1's stack next step
            buf = coll.shift(buf, ROW_AXIS, -1)
    return coll.relocal(out)


def _permute_cols_kernel(x, perm, g: Geometry):
    """Column analogue: rotation around the 'c' ring."""
    x = coll.local(x)
    _, myc = coll.my_rank()
    lj = jnp.arange(g.ltc)
    b = jnp.arange(g.nb)
    gout = (lj * g.pc + myc)[:, None] * g.nb + b[None, :]  # [ltc, nb]
    valid = gout < g.n
    src = jnp.where(valid, perm[jnp.clip(gout, 0, max(g.n - 1, 0))], 0)
    st = src // g.nb
    owner = st % g.pc
    lcol = (st // g.pc) * g.nb + src % g.nb
    ncols = g.ltc * g.nb
    lcol = jnp.clip(lcol, 0, ncols - 1)

    buf, out = x, jnp.zeros_like(x)
    for t in range(g.pc):  # static unroll, as in the rows kernel
        cc = (myc + t) % g.pc
        cols = buf.transpose(1, 3, 0, 2).reshape(ncols, g.ltr, g.mb)
        got = cols[lcol]  # [ltc, nb, ltr, mb]
        take = (owner == cc) & valid
        out = out + jnp.where(take[:, :, None, None], got, 0).transpose(2, 0, 3, 1)
        if t < g.pc - 1:
            buf = coll.shift(buf, COL_AXIS, -1)
    return coll.relocal(out)


def _ring_fn(grid, dist, coord):
    g = Geometry.of(dist)

    def build():
        kern = _permute_rows_kernel if coord == "rows" else _permute_cols_kernel
        stacked = P(ROW_AXIS, COL_AXIS)
        sm = jax.shard_map(
            partial(kern, g=g),
            mesh=grid.mesh,
            in_specs=(stacked, P()),
            out_specs=stacked,
            check_vma=False,
        )
        return _plan.jit("permute_ring", sm)

    return _plan.cached("permute_ring", (grid.cache_key, g, coord), build)


@origin_transparent
def permute(mat: DistributedMatrix, perm, coord: str = "rows") -> DistributedMatrix:
    """Gather-permutation: rows -> out[i, :] = in[perm[i], :];
    cols -> out[:, j] = in[:, perm[j]]."""
    n = mat.size.rows if coord == "rows" else mat.size.cols
    perm = jnp.asarray(np.asarray(perm), jnp.int32)
    if perm.shape != (n,):
        raise ValueError(f"perm must have shape ({n},), got {perm.shape}")
    if coord not in ("rows", "cols"):
        raise ValueError(f"coord must be 'rows' or 'cols', got {coord}")
    if (
        mat.grid.grid_size.count() == 1
        or n == 0
        or tuple(mat.dist.source_rank) != (0, 0)
    ):
        # single device or empty: global take under jit.  The source-rank
        # guard is defensive only — @origin_transparent re-labels nonzero
        # source ranks onto the rolled grid before this body runs, so the
        # ring kernel (whose index algebra assumes origin (0, 0)) always
        # sees (0, 0); the guard stays for direct internal callers that
        # bypass the decorator
        return mat.like(_permute_data_global(mat.data, perm, mat.dist, coord))
    return mat.like(_ring_fn(mat.grid, mat.dist, coord)(mat.data, perm))
