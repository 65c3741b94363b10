"""Column-sharded eigenvector panels: the shared intermediate of the
row-transform back-transform stages.

Both band-stage back-transforms (``bt_band_hh`` grouped-WY and the SBR
``sbr_back_transform``) act on E's ROWS with independent columns, so each
stage reshards the stacked block-cyclic E to column panels over the flat
device order (``P(None, ('r','c'))``), loops locally, and reshards back.
Running them back-to-back through the stacked layout costs two redundant
all-to-all pairs (ROADMAP "fuse the column-sharded row-transform
stages"); this carrier lets the first stage hand its column-sharded
result straight to the second, which performs the single final pack.

(reference analogue: bt_band_to_tridiag/impl.h keeps E tiles in place and
p2p-exchanges rows per group; here the relayout IS the communication, so
eliding intermediate relayouts is the optimization.)
"""
from __future__ import annotations

from dataclasses import dataclass

import jax

from dlaf_tpu.comm.grid import Grid
from dlaf_tpu.matrix.distribution import Distribution
from dlaf_tpu.matrix.matrix import DistributedMatrix


@dataclass
class ColPanels:
    """``data[n_pad, kpad]`` column-sharded over the flat device order;
    ``(n, k)`` the live extent; ``dist`` the stacked block-cyclic
    distribution to pack back into."""

    data: jax.Array
    n: int
    k: int
    grid: Grid
    dist: Distribution


def pack_to_matrix(cp: ColPanels) -> DistributedMatrix:
    """One all-to-all: column panels -> stacked block-cyclic matrix."""
    from dlaf_tpu.matrix import layout

    # bind scalars locally: the cached closure must NOT capture cp (it
    # would pin cp.data, an E-sized device buffer, for the process life)
    n, k, dist = cp.n, cp.k, cp.dist
    from dlaf_tpu.plan import core as _plan

    grid = cp.grid

    def build():
        def post(gp):
            return layout.pack(layout.pad_global(gp[:n, :k], dist), dist)

        return _plan.jit("colpanels_pack", post, out_shardings=grid.stacked_sharding())

    fn = _plan.cached(
        "colpanels_pack",
        (grid.cache_key, dist, n, k, tuple(cp.data.shape), cp.data.dtype),
        build,
    )
    return DistributedMatrix(dist, grid, fn(cp.data))
