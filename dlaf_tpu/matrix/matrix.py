"""Distributed matrix on a 2D device grid.

TPU-native analogue of ``dlaf::matrix::Matrix<T, Device>``
(reference: include/dlaf/matrix/matrix.h:62-630).  The reference Matrix owns a
``Distribution`` plus one async pipeline per local tile — the pipelines ARE
its dependency system.  Here dependencies are XLA program order, so the matrix
is just ``Distribution`` + one stacked device array
``data[Pr, Pc, ltr, ltc, mb, nb]`` sharded ``P('r','c')`` over the grid mesh
(see layout.py).  ``read()/readwrite()`` tile senders have no analogue;
algorithms consume ``data`` inside ``shard_map``/``jit`` and return new
arrays (functional style), with input donation providing in-place behavior.

Host-side convenience accessors (``set_tile``/``get_tile``/``to_global``) are
for tests and I/O, mirroring the reference test utilities
(test/include/dlaf_test/matrix/util_matrix.h).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dlaf_tpu.comm.grid import Grid
from dlaf_tpu.common.index import Index2D, Size2D
from dlaf_tpu.matrix import layout
from dlaf_tpu.matrix.distribution import Distribution


def place(x, sharding) -> jax.Array:
    """Place a host array under ``sharding``, multi-process safe.

    ``jax.device_put`` only reaches addressable devices; on a multi-process
    world each process contributes its shards via
    ``jax.make_array_from_callback`` (every process must hold the same host
    content — the reference's per-rank element-init makes the same
    assumption)."""
    if jax.process_count() > 1:
        x = np.asarray(x)
        return jax.make_array_from_callback(x.shape, sharding, lambda idx: x[idx])
    return jax.device_put(jnp.asarray(x), sharding)


def _relabel(x: jax.Array, sharding) -> jax.Array:
    """Re-wrap an array's EXISTING per-device buffers under a sharding over
    a reordered mesh of the same devices — zero copies, zero collectives
    (``device_put``/jit out_shardings both reject cross-order resharding).
    Only valid when the caller guarantees each device's shard content is
    the same under both labelings (the Grid.rolled identity)."""
    arrs = [s.data for s in x.addressable_shards]
    return jax.make_array_from_single_device_arrays(x.shape, sharding, arrs)


def _replicate_fn(grid: Grid):
    """Cached jitted identity with fully-replicated output sharding (one
    compile per mesh, not per to_global call)."""
    from dlaf_tpu.plan import core as _plan

    return _plan.cached(
        "replicate",
        (grid.cache_key,),
        lambda: _plan.jit("replicate", lambda v: v,
                          out_shardings=grid.replicated_sharding()),
    )


class DistributedMatrix:
    """A dense ``m x n`` matrix, 2D block-cyclic over ``grid``.

    ``data`` holds every local tile of every rank, stacked:
    ``data[r, c, li, lj]`` is the ``mb x nb`` tile with global tile index
    ``dist.global_tile_from_local((li, lj), (r, c))``; slots past the edge are
    zero-padded (uniform extents across ranks — SURVEY §7 "block-cyclic as
    library-level bookkeeping over an even shard").
    """

    def __init__(self, dist: Distribution, grid: Grid, data: jax.Array):
        if dist.grid_size != grid.grid_size:
            raise ValueError(f"distribution grid {dist.grid_size} != device grid {grid.grid_size}")
        expect = self.stacked_shape(dist)
        if tuple(data.shape) != expect:
            raise ValueError(f"data shape {data.shape}, expected {expect}")
        self.dist = dist
        self.grid = grid
        self.data = data

    # --- geometry -----------------------------------------------------------
    @staticmethod
    def stacked_shape(dist: Distribution):
        pr, pc = dist.grid_size
        ltr, ltc = dist.local_slots
        mb, nb = dist.block_size
        return (pr, pc, ltr, ltc, mb, nb)

    @property
    def size(self) -> Size2D:
        return self.dist.size

    @property
    def block_size(self) -> Size2D:
        return self.dist.block_size

    @property
    def nr_tiles(self) -> Size2D:
        return self.dist.nr_tiles

    @property
    def dtype(self):
        return self.data.dtype

    # --- constructors --------------------------------------------------------
    @classmethod
    def zeros(
        cls, grid: Grid, size, block_size, dtype=jnp.float32, source_rank=(0, 0)
    ) -> "DistributedMatrix":
        dist = Distribution(Size2D(*size), Size2D(*block_size), grid.grid_size, Index2D(*source_rank))
        shape = cls.stacked_shape(dist)
        sharding = grid.stacked_sharding()
        if jax.process_count() > 1:
            data = jax.make_array_from_callback(
                shape,
                sharding,
                lambda idx: np.zeros(
                    tuple(len(range(*s.indices(d)))
                          for s, d in zip(idx, shape, strict=True)),
                    dtype=np.dtype(dtype),
                ),
            )
        else:
            data = jax.device_put(jnp.zeros(shape, dtype=dtype), sharding)
        return cls(dist, grid, data)

    @classmethod
    def from_global(
        cls, grid: Grid, a, block_size, source_rank=(0, 0)
    ) -> "DistributedMatrix":
        """Distribute a host/global (m, n) array (pads, packs, places).

        Multi-host: every process must pass the SAME global array (the
        reference's per-rank element initialization makes the same
        assumption); each process then places only its addressable shards
        (``jax.make_array_from_callback``)."""
        a = np.asarray(a)
        dist = Distribution(
            Size2D(*a.shape), Size2D(*block_size), grid.grid_size, Index2D(*source_rank)
        )
        x = layout.pack(layout.pad_global(a, dist), dist)
        return cls(dist, grid, place(x, grid.stacked_sharding()))

    @classmethod
    def from_element_function(
        cls,
        grid: Grid,
        size,
        block_size,
        el: Callable[[np.ndarray, np.ndarray], np.ndarray],
        dtype=jnp.float32,
        source_rank=(0, 0),
    ) -> "DistributedMatrix":
        """Initialize from an element function ``el(i, j)`` evaluated on global
        indices (vectorized).  Mirrors the reference test-harness ``set(matrix,
        el)`` (test/include/dlaf_test/matrix/util_matrix.h)."""
        m, n = Size2D(*size)
        i, j = np.meshgrid(np.arange(m), np.arange(n), indexing="ij")
        a = np.asarray(el(i, j), dtype=np.dtype(dtype)) if m and n else np.zeros((m, n), np.dtype(dtype))
        return cls.from_global(grid, a.astype(np.dtype(dtype)), block_size, source_rank)

    def like(self, data: Optional[jax.Array] = None) -> "DistributedMatrix":
        return DistributedMatrix(self.dist, self.grid, self.data if data is None else data)

    def astype(self, dtype) -> "DistributedMatrix":
        """Copy with the data cast to ``dtype`` (same distribution/grid).
        Always a fresh buffer (even for a same-dtype cast) — safe to hand
        to donating algorithms."""
        dt = np.dtype(dtype)
        if dt == np.dtype(self.dtype):
            return self.like(jnp.copy(self.data))
        return self.like(self.data.astype(dt))

    def to_origin(self) -> "DistributedMatrix":
        """The same matrix re-labeled to source_rank (0, 0) over
        ``grid.rolled(sr, sc)`` — ZERO cross-device traffic: tile (g_r, g_c)
        of a source-(sr, sc) distribution lives on device
        ((g_r + sr) % Pr, ...), exactly where the rolled grid's origin-(0,0)
        distribution places it, so only the stacked-axis labeling rolls
        (each output shard is the input shard already resident on its
        device; asserted collective-free by tests/test_matrix.py).
        This is how nonzero source ranks reach the SPMD kernels
        (reference analogue: Distribution::source_rank_index offsets,
        matrix/distribution.h:115-137)."""
        sr, sc = self.dist.source_rank
        if (sr, sc) == (0, 0):
            return self
        rolled = self.grid.rolled(sr, sc)
        dist0 = Distribution(self.dist.size, self.dist.block_size, self.dist.grid_size)
        return DistributedMatrix(dist0, rolled, _relabel(self.data, rolled.stacked_sharding()))

    def with_source_rank(self, source_rank, grid: Grid) -> "DistributedMatrix":
        """Inverse of :func:`to_origin`: re-label an origin-(0, 0) matrix on
        a rolled grid back to ``source_rank`` on ``grid`` (zero traffic,
        same shard-residency argument)."""
        sr, sc = Index2D(*source_rank)
        if (sr, sc) == (0, 0):
            return self
        dist = Distribution(self.dist.size, self.dist.block_size, self.dist.grid_size, Index2D(sr, sc))
        return DistributedMatrix(dist, grid, _relabel(self.data, grid.stacked_sharding()))

    def _inplace(self, data: jax.Array) -> "DistributedMatrix":
        """In-place result semantics for algorithms that donate this matrix's
        buffer (reference algorithms mutate their input Matrix): repoint this
        object at the result so the caller's handle stays valid, and return a
        fresh handle to the same data."""
        self.data = data
        return DistributedMatrix(self.dist, self.grid, data)

    # --- host-side access (tests / IO) ---------------------------------------
    def to_global(self) -> np.ndarray:
        """Gather the full matrix to host (reference: test util ``gather``).

        Multi-host: the stacked array is first replicated across processes
        (an all-gather over ICI/DCN inside jit), then read from local
        shards — every process returns the full matrix."""
        if jax.process_count() > 1:
            gathered = _replicate_fn(self.grid)(self.data)
            x = np.asarray(gathered.addressable_data(0))
        else:
            x = np.asarray(jax.device_get(self.data))
        return np.asarray(layout.unpad_global(layout.unpack(x, self.dist), self.dist))

    def get_tile(self, gt) -> np.ndarray:
        if jax.process_count() > 1:
            raise NotImplementedError(
                "get_tile indexes local shards and is single-process only; "
                "on a multi-host world use to_global() (replicated gather)"
            )
        gt = Index2D(*gt)
        r, c = self.dist.rank_global_tile(gt)
        li, lj = self.dist.local_tile_index(gt)
        t = np.asarray(jax.device_get(self.data[r, c, li, lj]))
        ts = self.dist.tile_size_of(gt)
        return t[: ts.rows, : ts.cols]

    def set_tile(self, gt, value: np.ndarray) -> None:
        if jax.process_count() > 1:
            raise NotImplementedError(
                "set_tile updates local shards and is single-process only; "
                "on a multi-host world rebuild with from_global()"
            )
        gt = Index2D(*gt)
        r, c = self.dist.rank_global_tile(gt)
        li, lj = self.dist.local_tile_index(gt)
        ts = self.dist.tile_size_of(gt)
        mb, nb = self.dist.block_size
        buf = np.zeros((mb, nb), dtype=self.data.dtype)
        buf[: ts.rows, : ts.cols] = value
        self.data = self.data.at[r, c, li, lj].set(jnp.asarray(buf))

    def __repr__(self):
        return (
            f"DistributedMatrix({self.size.rows}x{self.size.cols}, "
            f"tiles {self.block_size.rows}x{self.block_size.cols}, grid {self.grid})"
        )
