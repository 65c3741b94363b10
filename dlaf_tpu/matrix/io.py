"""Matrix I/O: save/load distributed matrices.

TPU-native analogue of the reference HDF5 matrix I/O
(reference: include/dlaf/matrix/hdf5.h:94-308 FileHDF5 — per-rank hyperslab
read/write, used by debug dumps and miniapp --input-file).  Three formats:

- ``.h5`` (h5py): the reference's own format — one dataset per matrix.
  BOTH paths stream tile-row slabs (<= 2 x mb x N host staging, the
  single-controller hyperslab analogue of the reference's per-rank
  N^2/P reads): the write path fetches one tile-row stack per slab, the
  read path places each hyperslab into the donated device array under jit.
- ``.npz``: global array + distribution metadata in one file.
- sharded ``.npy``: one file per grid rank holding its local tile stack.

``save``/``load`` pick by extension.
"""
from __future__ import annotations

import os

import numpy as np

from dlaf_tpu.comm.grid import Grid
from dlaf_tpu.common.index import Size2D
from dlaf_tpu.matrix.matrix import DistributedMatrix, place


def maybe_dump(flag_name: str, path: str, mat: DistributedMatrix) -> None:
    """Debug-dump hook: save ``mat`` when the tune flag is set
    (reference debug_dump_* flags, tune.h:30-67)."""
    from dlaf_tpu.tune import get_tune_parameters

    if getattr(get_tune_parameters(), flag_name):
        save(path, mat)


def save(path: str, mat: DistributedMatrix) -> None:
    """Save a matrix + metadata; format by extension (.h5 -> HDF5)."""
    if str(path).endswith((".h5", ".hdf5")):
        return save_hdf5(path, mat)
    np.savez_compressed(
        path,
        data=mat.to_global(),
        block_size=np.asarray(tuple(mat.block_size)),
        grid_size=np.asarray(tuple(mat.dist.grid_size)),
    )


def load(path: str, grid: Grid, block_size=None) -> DistributedMatrix:
    if str(path).endswith((".h5", ".hdf5")):
        return load_hdf5(path, grid, block_size=block_size)
    with np.load(path) as z:
        a = z["data"]
        bs = tuple(z["block_size"]) if block_size is None else tuple(block_size)
    return DistributedMatrix.from_global(grid, a, Size2D(*bs))


def load_global(path: str, name: str = "a") -> np.ndarray:
    """Read just the HOST global array from a matrix file — the one place
    that knows the format contract (.h5/.hdf5 dataset ``name``; .npz key
    'data'); used by miniapp ``--input-file``."""
    if str(path).endswith((".h5", ".hdf5")):
        import h5py

        with h5py.File(path, "r") as f:
            return f[name][()]
    with np.load(path) as z:
        return z["data"]


def _row_fetch_fn(grid: Grid, shape, dtype):
    """Jitted REPLICATED fetch of one tile-ROW stack [Pc, ltc, mb, nb] at
    traced (rr, li) — the mirror of :func:`_row_update_fn`.  The replicated
    out_sharding makes the gather a collective every process dispatches and
    the result addressable everywhere, so the write path stays correct on
    multi-process worlds (plain ``np.asarray(mat.data[...])`` would try to
    materialize non-addressable shards there)."""
    import jax
    from jax import lax

    from dlaf_tpu.plan import core as _plan

    def build():
        def fetch(x, rr, li):
            z = np.int32(0)  # starts must share one integer type
            row = lax.dynamic_slice(
                x,
                (rr, z, li, z, z, z),
                (1, shape[1], 1, shape[3], shape[4], shape[5]),
            )
            return row[0, :, 0]

        return _plan.jit(
            "io_row_fetch",
            fetch,
            in_shardings=(grid.stacked_sharding(), None, None),
            out_shardings=grid.replicated_sharding(),
        )

    return _plan.cached(
        "io_row_fetch", (grid.cache_key, shape, str(np.dtype(dtype))), build
    )


def save_hdf5(path: str, mat: DistributedMatrix, name: str = "a",
              attrs: dict | None = None, datasets: dict | None = None) -> None:
    """Write to an HDF5 dataset ``name`` of global shape (reference
    FileHDF5::write, matrix/hdf5.h:94-308).  Streams one tile-row slab at a
    time — a single device fetch of that row's tile stack per slab, <= mb x N
    host staging, never the full N^2; block/grid geometry is attached as
    dataset attributes so a read can reproduce the distribution.
    ``attrs`` adds caller attributes to the dataset and ``datasets`` adds
    sibling datasets from host arrays (``resilience.save_checkpoint`` rides
    these for its panel index / taus stack), all in the same single rank-0
    write pass.

    COLLECTIVE on multi-process worlds: every process must call it (the
    per-slab gathers are collectives); only process 0 touches the file, and
    all processes synchronize before returning."""
    import h5py
    import jax

    m, n = mat.size
    mb, nb = mat.block_size
    pr, pc = mat.dist.grid_size
    sr, sc = mat.dist.source_rank
    multi = jax.process_count() > 1
    write = jax.process_index() == 0
    fetch = _row_fetch_fn(mat.grid, tuple(mat.data.shape), mat.dtype)
    f = h5py.File(path, "w") if write else None
    try:
        if write:
            ds = f.create_dataset(name, shape=(m, n), dtype=np.dtype(mat.dtype))
            ds.attrs["block_size"] = tuple(mat.block_size)
            ds.attrs["grid_size"] = tuple(mat.dist.grid_size)
            ds.attrs["source_rank"] = (sr, sc)
            for k, v in (attrs or {}).items():
                ds.attrs[k] = v
            for dname, arr in (datasets or {}).items():
                f.create_dataset(dname, data=np.asarray(arr))
        for i in range(mat.nr_tiles.rows):
            r0 = i * mb
            rows = min(mb, m - r0)
            # ONE device round-trip per tile row: the whole [Pc, ltc, mb, nb]
            # stack of owner row (i%pr + sr) % pr at slot i//pr
            # int32 indices: under x64, weak Python ints trace as s64 and the
            # spmd partitioner's s32 offset math fails HLO verification
            row_stack = np.asarray(
                fetch(mat.data, np.int32((i % pr + sr) % pr), np.int32(i // pr))
            )
            if not write:
                continue
            slab = np.empty((rows, n), dtype=np.dtype(mat.dtype))
            for j in range(mat.nr_tiles.cols):
                c0 = j * nb
                cols = min(nb, n - c0)
                t = row_stack[(j % pc + sc) % pc, j // pc]
                slab[:, c0 : c0 + cols] = t[:rows, :cols]
            ds[r0 : r0 + rows] = slab
    finally:
        if f is not None:
            f.close()
    if multi:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices("dlaf_tpu.matrix.io.save_hdf5")


def _row_update_fn(grid: Grid, shape, dtype):
    """Jitted donated update placing one tile-ROW stack [Pc, ltc, mb, nb]
    into the stacked array at traced (rr, li) — one compile serves every
    tile row (dynamic_update_slice, not static indices)."""
    import jax
    from jax import lax

    from dlaf_tpu.plan import core as _plan

    def build():
        def upd(x, row, rr, li):
            z = np.int32(0)  # starts must share one integer type
            return lax.dynamic_update_slice(
                x, row[None, :, None], (rr, z, li, z, z, z)
            )

        return _plan.jit(
            "io_row_update",
            upd,
            donate_argnums=(0,),
            in_shardings=(
                grid.stacked_sharding(),
                grid.replicated_sharding(),
                None,
                None,
            ),
            out_shardings=grid.stacked_sharding(),
        )

    return _plan.cached(
        "io_row_update", (grid.cache_key, shape, str(np.dtype(dtype))), build
    )


def load_hdf5(
    path: str, grid: Grid, name: str = "a", block_size=None
) -> DistributedMatrix:
    """Read an HDF5 dataset into a DistributedMatrix (reference
    FileHDF5::read, matrix/hdf5.h:94-308 — per-rank hyperslab reads).
    ``block_size=None`` takes the stored attribute (falling back to tune's
    default_block_size for foreign files).

    STREAMS tile-row slabs, mirroring the write path: host staging is
    <= 2 x (mb x N) (one hyperslab + its packed stack) regardless of N —
    never a controller O(N^2) buffer (asserted by a tracemalloc probe in
    tests/test_scalapack_io.py); each slab is placed into the donated
    device array under jit, so device memory is the matrix itself."""
    import h5py

    with h5py.File(path, "r") as f:
        ds = f[name]
        if block_size is None:
            if "block_size" in ds.attrs:
                block_size = tuple(int(v) for v in ds.attrs["block_size"])
            else:
                from dlaf_tpu.tune import get_tune_parameters

                b = int(get_tune_parameters().default_block_size)
                block_size = (b, b)
        src = tuple(int(v) for v in ds.attrs.get("source_rank", (0, 0)))
        # source_rank only reproducible on a matching grid shape
        pr, pc = grid.grid_size
        src = (src[0] % pr, src[1] % pc)
        m, n = ds.shape
        mb, nb = Size2D(*block_size)
        dtype = ds.dtype
        out = DistributedMatrix.zeros(grid, (m, n), (mb, nb), dtype, source_rank=src)
        dist = out.dist
        ltc = dist.local_slots.cols
        update = _row_update_fn(grid, tuple(out.data.shape), dtype)
        data = out.data
        nt = dist.nr_tiles.cols
        for i in range(dist.nr_tiles.rows):
            r0 = i * mb
            rows = min(mb, m - r0)
            slab = ds[r0 : r0 + rows]  # ONE hyperslab read, <= mb x N
            packed = np.zeros((pc, ltc, mb, nb), dtype)
            for j in range(nt):
                c0 = j * nb
                cols = min(nb, n - c0)
                packed[(j % pc + src[1]) % pc, j // pc, :rows, :cols] = slab[
                    :, c0 : c0 + cols
                ]
            # place() (not a bare ndarray into jit): device_put inside jit
            # dispatch only reaches addressable devices, so a raw host slab
            # breaks on multi-process worlds where the replicated sharding
            # spans non-addressable devices
            row = place(packed, grid.replicated_sharding())
            # int32 indices: see save_hdf5 — s64 starts break the partitioner
            data = update(
                data, row, np.int32((i % pr + src[0]) % pr), np.int32(i // pr)
            )
    return DistributedMatrix(dist, grid, data)


def save_sharded(prefix: str, mat: DistributedMatrix) -> None:
    """One .npy per grid rank holding its local tile stack (hyperslab-style;
    no gather)."""
    x = np.asarray(mat.data)
    pr, pc = mat.dist.grid_size
    os.makedirs(os.path.dirname(prefix) or ".", exist_ok=True)
    for r in range(pr):
        for c in range(pc):
            np.save(f"{prefix}.r{r}c{c}.npy", x[r, c])
    np.savez(
        f"{prefix}.meta.npz",
        size=np.asarray(tuple(mat.size)),
        block_size=np.asarray(tuple(mat.block_size)),
        grid_size=np.asarray((pr, pc)),
    )


def load_sharded(prefix: str, grid: Grid) -> DistributedMatrix:
    import jax
    import jax.numpy as jnp

    from dlaf_tpu.matrix.distribution import Distribution

    with np.load(f"{prefix}.meta.npz") as z:
        size = Size2D(*z["size"])
        bs = Size2D(*z["block_size"])
        pr, pc = z["grid_size"]
    if (pr, pc) != tuple(grid.grid_size):
        raise ValueError(f"file grid {(pr, pc)} != target grid {tuple(grid.grid_size)}")
    dist = Distribution(size, bs, grid.grid_size)
    blocks = np.stack(
        [
            np.stack([np.load(f"{prefix}.r{r}c{c}.npy") for c in range(pc)])
            for r in range(pr)
        ]
    )
    from dlaf_tpu.matrix.matrix import place

    data = place(blocks, grid.stacked_sharding())
    return DistributedMatrix(dist, grid, data)
