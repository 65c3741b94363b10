"""Batched solver drivers: vmapped L6 kernels over a leading batch axis.

Every driver in the stack solves one problem per call; a serving workload
is many independent small/medium problems, where per-call dispatch and
host round-trips dominate (arXiv:2112.09017 — keep the MXU fed).  The
drivers here take HOST stacks ``a[B, n, n]`` (and ``b[B, n, k]``), pad
each element up to a geometry bucket (bucketing.py), and run ONE compiled
executable per bucket: ``jax.vmap`` of the existing SPMD kernels inside a
``shard_map`` over a 3-axis mesh ``('b', 'r', 'c')``.

Two sharding modes over the same device set:

* **matrix mode** (``shard_batch=False``) — mesh ``(1, Pr, Pc)``: each
  element is block-cyclic over the full grid exactly like the single
  drivers, the batch axis is local and vmapped.  For N large enough that
  one problem saturates the mesh.
* **batch mode** (``shard_batch=True``) — mesh ``(ndev, 1, 1)``: the
  BATCH axis is sharded across all devices and each element runs on one
  device.  The kernels' collectives short-circuit to identity on the
  size-1 ``r``/``c`` axes at trace time, so the per-element program is
  pure local compute — the right shape for small-N traffic.  Default for
  ``n <= tune.serve_batch_shard_max_n``.

Per-element health: the Cholesky kernels' first-failing-pivot ``info``
carry rides the vmapped ``fori_loop`` unchanged, so the drivers return an
``info[B]`` vector — one indefinite element reports its own pivot and
does NOT poison its batch mates (LAPACK xPOTRF semantics, element-wise).

Bucket padding preserves those semantics: A is extended to
``blockdiag(A, I)`` (pad pivots are exactly 1 — the in-kernel
``pad_diag_identity`` trick applied at the service boundary), right-hand
sides are zero-padded (zero pad solution rows), and batch-mode batch
padding inserts identity elements.  Leading-block entries of a
right-looking factorization never read the pad tail, so a padded
element's factor/solution slice is bit-identical to the unpadded run at
the same tile geometry.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from dlaf_tpu.algorithms import _spmd
from dlaf_tpu.algorithms import cholesky as _chol
from dlaf_tpu.algorithms import triangular_solver as _tsv
from dlaf_tpu.comm import collectives as coll
from dlaf_tpu.comm.grid import COL_AXIS, ROW_AXIS, Grid
from dlaf_tpu.common.index import Size2D
from dlaf_tpu.matrix import layout
from dlaf_tpu.matrix.distribution import Distribution
from dlaf_tpu.matrix.matrix import place
from dlaf_tpu.ops import tile as t
from dlaf_tpu.plan import autotune as _autotune
from dlaf_tpu.plan import core as _plan
from dlaf_tpu.serve import bucketing

P = jax.sharding.PartitionSpec
BATCH_AXIS = "b"

_CHOL_KERNELS = {
    "bucketed": _chol._chol_L_bucketed_kernel,
    "masked": _chol._chol_L_kernel,
    "lookahead": _chol._chol_L_lookahead_kernel,
}


# --------------------------------------------------------------- plumbing


_default_grid_box: list = []


def _default_grid() -> Grid:
    if not _default_grid_box:
        devs = jax.devices()
        _default_grid_box.append(Grid.create(Size2D(1, len(devs)), devs))
    return _default_grid_box[0]


def _mesh3(grid: Grid, shard_batch: bool):
    """3-axis mesh over the grid's devices: ``(ndev, 1, 1)`` in batch mode,
    ``(1, Pr, Pc)`` in matrix mode.  Built raw (Grid only admits 2-axis
    ('r','c') meshes); the kernels resolve 'r'/'c' by name as usual."""

    def build():
        devs = grid.mesh.devices
        shape = (devs.size, 1, 1) if shard_batch else (1,) + devs.shape
        return jax.sharding.Mesh(
            devs.reshape(shape), (BATCH_AXIS, ROW_AXIS, COL_AXIS)
        )

    return _plan.cached("serve_mesh3", (grid.cache_key, bool(shard_batch)), build)


def _gather(mesh, *arrs):
    """Fetch device results to host numpy, multi-process safe (replicate
    across the mesh inside jit, then read local shards — the to_global()
    pattern)."""
    fn = _plan.cached(
        "serve_gather",
        tuple(int(d.id) for d in mesh.devices.flat),
        lambda: _plan.jit(
            "serve_gather", lambda *v: v,
            out_shardings=jax.sharding.NamedSharding(mesh, P()),
        ),
    )
    rep = fn(*arrs)
    if jax.process_count() > 1:
        return tuple(np.asarray(r.addressable_data(0)) for r in rep)
    return tuple(np.asarray(jax.device_get(r)) for r in rep)


def _pack_batch(a, dist: Distribution):
    """Host batched pack: ``[B, Mp, Np]`` -> ``[B, Pr, Pc, ltr, ltc, mb, nb]``
    (layout.pack with a leading batch axis; source rank fixed at (0,0))."""
    pr, pc = dist.grid_size
    ltr, ltc = dist.local_slots
    mb, nb = dist.block_size
    return a.reshape(a.shape[0], ltr, pr, mb, ltc, pc, nb).transpose(0, 2, 5, 1, 4, 3, 6)


def _unpack_batch(x, dist: Distribution):
    """Inverse of :func:`_pack_batch`: -> ``[B, Mp, Np]``."""
    mp, np_ = dist.padded_size
    return x.transpose(0, 3, 1, 5, 4, 2, 6).reshape(x.shape[0], mp, np_)


def _pad_spd(a, n_to: int, mp: int, np_: int):
    """``[B, n, n]`` -> ``[B, Mp, Np]``: blockdiag(A, I) up to the bucket
    order ``n_to`` (unit pad pivots), zeros beyond (the kernels' own
    tile-slot padding region)."""
    bsz, n = a.shape[0], a.shape[1]
    out = np.zeros((bsz, mp, np_), dtype=a.dtype)
    out[:, :n, :n] = a
    idx = np.arange(n, n_to)
    out[:, idx, idx] = 1.0
    return out


def _pad_rhs(b, mp: int):
    bsz, n, k = b.shape
    out = np.zeros((bsz, mp, k), dtype=b.dtype)
    out[:, :n, :] = b
    return out


def _pad_batch_count(nel: int, shards: int) -> int:
    return ((nel + shards - 1) // shards) * shards


def _mirror_l(a):
    """Upper-storage Hermitian stack -> mirrored lower storage (the U
    driver path's ``transpose(extract_triangle(A, 'U'), conj=True)`` done
    on host: exact conj/transpose, no float ops)."""
    up = np.triu(a)
    return np.conj(np.swapaxes(up, -1, -2))


def _check_stack(name: str, a, uplo: str):
    from dlaf_tpu.health import DistributionError

    if uplo not in (t.LOWER, t.UPPER):
        raise DistributionError(f"serve: bad uplo {uplo!r} (use 'L' or 'U')")
    a = np.asarray(a)
    if a.ndim != 3 or a.shape[1] != a.shape[2]:
        raise DistributionError(
            f"serve: {name} must be a [B, n, n] stack of square matrices, "
            f"got shape {a.shape}"
        )
    if a.shape[0] == 0 or a.shape[1] == 0:
        raise DistributionError(f"serve: {name} batch is empty: shape {a.shape}")
    return a


def _resolve_mode(op: str, n: int, dtype, shard_batch):
    """Mesh-mode choice: explicit caller value wins, else the autotuner
    (measured profile entry if one matches, analytic
    ``n <= tune.serve_batch_shard_max_n`` rule otherwise)."""
    if shard_batch is None:
        return _autotune.shard_batch(op, n, dtype)
    return bool(shard_batch)


def _default_block(op: str, n_bucket: int, dtype) -> int:
    """Bucket tile size: the autotuner's measured choice when a profile
    entry matches, else the analytic ``min(128, n)`` default."""
    return _autotune.block_size(op, n_bucket, dtype)


def _chol_variant() -> str:
    from dlaf_tpu.tune import get_tune_parameters

    return "lookahead" if get_tune_parameters().cholesky_lookahead else "bucketed"


def _dist_for(n_bucket: int, mb: int, grid: Grid, shard_batch: bool, k: int | None = None):
    gs = Size2D(1, 1) if shard_batch else grid.grid_size
    size = Size2D(n_bucket, n_bucket) if k is None else Size2D(n_bucket, k)
    return Distribution(size, Size2D(mb, mb), gs)


def _place_in(mesh, x):
    return place(x, jax.sharding.NamedSharding(mesh, P(BATCH_AXIS, ROW_AXIS, COL_AXIS)))


def _place_dense(mesh, x):
    return place(x, jax.sharding.NamedSharding(mesh, P(BATCH_AXIS)))


# ------------------------------------------------------------ executables


def _build_chol_exec(grid: Grid, dist: Distribution, shard_batch: bool, variant: str):
    """vmap of the L-factor kernel over the local batch axis, info carried
    per element (``info[B]`` out, spec P('b') — replicated over r/c, every
    rank computes the identical scan)."""
    g = _spmd.Geometry.of(dist)
    mesh = _mesh3(grid, shard_batch)
    kern = partial(_CHOL_KERNELS[variant], g=g, want_info=True)
    spec = P(BATCH_AXIS, ROW_AXIS, COL_AXIS)
    sm = jax.shard_map(
        jax.vmap(kern), mesh=mesh, in_specs=spec, out_specs=(spec, P(BATCH_AXIS)), check_vma=False
    )
    return _plan.jit("serve", sm, donate_argnums=(0,))


def _build_posv_batch_exec(grid: Grid, dist: Distribution, variant: str, uplo: str):
    """Batch-mode POSV: the vmapped SPMD factor kernel (1x1 geometry,
    collectives degenerate), then the DENSE two-triangular-solve
    composition UNROLLED per local element.  The unroll matters: a batched
    (vmapped) triangular_solve lowers to a different XLA codepath whose
    bits differ from the unbatched solve at ~eps, while the unrolled form
    emits the exact HLO the single driver's 1x1 path
    (``_trsm_single_device``) emits — so every batch element is
    bit-identical to its single call.  Local batches are small (B/ndev) so
    the unroll stays cheap to compile."""
    g = _spmd.Geometry.of(dist)
    mesh = _mesh3(grid, True)
    kern = partial(_CHOL_KERNELS[variant], g=g, want_info=True)

    def solve_all(x, b):
        l_st, info = jax.vmap(kern)(x)
        alpha = jnp.asarray(1.0, b.dtype)
        sols = []
        for i in range(x.shape[0]):  # static local batch extent
            ld = layout.unpad_global(layout.unpack(l_st[i], dist), dist)
            if uplo == t.LOWER:
                y = t.trsm(t.LEFT, t.LOWER, t.NO_TRANS, t.NON_UNIT, alpha, ld, b[i])
                sol = t.trsm(t.LEFT, t.LOWER, t.CONJ_TRANS, t.NON_UNIT, alpha, ld, y)
            else:
                # the factor is of the host-mirrored matrix; its U factor is
                # the conj-transpose — solve exactly like the single U driver
                ud = jnp.swapaxes(jnp.tril(ld), -1, -2).conj()
                y = t.trsm(t.LEFT, t.UPPER, t.CONJ_TRANS, t.NON_UNIT, alpha, ud, b[i])
                sol = t.trsm(t.LEFT, t.UPPER, t.NO_TRANS, t.NON_UNIT, alpha, ud, y)
            sols.append(sol)
        return jnp.stack(sols), info

    sm = jax.shard_map(
        solve_all,
        mesh=mesh,
        in_specs=(P(BATCH_AXIS, ROW_AXIS, COL_AXIS), P(BATCH_AXIS)),
        out_specs=(P(BATCH_AXIS), P(BATCH_AXIS)),
        check_vma=False,
    )
    return _plan.jit("serve", sm, donate_argnums=(1,))


def _build_posv_matrix_exec(grid: Grid, dist_a: Distribution, dist_b: Distribution,
                            variant: str, uplo: str):
    """Matrix-mode POSV: factor + two distributed TRSM kernels composed in
    one local function, vmapped over the (device-local) batch axis.  The U
    path solves with conj(L) of the mirrored factor — elementwise conj,
    no cross-mesh transpose needed."""
    g_a = _spmd.Geometry.of(dist_a)
    g_b = _spmd.Geometry.of(dist_b)
    mesh = _mesh3(grid, False)
    kern = partial(_CHOL_KERNELS[variant], g=g_a, want_info=True)
    from dlaf_tpu.tune import get_tune_parameters

    lookahead = get_tune_parameters().trsm_lookahead and g_a.mt > 1
    trsm_fn = _tsv._trsm_left_lookahead_kernel if lookahead else _tsv._trsm_left_bucketed_kernel
    solve = partial(trsm_fn, g_a=g_a, g_b=g_b, uplo=t.LOWER, diag=t.NON_UNIT, alpha=1.0)

    def one(x, b):
        l_st, info = kern(x)
        if uplo == t.UPPER:
            l_st = l_st.conj()  # A = conj(L) conj(L)^H for the mirrored factor
        y = solve(l_st, b, op=t.NO_TRANS)
        sol = solve(l_st, y, op=t.CONJ_TRANS)
        return sol, info

    spec = P(BATCH_AXIS, ROW_AXIS, COL_AXIS)
    sm = jax.shard_map(
        jax.vmap(one), mesh=mesh, in_specs=(spec, spec),
        out_specs=(spec, P(BATCH_AXIS)),
        check_vma=False,
    )
    return _plan.jit("serve", sm, donate_argnums=(1,))


def _build_eig_exec(grid: Grid):
    """Batch-mode eigensolver: per element, hermitize from lower storage
    and run the dense XLA ``eigh`` — the `_eigh_single_device` composition
    vmapped.  ``info[B]`` counts non-finite eigenpair entries (0 = ok)."""
    mesh = _mesh3(grid, True)

    def one(x):
        full = jnp.tril(x) + jnp.swapaxes(jnp.tril(x, -1), -1, -2).conj()
        w, v = jnp.linalg.eigh(full)
        bad = jnp.sum(~jnp.isfinite(w)) + jnp.sum(~jnp.isfinite(v.real))
        return w, v, bad.astype(jnp.int32)

    sm = jax.shard_map(
        jax.vmap(one), mesh=mesh, in_specs=P(BATCH_AXIS),
        out_specs=(P(BATCH_AXIS), P(BATCH_AXIS), P(BATCH_AXIS)),
        check_vma=False,
    )
    return _plan.jit("serve", sm, donate_argnums=(0,))


# ---------------------------------------------------------------- drivers


def batched_cholesky_factorization(uplo, a, grid=None, *, block_size=None,
                                   shard_batch=None, cache=None):
    """Factor ``B`` independent Hermitian positive-definite matrices
    ``a[B, n, n]`` at once.  Returns ``(l[B, n, n], info[B])`` host
    arrays: each element's ``uplo`` triangle holds its Cholesky factor
    (the other triangle follows the single-driver convention: update
    residue on the L path, untouched input on the U path), and ``info[b]``
    is the LAPACK-style 1-based first failing pivot of element ``b``
    (0 = success) — per-element isolation, one indefinite element does
    not poison the batch.

    ``shard_batch`` picks the mesh mode (see module docstring; default by
    ``tune.serve_batch_shard_max_n``); ``cache`` is a
    :class:`~dlaf_tpu.serve.bucketing.CompiledCache` (default: the
    process-wide one).  The problem is padded up to
    ``bucketing.bucket_for(n)``."""
    from dlaf_tpu.tune import blas3_precision

    a = _check_stack("a", a, uplo)
    bsz, n = a.shape[0], a.shape[1]
    grid = grid if grid is not None else _default_grid()
    cache = cache if cache is not None else bucketing.default_cache()
    nb_bucket = bucketing.bucket_for(n)
    mb = int(block_size) if block_size is not None else _default_block("potrf", nb_bucket, a.dtype)
    shard_batch = _resolve_mode("potrf", n, a.dtype, shard_batch)
    variant = _chol_variant()
    dist = _dist_for(nb_bucket, mb, grid, shard_batch)
    mesh = _mesh3(grid, shard_batch)
    # static identity only: trace-time knobs land in the key via the plan
    # layer's trace_suffix() (variant stays static — it names the kernel)
    key = ("potrf", nb_bucket, np.dtype(a.dtype).str, uplo, mb, shard_batch,
           grid.cache_key, variant)
    fn = cache.get(key, lambda: _build_chol_exec(grid, dist, shard_batch, variant))

    bshards = mesh.devices.shape[0]
    bp = _pad_batch_count(bsz, bshards)
    host = a if uplo == t.LOWER else _mirror_l(a)
    mp, np_ = dist.padded_size
    padded = _pad_spd(host, nb_bucket, mp, np_)
    if bp > bsz:
        eye = _pad_spd(np.zeros((bp - bsz, 0, 0), a.dtype), nb_bucket, mp, np_)
        padded = np.concatenate([padded, eye], axis=0)
    with blas3_precision():
        y, info = fn(_place_in(mesh, _pack_batch(padded, dist)))
    y_h, info_h = _gather(mesh, y, info)
    out = _unpack_batch(y_h, dist)[:bsz, :n, :n]
    if uplo == t.UPPER:
        out = np.tril(a, -1) + np.triu(np.conj(np.swapaxes(np.tril(out), -1, -2)))
    return np.ascontiguousarray(out), info_h[:bsz]


def batched_positive_definite_solver(uplo, a, b, grid=None, *, block_size=None,
                                     shard_batch=None, cache=None):
    """Solve ``B`` independent SPD systems ``a[i] x[i] = b[i]`` at once.

    ``a[B, n, n]``; ``b[B, n, k]`` (multi-RHS) or ``[B, n]`` (single RHS,
    returned with the same rank).  Returns ``(x, info)`` host arrays with
    per-element LAPACK-style factorization info (an element with
    ``info != 0`` has an indefinite ``a[i]``; its solution slot is
    garbage, its batch mates are unaffected)."""
    from dlaf_tpu.health import DistributionError
    from dlaf_tpu.tune import blas3_precision

    a = _check_stack("a", a, uplo)
    b = np.asarray(b)
    squeeze = b.ndim == 2
    if squeeze:
        b = b[:, :, None]
    if b.ndim != 3 or b.shape[0] != a.shape[0] or b.shape[1] != a.shape[1]:
        raise DistributionError(
            f"serve: b must be [B, n, k] (or [B, n]) matching a[B, n, n]; "
            f"got b shape {np.asarray(b).shape} for a shape {a.shape}"
        )
    bsz, n, k = b.shape
    grid = grid if grid is not None else _default_grid()
    cache = cache if cache is not None else bucketing.default_cache()
    nb_bucket = bucketing.bucket_for(n)
    mb = int(block_size) if block_size is not None else _default_block("posv", nb_bucket, a.dtype)
    shard_batch = _resolve_mode("posv", n, a.dtype, shard_batch)
    variant = _chol_variant()
    dist = _dist_for(nb_bucket, mb, grid, shard_batch)
    mesh = _mesh3(grid, shard_batch)
    key = ("posv", nb_bucket, np.dtype(a.dtype).str, uplo, mb, shard_batch, k,
           grid.cache_key, variant)

    bshards = mesh.devices.shape[0]
    bp = _pad_batch_count(bsz, bshards)
    host = a if uplo == t.LOWER else _mirror_l(a)
    mp, np_ = dist.padded_size
    padded = _pad_spd(host, nb_bucket, mp, np_)
    if bp > bsz:
        eye = _pad_spd(np.zeros((bp - bsz, 0, 0), a.dtype), nb_bucket, mp, np_)
        padded = np.concatenate([padded, eye], axis=0)
    if shard_batch:
        fn = cache.get(key, lambda: _build_posv_batch_exec(grid, dist, variant, uplo))
        rhs = _pad_rhs(b.astype(b.dtype, copy=False), nb_bucket)
        if bp > bsz:
            rhs = np.concatenate(
                [rhs, np.zeros((bp - bsz, nb_bucket, k), b.dtype)], axis=0
            )
        with blas3_precision():
            x, info = fn(_place_in(mesh, _pack_batch(padded, dist)),
                         _place_dense(mesh, rhs))
        x_h, info_h = _gather(mesh, x, info)
        out = x_h[:bsz, :n, :]
    else:
        dist_b = _dist_for(nb_bucket, mb, grid, shard_batch, k=k)
        fn = cache.get(
            key, lambda: _build_posv_matrix_exec(grid, dist, dist_b, variant, uplo)
        )
        mpb, npb = dist_b.padded_size
        rhs = np.zeros((bp, mpb, npb), b.dtype)
        rhs[:bsz, :n, :k] = b
        with blas3_precision():
            x, info = fn(_place_in(mesh, _pack_batch(padded, dist)),
                         _place_in(mesh, _pack_batch(rhs, dist_b)))
        x_h, info_h = _gather(mesh, x, info)
        out = _unpack_batch(x_h, dist_b)[:bsz, :n, :k]
    out = np.ascontiguousarray(out)
    return (out[:, :, 0] if squeeze else out), info_h[:bsz]


def batched_eigensolver(uplo, a, grid=None, *, shard_batch=None, cache=None):
    """Eigendecompose ``B`` independent Hermitian matrices ``a[B, n, n]``
    (``uplo`` triangle stored) at once.  Returns ``(w[B, n], v[B, n, n],
    info[B])``: ascending eigenvalues, eigenvectors in columns, and a
    per-element non-finite-entry count (0 = success).

    Batch-sharded mode only (the distributed eigensolver pipeline has
    host-side stages and cannot be vmapped); ``shard_batch=False`` raises
    :class:`~dlaf_tpu.health.DistributionError`.  Bucket padding appends
    unit eigenpairs supported entirely in the pad rows; they are
    identified by pad-row mass and compacted out on the host — an element
    whose own spectrum clusters exactly at 1.0 with pad-degenerate
    eigenvectors may see those pairs mixed (use an exact-fit bucket for
    such spectra)."""
    from dlaf_tpu.health import DistributionError
    from dlaf_tpu.tune import blas3_precision

    a = _check_stack("a", a, uplo)
    if shard_batch is not None and not shard_batch:
        raise DistributionError(
            "serve: batched_eigensolver only supports the batch-sharded mode "
            "(the distributed pipeline has host stages and cannot be vmapped); "
            "leave shard_batch unset or pass shard_batch=True"
        )
    bsz, n = a.shape[0], a.shape[1]
    grid = grid if grid is not None else _default_grid()
    cache = cache if cache is not None else bucketing.default_cache()
    nb_bucket = bucketing.bucket_for(n)
    mesh = _mesh3(grid, True)
    key = ("eigh", nb_bucket, np.dtype(a.dtype).str, grid.cache_key)
    fn = cache.get(key, lambda: _build_eig_exec(grid))

    bshards = mesh.devices.shape[0]
    bp = _pad_batch_count(bsz, bshards)
    host = a if uplo == t.LOWER else _mirror_l(a)
    padded = _pad_spd(host, nb_bucket, nb_bucket, nb_bucket)
    if bp > bsz:
        eye = _pad_spd(np.zeros((bp - bsz, 0, 0), a.dtype), nb_bucket, nb_bucket, nb_bucket)
        padded = np.concatenate([padded, eye], axis=0)
    with blas3_precision():
        w, v, info = fn(_place_dense(mesh, padded))
    w_h, v_h, info_h = _gather(mesh, w, v, info)
    w_h, v_h, info_h = w_h[:bsz], v_h[:bsz], info_h[:bsz]
    if nb_bucket == n:
        return w_h, v_h, info_h
    # compact out the pad eigenpairs: unit pairs supported in the pad rows
    mass = np.sum(np.abs(v_h[:, n:, :]) ** 2, axis=1)  # [B, nb_bucket]
    w_out = np.empty((bsz, n), w_h.dtype)
    v_out = np.empty((bsz, n, n), v_h.dtype)
    for i in range(bsz):
        keep = np.sort(np.argsort(mass[i], kind="stable")[:n])
        w_out[i] = w_h[i, keep]
        v_out[i] = v_h[i, :n, :][:, keep]
    return w_out, v_out, info_h
