"""Blocked compact-WY band-stage back-transform: E <- Q2 E on device.

TPU-native re-design of the reference bt_band_to_tridiagonal
(reference: include/dlaf/eigensolver/bt_band_to_tridiag/impl.h — grouped HH
applications, hh_apply_group_size, sub-b x b tiling).  The band->tridiagonal
reduction (native/band2trid.cpp band2trid_hh) emits Householder reflectors
(sweep s, chase step m) with head row ``1 + s + m*b`` and length <= b; the
full transformation is Q2 = H_1 H_2 ... H_R in generation order (s asc,
m asc), applied to eigenvectors as E <- Q2 E, i.e. last reflector first.

Instead of applying reflectors one by one (scalar, host-bound), groups of
``g`` consecutive sweeps at one chase level form a compact-WY factor
I - V T V^H over a window of w = b+g-1 rows, applied as three GEMMs — the
MXU-native formulation.  Group application order (derived from the overlap
structure: reflectors (s, m), (s', m') interact iff |(s-s') + (m-m')*b| < b):

    for sweep-block J descending:  for chase level m ascending:  apply G(J, m)

with reflectors inside a group accumulated forward (s ascending), which is
exactly LAPACK larft's forward/columnwise T:  T^{-1} = diag(1/tau) +
triu(V^H V, 1).  Total GEMM flops ~ 2 N^2 k (b+g)/b vs the 2 N^2 k of one
dense GEMM against an explicit Q2 — but no N x N Q2 is ever built.

Rotations act on E's rows; columns are independent, so under a column-sharded
layout the loop is communication-free across devices.
"""
from __future__ import annotations

from functools import partial

import numpy as np

from dlaf_tpu.matrix.matrix import DistributedMatrix


def _resolve_group_size(group_size):
    """tune.bt_band_hh_group_size with -1 = auto: 32 on CPU backends
    (measured 1.3-2.2x over 128 on the 8-device mesh — the larger group's
    V windows fall out of cache), 128 on accelerators (bigger MXU GEMMs
    per step; re-tune on hardware via scripts/tpu_day.sh)."""
    if group_size is None:
        from dlaf_tpu.tune import get_tune_parameters

        group_size = get_tune_parameters().bt_band_hh_group_size
    if group_size < 0:
        import jax

        group_size = 32 if jax.default_backend() == "cpu" else 128
    return group_size


def hh_schedule(n: int, b: int, g: int):
    """Group schedule in application order.

    Returns (groups, w) where each group is (base_shifted, [(col, slot), ...])
    with ``col`` the reflector's column inside the group's V (head offset
    within the window is ``col + delta``) and ``slot`` its storage index in
    the [R, b] reflector array; w = b + g - 1 is the window height.
    """
    if b <= 1 or n <= 2:
        return [], 0
    nsweeps = n - 2  # sweeps s = 0 .. n-3
    counts = [(n - 3 - s) // b + 1 for s in range(nsweeps)]
    offs = np.concatenate([[0], np.cumsum(counts)])
    w = b + g - 1
    n_pad = max(n, w)
    groups = []
    first_block = ((nsweeps - 1) // g) * g
    for j0 in range(first_block, -1, -g):
        j1 = min(j0 + g, nsweeps)
        mmax = (n - 3 - j0) // b
        for m in range(mmax + 1):
            base = 1 + j0 + m * b
            base_s = min(base, n_pad - w)
            delta = base - base_s
            cols = []
            for s in range(j0, j1):
                if 1 + s + m * b <= n - 2:
                    cols.append((delta + (s - j0), int(offs[s]) + m))
            if cols:
                groups.append((base_s, cols))
    return groups, w


def _build_factors(v_refl, taus, groups, w, g, b, dtype):
    """Host assembly of the padded per-group V windows and taus."""
    G = len(groups)
    V_all = np.zeros((G, w, g), dtype)
    tau_all = np.ones((G, g), dtype)  # pad: tau=1 with v=0 => identity factor
    offs = np.zeros(G, np.int32)
    for gi, (base_s, cols) in enumerate(groups):
        offs[gi] = base_s
        for ci, (row_off, slot) in enumerate(cols):
            t = taus[slot]
            if t == 0:
                continue  # identity reflector: leave v=0, tau=1
            L = min(b, w - row_off)
            V_all[gi, row_off : row_off + L, ci] = v_refl[slot, :L]
            tau_all[gi, ci] = t
    return V_all, tau_all, offs


def _wy_group_loop(e_pad, V_all, tau_all, offs, w, g, G, k):
    """Apply the G grouped compact-WY factors to the k-column block ``e_pad``
    (the shared core of the host-input and distributed back-transforms).

    T^{-1} = diag(1/tau) + triu(V^H V, 1)  (larft forward/columnwise)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    if G == 0:
        return e_pad
    M = jnp.einsum("gwi,gwj->gij", V_all.conj(), V_all)
    eye = jnp.eye(g, dtype=V_all.dtype)
    tinv = jnp.triu(M, 1) + eye[None] / tau_all[:, None, :]
    T_all = jax.scipy.linalg.solve_triangular(
        tinv, jnp.broadcast_to(eye, tinv.shape), lower=False
    )

    def body(i, e):
        off = offs[i]
        ew = lax.dynamic_slice(e, (off, jnp.zeros((), off.dtype)), (w, k))
        x = V_all[i].conj().T @ ew
        ew = ew - V_all[i] @ (T_all[i] @ x)
        return lax.dynamic_update_slice(e, ew, (off, jnp.zeros((), off.dtype)))

    return lax.fori_loop(0, G, body, e_pad)


def _apply_fn(n_pad, k, w, g, G, dtype, dist_key=None, dist=None, sharding=None, prec="float32"):
    """Jitted grouped-WY application (+ optional pack to stacked layout)."""
    import jax

    from dlaf_tpu.plan import core as _plan

    def build():
        from dlaf_tpu.matrix import layout

        def run(e_pad, V_all, tau_all, offs):
            e_pad = _wy_group_loop(e_pad, V_all, tau_all, offs, w, g, G, k)
            if dist is None:
                return e_pad
            eg = e_pad[: dist.size.rows, :]
            return layout.pack(layout.pad_global(eg, dist), dist)

        if sharding is not None:
            return _plan.jit("bt_band_apply", run, out_shardings=sharding)
        return _plan.jit("bt_band_apply", run)

    return _plan.cached(
        "bt_band_apply",
        (n_pad, k, w, g, G, np.dtype(dtype), dist_key, prec),
        build,
    )


def bt_band_to_tridiagonal_hh_dist(
    hh, mat_e: DistributedMatrix, group_size: int | None = None,
    out_cols: bool = False,
):
    """E := Q2 E with E ALREADY DISTRIBUTED (block-cyclic stacked layout).

    The rotations act on E's rows and E's columns are independent, so the
    group loop is communication-free under a column-sharded layout: the
    stacked block-cyclic E is resharded to column panels over the flat device
    order (one XLA all-to-all), every device applies the full WY group
    schedule to its ``k/P`` columns locally, and the result is resharded back
    (second all-to-all).  This replaces the reference's p2p exchange of E
    rows (bt_band_to_tridiag/impl.h distributed path) with two cheap
    relayouts — the TPU-native choice, since XLA owns layout transforms.
    No O(n x k) host or replicated array is ever formed.

    ``out_cols=True`` skips the final pack and returns the column-sharded
    :class:`~dlaf_tpu.matrix.colpanels.ColPanels` carrier for a following
    row-transform stage (sbr_back_transform) — eliding one all-to-all pair.
    (May still return a DistributedMatrix on the trivial no-reflector
    path; callers must accept either.)"""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dlaf_tpu.comm import collectives as coll
    from dlaf_tpu.comm.grid import COL_AXIS, ROW_AXIS
    from dlaf_tpu.matrix import layout
    from dlaf_tpu.obs.trace import phase

    from dlaf_tpu.tune import get_tune_parameters, matmul_precision

    d, e_, phases, v_refl, taus, band = hh
    grid = mat_e.grid
    dist = mat_e.dist
    n, k = dist.size
    dt = np.dtype(mat_e.dtype)
    group_size = _resolve_group_size(group_size)
    has_refl = v_refl.shape[0] > 0 and n > 2 and k > 0 and band > 1
    if not has_refl and (dt.kind != "c" or n == 0 or k == 0):
        return mat_e
    with phase("bt_band/factors"):
        if has_refl:
            g = max(1, min(group_size, band, n - 2))
            groups, w = hh_schedule(n, band, g)
            V_all, tau_all, offs = _build_factors(v_refl, taus, groups, w, g, band, dt)
            G = len(groups)
        else:
            g, w, G = 1, 1, 0
            V_all = np.zeros((0, 1, 1), dt)
            tau_all = np.ones((0, 1), dt)
            offs = np.zeros(0, np.int32)
        n_pad = max(n, w)
        ph = np.ones(n_pad, dt)
        if dt.kind == "c":
            ph[:n] = phases.astype(dt)
        factors = tuple(jnp.asarray(a) for a in (V_all, tau_all, offs, ph))
    Ptot = grid.grid_size.count()
    kloc = -(-k // Ptot)
    kpad = kloc * Ptot
    mesh = grid.mesh
    colspec = P(None, (ROW_AXIS, COL_AXIS))
    prec = get_tune_parameters().eigensolver_matmul_precision
    from dlaf_tpu.plan import core as _plan

    def build():
        def loop(va, ta, of, e_loc):
            return _wy_group_loop(e_loc, va, ta, of, w, g, G, kloc)

        sm = jax.shard_map(
            loop,
            mesh=mesh,
            in_specs=(P(), P(), P(), colspec),
            out_specs=colspec,
            check_vma=False,
        )

        def run(x, va, ta, of, phj):
            gg = layout.unpad_global(layout.unpack(x, dist), dist)
            gp = jnp.pad(gg, ((0, n_pad - n), (0, kpad - k)))
            gp = phj[:, None] * gp
            gp = jax.lax.with_sharding_constraint(gp, NamedSharding(mesh, colspec))
            gp = sm(va, ta, of, gp)
            if out_cols:
                return gp
            return layout.pack(layout.pad_global(gp[:n, :k], dist), dist)

        out_sh = (
            NamedSharding(mesh, colspec) if out_cols else grid.stacked_sharding()
        )
        # donation only helps when output aliases input (stacked -> stacked);
        # the col-sharded output can't alias, donating would only warn
        return _plan.jit(
            "bt_band_dist", run, out_shardings=out_sh,
            donate_argnums=() if out_cols else (0,),
        )

    fn = _plan.cached(
        "bt_band_dist",
        (grid.cache_key, dist, n_pad, kpad, w, g, G, dt, prec, out_cols),
        build,
    )
    with matmul_precision(prec), phase("bt_band/apply"):
        data = fn(mat_e.data, *factors)
    if out_cols:
        from dlaf_tpu.matrix.colpanels import ColPanels

        return ColPanels(data, n, k, grid, dist)
    return mat_e._inplace(data)


def bt_band_to_tridiagonal_hh(
    hh, e_host: np.ndarray, grid, block_size, group_size: int | None = None
) -> DistributedMatrix:
    """E := Q2 E from the Householder band-stage result ``hh`` (as returned
    by band_to_tridiag.band_to_tridiagonal_hh): the compact back-transform,
    run as blocked WY GEMMs on device.  ``e_host`` is the tridiagonal
    eigenvector block (n x k) on host; the result is distributed."""
    import jax
    import jax.numpy as jnp

    from dlaf_tpu.common.index import Index2D, Size2D
    from dlaf_tpu.matrix.distribution import Distribution

    d, e_, phases, v_refl, taus, band = hh
    dt = np.dtype(e_host.dtype)
    n, k = e_host.shape
    if dt.kind == "c":
        e_host = phases[:, None] * e_host
    if v_refl.shape[0] == 0 or n == 0 or k == 0:
        return DistributedMatrix.from_global(grid, e_host, block_size)
    from dlaf_tpu.tune import get_tune_parameters, matmul_precision

    group_size = _resolve_group_size(group_size)
    g = max(1, min(group_size, band, n - 2))
    groups, w = hh_schedule(n, band, g)
    V_all, tau_all, offs = _build_factors(v_refl, taus, groups, w, g, band, dt)
    n_pad = max(n, w)
    e_pad = e_host if n_pad == n else np.pad(e_host, ((0, n_pad - n), (0, 0)))

    dist = Distribution(Size2D(n, k), Size2D(*block_size), grid.grid_size, Index2D(0, 0))
    prec = get_tune_parameters().eigensolver_matmul_precision
    fn = _apply_fn(
        n_pad, k, w, g, len(groups), dt,
        dist_key=(grid.cache_key, dist), dist=dist, sharding=grid.stacked_sharding(),
        prec=prec,
    )
    with matmul_precision(prec):
        data = fn(jnp.asarray(e_pad), jnp.asarray(V_all), jnp.asarray(tau_all), jnp.asarray(offs))
    return DistributedMatrix(dist, grid, data)
