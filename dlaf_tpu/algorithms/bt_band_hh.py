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
from typing import NamedTuple

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


class HHSchedule(NamedTuple):
    """The grouped-WY schedule of one (n, b, g), groups in application order.

    Group (J, m) holds chase level ``m`` of sweeps ``J*g .. J*g+g-1``:
    column ``c`` is reflector (J*g+c, m), kept in row ``rows[gi, c]`` of
    the [R, b] reflector array (R where the reflector does not exist), and
    heads window row ``delta[gi] + c``."""

    w: int  # window height b + g - 1
    rows: np.ndarray  # [G, g] int32 reflector slot of each column
    offs: np.ndarray  # [G] int32 first E row of each group's window
    delta: np.ndarray  # [G] int32 bottom-edge clamp: head row - window row


def hh_schedule(n: int, b: int, g: int) -> HHSchedule:
    """Group schedule in application order: sweep blocks J descending,
    chase levels m ascending.  Sweep s (0 .. n-3) owns one reflector per
    chase level, (n-3-s)//b + 1 of them, in consecutive slots.  Needs
    b > 1 and n > 2."""
    nsweeps = n - 2
    w = b + g - 1
    n_pad = max(n, w)
    counts = (n - 3 - np.arange(nsweeps)) // b + 1
    slot = np.concatenate([[0], np.cumsum(counts)])
    j0 = np.arange(0, nsweeps, g)[::-1]
    jj = np.repeat(j0, counts[j0])
    m = np.arange(jj.size) - np.repeat(np.cumsum(counts[j0]) - counts[j0], counts[j0])
    s = jj[:, None] + np.arange(g)
    sc = np.minimum(s, nsweeps - 1)
    rows = np.where((s < nsweeps) & (m[:, None] < counts[sc]), slot[sc] + m[:, None], slot[-1])
    base = 1 + jj + m * b
    offs = np.minimum(base, n_pad - w)
    return HHSchedule(
        w, rows.astype(np.int32), offs.astype(np.int32), (base - offs).astype(np.int32)
    )


def _form_factors(v_flat, taus, *, sched: HHSchedule, b: int, g: int):
    """The padded per-group V windows [G, w, g] and taus [G, g] from the
    compact reflectors (``v_flat`` the [R, b] array flattened), on device.

    One gather of whole reflector rows puts each group's [g, b] block
    together; the block is skewed into its window (column c moves down c
    rows: a pad and a reshape), and the groups clamped at the bottom edge
    move down ``delta`` more rows (a shift by each set bit of delta).  An
    identity reflector (tau == 0) and a missing one read v = 0, tau = 1."""
    import jax.numpy as jnp

    w = sched.w
    G = sched.offs.size
    # tau rides along as column b; row R is the missing reflector
    vt = jnp.concatenate([v_flat.reshape(-1, b), taus[:, None]], axis=1)
    vt = jnp.pad(vt, ((0, 1), (0, 0)))
    x = vt.at[jnp.asarray(sched.rows)].get(mode="promise_in_bounds")
    t = x[..., b]
    ident = t == 0
    v = jnp.where(ident[..., None], 0, x[..., :b])
    y = jnp.pad(v, ((0, 0), (0, 0), (0, g))).reshape(G, g * (w + 1))[:, : g * w]
    y = y.reshape(G, g, w)
    for k in range(int(sched.delta.max()).bit_length()):
        moved = jnp.pad(y, ((0, 0), (0, 0), (1 << k, 0)))[..., :w]
        y = jnp.where((sched.delta >> k & 1).astype(bool)[:, None, None], moved, y)
    return y.transpose(0, 2, 1), jnp.where(ident, 1, t), jnp.asarray(sched.offs)


def _factors(v_refl, taus, n: int, b: int, g: int, dtype):
    """(w, G, (V_all, tau_all, offs)): the group schedule of (n, b, g) and
    its window-forming program, both built once per shape and dtype; per
    call only the chase's reflectors and taus go to the device."""
    import jax.numpy as jnp

    from dlaf_tpu.plan import core as _plan

    dt = np.dtype(dtype)

    def build():
        sched = hh_schedule(n, b, g)
        return sched, _plan.jit(
            "bt_band_factors", partial(_form_factors, sched=sched, b=b, g=g)
        )

    sched, form = _plan.cached("bt_band_factors", (n, b, g, dt), build)
    v = jnp.asarray(np.asarray(v_refl, dt).reshape(-1))
    return sched.w, sched.offs.size, form(v, jnp.asarray(np.asarray(taus, dt)))


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
            w, G, wy = _factors(v_refl, taus, n, band, g, dt)
        else:
            g, w, G = 1, 1, 0
            wy = (np.zeros((0, 1, 1), dt), np.ones((0, 1), dt), np.zeros(0, np.int32))
        n_pad = max(n, w)
        ph = np.ones(n_pad, dt)
        if dt.kind == "c":
            ph[:n] = phases.astype(dt)
        factors = tuple(jnp.asarray(a) for a in (*wy, ph))
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
    if v_refl.shape[0] == 0 or n <= 2 or k == 0 or band <= 1:
        return DistributedMatrix.from_global(grid, e_host, block_size)
    from dlaf_tpu.tune import get_tune_parameters, matmul_precision

    group_size = _resolve_group_size(group_size)
    g = max(1, min(group_size, band, n - 2))
    w, G, wy = _factors(v_refl, taus, n, band, g, dt)
    n_pad = max(n, w)
    e_pad = e_host if n_pad == n else np.pad(e_host, ((0, n_pad - n), (0, 0)))

    dist = Distribution(Size2D(n, k), Size2D(*block_size), grid.grid_size, Index2D(0, 0))
    prec = get_tune_parameters().eigensolver_matmul_precision
    fn = _apply_fn(
        n_pad, k, w, g, G, dt,
        dist_key=(grid.cache_key, dist), dist=dist, sharding=grid.stacked_sharding(),
        prec=prec,
    )
    with matmul_precision(prec):
        data = fn(jnp.asarray(e_pad), *wy)
    return DistributedMatrix(dist, grid, data)
