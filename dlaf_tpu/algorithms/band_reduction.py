"""Successive band reduction (SBR): band b1 -> band b2 on device.

Second reduction stage between ``reduction_to_band`` (dense -> b1) and the
host bulge-chasing tridiagonalization (b2 -> tridiagonal), shrinking the
host stage's O(N^2 b) cost by b1/b2 while the extra work runs as
MXU-shaped QRs + GEMMs on device.  The reference reaches the same goal by
tuning a single band size (eigensolver/internal/get_band_size.h) because
its bulge chase is a parallel multi-rank CPU pipeline
(band_to_tridiag/mc.h:477 SweepWorkerDist); in the single-controller TPU
design the chase is one host process, so a device-side band shrink is the
scaling lever (ELPA-style two-stage, see also Bischof-Lang SBR).

Algorithm (validated against a dense oracle in tests):  sweeps over column
blocks [c, c+b2).  Per sweep, QR-eliminate rows [c+b2, c+b1+b2) of the
block (the R diagonal lands exactly on distance b2), then chase the bulge:
each chase step QRs the b1 x b1 fill block [S[0]+b1, S[-1]+b1] x S (R
diagonal at distance b1) and applies Q two-sided inside a sliding dense
3*b1 window of the band.  Transient bandwidth stays < 2*b1, so the band
lives in compact [2*b1, n_pad] storage.  Every step skews one [2*b1,
3*b1] band slice into a dense window, updates it, and skews its lower band
back; each skew is a pad and a reshape (no index arrays, so no gather on
the device), and the window's corners beyond distance 2*b1 are exact
zeros.

The per-step b1 x b1 Q blocks — O(n^2 b1/b2) elements total — are staged
to HOST in fixed-size sweep chunks (the device only ever holds one
chunk), so transform storage never competes with the matrix for HBM.  The
back-transform streams the chunks back in reverse: within one sweep the
chase row ranges are disjoint, so a whole sweep applies as ONE batched
GEMM, communication-free under a column-sharded eigenvector layout (same
relayout trick as bt_band_hh).  Sweep chunks share compiled kernels: the
chunk's first sweep index is a traced argument and chase-step counts are
rounded up to coarse buckets (extra steps hit zero blocks and reduce to
identity no-ops).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import List, Tuple

import numpy as np

_CHUNK = 16  # sweeps per staged chunk
_K_ROUND = 16  # chase-step bucket granularity (bounds compile count)


@dataclass(frozen=True)
class SbrTransforms:
    """Q blocks of one SBR run, staged on host in sweep chunks.

    ``chunks[i] = (s0, q)`` with ``q[t, k]`` the b1 x b1 block acting on
    global rows ``(s0+t)*b2 + b2 + k*b1`` .. +b1; slots beyond a sweep's
    chase length hold identity (or harmless sign-flip no-ops)."""

    chunks: List[Tuple[int, np.ndarray]]
    n: int
    b1: int
    b2: int

    @property
    def n_sweeps(self) -> int:
        return sum(q.shape[0] for _, q in self.chunks)


def _n_sweeps(n: int, b2: int) -> int:
    return max(0, -(-(n - b2 - 1) // b2))


def _chase_bound(n: int, c: int, b1: int, b2: int) -> int:
    """Number of chase steps (k >= 1) for the sweep at column c, upper
    bound: chase k exists while S_k[0] = c + b2 + k*b1 < n."""
    return max(0, -(-(n - c - b2) // b1))


def _sweep_chunks(n: int, b1: int, b2: int):
    """Fixed-size sweep chunks [(s0, s1, K)]; K is the chase bucket of the
    chunk's FIRST sweep (the longest), rounded up to _K_ROUND so chunks
    share compiled kernels."""
    ns = _n_sweeps(n, b2)
    out = []
    s0 = 0
    while s0 < ns:
        s1 = min(ns, s0 + _CHUNK)
        k = _chase_bound(n, s0 * b2, b1, b2)
        k = min(-(-k // _K_ROUND) * _K_ROUND, _chase_bound(n, 0, b1, b2))
        out.append((s0, s1, max(k, 1)))
        s0 = s1
    return out


def _densify(abw):
    """Dense window M[i, j] = A[w0+i, w0+j] of a compact band slice
    ``abw`` [2*b1, 3*b1] (abw[d, j] = A[w0+j+d, w0+j]).

    Column j of the band moves down by j rows, a fixed skew done as a pad
    and a reshape: lt[j, i] = abw[i-j, j].  The lower triangle is lt.T,
    the upper conj(lt); beyond distance 2*b1 both are exact zeros."""
    import jax.numpy as jnp

    S, W = abw.shape
    lt = jnp.pad(abw.T, ((0, 0), (0, W + 1 - S))).reshape(-1)[: W * W]
    lt = lt.reshape(W, W)
    dd = jnp.arange(W)[:, None] - jnp.arange(W)[None, :]
    lower = (dd >= 0) & (dd < S)
    return jnp.where(lower, lt.T, jnp.where(dd < 0, jnp.conj(lt), 0))


def _scatter(abw, M):
    """Write the lower band of the window ``M`` back into ``abw``: the
    inverse skew, abw[d, j] = M[j+d, j] where j+d lies inside the window;
    entries past its edge keep ``abw``'s."""
    import jax.numpy as jnp

    S, W = abw.shape
    sk = jnp.pad(M.T.reshape(-1), (0, W)).reshape(W, W + 1)[:, :S].T
    s_valid = jnp.arange(S)[:, None] + jnp.arange(W)[None, :] < W
    return jnp.where(s_valid, sk, abw)


def _sbr_chunk_kernel(
    ab, qstack, s_base, *, b1: int, b2: int, CH: int, K: int, want_q: bool
):
    """Run sweeps [s_base, s_base+CH) with K chase steps each.

    ab: [2*b1, n_pad] compact lower-band storage (zero-padded past n);
    qstack: [CH, K+1, b1, b1] identity-initialized (0-size placeholder when
    ``want_q`` is False); s_base: traced chunk offset (so all full chunks
    share one compiled kernel per (CH, K) bucket)."""
    import jax.numpy as jnp
    from jax import lax

    W = 3 * b1
    S = 2 * b1

    def step(ab, w0, row_off: int, col_w: int):
        abw = lax.dynamic_slice(ab, (jnp.asarray(0, w0.dtype), w0), (S, W))
        M = _densify(abw)
        B = M[row_off : row_off + b1, 0:col_w]
        Q, _ = jnp.linalg.qr(B, mode="complete")
        # zero block => no-op: QR may return any orthogonal Q, but mixing
        # rows that still hold in-band data would break the band invariant
        Q = jnp.where(jnp.max(jnp.abs(B)) > 0, Q, jnp.eye(b1, dtype=Q.dtype))
        rows = slice(row_off, row_off + b1)
        M = M.at[rows, :].set(Q.conj().T @ M[rows, :])
        M = M.at[:, rows].set(M[:, rows] @ Q)
        abw = _scatter(abw, M)
        ab = lax.dynamic_update_slice(ab, abw, (jnp.asarray(0, w0.dtype), w0))
        return ab, Q

    def sweep_body(t, carry):
        ab, qstack = carry
        c = (s_base + t) * b2
        ab, Q0 = step(ab, c, b2, b2)
        z = jnp.asarray(0, jnp.asarray(t).dtype)
        if want_q:
            qstack = lax.dynamic_update_slice(qstack, Q0[None, None], (t, z, z, z))

        def chase_body(k, carry2):
            ab, qstack = carry2
            w0 = c + b2 + (k - 1) * b1
            ab, Q = step(ab, w0, b1, b1)
            if want_q:
                qstack = lax.dynamic_update_slice(
                    qstack, Q[None, None], (t, k, z, z)
                )
            return ab, qstack

        return lax.fori_loop(1, K + 1, chase_body, (ab, qstack))

    return lax.fori_loop(0, CH, sweep_body, (ab, qstack))


def sbr_reduce(ab_host: np.ndarray, b1: int, b2: int, want_q: bool = True):
    """Reduce the compact lower-band matrix ``ab_host`` ([>= b1+1, n] with
    ab[d, j] = A[j+d, j]) from band b1 to band b2 on device.

    Returns (ab2, tr): ab2 is [b2+2, n] host storage ready for the native
    bulge chase (row b2+1 zero scratch), tr the SbrTransforms for
    ``sbr_back_transform`` (empty when ``want_q=False`` — eigenvalues-only
    callers skip the transform storage).  Requires 1 <= b2 < b1."""
    import jax
    import jax.numpy as jnp

    from dlaf_tpu.obs.trace import phase
    from dlaf_tpu.tune import get_tune_parameters, matmul_precision

    n = ab_host.shape[1]
    dt = ab_host.dtype
    if not (1 <= b2 < b1):
        raise ValueError(f"sbr_reduce: need 1 <= b2 < b1, got {b1} -> {b2}")
    chunks = _sweep_chunks(n, b1, b2)
    if not chunks:
        ab2 = np.zeros((b2 + 2, n), dt)
        rows_in = min(ab_host.shape[0], b2 + 1)
        ab2[:rows_in] = ab_host[:rows_in]
        return ab2, SbrTransforms([], n, b1, b2)
    n_pad = n + 4 * b1 + b2
    ab0 = np.zeros((2 * b1, n_pad), dt)
    rows_in = min(ab_host.shape[0], b1 + 1)
    ab0[:rows_in, :n] = ab_host[:rows_in]
    prec = get_tune_parameters().eigensolver_matmul_precision
    eye = np.eye(b1, dtype=dt)
    ab = jnp.asarray(ab0)
    out_chunks: List[Tuple[int, np.ndarray]] = []
    with matmul_precision(prec):
        for (s0, s1, K) in chunks:
            CH = s1 - s0
            from dlaf_tpu.plan import core as _plan

            kern = _plan.cached(
                "sbr_chunk", (np.dtype(dt), b1, b2, n_pad, CH, K, prec, want_q),
                lambda: _plan.jit(
                    "sbr_chunk",
                    partial(_sbr_chunk_kernel, b1=b1, b2=b2, CH=CH, K=K,
                            want_q=want_q),
                    donate_argnums=(0, 1),
                ),
            )
            with phase("band_stage/sbr/chunk"):
                if want_q:
                    q0 = jnp.zeros((CH, K + 1, b1, b1), dt) + eye
                else:
                    q0 = jnp.zeros((0, 1, b1, b1), dt)
                ab, qchunk = kern(ab, q0, jnp.asarray(s0))
            if want_q:
                # stage to host immediately: the device only ever holds
                # one chunk of transform storage
                with phase("band_stage/sbr/readback"):
                    out_chunks.append((s0, np.asarray(jax.device_get(qchunk))))
    with phase("band_stage/sbr/readback"):
        ab_np = np.asarray(jax.device_get(ab))
    ab2 = np.zeros((b2 + 2, n), dt)
    ab2[: b2 + 1] = ab_np[: b2 + 1, :n]
    return ab2, SbrTransforms(out_chunks, n, b1, b2)


def _bt_chunk_loop(e_pad, qchunk, s_base, *, b1: int, b2: int, CH: int):
    """E := (chunk's Q product) E on the local column slice: sweeps in
    reverse, each applied as one batched GEMM over its disjoint windows."""
    import jax.numpy as jnp
    from jax import lax

    kcols = e_pad.shape[1]
    K = qchunk.shape[1] - 1
    span = (K + 1) * b1

    def sweep_body(t, e):
        s_loc = CH - 1 - t  # reverse order
        r0 = (s_base + s_loc) * b2 + b2
        z = jnp.asarray(0, jnp.asarray(r0).dtype)
        ew = lax.dynamic_slice(e, (r0, z), (span, kcols))
        ew = ew.reshape(K + 1, b1, kcols)
        qs = lax.dynamic_index_in_dim(qchunk, s_loc, 0, keepdims=False)
        ew = jnp.einsum("kab,kbc->kac", qs, ew)
        return lax.dynamic_update_slice(e, ew.reshape(span, kcols), (r0, z))

    return lax.fori_loop(0, CH, sweep_body, e_pad)


def sbr_back_transform(tr: SbrTransforms, mat_e, out_cols: bool = False):
    """E := Q_sbr E with E distributed: reshard to column panels (one
    all-to-all), stream the host-staged Q chunks through the device in
    reverse, apply each sweep's batched blocks locally, and reshard back —
    the same communication-free-rows pattern as bt_band_hh
    (reference: bt_band_to_tridiag/impl.h distributed path).

    ``mat_e`` may be a stacked DistributedMatrix OR the column-sharded
    :class:`~dlaf_tpu.matrix.colpanels.ColPanels` handed over by
    ``bt_band_to_tridiagonal_hh_dist(..., out_cols=True)`` — the fused
    form skips one unpack+pack all-to-all pair between the two stages.
    ``out_cols=True`` likewise returns ColPanels for the next stage
    (bt_reduction_to_band) instead of packing."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dlaf_tpu.comm import collectives as coll
    from dlaf_tpu.comm.grid import COL_AXIS, ROW_AXIS
    from dlaf_tpu.matrix import colpanels as cpan
    from dlaf_tpu.matrix import layout
    from dlaf_tpu.tune import get_tune_parameters, matmul_precision

    in_cols = isinstance(mat_e, cpan.ColPanels)
    if tr.n_sweeps == 0:
        if in_cols:
            return mat_e if out_cols else cpan.pack_to_matrix(mat_e)
        return mat_e
    if in_cols:
        n, k = mat_e.n, mat_e.k
    else:
        n, k = mat_e.dist.size
    if n != tr.n:
        raise ValueError(f"sbr_back_transform: E rows {n} != transform n {tr.n}")
    b1, b2 = tr.b1, tr.b2
    # every sweep's [r0, r0+span) slice must fit WITHOUT clamping (a
    # clamped start would misalign the real Q blocks)
    n_pad = max(
        n,
        max(
            (s0 + q.shape[0] - 1) * b2 + b2 + q.shape[1] * b1
            for (s0, q) in tr.chunks
        ),
    )
    grid = mat_e.grid
    dist = mat_e.dist
    dt = np.dtype(mat_e.data.dtype) if in_cols else np.dtype(mat_e.dtype)
    Ptot = grid.grid_size.count()
    kloc = -(-k // Ptot)
    kpad = kloc * Ptot
    mesh = grid.mesh
    colspec = P(None, (ROW_AXIS, COL_AXIS))
    col_sh = NamedSharding(mesh, colspec)
    prec = get_tune_parameters().eigensolver_matmul_precision
    if in_cols:
        # already column-sharded; only the row padding may differ (the WY
        # stage pads to its window, we pad to the chase span).  Row pad is
        # shard-local under column sharding — no communication.
        e_cols = mat_e.data
        if e_cols.shape[1] != kpad:
            raise ValueError(
                f"ColPanels kpad {e_cols.shape[1]} != expected {kpad}"
            )
        if e_cols.shape[0] < n_pad:
            from dlaf_tpu.plan import core as _plan

            rp = _plan.cached(
                "sbr_bt_rowpad",
                (grid.cache_key, tuple(e_cols.shape), n_pad, dt),
                lambda: _plan.jit(
                    "sbr_bt_rowpad",
                    lambda gp: jnp.pad(gp, ((0, n_pad - gp.shape[0]), (0, 0))),
                    out_shardings=col_sh,
                ),
            )
            e_cols = rp(e_cols)
        else:
            n_pad = int(e_cols.shape[0])
    else:
        from dlaf_tpu.plan import core as _plan

        def build_pre():
            def pre(x):
                gg = layout.unpad_global(layout.unpack(x, dist), dist)
                gp = jnp.pad(gg, ((0, n_pad - n), (0, kpad - k)))
                return jax.lax.with_sharding_constraint(gp, col_sh)

            # no donation: the stacked input cannot alias the col-sharded
            # padded output (different shapes), donating only warns
            return _plan.jit("sbr_bt_pre", pre, out_shardings=col_sh)

        e_cols = _plan.cached(
            "sbr_bt_pre", (grid.cache_key, dist, n_pad, kpad, dt), build_pre
        )(mat_e.data)
    # all stacked exits pack through the one shared jit in colpanels
    with matmul_precision(prec):
        for (s0, q) in reversed(tr.chunks):
            CH = q.shape[0]
            K = q.shape[1] - 1
            from dlaf_tpu.plan import core as _plan

            def build_apply(CH=CH):
                loop = partial(_bt_chunk_loop, b1=b1, b2=b2, CH=CH)
                sm = jax.shard_map(
                    lambda e, qc, sb: loop(e, qc, sb),
                    mesh=mesh,
                    in_specs=(colspec, P(), P()),
                    out_specs=colspec,
                    check_vma=False,
                )
                return _plan.jit("sbr_bt_apply", sm, out_shardings=col_sh,
                                 donate_argnums=(0,))

            apply_fn = _plan.cached(
                "sbr_bt_apply",
                (grid.cache_key, n_pad, kpad, b1, b2, CH, K, dt, prec),
                build_apply,
            )
            e_cols = apply_fn(e_cols, jnp.asarray(q), jnp.asarray(s0))
    if out_cols:
        return cpan.ColPanels(e_cols, n, k, grid, dist)
    out = cpan.pack_to_matrix(cpan.ColPanels(e_cols, n, k, grid, dist))
    return out if in_cols else mat_e._inplace(out.data)
