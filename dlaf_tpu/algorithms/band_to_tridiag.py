"""Band -> real symmetric tridiagonal reduction (host stage).

TPU-native placement of the reference band_to_tridiagonal
(reference: include/dlaf/eigensolver/band_to_tridiag.h:106-174 and
band_to_tridiag/mc.h — bulge-chasing SweepWorker pipeline, **CPU-only** in
the reference too, api.h:40-46).  The band is O(N*nb) data — tiny next to
the N^2 matrix — so like the reference we hop to the host for this
sequential stage: gather the band, reduce to tridiagonal on CPU, and return
the orthogonal/unitary transformation for the back-transform stage.

Round-1 implementation detail: the host reduction uses LAPACK via scipy
(Hessenberg reduction of the dense band matrix + phase normalization for the
complex case).  A native C++ bulge-chasing kernel that exploits bandedness
(O(N^2 b) instead of O(N^3)) replaces this in dlaf_tpu/native.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np
import scipy.linalg as sla

from dlaf_tpu.matrix.matrix import DistributedMatrix
from dlaf_tpu.obs.trace import phase


@dataclass
class BandToTridiagResult:
    """d, e: real tridiagonal (diagonal / off-diagonal); q2: host (n x n)
    transformation with q2^H B q2 = tridiag (the reference returns the
    equivalent compact HH reflector matrix); phases: the accumulated
    subdiagonal phase factors rolled into q2's columns (identity for real
    dtypes)."""

    d: np.ndarray
    e: np.ndarray
    q2: np.ndarray
    phases: np.ndarray = None


def _gather_band_tiles(mat: DistributedMatrix):
    """Fetch the diagonal and first-subdiagonal tiles to host in ONE jitted
    gather with replicated output — multi-process safe (``get_tile`` reads
    local shards and cannot cross processes) and a single O(N*nb) transfer
    instead of ~2*mt separate fetches.  Returns host arrays
    ``(diag [mt, mb, nb], sub [mt-1, mb, nb])`` (padded tile extents; the
    callers trim with ``tile_size_of``)."""
    dist, grid = mat.dist, mat.grid
    from dlaf_tpu.plan import core as _plan

    def build():
        # the cache key fully determines these index arrays, so they are
        # built only alongside the jit that closes over them
        mt = dist.nr_tiles.rows
        idx = {}
        for name, tiles in (
            ("diag", [(i, i) for i in range(mt)]),
            ("sub", [(i + 1, i) for i in range(mt - 1)]),
        ):
            rr, cc, ll, jj = [], [], [], []
            for gt in tiles:
                r, c = dist.rank_global_tile(gt)
                li, lj = dist.local_tile_index(gt)
                rr.append(r), cc.append(c), ll.append(li), jj.append(lj)
            idx[name] = tuple(np.asarray(v, np.int32) for v in (rr, cc, ll, jj))
        import jax

        rep = grid.replicated_sharding()
        return _plan.jit(
            "band_gather",
            lambda x: (x[idx["diag"]], x[idx["sub"]]),
            out_shardings=(rep, rep),
        )

    fn = _plan.cached(
        "band_gather",
        (grid.cache_key, tuple(dist.size), tuple(dist.block_size),
         tuple(dist.source_rank), str(np.dtype(mat.dtype))),
        build,
    )
    diag, sub = fn(mat.data)
    with phase("band_stage/readback"):
        return np.asarray(diag), np.asarray(sub)


def extract_band_host(mat: DistributedMatrix, band: int) -> np.ndarray:
    """Gather the Hermitian band (lower storage) to a dense host matrix
    (O(N*nb) transfers; never materializes N^2 on device)."""
    m = mat.size.rows
    nb = mat.block_size.rows
    a = np.zeros((m, m), dtype=np.dtype(mat.dtype))
    mt = mat.nr_tiles.rows
    diag, sub = _gather_band_tiles(mat)
    for i in range(mt):
        ts = mat.dist.tile_size_of((i, i))
        dt = diag[i][: ts.rows, : ts.cols]
        r0 = i * nb
        sz = dt.shape[0]
        a[r0 : r0 + sz, r0 : r0 + sz] = np.tril(dt)
        if i + 1 < mt:
            ts1 = mat.dist.tile_size_of((i + 1, i))
            st = sub[i][: ts1.rows, : ts1.cols]
            r1 = (i + 1) * nb
            sz1 = st.shape[0]
            # only the band part (upper triangle incl diag) of the subdiag
            # tile is band data; below it live red2band reflector tails
            a[r1 : r1 + sz1, r0 : r0 + sz] = np.triu(st)
    # element-level band mask (defensive: drop anything outside the band)
    i, j = np.meshgrid(np.arange(m), np.arange(m), indexing="ij")
    a = np.where((i - j > band) | (i < j), 0, a)
    return a + np.tril(a, -1).conj().T


def extract_band_storage(mat: DistributedMatrix, band: int) -> np.ndarray:
    """Gather the band into (band+2, n) lower-banded storage (the extra row
    is bulge scratch for the native kernel)."""
    m = mat.size.rows
    nb = mat.block_size.rows
    ab = np.zeros((band + 2, m), dtype=np.dtype(mat.dtype))
    mt = mat.nr_tiles.rows
    diag, sub = _gather_band_tiles(mat)
    for i in range(mt):
        ts = mat.dist.tile_size_of((i, i))
        dt_ = np.tril(diag[i][: ts.rows, : ts.cols])
        r0 = i * nb
        sz = dt_.shape[0]
        for off in range(min(band + 1, sz)):
            ab[off, r0 : r0 + sz - off] += np.diagonal(dt_, -off)
        if i + 1 < mt:
            ts1 = mat.dist.tile_size_of((i + 1, i))
            st = np.triu(sub[i][: ts1.rows, : ts1.cols])
            # subdiag tile element (a, b) is global (r0+nb+a, r0+b):
            # offset = nb + a - b in [1, band] — i.e. tile diagonal k = b - a
            # in [nb-band, nb-1]; scatter one diagonal (vector) at a time
            for k in range(max(0, nb - band), min(st.shape[1], nb)):
                diagv = np.diagonal(st, k)
                ab[nb - k, r0 + k : r0 + k + diagv.shape[0]] = diagv
    return ab


def band_to_tridiagonal(
    mat_band: DistributedMatrix,
    band: int | None = None,
    want_q: bool = True,
    backend: str = "auto",
) -> BandToTridiagResult:
    """Reduce the banded Hermitian matrix (band in the lower triangle of
    ``mat_band``, as produced by reduction_to_band) to real symmetric
    tridiagonal form.  Returns (d, e, q2); q2 is None when ``want_q=False``.

    Backends:
      - 'native': C++ bulge chasing (dlaf_tpu/native/band2trid.cpp) —
        O(N^2 b) reduction exploiting bandedness; Q accumulation is scalar
        O(N^3), so it wins when Q is NOT needed (eigenvalues-only paths).
      - 'lapack': dense Hessenberg via LAPACK (BLAS3) — faster when the
        explicit N x N Q is required.
      - 'auto': native for want_q=False, lapack otherwise.
    (Round-2 plan: native kernel returns the rotation stream for distributed
    application to the eigenvector block, removing the N x N Q entirely —
    the reference's compact-reflector strategy, bt_band_to_tridiag/impl.h.)
    """
    if band is None:
        band = getattr(mat_band, "band_size", mat_band.block_size.rows)
    m = mat_band.size.rows
    dt = np.dtype(mat_band.dtype)
    if m == 0:
        rd = np.float32 if dt in (np.dtype(np.float32), np.dtype(np.complex64)) else np.float64
        return BandToTridiagResult(np.zeros(0, rd), np.zeros(0, rd), np.zeros((0, 0), dt))
    if backend == "auto":
        backend = "lapack" if want_q else "native"
    if backend == "native":
        from dlaf_tpu.native import band2trid_native

        ab = extract_band_storage(mat_band, band)
        native = band2trid_native(ab, band, want_q=want_q)
        if native is not None:
            d_n, e_n, q = native
            if not want_q:
                r = _normalize_phases(d_n, e_n, None, dt)
                return r
            return _normalize_phases(d_n, e_n, q, dt)
        # fall through to lapack
    a = extract_band_host(mat_band, band)
    if not want_q:
        h = sla.hessenberg(a, calc_q=False)
        return _normalize_phases(
            np.real(np.diagonal(h)).copy(), np.diagonal(h, -1).copy(), None, dt
        )
    h, q = sla.hessenberg(a, calc_q=True)
    d = np.real(np.diagonal(h)).copy()
    e_raw = np.diagonal(h, -1).copy()
    return _normalize_phases(d, e_raw, q, dt)


def band_to_tridiagonal_hh(mat_band: DistributedMatrix, band: int | None = None):
    """Householder-sweep band stage retaining the compact reflector set
    (reference SweepWorker formulation, band_to_tridiag/mc.h:477-537).
    Returns (d, e, phases, V[R, band], tau[R], band) — consumable by
    bt_band_hh.bt_band_to_tridiagonal_hh's blocked device back-transform —
    or None when the native library is unavailable.

    ``e`` is real; for complex dtypes any residual subdiagonal phase (only
    the final entry, which no sweep covers) is folded into ``phases``."""
    if band is None:
        band = getattr(mat_band, "band_size", mat_band.block_size.rows)
    dt = np.dtype(mat_band.dtype)
    m = mat_band.size.rows
    if m == 0:
        return None
    ab = extract_band_storage(mat_band, band)
    return band_to_tridiagonal_hh_storage(ab, band, dt)


def resolve_chase_backend() -> str:
    """Where the bulge chase runs (tune ``band_chase_backend``): 'auto'
    picks the batched-wavefront DEVICE kernel on accelerator backends —
    removing the serial host ceiling — and the threaded native host kernel
    on CPU (where the "device" kernel would share cores with the host
    path)."""
    from dlaf_tpu.tune import get_tune_parameters

    be = get_tune_parameters().band_chase_backend
    if be != "auto":
        return be
    import jax

    return "device" if jax.default_backend() != "cpu" else "native"


def band_to_tridiagonal_hh_storage(ab: np.ndarray, band: int, dt, backend: str | None = None):
    """``band_to_tridiagonal_hh`` on compact (>= band+2, n) lower-band
    storage directly (the SBR second stage hands its reduced band here).
    Backend: 'device' = batched wavefront chase on the accelerator
    (band_chase_device.py), 'native' = threaded C++ host chase."""
    if backend is None:
        backend = resolve_chase_backend()
    if backend == "device" and band >= 2:
        from dlaf_tpu.algorithms.band_chase_device import device_chase_hh

        out = device_chase_hh(ab, band)
    else:
        from dlaf_tpu.native import band2trid_hh

        with phase("band_stage/chase/native"):
            out = band2trid_hh(ab, band)
    if out is None:
        return None
    d, e_raw, v_refl, taus = out
    with phase("band_stage/chase/phases"):
        norm = _normalize_phases(d, e_raw, None, np.dtype(dt))
    return norm.d, norm.e, norm.phases, v_refl, taus, band


def band_to_tridiagonal_storage(ab: np.ndarray, band: int, dt) -> "BandToTridiagResult | None":
    """Eigenvalues-only chase on compact lower-band storage: (d, e) with
    phases normalized, q None — or None when no chase backend is available
    (shared by band_to_tridiagonal's native branch and the eigenvalues-only
    SBR path)."""
    if resolve_chase_backend() == "device" and band >= 2:
        from dlaf_tpu.algorithms.band_chase_device import device_chase_hh

        out = device_chase_hh(ab, band, want_q=False)
        if out is not None:
            d_n, e_n = out[0], out[1]
            return _normalize_phases(d_n, e_n, None, np.dtype(dt))
    from dlaf_tpu.native import band2trid_native

    native = band2trid_native(ab, band, want_q=False)
    if native is None:
        return None
    d_n, e_n, _ = native
    return _normalize_phases(d_n, e_n, None, np.dtype(dt))


def band_to_tridiagonal_stream(mat_band: DistributedMatrix, band: int | None = None):
    """Native-kernel variant that retains the compact rotation stream instead
    of materializing Q (the reference's compact-reflector strategy).  Returns
    (d, e, phases, stream) — apply the band-stage back-transform to a real
    tridiagonal-eigenvector block E via ``stream.apply(E * nothing) ...``:

        E_band = stream.apply(phases[:, None] * E)

    (phases fold the complex subdiagonal normalization).  Returns None when
    the native library is unavailable."""
    from dlaf_tpu.native import band2trid_stream

    if band is None:
        band = getattr(mat_band, "band_size", mat_band.block_size.rows)
    dt = np.dtype(mat_band.dtype)
    m = mat_band.size.rows
    if m == 0:
        return None
    ab = extract_band_storage(mat_band, band)
    out = band2trid_stream(ab, band)
    if out is None:
        return None
    d, e_raw, stream = out
    norm = _normalize_phases(d, e_raw, None, dt)
    return norm.d, norm.e, norm.phases, stream


def _normalize_phases(d, e_raw, q, dt) -> BandToTridiagResult:
    """Roll subdiagonal phases into Q columns so (d, e) is real:
    (Q D)^H A (Q D) = real tridiag with D = diag of accumulated phases."""
    m = d.shape[0]
    phases = np.ones(m, dtype=dt)
    if dt.kind == "c":
        for j in range(m - 1):
            ph = e_raw[j] / np.abs(e_raw[j]) if np.abs(e_raw[j]) > 0 else 1.0
            phases[j + 1] = phases[j] * ph
        if q is not None:
            q = q * phases[None, :]
        e = np.abs(e_raw)
    else:
        e = np.real(e_raw).copy()
    rd = np.float32 if dt in (np.dtype(np.float32), np.dtype(np.complex64)) else np.float64
    return BandToTridiagResult(np.asarray(d).astype(rd), np.asarray(e).astype(rd), q, phases)
