"""Hermitian (generalized) eigensolver orchestration.

TPU-native analogue of the reference eigensolver drivers
(reference: include/dlaf/eigensolver/eigensolver.h:39-256,
eigensolver/eigensolver/impl.h:37-106 — HEEV pipeline; gen_eigensolver.h:67-99,
gen_eigensolver/impl.h:31-105 — HEGV).  Pipeline (same staging as the
reference):

  reduction_to_band  (distributed, device)         impl.h:85
  band_to_tridiagonal (host, like the reference's CPU-only stage) impl.h:87
  tridiagonal_eigensolver (distributed on-device D&C) impl.h:89
  bt_band_to_tridiagonal (distributed WY groups)   impl.h:94
  bt_reduction_to_band (distributed WY applies)    impl.h:95

Partial spectrum via eigenvalue index range (MatrixRef col-slice in the
reference, eigensolver/impl.h:52-57) maps to a narrower eigenvector matrix.
"""
from __future__ import annotations

from dlaf_tpu.algorithms._origin import origin_transparent

from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

from dlaf_tpu.algorithms.band_to_tridiag import band_to_tridiagonal
from dlaf_tpu.algorithms.bt_band_to_tridiag import bt_band_to_tridiagonal
from dlaf_tpu.algorithms.bt_reduction_to_band import bt_reduction_to_band
from dlaf_tpu.algorithms.cholesky import cholesky_factorization
from dlaf_tpu.algorithms.gen_to_std import generalized_to_standard
from dlaf_tpu.algorithms.reduction_to_band import get_band_size, reduction_to_band
from dlaf_tpu.algorithms.triangular_solver import triangular_solver
from dlaf_tpu.algorithms.tridiag_solver import tridiagonal_eigensolver
from dlaf_tpu.matrix import util as mutil
from dlaf_tpu.matrix.matrix import DistributedMatrix
from dlaf_tpu.ops import tile as t


@dataclass
class EigResult:
    eigenvalues: np.ndarray  # ascending, host
    eigenvectors: DistributedMatrix  # n x k distributed


@origin_transparent
def hermitian_eigensolver(
    uplo: str,
    mat_a: DistributedMatrix,
    spectrum: Optional[Tuple[int, int]] = None,
    backend: str = "auto",
) -> EigResult:
    """Eigendecomposition of the Hermitian matrix stored in the ``uplo``
    triangle of ``mat_a``.  ``spectrum=(il, iu)`` selects the eigenvalue
    index range (inclusive, 0-based).

    ``backend='auto'`` routes single-device grids to XLA's built-in ``eigh``
    (the QDWH spectral divide & conquer — the TPU-native dense eigensolver,
    analogous to the reference offloading tile work to cuSOLVER) and
    multi-device grids to the distributed band-reduction pipeline;
    'pipeline' forces the latter everywhere."""
    from dlaf_tpu.matrix.io import maybe_dump

    maybe_dump("debug_dump_eigensolver_data", "dlaf_dump_eigensolver_input.npz", mat_a)
    if uplo == t.UPPER:
        # lower-storage pipeline on the mirrored matrix
        mat_a = mutil.extract_triangle(mutil.hermitize(mat_a, "U"), "L")
        uplo = t.LOWER
    if backend == "auto" and mat_a.grid.grid_size.count() == 1 and mat_a.size.rows > 0:
        return _eigh_single_device(mat_a, spectrum)
    from dlaf_tpu.obs.trace import phase

    # one span over the pipeline: the host time between its stages is
    # named by the library on a profile
    with phase("heev"):
        return _pipeline(mat_a, spectrum)


def _pipeline(mat_a: DistributedMatrix, spectrum) -> EigResult:
    """The distributed band-reduction pipeline on lower storage."""
    grid = mat_a.grid
    nb = mat_a.block_size.rows
    n = mat_a.size.rows
    band = get_band_size(nb)
    from dlaf_tpu.common import stagetimer as st
    from dlaf_tpu import health, obs

    # stage-boundary NaN/Inf sentinels (health.check_finite): active only at
    # DLAF_TPU_CHECK_LEVEL >= 2 — a plain early return below that, so the
    # compiled pipeline stages are untouched; at level 2 they pinpoint the
    # first stage whose output went non-finite (NonFiniteError.stage)
    with obs.stage("red2band"):
        band_mat, taus = reduction_to_band(mat_a, band=band)
        st.barrier(band_mat.data, taus)
    health.check_finite("red2band", band_mat, taus)
    # default band stage: (optional) on-device SBR band shrink, then native
    # Householder bulge chasing (O(N^2 b_small) on host, compact reflector
    # set, no N x N Q2 anywhere) with the blocked compact-WY back-transform
    # running as GEMMs on device — the reference's strategy
    # (band_to_tridiag/mc.h SweepWorker + bt_band_to_tridiag/impl.h grouped
    # applies) plus the ELPA-style second stage; full AND partial spectra.
    # The tridiagonal stage defaults to the multi-level distributed D&C and
    # its eigenvector matrix stays DISTRIBUTED through all back-transforms
    # — no O(N^2) host object on this path.
    from dlaf_tpu.algorithms.bt_band_hh import bt_band_to_tridiagonal_hh_dist

    with obs.stage("band_stage"):
        hh, tr_sbr = _band_stage_hh(band_mat, band)
    if hh is not None:
        health.check_finite("band_stage", hh[0], hh[1])
        with obs.stage("tridiag"):
            evals, v = tridiagonal_eigensolver(
                grid, hh[0], hh[1], nb, dtype=mat_a.dtype, spectrum=spectrum
            )
            st.barrier(v.data)
        health.check_finite("tridiag", evals, v)
        with obs.stage("bt_band"):
            # the whole back-transform chain (bt_band -> sbr -> bt_red2band)
            # is row transforms over independent columns: hand E between
            # stages COLUMN-SHARDED (ColPanels), packing back to the stacked
            # layout exactly once at the end — elides the intermediate
            # all-to-all pairs and the per-panel W psums of bt_red2band.
            # (Trivial no-reflector paths may still yield a stacked matrix,
            # which every stage accepts.)
            e = bt_band_to_tridiagonal_hh_dist(hh, v, out_cols=True)
            st.barrier(e.data)
        health.check_finite("bt_band", e)
        if tr_sbr is not None:
            from dlaf_tpu.algorithms.band_reduction import sbr_back_transform

            with obs.stage("bt_sbr"):
                e = sbr_back_transform(tr_sbr, e, out_cols=True)
                st.barrier(e.data)
            health.check_finite("bt_sbr", e)
        with obs.stage("bt_red2band"):
            e = bt_reduction_to_band(e, band_mat, taus)
            st.barrier(e.data)
        health.check_finite("bt_red2band", e)
        return EigResult(evals, e)
    # fallback (native library unavailable): explicit-Q host band stage
    if n > 0:  # m == 0 lands here too, but trivially — don't warn for it
        import warnings

        warnings.warn(
            "band stage fallback: no bulge-chase backend (native C++ lib not "
            "built and device wavefront kernel not selected) — using a DENSE "
            "host Hessenberg band stage: O(N^2) host memory and O(N^3) host "
            "flops instead of O(N^2 b). Build the native library (needs g++) "
            "or set DLAF_TPU_BAND_CHASE_BACKEND=device.",
            RuntimeWarning,
            stacklevel=3,
        )
    b2t = band_to_tridiagonal(band_mat, band=band)
    evals, e_tri = tridiagonal_eigensolver(
        grid, b2t.d, b2t.e, nb, dtype=mat_a.dtype, spectrum=spectrum
    )
    e = bt_band_to_tridiagonal(b2t.q2, e_tri)
    e = bt_reduction_to_band(e, band_mat, taus)
    return EigResult(evals, e)


def _sbr_target(band: int) -> int:
    """SBR second-stage target band: largest divisor of ``band`` not above
    ``eigensolver_sbr_band`` when that shrinks the band, else 0 (off).
    -1 = auto: 32 on accelerator backends, off on CPU (there the "device"
    SBR stage runs on the same CPU and costs more than it saves —
    measured n=2048 A/B in docs/BENCHMARKS.md)."""
    from dlaf_tpu.tune import get_tune_parameters

    t_ = int(get_tune_parameters().eigensolver_sbr_band)
    if t_ < 0:
        import jax

        t_ = 32 if jax.default_backend() != "cpu" else 0
    if t_ <= 0 or band <= t_:
        return 0
    b2 = min(t_, band - 1)
    while band % b2:
        b2 -= 1
    return b2 if b2 >= 2 else 0


def _band_stage_hh(band_mat: DistributedMatrix, band: int, want_q: bool = True):
    """Band -> tridiagonal stage: optional on-device SBR shrink
    (band -> b2, algorithms/band_reduction.py), then the native host bulge
    chase at the small band.

    ``want_q=True`` returns (hh tuple or None, SbrTransforms or None);
    ``want_q=False`` returns (BandToTridiagResult or None, None) — the
    eigenvalues-only variant with no transform storage.  A None first
    element means the native kernel is unavailable; callers fall back to
    the dense band stage on the ORIGINAL band matrix."""
    from dlaf_tpu.algorithms.band_to_tridiag import (
        band_to_tridiagonal_hh,
        band_to_tridiagonal_hh_storage,
        band_to_tridiagonal_storage,
        extract_band_storage,
        resolve_chase_backend,
    )
    from dlaf_tpu.native import get_lib

    dt = np.dtype(band_mat.dtype)
    m = band_mat.size.rows
    if m == 0:
        return None, None
    b2 = _sbr_target(band)
    # a chase backend exists if the device wavefront kernel is selected
    # (it needs no toolchain, so the native lib is then not built) or the
    # native lib built
    chase_ok = resolve_chase_backend() == "device" or get_lib() is not None
    if b2 and chase_ok:
        from dlaf_tpu.algorithms.band_reduction import sbr_reduce
        from dlaf_tpu.common import stagetimer as st
        from dlaf_tpu import obs

        # no explicit barriers here: sbr_reduce and the chase return HOST
        # arrays (each stages its device blocks through device_get), so the
        # stage clocks already include their device work
        with obs.stage("band_stage/sbr"):
            ab = extract_band_storage(band_mat, band)
            ab2, tr = sbr_reduce(ab, band, b2, want_q=want_q)
        with obs.stage("band_stage/chase"):
            if want_q:
                hh = band_to_tridiagonal_hh_storage(ab2, b2, dt)
                return hh, (tr if hh is not None and tr.n_sweeps else None)
            return band_to_tridiagonal_storage(ab2, b2, dt), None
    if want_q:
        return band_to_tridiagonal_hh(band_mat, band=band), None
    if chase_ok:
        return (
            band_to_tridiagonal_storage(extract_band_storage(band_mat, band), band, dt),
            None,
        )
    return None, None


def _eigh_single_device(mat_a: DistributedMatrix, spectrum) -> EigResult:
    """Single-device fast path: XLA eigh on the hermitized dense matrix.
    Partial spectra slice the eigenvector block ON DEVICE (the unpack ->
    slice -> repack runs inside the same jit; no O(N^2) host round-trip)."""
    import jax
    import jax.numpy as jnp

    from dlaf_tpu.common.index import Size2D
    from dlaf_tpu.matrix.distribution import Distribution
    from dlaf_tpu.matrix import layout

    dist = mat_a.dist
    n = dist.size.rows
    sl = None
    out_dist = dist
    if spectrum is not None:
        il, iu = int(spectrum[0]), int(spectrum[1])
        if not 0 <= il <= iu < n:
            raise ValueError(f"spectrum ({il}, {iu}) out of range for n={n}")
        sl = (il, iu)
        out_dist = Distribution(
            Size2D(n, iu - il + 1), dist.block_size, dist.grid_size, dist.source_rank
        )
    # two jits: the expensive eigh compiles once per (dist, dtype); each
    # spectrum slice only adds a tiny slice-and-pack executable
    from dlaf_tpu.plan import core as _plan

    def build_eigh():
        def run(x):
            g = layout.unpad_global(layout.unpack(x, dist), dist)
            full = jnp.tril(g) + jnp.swapaxes(jnp.tril(g, -1), -1, -2).conj()
            return jnp.linalg.eigh(full)  # dense (w, v), on device

        return _plan.jit("eigh_local", run)

    def build_pack():
        def packrun(w, v):
            if sl is not None:
                w = w[sl[0] : sl[1] + 1]
                v = v[:, sl[0] : sl[1] + 1]
            return w, layout.pack(layout.pad_global(v, out_dist), out_dist)

        return _plan.jit("eigh_local_pack", packrun)

    eigh_fn = _plan.cached(
        "eigh_local", (dist, np.dtype(mat_a.dtype)), build_eigh
    )
    pack_fn = _plan.cached(
        "eigh_local_pack", (dist, np.dtype(mat_a.dtype), sl), build_pack
    )
    w, vdata = pack_fn(*eigh_fn(mat_a.data))
    evecs = DistributedMatrix(
        out_dist, mat_a.grid, jax.device_put(vdata, mat_a.grid.stacked_sharding())
    )
    return EigResult(np.asarray(w), evecs)


@origin_transparent
def hermitian_eigenvalues(
    uplo: str,
    mat_a: DistributedMatrix,
    spectrum: Optional[Tuple[int, int]] = None,
) -> np.ndarray:
    """Eigenvalues only (LAPACK jobz='N' analogue): skips all back-transforms
    and the N x N band-stage Q — the band reduction runs the native C++
    bulge-chasing kernel (O(N^2 b))."""
    import scipy.linalg as sla

    if uplo == t.UPPER:
        mat_a = mutil.extract_triangle(mutil.hermitize(mat_a, "U"), "L")
    if mat_a.grid.grid_size.count() == 1 and mat_a.size.rows > 0:
        # single-device: XLA eigvalsh directly
        res = _eigh_single_device(mat_a, spectrum)
        return res.eigenvalues
    band = get_band_size(mat_a.block_size.rows)
    band_mat, _ = reduction_to_band(mat_a, band=band)
    b2t, _ = _band_stage_hh(band_mat, band, want_q=False)
    if b2t is None:
        b2t = band_to_tridiagonal(band_mat, band=band, want_q=False)
    if b2t.d.shape[0] == 0:
        return b2t.d
    if spectrum is None:
        return sla.eigh_tridiagonal(b2t.d, b2t.e, eigvals_only=True)
    return sla.eigh_tridiagonal(
        b2t.d, b2t.e, eigvals_only=True, select="i", select_range=spectrum
    )


@origin_transparent
def hermitian_generalized_eigensolver(
    uplo: str,
    mat_a: DistributedMatrix,
    mat_b: DistributedMatrix,
    spectrum: Optional[Tuple[int, int]] = None,
    factorized: bool = False,
) -> EigResult:
    """Solve A x = lambda B x (A Hermitian, B Hermitian positive definite).

    ``factorized=True`` means ``mat_b`` already holds the Cholesky factor
    (reference hermitian_generalized_eigensolver_factorized,
    gen_eigensolver.h:99)."""
    from dlaf_tpu.common import stagetimer as st
    from dlaf_tpu import obs

    with obs.stage("cholesky_b"):
        fac = mat_b if factorized else cholesky_factorization(uplo, mat_b)
        st.barrier(fac.data)
    with obs.stage("gen_to_std"):
        a_std = generalized_to_standard(uplo, mat_a, fac)
        a_tri = mutil.extract_triangle(a_std, uplo)
        st.barrier(a_tri.data)
    res = hermitian_eigensolver(uplo, a_tri, spectrum=spectrum)
    # back-substitute: x = L^-H y (uplo=L) / U^-1 y (uplo=U)
    with obs.stage("back_subst"):
        if uplo == t.LOWER:
            e = triangular_solver(t.LEFT, t.LOWER, t.CONJ_TRANS, t.NON_UNIT, 1.0, fac, res.eigenvectors)
        else:
            e = triangular_solver(t.LEFT, t.UPPER, t.NO_TRANS, t.NON_UNIT, 1.0, fac, res.eigenvectors)
        st.barrier(e.data)
    return EigResult(res.eigenvalues, e)
