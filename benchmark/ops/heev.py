"""Hermitian eigensolver, all eigenpairs, DLA-Future's ``miniapp_eigensolver``.

Input: ``set_random_hermitian`` -- uniform [-1, 1], Hermitian.  Call: the
library's public ``hermitian_eigensolver("L", A)``.  Reference: LAPACK's
eigenvalues in float64 on the host, of the same input, and the eigenpair
residual and orthogonality of the returned vectors, computed in float64.
"""
from __future__ import annotations

import numpy as np

# A CPU rehearsal takes the accelerator's band and SBR defaults, which
# 'auto' turns off on the CPU, so it runs the path the chip runs.
REHEARSE_TUNE = {"eigensolver_sbr_band": 32, "eigensolver_min_band": 100}


def _native_chase_built(run) -> None:
    """The native chase, where the configuration asks for it, has to be
    built: without it the library takes a dense host band stage."""
    from dlaf_tpu.native import get_lib
    from dlaf_tpu.tune import get_tune_parameters

    if get_tune_parameters().band_chase_backend == "native" and get_lib() is None:
        raise SystemExit("benchmark: the native band chase did not build (g++)")


SETUP_CHECKS = (_native_chase_built,)


def flops(n: int, complex_: bool) -> float:
    """4/3 n^3: the count of the tridiagonal reduction, the convention of
    the repository's earlier reports; four real operations each for
    complex."""
    return (4.0 if complex_ else 1.0) * 4 * n**3 / 3


def bytes_moved(n: int, itemsize: int) -> float:
    """The least HBM traffic: read A once and write the eigenvectors once."""
    return 2.0 * n * n * itemsize


def generate(jax, jnp, key, n: int, dtype):
    """Uniform [-1, 1] Hermitian, on the device (traced inside one jitted
    call): the strictly lower triangle mirrored, the diagonal real."""
    dtype = jnp.dtype(dtype)
    real = jnp.finfo(dtype).dtype
    kr, ki = jax.random.split(key)
    r = jax.random.uniform(kr, (n, n), real, -1.0, 1.0)
    if jnp.issubdtype(dtype, jnp.complexfloating):
        r = r + 1j * jax.random.uniform(ki, (n, n), real, -1.0, 1.0)
    low = jnp.tril(r, -1)
    return (low + low.conj().T + jnp.diag(jnp.diagonal(r).real)).astype(dtype)


def solve(dt, mat, **kwargs):
    return dt.hermitian_eigensolver("L", mat, **kwargs)


def outputs(result):
    return result.eigenvectors.data


def gather(result):
    return np.asarray(result.eigenvalues), result.eigenvectors.to_global()


def reference(a: np.ndarray):
    """``(a in float64, its eigenvalues by LAPACK)``."""
    import scipy.linalg

    wide = np.complex128 if np.iscomplexobj(a) else np.float64
    a64 = a.astype(wide)
    return a64, scipy.linalg.eigvalsh(a64, check_finite=False)


def compare(ref, out) -> dict:
    """Over every eigenpair, each relative to ||A||_2: the widest
    eigenvalue gap to LAPACK's, the widest residual |A v - l v|, and the
    widest departure of V^H V from the identity."""
    a64, lam_ref = ref
    lam, v = out
    n = a64.shape[0]
    if lam.shape != (n,) or v.shape != (n, n):
        return {"eig_err": float("inf"), "residual": float("inf"),
                "orthogonality": float("inf")}
    anorm = float(np.max(np.abs(lam_ref)))
    v64 = v.astype(a64.dtype)
    lam64 = lam.astype(np.float64)
    return {
        "eig_err": float(np.max(np.abs(np.sort(lam64) - lam_ref)) / anorm),
        "residual": float(np.max(np.linalg.norm(a64 @ v64 - v64 * lam64, axis=0)) / anorm),
        "orthogonality": float(np.max(np.abs(v64.conj().T @ v64 - np.eye(n)))),
    }
