"""Lower Cholesky factorization, DLA-Future's ``miniapp_cholesky``.

Input: ``set_random_hermitian_positive_definite`` -- uniform [-1, 1],
Hermitian, with 2N added to the diagonal.  Call: the library's public
``cholesky_factorization("L", A)``, which donates its input.  Reference:
LAPACK's Cholesky in float64 on the host, of the same input.
"""
from __future__ import annotations

import numpy as np


def flops(n: int, complex_: bool) -> float:
    """The reference's ``total_ops`` for POTRF: n^3/6 multiplications and
    n^3/6 additions (miniapp_cholesky.cpp), four real operations each for
    complex."""
    return (4.0 if complex_ else 1.0) * n**3 / 3


def bytes_moved(n: int, itemsize: int) -> float:
    """The least HBM traffic: read A once and write L once."""
    return 2.0 * n * n * itemsize


def generate(jax, jnp, key, n: int, dtype):
    """The SPD input, on the device (traced inside one jitted call): the
    strictly lower triangle mirrored, the diagonal real plus 2N."""
    dtype = jnp.dtype(dtype)
    real = jnp.finfo(dtype).dtype
    kr, ki = jax.random.split(key)
    r = jax.random.uniform(kr, (n, n), real, -1.0, 1.0)
    if jnp.issubdtype(dtype, jnp.complexfloating):
        r = r + 1j * jax.random.uniform(ki, (n, n), real, -1.0, 1.0)
    low = jnp.tril(r, -1)
    return (low + low.conj().T + jnp.diag(jnp.diagonal(r).real + 2 * n)).astype(dtype)


def solve(dt, mat, **kwargs):
    return dt.cholesky_factorization("L", mat, **kwargs)


def outputs(result):
    """The device arrays a solve is complete after."""
    return result.data


def gather(result) -> np.ndarray:
    """The factor on the host: the lower triangle (the upper one holds
    update residue by the library's LAPACK semantics)."""
    return np.tril(result.to_global())


def reference(a: np.ndarray) -> np.ndarray:
    """Cholesky factor of ``a`` in float64 (LAPACK potrf)."""
    import scipy.linalg

    wide = np.complex128 if np.iscomplexobj(a) else np.float64
    return scipy.linalg.cholesky(a.astype(wide), lower=True, check_finite=False)


def compare(ref: np.ndarray, out: np.ndarray) -> dict:
    """Forward error of the strictly lower triangle, where the trailing
    updates land: the largest entry's error and the Frobenius error, each
    relative to the reference's own.  (The 2N diagonal holds f32 rounding
    of order 2N eps, which no matmul precision moves.)"""
    worst = big = err2 = ref2 = 0.0
    n, step = ref.shape[0], 1024
    for i0 in range(0, n, step):
        i1 = min(i0 + step, n)
        r = ref[i0:i1, :i1].copy()  # rows i0:i1 left of the diagonal block's end
        d = out[i0:i1, :i1] - r
        r[:, i0:] = np.tril(r[:, i0:], -1)  # strictly below the diagonal
        d[:, i0:] = np.tril(d[:, i0:], -1)
        worst = max(worst, float(np.max(np.abs(d))))
        big = max(big, float(np.max(np.abs(r))))
        err2 += float(np.vdot(d, d).real)
        ref2 += float(np.vdot(r, r).real)
    if not np.isfinite(err2):
        worst = err2 = float("inf")
    return {"offdiag_max_err": worst / big, "offdiag_fro_err": (err2 / ref2) ** 0.5}
