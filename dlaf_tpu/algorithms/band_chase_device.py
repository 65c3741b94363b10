"""Device-side small-band -> tridiagonal bulge chase (batched wavefront).

Removes the serial host ceiling of the band stage: the native C++ chase
(native/band2trid.cpp) pipelines Householder sweeps over HOST threads — on a
few-core controller it is the Amdahl limit of HEEV at large N (O(N^2 b)
scalar work).  This kernel runs the SAME reduction on the accelerator as a
*batched wavefront*: at device step T, sweep ``s`` executes chase unit
``m = T - 3s`` — the exact 3-step chase-distance discipline of the threaded
kernel (band2trid.cpp:520-524: unit (s, m) touches rows [1+s+mb, s+mb+2b],
so units {(s, T-3s)} have pairwise disjoint windows and commute).  Each
step gathers the active windows from compact band storage, applies the
two-sided / bulge Householder updates as one batched dense op, and scatters
back — O(n/(3b)) sweeps in flight, every one a 2b x 2b dense update that
XLA fuses, instead of one scalar chase on one core.

Reflector convention is IDENTICAL to the native kernel (reference
SweepWorker formulation, band_to_tridiag/mc.h:477-537): reflector (s, m)
has head row ``1 + s + m*b``, length ``min(b, n-head)``, ``v[0] = 1``,
stored at slot ``offs[s] + m`` (sweep asc, step asc) — so the blocked WY
back-transform (bt_band_hh) consumes the output unchanged.

Memory: sweeps run in blocks of ``SB`` (a block completes before the next
starts — the cross-block dependency is then trivially satisfied); each
block's reflectors ([SB, K_cap, b]) are staged to host when the block
finishes, so transform storage on device is O(SB * n/b * b), not O(n^2/b).
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import numpy as np

_K_ROUND = 32  # chase-unit bucket granularity (bounds compile count)


def _units(n: int, b: int, s: int) -> int:
    """Chase units (== reflector count) of sweep s: (n-3-s)//b + 1
    (band2trid.cpp b2t_hh_count)."""
    return (n - 3 - s) // b + 1


def _larfg_batched(x, L, jnp):
    """Batched LAPACK-convention Householder generation, masked to length
    ``L`` (per lane): returns (v, tau, beta) with H = I - tau v v^H,
    H^H x = beta e1, v[0] = 1.  Mirrors native/band2trid.cpp larfg_
    (same copysign convention => bit-comparable reflectors)."""
    SB, b = x.shape
    idx = jnp.arange(b)[None, :]
    inl = idx < L[:, None]
    x = jnp.where(inl, x, 0)
    alpha = x[:, 0]
    xnorm2 = jnp.sum(jnp.abs(x[:, 1:]) ** 2, axis=1)  # tail already L-masked
    alphr = jnp.real(alpha)
    alphi = jnp.imag(alpha) if jnp.iscomplexobj(x) else jnp.zeros_like(alphr)
    degenerate = (xnorm2 == 0) & (alphi == 0) | (L <= 1)
    beta = -jnp.copysign(jnp.sqrt(jnp.abs(alpha) ** 2 + xnorm2), alphr)
    beta = jnp.where(degenerate, alphr, beta)  # placeholder, tau=0 anyway
    safe_beta = jnp.where(beta == 0, 1.0, beta)
    tau = jnp.where(degenerate, 0.0, (safe_beta - alpha) / safe_beta)
    scale = jnp.where(degenerate, 0.0, 1.0 / jnp.where(alpha == safe_beta, 1.0, alpha - safe_beta))
    v = jnp.where(inl, x * scale[:, None], 0)
    v = v.at[:, 0].set(1.0)
    beta_out = jnp.where(degenerate, alpha, beta.astype(x.dtype))
    return v, tau.astype(x.dtype), beta_out


def _chase_block_kernel(
    ab_flat, vcur, taucur, v_out, tau_out, s0, counts, t_max,
    *, n: int, n_pad: int, b: int, SB: int, K: int,
):
    """Run sweeps [s0, s0+SB) to completion (wavefront over t_max device
    steps).  ab_flat: raveled [2b+1, n_pad] band storage; counts[SB]: units
    per sweep; v_out/tau_out: [SB, K, b] / [SB, K] reflector stage."""
    import jax.numpy as jnp
    from jax import lax

    W = 2 * b
    rw = jnp.arange(W)[:, None]
    cw = jnp.arange(W)[None, :]
    lower = rw >= cw
    idx_low = (rw - cw) * n_pad + cw  # + j per lane
    idx_up = (cw - rw) * n_pad + rw
    sl = jnp.arange(SB)
    cplx = jnp.iscomplexobj(vcur)

    def conj(z):
        return jnp.conj(z) if cplx else z

    def step(T, carry):
        ab, vcur, taucur, v_out, tau_out = carry
        m = T - 3 * sl                      # [SB] unit index per lane
        s = s0 + sl                         # global sweep index
        active = (m >= 0) & (m < counts)
        j = s + 1 + m * b                   # window origin (garbage if inactive)
        j = jnp.where(active, j, 0)

        # ---- initial reflector for lanes at m == 0 (from band column s:
        # A[s+1 .. s+1+L, s] = ab[1+i, s], L = min(b, n-1-s)) ----
        first = active & (m == 0)
        Lf = jnp.clip(n - 1 - s, 0, b)
        colidx = (1 + jnp.arange(b)[None, :]) * n_pad + s[:, None]
        x0 = jnp.take(ab, colidx, mode="clip").reshape(SB, b)
        v1n, t1n, beta0 = _larfg_batched(x0, Lf, jnp)
        # write back beta e1 into column s (masked: first lanes, i < Lf)
        col_new = jnp.where(jnp.arange(b)[None, :] == 0, beta0[:, None], 0)
        wmask = first[:, None] & (jnp.arange(b)[None, :] < Lf[:, None])
        ab = ab.at[jnp.where(wmask, colidx, ab.shape[0])].set(
            jnp.where(wmask, col_new, 0), mode="drop"
        )
        v1 = jnp.where(first[:, None], v1n, vcur)
        t1 = jnp.where(first, t1n, taucur)
        # stage slot (s, 0)
        v_out = jnp.where(
            (first[:, None, None]) & (jnp.arange(K)[None, :, None] == 0), v1[:, None, :], v_out
        )
        tau_out = jnp.where(first[:, None] & (jnp.arange(K)[None, :] == 0), t1[:, None], tau_out)

        # ---- densify the 2b x 2b Hermitian windows ----
        gl = jnp.take(ab, idx_low[None] + j[:, None, None], mode="clip")
        gu = jnp.take(ab, idx_up[None] + j[:, None, None], mode="clip")
        M = jnp.where(lower[None], gl, conj(gu))

        # ---- two-sided apply: M <- H1^H M H1 (v1 support [0, nlen)) ----
        v1w = jnp.concatenate([v1, jnp.zeros_like(v1)], axis=1)  # [SB, W]
        vhM = jnp.einsum("sr,src->sc", conj(v1w), M)
        M = M - conj(t1)[:, None, None] * v1w[:, :, None] * vhM[:, None, :]
        Mv = jnp.einsum("src,sc->sr", M, v1w)
        M = M - t1[:, None, None] * Mv[:, :, None] * conj(v1w)[:, None, :]

        # ---- next reflector from the bulge column (M[b:2b, 0]) ----
        mm = jnp.clip(n - b - j, 0, b)      # bulge height
        gen = active & (mm > 1)
        x2 = M[:, b:, 0]
        v2, t2, beta2 = _larfg_batched(x2, mm, jnp)
        # bulge column <- beta e1 (larfg writes through, cpp:556 via larfg_)
        i_b = jnp.arange(b)[None, :]
        new_bulge = jnp.where(i_b == 0, beta2[:, None], 0)
        col0 = jnp.where(gen[:, None] & (i_b < mm[:, None]), new_bulge, M[:, b:, 0])
        M = M.at[:, b:, 0].set(col0)
        # left apply H2^H to cols [1, b) (cpp hh_left: cols [j+1, j+nlen))
        v2w = jnp.concatenate([jnp.zeros_like(v2), v2], axis=1)
        vhM2 = jnp.einsum("sr,src->sc", conj(v2w), M)
        colmask = ((cw[0] >= 1) & (cw[0] < b))[None, :]
        upd = conj(t2)[:, None, None] * v2w[:, :, None] * jnp.where(colmask, vhM2, 0)[:, None, :]
        M = M - jnp.where(gen[:, None, None], upd, 0)

        # ---- scatter the lower windows back (disjoint across lanes) ----
        sc_idx = idx_low[None] + j[:, None, None]
        sc_mask = active[:, None, None] & lower[None]
        ab = ab.at[jnp.where(sc_mask, sc_idx, ab.shape[0])].set(
            jnp.where(sc_mask, M, 0), mode="drop"
        )

        # ---- stage reflector (s, m+1), carry state ----
        slot = jnp.where(gen, m + 1, K)     # K = out-of-range drop row
        kk = jnp.arange(K)[None, :]
        hit = kk == slot[:, None]
        v_out = jnp.where(hit[:, :, None], v2[:, None, :], v_out)
        tau_out = jnp.where(hit, t2[:, None], tau_out)
        vcur = jnp.where(gen[:, None], v2, v1)
        taucur = jnp.where(gen, t2, t1)
        return ab, vcur, taucur, v_out, tau_out

    return lax.fori_loop(0, t_max, step, (ab_flat, vcur, taucur, v_out, tau_out))


def device_chase_hh(
    ab_host: np.ndarray, band: int, sweeps_per_block: int = 0, want_q: bool = True
) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]]:
    """Band -> tridiagonal on DEVICE, retaining the compact reflector set.

    ``ab_host``: (>= band+1, n) compact lower-band storage (ab[d, j] =
    A[j+d, j]).  Returns (d, e_raw, V[R, band], tau[R]) in exactly the
    native kernel's slot convention (band2trid_hh), or None when the
    problem is degenerate for this path (band <= 1: already tridiagonal).
    ``want_q=False`` skips the host staging of V/tau (eigenvalues-only;
    the in-kernel reflector work is part of the chase either way).
    """
    import jax
    import jax.numpy as jnp

    from dlaf_tpu.tune import get_tune_parameters, matmul_precision

    b = int(band)
    n = ab_host.shape[1]
    dt = np.dtype(ab_host.dtype)
    rdt = np.float32 if dt in (np.dtype(np.float32), np.dtype(np.complex64)) else np.float64
    if b <= 1 or n <= 2:
        if b < 1 or n == 0:
            return None
        d = ab_host[0, :n].real.astype(rdt)
        e = ab_host[1, : n - 1].astype(dt) if n > 1 else np.zeros(0, dt)
        return d, e, np.zeros((0, max(b, 1)), dt), np.zeros(0, dt)
    nsweeps = n - 2
    K_full = _units(n, b, 0)
    if sweeps_per_block <= 0:
        sweeps_per_block = int(get_tune_parameters().band_chase_device_block)
    SB = max(8, min(sweeps_per_block, nsweeps))
    n_pad = n + 2 * b + 2
    ld = 2 * b + 1
    ab0 = np.zeros((ld, n_pad), dt)
    rows_in = min(ab_host.shape[0], b + 1)
    ab0[:rows_in, :n] = ab_host[:rows_in]
    ab = jnp.asarray(ab0).ravel()
    offs = np.concatenate([[0], np.cumsum([_units(n, b, s) for s in range(nsweeps)])])
    R = int(offs[-1])
    V = np.zeros((R, b), dt)
    tau = np.zeros(R, dt)
    prec = get_tune_parameters().eigensolver_matmul_precision
    with matmul_precision(prec):
        for s0 in range(0, nsweeps, SB):
            s1 = min(nsweeps, s0 + SB)
            counts = np.array(
                [_units(n, b, s) if s < nsweeps else 0 for s in range(s0, s0 + SB)],
                np.int32,
            )
            # bucket K so consecutive blocks share the compiled kernel
            K = int(min(-(-int(counts.max()) // _K_ROUND) * _K_ROUND, K_full))
            t_max = int(3 * (min(s1 - s0, SB) - 1) + counts.max())
            from dlaf_tpu.plan import core as _plan

            kern = _plan.cached(
                "band_chase", (dt, b, SB, K, n, n_pad, prec),
                lambda: _plan.jit(
                    "band_chase",
                    partial(
                        _chase_block_kernel, n=n, n_pad=n_pad, b=b, SB=SB, K=K
                    ),
                    donate_argnums=(0, 1, 2, 3, 4),
                ),
            )
            vcur = jnp.zeros((SB, b), dt)
            taucur = jnp.zeros((SB,), dt)
            v_out = jnp.zeros((SB, K, b), dt)
            tau_out = jnp.zeros((SB, K), dt)
            ab, _, _, v_out, tau_out = kern(
                ab, vcur, taucur, v_out, tau_out,
                jnp.asarray(s0, jnp.int32), jnp.asarray(counts), jnp.asarray(t_max, jnp.int32),
            )
            if want_q:
                v_np = np.asarray(jax.device_get(v_out))
                t_np = np.asarray(jax.device_get(tau_out))
                for i, s in enumerate(range(s0, s1)):
                    c = int(counts[i])
                    V[offs[s] : offs[s] + c] = v_np[i, :c]
                    tau[offs[s] : offs[s] + c] = t_np[i, :c]
    ab_np = np.asarray(jax.device_get(ab)).reshape(ld, n_pad)
    d = ab_np[0, :n].real.astype(rdt)
    e_raw = ab_np[1, : n - 1].astype(dt)
    return d, e_raw, V, tau
