"""Micro-kernel benchmark runner.

Analogue of the reference's kernel miniapps
(reference: miniapp/include/dlaf/miniapp/kernel_runner.h + miniapp/kernel/
larft/laset drivers): time individual tile-level kernels in isolation to
guide tile-size / backend tuning.

Usage: python -m dlaf_tpu.miniapp.kernel_runner [--nb 256] [--batch 16]
           [--type s] [--nreps 30] [--kernels potrf,trsm,gemm,tfactor]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

import dlaf_tpu.testing as tu
from dlaf_tpu.miniapp.common import DTYPES
from dlaf_tpu.ops import tile as t
from dlaf_tpu.plan import core as _plan


def _time(fn, *args, nreps: int) -> float:
    r = fn(*args)
    jax.block_until_ready(r)
    t0 = time.perf_counter()
    for _ in range(nreps):
        r = fn(*args)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / nreps


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--nb", type=int, default=256)
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--type", choices="sdcz", default="s")
    p.add_argument("--nreps", type=int, default=30)
    p.add_argument("--kernels", default="potrf,potrf_pallas,trsm,gemm,tfactor")
    p.add_argument(
        "--metrics", default="", metavar="PATH",
        help="write per-kernel timings as a dlaf_tpu.obs JSONL stream "
        "(one 'kernel' record per timed kernel)",
    )
    args = p.parse_args(argv)
    if args.metrics:
        from dlaf_tpu.obs import metrics as om

        om.enable(args.metrics)
        om.emit_run_meta("kernel_runner")
        om.emit_config()
    dtype = DTYPES[args.type]
    if np.dtype(dtype).itemsize == 8:
        jax.config.update("jax_enable_x64", True)
    nb, bt = args.nb, args.batch

    h = jnp.asarray(tu.random_hermitian_pd(nb, dtype, 0))
    l = jnp.asarray(tu.random_triangular(nb, dtype, lower=True, seed=1))
    panel = jnp.asarray(tu.random_matrix(bt * nb, nb, dtype, 2)).reshape(bt, nb, nb)
    a = jnp.asarray(tu.random_matrix(nb, nb, dtype, 3))
    v = jnp.asarray(tu.random_matrix(bt * nb, nb, dtype, 4))
    taus = jnp.asarray(np.full(nb, 1.5, np.dtype(dtype)))

    runners = {}
    runners["potrf"] = (_plan.jit("potrf", lambda x: t.potrf(x)), (h,), nb**3 / 3)
    try:
        from dlaf_tpu.ops import pallas_potrf

        if pallas_potrf.supported(h) and jax.default_backend() == "tpu":
            runners["potrf_pallas"] = (pallas_potrf.potrf_tile, (h,), nb**3 / 3)
    except Exception:
        pass
    runners["trsm"] = (
        _plan.jit("trsm", lambda lk, b: t.trsm(t.RIGHT, t.LOWER, t.CONJ_TRANS, t.NON_UNIT,
                                               1.0, lk, b)),
        (l, panel),
        bt * nb**3,
    )
    # hour-one A/B pair for tune.panel_trsm_pallas (real dtypes): the
    # column-blocked Pallas panel solve vs the XLA trsm above
    if np.dtype(dtype).kind == "f" and nb % 32 == 0:
        from dlaf_tpu.ops.pallas_panel_trsm import panel_trsm_right_lower_t

        flat_panel = panel.reshape(bt * nb, nb)
        runners["panel_trsm_pallas"] = (
            lambda lk, b: panel_trsm_right_lower_t(
                lk, b, False, jax.default_backend() == "cpu"
            ),
            (l, flat_panel),
            bt * nb**3,
        )
    # hour-one A/B pair for tune.dc_secular_pallas (f32): fused VMEM
    # bisection vs the XLA fori_loop formulation
    if np.dtype(dtype) == np.dtype(np.float32):
        from jax import lax as _lax

        from dlaf_tpu.ops.pallas_secular import secular_bisect

        K, S, ITERS = 1024, 512, 42
        rngs = np.random.default_rng(11)
        dsec = jnp.asarray(np.sort(rngs.standard_normal((K, S)).astype(np.float32), axis=1))
        z2s = jnp.asarray((rngs.standard_normal((K, S)).astype(np.float32)) ** 2 * 0.1)
        rhos = jnp.asarray(np.abs(rngs.standard_normal(K).astype(np.float32)) + 0.1)
        anc = dsec[:, 0] - 0.5
        lo_s = jnp.zeros(K, jnp.float32)
        hi_s = jnp.asarray(np.abs(rngs.standard_normal(K).astype(np.float32)) + 0.5)
        runners["secular_pallas"] = (
            lambda: secular_bisect(dsec, z2s, rhos, anc, lo_s, hi_s, ITERS,
                                   jax.default_backend() == "cpu"),
            (),
            2.0 * ITERS * K * S,  # div+add per pole per round
        )

        @jax.jit
        def _secular_xla():
            tiny = jnp.finfo(jnp.float32).tiny
            ag = dsec - anc[:, None]

            def body(_, lh):
                lo, hi = lh
                mid = 0.5 * (lo + hi)
                safe = jnp.where(ag - mid[:, None] == 0, tiny, ag - mid[:, None])
                fm = 1.0 + rhos * jnp.sum(z2s / safe, axis=1)
                return jnp.where(fm < 0, mid, lo), jnp.where(fm < 0, hi, mid)

            lo, hi = _lax.fori_loop(0, ITERS, body, (lo_s, hi_s))
            return 0.5 * (lo + hi)

        runners["secular_xla"] = (_secular_xla, (), 2.0 * ITERS * K * S)
    runners["gemm"] = (
        _plan.jit("gemm", lambda x, y: jnp.einsum("iab,jcb->ijac", x, y)),
        (panel, panel),
        2 * bt * bt * nb**3,
    )
    from dlaf_tpu.algorithms.reduction_to_band import _t_factor

    runners["tfactor"] = (
        _plan.jit("tfactor", lambda vv, tt: _t_factor(vv.reshape(-1, nb), tt, nb)),
        (v, taus),
        bt * nb**3,  # dominated by V^H V
    )
    # device wavefront bulge chase (band_chase_device): full chase at band
    # 32 over an n = batch*nb band matrix — the HEEV band-stage inner
    # kernel (opt-in: --kernels band_chase, use a small --nreps)
    from dlaf_tpu.algorithms.band_chase_device import device_chase_hh

    bband = 32
    nch = bt * nb
    abh = np.zeros((bband + 2, nch), np.dtype(dtype))
    rng_ = np.random.default_rng(7)
    abh[0] = 4.0 + rng_.standard_normal(nch)
    for dd in range(1, bband + 1):
        row = rng_.standard_normal(nch).astype(np.dtype(dtype))
        if np.dtype(dtype).kind == "c":
            row = row + 1j * rng_.standard_normal(nch)
        abh[dd, : nch - dd] = row[: nch - dd]

    runners["band_chase"] = (
        lambda: jnp.asarray(device_chase_hh(abh, bband, want_q=False)[0]),
        (),
        # O(n^2 b): ~n^2/(2b) chase units total, each a 2b x 2b two-sided
        # update (~8 b^2 flops) => ~4 n^2 b
        4.0 * bband * nch * nch,
    )

    for name in args.kernels.split(","):
        if name not in runners:
            continue
        fn, fargs, flops = runners[name]
        dt_s = _time(fn, *fargs, nreps=args.nreps)
        print(f"{name:14s} nb={nb} batch={bt} {np.dtype(dtype).name:10s} "
              f"{dt_s*1e3:9.3f} ms {flops/dt_s/1e9:10.1f} GFlop/s")
        if args.metrics:
            om.emit(
                "kernel", name=name, seconds=dt_s,
                gflops=flops / dt_s / 1e9, nb=nb, batch=bt,
                dtype=np.dtype(dtype).name, nreps=args.nreps,
            )
    if args.metrics:
        om.close()
        print(f"metrics written to {args.metrics}")
    return 0


if __name__ == "__main__":
    main()
