#!/usr/bin/env python
"""Headline benchmark: distributed Cholesky (POTRF) + HEEV on the local chip.

One process runs the stages in order and prints ONE JSON line
(``{"metric", "value", "unit", "vs_baseline", ...}``) when every stage
succeeded; a failing stage raises and the run exits non-zero with no
record.  Sizes N=2048 -> 4096 -> 8192 -> 16384 (nb=512, f32) for POTRF,
N=2048 -> 4096 -> 8192 (full pipeline backend) for HEEV.  The headline
value is the framework's distributed SPMD kernel (``backend='distributed'``);
the dense ("auto"-on-1x1) number is reported alongside in ``auto_gflops``.

``vs_baseline`` compares f32 TPU GFlop/s against 10 TFlop/s — an A100-class
per-device **f64** POTRF figure for the reference's GPU backend (the reference
publishes no in-repo numbers; see BASELINE.md).  The dtype mismatch is noted
in the emitted record itself.
"""
import json
import os
import sys
import time

import jax
import numpy as np

def _env_int(name, default):
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


NB = _env_int("DLAF_BENCH_NB", 512)
STAGES = tuple(
    int(s) for s in os.environ.get("DLAF_BENCH_STAGES", "2048,4096,8192,16384").split(",") if s.strip().isdigit()
) or (2048, 4096, 8192, 16384)
HEEV_STAGES = tuple(
    int(s) for s in os.environ.get("DLAF_BENCH_HEEV_STAGES", "2048,4096,8192").split(",") if s.strip().isdigit()
)
NRUNS = 2
BASELINE_GFLOPS = 10000.0
DTYPE_NOTE = "f32 TPU vs 10 TFlop/s f64 A100-class baseline (dtype mismatch, see BASELINE.md)"

# Dense MXU peak TFlop/s per chip, from the public per-chip specs (bf16
# multiply, f32 accumulate — the path JAX's default-precision f32 matmul
# takes on TPU).  Keyed by substrings of jax Device.device_kind.
_CHIP_PEAKS_TF = {
    "v2": 45.0,
    "v3": 123.0,
    "v4": 275.0,
    "v5 lite": 197.0,  # v5e
    "v5e": 197.0,
    "v5p": 459.0,
    "v6 lite": 918.0,  # v6e / Trillium
    "v6e": 918.0,
}
# Emulated-f64 cost model: TPUs have no f64 MXU; double-word (Dekker/
# two-product) emulation spends ~11 MXU ops per f64 FMA, so the usable f64
# roofline is ~peak/11.  An ESTIMATE for decision-grade MFU, labeled as such.
_EF64_FACTOR = 11.0


def chip_peaks_tflops(device_kind: str):
    """(f32_peak, emulated_f64_peak_estimate) in TFlop/s.  A device kind
    the table does not list is an error, not a default."""
    kind = (device_kind or "").lower()
    for key in sorted(_CHIP_PEAKS_TF, key=len, reverse=True):
        if key in kind:
            peak = _CHIP_PEAKS_TF[key]
            return peak, peak / _EF64_FACTOR
    raise ValueError(f"no peak for device_kind {device_kind!r}: add it to _CHIP_PEAKS_TF")



# --------------------------- stages ---------------------------------------

class _Bench:
    """Runs the stages and accumulates the record."""

    def __init__(self):
        self.rec = {
            "metric": f"potrf_gflops_nb{NB}_f32_1chip_distributed",
            "value": 0.0,
            "unit": "GFlop/s",
            "vs_baseline": 0.0,
        }

    def _time_potrf(self, a_host, n, backend):
        """Best wall time over NRUNS (first run = warmup/compile, untimed)."""
        from dlaf_tpu.algorithms.cholesky import cholesky_factorization
        from dlaf_tpu.comm.grid import Grid
        from dlaf_tpu.common.index import Size2D
        from dlaf_tpu.matrix.matrix import DistributedMatrix

        grid = Grid.create(Size2D(1, 1))
        best = None
        for i in range(NRUNS + 1):
            mat = DistributedMatrix.from_global(grid, a_host, (NB, NB))
            jax.block_until_ready(mat.data)
            t0 = time.perf_counter()
            out = cholesky_factorization("L", mat, backend=backend, _dump=False)
            jax.block_until_ready(out.data)
            dt = time.perf_counter() - t0
            if i == 0:
                continue
            best = dt if best is None else min(best, dt)
        return best

    def _time_heev(self, n):
        """HEEV (full pipeline backend): warmup/compile run, then one timed
        UNINSTRUMENTED run (the recorded number), then one instrumented run for the per-stage breakdown only (stage
        barriers serialize async dispatch, so that run must not feed the
        headline seconds)."""
        import dlaf_tpu.testing as tu
        from dlaf_tpu.algorithms.eigensolver import hermitian_eigensolver
        from dlaf_tpu.comm.grid import Grid
        from dlaf_tpu.common import stagetimer
        from dlaf_tpu.common.index import Size2D
        from dlaf_tpu.matrix.matrix import DistributedMatrix

        grid = Grid.create(Size2D(1, 1))
        a = tu.random_hermitian_pd(n, np.float32, seed=2)
        best, stages = None, None
        for i in range(3):  # warmup, timed, stage-breakdown
            mat = DistributedMatrix.from_global(grid, np.tril(a), (NB, NB))
            jax.block_until_ready(mat.data)
            if i == 2:
                stagetimer.start()
            try:
                t0 = time.perf_counter()
                res = hermitian_eigensolver("L", mat, backend="pipeline")
                jax.block_until_ready(res.eigenvectors.data)
                dt = time.perf_counter() - t0
            finally:
                # never leave global collection on: it would serialize the
                # stage barriers of every later benchmark run
                if i == 2:
                    stages = {k: round(v, 3) for k, v in stagetimer.stop().items()}
            if i < 2:  # the instrumented run never feeds the headline time
                best = dt if best is None else min(best, dt)
        return best, stages

    def run(self):
        from dlaf_tpu.miniapp import common as _c  # noqa: F401  persistent compile cache
        # structured metrics stream
        self.metrics_path = os.environ.get("DLAF_BENCH_METRICS", "")
        if self.metrics_path:
            from dlaf_tpu.obs import metrics as om

            om.enable(self.metrics_path)
            om.emit_run_meta("bench")
            om.emit_config()

        dev = jax.devices()[0]
        if dev.platform != "tpu":
            raise SystemExit(f"bench.py measures a TPU; jax.devices()[0] is {dev.platform!r}")
        # MFU bookkeeping: peak looked up from the device kind so every
        # number below can carry its fraction-of-roofline.  Reference
        # self-reports plain GFlop/s only (miniapp/miniapp_cholesky.cpp:155-172).
        kind = dev.device_kind
        self.peak_f32, self.peak_ef64 = chip_peaks_tflops(kind)
        self.rec["device_kind"] = kind
        self.rec["peak_tflops_f32"] = self.peak_f32
        self.rec["peak_tflops_ef64_est"] = round(self.peak_ef64, 2)

        import dlaf_tpu.testing as tu

        potrf_flops = lambda n: 2 * n**3 / 6  # n^3/6 adds + n^3/6 muls (reference types.h:160)
        heev_flops = lambda n: 4 * n**3 / 3
        for n in STAGES:
            a = tu.random_hermitian_pd(n, np.float32, seed=1)
            gf = potrf_flops(n) / self._time_potrf(a, n, "distributed") / 1e9
            gf_auto = potrf_flops(n) / self._time_potrf(a, n, "auto") / 1e9
            self.rec.update(
                metric=f"potrf_gflops_n{n}_nb{NB}_f32_1chip_distributed",
                value=round(gf, 3),
                vs_baseline=round(gf / BASELINE_GFLOPS, 4),
                note=DTYPE_NOTE,
                mfu=round(gf / 1e3 / self.peak_f32, 4),
                auto_gflops=round(gf_auto, 3),
                auto_mfu=round(gf_auto / 1e3 / self.peak_f32, 4),
            )
        for n in HEEV_STAGES:
            dt, stages = self._time_heev(n)
            gf_heev = heev_flops(n) / dt / 1e9
            self.rec["heev"] = {
                "metric": f"heev_n{n}_nb{NB}_f32_1chip_pipeline",
                "seconds": round(dt, 3),
                "gflops": round(gf_heev, 3),
                "flops_model": "4/3 N^3 (tridiagonal-reduction count)",
                "mfu": round(gf_heev / 1e3 / self.peak_f32, 4),
                "stages": stages,
            }
        # batched serving throughput (ISSUE 5): one vmapped B=16 N=512
        # posv dispatch vs a Python loop of 16 single solver calls on the
        # same devices — the number behind the serve acceptance criterion
        self.rec["serve"] = self._time_batched_posv(16, 512)
        # split-GEMM tier A/B (f32 — must run before the x64 flip below):
        # bf16x3-tier posv with refine_to='input' vs the default tier,
        # residual printed beside every GFlop/s column
        self.rec["posv_precision"] = self._time_posv_bf16x3_refined(2048)
        # fused trailing-update A/B (f32 — before the x64 flip): lookahead
        # POTRF with trailing_update_impl='fused' vs 'xla', bit parity
        # asserted beside both timings
        self.rec["potrf_fused_trailing"] = self._time_potrf_fused_trailing(2048)
        # LAST (flips x64; nothing f32 runs after): the mixed-precision A/B —
        # f32-factor-plus-refinement posv vs emulated-f64 posv
        self.rec["posv_mixed"] = self._time_posv_mixed(4096)
        if self.metrics_path:
            from dlaf_tpu.obs import metrics as om

            om.emit("bench", record=self.rec)
            om.close()
        return self.rec

    def _time_batched_posv(self, bsz, n):
        """Batched-serving throughput: best-of-2 timed B=``bsz`` N=``n``
        f32 batched posv dispatches (after a warmup/compile run) and the
        same problems as a loop of single
        positive_definite_solver calls for the speedup column."""
        import dlaf_tpu.testing as tu
        from dlaf_tpu import serve
        from dlaf_tpu.algorithms.solver import positive_definite_solver
        from dlaf_tpu.comm.grid import Grid
        from dlaf_tpu.common.index import Size2D
        from dlaf_tpu.matrix.matrix import DistributedMatrix

        a = np.stack(
            [tu.random_hermitian_pd(n, np.float32, seed=50 + i) for i in range(bsz)]
        )
        rhs = np.stack(
            [tu.random_matrix(n, 1, np.float32, seed=80 + i) for i in range(bsz)]
        )
        cache = serve.CompiledCache()
        times = []
        for i in range(NRUNS + 1):
            t0 = time.perf_counter()
            _, info = serve.batched_positive_definite_solver(
                "L", a, rhs, cache=cache
            )
            dt = time.perf_counter() - t0
            assert np.all(np.asarray(info) == 0), info
            if i > 0:
                times.append(dt)
        best = min(times)
        # in a fused batch every member's latency IS the dispatch time
        p50 = sorted(times)[len(times) // 2]
        rec = {
            "metric": f"batched_posv_throughput_b{bsz}_n{n}_f32",
            "seconds": round(best, 4),
            "problems_per_s": round(bsz / best, 2),
            "p50_latency_s": round(p50, 4),
            "batch": bsz,
            "n": n,
        }
        # baseline: the same problems through the single-call driver
        grid = Grid.create(Size2D(1, jax.device_count()))
        mb = min(128, n)

        def loop():
            for i in range(bsz):
                mat_a = DistributedMatrix.from_global(
                    grid, np.tril(a[i]), (mb, mb)
                )
                mat_b = DistributedMatrix.from_global(grid, rhs[i], (mb, mb))
                np.asarray(
                    positive_definite_solver("L", mat_a, mat_b).to_global()
                )

        loop()  # warmup/compile
        t0 = time.perf_counter()
        loop()
        loop_s = time.perf_counter() - t0
        rec["single_loop_seconds"] = round(loop_s, 4)
        rec["speedup_vs_single_loop"] = round(loop_s / best, 2)
        return rec

    def _time_posv_bf16x3_refined(self, n):
        """Split-GEMM tier A/B at N=``n``, nrhs=16, f32: default-tier posv
        vs bf16x3-tier posv with ``refine_to='input'`` (residual-corrected
        back to input rounding).  Each column carries its measured
        normalized residual so the throughput is never read without the
        accuracy it was bought at."""
        import dlaf_tpu.testing as tu
        from dlaf_tpu import tune
        from dlaf_tpu.algorithms.solver import positive_definite_solver
        from dlaf_tpu.comm.grid import Grid
        from dlaf_tpu.matrix.matrix import DistributedMatrix

        # full mesh, NOT 1x1: the single-device posv fast path factors via
        # jnp.linalg.cholesky and never traces a contract — only the SPMD
        # trailing updates feel the tier
        grid = Grid.create()
        a = tu.random_hermitian_pd(n, np.float32, seed=3)
        b = tu.random_matrix(n, 16, np.float32, seed=4)
        anorm = float(np.max(np.abs(a)))
        flops = n**3 / 3 + 4 * n**2 * 16
        rec = {"metric": f"posv_bf16x3_refined_n{n}_f32", "n": n, "nrhs": 16}
        tp = tune.get_tune_parameters()
        saved = tp.gemm_precision
        try:
            for col, tier, refine in (
                ("default", "default", None),
                ("bf16x3_refined", "bf16x3", "input"),
            ):
                best = x = None
                for _ in range(2):  # warmup/compile, then timed
                    tp.update(gemm_precision=tier)
                    mat_a = DistributedMatrix.from_global(grid, np.tril(a), (NB, NB))
                    mat_b = DistributedMatrix.from_global(grid, b, (NB, NB))
                    jax.block_until_ready(mat_a.data)
                    t0 = time.perf_counter()
                    x = positive_definite_solver("L", mat_a, mat_b, refine_to=refine)
                    jax.block_until_ready(x.data)
                    best = time.perf_counter() - t0
                xh = np.asarray(x.to_global())
                resid = float(
                    np.max(np.abs(b - a @ xh))
                    / (anorm * max(float(np.max(np.abs(xh))), 1e-30))
                )
                rec[col] = {
                    "seconds": round(best, 3),
                    "gflops": round(flops / best / 1e9, 3),
                    "residual": resid,
                    "gemm_precision": tier,
                    "refine_to": refine,
                }
        finally:
            tp.update(gemm_precision=saved)
        if "default" in rec and "bf16x3_refined" in rec:
            rec["speedup"] = round(
                rec["default"]["seconds"] / rec["bf16x3_refined"]["seconds"], 2
            )
        return rec

    def _time_potrf_fused_trailing(self, n):
        """Fused trailing-update A/B at N=``n``, f32: lookahead POTRF with
        ``trailing_update_impl='xla'`` vs ``'fused'`` on the full mesh,
        with the two factors compared bit-for-bit (the fused consumer's
        acceptance contract).  On the CPU mesh the fused leg goes through
        the interpret-mode consume ring, so the seconds column measures
        the interpreter, not VMEM residency — read it only for parity."""
        import dlaf_tpu.testing as tu
        from dlaf_tpu import tune
        from dlaf_tpu.algorithms.cholesky import cholesky_factorization
        from dlaf_tpu.comm.grid import Grid
        from dlaf_tpu.matrix.matrix import DistributedMatrix
        from dlaf_tpu.plan import core as plan_core

        # full mesh, NOT 1x1: the fused tier only engages on the SPMD
        # lookahead kernel (a 1x1 grid takes the single-device fast path)
        grid = Grid.create()
        a = np.tril(tu.random_hermitian_pd(n, np.float32, seed=5))
        flops = n**3 / 3
        rec = {"metric": f"potrf_fused_trailing_n{n}_f32", "n": n, "nb": NB,
               "grid": list(grid.grid_size)}
        tp = tune.get_tune_parameters()
        saved = (tp.trailing_update_impl, tp.cholesky_lookahead)
        factors = {}
        try:
            tp.update(cholesky_lookahead=True)
            for impl in ("xla", "fused"):
                tp.update(trailing_update_impl=impl)
                plan_core.reset()  # the knob is a trace-key suffix
                best = None
                for _ in range(2):  # warmup/compile, then timed
                    mat = DistributedMatrix.from_global(grid, a, (NB, NB))
                    jax.block_until_ready(mat.data)
                    t0 = time.perf_counter()
                    out = cholesky_factorization("L", mat)
                    jax.block_until_ready(out.data)
                    best = time.perf_counter() - t0
                factors[impl] = np.asarray(out.to_global())
                rec[impl] = {
                    "seconds": round(best, 3),
                    "gflops": round(flops / best / 1e9, 3),
                }
        finally:
            tp.update(trailing_update_impl=saved[0], cholesky_lookahead=saved[1])
            plan_core.reset()
        if "xla" in factors and "fused" in factors:
            rec["bit_identical"] = bool(
                np.array_equal(factors["xla"], factors["fused"])
            )
        return rec

    def _time_posv_mixed(self, n):
        """One timed mixed solve and one timed full-f64 solve at N=n,
        nrhs=16 (warmup run each).  Returns the comparison record."""
        import dlaf_tpu.testing as tu
        from dlaf_tpu.algorithms.cholesky import cholesky_factorization
        from dlaf_tpu.algorithms.solver import (
            cholesky_solver,
            positive_definite_solver_mixed,
        )
        from dlaf_tpu.comm.grid import Grid
        from dlaf_tpu.common.index import Size2D
        from dlaf_tpu.matrix.matrix import DistributedMatrix

        jax.config.update("jax_enable_x64", True)
        grid = Grid.create(Size2D(1, 1))
        a = tu.random_hermitian_pd(n, np.float64, seed=3)
        b = tu.random_matrix(n, 16, np.float64, seed=4)
        mat_a = DistributedMatrix.from_global(grid, np.tril(a), (NB, NB))
        mat_b = DistributedMatrix.from_global(grid, b, (NB, NB))
        mixed_s, info = None, None
        for i in range(2):  # warmup/compile, timed
            jax.block_until_ready(mat_a.data)
            t0 = time.perf_counter()
            x, info = positive_definite_solver_mixed("L", mat_a, mat_b)
            jax.block_until_ready(x.data)
            mixed_s = time.perf_counter() - t0
        rec = {
            "metric": f"posv_mixed_n{n}_nb{NB}_f64_via_f32",
            "mixed_s": round(mixed_s, 3),
            "iters": info.iters,
            "converged": bool(info.converged),
            "fallback": bool(info.fallback),
            "backward_error": float(info.backward_error),
        }
        # factor dominates: n^3/3 + two triangular solves (2*2*n^2*nrhs);
        # the mixed solve spends its flops in the f32 factor
        flops = n**3 / 3 + 4 * n**2 * 16
        rec["mixed_mfu_vs_f32"] = round(flops / mixed_s / 1e12 / self.peak_f32, 4)
        for i in range(2):
            fac = DistributedMatrix.from_global(grid, np.tril(a), (NB, NB))
            rhs = DistributedMatrix.from_global(grid, b, (NB, NB))
            jax.block_until_ready(fac.data)
            t0 = time.perf_counter()
            fac = cholesky_factorization("L", fac, _dump=False)
            xd = cholesky_solver("L", fac, rhs)
            jax.block_until_ready(xd.data)
            dt = time.perf_counter() - t0
            if i == 1:  # never record the warmup/compile run
                direct_s = dt
        rec["direct_f64_s"] = round(direct_s, 3)
        rec["speedup_vs_f64"] = round(direct_s / mixed_s, 2)
        rec["direct_f64_mfu_vs_ef64_est"] = round(
            flops / direct_s / 1e12 / self.peak_ef64, 4
        )
        return rec


def main():
    import argparse

    ap = argparse.ArgumentParser(description="dlaf_tpu headline benchmark")
    ap.add_argument(
        "--metrics", default="", metavar="PATH",
        help="write a dlaf_tpu.obs JSONL metrics stream to PATH (run "
        "metadata, config snapshot, the bench record, compile events)",
    )
    args = ap.parse_args()
    if args.metrics:
        os.environ["DLAF_BENCH_METRICS"] = os.path.abspath(args.metrics)
    print(json.dumps(_Bench().run()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
