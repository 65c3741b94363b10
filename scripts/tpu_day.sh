#!/bin/sh
# The full TPU measurement campaign, one command, ordered so the most
# important numbers land first (any wedge/crash still leaves artifacts).
# Usage: sh scripts/tpu_day.sh [outdir]   (default bench_results/tpu_day)
#
# Headline bench, per-stage breakdowns, micro-kernels, algorithm sweep, and
# the A/Bs that were only ever measured on the CPU mesh (lookahead, SBR,
# matmul precision).  Run it on a chip host; it fails without one.
set -x
OUT="${1:-bench_results/tpu_day}"
cd "$(dirname "$0")/.."
mkdir -p "$OUT"

# 0. liveness + environment
timeout 60 python -c "
import jax, jax.numpy as jnp, numpy as np
x = jnp.ones((256, 256), np.float32)
print('ALIVE', float(jnp.sum(x @ x)), jax.devices())
" > "$OUT/00_probe.txt" 2>&1 || exit 1

# 1. headline bench artifact (staged POTRF + HEEV; exits non-zero on a failed stage)
timeout 500 python bench.py > "$OUT/01_bench.json" 2> "$OUT/01_bench.err"

# 2. HEEV per-stage breakdown at increasing N (the round-2 'where does a
#    second go' question), native host chase + SBR engaged by default
for N in 4096 8192 16384; do
  timeout 900 python -m dlaf_tpu.miniapp.miniapp_eigensolver \
    --m $N --mb 512 --type s --nruns 1 --stage-times \
    > "$OUT/02_heev_stages_n$N.txt" 2>&1 || break
done

# 3. micro-kernels (incl. the Pallas potrf tile and the wavefront chase)
timeout 600 python -m dlaf_tpu.miniapp.kernel_runner --nb 256 --batch 16 \
  --kernels potrf,potrf_pallas,trsm,gemm,tfactor > "$OUT/03_kernels.txt" 2>&1
timeout 900 python -m dlaf_tpu.miniapp.kernel_runner --nb 256 --batch 16 \
  --nreps 2 --kernels band_chase > "$OUT/03_band_chase.txt" 2>&1
# the round-5 Pallas panel kernels: the delete-or-keep A/B for
# tune.panel_trsm_pallas / dc_secular_pallas (ROADMAP item 3)
timeout 600 python -m dlaf_tpu.miniapp.kernel_runner --nb 256 --batch 16 \
  --kernels trsm,panel_trsm_pallas,secular_pallas,secular_xla \
  > "$OUT/03_pallas_panel_ab.txt" 2>&1
timeout 600 python -m dlaf_tpu.miniapp.kernel_runner --nb 512 --batch 8 \
  --kernels trsm,panel_trsm_pallas > "$OUT/03_pallas_panel_ab_512.txt" 2>&1

# 4. per-algorithm sweep (single chip; CSV written through after every
#    config, so a timeout keeps the finished rows)
timeout 3600 python scripts/bench_sweep.py --algos cholesky,trsm,trmm,hemm,potri,heev \
  --grids 1x1 --sizes 4096,8192,16384 --mb 512 --nruns 2 --timeout 450 \
  --out "$OUT/04_sweep.csv" > "$OUT/04_sweep.log" 2>&1

# 5. A/Bs measured only on the CPU mesh so far
#    (a) lookahead on/off
for LA in 0 1; do
  DLAF_TPU_CHOLESKY_LOOKAHEAD=$LA timeout 600 python -m dlaf_tpu.miniapp.miniapp_cholesky \
    --m 8192 --mb 512 --type s --nruns 2 > "$OUT/05_potrf_lookahead$LA.txt" 2>&1
done
#    (b) SBR band shrink on/off at the HEEV band stage
for SBR in 0 32; do
  DLAF_TPU_EIGENSOLVER_SBR_BAND=$SBR timeout 900 python -m dlaf_tpu.miniapp.miniapp_eigensolver \
    --m 8192 --mb 512 --type s --nruns 1 --stage-times \
    > "$OUT/05_heev_sbr$SBR.txt" 2>&1
done
#    (c) BLAS-3 matmul precision: MXU fast path vs full f32 passes
for P in default high float32; do
  DLAF_TPU_BLAS3_MATMUL_PRECISION=$P timeout 600 python -m dlaf_tpu.miniapp.miniapp_cholesky \
    --m 8192 --mb 512 --type s --nruns 2 --check last \
    > "$OUT/05_potrf_prec_$P.txt" 2>&1
done

#    (d) mixed precision: the TPU-first claim (f32 MXU factor + refinement
#        vs emulated-f64 end to end) — posv and the full eigensolver
for APP in posv posv_mixed heev_mixed; do
  # nruns 1: heev_mixed is a full f32 pipeline + f64 refinement sweeps —
  # the 900s budget elsewhere covers ONE f32 eigensolve at this size
  timeout 900 python -m dlaf_tpu.miniapp.miniapp_suite $APP \
    --m 8192 --mb 512 --type d --nruns 1 --check last \
    > "$OUT/05_mixed_$APP.txt" 2>&1
done
#    (e) PARTIAL-spectrum mixed (round 5): O(n^2 k) target-precision work —
#        the 1024 smallest of N=8192 vs the full mixed run above
timeout 900 python -m dlaf_tpu.miniapp.miniapp_suite heev_mixed \
  --m 8192 --mb 512 --type d --nruns 1 --spectrum 0:1023 --check last \
  > "$OUT/05_mixed_heev_partial.txt" 2>&1
#    (f) collectives tiers: psum/v2/pallas three-way A/B on lookahead POTRF
#        (watchdog-probed per tier; per-tier GFlop/s + the modeled wire
#        split incl. the pallas overlapped column land in BENCH-shaped
#        JSON + obs.metrics).  THE decision gate for promoting 'pallas'
#        into the collectives 'auto' resolution.
timeout 900 python scripts/collectives_ab.py --m 8192 --mb 512 --nruns 2 \
  --out "$OUT/05_collectives_ab.json" --metrics "$OUT/05_collectives_ab.jsonl" \
  > "$OUT/05_collectives_ab.log" 2>&1
#    (g) split-GEMM precision tiers: default/bf16x3/bf16x3+refine/bf16x6
#        POSV A/B (watchdog-probed per tier; GFlop/s + modeled emulation
#        GFlop/s + residual per row).  THE decision gate for promoting the
#        bf16 tiers into gemm_precision 'auto' on real MXUs — the CPU-mesh
#        numbers only validated accuracy, never the speedup.
timeout 900 python scripts/precision_ab.py --m 4096 --mb 512 --nrhs 16 --nruns 2 \
  --out "$OUT/05_precision_ab.json" --metrics "$OUT/05_precision_ab.jsonl" \
  > "$OUT/05_precision_ab.log" 2>&1
#    (h) fused trailing-update consumer: pallas vs pallas+fused A/B per
#        consumer op — lookahead POTRF plus the PR-18 coverage (her2k
#        gen_to_std, TRTRI, red2band), one artifact per op (watchdog-
#        probed per leg; DeviceUnresponsiveError stale-flags the row and
#        the flight recorder drops flight_*.json).  THE decision gate for
#        promoting 'fused' into the trailing_update_impl 'auto'
#        resolution — the CPU mesh only proves bit parity, never the
#        VMEM-residency win.
for OP in potrf gen_to_std trtri red2band; do
  timeout 900 python scripts/collectives_ab.py --op $OP --m 8192 --mb 512 \
    --nruns 2 --tiers pallas,fused --flight-dir "$OUT" \
    --out "$OUT/05_trailing_ab_$OP.json" \
    --metrics "$OUT/05_trailing_ab_$OP.jsonl" \
    > "$OUT/05_trailing_ab_$OP.log" 2>&1
done

# 6. one profiler trace for the record
timeout 900 python -m dlaf_tpu.miniapp.miniapp_eigensolver --m 8192 --mb 512 \
  --type s --nruns 1 --trace "$OUT/06_trace" > "$OUT/06_trace.log" 2>&1

echo "tpu_day artifacts in $OUT"
