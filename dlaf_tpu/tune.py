"""Runtime configuration and tunable algorithm parameters.

TPU-native analogue of the reference's two config families
(reference: include/dlaf/init.h:32-55 ``configuration`` — runtime resources;
include/dlaf/tune.h:118-165 ``TuneParameters`` — algorithm knobs) with the
same three-layer precedence: defaults -> user values -> environment
(``DLAF_TPU_*``), mutable between calls via the module singleton
(reference getTuneParameters(), tune.h:168).

Most reference knobs govern machinery XLA owns here (thread pools, stream
pools, umpire pool geometry, communicator clones) and have no analogue; the
surviving knobs control algorithm shape choices.
"""
from __future__ import annotations

import contextlib
import contextvars
import os
from dataclasses import dataclass, field, fields


def _env(name: str, default, cast):
    v = os.environ.get(f"DLAF_TPU_{name.upper()}")
    if v is None:
        return default
    if cast is bool:
        return v.lower() in ("1", "true", "yes", "on")
    return cast(v)


@dataclass
class TuneParameters:
    """Algorithm knobs (reference tune.h:118-165).

    - ``default_block_size``: tile size used when callers don't specify one
      (reference block sizes come from the user's ScaLAPACK descriptor).
      256 keeps tiles MXU-shaped (multiples of 128 preferred on TPU).
    - ``eigensolver_min_band``: lower bound used by get_band_size to pick
      the eigensolver band (smallest divisor of nb >= this; reference
      tune.h:126, get_band_size.h:20).  -1 (default) = auto: 33 on CPU
      backends (nb=256 -> band 64; measured HEEV 1.12-1.13x over band 128
      on the mesh), 100 on accelerators (nb=256 -> band 128, the reference
      default — SBR absorbs the chase cost there).
    - ``bt_band_hh_group_size``: reflector sweeps fused per compact-WY group
      in the band back-transform (reference
      bt_band_to_tridiag_hh_apply_group_size, tune.h:105).  -1 (default) =
      auto: 32 on CPU backends (measured 2.2x the old 128 at N=2048, 1.3x
      at N=4096 — group windows exceed cache; docs/BENCHMARKS.md), 128 on
      accelerators (bigger MXU GEMMs per step; re-tune on hardware via
      scripts/tpu_day.sh).
    - ``tridiag_host_solver``: 'stemr' (MRRR) or 'stedc'-style host driver
      for the tridiagonal stage.
    - ``dc_leaf_size``: target leaf-block size for the distributed D&C
      tridiagonal solver (rounded to a tile multiple; subproblem sizes are
      this times powers of two).
    - ``eigensolver_matmul_precision``: JAX matmul precision for the
      eigensolver pipeline stages ('float32' | 'high' | 'bfloat16';
      'bfloat16_3x' is accepted as an alias of 'high' = three bf16 MXU
      passes).
      TPU MXU f32 matmuls default to bf16 passes (eps ~8e-3), which would
      destroy eigenvector orthogonality; the eigensolver traces its kernels
      under full-f32 precision by default.
    - ``blas3_matmul_precision``: the same lever for the BLAS-3 family
      (POTRF/TRSM/GEMM/TRMM/HEMM/TRTRI/POTRI/HEGST).  Default 'default'
      keeps JAX's global setting — the fast MXU path on TPU, which the
      round-1 on-chip residual checks passed — so throughput users change
      nothing; accuracy-critical users set 'float32' (or 'high' ==
      bf16_3x) per call or via DLAF_TPU_BLAS3_MATMUL_PRECISION.
      Both ``*_matmul_precision`` knobs are XLA dot-precision HINTS —
      ``jax.default_matmul_precision`` contexts jit itself keys on.  The
      explicit split-GEMM tier below (``gemm_precision``) supersedes them
      for the trailing-update contractions and is the one to reach for
      first; the hint knobs remain for the non-contract matmuls (panel
      factorizations, lax.linalg calls) and are validated through the
      same :func:`validate_matmul_precision` helper.
    - ``gemm_precision``: explicit split-GEMM compute tier for the
      trailing-update contractions (``ops.tile.contract`` — GEMM / HERK /
      HEMM / TRMM and every distributed trailing update reached through
      ``algorithms/_spmd.py``).  'default' = plain einsum at the operand
      dtype (bit-identical to the pre-tier code); 'bf16x3' = each real
      operand split into 2 bf16 slices (head + residual), 3 pruned
      cross-products accumulated in f32 (the TPU linear-algebra paper's
      3-pass scheme, arXiv:2112.09017) — ~f32-class forward error for
      f32 data at bf16 MXU throughput; 'bf16x6' = 3 slices / 6 products,
      the double-split used for f64 operands (f32-class accuracy — the
      f32 accumulation floors the error at ~k*2^-24; driver-level
      refinement (``refine_to=`` on positive_definite_solver /
      triangular_solver) restores target-precision residuals); 'auto'
      resolves analytically per contraction from static shape + backend
      (accelerator AND contracted extent >= 512 -> split tier by dtype,
      CPU -> default; no per-request search, the tritonBLAS argument).
      Complex dtypes route through four real split contracts
      (float-pair view); integer / sub-f32 operands are never split.
      Read at TRACE time: every compiled-kernel cache key carries
      ``_spmd.gemm_precision_trace_key()`` (DLAF001 enforces — a knob
      outside the key is a dead knob), which also folds in the ambient
      :func:`gemm_precision_scope` override that refinement uses to run
      its residual GEMMs at full precision.  Values outside
      {default, bf16x3, bf16x6, auto} raise health.ConfigurationError.
    - ``cholesky_lookahead``: use the lookahead SPMD kernel (panel k+1
      overlapped with the bulk trailing update — benefits multi-chip
      meshes; the bucketed kernel is the single-chip default).
    - ``eigensolver_sbr_band``: target band of the on-device SBR second
      stage (algorithms/band_reduction.py); engages when the reduction
      band exceeds it, shrinking the host bulge-chase cost by
      band/sbr_band.  0 disables; -1 (default) = auto: 32 when the default
      JAX backend is an accelerator, off on CPU (measured: the CPU-mesh
      "device" stage costs more than the host chase it saves).
    - ``gen_to_std_backend``: 'composed' (two full triangular solves,
      2 N^3 — the measured default: 1.16 s vs the fused 1.75 s at N=2048
      on the 8-device mesh) or 'fused' (LAPACK hegst tile recursion with
      the trailing solve deferred to one trsm — fewer true flops at
      ~1.67 N^3, but its her2k windows over-approximate in BOTH grid
      dimensions under the halving buckets, eating the advantage; see
      docs/BENCHMARKS.md).  1x1 grids always take the composed route.
    - ``bucket_segment_ratio``: window-shrink factor per bucketed segment
      (see _spmd.halving_segments) — smaller = tighter trailing windows
      (fewer wasted einsum flops), more compiled loop bodies.  Mean 2-D
      trailing-update overapproximation: ~1.69x at 2.0 (the historical
      halving), ~1.35x at 1.414, ~1.23x at the 1.26 default — measured
      +15-20% POTRF/TRSM steady-state at mt=32 for ~2x the one-time
      compile (docs/BENCHMARKS.md round-4 section).
    - ``band_chase_backend``: where the small-band -> tridiagonal bulge
      chase runs: 'native' (threaded C++ host kernel), 'device' (batched
      wavefront on the accelerator, algorithms/band_chase_device.py), or
      'auto' (native whenever the C++ kernel builds, device otherwise —
      the device wavefront is latency-bound on a v5e chip).
    - ``band_chase_device_block``: sweeps per device-chase block (bounds
      on-device reflector storage; each block stages its reflectors to
      host on completion).
    - ``panel_trsm_pallas``: route the Cholesky-panel triangular solve
      (Right/Lower/T/non-unit, real) through the column-blocked Pallas
      VMEM kernel (ops/pallas_panel_trsm.py).  Default off: CPU-validated
      via interpret-mode parity tests, awaiting the hour-one TPU A/B.
    - ``dc_secular_pallas``: run the D&C secular bisection as the fused
      Pallas kernel (ops/pallas_secular.py — pole tables resident in VMEM
      across all rounds instead of one HBM read per round).  Default off,
      same gating; f32 paths only.
    - ``collectives_impl``: implementation tier for the one-contributor
      redistribution collectives (``comm.collectives``: bcast/bcast2d and
      the transpose_panel family).  'psum' = the historical reduce tier
      (masked all-reduce, ~2(P-1)/P wire bytes per device per payload);
      'v2' = gather/permute tier (doubling ppermute chain, no add-tree,
      modeled (P-1)/P wire bytes — half the reduce tier); 'pallas' =
      neighbor-ring Pallas kernels (ops/pallas_panel_exchange) with async
      remote DMA on TPU — same (P-1)/P wire model, but exchanges inside a
      collectives.overlap_window (the lookahead kernels' panel exchanges)
      are modeled as overlapped by trailing compute; on CPU backends the
      tier runs its ring in Pallas interpret mode (correctness path, no
      DMA) — like the other Pallas knobs it awaits an on-hardware A/B
      (scripts/tpu_day.sh) before any default flips; 'auto' (default) =
      v2 on accelerator backends, psum on CPU until measured (never
      pallas).  Values outside {psum, v2, pallas, auto} raise
      health.ConfigurationError.  The knob is read at trace time; every
      compiled-kernel cache keys on the resolved tier
      (collectives.collectives_trace_key), so flipping it between calls
      retraces correctly.  True multi-contributor sums (psum_axis) are
      reductions in every tier.
    - ``trailing_update_impl``: implementation tier for the lookahead
      trailing update (the bulk ``x - cp @ rp^H`` einsum behind every
      panel step).  'xla' = the einsum as XLA HLO (panels round-trip
      through HBM between the exchange and the GEMM); 'fused' = the
      Pallas trailing-update consumer (ops/pallas_trailing_update): the
      GEMM/HERK reads panel operands straight out of the ring-DMA
      landing slots of the panel exchange (per-slot recv semaphores gate
      each hop's update slice) with the bf16x3/bf16x6 split-GEMM slice
      decomposition traced INSIDE the kernel, so the MXU consumes bf16
      operands without the slices round-tripping through HBM; on CPU
      backends the tier runs a ppermute-transport ring plus the update
      kernel in Pallas interpret mode — bit-identical to 'xla' (the
      tier-1 acceptance path); 'auto' (default) = 'xla' until the
      scripts/tpu_day.sh stage-5h A/B promotes the fused tier (never
      'fused' unmeasured, matching the pallas-collectives precedent; a
      plan profile may override).  Values outside {xla, fused, auto}
      raise health.ConfigurationError.  Read at trace time; the resolved
      tier is part of plan.trace_suffix (_spmd.trailing_update_trace_key)
      so every compiled-kernel cache retraces on a flip.
    - ``serve_buckets``: comma-separated problem orders the serve layer
      pads requests up to (``dlaf_tpu.serve``); a request of order n runs
      at the smallest bucket >= n, sizes beyond the largest round up to a
      multiple of it.  Fewer buckets = fewer compiles, more padding flops.
    - ``serve_cache_capacity``: bound on the serve layer's LRU of compiled
      bucket executables; least-recently-used buckets are evicted (and
      counted) beyond this.
    - ``serve_batch_shard_max_n``: batched drivers shard the BATCH axis
      across all devices (one element per device, collectives degenerate)
      when the problem order is <= this; larger problems keep the matrix
      axes sharded and vmap the batch locally.
    - ``serve_max_queue``: SolverPool backpressure bound — submissions
      beyond this many queued requests raise ``QueueFullError``.
    - ``serve_max_batch``: most requests the pool worker fuses into one
      batched dispatch.
    - ``serve_linger_ms``: the gateway's continuous-batching max-linger —
      a forming bucket batch dispatches as soon as it is FULL
      (``serve_max_batch`` members), and a partial batch dispatches once
      its oldest member has lingered this many milliseconds; until then a
      newly admitted compatible request joins the in-flight forming batch
      instead of waiting for a fresh group.  0 = dispatch whatever is
      formed as soon as the dispatcher sees it (lowest latency, lowest
      batch fill).
    - ``serve_compile_grace_s``: first-compile grace budget for a COLD
      serve bucket — the first dispatch of a (kind, bucket, dtype, ...)
      group on a pool extends its deadline budget by this many seconds so
      one-time executable compilation does not count against the
      requests' own deadlines (a cold replica no longer sheds its very
      first requests).  Consumed grace is emitted as a ``serve``
      ``compile_grace`` event.  0 disables (compile time counts against
      request deadlines again).
    - ``serve_gateway_max_queue``: gateway admission bound — beyond this
      many admitted-but-undispatched requests (fair queue + forming
      batches) the gateway sheds: expired requests are evicted first,
      then the lowest-priority queued request if the newcomer outranks
      it, else the newcomer is rejected with ``QueueFullError``.
    - ``serve_fleet_heartbeat_s``: period of the fleet supervisor's
      heartbeat/probe sweep over its worker processes
      (``serve.supervisor``).  Each sweep sends one heartbeat frame per
      worker (watchdog-probe semantics over the wire) and pumps the
      gateway's failover check.
    - ``serve_fleet_backoff_base_s`` / ``serve_fleet_backoff_cap_s``:
      exponential restart backoff for crashed/hung workers — the k-th
      consecutive failure waits ``min(cap, base * 2**k)`` seconds before
      the respawn.
    - ``serve_fleet_crash_loop``: consecutive-failure count that opens the
      crash-loop circuit breaker; the supervisor stops restarting that
      worker (emitting a ``fleet`` ``circuit_open`` event) until a human
      (or a scale-up) intervenes.  A worker that stays ready longer than
      the backoff cap resets its failure streak.
    - ``serve_fleet_hang_restart_s``: how long a worker may fail probes
      while its process is still alive before the supervisor declares it
      hung and kills/restarts it — longer than any expected network
      partition (the ``network_partition`` fault heals within this
      window; a truly wedged PJRT client does not).
    - ``serve_fleet_scale_up_p95_s`` / ``serve_fleet_scale_up_queue``:
      autoscaler scale-UP triggers — sustained worst-tenant p95 above the
      former, or gateway queue depth above the latter, spawns a worker.
    - ``serve_fleet_scale_down_queue``: sustained queue depth below this
      (with p95 also healthy) retires the emptiest worker.
    - ``serve_fleet_scale_up_cooldown_s`` /
      ``serve_fleet_scale_down_cooldown_s``: minimum spacing after any
      scale action before the next up/down decision — the hysteresis that
      bounds oscillation (down-cooldown is the longer one so a burst's
      trailing edge does not flap spawn/retire).
    - ``serve_fleet_max_frame_mb``: wire-frame size bound for the fleet
      transports (``serve.wire``) — a forged length prefix must not make
      a reader allocate gigabytes.
    - ``telemetry``: master switch for the live instrument registry
      (``obs.telemetry``) — counters/gauges/histograms at the gateway,
      pool, wire codec, supervisor and workers.  Off (default), every
      instrument accessor returns a shared no-op after one flag test.
    - ``telemetry_harvest_min_samples``: completed batches a geometry
      needs before the service-time harvester includes it in the
      persisted plan profile (fewer = noise steering the autotuner).
    - ``telemetry_shadow_idle_s``: seconds a serve fleet must sit idle
      (no gateway backlog, no pending work) before the fleet monitor
      starts a shadow sweep on the least-loaded replica — micro
      measurements of the harvested traffic mix folded into the plan
      profile (``plan.shadow``).  0 (default) disables shadow sweeps;
      real work preempts a running sweep within one micro-batch.
    - ``slo_burn_target_p95_s``: per-request latency above this counts
      against the tenant's error budget in the SLO burn-rate monitor
      (sheds always count).
    - ``slo_burn_budget``: allowed bad-request fraction (error budget);
      burn rate = windowed bad fraction / budget.
    - ``slo_burn_fast_s`` / ``slo_burn_slow_s``: the dual sliding
      windows — a tenant fires only when BOTH windows burn at or above
      ``slo_burn_threshold`` (fast catches the spike, slow stops a blip
      from paging).
    - ``debug_dump_eigensolver_data``: dump per-stage matrices to .npz
      (reference debug_dump_* flags, tune.h:30-67).
    """

    default_block_size: int = field(default_factory=lambda: _env("default_block_size", 256, int))
    eigensolver_min_band: int = field(default_factory=lambda: _env("eigensolver_min_band", -1, int))
    eigensolver_sbr_band: int = field(default_factory=lambda: _env("eigensolver_sbr_band", -1, int))
    bt_band_hh_group_size: int = field(
        default_factory=lambda: _env("bt_band_hh_group_size", -1, int)
    )
    tridiag_host_solver: str = field(default_factory=lambda: _env("tridiag_host_solver", "stemr", str))
    dc_leaf_size: int = field(default_factory=lambda: _env("dc_leaf_size", 512, int))
    eigensolver_matmul_precision: str = field(
        default_factory=lambda: _env("eigensolver_matmul_precision", "float32", str)
    )
    blas3_matmul_precision: str = field(
        default_factory=lambda: _env("blas3_matmul_precision", "default", str)
    )
    gemm_precision: str = field(
        default_factory=lambda: _env("gemm_precision", "default", str)
    )
    gen_to_std_backend: str = field(
        default_factory=lambda: _env("gen_to_std_backend", "composed", str)
    )
    bucket_segment_ratio: float = field(
        default_factory=lambda: _env("bucket_segment_ratio", 1.26, float)
    )
    band_chase_backend: str = field(
        default_factory=lambda: _env("band_chase_backend", "auto", str)
    )
    band_chase_device_block: int = field(
        default_factory=lambda: _env("band_chase_device_block", 128, int)
    )
    cholesky_lookahead: bool = field(default_factory=lambda: _env("cholesky_lookahead", False, bool))
    trsm_lookahead: bool = field(default_factory=lambda: _env("trsm_lookahead", False, bool))
    # Pallas panel kernels (VERDICT r4 missing #6 / ROADMAP item 3): landed
    # CPU-validated (interpret-mode parity tests), DEFAULT OFF until an
    # on-hardware A/B justifies them — nothing lands unmeasured.
    collectives_impl: str = field(default_factory=lambda: _env("collectives_impl", "auto", str))
    trailing_update_impl: str = field(
        default_factory=lambda: _env("trailing_update_impl", "auto", str)
    )
    serve_buckets: str = field(
        default_factory=lambda: _env("serve_buckets", "256,512,1024,2048", str)
    )
    serve_cache_capacity: int = field(
        default_factory=lambda: _env("serve_cache_capacity", 16, int)
    )
    serve_batch_shard_max_n: int = field(
        default_factory=lambda: _env("serve_batch_shard_max_n", 1024, int)
    )
    serve_max_queue: int = field(default_factory=lambda: _env("serve_max_queue", 256, int))
    serve_max_batch: int = field(default_factory=lambda: _env("serve_max_batch", 64, int))
    serve_linger_ms: float = field(default_factory=lambda: _env("serve_linger_ms", 5.0, float))
    serve_compile_grace_s: float = field(
        default_factory=lambda: _env("serve_compile_grace_s", 120.0, float)
    )
    serve_gateway_max_queue: int = field(
        default_factory=lambda: _env("serve_gateway_max_queue", 2048, int)
    )
    serve_fleet_heartbeat_s: float = field(
        default_factory=lambda: _env("serve_fleet_heartbeat_s", 1.0, float)
    )
    serve_fleet_backoff_base_s: float = field(
        default_factory=lambda: _env("serve_fleet_backoff_base_s", 0.5, float)
    )
    serve_fleet_backoff_cap_s: float = field(
        default_factory=lambda: _env("serve_fleet_backoff_cap_s", 10.0, float)
    )
    serve_fleet_crash_loop: int = field(
        default_factory=lambda: _env("serve_fleet_crash_loop", 5, int)
    )
    serve_fleet_hang_restart_s: float = field(
        default_factory=lambda: _env("serve_fleet_hang_restart_s", 10.0, float)
    )
    serve_fleet_scale_up_p95_s: float = field(
        default_factory=lambda: _env("serve_fleet_scale_up_p95_s", 2.0, float)
    )
    serve_fleet_scale_up_queue: int = field(
        default_factory=lambda: _env("serve_fleet_scale_up_queue", 32, int)
    )
    serve_fleet_scale_down_queue: int = field(
        default_factory=lambda: _env("serve_fleet_scale_down_queue", 2, int)
    )
    serve_fleet_scale_up_cooldown_s: float = field(
        default_factory=lambda: _env("serve_fleet_scale_up_cooldown_s", 10.0, float)
    )
    serve_fleet_scale_down_cooldown_s: float = field(
        default_factory=lambda: _env("serve_fleet_scale_down_cooldown_s", 30.0, float)
    )
    serve_fleet_max_frame_mb: float = field(
        default_factory=lambda: _env("serve_fleet_max_frame_mb", 64.0, float)
    )
    telemetry: bool = field(default_factory=lambda: _env("telemetry", False, bool))
    telemetry_harvest_min_samples: int = field(
        default_factory=lambda: _env("telemetry_harvest_min_samples", 8, int)
    )
    telemetry_shadow_idle_s: float = field(
        default_factory=lambda: _env("telemetry_shadow_idle_s", 0.0, float)
    )
    slo_burn_target_p95_s: float = field(
        default_factory=lambda: _env("slo_burn_target_p95_s", 2.0, float)
    )
    slo_burn_budget: float = field(
        default_factory=lambda: _env("slo_burn_budget", 0.05, float)
    )
    slo_burn_fast_s: float = field(
        default_factory=lambda: _env("slo_burn_fast_s", 60.0, float)
    )
    slo_burn_slow_s: float = field(
        default_factory=lambda: _env("slo_burn_slow_s", 600.0, float)
    )
    slo_burn_threshold: float = field(
        default_factory=lambda: _env("slo_burn_threshold", 2.0, float)
    )
    panel_trsm_pallas: bool = field(default_factory=lambda: _env("panel_trsm_pallas", False, bool))
    dc_secular_pallas: bool = field(default_factory=lambda: _env("dc_secular_pallas", False, bool))
    debug_dump_eigensolver_data: bool = field(
        default_factory=lambda: _env("debug_dump_eigensolver_data", False, bool)
    )
    debug_dump_cholesky_data: bool = field(
        default_factory=lambda: _env("debug_dump_cholesky_data", False, bool)
    )

    def update(self, **kwargs) -> "TuneParameters":
        for k, v in kwargs.items():
            if k not in {f.name for f in fields(self)}:
                raise ValueError(f"unknown tune parameter {k!r}")
            if k == "collectives_impl":
                validate_collectives_impl(v)
            elif k == "trailing_update_impl":
                validate_trailing_update_impl(v)
            elif k == "gemm_precision":
                validate_gemm_precision(v)
            elif k in ("blas3_matmul_precision", "eigensolver_matmul_precision"):
                validate_matmul_precision(v, knob=k)
            elif k.startswith("serve_fleet_"):
                validate_serve_fleet_knob(k, v)
            elif k.startswith("slo_burn_") or k.startswith("telemetry_"):
                validate_telemetry_knob(k, v)
            setattr(self, k, v)
        return self


COLLECTIVES_IMPLS = ("psum", "v2", "pallas", "auto")
TRAILING_UPDATE_IMPLS = ("xla", "fused", "auto")
GEMM_PRECISIONS = ("default", "bf16x3", "bf16x6", "auto")


def validate_trailing_update_impl(value) -> str:
    """Reject trailing-update tiers outside the documented domain — same
    fail-fast shape as :func:`validate_collectives_impl`: checked on
    explicit ``update(trailing_update_impl=...)`` AND when the lookahead
    kernels resolve the knob at trace time, so a typo'd
    ``DLAF_TPU_TRAILING_UPDATE_IMPL`` env value surfaces as a
    ConfigurationError, not a deep-trace failure."""
    if value not in TRAILING_UPDATE_IMPLS:
        from dlaf_tpu.health import ConfigurationError

        raise ConfigurationError(
            f"trailing_update_impl must be one of {TRAILING_UPDATE_IMPLS}, "
            f"got {value!r} (env DLAF_TPU_TRAILING_UPDATE_IMPL)"
        )
    return value


def validate_gemm_precision(value) -> str:
    """Reject split-GEMM tiers outside the documented domain — same
    fail-fast shape as :func:`validate_collectives_impl`: checked on
    explicit ``update(gemm_precision=...)`` AND when ``ops.tile.contract``
    resolves the knob at trace time, so a typo'd ``DLAF_TPU_GEMM_PRECISION``
    env value surfaces as a ConfigurationError, not a deep-trace failure."""
    if value not in GEMM_PRECISIONS:
        from dlaf_tpu.health import ConfigurationError

        raise ConfigurationError(
            f"gemm_precision must be one of {GEMM_PRECISIONS}, "
            f"got {value!r} (env DLAF_TPU_GEMM_PRECISION)"
        )
    return value


def validate_matmul_precision(value, knob: str = "matmul_precision") -> str:
    """Reject matmul-precision hint strings outside the domain JAX accepts
    (after alias normalization) with a structured error naming the knob."""
    if normalize_matmul_precision(value) not in MATMUL_PRECISIONS:
        from dlaf_tpu.health import ConfigurationError

        raise ConfigurationError(
            f"{knob} must be one of {sorted(MATMUL_PRECISIONS)} or an alias "
            f"{sorted(_PRECISION_ALIASES)}, got {value!r} "
            f"(env DLAF_TPU_{knob.upper()})"
        )
    return value


# the ambient split-GEMM tier override: refinement loops (algorithms/refine.py)
# run their residual GEMMs under gemm_precision_scope('default') so the
# correction sweeps measure against full-precision residuals while the
# factorization/solve kernels keep the fast tier.  Trace state: the override
# is part of gemm_precision_trace_key(), so scoped and unscoped traces of the
# same kernel can never alias one executable.
_gemm_precision_override: contextvars.ContextVar = contextvars.ContextVar(
    "dlaf_tpu_gemm_precision_override", default=None
)


@contextlib.contextmanager
def gemm_precision_scope(tier: str):
    """Force the split-GEMM tier for contractions traced inside the scope,
    overriding ``tune.gemm_precision`` (see ``_gemm_precision_override``)."""
    validate_gemm_precision(tier)
    token = _gemm_precision_override.set(tier)
    try:
        yield tier
    finally:
        _gemm_precision_override.reset(token)


def resolved_gemm_precision() -> str:
    """The split-GEMM tier in effect at this trace point: the ambient
    :func:`gemm_precision_scope` override when active, else the tune knob
    (validated — fail-fast on a typo'd env value).  'auto' is returned
    as-is: it resolves per contraction site from static shape + backend
    (``ops.tile.contract``), both of which are already cache-key state."""
    override = _gemm_precision_override.get()
    if override is not None:
        return override
    return validate_gemm_precision(get_tune_parameters().gemm_precision)


#: bf16 MXU passes per output element relative to one fused pass — the
#: modeled-flops multiplier obs/bench attribute the split tiers' extra work
#: with (report_metrics.py precision roll-up).
GEMM_TIER_FLOP_MULTIPLIER = {"default": 1, "auto": 1, "bf16x3": 3, "bf16x6": 6}


def validate_serve_fleet_knob(knob: str, value) -> None:
    """Fail-fast domain check for the ``serve_fleet_*`` knobs: every one is
    a positive number (``serve_fleet_scale_down_queue`` may be 0 — "only
    scale down when idle"); ``serve_fleet_crash_loop`` must be an integer
    >= 1 (a 0 threshold would open the circuit before the first spawn).
    Same shape as :func:`validate_collectives_impl`: checked on explicit
    ``update(...)`` AND when the supervisor/autoscaler read the knobs, so
    a typo'd ``DLAF_TPU_SERVE_FLEET_*`` env value surfaces as a
    ConfigurationError, not a stuck fleet."""
    from dlaf_tpu.health import ConfigurationError

    try:
        v = float(value)
    except (TypeError, ValueError):
        raise ConfigurationError(
            f"{knob} must be numeric, got {value!r} "
            f"(env DLAF_TPU_{knob.upper()})") from None
    floor = 0.0 if knob == "serve_fleet_scale_down_queue" else None
    if floor is not None:
        ok = v >= floor
    elif knob == "serve_fleet_crash_loop":
        ok = v >= 1 and float(v).is_integer()
    else:
        ok = v > 0
    if not ok:
        raise ConfigurationError(
            f"{knob} must be {'an integer >= 1' if knob == 'serve_fleet_crash_loop' else '> 0'}, "
            f"got {value!r} (env DLAF_TPU_{knob.upper()})")


def validate_telemetry_knob(knob: str, value) -> None:
    """Fail-fast domain check for the telemetry-plane knobs: every one is
    a positive number; ``slo_burn_budget`` must additionally be <= 1 (it
    is a fraction of traffic) and ``telemetry_harvest_min_samples`` an
    integer >= 1.  Same shape as :func:`validate_serve_fleet_knob` — a
    typo'd ``DLAF_TPU_SLO_BURN_*`` / ``DLAF_TPU_TELEMETRY_*`` env value
    surfaces as a ConfigurationError, not a silent monitor."""
    from dlaf_tpu.health import ConfigurationError

    try:
        v = float(value)
    except (TypeError, ValueError):
        raise ConfigurationError(
            f"{knob} must be numeric, got {value!r} "
            f"(env DLAF_TPU_{knob.upper()})") from None
    if knob == "telemetry_harvest_min_samples":
        ok = v >= 1 and float(v).is_integer()
        domain = "an integer >= 1"
    elif knob == "telemetry_shadow_idle_s":
        ok = v >= 0
        domain = ">= 0 (0 disables shadow sweeps)"
    elif knob == "slo_burn_budget":
        ok = 0 < v <= 1
        domain = "a fraction in (0, 1]"
    else:
        ok = v > 0
        domain = "> 0"
    if not ok:
        raise ConfigurationError(
            f"{knob} must be {domain}, got {value!r} "
            f"(env DLAF_TPU_{knob.upper()})")


def validate_collectives_impl(value) -> str:
    """Reject values outside the documented domain with a structured error.

    Called both on explicit ``update(collectives_impl=...)`` and when the
    collectives layer resolves the knob at trace time — the latter is what
    catches a typo'd ``DLAF_TPU_COLLECTIVES_IMPL`` env value, which would
    otherwise surface as a confusing deep-trace failure."""
    if value not in COLLECTIVES_IMPLS:
        from dlaf_tpu.health import ConfigurationError

        raise ConfigurationError(
            f"collectives_impl must be one of {COLLECTIVES_IMPLS}, "
            f"got {value!r} (env DLAF_TPU_COLLECTIVES_IMPL)"
        )
    return value


_params: TuneParameters | None = None


def get_tune_parameters() -> TuneParameters:
    """Module singleton, mutable between algorithm calls (tune.h:168)."""
    global _params
    if _params is None:
        _params = TuneParameters()
    return _params


def initialize(**overrides) -> TuneParameters:
    """Reset parameters from defaults+env, then apply explicit overrides
    (reference dlaf::initialize precedence: user cfg < env < CLI).

    Also (re)applies the environment-driven plan wiring: the persistent
    compilation cache (:func:`setup_compile_cache`, env
    ``DLAF_TPU_COMPILE_CACHE`` — serve replicas get zero-compile cold
    starts without going through the miniapp path) and the autotune
    measured-sweep profile (env ``DLAF_TPU_PLAN_PROFILE``,
    ``dlaf_tpu.plan.autotune``)."""
    global _params
    _params = TuneParameters()
    p = _params.update(**overrides)
    setup_compile_cache()
    from dlaf_tpu.plan import autotune

    autotune.load_profile()
    from dlaf_tpu.obs import telemetry

    if p.telemetry:
        telemetry.enable()
    else:
        telemetry.disable()
    return p


_compile_cache_dir: str | None = None

# The compile cache's home unless the environment names one: a fixed
# directory in the checkout (a moving path never hits; .gitignore lists it)
DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def setup_compile_cache(base: str | None = None, *,
                        min_compile_s: float | None = None,
                        force: bool = False) -> str | None:
    """Configure the JAX persistent compilation cache so repeated processes
    skip backend compiles (the zero-compile cold start — see
    ``dlaf_tpu.plan``).

    ``JAX_COMPILATION_CACHE_DIR`` in the environment wins: JAX reads it
    itself and nothing is configured here.  Otherwise the directory is the
    explicit ``base`` argument, else env ``DLAF_TPU_COMPILE_CACHE``, else
    :data:`DEFAULT_COMPILE_CACHE`.  An EMPTY value disables — the test
    suite relies on this (serializing the largest 8-device shard_map
    executables can crash the cache backend; conftest pins the env to "").

    ``min_compile_s`` (else env ``DLAF_TPU_COMPILE_CACHE_MIN_S``, default
    1.0) sets ``jax_persistent_cache_min_compile_time_secs`` — lower it to
    0 to persist even trivial executables (the acceptance test does).
    Returns the dir in effect, or None when disabled."""
    global _compile_cache_dir
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        _compile_cache_dir = os.environ["JAX_COMPILATION_CACHE_DIR"]
        return _compile_cache_dir
    if base is None:
        base = os.environ.get("DLAF_TPU_COMPILE_CACHE", DEFAULT_COMPILE_CACHE)
    if not base:
        return None
    cache_dir = os.path.abspath(os.path.expanduser(base))
    if min_compile_s is None:
        min_compile_s = float(os.environ.get("DLAF_TPU_COMPILE_CACHE_MIN_S", 1.0))
    if cache_dir == _compile_cache_dir and not force:
        return cache_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", float(min_compile_s)
    )
    _reset_jax_compilation_cache()
    _compile_cache_dir = cache_dir
    return cache_dir


def _reset_jax_compilation_cache() -> None:
    """Un-latch jax's cache-enablement decision.  The compilation-cache
    module decides "is a cache configured?" ONCE, at the first compile —
    a process that compiled anything before ``setup_compile_cache`` ran
    (late ``tune.initialize``, a probe jit at import time) would silently
    never persist.  reset_cache() is jax's own back-to-pristine hook."""
    from jax.experimental.compilation_cache import compilation_cache as _cc

    _cc.reset_cache()


def compile_cache_dir() -> str | None:
    """The persistent-cache dir in effect, or None (off)."""
    return _compile_cache_dir


def disable_compile_cache() -> None:
    """Turn the persistent compilation cache back off (tests restore the
    suite-wide disabled state after exercising :func:`setup_compile_cache`)."""
    global _compile_cache_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", None)
    _reset_jax_compilation_cache()
    _compile_cache_dir = None


def config_snapshot() -> dict:
    """The effective configuration as one plain dict: every tune knob with
    its current value plus the JAX runtime facts the knobs' auto modes key
    on.  Single source for print_config and the obs.metrics 'config'
    record (the JSONL snapshot must show the same truth the console
    dump does)."""
    import jax

    p = get_tune_parameters()
    snap = {
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "process_count": jax.process_count(),
        "x64": bool(jax.config.jax_enable_x64),
    }
    snap.update({f.name: getattr(p, f.name) for f in fields(p)})
    return snap


def print_config(file=None) -> None:
    """Dump the effective configuration (reference --dlaf:print-config,
    src/init.cpp:377-383) — the rendered form of :func:`config_snapshot`."""
    import sys

    out = file or sys.stdout
    snap = config_snapshot()
    print("dlaf_tpu configuration:", file=out)
    print(f"  backend: {snap['backend']}  devices: {snap['device_count']}"
          f"  processes: {snap['process_count']}  x64: {snap['x64']}",
          file=out)
    p = get_tune_parameters()
    for f in fields(p):
        print(f"  {f.name}: {snap[f.name]}  (env DLAF_TPU_{f.name.upper()})",
              file=out)


# user-facing spellings -> jax.default_matmul_precision enum values
# ('high' == three bf16 passes on TPU MXU, 'highest'/'float32' == six)
_PRECISION_ALIASES = {"bfloat16_3x": "high", "bf16_3x": "high", "f32": "float32"}

#: post-normalization domain of the *_matmul_precision hint knobs — the
#: strings jax.default_matmul_precision accepts plus the ''/'default' no-op
MATMUL_PRECISIONS = frozenset(
    {"", "default", "bfloat16", "tensorfloat32", "high", "float32", "highest"}
)


def normalize_matmul_precision(p: str) -> str:
    return _PRECISION_ALIASES.get(p, p)


def matmul_precision(p: str, knob: str = "matmul_precision"):
    """Context manager for a matmul-precision string ('' / 'default' =
    no-op, keeping JAX's global setting; aliases normalized) — the single
    resolution point for the per-family precision knobs: every value is
    validated here (fail-fast ConfigurationError on a typo'd env value,
    same shape as validate_collectives_impl at resolve time)."""
    import contextlib

    p = normalize_matmul_precision(validate_matmul_precision(p, knob=knob))
    if p in ("", "default"):
        return contextlib.nullcontext()
    import jax

    return jax.default_matmul_precision(p)


def blas3_precision():
    """Context manager applying ``blas3_matmul_precision`` around a BLAS-3
    kernel call."""
    return matmul_precision(
        get_tune_parameters().blas3_matmul_precision, knob="blas3_matmul_precision"
    )


def eigensolver_precision():
    """Context manager applying ``eigensolver_matmul_precision`` around an
    eigensolver pipeline stage — the eigensolver-family counterpart of
    :func:`blas3_precision`, resolving through the same validated helper."""
    return matmul_precision(
        get_tune_parameters().eigensolver_matmul_precision,
        knob="eigensolver_matmul_precision",
    )
