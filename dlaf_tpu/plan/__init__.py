"""dlaf_tpu.plan — the unified executable-plan layer.

Three pieces:

* :mod:`~dlaf_tpu.plan.core` — the ONE compiled-kernel cache: every kernel
  family and the serve layer resolve executables through
  :func:`cached`, whose key is built in one place
  (:func:`plan_key` = per-site static identity + :func:`trace_suffix`,
  the full ambient trace-key set); builders jit through :func:`jit`, which
  names each program ``jit_<op>``.  :func:`warmup` prefetches a bucket
  ladder; with the persistent compilation cache configured
  (``tune.setup_compile_cache``) a respawned replica AOT-loads everything
  — zero backend compiles.
* :mod:`~dlaf_tpu.plan.autotune` — analytical parameter choice per
  geometry (tritonBLAS-style closed forms equal to the shipped hand-tuned
  defaults) with a measured-profile override.
* :mod:`~dlaf_tpu.plan.sweep` — the offline measured-sweep CLI
  (``python -m dlaf_tpu.plan.sweep``) producing that profile.
"""
from dlaf_tpu.plan import autotune
from dlaf_tpu.plan.core import (
    cached,
    compile_counts,
    evict,
    jit,
    lookup,
    plan_key,
    reset,
    stats,
    trace_suffix,
    warmup,
)

__all__ = [
    "autotune",
    "cached",
    "compile_counts",
    "evict",
    "jit",
    "lookup",
    "plan_key",
    "reset",
    "stats",
    "trace_suffix",
    "warmup",
]
