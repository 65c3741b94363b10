"""The unified plan cache: ONE key builder for every compiled executable.

Before this module each kernel family kept its own module-level dict and
hand-folded the trace-time knobs into its key — ~10 independent cache
sites that only the DLAF001 linter kept honest.  Here the key is built in
one place: ``plan_key(op, static_key)`` appends :func:`trace_suffix` — the
full trace-key set (collectives tier, panel-TRSM pallas flag, split-GEMM
tier, trailing-update tier, bucket ratio, lookahead knobs, the serve
bucket token, and the autotune profile fingerprint) — to the caller's
static geometry key.
Call sites keep only what is genuinely per-site (grid identity, Geometry,
uplo, variant, dtype); everything ambient comes from the suffix, uniformly.
Uniform over-keying is deliberate: a masked-variant kernel retracing when
``bucket_segment_ratio`` changes costs one spurious compile, while a knob
missing from a key aliases stale executables — the asymmetry that created
the "a knob outside the key is a dead knob" rule in the first place.

Cold start: entries built here are ordinary jitted callables, so when the
JAX persistent compilation cache is configured (``tune.setup_compile_cache``,
env ``DLAF_TPU_COMPILE_CACHE``) their backend compiles serialize to disk.
A fresh process that replays the same op mix — e.g. via :func:`warmup` over
the serve bucket ladder — re-traces but AOT-loads every executable: zero
backend compiles.  The jax.monitoring counters exposed by
:func:`compile_counts` discriminate the two (``pcache_misses`` = true
backend compiles when the persistent cache is on; ``pcache_hits`` = AOT
loads), and every hit/miss/build/warmup flows through ``obs.metrics`` as
``plan`` events so cold-start cost is attributable from the JSONL stream.

Names: every builder jits through :func:`jit`, so each program's module
is ``jit_<op>`` — what profiler traces, compile logs and the program-load
counter (``obs.program_loads``) show.  ``jax.jit`` of a ``partial`` or a
``lambda`` would name it ``jit__unknown`` / ``jit__lambda_`` (lint rule
DLAF005).
"""
from __future__ import annotations

import inspect
import threading
import time

# One process-wide registry.  An RLock (not a Lock): builders may
# themselves resolve nested plans (composed kernels), and builds run
# outside the lock anyway — the lock only guards the dict and counters.
_lock = threading.RLock()
_entries: dict = {}
_counters = {"hit": 0, "miss": 0, "build": 0, "evict": 0}

#: jax.monitoring-fed compile counters (process-cumulative):
#: ``backend_compiles`` counts backend_compile durations — these fire even
#: when the executable comes from the persistent cache, so they measure
#: compile *requests*, not compile work; ``pcache_misses`` counts true
#: backend compiles (persistent-cache misses) and ``pcache_hits`` counts
#: AOT deserializations.  The latter two only move while a persistent
#: cache dir is configured.
_compile_events = {"backend_compiles": 0, "pcache_hits": 0, "pcache_misses": 0}
_monitoring_registered = False
#: JAX's event around one backend compile or persistent-cache load: a
#: scalar (its start) then a duration, both with ``fun_name``
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
# per thread: whether the compile in progress was a persistent-cache hit
_pending = threading.local()


def jit(op: str, fun, **jit_kwargs):
    """``jax.jit(fun, **jit_kwargs)`` whose program is named ``jit_<op>``.

    ``fun`` is wrapped in a function called ``op`` (its signature kept, so
    static argument names still resolve); shardings, donation and every
    other keyword pass through unchanged."""
    import jax

    def named(*args, **kwargs):
        return fun(*args, **kwargs)

    named.__name__ = named.__qualname__ = op
    try:
        named.__signature__ = inspect.signature(fun)
    except (TypeError, ValueError):
        pass
    return jax.jit(named, **jit_kwargs)


def register_monitoring() -> None:
    """The process's one set of ``jax.monitoring`` listeners (idempotent;
    jax.monitoring has no unregister, so they stay installed for process
    life).  They count compile / persistent-cache events, charge each
    compile or load to the open phase (``obs.trace.load_started`` /
    ``load_finished``), and forward the events to the ``obs.metrics``
    stream.  Only a compile or a load fires them."""
    global _monitoring_registered
    with _lock:
        if _monitoring_registered:
            return
        _monitoring_registered = True
    from jax import monitoring

    from dlaf_tpu.obs import metrics as om
    from dlaf_tpu.obs import trace as ot

    def _on_start(event: str, value, fun_name: str = "?", **kw) -> None:
        if event == _BACKEND_COMPILE:
            _pending.hit = False
            # "jit(op)" -> "jit_op", the module name the device trace shows
            if fun_name.startswith("jit(") and fun_name.endswith(")"):
                fun_name = f"jit_{fun_name[4:-1]}"
            ot.load_started(fun_name)

    def _on_duration(event: str, duration: float, **kw) -> None:
        if event == _BACKEND_COMPILE:
            _compile_events["backend_compiles"] += 1
            ot.load_finished(loaded=getattr(_pending, "hit", False))
            _pending.hit = False
        om.forward_compile(event, duration)

    def _on_event(event: str, **kw) -> None:
        if event.endswith("/cache_hits"):
            _compile_events["pcache_hits"] += 1
            _pending.hit = True
        elif event.endswith("/cache_misses"):
            _compile_events["pcache_misses"] += 1
        om.forward_cache(event)

    monitoring.register_scalar_listener(_on_start)
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


def compile_counts() -> dict:
    """Snapshot of the process-cumulative compile counters (see
    ``_compile_events``); subtract two snapshots to attribute a phase."""
    register_monitoring()
    return dict(_compile_events)


def _persistent_cache_on() -> bool:
    import jax

    return bool(jax.config.jax_compilation_cache_dir)


def _compiles_delta(before: dict, after: dict) -> dict:
    """Phase attribution between two :func:`compile_counts` snapshots.
    ``compiles`` means true backend compiles: persistent-cache misses when
    the cache is on, raw backend compiles otherwise (without a cache dir
    the miss counter never moves and would undercount)."""
    d = {k: after[k] - before[k] for k in before}
    d["compiles"] = (
        d["pcache_misses"] if _persistent_cache_on() else d["backend_compiles"]
    )
    d["aot_loads"] = d["pcache_hits"]
    return d


# ------------------------------------------------------------- key builder


def trace_suffix() -> tuple:
    """Every ambient trace-time knob, in ONE place — appended to every plan
    key by :func:`plan_key`.  Adding a knob that is read inside any kernel
    trace means adding it HERE (DLAF001 resolves this function's knob reads
    transitively when auditing ``plan.cached`` call sites, so the linter
    keeps this list honest the same way it kept the old per-site keys
    honest)."""
    from dlaf_tpu.algorithms import _spmd
    from dlaf_tpu.comm import collectives as coll
    from dlaf_tpu.plan import autotune
    from dlaf_tpu.serve import context as serve_context
    from dlaf_tpu.tune import get_tune_parameters

    p = get_tune_parameters()
    return (
        coll.collectives_trace_key(),
        _spmd.trsm_trace_key(),
        _spmd.gemm_precision_trace_key(),
        _spmd.trailing_update_trace_key(),
        _spmd.bucket_ratio(),
        bool(p.trsm_lookahead),
        bool(p.cholesky_lookahead),
        serve_context.serve_trace_key(),
        autotune.profile_fingerprint(),
    )


def plan_key(op: str, static_key: tuple = ()) -> tuple:
    """The full cache key for executable ``op`` with per-site static
    identity ``static_key`` (grid identity / Geometry / dtype / uplo /
    variant — whatever distinguishes the call site's traces beyond the
    ambient knobs)."""
    return (str(op),) + tuple(static_key) + trace_suffix()


# -------------------------------------------------------------- the cache


def cached(op: str, static_key: tuple, builder):
    """The single compiled-executable cache: return the executable for
    ``plan_key(op, static_key)``, building it with ``builder()`` on a miss.

    Builds run OUTSIDE the lock (a slow trace never blocks hits); a lost
    build race keeps the winner.  Hit/miss/build events go to
    ``obs.metrics`` (kind ``plan``) when a sink is active."""
    from dlaf_tpu.obs import metrics as om

    register_monitoring()
    key = plan_key(op, static_key)
    with _lock:
        fn = _entries.get(key)
        if fn is not None:
            _counters["hit"] += 1
        else:
            _counters["miss"] += 1
    if fn is not None:
        om.emit("plan", event="hit", op=op)
        return fn
    om.emit("plan", event="miss", op=op)
    before = dict(_compile_events)
    t0 = time.perf_counter()
    fn = builder()
    dt = time.perf_counter() - t0
    with _lock:
        prev = _entries.get(key)
        if prev is not None:
            fn = prev
        else:
            _entries[key] = fn
            _counters["build"] += 1
    om.emit("plan", event="build", op=op, seconds=dt,
            **_compiles_delta(before, dict(_compile_events)))
    return fn


def lookup(key: tuple):
    """The executable stored under a full plan key, or None (no counters)."""
    with _lock:
        return _entries.get(key)


def keys() -> tuple:
    """Snapshot of every full plan key currently registered (tests and
    report tooling; the suffix elements make knob coverage assertable)."""
    with _lock:
        return tuple(_entries)


def evict(key: tuple) -> bool:
    """Drop the entry stored under a FULL plan key (as returned by
    :func:`plan_key`); the serve LRU calls this so an evicted bucket's
    executable is truly released.  Returns whether an entry was removed."""
    from dlaf_tpu.obs import metrics as om

    with _lock:
        found = _entries.pop(key, None) is not None
        if found:
            _counters["evict"] += 1
    if found:
        om.emit("plan", event="evict", op=key[0] if key else None)
    return found


def reset() -> None:
    """Clear every plan entry and the hit/miss counters (tests, and the
    teardown half of a warm-replica rebuild).  Compile counters are
    process-cumulative and stay."""
    with _lock:
        _entries.clear()
        for k in _counters:
            _counters[k] = 0


def stats() -> dict:
    """Counters + size + compile counters, one dict (report_metrics shape)."""
    with _lock:
        out = dict(_counters)
        out["entries"] = len(_entries)
    out.update(compile_counts())
    tot = out["hit"] + out["miss"]
    out["hit_rate"] = out["hit"] / tot if tot else 0.0
    return out


# ----------------------------------------------------------------- warmup


def warmup(buckets=None, *, ops=("potrf", "posv", "eigh"), dtypes=("float32",),
           grid=None, nrhs=1, cache=None) -> dict:
    """Prefetch the serve executables for a bucket ladder: one tiny batch
    per (op, bucket, dtype) through the real batched drivers, so every
    plan entry (and, when the persistent compilation cache is configured,
    every serialized executable) exists before the first request lands.

    Returns a summary dict (``plans``/``compiles``/``aot_loads``/
    ``seconds`` + per-plan ``records``); each warmed plan also emits a
    ``plan`` ``warmup`` event carrying its compile attribution — the
    cold-start oracle the acceptance test and the CI lane read."""
    import numpy as np

    from dlaf_tpu.obs import metrics as om
    from dlaf_tpu.serve import batched, bucketing

    register_monitoring()
    if buckets is None:
        buckets = bucketing.bucket_table()
    records = []
    t_all = time.perf_counter()
    total0 = dict(_compile_events)
    for dtype in dtypes:
        dt = np.dtype(dtype)
        for n in buckets:
            n = int(n)
            spd = np.eye(n, dtype=dt)[None] * 2.0
            for op in ops:
                before = dict(_compile_events)
                t0 = time.perf_counter()
                if op == "potrf":
                    batched.batched_cholesky_factorization(
                        "L", spd, grid, cache=cache)
                elif op == "posv":
                    rhs = np.ones((1, n, nrhs), dt)
                    batched.batched_positive_definite_solver(
                        "L", spd, rhs, grid, cache=cache)
                elif op == "eigh":
                    batched.batched_eigensolver("L", spd, grid, cache=cache)
                else:
                    from dlaf_tpu.health import ConfigurationError

                    raise ConfigurationError(
                        f"plan.warmup: unknown op {op!r} "
                        "(supported: potrf, posv, eigh)")
                rec = {"op": op, "n": n, "dtype": dt.str,
                       "seconds": time.perf_counter() - t0}
                rec.update(_compiles_delta(before, dict(_compile_events)))
                om.emit("plan", event="warmup", **rec)
                records.append(rec)
    out = _compiles_delta(total0, dict(_compile_events))
    out.update(plans=len(records), seconds=time.perf_counter() - t_all,
               records=records)
    return out
