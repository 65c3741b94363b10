"""Opt-in per-stage wall-time breakdown for pipeline algorithms.

The reference exposes pipeline structure through pika/APEX instrumentation
hooks and per-stage debug dumps (reference: tune.h:30-67 debug_dump_*,
SURVEY §5 tracing row).  Here the analogue is two-level: ``--trace`` on the
miniapps captures a full jax.profiler timeline, and this module gives the
cheap always-available summary — wall seconds per named pipeline stage
(red2band / band stage / tridiag / back-transforms ...).

Collection is OFF by default and costs nothing (the context manager yields
immediately).  When ON, each stage boundary BLOCKS on its outputs
(``barrier``) so the attribution is honest — which serializes JAX's async
dispatch and can add a few percent to total wall time; that is why it is
opt-in (``--stage-times`` on the miniapps).
"""
from __future__ import annotations

import contextlib
import time

_times: dict | None = None


def start() -> None:
    """Begin collecting; resets any previous breakdown."""
    global _times
    _times = {}


def stop() -> dict:
    """Stop collecting and return {stage: seconds} in insertion order."""
    global _times
    t, _times = _times or {}, None
    return t


def barrier(*trees) -> None:
    """Block until the given jax values are ready — only while collecting
    (stage attribution needs a sync point; otherwise async dispatch lets a
    stage's device work bleed into the next stage's clock)."""
    if _times is None:
        return
    import jax

    for tr in trees:
        if tr is not None:
            jax.block_until_ready(tr)


@contextlib.contextmanager
def stage(name: str):
    """Accumulate wall time of the body under ``name`` (no-op when off)."""
    if _times is None:
        yield
        return
    t0 = time.perf_counter()
    try:
        yield
    finally:
        # re-check: a nested start/stop must not resurrect collection
        if _times is not None:
            _times[name] = _times.get(name, 0.0) + time.perf_counter() - t0


def report(times: dict, total: float | None = None) -> str:
    """One-line breakdown: ``stage 1.234s (56%) | ...``.  Keys containing
    '/' are sub-stages nested inside a top-level stage and are excluded from
    the default total (their parent already counts them)."""
    if total is None:
        total = sum(v for k, v in times.items() if "/" not in k) or 1.0
    return " | ".join(
        f"{k} {v:.3f}s ({100.0 * v / total:.0f}%)" for k, v in times.items()
    )
