"""Health subsystem: error taxonomy, info codes, NaN sentinels, recovery.

LAPACK/ScaLAPACK report failure through ``info`` codes (the non-SPD pivot
index from POTRF, non-convergence counts from the eigensolvers) and the
reference guards its internals with three-level assertions
(include/dlaf/common/assert.h).  This module is the reproduction's
info-code half:

* a structured exception taxonomy (:class:`DlafError` and subclasses)
  replacing bare ``ValueError``/``AssertionError`` at API boundaries —
  :class:`DistributionError` subclasses ``ValueError`` so existing
  ``except ValueError`` callers keep working;
* LAPACK-compatible **1-based** info-code conventions: ``info == 0`` is
  success, ``info == k > 0`` means the leading minor of order k is not
  positive definite (the k-th pivot failed);
* NaN/Inf **sentinels** (:func:`check_finite`) at pipeline stage seams,
  gated by ``DLAF_TPU_CHECK_LEVEL >= 2`` exactly like
  ``checks.assert_heavy`` — a no-op (and zero change to any compiled
  computation) below that level;
* a health **event stream** (:func:`record`) feeding ``obs.metrics`` so
  detector hits, retries, shifts and fallbacks land in the same JSONL
  audit trail as PR 1's run metrics, plus :func:`capture_events` for
  tests that assert a detector actually fired.

Sentinels and heavy checks are collective-safe obligations: on a
multi-process world EVERY process must reach them (they gather device
data), the same contract as ``DistributedMatrix.to_global``.
"""
from __future__ import annotations

from contextlib import contextmanager

from dlaf_tpu.obs import metrics as _om

# --------------------------------------------------------------- taxonomy


class DlafError(Exception):
    """Base of the dlaf_tpu error taxonomy."""


class NotPositiveDefiniteError(DlafError, ArithmeticError):
    """A Cholesky-based driver met a non-positive pivot.

    ``info`` is the LAPACK-style 1-based index of the first failing pivot
    (the leading minor of order ``info`` is not positive definite).
    ``shift`` is the last diagonal shift tried when bounded recovery was
    on (0.0 when recovery was off)."""

    def __init__(self, info: int, message: str | None = None, shift: float = 0.0):
        self.info = int(info)
        self.shift = float(shift)
        if message is None:
            message = (
                f"matrix is not positive definite: the leading minor of "
                f"order {self.info} failed (LAPACK info={self.info})"
            )
            if shift:
                message += f"; last diagonal shift tried: {shift:g}"
        super().__init__(message)


class ConvergenceError(DlafError, RuntimeError):
    """An iterative driver (refinement, mixed-precision solve) did not meet
    its convergence criterion within its iteration budget.  Carries the
    driver's info object (e.g. ``MixedSolveInfo`` / ``EigRefineInfo``)."""

    def __init__(self, message: str, info=None):
        self.info = info
        super().__init__(message)


class DistributionError(DlafError, ValueError):
    """Invalid matrix/grid distribution or API misuse (bad descriptor,
    non-square tiles, shape mismatch).  Subclasses ``ValueError`` so
    pre-taxonomy callers catching ``ValueError`` keep working."""


class ConfigurationError(DlafError, ValueError):
    """A tune/config knob holds a value outside its documented domain
    (e.g. a typo'd ``DLAF_TPU_COLLECTIVES_IMPL``).  Subclasses
    ``ValueError`` so pre-taxonomy callers catching ``ValueError`` keep
    working."""


class NonFiniteError(DlafError, ArithmeticError):
    """A stage-boundary sentinel found NaN/Inf.  ``stage`` names the first
    pipeline stage whose output went non-finite."""

    def __init__(self, stage: str, message: str | None = None):
        self.stage = stage
        super().__init__(
            message
            or f"non-finite values (NaN/Inf) first appeared after stage {stage!r}"
        )


class DeadlineExceededError(DlafError, TimeoutError):
    """A deadline-bounded operation did not complete within its budget
    (``resilience.deadline`` / ``run_with_deadline``).  ``budget_s`` is the
    wall-clock bound that was exceeded; ``label`` names the bounded
    operation when the caller supplied one.  Subclasses ``TimeoutError``
    so generic timeout handlers keep working."""

    def __init__(self, budget_s: float, label: str | None = None,
                 message: str | None = None):
        self.budget_s = float(budget_s)
        self.label = label
        if message is None:
            what = f" ({label})" if label else ""
            message = (
                f"operation{what} exceeded its deadline of "
                f"{self.budget_s:g} s"
            )
        super().__init__(message)


class QueueFullError(DlafError, RuntimeError):
    """A ``serve.SolverPool`` rejected a submission under backpressure:
    the queue already holds ``size`` requests against a bound of
    ``capacity`` (``tune.serve_max_queue``).  Callers should shed load or
    retry after draining results — the pool never blocks ``submit``."""

    def __init__(self, size: int, capacity: int, message: str | None = None):
        self.size = int(size)
        self.capacity = int(capacity)
        super().__init__(
            message
            or (
                f"solver pool queue is full: {self.size} queued requests "
                f"at capacity {self.capacity}"
            )
        )


class TenantQuotaExceededError(QueueFullError):
    """The serve gateway shed a request at admission because the tenant's
    token-bucket quota was exhausted (``serve.TenantConfig.rate`` /
    ``burst``).  Subclasses :class:`QueueFullError` so generic
    shed-and-retry handlers keep working; ``tenant`` names the offender
    and ``rate`` its configured refill rate in requests/second."""

    def __init__(self, tenant: str, rate: float, message: str | None = None):
        self.tenant = str(tenant)
        self.rate = float(rate)
        super().__init__(
            0, 0,
            message
            or (
                f"tenant {self.tenant!r} exceeded its request quota "
                f"(token bucket empty at rate {self.rate:g}/s); retry later"
            ),
        )


class WireProtocolError(DlafError, RuntimeError):
    """A serve fleet wire frame violated the framing contract
    (``serve.wire``): bad magic, a length prefix beyond the frame bound,
    a stream that ended mid-frame, or a header that is not valid JSON.
    ``reason`` is a short machine-stable tag (``"magic"`` / ``"oversize"``
    / ``"truncated"`` / ``"header"`` / ``"array"``) so tests and the
    supervisor's restart policy can branch without string-matching the
    human message."""

    def __init__(self, reason: str, message: str | None = None):
        self.reason = str(reason)
        super().__init__(
            message or f"wire protocol violation ({self.reason})"
        )


class RemoteWorkerError(DlafError, RuntimeError):
    """A fleet worker process reported a failure whose type has no
    constructor mapping in the wire error registry (``serve.wire``
    rebuilds known taxonomy errors typed; everything else lands here).
    ``remote_type`` preserves the original exception class name."""

    def __init__(self, remote_type: str, message: str | None = None):
        self.remote_type = str(remote_type)
        super().__init__(
            message or f"worker raised {self.remote_type}"
        )


class DeviceUnresponsiveError(DlafError, RuntimeError):
    """The device watchdog's bounded liveness probe was exhausted: the
    device did not answer a tiny pre-compiled kernel within ``budget_s``
    (a preempted host, a wedged runtime)."""

    def __init__(self, budget_s: float = 0.0, device: str = "default",
                 message: str | None = None):
        self.budget_s = float(budget_s)
        self.device = device
        super().__init__(
            message
            or (
                f"device {device} unresponsive: liveness probe did not "
                f"complete within {self.budget_s:g} s"
            )
        )


# ----------------------------------------------------------- event stream

_captured: list | None = None


def record(event: str, **fields) -> None:
    """Record one health event (detector hit, retry, shift, fallback).

    Events go to the active ``obs.metrics`` stream (kind ``"health"``) when
    one is enabled, and to the innermost :func:`capture_events` list when a
    test is capturing.  Free when neither is active."""
    if _captured is not None:
        _captured.append({"event": event, **fields})
    _om.emit("health", event=event, **fields)


@contextmanager
def capture_events():
    """Collect health events into the yielded list (for tests).

    Nested captures see only their own events; the outer capture resumes
    when the inner one exits."""
    global _captured
    prev, _captured = _captured, []
    try:
        yield _captured
    finally:
        _captured = prev


# --------------------------------------------------------------- sentinels


def check_finite(stage: str, *operands) -> None:
    """NaN/Inf sentinel at a pipeline stage boundary.

    Below ``DLAF_TPU_CHECK_LEVEL`` 2 this returns immediately without
    touching any operand — stage outputs flow through unchanged and no
    computation is traced, so compiled driver HLO is byte-identical with
    sentinels off (the same guarantee obs.comms makes for accounting).

    At level >= 2 every operand (``DistributedMatrix`` or array) is
    reduced with ``isfinite``; the per-operand flags are stacked into ONE
    device→host sync per call site (not one per operand), and the first
    non-finite operand raises :class:`NonFiniteError` naming ``stage``.
    Collective-safe: on multi-process grids all processes must call this
    (all do — it sits in SPMD driver code every rank runs).
    """
    from dlaf_tpu.common import checks

    if checks.check_level() < 2:
        return
    import jax.numpy as jnp
    import numpy as np

    datas = [getattr(op, "data", op) for op in operands if op is not None]
    if not datas:
        return
    flags = np.asarray(
        jnp.stack([jnp.all(jnp.isfinite(d)) for d in datas])
    )
    if not flags.all():
        record("nonfinite", stage=stage, operand=int(np.argmin(flags)))
        raise NonFiniteError(stage)
