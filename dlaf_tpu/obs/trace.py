"""Trace annotation: named phases on profiler timelines and in compiled HLO.

Two distinct mechanisms, chosen by where the name must land:

``scope(name)``
    ``jax.named_scope`` for use INSIDE traced kernel bodies.  JAX's name
    stack does not cross a ``jit`` boundary from the outside, so a scope
    entered around a compiled call never reaches that kernel's HLO — the
    scopes must live in the function being traced.  Names placed this way
    appear in the *compiled* executable's op metadata
    (``lower(...).compile().as_text()``) and as grouping rows in
    ``--trace`` / XProf timelines.  They never change the computation
    (StableHLO is byte-identical with and without them only for the
    location metadata — tests assert op-level equivalence via the
    disabled-path HLO check in tests/test_obs.py).

``phase(name)``
    Host-level phase marker for orchestration code (the Python that calls
    compiled kernels): a ``jax.profiler.TraceAnnotation`` so host timeline
    slices carry the phase name, plus an append to the module phase log
    when one is active (``start_phase_log``), which is how tests assert
    "this run entered >= N named phases" without hardware or a profiler.
    Open phases form a per-thread name stack (:func:`current`).

Program loads
    Every program JAX compiles or loads from the persistent cache is charged
    to the innermost open phase (``obs.stage`` enters one too) and marked on
    the profiler timeline as a host span ``program_load/<phase>/<program>``
    over the compile or load itself.  ``plan.core``'s ``jax.monitoring``
    listeners call :func:`load_started` / :func:`load_finished`; the counts
    are read with :func:`program_loads`.  Steady-state solves compile
    nothing, so they never reach this code.

Everything here is allocation-free on the off path: ``phase`` with no log
active costs one TraceAnnotation enter/exit (nanoseconds, host-side only)
and one append/pop on the name stack, and ``scope`` is plain
``jax.named_scope``.
"""
from __future__ import annotations

import contextlib
import threading

import jax

from dlaf_tpu.obs import spans as _spans

# Ordered log of phase names entered while a log is active (None = off).
_phase_log: list | None = None
_lock = threading.Lock()
# Per thread: ``names``, the stack of open phases; ``loads``, the program
# loads in progress as [TraceAnnotation, phase].
_local = threading.local()
#: phase -> {"compiled": n, "loaded": m}, process-cumulative.
_loads: dict = {}
NO_PHASE = "(no phase)"


def scope(name: str):
    """``jax.named_scope`` alias for in-kernel phase names (see module doc:
    must be entered inside the traced function to reach that kernel's HLO)."""
    return jax.named_scope(name)


def start_phase_log() -> None:
    """Begin recording phase names entered via :func:`phase`; resets any
    previous log."""
    global _phase_log
    with _lock:
        _phase_log = []


def stop_phase_log() -> list:
    """Stop recording and return the ordered list of phase names entered."""
    global _phase_log
    with _lock:
        log, _phase_log = _phase_log or [], None
    return log


def phase_log_active() -> bool:
    return _phase_log is not None


def _stack(attr: str) -> list:
    s = getattr(_local, attr, None)
    if s is None:
        s = []
        setattr(_local, attr, s)
    return s


def current() -> str:
    """The innermost phase open on this thread, or ``NO_PHASE``."""
    names = getattr(_local, "names", None)
    return names[-1] if names else NO_PHASE


@contextlib.contextmanager
def phase(name: str):
    """Host-level named phase around orchestration code (see module doc).

    When request-scoped span tracing is live AND an ambient span context is
    bound on this task/thread (``spans.bind``/an open ``spans.span``), the
    phase additionally lands as a ``phase.<name>`` child span — this is how
    driver phases (potrf panels, red2band sweeps) attach under the serve
    request that triggered them.  Off path unchanged: one enable-flag test."""
    if _phase_log is not None:
        with _lock:
            if _phase_log is not None:
                _phase_log.append(name)
    names = _stack("names")
    names.append(name)
    try:
        if _spans.current_if_active() is not None:
            with _spans.span(f"phase.{name}"), jax.profiler.TraceAnnotation(name):
                yield
        else:
            with jax.profiler.TraceAnnotation(name):
                yield
    finally:
        names.pop()


def load_started(program: str) -> None:
    """A compile or persistent-cache load of ``program`` begins on this
    thread: open its ``program_load/<phase>/<program>`` span."""
    where = current()
    ann = jax.profiler.TraceAnnotation(f"program_load/{where}/{program}")
    ann.__enter__()
    _stack("loads").append((ann, where))


def load_finished(loaded: bool) -> None:
    """The innermost load begun on this thread ended: close its span and
    count it, as ``loaded`` from the persistent cache or else compiled."""
    loads = _stack("loads")
    if loads:
        ann, where = loads.pop()
        ann.__exit__(None, None, None)
    else:  # begun before the listeners were registered
        where = current()
    with _lock:
        rec = _loads.setdefault(where, {"compiled": 0, "loaded": 0})
        rec["loaded" if loaded else "compiled"] += 1


def program_loads() -> dict:
    """Snapshot ``{phase: {"compiled": n, "loaded": m}}`` of every program
    compiled or loaded since the process started, by the innermost phase
    open at the time; subtract two snapshots to attribute a run."""
    with _lock:
        return {k: dict(v) for k, v in _loads.items()}
