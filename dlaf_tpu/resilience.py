"""Resilience subsystem: deadlines, device watchdog, checkpoint/restart.

The reference's pika runtime never blocks unboundedly — every MPI transfer
is a pollable task the scheduler can abandon — while our drivers call
``block_until_ready()`` with no time bound, and on real pods the dominant
failure mode is preemption mid-factorization (hours-long DMM/polar jobs
forfeit all work when a host disappears, arXiv:2112.09017).  This module is
the bounded-time half of the repro's robustness story, three pillars wired
through the :mod:`dlaf_tpu.health` taxonomy and the ``obs.metrics`` event
stream:

* **Deadlines** — :func:`deadline` (ambient, context-managed) and
  :func:`run_with_deadline` (explicit wrapper) bound blocking host syncs.
  The blocked wait runs on a worker thread and the caller waits with a
  timeout; on expiry the caller gets
  :class:`~dlaf_tpu.health.DeadlineExceededError` within the budget (the
  abandoned wait keeps blocking on its daemon thread — Python cannot
  interrupt a C-blocked thread, the same reason the reference polls
  MPI_Test instead of MPI_Wait).  ``deadline()`` additionally runs a
  monitor thread that health-records ``deadline_expired`` even when the
  main thread is stuck in a foreign unbounded block.

* **Device watchdog** — :class:`DeviceWatchdog` probes device liveness
  with a tiny pre-compiled kernel under a budget and classifies probe
  exhaustion as :class:`~dlaf_tpu.health.DeviceUnresponsiveError`;
  :func:`run_with_watchdog` runs the wrapped computation only after a
  probe answered.

* **Checkpoint/restart** — :func:`save_checkpoint` /
  :func:`load_checkpoint` back the panel-granular ``checkpoint_every=`` /
  ``resume_from=`` options of the long-running panel-loop drivers
  (``cholesky_factorization``, ``reduction_to_band``).  State goes through
  ``matrix/io``'s collective rank-0-write HDF5 path: every process
  dispatches the slab gathers, only process 0 touches the file, and the
  write is ATOMIC (tmp file + rename) so a preemption mid-write leaves the
  previous checkpoint intact.  Writes and restores are collective-safe
  obligations: on a multi-process world EVERY process must reach them.

Fault injection (``dlaf_tpu.testing.faults.hang`` / ``slow_collective`` /
``preempt_at``) plugs into the module-level injection registry below; the
DETECTION paths (bounded waits, watchdog probes, checkpoint restore) are
always the production code paths.
"""
from __future__ import annotations

import contextvars
import json
import os
import threading
import time
from contextlib import contextmanager

import numpy as np

from dlaf_tpu import health
from dlaf_tpu.health import DeadlineExceededError, DeviceUnresponsiveError

CKPT_SCHEMA = "dlaf_tpu.ckpt/1"

#: health events this module emits (consumed by scripts/report_metrics.py)
EVENTS = (
    "deadline_exceeded",
    "deadline_expired",
    "device_probe",
    "device_unresponsive",
    "checkpoint_written",
    "checkpoint_restored",
    "checkpoint_config_mismatch",
)

# ------------------------------------------------------------- deadlines

_local = threading.local()


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


@contextmanager
def deadline(seconds: float, label: str | None = None):
    """Ambient deadline: inside the context, resilience-aware sync points
    (:func:`sync`, the drivers' checkpoint panel boundaries) bound their
    blocking waits by the remaining budget and raise
    :class:`DeadlineExceededError` once it is spent.  Nestable — the
    tightest enclosing deadline wins.

    A monitor thread health-records ``deadline_expired`` if the context is
    still open when the budget runs out — a liveness signal that fires
    even when the main thread is wedged in an unbounded foreign block."""
    seconds = float(seconds)
    expiry = time.monotonic() + seconds
    _stack().append(expiry)
    done = threading.Event()

    def monitor():
        if not done.wait(max(expiry - time.monotonic(), 0.0)):
            health.record("deadline_expired", seconds=seconds, label=label)

    th = threading.Thread(target=monitor, name="dlaf-deadline-monitor", daemon=True)
    th.start()
    try:
        yield
    finally:
        done.set()
        _stack().remove(expiry)


def remaining() -> float | None:
    """Seconds left on the tightest ambient deadline (None: no deadline)."""
    st = _stack()
    if not st:
        return None
    return min(st) - time.monotonic()


def check_deadline(label: str | None = None) -> None:
    """Raise :class:`DeadlineExceededError` if an ambient deadline is spent."""
    rem = remaining()
    if rem is not None and rem <= 0:
        health.record("deadline_exceeded", label=label, where="check")
        from dlaf_tpu.obs import flight

        flight.auto_dump(f"deadline_exceeded:{label or 'unlabeled'}")
        raise DeadlineExceededError(0.0, label=label)


def run_with_deadline(fn, *args, seconds: float | None = None,
                      label: str | None = None, **kwargs):
    """Run ``fn(*args, **kwargs)`` bounded by ``seconds`` wall-clock seconds
    (default: the remaining ambient deadline; unbounded when neither is
    set).  The call runs on a daemon worker thread and the caller waits
    with a timeout, so even a wait that is hung inside native code (a
    wedged device under ``block_until_ready``) is converted into
    :class:`DeadlineExceededError` within the budget — the abandoned call
    keeps blocking in the background and its eventual result is dropped.
    Exceptions from ``fn`` propagate unchanged."""
    if seconds is None:
        seconds = remaining()
    if seconds is None:
        return fn(*args, **kwargs)
    if seconds <= 0:
        health.record("deadline_exceeded", label=label, budget_s=seconds)
        raise DeadlineExceededError(seconds, label=label)
    box: dict = {}
    done = threading.Event()
    # the worker inherits the caller's contextvars (the ambient span
    # context, for one) so host-side instrumentation inside fn nests
    # under the request that dispatched it
    ctx = contextvars.copy_context()

    def worker():
        try:
            box["value"] = ctx.run(fn, *args, **kwargs)
        except BaseException as exc:  # noqa: BLE001 - re-raised on the caller
            box["error"] = exc
        finally:
            done.set()

    th = threading.Thread(target=worker, name="dlaf-deadline-worker", daemon=True)
    th.start()
    if not done.wait(seconds):
        health.record("deadline_exceeded", label=label, budget_s=seconds)
        from dlaf_tpu.obs import flight

        # the watchdog's own probe classifies (and dumps) at its layer —
        # dumping here too would burn the rate limit on the wrong reason
        if not (label or "").startswith("watchdog"):
            flight.auto_dump(f"deadline_exceeded:{label or 'unlabeled'}")
        raise DeadlineExceededError(seconds, label=label)
    if "error" in box:
        raise box["error"]
    return box["value"]


# ------------------------------------------ fault-injection registry

# Written ONLY by dlaf_tpu.testing.faults; production code merely reads it.
# "sync_delay" stalls every bounded device wait (a hung/slow device),
# "panel_delay" stalls each driver panel boundary (a slow interconnect),
# "boundary_hooks" run at panel boundaries (simulated preemption).
_injected: dict = {"sync_delay": 0.0, "panel_delay": 0.0, "boundary_hooks": []}


def _blocked_wait(trees) -> None:
    """The production device-wait path: any injected device stall applies,
    then block until every tree is ready."""
    d = _injected["sync_delay"]
    if d:
        time.sleep(d)
    import jax

    for tr in trees:
        if tr is not None:
            jax.block_until_ready(tr)


def sync(*trees, label: str | None = None, seconds: float | None = None) -> None:
    """Deadline-aware ``block_until_ready``: bounded by ``seconds`` or the
    ambient deadline when one is active, a plain blocking wait otherwise."""
    if seconds is None:
        seconds = remaining()
    if seconds is None and not _injected["sync_delay"]:
        import jax

        for tr in trees:
            if tr is not None:
                jax.block_until_ready(tr)
        return
    run_with_deadline(_blocked_wait, trees, seconds=seconds, label=label)


def panel_boundary(algo: str, panel: int, *trees) -> None:
    """Driver hook between panel segments of a checkpointed factorization:
    the fault-injection point (simulated preemption, slow collectives),
    the ambient deadline check, and — when a deadline or an injected device
    stall is active — a bounded sync of the segment outputs.  Without
    either, no host sync happens here and async dispatch is preserved."""
    for hook in list(_injected["boundary_hooks"]):
        hook(algo, panel)
    d = _injected["panel_delay"]
    if d:
        time.sleep(d)
    label = f"{algo}.panel{panel}"
    check_deadline(label=label)
    if trees and (remaining() is not None or _injected["sync_delay"]):
        sync(*trees, label=label)


# -------------------------------------------------------------- watchdog


class DeviceWatchdog:
    """Bounded liveness probe for one device.

    The probe kernel (a tiny matmul + reduction) is compiled ahead of time
    on construction wherever possible, so a probe measures dispatch +
    execution + device→host readback, not compilation.  Every phase of the
    probe — including dispatch, which can hang on a wedged runtime —
    runs under :func:`run_with_deadline`, so :meth:`probe` returns (or
    raises) within ``budget_s``."""

    def __init__(self, budget_s: float = 5.0, device=None, n: int = 64):
        self.budget_s = float(budget_s)
        self._n = int(n)
        self._device = device
        self._exec = None
        self._x = None

    def _ensure_compiled(self):
        import jax
        import jax.numpy as jnp

        if self._exec is not None:
            return
        if self._device is None:
            self._device = jax.devices()[0]
        x = jax.device_put(
            np.ones((self._n, self._n), np.float32), self._device
        )
        from dlaf_tpu.plan import core as _plan

        fn = _plan.jit("health_probe", lambda a: jnp.sum(a @ a))
        self._exec = fn.lower(x).compile()
        self._x = x

    def probe(self, budget_s: float | None = None) -> float:
        """One bounded liveness probe; returns the round-trip seconds.

        Raises :class:`DeviceUnresponsiveError` (health-recorded) when the
        device does not answer within the budget."""
        budget = self.budget_s if budget_s is None else float(budget_s)
        t0 = time.monotonic()

        def _run():
            self._ensure_compiled()
            _blocked_wait((self._exec(self._x),))

        try:
            run_with_deadline(_run, seconds=budget, label="watchdog.probe")
        except DeadlineExceededError as exc:
            health.record(
                "device_unresponsive",
                budget_s=budget,
                device=str(self._device or "default"),
            )
            from dlaf_tpu.obs import flight

            flight.auto_dump("device_unresponsive")
            raise DeviceUnresponsiveError(
                budget_s=budget, device=str(self._device or "default")
            ) from exc
        dt = time.monotonic() - t0
        health.record("device_probe", seconds=dt, budget_s=budget)
        return dt

    def alive(self, budget_s: float | None = None) -> bool:
        try:
            self.probe(budget_s)
            return True
        except DeviceUnresponsiveError:
            return False


def run_with_watchdog(fn, *args, watchdog: DeviceWatchdog | None = None,
                      budget_s: float = 5.0, **kwargs):
    """Probe device liveness, then run ``fn``.  A device that does not
    answer raises :class:`DeviceUnresponsiveError`; the work never moves
    to another platform."""
    wd = watchdog if watchdog is not None else DeviceWatchdog(budget_s=budget_s)
    wd.probe()
    return fn(*args, **kwargs)


# ---------------------------------------------------- checkpoint/restart


def _world_sync(tag: str) -> None:
    import jax

    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(tag)


def _pyattr(v):
    """h5py attribute -> plain python value (numpy scalars/bytes unwrapped)."""
    if isinstance(v, bytes):
        return v.decode()
    if isinstance(v, np.generic):
        return v.item()
    if isinstance(v, np.ndarray):
        return v.tolist()
    return v


def save_checkpoint(path: str, mat, *, algo: str, panel: int, info: int = 0,
                    extras: dict | None = None) -> None:
    """Write one panel-granular checkpoint of ``mat`` at ``panel``.

    COLLECTIVE: every process must call it (the matrix write dispatches
    per-slab gathers through ``matrix/io.save_hdf5``); only process 0
    touches the file.  Atomic: the state lands in ``path + '.tmp'`` and is
    renamed into place only once complete, so a preemption mid-write never
    corrupts the previous checkpoint.  ``extras`` maps dataset names to
    rank-replicated host arrays (e.g. reduction_to_band's taus); the tune
    config snapshot and the collectives trace key ride along as attributes
    so a resume can flag drifted configuration."""
    import jax

    from dlaf_tpu import tune
    from dlaf_tpu.comm import collectives as coll
    from dlaf_tpu.matrix import io as mio

    tmp = str(path) + ".tmp"
    mio.save_hdf5(
        tmp,
        mat,
        attrs={
            "ckpt_schema": CKPT_SCHEMA,
            "algo": str(algo),
            "panel": int(panel),
            "info": int(info),
            "tune_snapshot": json.dumps(
                tune.config_snapshot(), default=str, sort_keys=True
            ),
            "collectives_key": str(coll.collectives_trace_key()),
        },
        datasets=extras or {},
    )
    if jax.process_index() == 0:
        os.replace(tmp, path)
    _world_sync("dlaf_tpu.resilience.save_checkpoint")
    health.record("checkpoint_written", algo=algo, panel=int(panel), path=str(path))


def load_checkpoint(path: str, mat, *, algo: str, extras: tuple = ()):
    """Restore a checkpoint written by :func:`save_checkpoint`.

    ``mat`` supplies the target geometry (size, tile size, grid,
    source rank, dtype) — a mismatch against the stored state raises
    :class:`~dlaf_tpu.health.DistributionError` instead of silently
    resuming into the wrong distribution.  Returns ``(data, attrs,
    extra_arrays)`` where ``data`` is the restored device state on
    ``mat``'s distribution, ``attrs`` carries ``panel``/``info``/the
    stored snapshots, and ``extra_arrays`` holds the requested ``extras``
    datasets as host arrays.  COLLECTIVE on multi-process worlds (the
    streamed read places slabs through replicated device puts); a tune or
    collectives-tier drift against the stored snapshot is health-recorded
    (``checkpoint_config_mismatch``), not fatal — the restored matrix
    state is tier-independent."""
    import h5py

    from dlaf_tpu import tune
    from dlaf_tpu.comm import collectives as coll
    from dlaf_tpu.health import DistributionError
    from dlaf_tpu.matrix import io as mio

    with h5py.File(path, "r") as f:
        if "a" not in f:
            raise DistributionError(f"{path}: not a dlaf_tpu checkpoint (no dataset 'a')")
        ds = f["a"]
        attrs = {k: _pyattr(v) for k, v in ds.attrs.items()}
        if attrs.get("ckpt_schema") != CKPT_SCHEMA:
            raise DistributionError(
                f"{path}: not a dlaf_tpu checkpoint "
                f"(schema {attrs.get('ckpt_schema')!r} != {CKPT_SCHEMA!r})"
            )
        if attrs.get("algo") != algo:
            raise DistributionError(
                f"{path}: checkpoint belongs to {attrs.get('algo')!r}, not {algo!r}"
            )
        if tuple(ds.shape) != tuple(mat.size):
            raise DistributionError(
                f"{path}: checkpoint is {tuple(ds.shape)}, matrix is {tuple(mat.size)}"
            )
        if tuple(attrs.get("block_size", ())) != tuple(mat.block_size):
            raise DistributionError(
                f"{path}: checkpoint tile size {attrs.get('block_size')} != "
                f"matrix tile size {tuple(mat.block_size)}"
            )
        if np.dtype(ds.dtype) != np.dtype(mat.dtype):
            raise DistributionError(
                f"{path}: checkpoint dtype {ds.dtype} != matrix dtype "
                f"{np.dtype(mat.dtype)}"
            )
        missing = [name for name in extras if name not in f]
        if missing:
            raise DistributionError(f"{path}: checkpoint missing datasets {missing}")
        extra_arrays = {name: np.asarray(f[name][()]) for name in extras}
    loaded = mio.load_hdf5(path, mat.grid, block_size=tuple(mat.block_size))
    if loaded.dist != mat.dist:
        raise DistributionError(
            f"{path}: restored distribution {loaded.dist} != target {mat.dist}"
        )
    try:
        stored = json.loads(attrs.get("tune_snapshot", "{}"))
        now = json.loads(json.dumps(tune.config_snapshot(), default=str, sort_keys=True))
        drift = sorted(
            k for k in set(stored) | set(now) if stored.get(k) != now.get(k)
        )
    except ValueError:
        drift = ["tune_snapshot:unreadable"]
    if str(coll.collectives_trace_key()) != attrs.get("collectives_key", ""):
        drift.append("collectives_impl")
    if drift:
        health.record("checkpoint_config_mismatch", algo=algo, keys=drift[:16])
    health.record(
        "checkpoint_restored", algo=algo, panel=int(attrs.get("panel", 0)),
        path=str(path),
    )
    return loaded.data, attrs, extra_arrays
