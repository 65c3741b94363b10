"""Multi-host (multi-process) runtime bring-up.

TPU-native analogue of the reference's MPI world initialization
(reference: include/dlaf/communication/init.h MPI init guard +
src/init.cpp:366-443 — MPI_THREAD_MULTIPLE check, pika MPI polling).  On
TPU pods the communication backend is XLA collectives over ICI/DCN; the
only host-side obligation is bringing up the JAX distributed runtime so
``jax.devices()`` spans every process's chips.  After :func:`initialize`,
the normal single-controller-style code runs unchanged on every process
(classic SPMD — the same obligation the reference places on its MPI
ranks): build one :class:`~dlaf_tpu.comm.grid.Grid` over the global
device list, initialize matrices with
``DistributedMatrix.from_global``/``from_element_function`` (every
process passes the same global content), call algorithms.

Environment-driven (the standard JAX cluster envs / TPU metadata), or
explicit::

    from dlaf_tpu.comm import multihost
    multihost.initialize()                       # TPU pod / cluster envs
    multihost.initialize("host0:1234", 4, rank)  # explicit coordinator

This module is exercised in CI only in its single-process form (this
container has one process); the multi-process branches use the standard
``jax.distributed`` / ``make_array_from_callback`` / replicate-gather
APIs and carry no environment-specific logic.
"""
from __future__ import annotations

_initialized = False
_world_up = False  # a REAL jax.distributed world came up (vs a no-op)


def initialize(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    retries: int = 0,
    backoff_s: float = 1.0,
    deadline_s: float | None = None,
    initialization_timeout: float | None = None,
) -> None:
    """Bring up the JAX distributed runtime (idempotent).

    With no arguments, defers to ``jax.distributed.initialize()``'s
    environment/cloud autodetection (TPU pod metadata, SLURM, etc.).  A
    single-process environment where autodetection finds no cluster is
    left untouched — algorithms run exactly as before.  A later EXPLICIT
    call (with a coordinator address) overrides an earlier no-op.

    Pod bring-up is the one place a transient failure is EXPECTED (the
    coordinator process races the workers; preemptible hosts restart):
    with ``retries > 0``, a failed EXPLICIT-coordinator bring-up is
    retried with exponential backoff (``backoff_s`` doubling each
    attempt, capped at 30s), giving up after ``retries`` retries or when
    ``deadline_s`` wall-clock seconds have elapsed — whichever comes
    first.  Each retry is health-recorded (``multihost_retry``); the
    defaults (``retries=0``) keep behavior identical to before.
    Autodetected single-process no-ops never retry — there is nothing to
    wait for.

    ``initialization_timeout`` bounds the coordinator HANDSHAKE itself (in
    seconds, passed through to ``jax.distributed.initialize``) — without it only the inter-attempt backoff
    honors ``deadline_s`` while each individual handshake blocks for jax's
    default (5 minutes).  When unset but ``deadline_s`` is given, the
    remaining deadline budget is used, so the whole bring-up — handshakes
    included — stays inside ``deadline_s``.
    """
    global _initialized, _world_up
    explicit = coordinator_address is not None
    if _initialized and (_world_up or not explicit):
        # idempotent: repeated calls (explicit or not) after a successful
        # bring-up no-op; only an explicit call may override an earlier
        # single-process NO-OP
        return

    import time

    import jax

    # CPU multi-process worlds need a host collectives implementation in
    # the CPU client; the installed JAX defaults
    # jax_cpu_collectives_implementation to 'gloo', so nothing is set here
    # (TPU worlds use ICI/DCN and never read it).
    start = time.monotonic()
    attempt = 0
    while True:
        init_kwargs = {}
        timeout = initialization_timeout
        if timeout is None and deadline_s is not None:
            # bound each handshake by what is left of the deadline
            timeout = max(deadline_s - (time.monotonic() - start), 1.0)
        if timeout is not None:
            init_kwargs["initialization_timeout"] = int(max(timeout, 1.0))
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
                **init_kwargs,
            )
            _world_up = True
            break
        except ValueError:
            # jax's cluster autodetection (TPU pod metadata, SLURM, GKE, the
            # coordinator envs) found nothing and no explicit coordinator was
            # given: a single-process world, nothing to bring up
            if explicit:
                raise
            break
        except RuntimeError as exc:
            # backend already initialized / double init: fine when the world
            # is effectively single-process; otherwise the caller initialized
            # too late (after first device use), or the coordinator is not up
            # yet (connect/handshake failure — the retryable case)
            if not explicit and jax.process_count() == 1:
                import warnings

                warnings.warn(
                    "multihost.initialize() called after the XLA backend came "
                    "up; continuing single-process",
                    RuntimeWarning,
                    stacklevel=2,
                )
                break
            elapsed = time.monotonic() - start
            out_of_time = deadline_s is not None and elapsed >= deadline_s
            if not explicit or attempt >= retries or out_of_time:
                raise
            wait = min(backoff_s * (2.0**attempt), 30.0)
            if deadline_s is not None:
                wait = min(wait, max(deadline_s - elapsed, 0.0))
            attempt += 1
            from dlaf_tpu import health

            health.record(
                "multihost_retry",
                attempt=attempt,
                wait_s=wait,
                error=str(exc)[:200],
            )
            time.sleep(wait)
    _initialized = True


def process_info() -> tuple[int, int]:
    """(process_id, process_count) of the running world."""
    import jax

    return jax.process_index(), jax.process_count()


def is_main_process() -> bool:
    """True on the process that should do controller-side printing/IO."""
    import jax

    return jax.process_index() == 0
