"""Structured run metrics: schema-versioned JSONL event stream per run.

Every enabled run leaves an audit trail: one JSON object per line, each
carrying ``schema`` (version tag), ``kind`` (record type), ``ts`` (unix
seconds) and ``rank`` (jax process index).  Record kinds and their
required payload fields are the single source of truth in
:data:`REQUIRED_FIELDS`; :func:`validate_record` enforces them (used by
tests and by ``scripts/report_metrics.py``).

Multi-process: rank 0 writes ``PATH``; rank r > 0 writes ``PATH.rank<r>``.
``close()`` syncs the world (when ``jax.distributed`` is up) and then has
rank 0 append every part file it can see into ``PATH`` — which merges
fully on a shared filesystem or a single-host multi-process world (the
test harness); on disjoint hosts the per-rank parts simply stay put next
to each host's working directory.

Off by default and free when off: :func:`emit` is one ``is None`` test
per sink (emitter + flight-recorder tee).  Compile-time records ride
``plan.core``'s one set of ``jax.monitoring`` listeners (registered at the
latest on first :func:`enable`), forwarded only while an emitter is active.

Schema history: ``/1`` is the original record set; ``/2`` adds the
``span`` (request-scoped tracing, ``obs.spans``) and ``flight`` (crash
dump pointers, ``obs.flight``) kinds; ``/3`` adds the ``scenario``
(scenario-run results and replay verdicts, ``dlaf_tpu.scenario``) and
``capacity`` (service-time fits and replicas-needed predictions,
``scenario.capacity``) kinds, and stamps ``gw.request`` root spans with
the replayable request attrs (shape, dtype, deadline, batch group key);
``/4`` adds the ``plan`` kind (unified executable-plan cache events —
hit/miss/build/evict/warmup/decision, ``dlaf_tpu.plan``); ``/5`` adds
the ``fleet`` kind (cross-process serve fleet lifecycle — worker spawn/
ready/exit/restart, circuit breaker, failover re-dispatch, autoscale
decisions with their triggering signals, child flight-dump collection;
``dlaf_tpu.serve.supervisor`` / ``serve.fleet``); ``/6`` adds the
``telemetry`` kind (live instrument-registry snapshots — fleet-merged
counters/gauges/histograms, ``obs.telemetry``) and the ``slo_burn``
kind (dual-window error-budget burn-rate transitions per tenant).
Writers stamp ``/6``; readers (:func:`validate_record`,
:func:`read_jsonl`) accept all six so old BENCH and metrics artifacts
keep parsing.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time

SCHEMA = "dlaf_tpu.obs/6"
#: every schema tag a reader accepts (old artifacts carry /1 - /5).
SCHEMAS = ("dlaf_tpu.obs/1", "dlaf_tpu.obs/2", "dlaf_tpu.obs/3",
           "dlaf_tpu.obs/4", "dlaf_tpu.obs/5", "dlaf_tpu.obs/6")

#: kind -> payload fields every record of that kind must carry.
REQUIRED_FIELDS: dict = {
    "run_meta": ("argv", "jax_version", "backend", "process_count", "device_count"),
    "config": ("config",),
    "stages": ("stages",),
    "comms": ("rows",),
    "run": ("name", "seconds"),
    "kernel": ("name", "seconds"),
    "bench": ("record",),
    "compile": ("event", "duration_s"),
    "compile_cache": ("event",),
    "note": ("text",),
    "health": ("event",),
    "serve": ("event",),
    # /2 additions:
    "span": ("name", "trace_id", "span_id", "t0_s", "dur_s"),
    "flight": ("reason", "path", "events"),
    # /3 additions:
    "scenario": ("event",),
    "capacity": ("event",),
    # /4 additions:
    "plan": ("event",),
    # /5 additions:
    "fleet": ("event",),
    # /6 additions:
    "telemetry": ("snapshot",),
    "slo_burn": ("tenant", "fast_burn", "slow_burn", "firing"),
}

_emitter = None
# Optional secondary sink (the flight recorder's ring tap): called as
# _tee(kind, fields) for every record emitted, whether or not a JSONL
# emitter is active.  None = off (the common case; emit() stays two
# module-global tests on the off path).
_tee = None
# Additional record taps (fleet workers buffering span records for wire
# streaming).  Unlike the single-slot tee this is a list; None when empty
# so the off path stays one module-global test.
_taps = None


class MetricsEmitter:
    """JSONL writer bound to one output path (rank-suffixed off rank 0)."""

    def __init__(self, path: str):
        import jax

        self.base_path = path
        self.rank = jax.process_index()
        self.nprocs = jax.process_count()
        self.path = path if self.rank == 0 else f"{path}.rank{self.rank}"
        self._fh = open(self.path, "w")
        # The gateway dispatcher thread, pool workers/done-callbacks and
        # jax.monitoring listeners all emit concurrently; an unlocked
        # write+flush pair can interleave half-lines into the JSONL.
        self._lock = threading.Lock()

    def emit(self, kind: str, **fields) -> None:
        rec = {"schema": SCHEMA, "kind": kind, "ts": time.time(), "rank": self.rank}
        rec.update(fields)
        line = json.dumps(rec, default=_jsonable) + "\n"
        with self._lock:
            if self._fh is None:
                return  # closed concurrently: drop rather than raise
            self._fh.write(line)
            self._fh.flush()

    def close(self) -> None:
        """Flush, world-sync, and merge rank part files into ``base_path``."""
        with self._lock:
            fh, self._fh = self._fh, None
        if fh is None:
            return
        fh.close()
        if self.nprocs > 1:
            try:
                from jax.experimental import multihost_utils

                multihost_utils.sync_global_devices("dlaf_tpu.obs.metrics.close")
            except Exception:
                pass  # world already torn down: merge whatever is on disk
            if self.rank == 0:
                with open(self.base_path, "a") as out:
                    for r in range(1, self.nprocs):
                        part = f"{self.base_path}.rank{r}"
                        if os.path.exists(part):
                            with open(part) as fh:
                                out.write(fh.read())
                            os.remove(part)


def _jsonable(x):
    """Fallback serializer: numpy scalars, dtypes, paths, anything str-able."""
    try:
        return x.item()  # numpy scalar
    except AttributeError:
        return str(x)


def enable(path: str) -> MetricsEmitter:
    """Open the metrics stream at ``path`` (closing any previous one); the
    compile records come from ``plan.core``'s jax.monitoring listeners."""
    from dlaf_tpu.plan import core as _plan

    global _emitter
    if _emitter is not None:
        _emitter.close()
    _plan.register_monitoring()
    _emitter = MetricsEmitter(path)
    return _emitter


def enabled() -> bool:
    return _emitter is not None


def get() -> MetricsEmitter | None:
    return _emitter


def emit(kind: str, **fields) -> None:
    """Emit one record on the active sinks (JSONL stream, flight tee,
    registered taps); no-op when all are off."""
    if _emitter is not None:
        _emitter.emit(kind, **fields)
    if _tee is not None:
        _tee(kind, fields)
    if _taps is not None:
        for tap in _taps:
            tap(kind, fields)


def set_tee(fn) -> None:
    """Install (or clear, with None) the secondary record sink — the
    flight recorder's ring tap.  One slot: last caller wins."""
    global _tee
    _tee = fn


def add_tap(fn) -> None:
    """Register an additional record sink, called as ``fn(kind, fields)``
    for every emitted record (the fleet worker's span-streaming buffer).
    Multiple taps coexist — unlike the single-slot flight tee."""
    global _taps
    taps = list(_taps or ())
    taps.append(fn)
    _taps = taps


def sinking() -> bool:
    """True when at least one sink would receive an emitted record."""
    return _emitter is not None or _tee is not None or _taps is not None


def close() -> None:
    """Close (and on multi-process worlds merge) the active stream."""
    global _emitter
    if _emitter is None:
        return
    em, _emitter = _emitter, None
    em.close()


def forward_compile(event: str, duration: float) -> None:
    """A ``jax.monitoring`` duration event (``plan.core``'s listener hands
    each one on): compile durations become ``compile`` records while a
    stream is open."""
    if _emitter is not None and "compile" in event:
        emit("compile", event=event, duration_s=float(duration))


def forward_cache(event: str) -> None:
    """A ``jax.monitoring`` event: persistent-cache and compile events
    become ``compile_cache`` records while a stream is open."""
    if _emitter is not None and ("cache" in event or "compile" in event):
        emit("compile_cache", event=event)


# ---------------------------------------------------------------- helpers


def emit_run_meta(name: str, **extra) -> None:
    """The once-per-run identity record (argv, jax/backend/world facts)."""
    if _emitter is None:
        return
    import jax

    emit(
        "run_meta",
        name=name,
        argv=list(sys.argv),
        jax_version=jax.__version__,
        backend=jax.default_backend(),
        process_count=jax.process_count(),
        device_count=jax.device_count(),
        local_device_count=jax.local_device_count(),
        **extra,
    )


def emit_config() -> None:
    """Snapshot the live tune.py configuration (same facts print_config
    renders as text)."""
    if _emitter is None:
        return
    from dlaf_tpu import tune

    emit("config", config=tune.config_snapshot())


def emit_stages(times: dict, total: float | None = None) -> None:
    """Stage wall-time breakdown from ``common.stagetimer`` ({name: s})."""
    if _emitter is None or not times:
        return
    fields = {"stages": {k: float(v) for k, v in times.items()}}
    if total is not None:
        fields["total_s"] = float(total)
    emit("stages", **fields)


def emit_comms(acc: dict) -> None:
    """Comms accounting rows from ``obs.comms`` (stop()/snapshot() dict)."""
    if _emitter is None or not acc:
        return
    from dlaf_tpu.obs import comms

    emit("comms", rows=comms.as_records(acc))


def append_records(path: str, records: list, rank: int = 0) -> None:
    """Append schema-stamped records to ``path`` WITHOUT importing jax.

    For host-side supervisors that must write metrics about a device that
    may be dead (bench.py's parent process classifying an unresponsive
    child): creating an emitter would bring up the very backend being
    diagnosed.  Each record supplies ``kind`` plus its payload fields;
    ``schema``/``ts``/``rank`` are stamped here and every record is
    validated before anything is written (all-or-nothing)."""
    stamped = []
    for rec in records:
        out = {"schema": SCHEMA, "ts": time.time(), "rank": int(rank)}
        out.update(rec)
        validate_record(out)
        stamped.append(out)
    with open(path, "a") as fh:
        for out in stamped:
            fh.write(json.dumps(out, default=_jsonable) + "\n")


def validate_record(rec: dict) -> None:
    """Raise ValueError unless ``rec`` is a schema-valid metrics record."""
    if not isinstance(rec, dict):
        raise ValueError(f"record is not an object: {type(rec).__name__}")
    if rec.get("schema") not in SCHEMAS:
        raise ValueError(f"bad schema tag: {rec.get('schema')!r} not in {SCHEMAS}")
    kind = rec.get("kind")
    if kind not in REQUIRED_FIELDS:
        raise ValueError(f"unknown record kind: {kind!r}")
    for base in ("ts", "rank"):
        if base not in rec:
            raise ValueError(f"{kind} record missing base field {base!r}")
    missing = [f for f in REQUIRED_FIELDS[kind] if f not in rec]
    if missing:
        raise ValueError(f"{kind} record missing fields: {missing}")


def read_jsonl(path: str) -> list:
    """Parse + validate a metrics file; returns the record list."""
    out = []
    with open(path) as fh:
        for ln, line in enumerate(fh, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                raise ValueError(f"{path}:{ln}: not JSON: {e}") from e
            validate_record(rec)
            out.append(rec)
    return out
