"""dlaf_tpu.obs — unified observability: tracing, comms accounting, metrics.

The reference exposes pipeline structure through pika/APEX instrumentation
(SURVEY §5 tracing row); this package is the TPU-native analogue, built from
three independent, individually opt-in pieces:

  ``obs.trace``    named phases — ``jax.named_scope`` inside jitted kernel
                   bodies (visible in compiled-HLO op metadata and profiler
                   timelines) plus host-level ``TraceAnnotation`` phases with
                   an optional phase log for tests; every program compile or
                   load is charged to the open phase (:func:`program_loads`)
                   and spans it on the profiler timeline.
  ``obs.comms``    trace-time accounting of every collective in
                   ``comm.collectives`` — message counts and analytic byte
                   volumes per (kind, dtype, axis) without touching the HLO.
  ``obs.metrics``  schema-versioned JSONL run records: run metadata, tune
                   config, stage wall-times, comms volumes, compile
                   durations, persistent-cache hits — rank-aware with a
                   rank-0 merge on multi-process worlds.
  ``obs.spans``    request-scoped span tracing — contextvar-propagated
                   trace/span/parent ids over host orchestration code
                   (gateway admission through driver phases), emitted as
                   ``span`` records on the metrics stream.
  ``obs.flight``   crash flight recorder — bounded in-memory ring of the
                   last N span/serve/health events (live even with JSONL
                   off) dumped atomically on deadline/watchdog/dispatch
                   failures, plus a device-memory watermark sampler.
  ``obs.telemetry``  live fleet metrics — low-cardinality counter/gauge/
                   histogram registry (env-gated, no-op when off), the
                   dual-window SLO burn-rate monitor, and the
                   service-time harvester that rolls completed-batch
                   timings into a loadable ``plan`` profile.
  ``obs.export``   ``python -m dlaf_tpu.obs.export`` — merged multi-rank
                   span records to Chrome-trace/Perfetto JSON.

Everything is OFF by default and the off path is free: ``comms.record`` and
``metrics.emit`` return immediately on ``None`` module globals, ``spans.span``
returns a shared no-op after one flag test, and the in-kernel ``named_scope``
names only annotate op metadata (they change no computation — asserted by
tests/test_obs.py HLO-equality test).
"""
from __future__ import annotations

import contextlib

from dlaf_tpu.common import stagetimer as _st
from dlaf_tpu.obs import comms, flight, metrics, spans, telemetry, trace
from dlaf_tpu.obs.trace import phase, program_loads, scope

__all__ = ["comms", "flight", "metrics", "spans", "telemetry", "trace",
           "phase", "program_loads", "scope", "stage"]


@contextlib.contextmanager
def stage(name: str):
    """Combined pipeline-stage marker: stagetimer wall-clock bucket (when
    ``--stage-times`` collection is on) + host trace phase (TraceAnnotation
    on profiler timelines, phase-log entry when a log is active).  The
    everything-off path enters two no-op context managers and nothing else."""
    with _st.stage(name), trace.phase(name):
        yield
