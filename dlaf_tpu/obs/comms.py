"""Trace-time communication accounting for ``comm.collectives``.

The reference counts MPI traffic in its communicator layer; on TPU the
collectives are compiled into the executable, so the accounting hooks in at
the only moment Python sees them: **trace time**.  Every public collective
in ``comm.collectives`` calls :func:`record` with its payload operand right
before issuing the ``lax`` collective.  While accounting is off (the
default) that call is a single ``is None`` test — no allocation, no HLO
difference, nothing.

What a record means
-------------------
``record`` fires once per *trace* of each collective call site, so counts
are per-compilation, per logical call site in the traced program:

* a collective inside ``lax.fori_loop``'s body counts ONCE even though the
  device executes it every iteration (XLA traces the body once) — multiply
  by the trip count yourself when a loop dominates;
* SPMD means one trace covers all devices: counts and bytes are
  **per-device payload** figures (every device moves that much), with the
  participant count available in the ``axis_size`` column for aggregate
  math (e.g. ring all-gather moves ``(P-1)/P * P * nbytes`` on the wire).

Byte volumes are analytic: ``prod(shape) * dtype.itemsize`` of the operand
handed to the ``lax`` collective — the logical payload, not a model of the
algorithm XLA picks (recursive-halving psum etc. move different wire bytes;
the logical volume is the stable, comparable figure).

Modeled wire bytes
------------------
Next to the logical payload, each record carries an analytic ring-model
wire cost per device (:func:`wire_model`), keyed on the collective *kind*:
reduce-tier redistributions (``psum``/``bcast``/``transpose_panel``) cost a
full all-reduce ``2(P-1)/P * payload``; the one-contributor tiers
(``*_v2`` doubling chain, ``*_pallas`` neighbor ring) deliver each payload
byte across ``P-1`` links once, ``(P-1)/P * payload`` per device — the
"modeled bytes saved" figure ``scripts/report_metrics.py`` prints is the
difference.  It is a model of the semantic redistribution on a ring,
deliberately NOT a count of the instructions XLA emits (which vary by
backend and version); like the payload column it is exact, comparable, and
hardware-free.

Overlapped wire bytes
---------------------
The fourth accumulator column splits the modeled wire bytes into exposed
vs *overlapped*: a record with ``overlapped=True`` (the pallas DMA tier
issuing inside a ``collectives.overlap_window`` — an exchange whose hops
can drain under trailing compute) contributes its modeled bytes to both
the wire total and the overlapped column.  ``exposed = wire - overlapped``
is the latency a panel step actually waits on; the psum/v2 tiers are hard
XLA barriers, never overlapped, which is exactly the modeled difference
the three-way A/B in ``scripts/collectives_ab.py`` reports.

Kinds ending ``_fused`` (the trailing-update consumer of
``ops.pallas_trailing_update``, which reads panel operands straight out of
the ring-DMA landing slots) are *definitionally* overlapped: every hop's
bytes are consumed by the in-kernel update while the next hop's DMA is in
flight, window or no window, so :func:`record` forces their overlap flag.
Their wire cost is the same one-contributor ``(P-1)/P`` ring as the
v2/pallas tiers.
"""
from __future__ import annotations

import math

import numpy as np
from jax import lax

# (kind, dtype, axis, axis_size) ->
#     [call_count, payload_bytes_total, modeled_wire_bytes_total,
#      overlapped_wire_bytes_total]
_acc: dict | None = None


def start() -> None:
    """Begin accounting; resets any previous accumulation."""
    global _acc
    _acc = {}


def stop() -> dict:
    """Stop accounting and return {(kind, dtype, axis, axis_size):
    [count, bytes, modeled_wire_bytes, overlapped_wire_bytes]} in
    first-seen order."""
    global _acc
    acc, _acc = _acc or {}, None
    return acc


def snapshot() -> dict:
    """Copy of the running accumulation without stopping it."""
    return {k: list(v) for k, v in (_acc or {}).items()}


def wire_model(kind: str, axis_size: int, nbytes: int) -> int:
    """Analytic per-device ring wire bytes for one collective of ``kind``
    with logical payload ``nbytes`` over ``axis_size`` participants.

    Unknown axis contexts (axis_size 0) model as free — there is no ring to
    cost.  Kinds: reduce-tier redistributions and true sums are ring
    all-reduces; the one-contributor tiers (v2 doubling chain, pallas
    neighbor ring) deliver each byte over P-1 links once; ``shift`` is one
    neighbor hop; ``all_gather`` materializes the other P-1 blocks."""
    p = int(axis_size)
    if p <= 1:
        return 0
    if kind.endswith("_v2") or kind.endswith("_pallas") \
            or kind.endswith("_fused"):
        return round((p - 1) * nbytes / p)
    if kind == "shift":
        return nbytes
    if kind == "all_gather":
        return (p - 1) * nbytes
    # psum-lowered: psum / bcast / transpose_panel (ring all-reduce)
    return round(2 * (p - 1) * nbytes / p)


def record(kind: str, x, axis: str | None = None, overlapped: bool = False) -> None:
    """Account one collective call site: ``x`` is the operand about to be
    handed to the ``lax`` collective, ``axis`` its mesh axis (None for 2D /
    axis-free ops).  ``overlapped=True`` classifies the modeled wire bytes
    as drainable under trailing compute (pallas DMA tier inside a
    ``collectives.overlap_window``); kinds ending ``_fused`` are forced
    overlapped — the trailing-update consumer drains hops under its own
    MXU work by construction.  Runs at trace time only; no-op unless
    :func:`start`."""
    if _acc is None:
        return
    overlapped = overlapped or kind.endswith("_fused")
    try:
        size = lax.psum(1, axis) if axis is not None else 0
    except (NameError, KeyError, ValueError):  # outside an axis context
        size = 0
    nbytes = math.prod(x.shape) * np.dtype(x.dtype).itemsize
    key = (kind, np.dtype(x.dtype).name, axis or "", int(size))
    ent = _acc.setdefault(key, [0, 0, 0, 0])
    while len(ent) < 4:  # legacy accumulations started before this column
        ent.append(0)
    wire = wire_model(kind, int(size), nbytes)
    ent[0] += 1
    ent[1] += nbytes
    ent[2] += wire
    ent[3] += wire if overlapped else 0


def as_records(acc: dict) -> list:
    """Render an accumulation dict into JSON-ready row dicts (one per
    (kind, dtype, axis, axis_size) bucket).  Accepts legacy two- and
    three-element values (pre-wire-model / pre-overlap accumulations),
    modeling missing wire bytes on the fly and treating missing overlap as
    fully exposed."""
    rows = []
    for (kind, dtype, axis, size), val in acc.items():
        count, nbytes = val[0], val[1]
        wire = val[2] if len(val) > 2 else wire_model(kind, size, nbytes)
        rows.append(
            {
                "collective": kind,
                "dtype": dtype,
                "axis": axis,
                "axis_size": size,
                "messages": count,
                "bytes": nbytes,
                "modeled_wire_bytes": wire,
                "overlapped_wire_bytes": val[3] if len(val) > 3 else 0,
            }
        )
    return rows
