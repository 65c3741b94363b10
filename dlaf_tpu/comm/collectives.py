"""Collective primitives over the 2D grid, used inside ``shard_map``.

TPU-native replacement for the reference's async tile collectives
(reference: include/dlaf/communication/kernels/{all_reduce,broadcast,reduce,
p2p,p2p_allsum}.h and broadcast_panel.h).  Correspondence:

  schedule_bcast_send/recv      -> ``bcast`` (psum of root-masked data)
  scheduleAllReduce             -> ``lax.psum`` over a mesh axis
  scheduleSend/Recv ring        -> ``shift`` (lax.ppermute)
  broadcast_panel col->row      -> ``transpose_panel`` (the diagonal-crossing
                                   trick of broadcast_panel.h:30-189 becomes a
                                   masked gather + psum over the row axis)

Communicator pipelines/clones and MPI message ordering (communicator
pipelines, §2.4 of SURVEY.md) have no analogue: XLA orders collectives by
data flow and schedules independent ones concurrently.

Implementation tiers
--------------------
Every redistribution here has exactly ONE contributor per output slot, so
two implementations are interchangeable:

* ``'psum'`` — the historical tier: ``lax.psum`` of root-masked / zero-padded
  contributions.  Robust, but pays full all-reduce wire cost
  (~``2(P-1)/P * payload`` on a ring) plus an add-tree over zeros.
* ``'v2'`` — one-contributor redistributions as permutes: a doubling
  ``lax.ppermute`` forward chain (``ceil(log2 P)`` rounds) carries the
  payload from its unique source to every destination with no reduction at
  all; out-of-range slots are zero-filled locally.  Semantically a true
  broadcast (reference broadcast_panel.h / kernels/broadcast.h), modeled at
  ``(P-1)/P * payload`` wire bytes per device — half the reduce tier.
* ``'pallas'`` — the same one-contributor semantics as a neighbor ring in
  Pallas kernels (``ops/pallas_panel_exchange``): on TPU one fused
  ``pltpu.make_async_remote_copy`` kernel whose DMA hops can drain under
  the trailing MXU work (collectives issued inside an
  :func:`overlap_window` report their modeled wire bytes as *overlapped*);
  on CPU/interpret backends the identical ring schedule with ppermute
  transport and the interpret-mode merge kernel.  Bit-identical to v2 by
  construction (pure copies/selects), same ``(P-1)/P`` modeled wire cost.

Selection: ``tune.TuneParameters.collectives_impl``
(``'psum' | 'v2' | 'pallas' | 'auto'``, env ``DLAF_TPU_COLLECTIVES_IMPL``;
``'auto'`` = v2 on accelerator backends, psum on CPU until measured —
never pallas until a live TPU A/B lands).  The knob is read at TRACE time
— compiled-kernel caches must include :func:`collectives_trace_key` or
flipping the knob would silently reuse stale executables.

All functions assume they run inside ``shard_map`` over a mesh with axes
``('r', 'c')`` (see grid.ROW_AXIS/COL_AXIS).

Every collective reports its payload to ``obs.comms`` at trace time (the
``_rec`` calls) — one ``is None`` test when accounting is off, and never a
change to the traced computation (tests/test_obs.py asserts the lowered
HLO is byte-identical either way).  The v2 primitives report distinct kinds
(``bcast_v2``, ``transpose_panel_v2``) so the modeled wire-byte column in
the metrics distinguishes reduce-tier from permute-tier traffic.

Degenerate cases short-circuit to identity: a size-1 axis (single-row or
single-column grid) and ``shift`` by a multiple of the axis size emit no
collective ops at all (and report nothing — there is no traffic).
"""
from __future__ import annotations

import contextlib
import contextvars

import jax
import jax.numpy as jnp
from jax import lax

from dlaf_tpu.comm.grid import COL_AXIS, ROW_AXIS
from dlaf_tpu.obs.comms import record as _rec


def my_rank():
    """(row, col) coords of this device in the grid (traced scalars)."""
    return lax.axis_index(ROW_AXIS), lax.axis_index(COL_AXIS)


def axis_size(axis: str) -> int:
    """Static size of a mesh axis from inside shard_map.  ``lax.axis_size``
    only exists on newer jax; ``psum`` of a literal folds to a Python int on
    every version."""
    fn = getattr(lax, "axis_size", None)
    return fn(axis) if fn is not None else lax.psum(1, axis)


def grid_shape():
    return axis_size(ROW_AXIS), axis_size(COL_AXIS)


# ------------------------------------------------------------ impl tiers


def _impl() -> str:
    """Resolve ``tune.collectives_impl`` to the active tier
    ('psum'|'v2'|'pallas').

    ``'auto'`` consults the plan autotuner: a loaded sweep profile's
    measured winner when one exists, else the analytic rule (v2 on
    accelerator backends, psum on CPU where the masked all-reduce
    benchmarks at parity).  It never resolves to pallas — that tier is
    explicit-opt-in until a live TPU A/B (scripts/tpu_day.sh stage 5f)
    justifies promotion.  Read lazily so comm does not import tune at
    module load."""
    from dlaf_tpu import tune

    impl = tune.get_tune_parameters().collectives_impl
    if impl == "auto":
        from dlaf_tpu.plan import autotune

        return autotune.collectives_tier(jax.default_backend())
    tune.validate_collectives_impl(impl)  # ConfigurationError on typos
    return impl


def collectives_trace_key() -> str:
    """The resolved implementation tier, for compiled-kernel cache keys.

    Same rule as _spmd.trsm_trace_key: a knob outside the key is a dead
    knob — flipping ``collectives_impl`` between calls must retrace, not
    silently reuse an executable traced under the other tier."""
    return _impl()


# ------------------------------------------------------------ overlap scope

_overlap_depth = contextvars.ContextVar(
    "dlaf_tpu_collectives_overlap_depth", default=0
)


@contextlib.contextmanager
def overlap_window():
    """Mark the enclosed collectives as schedulable under trailing compute.

    Algorithms enter this around panel exchanges whose results the next
    bulk phase does NOT immediately need (the lookahead dataflow pattern).
    It never changes what is computed — only how ``obs.comms`` classifies
    the modeled wire bytes: the pallas tier's DMA hops can drain while the
    MXU runs, so its records inside a window count as *overlapped*; the
    psum/v2 tiers lower to XLA collectives that barrier regardless, so
    their bytes stay *exposed* even here.  That split is the modeled win
    ``scripts/report_metrics.py`` prints and the tpu_day A/B measures.

    The nesting depth is a ``contextvars.ContextVar`` — per-thread and
    per-async-task — because windows are entered at trace time and
    ``dlaf_tpu.serve`` traces on an async pool: a window open on one
    worker must not classify a concurrent trace's records as overlapped."""
    token = _overlap_depth.set(_overlap_depth.get() + 1)
    try:
        yield
    finally:
        _overlap_depth.reset(token)


def _rec_tier(kind: str, x, axis: str) -> None:
    """Record a pallas-tier collective, overlapped iff inside a window."""
    _rec(kind, x, axis, overlapped=_overlap_depth.get() > 0)


def _forward_chain(y, have, axis: str):
    """Doubling ``ppermute`` forward chain along ``axis``.

    ``have`` is a bool array whose shape is a leading prefix of ``y``'s
    (scalar for a whole-payload broadcast, per-slot vector for a panel
    exchange).  Invariant per slot: ``have == True`` implies ``y`` holds
    the true contributed value — a rank only takes an incoming value for a
    slot it does not yet have, and only from a rank that has it, so
    garbage is never marked valid.  After ``ceil(log2 P)`` rounds every
    rank's ``have`` is the OR over the axis and every reachable slot is
    filled; no reduction is ever issued."""
    n = axis_size(axis)
    s = 1
    while s < n:
        perm = [(i, (i + s) % n) for i in range(n)]
        y_in = lax.ppermute(y, axis, perm)
        h_in = lax.ppermute(have, axis, perm)
        take = jnp.logical_and(jnp.logical_not(have), h_in)
        take = take.reshape(take.shape + (1,) * (y.ndim - take.ndim))
        y = jnp.where(take, y_in, y)
        have = jnp.logical_or(have, h_in)
        s *= 2
    return y, have


# ------------------------------------------------------------ primitives


def bcast(x, root, axis: str, *, consumed: bool = False):
    """Broadcast ``x`` from the device with ``axis_index(axis) == root`` to
    all devices along ``axis``.  ``root`` may be traced.

    psum tier: a psum of root-masked data — O(log P) on ICI, no explicit
    send/recv pairing (replaces schedule_bcast_send/recv).  v2 tier: a
    doubling ppermute chain seeded at the (traced) root — a true one-
    contributor broadcast with no add-tree.  pallas tier: the neighbor-ring
    DMA kernel seeded the same way (ops/pallas_panel_exchange).  Size-1
    axes are the identity.

    ``consumed=True`` marks the payload as consumed in-kernel by the fused
    trailing-update tier (ops.pallas_trailing_update): under the pallas
    tier the record kind becomes ``bcast_fused`` — its ring hops drain
    under the update's MXU work, so ``obs.comms`` classifies the bytes as
    overlapped unconditionally.  Only the pallas transport earns the tag
    (the psum/v2 tiers lower to XLA collectives that barrier regardless);
    the traced computation is identical either way."""
    if axis_size(axis) == 1:
        return x
    me = lax.axis_index(axis)
    impl = _impl()
    if impl == "pallas":
        from dlaf_tpu.ops import pallas_panel_exchange as ppe

        _rec_tier("bcast_fused" if consumed else "bcast_pallas", x, axis)
        return ppe.ring_bcast(x, me == root, axis)
    if impl == "v2":
        _rec("bcast_v2", x, axis)
        y, _ = _forward_chain(x, me == root, axis)
        return y
    _rec("bcast", x, axis)
    zero = jnp.zeros_like(x)
    return lax.psum(jnp.where(me == root, x, zero), axis)


def bcast2d(x, root_r, root_c):
    """Broadcast from grid rank (root_r, root_c) to the full grid."""
    return bcast(bcast(x, root_c, COL_AXIS), root_r, ROW_AXIS)


def psum_axis(x, axis: str):
    """True all-reduce along ``axis`` (multi-contributor sums stay psum in
    every tier).  Size-1 axes are the identity."""
    if axis_size(axis) == 1:
        return x
    _rec("psum", x, axis)
    return lax.psum(x, axis)


def shift(x, axis: str, offset: int = 1):
    """Ring shift along a grid axis: device i receives the value from device
    ``(i - offset) % P`` (replaces p2p send/recv chains; lax.ppermute rides
    ICI neighbor links).  A zero net offset (offset % P == 0, including any
    offset on a size-1 axis) is the identity and emits nothing."""
    n = axis_size(axis)
    if offset % n == 0:
        return x
    _rec("shift", x, axis)
    perm = [(i, (i + offset) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm)


def all_gather_axis(x, axis: str):
    """Gather local blocks along an axis; result has a new leading axis of
    size P ordered by axis index.  Size-1 axes just add the leading axis."""
    if axis_size(axis) == 1:
        return x[None]
    _rec("all_gather", x, axis)
    return lax.all_gather(x, axis)


def select_local_tiles(panel_global, local_count: int, grid_dim, my_coord, src=0):
    """From a globally-indexed tile stack ``panel_global[nt_pad, ...]`` take
    this rank's block-cyclic subset ``[local_count, ...]``
    (tile ``lt`` -> global ``lt*P + (my - src) % P``)."""
    idx = jnp.arange(local_count) * grid_dim + (my_coord - src) % grid_dim
    n = panel_global.shape[0]
    valid = (idx < n).reshape((local_count,) + (1,) * (panel_global.ndim - 1))
    taken = jnp.take(panel_global, jnp.clip(idx, 0, n - 1), axis=0)
    return jnp.where(valid, taken, jnp.zeros_like(taken))


def _panel_exchange(taken, have, axis: str):
    """Shared tail of the four ``transpose_panel*`` variants.

    Each output slot has at most one contributing rank along ``axis`` —
    marked per slot in ``have[slots]``, candidate value in
    ``taken[slots, ...]`` (garbage where ``have`` is False).  Slots with no
    contributor anywhere on the axis come out zero in both tiers (matching
    the historical psum-of-masked-zeros semantics)."""
    hmask = have.reshape(have.shape + (1,) * (taken.ndim - have.ndim))
    if axis_size(axis) == 1:
        return jnp.where(hmask, taken, jnp.zeros_like(taken))
    impl = _impl()
    if impl == "pallas":
        from dlaf_tpu.ops import pallas_panel_exchange as ppe

        _rec_tier("transpose_panel_pallas", taken, axis)
        y, have_all = ppe.ring_exchange(taken, have, axis)
        amask = have_all.reshape(have_all.shape + (1,) * (y.ndim - have_all.ndim))
        return jnp.where(amask, y, jnp.zeros_like(y))
    if impl == "v2":
        _rec("transpose_panel_v2", taken, axis)
        y, have_all = _forward_chain(taken, have, axis)
        amask = have_all.reshape(have_all.shape + (1,) * (y.ndim - have_all.ndim))
        return jnp.where(amask, y, jnp.zeros_like(y))
    contrib = jnp.where(hmask, taken, jnp.zeros_like(taken))
    _rec("transpose_panel", contrib, axis)
    return lax.psum(contrib, axis)


def transpose_panel_parts(cp, nr_row_tiles, ltc: int):
    """The (taken, have) pair of :func:`transpose_panel` WITHOUT the
    exchange: per output slot, this rank's candidate tile and whether this
    rank is the slot's unique contributor along the row axis.  The fused
    trailing-update consumer (ops.pallas_trailing_update) feeds these to
    its own ring transport so the redistribution geometry — the diagonal-
    crossing slot map of broadcast_panel.h — is stated exactly once."""
    myr, myc = my_rank()
    pr, pc = grid_shape()
    ltr = cp.shape[0]
    jv = jnp.arange(ltc) * pc + myc  # global tile index wanted at each slot
    src_slot = jnp.clip(jv // pr, 0, ltr - 1)
    have = (jv % pr == myr) & (jv < nr_row_tiles)
    taken = jnp.take(cp, src_slot, axis=0)
    return taken, have


def transpose_panel(cp, nr_row_tiles, ltc: int):
    """Column panel -> row panel redistribution.

    ``cp[ltr, mb, nb]`` holds (after a col-axis broadcast) the panel tiles for
    this rank-row's global row-tiles ``i = li*Pr + myr``.  Returns
    ``rp[ltc, mb, nb]`` with ``rp[lj] = panel tile of global index
    j = lj*Pc + myc`` (zero where ``j >= nr_row_tiles``), i.e. the panel
    re-distributed along each rank's *column* ownership — the TPU analogue of
    the transposed-panel broadcast (reference broadcast_panel.h:116-189).

    Cost: one psum over the row axis of ``ltc`` tiles (psum tier), or a
    log2(Pr)-round ppermute chain with no reduction (v2 tier).
    """
    taken, have = transpose_panel_parts(cp, nr_row_tiles, ltc)
    return _panel_exchange(taken, have, ROW_AXIS)


def transpose_panel_windowed_parts(cp, jv, rs, nr_row_tiles):
    """The (taken, have) pair of :func:`transpose_panel_windowed` WITHOUT
    the exchange — the windowed sibling of :func:`transpose_panel_parts`,
    consumed by the fused trailing-update transports (gen_to_std her2k,
    TRTRI, red2band) so the bucketed slot map is stated exactly once."""
    myr, _ = my_rank()
    pr, _ = grid_shape()
    L = cp.shape[0]
    src_slot = jv // pr - rs
    have = (jv % pr == myr) & (jv < nr_row_tiles) & (src_slot >= 0) & (src_slot < L)
    taken = jnp.take(cp, jnp.clip(src_slot, 0, L - 1), axis=0)
    return taken, have


def transpose_panel_windowed(cp, jv, rs, nr_row_tiles):
    """Windowed variant of :func:`transpose_panel` for bucketed trailing
    updates: ``cp[L, ...]`` holds panel tiles for this rank's local row slots
    ``rs .. rs+L-1`` (global tiles ``(rs+i)*Pr + myr``); returns
    ``rp[C, ...]`` with ``rp[c] = panel tile of global index jv[c]`` (zero
    where out of range).  ``rs`` may differ per rank row (each contributor
    uses its own window offset)."""
    taken, have = transpose_panel_windowed_parts(cp, jv, rs, nr_row_tiles)
    return _panel_exchange(taken, have, ROW_AXIS)


def transpose_panel_rows_windowed_parts(rp, iv, cs, nr_col_tiles):
    """The (taken, have) pair of :func:`transpose_panel_rows_windowed`
    WITHOUT the exchange (column-axis mirror of
    :func:`transpose_panel_windowed_parts`)."""
    _, myc = my_rank()
    _, pc = grid_shape()
    C = rp.shape[0]
    src_slot = iv // pc - cs
    have = (iv % pc == myc) & (iv < nr_col_tiles) & (src_slot >= 0) & (src_slot < C)
    taken = jnp.take(rp, jnp.clip(src_slot, 0, C - 1), axis=0)
    return taken, have


def transpose_panel_rows_windowed(rp, iv, cs, nr_col_tiles):
    """Windowed mirror of :func:`transpose_panel_windowed` (row panel ->
    column panel): ``rp[C, ...]`` holds panel tiles for this rank's local
    col slots ``cs .. cs+C-1`` (global tiles ``(cs+j)*Pc + myc``); returns
    ``cp[W, ...]`` with ``cp[w] = panel tile of global index iv[w]`` (zero
    where out of range).  ``cs`` may differ per rank column (each
    contributor uses its own window offset); pass ``cs=0`` with a full
    ``C=ltc`` panel for the unwindowed-source case."""
    taken, have = transpose_panel_rows_windowed_parts(rp, iv, cs, nr_col_tiles)
    return _panel_exchange(taken, have, COL_AXIS)


def transpose_panel_rows(rp, nr_col_tiles, ltr: int):
    """Row panel -> column panel redistribution (inverse of
    :func:`transpose_panel`).

    ``rp[ltc, ...]`` holds (after a row-axis broadcast) panel tiles indexed by
    this rank-column's global col-tiles ``j = lj*Pc + myc``.  Returns
    ``cp[ltr, ...]`` with ``cp[li] = panel tile of global index
    i = li*Pr + myr`` (zero where ``i >= nr_col_tiles``).  Cost: one psum over
    the col axis (psum tier) or a log2(Pc)-round ppermute chain (v2 tier)."""
    myr, myc = my_rank()
    pr, pc = grid_shape()
    ltc = rp.shape[0]
    iv = jnp.arange(ltr) * pr + myr
    src_slot = jnp.clip(iv // pc, 0, ltc - 1)
    have = (iv % pc == myc) & (iv < nr_col_tiles)
    taken = jnp.take(rp, src_slot, axis=0)
    return _panel_exchange(taken, have, COL_AXIS)


def spmd(grid, fn, static_argnums=(), donate_argnums=(), out_specs=None, name="spmd"):
    """jit(shard_map(fn)) over the grid mesh with stacked-layout specs.

    ``fn`` receives each array argument as the device-local block with the
    two leading (grid) axes of size 1 — use :func:`local` / :func:`relocal`
    to strip/restore them.

    ``out_specs`` overrides the output partitioning (default: the stacked
    ``P('r', 'c')`` layout for every output).  Kernels that return
    auxiliary rank-replicated scalars next to the matrix — e.g. the
    Cholesky ``info`` code — pass ``(P('r', 'c'), P())``; every rank must
    compute the identical value for a ``P()`` output.

    ``name``: the program is ``jit_<name>`` (``plan.jit``); the plan
    cache's builders pass their op.
    """
    P = jax.sharding.PartitionSpec
    spec = P(ROW_AXIS, COL_AXIS)
    sm = jax.shard_map(
        fn, mesh=grid.mesh, in_specs=spec,
        out_specs=spec if out_specs is None else out_specs,
        check_vma=False,
    )
    from dlaf_tpu.plan import core as _plan

    return _plan.jit(name, sm, static_argnums=static_argnums, donate_argnums=donate_argnums)


def local(x):
    """Strip the two size-1 leading grid axes of a shard_map-local block."""
    return x.reshape(x.shape[2:])


def relocal(x):
    """Restore the two size-1 leading grid axes for shard_map output."""
    return x.reshape((1, 1) + x.shape)
