"""Pallas TPU distributed panel exchange: ring DMA with compute overlap.

The third collectives tier (``tune.collectives_impl='pallas'``).  The psum
and v2 tiers both lower to XLA collectives — hard barriers between the
factor, exchange, and trailing-update phases of every panel step.  This
module moves the one-contributor panel redistributions
(``comm.collectives``: ``bcast`` and the ``transpose_panel*`` family) into
Pallas kernels built on ``pltpu.make_async_remote_copy`` so the factored
panel streams over ICI neighbor links on the DMA engines **while** the
previous iteration's trailing GEMM still owns the MXU — the DLA-Future
lookahead/dataflow model (PAPER.md L2/L6) done with async DMA instead of a
task runtime (the pattern of SNIPPETS.md [1]/[3]).

Schedule
--------
Everything here is one ring: ``P-1`` unconditional neighbor hops along the
mesh axis.  Each rank carries a ``(payload, have)`` pair — ``have[slot]``
marks the slots whose payload bytes this rank has contributed or received.
Per hop every rank sends its current pair one step right and merges the
incoming pair with pure copies/selects::

    take = ~have & have_in
    y    = where(take, y_in, y)        # contributor bytes, verbatim
    have |= have_in

After ``P-1`` hops every rank holds the union of all contributions.  Every
slot has at most one contributor, so the merge never mixes values — the
result is BIT-identical to the v2 doubling chain (and to the psum tier's
masked all-reduce), which is what lets ``tests/test_collectives_pallas.py``
assert exact equality rather than tolerances.

Why a ring and not the v2 doubling chain: doubling needs hop distances
1, 2, 4, ... (non-neighbor links, routed on real ICI), while the ring uses
only nearest neighbors — exactly what ``make_async_remote_copy`` streams
fastest — and its per-hop data dependence is one deterministic neighbor,
which is what makes the double-buffered overlap safe (see below).

Execution paths
---------------
``ring_exchange`` picks per backend at trace time:

* **TPU**: one fused ``pallas_call`` (``_dma_ring_kernel``) running all
  ``P-1`` hops with double-buffered VMEM landing slots, per-slot DMA
  send/recv semaphores, and per-slot capacity (ack) semaphores for
  backpressure.  The ring is unidirectional: a rank's landing slots are
  written by its *upstream* neighbor, while its own sends gate only the
  downstream side — ordering propagates only the long way around the
  ring, so without an explicit ack an upstream rank could run up to
  ``P-1`` hops ahead and its hop-``s+2`` copy could overwrite landing
  slot ``s%2`` before a skewed rank merged hop ``s``.  The protocol
  (``_ring_hops``): after merging hop ``s`` the receiver signals the
  writer's capacity semaphore for that slot, and the writer waits on it
  before reusing the slot at hop ``s+2``; the first two hops need no
  wait, and an ack is only sent when the writer will actually reuse the
  slot, so every semaphore drains to zero at kernel exit.  Deadlock
  freedom: every rank starts its hop-``s`` send before waiting on its
  own recv, and every wait is on an event strictly earlier in the global
  hop order (recv waits on the upstream hop-``s`` send, capacity waits
  on the downstream hop-``s-2`` merge), so a delayed rank stalls its
  neighbors at a semaphore — never a cycle.
* **CPU / interpret (the tier-1 mesh)**: the identical ring schedule with
  the hop transport as ``lax.ppermute`` and the per-hop merge as a Pallas
  kernel in interpret mode — the jax-0.4.37 interpreter only discharges
  remote DMA over a single named mesh axis, so on the 2D ('r','c') grid
  the kernel under test is the merge, and the remote-copy kernel itself is
  exercised by the single-axis interpret tests in
  ``tests/test_collectives_pallas.py`` (entry barrier and capacity acks
  off there: the interpreter executes ranks in a deterministic sequence,
  so there is no rank to race and no remote signal to discharge).
  Interpret-mode constraint: Pallas
  outputs must be numeric (bool outputs crash the 0.4.37 interpreter), so
  ``have`` masks travel as int32 and complex payloads travel as
  bit-preserving float pair views (``.view()`` roundtrips exactly).

``fused_factor_bcast`` composes the existing ``ops/pallas_potrf`` and
``ops/pallas_panel_trsm`` kernel bodies with the DMA ring in ONE
``pallas_call``: the diagonal tile factors and the panel solve runs with
everything VMEM-resident, and the factored panel starts streaming to the
ring the moment it exists — no HBM round-trip, no XLA barrier between
factor and exchange.  TPU-only (gated by ``fusion_supported``); the CPU
mesh keeps the unfused path, which is the same math.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlaf_tpu.ops import pallas_panel_trsm as _ptrsm
from dlaf_tpu.ops import pallas_potrf as _ppotrf


def _axis_size(axis: str) -> int:
    """Static mesh-axis size from inside shard_map (psum of a literal folds
    to a Python int on every jax version; see comm.collectives.axis_size)."""
    fn = getattr(lax, "axis_size", None)
    return int(fn(axis)) if fn is not None else int(lax.psum(1, axis))


def _use_dma() -> bool:
    """The compiled remote-DMA kernel runs only on real TPU backends; every
    other backend takes the ppermute-transport ring with the interpret-mode
    merge kernel (same schedule, same bits)."""
    return jax.default_backend() == "tpu"


# ----------------------------------------------------------- collective ids
#
# Mosaic kernels with the same ``collective_id`` share barrier-semaphore
# state and must NEVER be live on a device concurrently.  This tier exists
# precisely so its DMA kernels can drain while later work (including other
# ``has_side_effects`` kernels not data-dependent on them) runs, so any two
# kernels the scheduler could overlap need distinct ids.  Allocation: one
# id per (entry-point kind, mesh axis) call-site class —
#
#   1     ``fused_factor_bcast`` (lookahead panel factor+send)
#   2, 3  ``ring_bcast`` along 'r' / 'c'
#   4, 5  ``ring_exchange`` (the ``transpose_panel*`` family) along 'r'/'c'
#   8+    any other (kind, axis) pair, allocated on first use
#
# Residual invariant (documented, not machine-checkable here): two kernels
# of the SAME class must be ordered by data dependence.  Every call site in
# ``comm.collectives`` satisfies this today — each panel step's exchange
# consumes the previous step's output through the loop carry, and within a
# step the bcast -> transpose chain is data-dependent.  A caller issuing
# two genuinely independent same-class exchanges in one program must pass
# distinct ids to ``dma_ring_exchange`` explicitly.

FUSED_COLLECTIVE_ID = 1
_RESERVED_COLLECTIVE_IDS = {
    ("bcast", "r"): 2,
    ("bcast", "c"): 3,
    ("exchange", "r"): 4,
    ("exchange", "c"): 5,
}
_dynamic_collective_ids: dict = {}


def collective_id_for(kind: str, axis: str) -> int:
    """Stable ``collective_id`` for a (kind, axis) call-site class (table
    above).  Deterministic across ranks: reserved pairs come from the
    static table, and first-use allocation for any other pair follows the
    identical trace order on every rank of an SPMD program."""
    key = (kind, axis)
    cid = _RESERVED_COLLECTIVE_IDS.get(key)
    if cid is None:
        cid = _dynamic_collective_ids.setdefault(
            key, 8 + len(_dynamic_collective_ids)
        )
    return cid


# --------------------------------------------------------------- flattening
#
# Both kernels work on a canonical 2D layout: payload (slots, w) in a real
# dtype, have-mask (slots, 1) int32.  ``_to_wire``/``_from_wire`` map any
# (slots, ...) payload (or a scalar-have whole-payload broadcast) onto it.


#: lanes of every in-kernel have-mask: Mosaic slices and DMAs only
#: lane-aligned VMEM buffers, so a (slots, 1) mask travels as
#: (slots, HAVE_LANES) with the flag repeated in every lane
HAVE_LANES = 128


def _lanes(h):
    """(slots, 1) have-mask -> the lane-dense (slots, HAVE_LANES) kernel form."""
    return jnp.broadcast_to(h, (h.shape[0], HAVE_LANES))


def _flag(take, like):
    """Lane 0 of a lane-dense (slots, HAVE_LANES) mask, shaped to broadcast
    against ``like`` (leading axis = slots, or a single slot)."""
    return take[:, :1].reshape((take.shape[0],) + (1,) * (like.ndim - 1))


def _to_wire(y, have):
    slots = int(np.prod(have.shape)) if have.ndim else 1
    yf = y.reshape(slots, -1)
    if jnp.issubdtype(yf.dtype, jnp.complexfloating):
        # bit-preserving reinterpret: c64 -> f32 pairs, c128 -> f64 pairs
        yf = yf.view(jnp.float32 if yf.dtype == jnp.complex64 else jnp.float64)
    h = have.astype(jnp.int32).reshape(slots, 1)
    return yf, h


def _from_wire(yf, h, y_template, have_template):
    if jnp.issubdtype(y_template.dtype, jnp.complexfloating):
        yf = yf.view(y_template.dtype)
    y = yf.reshape(y_template.shape).astype(y_template.dtype)
    have = (h != 0).reshape(have_template.shape)
    return y, have


# ------------------------------------------------------------- merge kernel


def _merge_kernel(y_ref, yin_ref, h_ref, hin_ref, oy_ref, oh_ref):
    """One ring-hop merge: take incoming bytes only for slots not yet held.
    Pure select — contributor bytes pass through verbatim (bit-exactness
    across tiers depends on this kernel never doing arithmetic on payload)."""
    have = h_ref[...]
    h_in = hin_ref[...]
    take = jnp.logical_and(have == 0, h_in != 0)
    oy_ref[...] = jnp.where(take, yin_ref[...], y_ref[...])
    oh_ref[...] = have | h_in


@functools.partial(jax.jit, static_argnums=(4,))
def merge_hop(yf, y_in, h, h_in, interpret: bool = False):
    """The hop merge as a pallas_call on the canonical wire layout."""
    return pl.pallas_call(
        _merge_kernel,
        out_shape=(
            jax.ShapeDtypeStruct(yf.shape, yf.dtype),
            jax.ShapeDtypeStruct(h.shape, h.dtype),
        ),
        interpret=interpret,
    )(yf, y_in, h, h_in)


# ------------------------------------------------------- emulated transport


def _ppermute_ring(yf, h, axis: str, n: int, interpret: bool):
    """The ring schedule with lax.ppermute as the hop transport.  Used on
    every non-TPU backend: identical merge semantics to the DMA kernel, so
    the tier's numerical contract is CI-tested on the tier-1 CPU mesh."""
    perm = [(i, (i + 1) % n) for i in range(n)]
    for _ in range(n - 1):
        y_in = lax.ppermute(yf, axis, perm)
        h_in = lax.ppermute(h, axis, perm)
        yf, h = merge_hop(yf, y_in, h, h_in, interpret)
    return yf, h


# ------------------------------------------------------------ DMA transport


def _neighbor_ids(ring_axis: str, mesh_axes: tuple, offset: int):
    """device_id (and its type) of the rank ``offset`` steps along the ring.

    Single-axis meshes address by scalar logical index (the only form the
    interpreter discharges); multi-axis meshes address by the full mesh
    coordinate tuple with the ring axis advanced."""
    n = _axis_size(ring_axis)
    me = lax.axis_index(ring_axis)
    # explicit int32 operands: Mosaic lowers no int64 (jax_enable_x64)
    step = lax.rem(me + np.int32(offset + n), np.int32(n))
    if len(mesh_axes) == 1:
        return step, pltpu.DeviceIdType.LOGICAL
    coords = tuple(
        step if a == ring_axis else lax.axis_index(a) for a in mesh_axes
    )
    return coords, pltpu.DeviceIdType.MESH


def _ring_hops(
    acc_y, acc_h, land_y, land_h,
    send_y_sem, recv_y_sem, send_h_sem, recv_h_sem, cap_sem,
    *, nhops: int, dst, src, id_type, backpressure: bool,
):
    """The shared P-1-hop ring loop (both DMA kernels run exactly this).

    ``acc_y/acc_h`` are the VMEM-resident merge accumulators, ``land_y/
    land_h`` the two incoming landing slots.  Per hop s: wait (hops >= 2)
    for the downstream neighbor's capacity ack on slot ``s%2``, start the
    unconditional send of the accumulator pair into the neighbor's slot
    ``s%2``, wait for our own slot ``s%2`` from upstream, wait for the
    send (the accumulator must not be mutated under an in-flight read),
    merge, then ack the upstream writer if it will reuse the slot.

    The capacity semaphore is the backpressure that makes TWO landing
    slots safe at any ring size: without it, ordering propagates only the
    long way around the unidirectional ring, so an upstream rank could
    run up to P-1 hops ahead of a skewed rank and overwrite slot ``s%2``
    with its hop-``s+2`` copy before hop ``s`` was merged.  Wait/signal
    pairing is exact — the writer waits at hops ``2..nhops-1``, the
    receiver signals at hops ``0..nhops-3`` — so the semaphores drain to
    zero at kernel exit.  send-before-recv-wait is the deadlock ordering
    the skew test leans on; the capacity wait precedes the send and
    depends only on the downstream hop-``s-2`` merge, an event strictly
    earlier in the global hop order, so it cannot close a cycle either.

    ``backpressure=False`` is for the interpreter only (ranks execute
    sequentially; remote semaphore signals are not discharged there)."""
    for s in range(nhops):  # static: P-1 hops
        slot = np.int32(s % 2)  # int32 index: Mosaic lowers no int64
        if backpressure and s >= 2:
            # downstream neighbor must have merged our hop s-2 copy out of
            # this landing slot before we overwrite it with hop s
            pltpu.semaphore_wait(cap_sem.at[slot], 1)
        cp_y = pltpu.make_async_remote_copy(
            src_ref=acc_y,
            dst_ref=land_y.at[slot],
            send_sem=send_y_sem.at[slot],
            recv_sem=recv_y_sem.at[slot],
            device_id=dst,
            device_id_type=id_type,
        )
        cp_h = pltpu.make_async_remote_copy(
            src_ref=acc_h,
            dst_ref=land_h.at[slot],
            send_sem=send_h_sem.at[slot],
            recv_sem=recv_h_sem.at[slot],
            device_id=dst,
            device_id_type=id_type,
        )
        cp_y.start()
        cp_h.start()
        cp_y.wait_recv()
        cp_h.wait_recv()
        cp_y.wait_send()
        cp_h.wait_send()
        have = acc_h[...]
        h_in = land_h[slot]
        take = jnp.logical_and(have == 0, h_in != 0)
        acc_y[...] = jnp.where(_flag(take, acc_y), land_y[slot], acc_y[...])
        acc_h[...] = have | h_in
        if backpressure and s + 2 < nhops:
            # slot consumed: the upstream writer may reuse it at hop s+2
            pltpu.semaphore_signal(
                cap_sem.at[slot], device_id=src, device_id_type=id_type
            )


def _dma_ring_kernel(
    y_ref, h_ref, oy_ref, oh_ref, land_y, land_h,
    send_y_sem, recv_y_sem, send_h_sem, recv_h_sem, cap_sem,
    *, nhops: int, ring_axis: str, mesh_axes: tuple, sync: bool,
):
    """All P-1 ring hops in one kernel launch (see ``_ring_hops`` for the
    hop protocol).  ``oy_ref/oh_ref`` double as the merge accumulator,
    VMEM-resident for the whole kernel.  ``sync`` gates the cross-rank
    synchronization (entry barrier + capacity acks): on for the compiled
    TPU path, off under the interpreter."""
    dst, id_type = _neighbor_ids(ring_axis, mesh_axes, +1)
    src, _ = _neighbor_ids(ring_axis, mesh_axes, -1)

    oy_ref[...] = y_ref[...]
    oh_ref[...] = h_ref[...]

    if sync:
        # both neighbors must have entered the kernel (buffers + semaphores
        # live) before any remote write lands; signal each, await both
        bar = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(bar, device_id=dst, device_id_type=id_type)
        pltpu.semaphore_signal(bar, device_id=src, device_id_type=id_type)
        pltpu.semaphore_wait(bar, 2)

    _ring_hops(
        oy_ref, oh_ref, land_y, land_h,
        send_y_sem, recv_y_sem, send_h_sem, recv_h_sem, cap_sem,
        nhops=nhops, dst=dst, src=src, id_type=id_type, backpressure=sync,
    )


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def dma_ring_exchange(yf, h, ring_axis: str, mesh_axes: tuple,
                      interpret: bool = False, collective_id: int = 0):
    """The fused remote-DMA ring on the canonical wire layout.

    ``mesh_axes`` is the full ordered axis-name tuple of the enclosing
    shard_map mesh (device ids are mesh coordinates when it has more than
    one axis).  ``interpret=True`` runs the identical kernel on the
    interpreter — single-axis meshes only (the 0.4.37 discharge rule), and
    without the entry barrier or capacity acks (the interpreter executes
    ranks in a deterministic sequence; there is no rank to race).

    ``collective_id`` MUST be distinct for any two kernels that could be
    live concurrently (they share barrier-semaphore state) — callers go
    through :func:`collective_id_for` per (entry-point, axis) class; see
    the allocation table above."""
    n = _axis_size(ring_axis)
    if n == 1:
        return yf, h
    hl = _lanes(h)
    scratch = [
        pltpu.VMEM((2,) + yf.shape, yf.dtype),
        pltpu.VMEM((2,) + hl.shape, h.dtype),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.REGULAR((2,)),  # per-slot capacity acks
    ]
    kernel = functools.partial(
        _dma_ring_kernel,
        nhops=n - 1,
        ring_axis=ring_axis,
        mesh_axes=mesh_axes,
        sync=not interpret,
    )
    oy, oh = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct(yf.shape, yf.dtype),
            jax.ShapeDtypeStruct(hl.shape, h.dtype),
        ),
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            collective_id=collective_id, has_side_effects=True
        ),
    )(yf, hl)
    return oy, oh[:, :1]


# ------------------------------------------------------------- entry points


def ring_exchange(y, have, axis: str, *, mesh_axes=("r", "c"),
                  kind: str = "exchange"):
    """Forward-ring exchange of a one-contributor slotted payload.

    ``have``'s shape is a leading prefix of ``y``'s (scalar for a whole-
    payload broadcast, per-slot vector for a panel exchange); slots whose
    ``have`` is set carry this rank's contribution.  Returns ``(y, have)``
    after P-1 hops: every slot with any contributor on the axis holds that
    contributor's exact bytes everywhere, slots with none keep the local
    input (callers mask them, matching the v2 tier).  Bit-identical to
    ``comm.collectives._forward_chain``.

    ``kind`` names the call-site class for the collective-id allocation
    (``collective_id_for(kind, axis)``) — distinct classes may be live
    concurrently, same-class calls must be chained by data dependence."""
    n = _axis_size(axis)
    if n == 1:
        return y, have
    yf, h = _to_wire(y, have)
    if _use_dma():
        yf, h = dma_ring_exchange(
            yf, h, axis, tuple(mesh_axes), False, collective_id_for(kind, axis)
        )
    else:
        yf, h = _ppermute_ring(yf, h, axis, n, interpret=True)
    return _from_wire(yf, h, y, have)


def ring_bcast(x, is_root, axis: str, *, mesh_axes=("r", "c")):
    """Whole-payload broadcast on the ring: the rank with ``is_root`` set
    contributes, everyone ends with its bytes."""
    y, _ = ring_exchange(x, is_root, axis, mesh_axes=mesh_axes, kind="bcast")
    return y


# ------------------------------------------------------- fused factor+send


def fusion_supported(d, xc) -> bool:
    """The fused factor-and-send kernel covers the lookahead Cholesky panel
    case: f32 tiles, MXU/VPU-aligned tile side (the composed trsm
    kernel column-blocks by 32 and Mosaic wants lane-width multiples), and
    a panel that is a stack of square tiles."""
    return (
        np.dtype(d.dtype) == np.dtype(np.float32)  # Mosaic has no f64
        and d.ndim == 2
        and d.shape[0] == d.shape[1]
        and xc.ndim == 3
        and xc.shape[1:] == d.shape
        and d.shape[0] % 128 == 0
        and d.shape[0] <= _ptrsm.MAX_NB
    )


def _row_blocks(mask, mb: int):
    """(ltr, HAVE_LANES) per-tile mask -> (ltr*mb, mb) per-element mask
    (Mosaic has no 1-D gather; a broadcast + reshape expands it)."""
    ltr = mask.shape[0]
    m = jnp.broadcast_to(mask[:, :1].reshape(ltr, 1, 1), (ltr, mb, mb))
    return m.reshape(ltr * mb, mb)


def _fused_kernel(d_ref, xc_ref, root_ref, below_ref, lkk_ref, cp_ref,
                  u_ref, land_y, land_h, acc_h,
                  send_y_sem, recv_y_sem, send_h_sem, recv_h_sem, cap_sem,
                  *, nhops: int, ring_axis: str, mesh_axes: tuple, mb: int):
    """potrf + panel trsm + ring send, one launch, panel never leaves VMEM.

    Composes the existing kernel bodies: ``pallas_potrf._potrf_kernel``
    factors the diagonal tile in place, ``pallas_panel_trsm._kernel``
    solves the (ltr*mb, mb) row-flattened panel against it, and the ring
    send of the root column's masked panel starts immediately — trailing
    work queued behind this kernel overlaps the remaining hops."""
    # 1. diagonal factor (identical on every rank: d was diag-broadcast)
    _ppotrf._potrf_kernel(d_ref, lkk_ref)
    lkk = lkk_ref[...]

    # 2. op()-resolve L -> L^T once (real dtypes: conj is the identity),
    #    then the column-blocked panel solve with the factor VMEM-resident
    u_ref[...] = jnp.tril(lkk).T
    _ptrsm._kernel(u_ref, xc_ref, cp_ref, nb=mb)

    # 3. mask to the strictly-below-diagonal rows and ring-broadcast the
    #    root column's panel (same merge contract as _dma_ring_kernel)
    me = lax.axis_index(ring_axis)
    root = root_ref[0, 0]
    is_root = (me == root).astype(jnp.int32)
    keep = _row_blocks(below_ref[...], mb) * is_root  # below: gi > k
    cp_ref[...] = jnp.where(keep != 0, cp_ref[...], jnp.zeros_like(cp_ref))
    acc_h[...] = jnp.full(acc_h.shape, is_root)

    dst, id_type = _neighbor_ids(ring_axis, mesh_axes, +1)
    src, _ = _neighbor_ids(ring_axis, mesh_axes, -1)
    bar = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(bar, device_id=dst, device_id_type=id_type)
    pltpu.semaphore_signal(bar, device_id=src, device_id_type=id_type)
    pltpu.semaphore_wait(bar, 2)

    _ring_hops(
        cp_ref, acc_h, land_y, land_h,
        send_y_sem, recv_y_sem, send_h_sem, recv_h_sem, cap_sem,
        nhops=nhops, dst=dst, src=src, id_type=id_type, backpressure=True,
    )


@functools.partial(jax.jit, static_argnums=(4, 5))
def fused_factor_bcast(d, xc, below, root, ring_axis: str = "c",
                       mesh_axes: tuple = ("r", "c")):
    """Fused lookahead panel step: ``(lkk, cp)`` from the (already diag-
    broadcast, hermitized) tile ``d`` and this rank's panel column ``xc``.

    ``below[ltr]`` masks the strictly-sub-diagonal row tiles, ``root`` is
    the (traced) owning column index along ``ring_axis``.  Equivalent to
    ``potrf_tile(d)`` + ``panel_trsm_right_lower_t`` + ``coll.bcast`` of
    the masked panel, with the exchange streaming on the DMA engines
    instead of barriering.  TPU-only (``fusion_supported`` + backend gate
    at the call site)."""
    mb = d.shape[-1]
    ltr = xc.shape[0]
    n = _axis_size(ring_axis)
    herm = jnp.tril(d) + jnp.tril(d, -1).T
    flat = xc.reshape(ltr * mb, mb)
    root_arr = jnp.asarray(root, jnp.int32).reshape(1, 1)
    below_arr = _lanes(below.astype(jnp.int32).reshape(ltr, 1))
    scratch = [
        pltpu.VMEM((mb, mb), d.dtype),                 # u = tril(L)^T
        pltpu.VMEM((2, ltr * mb, mb), d.dtype),        # landing slots
        pltpu.VMEM((2, 1, HAVE_LANES), jnp.int32),
        pltpu.VMEM((1, HAVE_LANES), jnp.int32),        # have accumulator
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.REGULAR((2,)),     # per-slot capacity acks
    ]
    kernel = functools.partial(
        _fused_kernel,
        nhops=n - 1,
        ring_axis=ring_axis,
        mesh_axes=tuple(mesh_axes),
        mb=mb,
    )
    lkk, cp = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((mb, mb), d.dtype),
            jax.ShapeDtypeStruct((ltr * mb, mb), d.dtype),
        ),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            collective_id=FUSED_COLLECTIVE_ID, has_side_effects=True
        ),
    )(herm, flat, root_arr, below_arr)
    return lkk, cp.reshape(ltr, mb, mb)
