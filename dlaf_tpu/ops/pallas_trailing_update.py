"""Fused Pallas trailing-update consumer: panels that never leave VMEM.

The fourth trailing-update surface (``tune.trailing_update_impl='fused'``).
Under the ``xla`` tier the lookahead Cholesky step lands the exchanged row
panel in HBM and re-loads it into the trailing einsum — plus, under the
split-GEMM tiers, each bf16 slice round-trips through HBM per product.
This module composes the ring-DMA machinery of
``ops/pallas_panel_exchange`` (PR 6) with the split-GEMM decomposition of
``ops/tile.contract`` (PR 9) so the GEMM/HERK consumes panel operands
straight out of the double-buffered ring-DMA landing slots, with the
bf16x3/bf16x6 slice decomposition performed INSIDE the kernel — the MXU
reads bf16 operands that never existed in HBM.

Consume schedule
----------------
``dma_ring_consume`` runs the ``_ring_hops`` protocol of the exchange
kernel with one change: after merging hop ``s`` the kernel applies that
hop's freshly-landed tiles to the trailing matrix — reading the operand
straight out of landing slot ``s%2`` — and only THEN signals the slot's
capacity semaphore.  The upstream writer therefore cannot reuse the slot
at hop ``s+2`` until the update consumed it (the slot-reuse backpressure
the tests assert via :func:`consume_schedule`), and hop ``s+1``'s DMA is
already in flight while hop ``s``'s update owns the MXU — update hop h
while hop h+1 streams.

Per-hop exactness: the trailing contraction ``iab,jcb->ijac`` contracts
ONLY over ``b`` — every output element takes its contribution from exactly
ONE panel slot ``j`` — so applying slot ``j``'s contribution at the hop it
lands is the same sum the one-shot einsum computes, with no cross-slot
accumulation-order hazard.  Slots outside the hop's fresh set contribute
an exactly-zero masked operand (the same zero contribution the one-shot
einsum carries for masked slots).

Execution paths
---------------
* **TPU, real dtypes**: :func:`dma_ring_consume` — the remote-DMA consume
  kernel above (also runnable under the interpreter on single-named-axis
  meshes, like the exchange kernel, with the cross-rank sync off).  First
  cut: the per-hop update is a masked full-panel contraction (fresh slots
  carry data, the rest exact zeros), so it spends ring-length redundant
  MXU flops in exchange for the overlap; the hop-sliced refinement is
  staged behind the tpu_day 5h A/B like the rest of the tier.
* **CPU / non-TPU (the tier-1 parity path)**: the ring transport is
  ``ppe.ring_exchange`` with ``kind='consume'`` (bit-identical to the
  psum/v2/pallas transports — one-contributor pure-select merges), and the
  update is ONE interpret-mode Pallas kernel (:func:`trailing_update`)
  tracing the identical ``tile.contract`` the XLA tier traces — same
  jaxpr, same bits, which is what lets the tier-1 acceptance assert
  ``fused`` == ``xla`` bit-exactly.  Complex payloads cross the kernel
  boundary as bit-preserving float-pair views (the 0.4.37 interpreter
  cannot initialize complex Pallas outputs) and are viewed back inside —
  verified bit-exact including NaN propagation.

``fused_step`` extends ``ppe.fused_factor_bcast`` into the full
single-kernel lookahead pipeline — consume-update, narrow update, diagonal
factor, panel solve, and the next panel's ring send in ONE ``pallas_call``
(see its docstring for the VMEM residency story).  TPU-only, gated by
:func:`fused_step_supported`; every collective ring inside it gets its own
``collective_id_for`` entry and its own semaphore set (phases of one
kernel are not synchronization points — shared semaphores across phases
would race on skewed ranks).

No module-level executable caches here: entry points are traced inside
callers that key through ``plan.cached`` (the ``trailing_update_impl``
trace key rides ``plan.trace_suffix``), and direct callers re-trace.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlaf_tpu.ops import pallas_panel_exchange as ppe
from dlaf_tpu.ops import pallas_panel_trsm as _ptrsm
from dlaf_tpu.ops import pallas_potrf as _ppotrf
from dlaf_tpu.ops import tile as t

#: the lookahead trailing-update contraction (cholesky geometry): panel
#: slot j is the ONLY contributor to output column-slot j — the property
#: that makes per-hop application bit-equal to the one-shot einsum
TRAILING_SUBSCRIPTS = "iab,jcb->ijac"

#: scoped-VMEM limit of the ring kernels that keep the whole local trailing
#: matrix resident (of v5e's 128 MiB): the default 16 MiB scope does not
#: hold even a 2x2-tile local matrix's in-kernel contraction
VMEM_LIMIT_BYTES = 100 << 20


def consume_schedule(nhops: int) -> list:
    """The per-hop event order of :func:`dma_ring_consume`, as data.

    Returns ``(event, hop, slot)`` triples with ``event`` one of
    ``cap_wait | dma_start | recv_wait | send_wait | update | cap_signal``.
    This is the protocol the kernel loop is generated from (same hop
    arithmetic, same gating), stated separately so tests can assert the
    backpressure invariants without a TPU: the ``update`` of hop ``s``
    precedes the ``cap_signal`` that licenses the writer's slot reuse at
    hop ``s+2``, every ``cap_wait`` pairs with the hop-``s-2`` signal on
    the same slot, and the semaphore counts balance to zero."""
    events = []
    for s in range(nhops):
        slot = s % 2
        if s >= 2:
            events.append(("cap_wait", s, slot))
        events.append(("dma_start", s, slot))
        events.append(("recv_wait", s, slot))
        events.append(("send_wait", s, slot))
        events.append(("update", s, slot))
        if s + 2 < nhops:
            events.append(("cap_signal", s, slot))
    return events


# ---------------------------------------------------- one-shot update kernel


def _pair_dtype(dtype):
    """(wire float dtype, complex dtype | None) for a payload dtype."""
    dt = jnp.dtype(dtype)
    if dt == jnp.complex64:
        return jnp.dtype(jnp.float32), dt
    if dt == jnp.complex128:
        return jnp.dtype(jnp.float64), dt
    return dt, None


def _update_kernel(x_ref, a_ref, b_ref, o_ref, *, subscripts, cdtype, tier):
    """x - contract(subscripts, a, b), all operands VMEM-resident.

    The contraction is ``tile.contract`` itself, traced INSIDE the kernel:
    under the split-GEMM tiers the bf16 slice decomposition happens here,
    in VMEM — and because the identical function produces the identical
    jaxpr the XLA tier traces, interpret-mode execution is bit-equal to
    the unfused path (the tier-1 parity contract).  Complex operands
    arrive as float-pair views and are viewed back before the math."""
    x, a, b = x_ref[...], a_ref[...], b_ref[...]
    if cdtype is not None:
        x, a, b = x.view(cdtype), a.view(cdtype), b.view(cdtype)
    out = x - t.contract(subscripts, a, b, tier=tier)
    if cdtype is not None:
        out = out.view(x_ref.dtype)
    o_ref[...] = out


def trailing_update(x, a, b, subscripts: str = TRAILING_SUBSCRIPTS, *,
                    interpret: bool | None = None, tier: str | None = None):
    """One fused trailing update ``x - contract(subscripts, a, b)`` as a
    single Pallas kernel (VMEM-resident operands, in-kernel split-GEMM).

    ``interpret=None`` resolves per backend (compiled on TPU, interpreter
    everywhere else).  ``tier=None`` resolves ``tune.gemm_precision`` at
    trace time exactly like ``tile.contract`` — callers outside a
    plan-keyed trace pass the tier explicitly.  Deliberately NOT jitted
    here: inside the algorithm kernels it traces inline under their plan
    key; direct (test) callers re-trace per call, which is what makes
    flipping knobs between calls safe."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    fdt, cdtype = _pair_dtype(x.dtype)
    xw, aw, bw = x, a, b
    if cdtype is not None:
        xw, aw, bw = x.view(fdt), a.view(fdt), b.view(fdt)
    out = pl.pallas_call(
        functools.partial(
            _update_kernel, subscripts=subscripts, cdtype=cdtype, tier=tier
        ),
        out_shape=jax.ShapeDtypeStruct(xw.shape, xw.dtype),
        interpret=interpret,
    )(xw, aw, bw)
    if cdtype is not None:
        out = out.view(cdtype)
    return out


def update_kernel_ok(dtype) -> bool:
    """Whether :func:`trailing_update` / :func:`panel_contract` can run for
    this dtype on this backend: everywhere under the interpreter; real-only
    on compiled TPU (Mosaic has no complex arithmetic — the float-pair
    trick needs the interpreter's bitcast semantics)."""
    if jax.default_backend() != "tpu":
        return True
    return not jnp.issubdtype(jnp.dtype(dtype), jnp.complexfloating)


def _contract_kernel(a_ref, b_ref, o_ref, *, subscripts, cdtype, tier):
    """contract(subscripts, a, b), operands VMEM-resident — the one-shot
    sibling of ``_update_kernel`` for contractions whose result feeds a
    cross-rank reduction rather than an in-place subtraction."""
    a, b = a_ref[...], b_ref[...]
    if cdtype is not None:
        a, b = a.view(cdtype), b.view(cdtype)
    out = t.contract(subscripts, a, b, tier=tier)
    if cdtype is not None:
        out = out.view(a_ref.dtype)
    o_ref[...] = out


def panel_contract(a, b, subscripts: str, *,
                   interpret: bool | None = None, tier: str | None = None):
    """One panel contraction ``contract(subscripts, a, b)`` as a single
    Pallas kernel (VMEM-resident operands, in-kernel split-GEMM).

    Contractions that SUM over the panel slot axis — the TRTRI column
    update ``ijab,jbc->iac`` and its upper mirror — have a cross-slot
    accumulation order, so applying hops out of the ring landing slots
    would reassociate that sum (NOT bit-safe, unlike
    ``TRAILING_SUBSCRIPTS``).  The fused tier instead pairs the consume
    ring TRANSPORT (:func:`consume_exchange`) with this one-shot in-VMEM
    contraction: same jaxpr as the XLA tier's ``tile.contract``, so
    interpret-mode execution is bit-equal (the tier-1 parity contract).
    Note this is ``contract``, not ``0 - contract`` via
    :func:`trailing_update` on zeros — ``0.0 - x`` flips the sign bit of
    signed zeros where ``-x`` (applied by the caller) does not."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    fdt, cdtype = _pair_dtype(a.dtype)
    osd = jax.eval_shape(
        lambda a_, b_: t.contract(subscripts, a_, b_, tier=tier), a, b
    )
    oshape, odtype = osd.shape, osd.dtype
    aw, bw = a, b
    if cdtype is not None:
        aw, bw = a.view(fdt), b.view(fdt)
        oshape = oshape[:-1] + (2 * oshape[-1],)
        odtype = fdt
    out = pl.pallas_call(
        functools.partial(
            _contract_kernel, subscripts=subscripts, cdtype=cdtype, tier=tier
        ),
        out_shape=jax.ShapeDtypeStruct(oshape, odtype),
        interpret=interpret,
    )(aw, bw)
    if cdtype is not None:
        out = out.view(cdtype)
    return out


def consume_exchange(taken, have, ring_axis: str, *, mesh_axes=("r", "c")):
    """The consume ring's TRANSPORT alone: exchange the one-contributor
    panel parts along ``ring_axis`` and return the merged panel (zero where
    no rank contributed), recorded as ``transpose_panel_fused``.

    Callers whose trailing contraction sums across panel slots (TRTRI)
    pair this with :func:`panel_contract` instead of consuming per hop —
    the ring schedule and ``collective_id_for('consume', axis)`` class
    match :func:`dma_ring_consume`; only the application is hoisted out of
    the hop loop into the one-shot kernel.  Bit-identical to the
    ``_panel_exchange`` transports (one-contributor pure-select merges)."""
    from dlaf_tpu.obs.comms import record as _rec

    _rec("transpose_panel_fused", taken, ring_axis)
    if ppe._axis_size(ring_axis) == 1:
        hmask = have.reshape(have.shape + (1,) * (taken.ndim - have.ndim))
        return jnp.where(hmask, taken, jnp.zeros_like(taken))
    y, have_all = ppe.ring_exchange(
        taken, have, ring_axis, mesh_axes=tuple(mesh_axes), kind="consume"
    )
    amask = have_all.reshape(have_all.shape + (1,) * (y.ndim - have_all.ndim))
    return jnp.where(amask, y, jnp.zeros_like(y))


# ------------------------------------------------------- consume ring kernel


def _apply_update(ox_ref, cp_ref, y, mask, *, subscripts):
    """Subtract the masked panel contribution from the trailing accumulator.

    ``y[slots, mb, nb]`` is the operand source (a landing slot or the local
    contribution), lane-dense ``mask[slots, HAVE_LANES]`` selects the slots
    to apply; the rest
    contribute an exactly-zero operand — the same zero contribution the
    one-shot einsum carries for masked slots, so summing per-hop
    applications reproduces its per-element arithmetic."""
    contrib = jnp.where(ppe._flag(mask != 0, y), y, jnp.zeros_like(y))
    ox_ref[...] = ox_ref[...] - t.contract(subscripts, cp_ref[...], contrib)


def _consume_hops(
    ox_ref, cp_ref, z_ref, acc_y, acc_h, land_y, land_h,
    send_y_sem, recv_y_sem, send_h_sem, recv_h_sem, cap_sem,
    *, nhops: int, dst, src, id_type, backpressure: bool, subscripts: str,
):
    """The P-1 consume hops — ``ppe._ring_hops`` with the update spliced in
    between the merge and the capacity ack (the :func:`consume_schedule`
    order).  The update reads the fresh tiles straight out of landing slot
    ``s%2``; the ack after it is the slot-reuse backpressure."""
    for s in range(nhops):
        slot = np.int32(s % 2)  # int32 index: Mosaic lowers no int64
        if backpressure and s >= 2:
            pltpu.semaphore_wait(cap_sem.at[slot], 1)
        cp_y = pltpu.make_async_remote_copy(
            src_ref=acc_y, dst_ref=land_y.at[slot],
            send_sem=send_y_sem.at[slot], recv_sem=recv_y_sem.at[slot],
            device_id=dst, device_id_type=id_type,
        )
        cp_h = pltpu.make_async_remote_copy(
            src_ref=acc_h, dst_ref=land_h.at[slot],
            send_sem=send_h_sem.at[slot], recv_sem=recv_h_sem.at[slot],
            device_id=dst, device_id_type=id_type,
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
        acc_y[...] = jnp.where(ppe._flag(take, acc_y), land_y[slot], acc_y[...])
        acc_h[...] = have | h_in
        # consume hop s out of its landing slot while hop s+1 is in flight
        _apply_update(
            ox_ref, cp_ref, land_y[slot],
            take.astype(jnp.int32) * (z_ref[...] == 0),
            subscripts=subscripts,
        )
        if backpressure and s + 2 < nhops:
            # only AFTER the update: the writer may now reuse the slot
            pltpu.semaphore_signal(
                cap_sem.at[slot], device_id=src, device_id_type=id_type
            )


def _dma_ring_consume_kernel(
    x_ref, y_ref, h_ref, cp_ref, z_ref, ox_ref, oy_ref, oh_ref,
    land_y, land_h, send_y_sem, recv_y_sem, send_h_sem, recv_h_sem, cap_sem,
    *, nhops: int, ring_axis: str, mesh_axes: tuple, sync: bool,
    subscripts: str,
):
    """Merge-and-consume over the whole ring in one launch: the local
    contribution is applied before hop 0, each later hop's fresh tiles as
    they land.  ``oy_ref/oh_ref`` double as the merge accumulator, exactly
    like ``ppe._dma_ring_kernel``."""
    dst, id_type = ppe._neighbor_ids(ring_axis, mesh_axes, +1)
    src, _ = ppe._neighbor_ids(ring_axis, mesh_axes, -1)

    ox_ref[...] = x_ref[...]
    oy_ref[...] = y_ref[...]
    oh_ref[...] = h_ref[...]

    if sync:
        bar = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(bar, device_id=dst, device_id_type=id_type)
        pltpu.semaphore_signal(bar, device_id=src, device_id_type=id_type)
        pltpu.semaphore_wait(bar, 2)

    # hop "-1": this rank's own contributed slots never arrive by ring
    _apply_update(
        ox_ref, cp_ref, y_ref[...], h_ref[...] * (z_ref[...] == 0),
        subscripts=subscripts,
    )
    _consume_hops(
        ox_ref, cp_ref, z_ref, oy_ref, oh_ref, land_y, land_h,
        send_y_sem, recv_y_sem, send_h_sem, recv_h_sem, cap_sem,
        nhops=nhops, dst=dst, src=src, id_type=id_type, backpressure=sync,
        subscripts=subscripts,
    )


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9))
def dma_ring_consume(x, yf, h, cp, z, ring_axis: str, mesh_axes: tuple,
                     interpret: bool = False, collective_id: int = 0,
                     subscripts: str = TRAILING_SUBSCRIPTS):
    """The fused consume ring: exchange the one-contributor panel
    ``(yf[slots, mb, nb], h[slots, 1])`` along ``ring_axis`` AND apply each
    slot's trailing contribution ``contract(subscripts, cp, slot)`` to
    ``x`` at the hop the slot lands, reading straight out of the landing
    slot.  ``z[slots, 1]`` suppresses slots whose update is applied
    elsewhere (the lookahead narrow column).  Real dtypes only (complex
    callers go through the transport + :func:`trailing_update` pair).

    Returns ``(x', yf', h')`` — the updated trailing matrix plus the fully
    merged panel and have mask (the caller still needs the panel for the
    narrow update).  ``interpret=True`` follows the exchange kernel's
    rules: single-named-axis meshes, cross-rank sync off.

    ``collective_id`` must come from ``ppe.collective_id_for('consume',
    axis)`` — the consume ring is a distinct call-site class from the
    exchange rings and may be live while other classes drain (DLAF002
    checks the explicit id at every call site)."""
    n = ppe._axis_size(ring_axis)
    if n == 1:
        # no ring: the whole update is the local contribution
        m = ((h != 0) & (z == 0)).reshape(h.shape[0], 1, 1)
        contrib = jnp.where(m, yf, jnp.zeros_like(yf))
        return x - t.contract(subscripts, cp, contrib), yf, h
    hl, zl = ppe._lanes(h), ppe._lanes(z)
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
        _dma_ring_consume_kernel,
        nhops=n - 1,
        ring_axis=ring_axis,
        mesh_axes=tuple(mesh_axes),
        sync=not interpret,
        subscripts=subscripts,
    )
    x2, y2, h2 = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(yf.shape, yf.dtype),
            jax.ShapeDtypeStruct(hl.shape, h.dtype),
        ),
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            collective_id=collective_id, has_side_effects=True,
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
    )(x, yf, hl, cp, zl)
    return x2, y2, h2[:, :1]


# ----------------------------------------------------- fused orchestration


def fused_transpose_update(x, cp, taken, have, suppress, ring_axis: str, *,
                           mesh_axes=("r", "c"), conj_panel: bool = True):
    """The fused tier's exchange-and-consume of one lookahead panel step.

    ``(taken, have)`` are ``coll.transpose_panel_parts`` of the broadcast
    column panel ``cp``; ``suppress[slots]`` masks the slots whose update
    the caller applies narrowly (column k+1).  Returns ``(x', rp)`` with
    ``rp`` bit-identical to ``coll.transpose_panel``'s output and ``x'``
    bit-identical to ``x - contract(iab,jcb->ijac, cp, rp_bulk.conj())``
    of the ``xla`` tier (``conj_panel=False`` skips the conjugation for
    callers whose contraction takes the panel unconjugated).

    Transport + update selection: on TPU with real payloads, the
    :func:`dma_ring_consume` kernel (per-hop in-kernel application); on
    every other backend, the ppermute ring transport (``kind='consume'``)
    plus the one-shot interpret-mode :func:`trailing_update` kernel — the
    identical expressions the XLA tier traces, inside Pallas.  Wire bytes
    are recorded as ``transpose_panel_fused`` — a one-contributor ring
    whose hops are consumed in-kernel, so ``obs.comms`` classifies them
    overlapped unconditionally."""
    from dlaf_tpu.obs.comms import record as _rec

    _rec("transpose_panel_fused", taken, ring_axis)
    n = ppe._axis_size(ring_axis)
    real = not jnp.issubdtype(jnp.dtype(x.dtype), jnp.complexfloating)
    if ppe._use_dma() and n > 1 and real:
        h = have.astype(jnp.int32).reshape(-1, 1)
        z = suppress.astype(jnp.int32).reshape(-1, 1)
        x2, y2, h2 = dma_ring_consume(
            x, taken, h, cp, z, ring_axis, tuple(mesh_axes), False,
            ppe.collective_id_for("consume", ring_axis),
        )
        amask = (h2 != 0).reshape(h2.shape[0], 1, 1)
        return x2, jnp.where(amask, y2, jnp.zeros_like(y2))
    y, have_all = ppe.ring_exchange(
        taken, have, ring_axis, mesh_axes=tuple(mesh_axes), kind="consume"
    )
    amask = have_all.reshape(have_all.shape + (1,) * (y.ndim - have_all.ndim))
    rp = jnp.where(amask, y, jnp.zeros_like(y))
    smask = suppress.reshape(suppress.shape + (1,) * (rp.ndim - suppress.ndim))
    rp_bulk = jnp.where(smask, jnp.zeros_like(rp), rp)
    b = rp_bulk.conj() if conj_panel else rp_bulk
    if update_kernel_ok(x.dtype):
        x = trailing_update(x, cp, b, TRAILING_SUBSCRIPTS)
    else:  # compiled TPU + complex payload: same math, XLA einsum
        x = x - t.contract(TRAILING_SUBSCRIPTS, cp, b)
    return x, rp


# --------------------------------------------------- fused full-step kernel


def fused_step_supported(x, cp) -> bool:
    """The single-kernel lookahead step covers the f32 square-tile
    Cholesky case with MXU/VPU-aligned tile side (same alignment gates as
    ``ppe.fusion_supported`` — the composed trsm kernel column-blocks by 32
    and Mosaic wants lane-width multiples)."""
    mb = x.shape[-1]
    return (
        np.dtype(x.dtype) == np.dtype(np.float32)  # Mosaic has no f64
        and x.ndim == 4
        and x.shape[-2] == mb
        and cp.ndim == 3
        and cp.shape[-2:] == (mb, mb)
        and mb % 128 == 0
        and mb <= _ptrsm.MAX_NB
    )


def _masked_tile(stack, idx_ref_val, axis_len: int):
    """stack[idx] for a traced idx, as a masked sum (Mosaic-friendly: no
    dynamic gather) — requires the mask to select at most one slot."""
    sel = (_iota((axis_len,) + (1,) * (stack.ndim - 1), 0) == idx_ref_val)
    return jnp.sum(stack * sel.astype(stack.dtype), axis=0)


def _iota(shape, axis: int):
    """Index along ``axis`` at full rank (Mosaic cannot reshape a 1-D
    vector into a higher-rank one)."""
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _fused_step_kernel(
    x_ref, y_ref, h_ref, z_ref, cp_ref, below_ref, par_ref,
    ox_ref, orp_ref, orh_ref, od_ref, olkk_ref, ocp_ref,
    land_y, land_h, dland_y, dland_h, d2land_y, d2land_h,
    cland_y, cland_h, u_ref, xc_ref, dh_ref, acc_h,
    s1y, r1y, s1h, r1h, c1, s2y, r2y, s2h, r2h, c2,
    s3y, r3y, s3h, r3h, c3, s4y, r4y, s4h, r4h, c4,
    *, nhops_r: int, nhops_c: int, mesh_axes: tuple, mb: int,
):
    """The whole lookahead body in ONE launch — update(k) -> narrow(k+1) ->
    factor(k+1) -> solve(k+1) -> send(k+1), everything VMEM-resident:

    1. consume ring over 'r': merge the row panel AND apply each hop's
       trailing update straight out of the landing slots;
    2. narrow update of column k+1 from the now-complete row panel;
    3. in-kernel 2D ring broadcast of the updated diagonal tile
       ('c' then 'r' — the ``bcast_diag_tile`` order);
    4. ``pallas_potrf`` sweep + ``pallas_panel_trsm`` solve of the panel;
    5. masked ring send of the factored panel over 'c'
       (the ``fused_factor_bcast`` tail).

    ``par_ref[1, 8]`` int32: [kc1, kr1, l_next, lkr1, lkc1, 0, 0, 0] — the
    traced owner/slot indices of step k+1.  Every ring phase has its OWN
    DMA + capacity semaphores: phases are not synchronization points, so a
    rank ahead in phase p+1 must not signal into a neighbor still draining
    phase p (the inter-phase race a shared semaphore would create)."""
    ltr, ltc = x_ref.shape[0], x_ref.shape[1]
    dst_r, id_r = ppe._neighbor_ids("r", mesh_axes, +1)
    src_r, _ = ppe._neighbor_ids("r", mesh_axes, -1)
    dst_c, id_c = ppe._neighbor_ids("c", mesh_axes, +1)
    src_c, _ = ppe._neighbor_ids("c", mesh_axes, -1)
    me_r = lax.axis_index("r")
    me_c = lax.axis_index("c")
    kc1 = par_ref[0, 0]
    kr1 = par_ref[0, 1]
    l_next = par_ref[0, 2]
    lkr1 = par_ref[0, 3]
    lkc1 = par_ref[0, 4]

    ox_ref[...] = x_ref[...]
    orp_ref[...] = y_ref[...]
    orh_ref[...] = h_ref[...]

    bar = pltpu.get_barrier_semaphore()
    for dev, idt in ((dst_r, id_r), (src_r, id_r), (dst_c, id_c), (src_c, id_c)):
        pltpu.semaphore_signal(bar, device_id=dev, device_id_type=idt)
    pltpu.semaphore_wait(bar, 4)

    # -- 1. consume ring over 'r' (local contribution first, then P-1 hops)
    _apply_update(
        ox_ref, cp_ref, y_ref[...], h_ref[...] * (z_ref[...] == 0),
        subscripts=TRAILING_SUBSCRIPTS,
    )
    _consume_hops(
        ox_ref, cp_ref, z_ref, orp_ref, orh_ref, land_y, land_h,
        s1y, r1y, s1h, r1h, c1,
        nhops=nhops_r, dst=dst_r, src=src_r, id_type=id_r, backpressure=True,
        subscripts=TRAILING_SUBSCRIPTS,
    )

    # -- 2. narrow update: column k+1 only, from the merged row panel
    rp1 = _masked_tile(
        jnp.where(ppe._flag(orh_ref[...] != 0, orp_ref), orp_ref[...],
                  jnp.zeros_like(orp_ref[...])),
        l_next, ltc,
    )
    upd1 = t.contract("iab,cb->iac", cp_ref[...], rp1)
    colmask = (
        (_iota((1, ltc, 1, 1), 1) == l_next) & (me_c == kc1)
    ).astype(ox_ref.dtype)
    ox_ref[...] = ox_ref[...] - upd1[:, None] * colmask

    # -- 3. diagonal tile of step k+1 -> everyone ('c' ring then 'r' ring)
    rsel = (_iota((ltr, 1, 1, 1), 0) == lkr1).astype(ox_ref.dtype)
    csel = (_iota((1, ltc, 1, 1), 1) == lkc1).astype(ox_ref.dtype)
    d_own = jnp.sum(ox_ref[...] * rsel * csel, axis=(0, 1))
    own = (me_r == kr1) & (me_c == kc1)
    od_ref[...] = jnp.where(own, d_own, jnp.zeros_like(d_own))
    acc_h[...] = jnp.full(acc_h.shape, own.astype(jnp.int32))
    ppe._ring_hops(
        od_ref, acc_h, dland_y, dland_h, s2y, r2y, s2h, r2h, c2,
        nhops=nhops_c, dst=dst_c, src=src_c, id_type=id_c, backpressure=True,
    )
    ppe._ring_hops(
        od_ref, acc_h, d2land_y, d2land_h, s3y, r3y, s3h, r3h, c3,
        nhops=nhops_r, dst=dst_r, src=src_r, id_type=id_r, backpressure=True,
    )

    # -- 4. factor + panel solve, everything VMEM-resident
    dh_ref[...] = jnp.tril(od_ref[...]) + jnp.tril(od_ref[...], -1).T
    _ppotrf._potrf_kernel(dh_ref, olkk_ref)
    u_ref[...] = jnp.tril(olkk_ref[...]).T
    xsel = (_iota((1, ltc, 1, 1), 1) == l_next).astype(ox_ref.dtype)
    xc_ref[...] = jnp.sum(ox_ref[...] * xsel, axis=1).reshape(ltr * mb, mb)
    _ptrsm._kernel(u_ref, xc_ref, ocp_ref, nb=mb)

    # -- 5. mask to sub-diagonal rows of the owning column, ring-send ('c')
    is_root = (me_c == kc1).astype(jnp.int32)
    keep = ppe._row_blocks(below_ref[...], mb) * is_root
    ocp_ref[...] = jnp.where(keep != 0, ocp_ref[...], jnp.zeros_like(ocp_ref))
    acc_h[...] = jnp.full(acc_h.shape, is_root)
    ppe._ring_hops(
        ocp_ref, acc_h, cland_y, cland_h, s4y, r4y, s4h, r4h, c4,
        nhops=nhops_c, dst=dst_c, src=src_c, id_type=id_c, backpressure=True,
    )


@functools.partial(jax.jit, static_argnums=(7,))
def fused_step(x, taken, have, suppress, cp, below1, params,
               mesh_axes: tuple = ("r", "c")):
    """One lookahead Cholesky body as a single Mosaic kernel (see
    ``_fused_step_kernel``).  TPU-only; callers gate on
    :func:`fused_step_supported` and backend.

    ``taken/have/suppress`` are the step-k row-panel parts and narrow-slot
    mask, ``cp`` the step-k broadcast column panel, ``below1[ltr]`` the
    strictly-below mask of step k+1, ``params`` the int32 index vector
    ``[kc1, kr1, l_next, lkr1, lkc1, 0, 0, 0]``.  Returns
    ``(x', rp, lkk1, cp1, d1)`` — ``d1`` is the broadcast diagonal tile of
    step k+1 so the caller's pivot scan sees the identical operand."""
    ltr, ltc = x.shape[0], x.shape[1]
    mb = x.shape[-1]
    nr = ppe._axis_size("r")
    nc = ppe._axis_size("c")
    h = ppe._lanes(have.astype(jnp.int32).reshape(ltc, 1))
    z = ppe._lanes(suppress.astype(jnp.int32).reshape(ltc, 1))
    below_arr = ppe._lanes(below1.astype(jnp.int32).reshape(ltr, 1))
    par = params.astype(jnp.int32).reshape(1, 8)
    HL = ppe.HAVE_LANES
    dma2 = pltpu.SemaphoreType.DMA((2,))
    reg2 = pltpu.SemaphoreType.REGULAR((2,))
    scratch = [
        pltpu.VMEM((2, ltc, mb, mb), x.dtype),     # consume landing slots
        pltpu.VMEM((2, ltc, HL), jnp.int32),
        pltpu.VMEM((2, mb, mb), x.dtype),          # d 'c'-ring landing
        pltpu.VMEM((2, 1, HL), jnp.int32),
        pltpu.VMEM((2, mb, mb), x.dtype),          # d 'r'-ring landing
        pltpu.VMEM((2, 1, HL), jnp.int32),
        pltpu.VMEM((2, ltr * mb, mb), x.dtype),    # cp send landing
        pltpu.VMEM((2, 1, HL), jnp.int32),
        pltpu.VMEM((mb, mb), x.dtype),             # u = tril(L)^T
        pltpu.VMEM((ltr * mb, mb), x.dtype),       # flattened panel column
        pltpu.VMEM((mb, mb), x.dtype),             # hermitized diag tile
        pltpu.VMEM((1, HL), jnp.int32),            # have accumulator
    ] + [dma2, dma2, dma2, dma2, reg2] * 4         # one sem set per phase
    kernel = functools.partial(
        _fused_step_kernel,
        nhops_r=nr - 1, nhops_c=nc - 1, mesh_axes=tuple(mesh_axes), mb=mb,
    )
    x2, rp, rh, d1, lkk1, cp1 = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((ltc, mb, mb), x.dtype),
            jax.ShapeDtypeStruct((ltc, HL), jnp.int32),
            jax.ShapeDtypeStruct((mb, mb), x.dtype),
            jax.ShapeDtypeStruct((mb, mb), x.dtype),
            jax.ShapeDtypeStruct((ltr * mb, mb), x.dtype),
        ),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            collective_id=ppe.collective_id_for("fused_step", "r"),
            has_side_effects=True,
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
    )(x, taken, h, z, cp, below_arr, par)
    rp = jnp.where(ppe._flag(rh != 0, rp), rp, jnp.zeros_like(rp))
    return x2, rp, lkk1, cp1.reshape(ltr, mb, mb), d1
