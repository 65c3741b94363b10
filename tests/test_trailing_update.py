"""Fused Pallas trailing-update consumer: parity, backpressure, overlap.

The fused tier (``tune.trailing_update_impl='fused'``,
``dlaf_tpu/ops/pallas_trailing_update.py``) must be BIT-identical to the
XLA lookahead path — the consume ring is a transport/residency
optimization, not an approximation.  On the tier-1 CPU mesh the one-shot
update kernel and the consume ring run in Pallas interpret mode; the
remote-DMA consume kernel (``dma_ring_consume``) is exercised on
single-axis meshes, the only form the jax-0.4.37 interpreter discharges
remote copies for.

Coverage: one-shot ``trailing_update`` bit parity vs ``ops/tile.contract``
(f32 + the float-pair complex path), the in-kernel bf16x3 split-GEMM tier
(bit-identical to the tile-level tier, error-bounded vs f64), the
``consume_schedule`` backpressure invariants, the interpret-mode
``dma_ring_consume`` merge+update contract on 2- and 4-rank rings with a
suppress mask, end-to-end lookahead POTRF and POSV fused-vs-xla bit
parity over {1x2, 2x2, 2x4} x {f32, c64}, the >=70%% overlapped-wire
acceptance bound under pallas+fused, and the knob validation /
'auto'-never-fused / trace-suffix policy rules.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

import dlaf_tpu.testing as tu
from dlaf_tpu import tune
from dlaf_tpu.comm import collectives as coll
from dlaf_tpu.matrix.matrix import DistributedMatrix
from dlaf_tpu.ops import pallas_panel_exchange as ppe
from dlaf_tpu.ops import pallas_trailing_update as ptu
from dlaf_tpu.ops import tile as t

SHAPES = [(1, 2), (2, 2), (2, 4)]
DTYPES = [np.float32, np.complex64]


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_state():
    """Release this module's executables when it finishes (same rationale
    as test_collectives_pallas: every parity case traces fresh under a
    flipped knob, so nothing here is reused by later modules)."""
    yield
    jax.clear_caches()


@contextlib.contextmanager
def _knobs(**kw):
    tp = tune.get_tune_parameters()
    old = {k: getattr(tp, k) for k in kw}
    tp.update(**kw)
    try:
        yield
    finally:
        tp.update(**old)


def _grid(comm_grids, shape):
    return next(g for g in comm_grids if tuple(g.grid_size) == shape)


def _rand(shape, dtype, seed):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(shape)
    if np.issubdtype(dtype, np.complexfloating):
        x = x + 1j * rng.standard_normal(shape)
    return x.astype(dtype)


# ------------------------------------------------- one-shot update kernel


@pytest.mark.parametrize("dtype", DTYPES)
def test_trailing_update_bit_parity(dtype):
    """The kernel IS x - contract(...): bit-identical to the tile-level
    einsum for real payloads and through the float-pair view for complex
    (the interpreter cannot emit complex outputs)."""
    x = _rand((3, 3, 8, 8), dtype, seed=7)
    a = _rand((3, 8, 4), dtype, seed=11)
    b = _rand((3, 8, 4), dtype, seed=13)
    ref = np.asarray(jax.jit(
        lambda x, a, b: x - t.contract(ptu.TRAILING_SUBSCRIPTS, a, b)
    )(x, a, b))
    out = np.asarray(ptu.trailing_update(x, a, b))
    np.testing.assert_array_equal(ref, out)


def test_trailing_update_trsm_subscripts():
    """The TRSM lookahead uses the row-update contraction; the kernel must
    honor arbitrary batched subscripts, not just the POTRF one."""
    sub = "iab,jbc->ijac"
    x = _rand((2, 4, 8, 8), np.float32, seed=17)
    cp = _rand((2, 8, 8), np.float32, seed=19)
    xr = _rand((4, 8, 8), np.float32, seed=23)
    ref = np.asarray(jax.jit(lambda x, a, b: x - t.contract(sub, a, b))(x, cp, xr))
    out = np.asarray(ptu.trailing_update(x, cp, xr, sub))
    np.testing.assert_array_equal(ref, out)


def test_trailing_update_bf16x3_in_kernel():
    """The split-GEMM tier decomposes INSIDE the kernel: bit-identical to
    the tile-level bf16x3 contract, and error-bounded against f64 (the
    bf16x3 representation recovers ~f32 accuracy; the loose 1e-5 relative
    bound would catch a dropped correction limb immediately)."""
    x = _rand((3, 3, 8, 8), np.float32, seed=29)
    a = _rand((3, 8, 8), np.float32, seed=31)
    b = _rand((3, 8, 8), np.float32, seed=37)
    ref = np.asarray(jax.jit(
        lambda x, a, b: x - t.contract(ptu.TRAILING_SUBSCRIPTS, a, b, tier="bf16x3")
    )(x, a, b))
    out = np.asarray(ptu.trailing_update(x, a, b, tier="bf16x3"))
    np.testing.assert_array_equal(ref, out)
    exact = x.astype(np.float64) - np.einsum(
        ptu.TRAILING_SUBSCRIPTS, a.astype(np.float64), b.astype(np.float64)
    )
    scale = max(float(np.max(np.abs(exact))), 1.0)
    assert float(np.max(np.abs(out - exact))) / scale < 1e-5


def test_update_kernel_ok_gates():
    """Off-TPU the interpret path takes everything; the compiled Mosaic
    path has no complex arithmetic, so the gate is the fallback contract
    the algorithms rely on."""
    assert ptu.update_kernel_ok(np.dtype(np.float32))
    assert ptu.update_kernel_ok(np.dtype(np.complex64))  # interpret path


# ------------------------------------------------- the consume schedule


def test_consume_schedule_backpressure():
    """The slot-reuse protocol, asserted as data: hop ``s``'s update
    precedes the cap_signal that licenses the writer's reuse of the same
    landing slot at hop ``s+2``, every cap_wait pairs with the hop-``s-2``
    signal on the same slot, and waits balance signals exactly."""
    for nhops in (1, 2, 3, 5, 8):
        ev = ptu.consume_schedule(nhops)
        # per-hop internal order: dma_start < recv_wait < update, and the
        # update strictly precedes any cap_signal of the same hop
        for s in range(nhops):
            idx = {e: i for i, (e, h, _) in enumerate(ev) if h == s}
            assert idx["dma_start"] < idx["recv_wait"] < idx["update"]
            if "cap_signal" in idx:
                assert idx["update"] < idx["cap_signal"]
        waits = [(h, sl) for e, h, sl in ev if e == "cap_wait"]
        signals = [(h, sl) for e, h, sl in ev if e == "cap_signal"]
        # every wait at hop s pairs with the signal at s-2, same slot
        assert waits == [(h, sl) for h, sl in
                         [(h + 2, sl) for h, sl in signals]]
        for h, sl in waits:
            assert sl == h % 2 and (h - 2, sl) in signals
        # counts balance: no unconsumed capacity tokens at ring end
        assert len(waits) == len(signals) == max(nhops - 2, 0)
        # the signal for slot s%2 lands before the wait that consumes it
        order = {("cap_signal", h, sl): i for i, (e, h, sl) in enumerate(ev)
                 if e == "cap_signal"}
        for i, (e, h, sl) in enumerate(ev):
            if e == "cap_wait":
                assert order[("cap_signal", h - 2, sl)] < i


# ------------------------------------------- the consume ring, interpret
#
# Same caveat as the exchange ring: the jax-0.4.37 interpreter discharges
# remote DMA only on single-named-axis meshes, so the REAL consume kernel
# (remote copies + recv-gated per-hop updates + capacity backpressure)
# runs here on a 1-D 'x' ring.


def _consume_ring(n, slots, contributors, suppress, seed):
    """Reference: merge the ring (owner slots travel), mask by have & ~z,
    one jitted XLA contract.  The kernel's per-hop application must be
    bit-identical — each output element reads exactly one slot, so hop
    order never reassociates the sum."""
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip(f"needs {n} devices")
    mesh = Mesh(np.array(devs[:n]), ("x",))
    mb = 8
    x = _rand((n, 3, slots, mb, mb), np.float32, seed=seed)
    cp = _rand((n, 3, mb, mb), np.float32, seed=seed + 1)
    y = _rand((n, slots, mb, mb), np.float32, seed=seed + 2)
    h = np.zeros((n, slots, 1), np.int32)
    for slot, rank in contributors.items():
        h[rank, slot, 0] = 1
    z = np.zeros((n, slots, 1), np.int32)
    for rank, slot in suppress:
        z[rank, slot, 0] = 1

    def fn(xl, cpl, yl, hl, zl):
        sq = lambda v: v.reshape(v.shape[1:])
        ox, oy, oh = ptu.dma_ring_consume(
            sq(xl), sq(yl), sq(hl), sq(cpl), sq(zl), "x", ("x",), True,
            ppe.collective_id_for("consume", "x"),
        )
        return ox[None], oy[None], oh[None]

    f = jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(P("x"),) * 5, out_specs=(P("x"),) * 3, check_vma=False
    ))
    ox, oy, oh = (np.asarray(v) for v in f(x, cp, y, h, z))
    ref_update = jax.jit(
        lambda x, cp, b: x - t.contract(ptu.TRAILING_SUBSCRIPTS, cp, b)
    )
    for r in range(n):
        merged = np.array(y[r])
        hall = np.zeros(slots, np.int32)
        for slot, rank in contributors.items():
            merged[slot] = y[rank, slot]
            hall[slot] = 1
        # exchange contract: owner bytes on every rank, have merged
        np.testing.assert_array_equal(oy[r], merged)
        np.testing.assert_array_equal(oh[r, :, 0], hall)
        mask = ((hall != 0) & (z[r, :, 0] == 0)).reshape(slots, 1, 1)
        want = np.asarray(ref_update(x[r], cp[r], np.where(mask, merged, 0)))
        np.testing.assert_array_equal(ox[r], want)


@pytest.mark.parametrize("n", [2, 4])
def test_dma_ring_consume_kernel(n):
    # slot 1 unowned (contributes nothing anywhere); owners chosen so
    # payloads cross the whole ring; rank 0 suppresses its slot-0 update
    # (the gj == k+1 narrow-column exclusion) while others apply it
    _consume_ring(n, slots=3, contributors={0: n - 1, 2: 0},
                  suppress=[(0, 0)], seed=211)


def test_dma_ring_consume_all_slots_owned():
    # every slot owned by a distinct rank: every hop of the
    # double-buffered schedule applies fresh bytes under backpressure
    _consume_ring(4, slots=4, contributors={0: 2, 1: 0, 2: 3, 3: 1},
                  suppress=[(1, 2), (3, 0)], seed=223)


def test_dma_ring_consume_single_rank():
    # n == 1: no ring at all — the masked one-shot update, exactly
    mb = 8
    x = _rand((2, 2, mb, mb), np.float32, seed=227)
    cp = _rand((2, mb, mb), np.float32, seed=229)
    y = _rand((2, mb, mb), np.float32, seed=233)
    h = np.array([[1], [0]], np.int32)
    z = np.array([[0], [0]], np.int32)
    devs = jax.devices()
    mesh = Mesh(np.array(devs[:1]), ("x",))

    def fn(xl, cpl, yl, hl, zl):
        sq = lambda v: v.reshape(v.shape[1:])
        ox, oy, oh = ptu.dma_ring_consume(
            sq(xl), sq(yl), sq(hl), sq(cpl), sq(zl), "x", ("x",), True,
            ppe.collective_id_for("consume", "x"),
        )
        return ox[None], oy[None], oh[None]

    f = jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(P("x"),) * 5, out_specs=(P("x"),) * 3, check_vma=False
    ))
    ox, oy, oh = (np.asarray(v)[0] for v in
                  f(x[None], cp[None], y[None], h[None], z[None]))
    mask = (h[:, 0] != 0).reshape(2, 1, 1)
    want = np.asarray(jax.jit(
        lambda x, cp, b: x - t.contract(ptu.TRAILING_SUBSCRIPTS, cp, b)
    )(x, cp, np.where(mask, y, 0)))
    np.testing.assert_array_equal(ox, want)
    np.testing.assert_array_equal(oy, y)
    np.testing.assert_array_equal(oh, h)


# --------------------------------------------------------------- end-to-end


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_potrf_fused_vs_xla(comm_grids, shape, dtype):
    """The acceptance contract: the fused tier's lookahead POTRF is
    bit-identical to the XLA tier's on every tier-1 grid, both dtypes
    (complex falls back to the plain contract inside the fused path — the
    schedule is still the fused one)."""
    from dlaf_tpu.algorithms.cholesky import cholesky_factorization

    grid = _grid(comm_grids, shape)
    a = tu.random_hermitian_pd(40, dtype, seed=31)

    def run():
        mat = DistributedMatrix.from_global(grid, np.tril(a), (8, 8))
        return cholesky_factorization("L", mat).to_global()

    with _knobs(cholesky_lookahead=True):
        with _knobs(trailing_update_impl="xla"):
            ref = run()
        with _knobs(trailing_update_impl="fused"):
            out = run()
    np.testing.assert_array_equal(ref, out)


@pytest.mark.parametrize("dtype", DTYPES)
def test_posv_fused_vs_xla(grid_2x4, dtype):
    """POSV drives both fused consumers (the POTRF consume ring and the
    TRSM row update) in one pipeline; fused-vs-xla must stay bit-exact
    end to end."""
    from dlaf_tpu.algorithms.solver import positive_definite_solver

    a = tu.random_hermitian_pd(40, dtype, seed=43)
    b = tu.random_matrix(40, 16, dtype, seed=47)

    def run():
        mat_a = DistributedMatrix.from_global(grid_2x4, np.tril(a), (8, 8))
        mat_b = DistributedMatrix.from_global(grid_2x4, b, (8, 8))
        return positive_definite_solver("L", mat_a, mat_b).to_global()

    with _knobs(cholesky_lookahead=True, trsm_lookahead=True):
        with _knobs(trailing_update_impl="xla"):
            ref = run()
        with _knobs(trailing_update_impl="fused"):
            out = run()
    np.testing.assert_array_equal(ref, out)


# ------------------------------------------- the one-shot contract kernel


@pytest.mark.parametrize("dtype", DTYPES)
def test_panel_contract_bit_parity(dtype):
    """The TRTRI fused path's one-shot kernel IS contract(...): bit-equal
    to the tile-level einsum (its ``ijab,jbc->iac`` sums over panel slots,
    so it must NOT be consumed per hop — this kernel is the alternative)."""
    xs = _rand((3, 4, 8, 8), dtype, seed=61)
    rp = _rand((4, 8, 8), dtype, seed=67)
    ref = np.asarray(jax.jit(
        lambda a, b: t.contract("ijab,jbc->iac", a, b)
    )(xs, rp))
    out = np.asarray(ptu.panel_contract(xs, rp, "ijab,jbc->iac"))
    np.testing.assert_array_equal(ref, out)
    # and the upper mirror's subscripts (consumed operand first)
    cp = _rand((3, 8, 8), dtype, seed=69)
    ref2 = np.asarray(jax.jit(
        lambda a, b: t.contract("iab,ijbc->jac", a, b)
    )(cp, xs))
    out2 = np.asarray(ptu.panel_contract(cp, xs, "iab,ijbc->jac"))
    np.testing.assert_array_equal(ref2, out2)


def test_panel_contract_signed_zero():
    """Why the fused TRTRI uses panel_contract and not trailing_update on a
    zero accumulator: ``0.0 - x`` flips the sign of signed zeros where the
    caller's ``-contract`` (on the identical bits) does not."""
    a = np.zeros((1, 1, 2, 2), np.float32)
    b = np.zeros((1, 2, 2), np.float32)
    out = np.asarray(ptu.panel_contract(a, b, "ijab,jbc->iac"))
    assert not np.signbit(out).any()


# --------------------------------------- the new consumers: parity e2e


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_gen_to_std_fused_vs_xla(comm_grids, shape, dtype):
    """The her2k window consumer: fused hegst phase A under the fused tier
    is bit-identical to the XLA tier (two consume rings per step, one per
    two-sided addend, suppressed left of the panel)."""
    import scipy.linalg as sla

    from dlaf_tpu.algorithms.gen_to_std import generalized_to_standard

    grid = _grid(comm_grids, shape)
    a = tu.random_hermitian_pd(40, dtype, seed=71)
    b = tu.random_hermitian_pd(40, dtype, seed=73)
    l = np.tril(sla.cholesky(b, lower=True)).astype(dtype)

    def run():
        ma = DistributedMatrix.from_global(grid, a, (8, 8))
        mb = DistributedMatrix.from_global(grid, l, (8, 8))
        return generalized_to_standard("L", ma, mb).to_global()

    with _knobs(gen_to_std_backend="fused"):
        with _knobs(trailing_update_impl="xla"):
            ref = run()
        with _knobs(trailing_update_impl="fused"):
            out = run()
    np.testing.assert_array_equal(ref, out)


@pytest.mark.parametrize("uplo", ["L", "U"])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_trtri_fused_vs_xla(comm_grids, shape, dtype, uplo):
    """The TRTRI column/row-update consumer: consume-ring transport plus
    the one-shot panel_contract kernel, bit-identical to the XLA tier on
    both triangles."""
    import scipy.linalg as sla

    from dlaf_tpu.algorithms.inverse import triangular_inverse

    grid = _grid(comm_grids, shape)
    b = tu.random_hermitian_pd(40, dtype, seed=79)
    f = sla.cholesky(b, lower=(uplo == "L")).astype(dtype)
    f = np.tril(f) if uplo == "L" else np.triu(f)

    def run():
        m = DistributedMatrix.from_global(grid, f, (8, 8))
        return triangular_inverse(uplo, "N", m).to_global()

    with _knobs(trailing_update_impl="xla"):
        ref = run()
    with _knobs(trailing_update_impl="fused"):
        out = run()
    np.testing.assert_array_equal(ref, out)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_red2band_fused_vs_xla(comm_grids, shape, dtype):
    """The red2band two-sided consumer: W2 addend applied by the one-shot
    kernel, the diagonal-crossing V addend consumed out of the ring —
    matrix AND taus bit-identical to the XLA tier."""
    from dlaf_tpu.algorithms.reduction_to_band import reduction_to_band

    grid = _grid(comm_grids, shape)
    a = tu.random_hermitian_pd(48, dtype, seed=83)

    def run():
        m = DistributedMatrix.from_global(grid, a, (8, 8))
        out, taus = reduction_to_band(m, band=4)
        return np.asarray(out.to_global()), np.asarray(taus)

    with _knobs(trailing_update_impl="xla"):
        ref_m, ref_t = run()
    with _knobs(trailing_update_impl="fused"):
        out_m, out_t = run()
    np.testing.assert_array_equal(ref_m, out_m)
    np.testing.assert_array_equal(ref_t, out_t)


def test_her2k_suppress_mask_edge(comm_grids):
    """The two-sided her2k suppress edge, both halves.

    (a) The invariant the suppression RELIES on: under the xla tier the
    exchanged her2k panels are exactly zero at window slots ``jv <= k``
    (the below-mask zeroed them before the bcast), so zeroing them in the
    fused tier is bitwise identity.  (b) The machinery itself: a poisoned
    suppressed slot must not perturb the trailing matrix, while the
    returned merged panel still carries its bytes (the narrow-update
    contract)."""
    grid = _grid(comm_grids, (2, 4))
    # (a) tiny clamped geometry: mt=3 on 2x4 forces windows whose clamped
    # slots sit at or left of the panel — exactly the suppressed set
    import scipy.linalg as sla

    from dlaf_tpu.algorithms.gen_to_std import generalized_to_standard

    a = tu.random_hermitian_pd(24, np.float32, seed=89)
    b = tu.random_hermitian_pd(24, np.float32, seed=97)
    l = np.tril(sla.cholesky(b, lower=True)).astype(np.float32)

    def run():
        ma = DistributedMatrix.from_global(grid, a, (8, 8))
        mb = DistributedMatrix.from_global(grid, l, (8, 8))
        return generalized_to_standard("L", ma, mb).to_global()

    with _knobs(gen_to_std_backend="fused"):
        with _knobs(trailing_update_impl="xla"):
            ref = run()
        with _knobs(trailing_update_impl="fused"):
            out = run()
    np.testing.assert_array_equal(ref, out)

    # (b) direct: suppressed-but-owned slot poisoned with huge garbage
    devs = jax.devices()
    mesh = Mesh(np.array(devs[:2]), ("x",))
    mb = 8
    x = _rand((2, 3, 2, mb, mb), np.float32, seed=101)
    cp = _rand((2, 3, mb, mb), np.float32, seed=103)
    taken = _rand((2, 2, mb, mb), np.float32, seed=107)
    taken[0, 0] = 1e30  # poisoned payload in the suppressed slot
    have = np.array([[True, False], [False, True]])
    suppress = np.array([[True, False], [True, False]])

    def fn(xl, cpl, tl, hl, sl):
        sq = lambda v: v.reshape(v.shape[1:])
        ox, orp = ptu.fused_transpose_update(
            sq(xl), sq(cpl), sq(tl), sq(hl), sq(sl), "x", mesh_axes=("x",)
        )
        return ox[None], orp[None]

    f = jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(P("x"),) * 5, out_specs=(P("x"),) * 2, check_vma=False
    ))
    ox, orp = (np.asarray(v) for v in f(x, cp, taken, have, suppress))
    merged = np.stack([taken[0, 0], taken[1, 1]])
    for r in range(2):
        # the merged panel still ships the poisoned bytes...
        np.testing.assert_array_equal(orp[r], merged)
        # ...but the trailing update never read slot 0
        contrib = np.where(
            np.array([False, True]).reshape(2, 1, 1), merged, 0
        )
        want = np.asarray(jax.jit(
            lambda x, a, b: x - t.contract(ptu.TRAILING_SUBSCRIPTS, a, b)
        )(x[r], cp[r], contrib.conj()))
        np.testing.assert_array_equal(ox[r], want)
        assert np.isfinite(ox[r]).all()


# ------------------------------------------------------- overlap accounting


def test_fused_overlap_fraction(grid_2x4):
    """The acceptance bound: under pallas collectives + the fused consumer
    at least 70%% of the lookahead POTRF's modeled panel-exchange wire
    bytes classify overlapped (the consumed panels are definitionally
    overlapped — the update IS the receive)."""
    from dlaf_tpu.algorithms.cholesky import cholesky_factorization
    from dlaf_tpu.obs import comms as ocomms

    a = tu.random_hermitian_pd(48, np.float32, seed=59)
    with _knobs(collectives_impl="pallas", cholesky_lookahead=True,
                trailing_update_impl="fused"):
        ocomms.start()
        mat = DistributedMatrix.from_global(grid_2x4, np.tril(a), (8, 8))
        cholesky_factorization("L", mat).data.block_until_ready()
        acc = ocomms.stop()
    rows = [r for r in ocomms.as_records(acc)
            if r["collective"].endswith(("_pallas", "_fused"))]
    tot = sum(r["modeled_wire_bytes"] for r in rows)
    ov = sum(r["overlapped_wire_bytes"] for r in rows)
    assert tot > 0, "panel collectives must have traced inside the bracket"
    assert ov >= 0.7 * tot, (ov, tot, rows)
    # the fused rows themselves are fully overlapped by construction
    fused = [r for r in rows if r["collective"].endswith("_fused")]
    assert fused and all(
        r["overlapped_wire_bytes"] == r["modeled_wire_bytes"] for r in fused
    ), fused


def _overlap_rows(acc, suffixes=("_pallas", "_fused")):
    from dlaf_tpu.obs import comms as ocomms

    rows = [r for r in ocomms.as_records(acc)
            if r["collective"].endswith(suffixes)]
    tot = sum(r["modeled_wire_bytes"] for r in rows)
    ov = sum(r["overlapped_wire_bytes"] for r in rows)
    return rows, tot, ov


def test_gen_to_std_fused_overlap_fraction(grid_2x4):
    """The her2k consumer's acceptance bound: >=70%% of the fused hegst's
    modeled panel-exchange wire bytes classify overlapped.  Needs a
    geometry where panel traffic (quadratic in tiles) dominates the
    diag-tile bcasts (linear), and trsm lookahead on so phase B's panels
    are consumed too — mt=24 measures 72%%."""
    import scipy.linalg as sla

    from dlaf_tpu.algorithms.gen_to_std import generalized_to_standard
    from dlaf_tpu.obs import comms as ocomms

    a = tu.random_hermitian_pd(192, np.float32, seed=109)
    b = tu.random_hermitian_pd(192, np.float32, seed=113)
    l = np.tril(sla.cholesky(b, lower=True)).astype(np.float32)
    with _knobs(collectives_impl="pallas", trailing_update_impl="fused",
                gen_to_std_backend="fused", trsm_lookahead=True):
        ocomms.start()
        ma = DistributedMatrix.from_global(grid_2x4, a, (8, 8))
        mb = DistributedMatrix.from_global(grid_2x4, l, (8, 8))
        generalized_to_standard("L", ma, mb).data.block_until_ready()
        acc = ocomms.stop()
    rows, tot, ov = _overlap_rows(acc)
    assert tot > 0, rows
    assert ov >= 0.7 * tot, (ov, tot, rows)


def test_trtri_fused_overlap_fraction(grid_2x4):
    """The TRTRI consumer's acceptance bound (83%% measured at mt=16: the
    consumed panel bcast + consume-ring transport dominate; the s_full
    psum reduction is not panel-exchange traffic and is excluded by the
    pallas/fused row filter)."""
    import scipy.linalg as sla

    from dlaf_tpu.algorithms.inverse import triangular_inverse
    from dlaf_tpu.obs import comms as ocomms

    b = tu.random_hermitian_pd(128, np.float32, seed=127)
    l = np.tril(sla.cholesky(b, lower=True)).astype(np.float32)
    with _knobs(collectives_impl="pallas", trailing_update_impl="fused"):
        ocomms.start()
        m = DistributedMatrix.from_global(grid_2x4, l, (8, 8))
        triangular_inverse("L", "N", m).data.block_until_ready()
        acc = ocomms.stop()
    rows, tot, ov = _overlap_rows(acc)
    assert tot > 0, rows
    assert ov >= 0.7 * tot, (ov, tot, rows)


def test_red2band_fused_overlap_fraction(grid_2x4):
    """red2band's panel-EXCHANGE bytes (the transpose_panel family) are
    fully overlapped under the fused tier.  Scoped to that family: the
    op's wire profile is dominated by the O(N band) column-strip gather
    feeding the redundant Householder panel — a broadcast consumed by
    panel factorization on every rank, not a trailing-update panel
    exchange, and out of scope for the consume ring by construction."""
    from dlaf_tpu.algorithms.reduction_to_band import reduction_to_band
    from dlaf_tpu.obs import comms as ocomms

    a = tu.random_hermitian_pd(128, np.float32, seed=131)
    with _knobs(collectives_impl="pallas", trailing_update_impl="fused"):
        ocomms.start()
        m = DistributedMatrix.from_global(grid_2x4, a, (8, 8))
        out, _ = reduction_to_band(m, band=8)
        out.data.block_until_ready()
        acc = ocomms.stop()
    rows = [r for r in ocomms.as_records(acc)
            if r["collective"].startswith("transpose_panel")]
    tot = sum(r["modeled_wire_bytes"] for r in rows)
    ov = sum(r["overlapped_wire_bytes"] for r in rows)
    assert tot > 0, rows
    assert ov == tot, (ov, tot, rows)
    assert all(r["collective"] == "transpose_panel_fused" for r in rows)


# ------------------------------------------------------ validation / policy


def test_update_rejects_bad_trailing_impl():
    from dlaf_tpu.health import ConfigurationError

    tp = tune.get_tune_parameters()
    old = tp.trailing_update_impl
    with pytest.raises(ConfigurationError, match="trailing_update_impl"):
        tp.update(trailing_update_impl="fussed")
    assert tp.trailing_update_impl == old


def test_auto_never_resolves_fused():
    """fused stays explicit-opt-in until the tpu_day stage-5h A/B promotes
    it; without a device profile 'auto' is xla — everywhere, not just on
    the CPU mesh."""
    from dlaf_tpu.algorithms import _spmd
    from dlaf_tpu.plan import autotune

    with _knobs(trailing_update_impl="auto"):
        assert autotune.trailing_update_tier() == "xla"
        assert _spmd.trailing_update_trace_key() == "xla"
    with _knobs(trailing_update_impl="fused"):
        assert _spmd.trailing_update_trace_key() == "fused"


def test_trailing_impl_in_trace_suffix():
    """Compiled-kernel caches key on plan.trace_suffix(); the fused tier
    must show up there or flipping the knob would reuse xla executables."""
    from dlaf_tpu.plan import core as plan_core

    with _knobs(trailing_update_impl="xla"):
        sx = plan_core.trace_suffix()
    with _knobs(trailing_update_impl="fused"):
        sf = plan_core.trace_suffix()
    assert sx != sf
    assert "fused" in sf and "fused" not in sx


def test_consume_collective_ids_distinct():
    """The consume ring and the fused step allocate their own ids — never
    the exchange/bcast ids they could be live concurrently with."""
    base = [ppe.collective_id_for(k, a)
            for k in ("bcast", "exchange") for a in ("r", "c")]
    base.append(ppe.FUSED_COLLECTIVE_ID)
    extra = [ppe.collective_id_for("consume", "r"),
             ppe.collective_id_for("consume", "c"),
             ppe.collective_id_for("fused_step", "r")]
    assert len(set(base + extra)) == len(base) + len(extra)
    for k, a in (("consume", "r"), ("consume", "c"), ("fused_step", "r")):
        assert ppe.collective_id_for(k, a) == ppe.collective_id_for(k, a)


# ------------------------------------------------------------ serve warmup


def test_replica_warmup_populates_plan():
    """A warm replica serves its first request against a populated plan:
    Replica(warm=True) routes plan.warmup over the pool's own grid/cache
    and stores the compile attribution."""
    from dlaf_tpu import serve
    from dlaf_tpu.serve.router import Replica

    with serve.SolverPool(block_size=8, cache=serve.CompiledCache()) as pool:
        rep = Replica(
            "r0", pool, warm=True,
            warmup_kwargs=dict(buckets=[16], ops=("potrf",),
                               dtypes=("float32",)),
        )
        assert rep.warm_summary is not None
        assert rep.warm_summary["plans"] >= 1
        assert rep.warm_summary["seconds"] >= 0
        # idempotent re-warm through the method itself
        again = rep.warmup(buckets=[16], ops=("potrf",), dtypes=("float32",))
        assert again["plans"] >= 1
