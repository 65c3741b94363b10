"""Pallas-tier collectives: parity with v2, the DMA ring kernel, overlap.

The pallas tier (``tune.collectives_impl='pallas'``,
``dlaf_tpu/ops/pallas_panel_exchange.py``) must be BIT-identical to the v2
doubling chain — the ring is a transport/overlap optimization, not an
approximation.  On the tier-1 CPU mesh the tier runs its ppermute-transport
ring with the merge kernel in Pallas interpret mode; the remote-DMA kernel
itself (``dma_ring_exchange``) is exercised here on single-axis meshes,
the only form the jax-0.4.37 interpreter discharges remote copies for.

Coverage: property tests per primitive over {1x2, 2x2, 2x4} x {f32, c64}
against the v2 tier (itself psum-verified in test_collectives_v2.py),
end-to-end POTRF (bucketed + lookahead) and TRSM agreement, the DMA ring
kernel's merge/have contract on 2- and 4-rank rings, a
``testing.faults.slow_collective`` no-deadlock case, the >=50%%
overlapped-wire acceptance bound for lookahead POTRF, and the
``ConfigurationError`` validation + 'auto'-never-pallas resolution rules.
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
from dlaf_tpu.comm.grid import COL_AXIS, ROW_AXIS
from dlaf_tpu.matrix.matrix import DistributedMatrix
from dlaf_tpu.ops import pallas_panel_exchange as ppe
from dlaf_tpu.ops import tile as t

SHAPES = [(1, 2), (2, 2), (2, 4)]
DTYPES = [np.float32, np.complex64]


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_state():
    """Release this module's executables when it finishes.

    Every parity case traces fresh under a flipped impl knob, so nothing
    here is reused by later modules — but the interpret-mode pallas rings
    plus the per-tier POTRF/TRSM e2e kernels leave a few hundred MB of
    compiled state alive in the long single-process tier-1 run, enough to
    push the XLA:CPU JIT over the edge on later large complex-SUMMA
    compiles (observed as a deterministic backend_compile segfault in
    test_multiplication on a 1-CPU host).  Dropping the caches restores
    the process shape later modules were developed against; they re-trace
    their own kernels anyway.
    """
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


def _impl(value):
    return _knobs(collectives_impl=value)


def _grid(comm_grids, shape):
    return next(g for g in comm_grids if tuple(g.grid_size) == shape)


def _run(grid, fn, *args):
    """Fresh jit per call (traces under the active impl; no cache reuse)."""
    f = coll.spmd(grid, lambda *xs: coll.relocal(fn(*[coll.local(x) for x in xs])))
    args = [jax.device_put(a, grid.stacked_sharding()) for a in args]
    return np.asarray(f(*args))


def _vs_v2(grid, fn, *args):
    """v2 is the reference (itself bit-checked against psum in
    test_collectives_v2.py, so agreement here closes the three-tier set)."""
    with _impl("v2"):
        ref = _run(grid, fn, *args)
    with _impl("pallas"):
        out = _run(grid, fn, *args)
    np.testing.assert_array_equal(ref, out)
    return ref


def _rand(shape, dtype, seed):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(shape)
    if np.issubdtype(dtype, np.complexfloating):
        x = x + 1j * rng.standard_normal(shape)
    return x.astype(dtype)


# ------------------------------------------------------------ property tests


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_bcast_parity(comm_grids, shape, dtype):
    grid = _grid(comm_grids, shape)
    pr, pc = shape
    x = _rand((pr, pc, 3, 4), dtype, seed=7)
    for axis, root in ((COL_AXIS, pc - 1), (ROW_AXIS, 0), (COL_AXIS, 0)):
        out = _vs_v2(grid, lambda v: coll.bcast(v, root, axis), x)
        # correctness against the replicated expectation, not just agreement
        for r in range(pr):
            for c in range(pc):
                src = (r, root) if axis == COL_AXIS else (root, c)
                np.testing.assert_array_equal(out[r, c], x[src])


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_bcast_traced_root_parity(comm_grids, shape, dtype):
    """Roots computed from a traced loop counter (the algorithms' k % P
    pattern) must agree between tiers too."""
    grid = _grid(comm_grids, shape)
    pr, pc = shape
    x = _rand((pr, pc, 2, 3), dtype, seed=11)

    def fn(v):
        k = jnp.sum(jnp.ones((), jnp.int32))  # traced 1
        return coll.bcast(v, k % pc, COL_AXIS)

    out = _vs_v2(grid, fn, x)
    for r in range(pr):
        for c in range(pc):
            np.testing.assert_array_equal(out[r, c], x[r, 1 % pc])


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_transpose_panel_parity(comm_grids, shape, dtype):
    grid = _grid(comm_grids, shape)
    pr, pc = shape
    mt = 5  # ragged vs both pr and pc
    ltr, ltc, mb = -(-mt // pr), -(-mt // pc), 2
    x = _rand((pr, pc, ltr, mb, mb), dtype, seed=17)
    out = _vs_v2(grid, lambda cp: coll.transpose_panel(cp, mt, ltc), x)
    # contributor for slot lj in column c is rank row jv % pr with its own cp
    for r in range(pr):
        for c in range(pc):
            for lj in range(ltc):
                j = lj * pc + c
                if j < mt:
                    want = x[j % pr, c, min(j // pr, ltr - 1)]
                else:
                    want = np.zeros((mb, mb), dtype)
                np.testing.assert_array_equal(out[r, c, lj], want)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_transpose_panel_rows_parity(comm_grids, shape, dtype):
    grid = _grid(comm_grids, shape)
    pr, pc = shape
    nt = 5
    ltr, ltc, mb = -(-nt // pr), -(-nt // pc), 2
    x = _rand((pr, pc, ltc, mb, mb), dtype, seed=19)
    out = _vs_v2(grid, lambda rp: coll.transpose_panel_rows(rp, nt, ltr), x)
    for r in range(pr):
        for c in range(pc):
            for li in range(ltr):
                i = li * pr + r
                if i < nt:
                    want = x[r, i % pc, min(i // pc, ltc - 1)]
                else:
                    want = np.zeros((mb, mb), dtype)
                np.testing.assert_array_equal(out[r, c, li], want)


@pytest.mark.parametrize("rs", [0, 1])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_transpose_panel_windowed_parity(comm_grids, shape, dtype, rs):
    grid = _grid(comm_grids, shape)
    pr, pc = shape
    mt = 5
    ltr, ltc, mb = -(-mt // pr), -(-mt // pc), 2
    L = max(ltr - rs, 1)
    x = _rand((pr, pc, L, mb, mb), dtype, seed=23 + rs)

    def fn(cp):
        _, myc = coll.my_rank()
        jv = jnp.arange(ltc) * pc + myc
        return coll.transpose_panel_windowed(cp, jv, rs, mt)

    _vs_v2(grid, fn, x)


@pytest.mark.parametrize("cs", [0, 1])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_transpose_panel_rows_windowed_parity(comm_grids, shape, dtype, cs):
    grid = _grid(comm_grids, shape)
    pr, pc = shape
    nt = 5
    ltr, ltc, mb = -(-nt // pr), -(-nt // pc), 2
    C = max(ltc - cs, 1)
    x = _rand((pr, pc, C, mb, mb), dtype, seed=29 + cs)

    def fn(rp):
        myr, _ = coll.my_rank()
        iv = jnp.arange(ltr) * pr + myr
        return coll.transpose_panel_rows_windowed(rp, iv, cs, nt)

    _vs_v2(grid, fn, x)


# ------------------------------------------------- the DMA kernel, interpret
#
# The compiled TPU path and the CPU path share the schedule but not the
# transport; these run the REAL remote-DMA kernel (make_async_remote_copy +
# send/recv semaphores + double-buffered landing slots) on the interpreter,
# which discharges remote copies for single-named-axis meshes only.


def _dma_ring(n, slots, w, contributors, seed):
    """contributors: slot -> owning rank.  Asserts the post-ring invariant:
    owned slots hold the owner's exact bytes on EVERY rank with have=1,
    unowned slots keep the local input with have=0."""
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip(f"needs {n} devices")
    mesh = Mesh(np.array(devs[:n]), ("x",))
    rng = np.random.default_rng(seed)
    y = rng.standard_normal((n, slots, w)).astype(np.float32)
    h = np.zeros((n, slots, 1), np.int32)
    for slot, rank in contributors.items():
        h[rank, slot, 0] = 1

    def fn(yl, hl):
        yl = yl.reshape(yl.shape[1:])  # strip the size-1 shard axis
        hl = hl.reshape(hl.shape[1:])
        oy, oh = ppe.dma_ring_exchange(yl, hl, "x", ("x",), True)
        return oy[None], oh[None]

    f = jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(P("x"), P("x")), out_specs=(P("x"), P("x")), check_vma=False
    ))
    oy, oh = f(y, h)
    oy, oh = np.asarray(oy), np.asarray(oh)
    for r in range(n):
        for s in range(slots):
            if s in contributors:
                np.testing.assert_array_equal(oy[r, s], y[contributors[s], s])
                assert oh[r, s, 0] == 1
            else:
                np.testing.assert_array_equal(oy[r, s], y[r, s])
                assert oh[r, s, 0] == 0


@pytest.mark.parametrize("n", [2, 4])
def test_dma_ring_kernel(n):
    # slot 1 unowned; owners chosen so payloads cross the whole ring
    _dma_ring(n, slots=3, w=8, contributors={0: n - 1, 2: 0}, seed=101)


def test_dma_ring_kernel_all_slots_owned():
    # every slot owned by a distinct rank: the full transpose_panel pattern,
    # and every hop of the double-buffered schedule carries fresh bytes
    _dma_ring(4, slots=4, w=16, contributors={0: 2, 1: 0, 2: 3, 3: 1}, seed=103)


def test_dma_ring_single_rank_identity():
    # n == 1: the exchange is the identity (no kernel launch at all)
    y = jnp.arange(12, dtype=jnp.float32).reshape(3, 4)
    h = jnp.ones((3, 1), jnp.int32)
    devs = jax.devices()
    mesh = Mesh(np.array(devs[:1]), ("x",))

    def fn(yl, hl):
        oy, oh = ppe.dma_ring_exchange(
            yl.reshape(yl.shape[1:]), hl.reshape(hl.shape[1:]), "x", ("x",), True
        )
        return oy[None], oh[None]

    f = jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(P("x"), P("x")), out_specs=(P("x"), P("x")), check_vma=False
    ))
    oy, oh = f(y[None], h[None])
    np.testing.assert_array_equal(np.asarray(oy)[0], np.asarray(y))
    np.testing.assert_array_equal(np.asarray(oh)[0], np.asarray(h))


# --------------------------------------------------------------- end-to-end


E2E_SHAPES = [(2, 2), (2, 4)]


@pytest.mark.parametrize("lookahead", [False, True])
@pytest.mark.parametrize("shape", E2E_SHAPES)
def test_cholesky_v2_vs_pallas(comm_grids, shape, lookahead):
    from dlaf_tpu.algorithms.cholesky import cholesky_factorization

    grid = _grid(comm_grids, shape)
    a = tu.random_hermitian_pd(40, np.float32, seed=31)

    def run():
        mat = DistributedMatrix.from_global(grid, np.tril(a), (8, 8))
        return cholesky_factorization("L", mat).to_global()

    with _knobs(cholesky_lookahead=lookahead):
        with _impl("v2"):
            ref = run()
        with _impl("pallas"):
            out = run()
    np.testing.assert_array_equal(ref, out)


@pytest.mark.parametrize("shape", E2E_SHAPES)
def test_trsm_v2_vs_pallas(comm_grids, shape):
    from dlaf_tpu.algorithms.triangular_solver import triangular_solver

    grid = _grid(comm_grids, shape)
    a = np.tril(tu.random_matrix(40, 40, np.float32, seed=37)) + 40 * np.eye(
        40, dtype=np.float32
    )
    b = tu.random_matrix(40, 24, np.float32, seed=41)

    def run():
        mat_a = DistributedMatrix.from_global(grid, a, (8, 8))
        mat_b = DistributedMatrix.from_global(grid, b, (8, 8))
        return triangular_solver(
            t.LEFT, t.LOWER, t.NO_TRANS, t.NON_UNIT, 1.0, mat_a, mat_b
        ).to_global()

    with _impl("v2"):
        ref = run()
    with _impl("pallas"):
        out = run()
    np.testing.assert_array_equal(ref, out)


def test_slow_collective_no_deadlock(grid_2x4):
    """Interconnect skew (every panel boundary stalled) must not deadlock
    the ring: the send-before-recv-wait ordering means a delayed rank
    stalls its neighbors, never a cycle.  The factorization completes with
    bits identical to the v2 tier's under the same fault."""
    from dlaf_tpu.algorithms.cholesky import cholesky_factorization
    from dlaf_tpu.testing import faults

    a = tu.random_hermitian_pd(32, np.float32, seed=53)
    mk = lambda: DistributedMatrix.from_global(grid_2x4, np.tril(a), (8, 8))
    # checkpoint_every=1 routes every panel through resilience.panel_boundary,
    # the slow_collective injection point
    with _impl("v2"):
        ref = cholesky_factorization("L", mk(), checkpoint_every=1).to_global()
    with _impl("pallas"), faults.slow_collective(0.05):
        out = cholesky_factorization("L", mk(), checkpoint_every=1).to_global()
    np.testing.assert_array_equal(ref, out)


# ------------------------------------------------------- overlap accounting


def test_lookahead_overlap_fraction(grid_2x4):
    """The acceptance bound: under the pallas tier at least half of the
    lookahead POTRF's modeled panel-exchange wire bytes are classified
    overlapped (issued under the trailing-update overlap windows)."""
    from dlaf_tpu.algorithms.cholesky import cholesky_factorization
    from dlaf_tpu.obs import comms as ocomms

    # fresh geometry (mt=6): comms counts are trace-time, so the kernel
    # must actually trace inside the start/stop bracket
    a = tu.random_hermitian_pd(48, np.float32, seed=59)
    with _impl("pallas"), _knobs(cholesky_lookahead=True):
        ocomms.start()
        mat = DistributedMatrix.from_global(grid_2x4, np.tril(a), (8, 8))
        cholesky_factorization("L", mat).data.block_until_ready()
        acc = ocomms.stop()
    rows = [r for r in ocomms.as_records(acc)
            if r["collective"].endswith("_pallas")]
    tot = sum(r["modeled_wire_bytes"] for r in rows)
    ov = sum(r["overlapped_wire_bytes"] for r in rows)
    assert tot > 0, "pallas collectives must have traced inside the bracket"
    assert ov >= 0.5 * tot, (ov, tot, rows)


def test_psum_v2_never_overlapped(grid_2x4):
    """The reduce tiers lower to XLA collectives — hard barriers — so their
    records never count as overlapped, windows or not."""
    from dlaf_tpu.algorithms.cholesky import cholesky_factorization
    from dlaf_tpu.obs import comms as ocomms

    a = tu.random_hermitian_pd(48, np.float32, seed=61)
    for tier in ("psum", "v2"):
        with _impl(tier), _knobs(cholesky_lookahead=True):
            ocomms.start()
            mat = DistributedMatrix.from_global(grid_2x4, np.tril(a), (8, 8))
            cholesky_factorization("L", mat).data.block_until_ready()
            acc = ocomms.stop()
        assert all(r["overlapped_wire_bytes"] == 0
                   for r in ocomms.as_records(acc)), tier


# ------------------------------------------------- concurrency / fallback


def test_collective_ids_distinct_and_stable():
    """Kernels sharing a collective_id share barrier-semaphore state and
    must never be live concurrently; every call-site class the scheduler
    could overlap (the whole point of the tier) gets a distinct id."""
    classes = [(k, a) for k in ("bcast", "exchange") for a in ("r", "c")]
    ids = [ppe.collective_id_for(k, a) for k, a in classes]
    ids.append(ppe.FUSED_COLLECTIVE_ID)
    assert len(set(ids)) == len(ids)
    # stable across calls (same trace order on every SPMD rank)
    for k, a in classes:
        assert ppe.collective_id_for(k, a) == ppe.collective_id_for(k, a)
    # unknown classes allocate deterministically on first use, off the
    # reserved range
    extra = ppe.collective_id_for("exchange", "b")
    assert extra == ppe.collective_id_for("exchange", "b")
    assert extra not in ids


def test_overlap_window_thread_isolated():
    """The window depth is a ContextVar: dlaf_tpu.serve traces on an async
    pool, so a window open on one thread must not classify a concurrent
    trace's records as overlapped."""
    import threading

    seen = {}

    def probe():
        seen["other_thread"] = coll._overlap_depth.get()

    with coll.overlap_window():
        t = threading.Thread(target=probe)
        t.start()
        t.join()
        seen["inside"] = coll._overlap_depth.get()
    seen["after"] = coll._overlap_depth.get()
    assert seen == {"other_thread": 0, "inside": 1, "after": 0}


def test_fused_panel_bcast_decline_and_propagate(monkeypatch):
    """_fused_panel_bcast falls back (with a one-time warning) only on the
    narrow kernel-unavailable declines; real trace-time bugs propagate
    instead of silently disengaging the fused tier."""
    import warnings

    from dlaf_tpu.algorithms import cholesky as ch

    d = np.eye(128, dtype=np.float32)
    xc = np.zeros((1, 128, 128), np.float32)
    below = np.ones((1,), bool)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(coll, "axis_size", lambda axis: 2)
    monkeypatch.setattr(ch, "_fused_decline_warned", False)

    def raise_(e):
        def fn(*a, **k):
            raise e

        return fn

    with _impl("pallas"):
        monkeypatch.setattr(
            ppe, "fused_factor_bcast", raise_(NotImplementedError("no mosaic"))
        )
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            assert ch._fused_panel_bcast(d, xc, below, 0, False) is None
        assert any("declined" in str(w.message) for w in rec)
        monkeypatch.setattr(ppe, "fused_factor_bcast", raise_(RuntimeError("boom")))
        with pytest.raises(RuntimeError, match="boom"):
            ch._fused_panel_bcast(d, xc, below, 0, False)
    # off-tier: static gate declines before touching the kernel, no warning
    with _impl("v2"), warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        assert ch._fused_panel_bcast(d, xc, below, 0, False) is None
    assert not rec


# ------------------------------------------------------ validation / policy


def test_update_rejects_bad_impl():
    from dlaf_tpu.health import ConfigurationError, DlafError

    tp = tune.get_tune_parameters()
    old = tp.collectives_impl
    with pytest.raises(ConfigurationError, match="collectives_impl"):
        tp.update(collectives_impl="palas")
    # the typo was rejected before assignment; also classified DlafError
    assert tp.collectives_impl == old
    assert issubclass(ConfigurationError, DlafError)
    assert issubclass(ConfigurationError, ValueError)


def test_env_typo_raises_at_resolution(comm_grids):
    """A value that bypassed update() (env-injected) raises the structured
    error when the collectives layer resolves the knob at trace time."""
    from dlaf_tpu.health import ConfigurationError

    grid = _grid(comm_grids, (2, 2))
    x = np.zeros((2, 2, 1), np.float32)
    tp = tune.get_tune_parameters()
    old = tp.collectives_impl
    tp.collectives_impl = "pallaz"  # direct set: the env-read path's shape
    try:
        with pytest.raises(ConfigurationError, match="collectives_impl"):
            _run(grid, lambda v: coll.bcast(v, 0, COL_AXIS), x)
    finally:
        tp.collectives_impl = old


def test_auto_never_resolves_pallas():
    """pallas stays explicit-opt-in until the tpu_day stage-5f A/B promotes
    it; on the CPU test mesh 'auto' is psum, and never pallas anywhere."""
    with _impl("auto"):
        key = coll.collectives_trace_key()
        assert key != "pallas"
        assert key == "psum"  # the CPU-mesh resolution


def test_pallas_in_trace_key():
    """Compiled-kernel caches key on collectives_trace_key(); the pallas
    tier must show up there or flipping the knob would reuse v2 traces."""
    with _impl("pallas"):
        assert coll.collectives_trace_key() == "pallas"
