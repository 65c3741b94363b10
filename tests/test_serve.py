"""dlaf_tpu.serve — batched solver service (ISSUE 5).

Covers the three layers: the vmapped batch drivers (bit-exactness against
the single-problem SPMD kernels, per-element info isolation, both sharding
modes), the shape-bucketed compile cache (bucket policy, compile counts,
LRU eviction, obs events), and the async SolverPool (futures, grouping,
backpressure, deadlines).  The throughput acceptance test at the bottom
asserts the B=16 N=512 f32 batched posv beats a Python loop of single
solver calls on the full mesh by >= 3x post-warmup.
"""
import threading
import time
from contextlib import contextmanager

import numpy as np
import pytest

import dlaf_tpu.testing as tu
from dlaf_tpu import serve, tune
from dlaf_tpu.algorithms.cholesky import cholesky_factorization
from dlaf_tpu.algorithms.solver import positive_definite_solver
from dlaf_tpu.health import (
    DeadlineExceededError,
    DeviceUnresponsiveError,
    DistributionError,
    QueueFullError,
)
from dlaf_tpu.matrix.matrix import DistributedMatrix
from dlaf_tpu.obs import metrics as om
from dlaf_tpu.serve import bucketing
from dlaf_tpu.testing import faults


@contextmanager
def _tuned(**kw):
    """Apply tune overrides for one test, restore defaults+env after."""
    tune.initialize(**kw)
    try:
        yield
    finally:
        tune.initialize()


def _spd_batch(B, n, dtype, seed=0):
    return np.stack(
        [tu.random_hermitian_pd(n, dtype, seed=seed + i) for i in range(B)]
    )


# ---------------------------------------------------------------- bucketing


def test_bucket_policy():
    with _tuned(serve_buckets="256,512,1024"):
        assert bucketing.bucket_table() == (256, 512, 1024)
        assert bucketing.bucket_for(1) == 256
        assert bucketing.bucket_for(256) == 256
        assert bucketing.bucket_for(257) == 512
        assert bucketing.bucket_for(1024) == 1024
        # beyond the largest bucket: round up to a multiple of it
        assert bucketing.bucket_for(1025) == 2048
        assert bucketing.bucket_for(2049) == 3072
    # env-shaped overrides parse; garbage fails loudly
    with _tuned(serve_buckets=" 64 , 32 "):
        assert bucketing.bucket_table() == (32, 64)
    for bad in ("", "0", "abc", "32,-4"):
        with _tuned(serve_buckets=bad):
            with pytest.raises(DistributionError, match="serve_buckets"):
                bucketing.bucket_table()


def test_serving_token_scopes_trace_key():
    from dlaf_tpu.plan import core as plan_core
    from dlaf_tpu.serve.context import serve_trace_key, serving

    assert serve_trace_key() is None
    with serving(("potrf", 256)):
        assert serve_trace_key() == ("potrf", 256)
        # the plan layer folds the token into every key via trace_suffix
        assert ("potrf", 256) in plan_core.trace_suffix()
        with serving("inner"):
            assert serve_trace_key() == "inner"
        assert serve_trace_key() == ("potrf", 256)
    assert serve_trace_key() is None
    # exception-safe restore
    with pytest.raises(RuntimeError):
        with serving("tok"):
            raise RuntimeError("boom")
    assert serve_trace_key() is None


def test_serve_trace_knobs_carry_trsm_lookahead():
    """DLAF001 regression: ``trsm_lookahead`` selects the posv matrix-mode
    solve kernel inside the cached builder, so the serve executable key
    must separate the two variants — the knob now rides every key via the
    plan layer's ambient trace suffix instead of a per-site knob tuple."""
    from dlaf_tpu.plan import core as plan_core

    with _tuned(trsm_lookahead=True):
        on = plan_core.trace_suffix()
    with _tuned(trsm_lookahead=False):
        off = plan_core.trace_suffix()
    assert on != off


# ----------------------------------------------------- batched bit-exactness


@pytest.mark.parametrize("uplo", "LU")
@pytest.mark.parametrize("dtype", [np.float32, np.complex64], ids=str)
def test_batched_potrf_bitexact_vs_single(grid_1x1, uplo, dtype):
    """Batch-sharded potrf must be BIT-IDENTICAL to a loop of single
    ``cholesky_factorization`` calls (return_info=True routes the single
    call through the same SPMD kernel the batch vmaps)."""
    B, n, nb = 3, 48, 16
    a = _spd_batch(B, n, dtype, seed=10)
    with _tuned(serve_buckets="48"):
        l, info = serve.batched_cholesky_factorization(
            uplo, a, block_size=nb, shard_batch=True,
            cache=serve.CompiledCache(),
        )
    assert l.shape == (B, n, n) and info.shape == (B,)
    assert np.all(info == 0)
    for i in range(B):
        mat = DistributedMatrix.from_global(grid_1x1, a[i], (nb, nb))
        fac, inf = cholesky_factorization(uplo, mat, return_info=True)
        assert int(inf) == 0
        np.testing.assert_array_equal(np.asarray(fac.to_global()), l[i])


@pytest.mark.parametrize("uplo", "LU")
@pytest.mark.parametrize("dtype", [np.float32, np.complex64], ids=str)
def test_batched_posv_bitexact_vs_single(grid_1x1, uplo, dtype):
    B, n, k, nb = 3, 48, 3, 16
    a = _spd_batch(B, n, dtype, seed=20)
    rng = np.random.default_rng(21)
    b = rng.standard_normal((B, n, k)).astype(dtype)
    with _tuned(serve_buckets="48"):
        x, info = serve.batched_positive_definite_solver(
            uplo, a, b, block_size=nb, shard_batch=True,
            cache=serve.CompiledCache(),
        )
    assert x.shape == (B, n, k) and np.all(info == 0)
    for i in range(B):
        mat_a = DistributedMatrix.from_global(grid_1x1, a[i], (nb, nb))
        mat_b = DistributedMatrix.from_global(grid_1x1, b[i], (nb, nb))
        xr, inf = positive_definite_solver(uplo, mat_a, mat_b, return_info=True)
        assert int(inf) == 0
        np.testing.assert_array_equal(np.asarray(xr.to_global()), x[i])


def test_batched_potrf_bucket_padding_exact(grid_1x1):
    """An n that doesn't fill its bucket is padded with an identity block:
    the leading n x n factor matches the unpadded factorization to a few
    ulps of the factor's scale.  Bit identity does not hold on XLA:CPU:
    the padded and unpadded programs contract different tile counts, the
    dots are blocked differently, and a handful of entries differ in the
    last bits (3 of 1600, by 7.5e-9, at this seed)."""
    B, n, nb = 2, 40, 8
    a = _spd_batch(B, n, np.float32, seed=30)
    with _tuned(serve_buckets="64"):
        l, info = serve.batched_cholesky_factorization(
            "L", a, block_size=nb, shard_batch=True,
            cache=serve.CompiledCache(),
        )
    assert np.all(info == 0)
    for i in range(B):
        mat = DistributedMatrix.from_global(grid_1x1, a[i], (nb, nb))
        fac, _ = cholesky_factorization("L", mat, return_info=True)
        scale = np.finfo(np.float32).eps * np.abs(l[i]).max()
        np.testing.assert_allclose(
            np.asarray(fac.to_global()), l[i], rtol=0, atol=4 * scale)


def test_batched_posv_single_rhs_squeeze():
    B, n = 2, 24
    a = _spd_batch(B, n, np.float32, seed=40)
    rng = np.random.default_rng(41)
    b = rng.standard_normal((B, n)).astype(np.float32)
    with _tuned(serve_buckets="24"):
        x, info = serve.batched_positive_definite_solver(
            "L", a, b, block_size=8, cache=serve.CompiledCache()
        )
    assert x.shape == (B, n) and np.all(info == 0)
    for i in range(B):
        resid = np.abs(a[i] @ x[i] - b[i]).max()
        assert resid < 1e-3


def test_batched_input_validation():
    a = _spd_batch(2, 16, np.float32)
    rng = np.random.default_rng(0)
    with pytest.raises(DistributionError, match="uplo"):
        serve.batched_cholesky_factorization("X", a)
    with pytest.raises(DistributionError, match="stack of square"):
        serve.batched_cholesky_factorization("L", a[0])
    with pytest.raises(DistributionError, match="stack of square"):
        serve.batched_cholesky_factorization("L", a[:, :, :8])
    with pytest.raises(DistributionError, match="b must be"):
        serve.batched_positive_definite_solver(
            "L", a, rng.standard_normal((3, 16, 2)).astype(np.float32)
        )
    with pytest.raises(DistributionError, match="b must be"):
        serve.batched_positive_definite_solver(
            "L", a, rng.standard_normal((2, 8, 2)).astype(np.float32)
        )


def test_batched_info_isolation_break_spd():
    """One indefinite element must report its own pivot without poisoning
    the factors or info codes of its batch neighbours."""
    B, n, nb = 4, 32, 8
    a = _spd_batch(B, n, np.float32, seed=50)
    bad = a.copy()
    bad[2] = faults.break_spd(bad[2], 5)
    with _tuned(serve_buckets="32"):
        cache = serve.CompiledCache()
        l_good, info_good = serve.batched_cholesky_factorization(
            "L", a, block_size=nb, shard_batch=True, cache=cache
        )
        l_bad, info_bad = serve.batched_cholesky_factorization(
            "L", bad, block_size=nb, shard_batch=True, cache=cache
        )
    assert np.all(info_good == 0)
    assert info_bad[2] == 6  # first failing pivot, LAPACK 1-based
    assert np.all(info_bad[[0, 1, 3]] == 0)
    for i in (0, 1, 3):
        np.testing.assert_array_equal(l_good[i], l_bad[i])


def test_batched_posv_matrix_mode_residual():
    """shard_batch=False: the matrix axes stay sharded over the full grid
    and the batch is a sequential vmap — the large-N serving mode."""
    B, n, nb = 3, 48, 16
    a = _spd_batch(B, n, np.float32, seed=60)
    rng = np.random.default_rng(61)
    b = rng.standard_normal((B, n, 2)).astype(np.float32)
    with _tuned(serve_buckets="48"):
        cache = serve.CompiledCache()
        for uplo in "LU":
            x, info = serve.batched_positive_definite_solver(
                uplo, a, b, block_size=nb, shard_batch=False, cache=cache
            )
            assert np.all(info == 0)
            resid = max(np.abs(a[i] @ x[i] - b[i]).max() for i in range(B))
            assert resid < 1e-3


def test_batched_eigensolver():
    B, n = 3, 32
    a = _spd_batch(B, n, np.float32, seed=70)
    with _tuned(serve_buckets="32"):
        w, v, info = serve.batched_eigensolver(
            "L", a, cache=serve.CompiledCache()
        )
    assert w.shape == (B, n) and v.shape == (B, n, n)
    assert np.all(info == 0)
    for i in range(B):
        err = np.abs(a[i] @ v[i] - v[i] * w[i][None, :]).max()
        assert err < 1e-3
        assert np.all(np.diff(w[i]) >= 0)
    # bucket-padded order: pad eigenpairs are compacted away and the true
    # spectrum matches the exact-fit run
    with _tuned(serve_buckets="64"):
        w2, v2, info2 = serve.batched_eigensolver(
            "L", a, cache=serve.CompiledCache()
        )
    assert w2.shape == (B, n) and np.all(info2 == 0)
    for i in range(B):
        np.testing.assert_allclose(w2[i], w[i], atol=1e-4)
        err = np.abs(a[i] @ v2[i] - v2[i] * w2[i][None, :]).max()
        assert err < 1e-3
    # eigh serves batch mode only
    with pytest.raises(DistributionError, match="shard_batch"):
        serve.batched_eigensolver("L", a, shard_batch=False)


# --------------------------------------------------------------- compile cache


def test_mixed_shape_stream_compiles_one_executable_per_bucket(tmp_path):
    """ISSUE acceptance: a stream of mixed shapes hitting 3 buckets must
    compile <= 3 executables — counted both by the cache's own counters
    and by the obs.metrics serve events."""
    path = str(tmp_path / "serve_cache.jsonl")
    om.enable(path)
    try:
        with _tuned(serve_buckets="16,32,48"):
            cache = serve.CompiledCache(capacity=8)
            stream = [12, 24, 40, 16, 30, 48, 9, 22, 33]  # 3 buckets, 9 shapes
            for i, n in enumerate(stream):
                a = _spd_batch(2, n, np.float32, seed=100 + i)
                _, info = serve.batched_cholesky_factorization(
                    "L", a, block_size=8, shard_batch=True, cache=cache
                )
                assert np.all(info == 0)
        assert len(cache) == 3
        assert cache.counters["miss"] == 3
        assert cache.counters["hit"] == len(stream) - 3
        assert cache.counters["evict"] == 0
        assert cache.hit_rate() == pytest.approx((len(stream) - 3) / len(stream))
    finally:
        om.close()
    recs = [r for r in om.read_jsonl(path) if r["kind"] == "serve"]
    compiles = [r for r in recs if r["event"] == "compile"]
    assert 0 < len(compiles) <= 3
    assert all(r["seconds"] > 0 for r in compiles)
    assert sum(r["event"] == "cache_miss" for r in recs) == 3
    assert sum(r["event"] == "cache_hit" for r in recs) == len(stream) - 3


def test_cache_eviction_under_cap(tmp_path):
    """ISSUE acceptance: with capacity 2, a third bucket evicts the LRU
    entry, the eviction is counted and emitted, and re-touching the
    evicted bucket recompiles (miss, not stale hit)."""
    path = str(tmp_path / "serve_evict.jsonl")
    om.enable(path)
    try:
        with _tuned(serve_buckets="16,32,48"):
            cache = serve.CompiledCache(capacity=2)
            for n in (16, 32, 48):  # third insert evicts bucket 16
                a = _spd_batch(2, n, np.float32, seed=200 + n)
                serve.batched_cholesky_factorization(
                    "L", a, block_size=8, shard_batch=True, cache=cache
                )
            assert len(cache) == 2
            assert cache.counters == {"hit": 0, "miss": 3, "evict": 1}
            # bucket 16 was evicted: a revisit is a fresh miss (and evicts
            # 32, now the least recently used)
            a = _spd_batch(2, 16, np.float32, seed=201)
            serve.batched_cholesky_factorization(
                "L", a, block_size=8, shard_batch=True, cache=cache
            )
            assert cache.counters == {"hit": 0, "miss": 4, "evict": 2}
    finally:
        om.close()
    recs = [r for r in om.read_jsonl(path) if r["kind"] == "serve"]
    assert sum(r["event"] == "cache_evict" for r in recs) == 2


# ---------------------------------------------------------------- SolverPool


def _gated_pool(**kw):
    """Pool whose worker blocks before each dispatch until gate.set() —
    makes queue-occupancy tests deterministic."""
    pool = serve.SolverPool(**kw)
    gate = threading.Event()
    orig = pool._dispatch

    def gated(key, reqs):
        gate.wait(60.0)
        orig(key, reqs)

    pool._dispatch = gated
    return pool, gate


def _drain_to_worker(pool, timeout=10.0):
    t0 = time.monotonic()
    while pool.pending() and time.monotonic() - t0 < timeout:
        time.sleep(0.005)
    assert pool.pending() == 0


def test_pool_end_to_end_mixed_kinds():
    n, nb = 24, 8
    a = tu.random_hermitian_pd(n, np.float32, seed=80)
    rng = np.random.default_rng(81)
    b1 = rng.standard_normal((n, 2)).astype(np.float32)
    bvec = rng.standard_normal(n).astype(np.float32)
    with _tuned(serve_buckets="24"):
        with serve.SolverPool(block_size=nb, cache=serve.CompiledCache()) as pool:
            f_potrf = pool.submit("potrf", "L", a)
            f_posv = pool.submit("posv", "L", a, b1)
            f_vec = pool.submit("posv", "L", a, bvec)
            f_eigh = pool.submit("eigh", "L", a)
            r = pool.result(f_potrf, timeout=300)
            assert r.kind == "potrf" and r.info == 0 and r.queue_s >= 0.0
            low = np.tril(r.x)
            assert np.abs(low @ low.T - a).max() < 1e-3
            r = pool.result(f_posv, timeout=300)
            assert r.x.shape == (n, 2)
            assert np.abs(a @ r.x - b1).max() < 1e-3
            r = pool.result(f_vec, timeout=300)
            assert r.x.shape == (n,)  # 1-D in, 1-D out
            assert np.abs(a @ r.x - bvec).max() < 1e-3
            r = pool.result(f_eigh, timeout=300)
            assert r.info == 0
            assert np.abs(a @ r.v - r.v * r.w[None, :]).max() < 1e-3
            assert pool.pending() == 0


def test_pool_groups_mixed_n_into_one_dispatch():
    """Two requests with different n in the same bucket must share ONE
    compiled executable (one cache miss) and both come back sliced to
    their own order."""
    rng = np.random.default_rng(90)
    a1 = tu.random_hermitian_pd(20, np.float32, seed=91)
    a2 = tu.random_hermitian_pd(28, np.float32, seed=92)
    b1 = rng.standard_normal((20, 2)).astype(np.float32)
    b2 = rng.standard_normal((28, 2)).astype(np.float32)
    with _tuned(serve_buckets="32"):
        cache = serve.CompiledCache()
        pool, gate = _gated_pool(block_size=8, cache=cache)
        with pool:
            f1 = pool.submit("posv", "L", a1, b1)
            f2 = pool.submit("posv", "L", a2, b2)
            gate.set()
            r1, r2 = pool.result(f1, 300), pool.result(f2, 300)
        assert r1.x.shape == (20, 2) and r2.x.shape == (28, 2)
        assert np.abs(a1 @ r1.x - b1).max() < 1e-3
        assert np.abs(a2 @ r2.x - b2).max() < 1e-3
        assert cache.counters["miss"] == 1  # one bucket-32 executable


def test_pool_backpressure_queue_full():
    n = 16
    a = tu.random_hermitian_pd(n, np.float32, seed=95)
    with _tuned(serve_buckets="16"):
        pool, gate = _gated_pool(
            max_queue=1, block_size=8, cache=serve.CompiledCache()
        )
        with pool:
            f1 = pool.submit("potrf", "L", a)  # worker picks this up
            _drain_to_worker(pool)             # ...and blocks on the gate
            f2 = pool.submit("potrf", "L", a)  # fills the queue (cap 1)
            with pytest.raises(QueueFullError) as exc:
                pool.submit("potrf", "L", a)
            assert exc.value.size == 1 and exc.value.capacity == 1
            gate.set()
            assert pool.result(f1, 300).info == 0
            assert pool.result(f2, 300).info == 0


def test_pool_deadline_expires_in_queue():
    """A request whose budget is gone by dispatch time fails with
    DeadlineExceededError WITHOUT being dispatched; queue neighbours with
    budget still complete.  Compile grace is pinned off — this asserts the
    bare expiry path; the grace-covered cold path has its own tests."""
    n = 16
    a = tu.random_hermitian_pd(n, np.float32, seed=96)
    with _tuned(serve_buckets="16", serve_compile_grace_s=0.0):
        pool, gate = _gated_pool(block_size=8, cache=serve.CompiledCache())
        with pool:
            f_dead = pool.submit("potrf", "L", a, deadline_s=0.0)
            f_live = pool.submit("potrf", "L", a)
            gate.set()
            with pytest.raises(DeadlineExceededError):
                pool.result(f_dead, 300)
            assert pool.result(f_live, 300).info == 0


def test_pool_close_cancels_stranded_and_rejects_submit():
    n = 16
    a = tu.random_hermitian_pd(n, np.float32, seed=97)
    with _tuned(serve_buckets="16"):
        pool, gate = _gated_pool(block_size=8, cache=serve.CompiledCache())
        f1 = pool.submit("potrf", "L", a)
        _drain_to_worker(pool)
        f2 = pool.submit("potrf", "L", a)  # still queued when we close
        closer = threading.Thread(target=pool.close)
        closer.start()
        t0 = time.monotonic()
        while not f2.cancelled() and time.monotonic() - t0 < 10.0:
            time.sleep(0.005)
        assert f2.cancelled()  # stranded request cancelled at close
        with pytest.raises(DistributionError, match="closed"):
            pool.submit("potrf", "L", a)
        gate.set()  # let the in-flight dispatch finish; close() then joins
        closer.join(timeout=60.0)
        assert not closer.is_alive()
        assert pool.result(f1, 300).info == 0  # in-flight work still lands
        pool.close()  # idempotent


def test_pool_submit_validation():
    a = tu.random_hermitian_pd(16, np.float32, seed=98)
    with serve.SolverPool(cache=serve.CompiledCache()) as pool:
        with pytest.raises(DistributionError, match="kind"):
            pool.submit("getrf", "L", a)
        with pytest.raises(DistributionError, match="square"):
            pool.submit("potrf", "L", a[:8])
        with pytest.raises(DistributionError, match="right-hand side"):
            pool.submit("posv", "L", a)
        with pytest.raises(DistributionError, match="right-hand side"):
            pool.submit("potrf", "L", a, a[:, 0])
        with pytest.raises(DistributionError, match="b must be"):
            pool.submit("posv", "L", a, np.zeros((8, 2), np.float32))
    with pytest.raises(DistributionError, match="bounds"):
        serve.SolverPool(max_queue=0)


def test_pool_info_codes_resolve_not_reject():
    """An indefinite matrix is a RESULT (info != 0), not an infrastructure
    failure: the future resolves and neighbours are untouched."""
    n = 16
    good = tu.random_hermitian_pd(n, np.float32, seed=99)
    bad = faults.break_spd(good.copy(), 4)
    with _tuned(serve_buckets="16"):
        with serve.SolverPool(block_size=8, cache=serve.CompiledCache()) as pool:
            f_bad = pool.submit("potrf", "L", bad)
            f_good = pool.submit("potrf", "L", good)
            assert pool.result(f_bad, 300).info == 5
            assert pool.result(f_good, 300).info == 0


def test_pool_racing_submitters_typed_backpressure():
    """ISSUE 7 satellite: N threads racing into a full queue each get a
    TYPED QueueFullError — no hangs, and every accepted request is
    dispatched exactly once."""
    n_threads, cap = 8, 2
    a = tu.random_hermitian_pd(16, np.float32, seed=400)
    with _tuned(serve_buckets="16"):
        pool, gate = _gated_pool(
            max_queue=cap, block_size=8, cache=serve.CompiledCache()
        )
        dispatched = []
        orig = pool._dispatch

        def recording(key, reqs):
            dispatched.extend(id(r.future) for r in reqs)
            orig(key, reqs)

        pool._dispatch = recording
        try:
            # worker holds one request at the gate; the queue is now empty
            first = pool.submit("potrf", "L", a)
            _drain_to_worker(pool)
            start = threading.Barrier(n_threads)
            outcomes = [None] * n_threads

            def racer(i):
                start.wait()
                try:
                    outcomes[i] = pool.submit("potrf", "L", a)
                except QueueFullError as e:
                    outcomes[i] = e

            threads = [threading.Thread(target=racer, args=(i,))
                       for i in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60.0)
            assert not any(t.is_alive() for t in threads)  # no hangs
            accepted = [o for o in outcomes if not isinstance(o, QueueFullError)]
            rejected = [o for o in outcomes if isinstance(o, QueueFullError)]
            assert len(accepted) == cap  # exactly the queue capacity got in
            assert len(rejected) == n_threads - cap
            for e in rejected:
                assert e.capacity == cap and e.size >= cap
            gate.set()
            assert first.result(300).info == 0
            for f in accepted:
                assert f.result(300).info == 0
            # exactly once: every accepted future dispatched a single time
            assert sorted(dispatched) == sorted(
                {id(f) for f in [first] + accepted}
            )
        finally:
            gate.set()
            pool.close()


# ---------------------------------------------------------- cold-start grace


def test_pool_compile_grace_covers_cold_dispatch(tmp_path):
    """ISSUE 7 satellite: the FIRST dispatch of a group budgets compile
    time separately — a tight deadline that could never cover compilation
    still completes cold, and the grace consumption is an obs event."""
    path = str(tmp_path / "grace.jsonl")
    a = tu.random_hermitian_pd(16, np.float32, seed=500)
    om.enable(path)
    try:
        with _tuned(serve_buckets="16", serve_compile_grace_s=120.0):
            with serve.SolverPool(block_size=8,
                                  cache=serve.CompiledCache()) as pool:
                # budget far smaller than any compile, but the group is cold
                f = pool.submit("potrf", "L", a, deadline_s=1.0)
                assert pool.result(f, 300).info == 0
                # the group is warm now: a spent budget sheds pre-dispatch
                f2 = pool.submit("potrf", "L", a, deadline_s=0.0)
                with pytest.raises(DeadlineExceededError):
                    pool.result(f2, 300)
    finally:
        om.close()
    recs = [r for r in om.read_jsonl(path) if r["kind"] == "serve"]
    grace = [r for r in recs if r["event"] == "compile_grace"]
    assert len(grace) == 1
    assert grace[0]["op"] == "potrf" and grace[0]["grace_s"] == 120.0
    assert grace[0]["budget_s"] > 120.0


def test_pool_failed_cold_dispatch_keeps_group_cold(monkeypatch):
    """REVIEW regression: a cold dispatch that dies before its compile
    lands must NOT mark the group warm — the next request of that group
    still gets the compile grace instead of re-creating the cold-replica
    shedding the knob exists to fix."""
    from dlaf_tpu.serve import batched

    a = tu.random_hermitian_pd(16, np.float32, seed=502)
    calls = {"n": 0}
    real = batched.batched_cholesky_factorization

    def flaky(*args, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise DeviceUnresponsiveError(
                message="injected transient fault before first compile"
            )
        return real(*args, **kw)

    monkeypatch.setattr(batched, "batched_cholesky_factorization", flaky)
    with _tuned(serve_buckets="16", serve_compile_grace_s=120.0):
        with serve.SolverPool(block_size=8, cache=serve.CompiledCache()) as pool:
            f1 = pool.submit("potrf", "L", a, deadline_s=1.0)
            with pytest.raises(DeviceUnresponsiveError):
                pool.result(f1, 300)
            # still cold: a budget far too small for any compile completes
            # only because the grace applies to this retry too
            f2 = pool.submit("potrf", "L", a, deadline_s=0.05)
            assert pool.result(f2, 300).info == 0
            assert calls["n"] == 2


def test_pool_no_grace_sheds_cold_expired():
    """With the grace knob zeroed, PR-5 semantics return: a cold request
    whose budget is spent sheds without dispatching."""
    a = tu.random_hermitian_pd(16, np.float32, seed=501)
    with _tuned(serve_buckets="16", serve_compile_grace_s=0.0):
        with serve.SolverPool(block_size=8, cache=serve.CompiledCache()) as pool:
            f = pool.submit("potrf", "L", a, deadline_s=0.0)
            with pytest.raises(DeadlineExceededError):
                pool.result(f, 300)


# ------------------------------------------------------------- adopt / drain


def test_pool_drain_adopt_preserves_futures():
    """drain() hands queued requests (futures intact) to a sibling's
    adopt(): the ORIGINAL futures resolve from the adopting pool."""
    a = tu.random_hermitian_pd(16, np.float32, seed=600)
    with _tuned(serve_buckets="16"):
        cache = serve.CompiledCache()
        pool_a, gate = _gated_pool(block_size=8, max_batch=1, cache=cache)
        try:
            with serve.SolverPool(block_size=8, cache=cache) as pool_b:
                f_flight = pool_a.submit("potrf", "L", a)
                _drain_to_worker(pool_a)  # worker holds it at the gate
                queued = [pool_a.submit("potrf", "L",
                                        tu.random_hermitian_pd(
                                            16, np.float32, seed=601 + i))
                          for i in range(3)]
                drained = pool_a.drain()
                assert len(drained) == 3 and pool_a.pending() == 0
                assert pool_b.adopt(drained) == []  # all fit
                for f in queued:
                    assert f.result(timeout=300).info == 0  # resolved by b
                gate.set()
                assert f_flight.result(timeout=300).info == 0
        finally:
            gate.set()
            pool_a.close()


def test_pool_adopt_returns_overflow_untouched():
    a = tu.random_hermitian_pd(16, np.float32, seed=610)
    with _tuned(serve_buckets="16"):
        cache = serve.CompiledCache()
        pool, gate = _gated_pool(max_queue=1, block_size=8, cache=cache)
        try:
            f0 = pool.submit("potrf", "L", a)
            _drain_to_worker(pool)
            reqs = [serve.make_request("potrf", "L", a) for _ in range(3)]
            overflow = pool.adopt(reqs)
            assert overflow == reqs[1:]  # capacity 1: the tail comes back
            assert all(not r.future.done() for r in overflow)  # untouched
            gate.set()
            assert f0.result(300).info == 0
            assert reqs[0].future.result(timeout=300).info == 0
            # a closed pool adopts nothing
            pool.close()
            assert pool.adopt(overflow) == overflow
        finally:
            gate.set()
            pool.close()


def test_pool_future_callbacks_run_outside_exec_lock():
    """DLAF004 regression: ``_dispatch`` used to hold the module
    ``_EXEC_LOCK`` (a plain, non-reentrant Lock) while completing futures.
    Done-callbacks run synchronously on the dispatcher thread, so any
    callback touching the serve layer — a resubmit, anything that
    dispatches behind the same lock — deadlocked.  Futures must complete
    only after the lock drops."""
    from dlaf_tpu.serve import pool as pool_mod

    a = tu.random_hermitian_pd(16, np.float32, seed=620)
    with _tuned(serve_buckets="16"):
        pool, gate = _gated_pool(block_size=8, cache=serve.CompiledCache())
        with pool:
            fut = pool.submit("potrf", "L", a)
            acquired = []
            fired = threading.Event()

            def grab_exec_lock(_f):
                ok = pool_mod._EXEC_LOCK.acquire(timeout=5.0)
                if ok:
                    pool_mod._EXEC_LOCK.release()
                acquired.append(ok)
                fired.set()

            # the worker is parked at the gate, so the callback is attached
            # before the dispatch can possibly complete
            fut.add_done_callback(grab_exec_lock)
            gate.set()
            assert pool.result(fut, timeout=300).info == 0
            assert fired.wait(30.0)
            assert acquired == [True]


# --------------------------------------------------------- cache event labels


def test_cache_events_carry_bucket_labels(tmp_path):
    """ISSUE 7 satellite: hit/miss/evict events carry structured
    (op, n, dtype) labels so report_metrics can attribute churn."""
    from dlaf_tpu.serve.bucketing import key_labels

    assert key_labels(("potrf", 32, "<f4", "L")) == {
        "op": "potrf", "n": 32, "dtype": "<f4"
    }
    assert key_labels(("x",)) == {}
    assert key_labels("not-a-tuple") == {}
    path = str(tmp_path / "labels.jsonl")
    om.enable(path)
    try:
        with _tuned(serve_buckets="16,32"):
            cache = serve.CompiledCache(capacity=1)
            for n in (16, 32, 16):  # miss, miss+evict, miss again
                serve.batched_cholesky_factorization(
                    "L", _spd_batch(1, n, np.float32, seed=n),
                    block_size=8, shard_batch=True, cache=cache,
                )
    finally:
        om.close()
    recs = [r for r in om.read_jsonl(path) if r["kind"] == "serve"]
    misses = [r for r in recs if r["event"] == "cache_miss"]
    assert len(misses) == 3
    for r in misses:
        assert r["op"] == "potrf" and r["n"] in (16, 32)
        assert r["dtype"] == np.dtype(np.float32).str
    evicts = [r for r in recs if r["event"] == "cache_evict"]
    assert len(evicts) == 2
    assert all("op" in r and "n" in r and "dtype" in r for r in evicts)


# ------------------------------------------------------ throughput acceptance


def test_batched_posv_throughput_vs_single_loop(grid_2x4):
    """ISSUE 5 acceptance: B=16 N=512 f32 batched posv >= 3x the wall-clock
    throughput of a Python loop of 16 single positive_definite_solver
    calls on the full 2x4 mesh (both post-warmup)."""
    B, n, k, nb = 16, 512, 1, 128
    rng = np.random.default_rng(7)
    a = _spd_batch(B, n, np.float32, seed=300)
    b = rng.standard_normal((B, n, k)).astype(np.float32)

    def loop_single():
        outs = []
        for i in range(B):
            mat_a = DistributedMatrix.from_global(grid_2x4, np.tril(a[i]), (nb, nb))
            mat_b = DistributedMatrix.from_global(grid_2x4, b[i], (nb, nb))
            outs.append(np.asarray(positive_definite_solver("L", mat_a, mat_b).to_global()))
        return outs

    cache = serve.CompiledCache()

    def batched():
        x, info = serve.batched_positive_definite_solver("L", a, b, cache=cache)
        assert np.all(info == 0)
        return x

    # warmup: compile both paths, and check both actually solve the systems
    x_batched = batched()
    x_loop = loop_single()
    for i in range(B):
        scale = np.abs(a[i]).max() * max(np.abs(x_batched[i]).max(), 1.0)
        assert np.abs(a[i] @ x_batched[i] - b[i]).max() < 1e-4 * n * scale
        assert np.abs(a[i] @ x_loop[i] - b[i]).max() < 1e-4 * n * scale

    t_loop = min(_timed(loop_single) for _ in range(2))
    t_batched = min(_timed(batched) for _ in range(2))
    speedup = t_loop / t_batched
    print(f"\nserve throughput: loop {t_loop:.3f}s  batched {t_batched:.3f}s  "
          f"speedup {speedup:.2f}x")
    assert speedup >= 3.0, (
        f"batched posv only {speedup:.2f}x the single-call loop "
        f"(loop {t_loop:.3f}s, batched {t_batched:.3f}s)"
    )


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0
