"""Worker process for the REAL multi-process ``jax.distributed`` tests.

Each worker is one process of an N-process world (the analogue of one MPI
rank in the reference's 6-rank test fixture,
reference: test/include/dlaf_test/comm_grids/grids_6_ranks.h:26-60 wired by
cmake/DLAF_AddTest.cmake via ``mpiexec -n 6``).  The parent test
(test_multiprocess.py) spawns ``nprocs`` of these with a shared local
coordinator; each brings up ``comm.multihost``, builds one Grid over the
GLOBAL device list (local devices x nprocs), runs a distributed algorithm,
and verifies residuals ON EVERY PROCESS — any assertion failure exits
nonzero and fails the parent test.

Run standalone for debugging::

    python tests/multiproc_worker.py --coordinator 127.0.0.1:47002 \
        --nprocs 2 --rank {0,1} --local-devices 4 --case potrf
"""
import argparse
import os
import sys


def _env_setup(local_devices: int) -> None:
    """Must run before jax import (mirrors tests/conftest.py)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={local_devices}"
        )
    os.environ.setdefault("JAX_ENABLE_X64", "true")
    os.environ["DLAF_TPU_COMPILE_CACHE"] = ""


def case_roundtrip(grid, args):
    """from_global/to_global across processes: every process passes the same
    global array, places only its addressable shards, and gathers the full
    matrix back (replicated all-gather inside jit)."""
    import numpy as np

    import dlaf_tpu.testing as tu
    from dlaf_tpu.matrix.matrix import DistributedMatrix

    a = tu.random_matrix(args.n, args.n, np.float64, seed=7)
    mat = DistributedMatrix.from_global(grid, a, (args.nb, args.nb))
    np.testing.assert_array_equal(mat.to_global(), a)
    # transpose exercises a cross-process collective beyond pure layout
    from dlaf_tpu.matrix.util import transpose

    np.testing.assert_array_equal(transpose(mat).to_global(), a.T)


def case_potrf(grid, args):
    """Distributed Cholesky with factorization residual ||L L^H - A||."""
    import numpy as np

    import dlaf_tpu.testing as tu
    from dlaf_tpu.algorithms.cholesky import cholesky_factorization
    from dlaf_tpu.matrix.matrix import DistributedMatrix

    a = tu.random_hermitian_pd(args.n, np.float64, seed=13)
    mat = DistributedMatrix.from_global(grid, np.tril(a), (args.nb, args.nb))
    fac = cholesky_factorization("L", mat)
    ell = np.tril(fac.to_global())
    res = ell @ ell.conj().T - a
    tol = tu.tol_for(np.float64, args.n, 100.0)
    assert np.max(np.abs(res)) < tol * np.abs(a).max(), np.max(np.abs(res))


def case_heev(grid, args):
    """Full HEEV pipeline (red2band -> band2trid -> D&C -> back-transforms)
    with the reference's correctness criteria: eigenvalues vs LAPACK,
    residual ||A V - V Lambda||, orthogonality ||V^H V - I||
    (reference: dlaf_test/eigensolver/test_eigensolver_correctness.h:35-79)."""
    import numpy as np

    import dlaf_tpu.testing as tu
    from dlaf_tpu.algorithms.eigensolver import hermitian_eigensolver
    from dlaf_tpu.matrix.matrix import DistributedMatrix

    a = tu.random_hermitian_pd(args.n, np.float64, seed=21)
    mat = DistributedMatrix.from_global(grid, np.tril(a), (args.nb, args.nb))
    res = hermitian_eigensolver("L", mat, backend="pipeline")
    tol = tu.tol_for(np.float64, args.n, 500.0)
    np.testing.assert_allclose(res.eigenvalues, np.linalg.eigvalsh(a), atol=tol)
    v = res.eigenvectors.to_global()
    resid = a @ v - v * res.eigenvalues[None, :]
    assert np.max(np.abs(resid)) < tol * max(1.0, np.abs(a).max()), np.max(np.abs(resid))
    ortho = v.conj().T @ v - np.eye(v.shape[1])
    assert np.max(np.abs(ortho)) < tol, np.max(np.abs(ortho))


def case_scalapack_local(grid, args):
    """Distributed-buffer ScaLAPACK mode: each process passes ONLY its local
    block-cyclic slabs and gets its local result slabs back (the reference's
    per-rank buffer model, include/dlaf_c/grid.h:77 BLACS-grid adoption).
    At no point does any process hold a controller O(N^2) input buffer of
    the distributed matrix (the global array here is only the test oracle)."""
    import numpy as np

    import dlaf_tpu.testing as tu
    from dlaf_tpu.scalapack import api as sapi

    n, nb = args.n, args.nb
    a = tu.random_hermitian_pd(n, np.float64, seed=29)
    desc = sapi.make_desc(n, n, nb, nb)
    tol = tu.tol_for(np.float64, n, 100.0)

    # --- POTRF: slabs in, factor slabs out -------------------------------
    local_a = sapi.global_to_local(np.tril(a), desc, grid)  # THIS process only
    assert local_a, "process owns no grid position"
    for rank, slab in local_a.items():
        assert slab.shape == sapi.local_shape(desc, grid.grid_size, rank)
    local_l = sapi.ppotrf_local("L", local_a, desc, grid)
    assert set(local_l) == set(local_a)
    expected_l = np.linalg.cholesky(a)
    ones = np.tril(np.ones((n, n)))
    for rank, slab in local_l.items():
        want = sapi._slab_from_global(expected_l, desc, grid.grid_size, rank)
        mask = sapi._slab_from_global(ones, desc, grid.grid_size, rank)
        err = np.max(np.abs((slab - want) * mask)) if slab.size else 0.0
        assert err < tol * np.abs(a).max(), (rank, err)

    # --- POSV: factor + solve, all slabs ---------------------------------
    nrhs = 3
    rhs = tu.random_matrix(n, nrhs, np.float64, seed=30)
    desc_b = sapi.make_desc(n, nrhs, nb, nb)
    local_rhs = sapi.global_to_local(rhs, desc_b, grid)
    _fac2, local_x = sapi.pposv_local("L", local_a, desc, local_rhs, desc_b, grid)
    x = sapi.matrix_from_local(local_x, desc_b, grid).to_global()
    assert np.max(np.abs(a @ x - rhs)) < tol * np.abs(a).max()

    # --- HEEV: slabs in, (w, eigenvector slabs) out ----------------------
    local_w, local_v = sapi.pheevd_local("L", local_a, desc, grid)
    np.testing.assert_allclose(
        local_w, np.linalg.eigvalsh(a), atol=tu.tol_for(np.float64, n, 500.0)
    )
    vmat = sapi.matrix_from_local(local_v, desc, grid)
    v = vmat.to_global()
    resid = a @ v - v * local_w[None, :]
    assert np.max(np.abs(resid)) < tu.tol_for(np.float64, n, 500.0) * max(
        1.0, np.abs(a).max()
    ), np.max(np.abs(resid))


def case_potrf_src(grid, args):
    """Distributed Cholesky on a SOURCE-RANK matrix across processes: the
    zero-copy origin relabeling (make_array_from_single_device_arrays over
    per-process addressable shards) must compose with cross-process
    collectives, and the in-place contract must hold on every rank."""
    import numpy as np

    import dlaf_tpu.testing as tu
    from dlaf_tpu.algorithms.cholesky import cholesky_factorization
    from dlaf_tpu.matrix.matrix import DistributedMatrix

    a = tu.random_hermitian_pd(args.n, np.float64, seed=43)
    src = (1, 2)
    mat = DistributedMatrix.from_global(grid, np.tril(a), (args.nb, args.nb),
                                        source_rank=src)
    fac = cholesky_factorization("L", mat)
    assert tuple(fac.dist.source_rank) == src
    tol = tu.tol_for(np.float64, args.n, 100.0)
    ell = np.tril(fac.to_global())
    assert np.max(np.abs(ell @ ell.conj().T - a)) < tol * np.abs(a).max()
    # in-place contract on the caller's handle, in the caller's labeling
    np.testing.assert_array_equal(np.tril(mat.to_global()), ell)


def case_hegv(grid, args):
    """Generalized HEGV pipeline across processes (gen_to_std + HEEV +
    back-substitution), B-orthonormality checked on every rank."""
    import numpy as np

    import dlaf_tpu.testing as tu
    from dlaf_tpu.algorithms.eigensolver import hermitian_generalized_eigensolver
    from dlaf_tpu.matrix.matrix import DistributedMatrix

    a = tu.random_hermitian_pd(args.n, np.float64, seed=33)
    b = tu.random_hermitian_pd(args.n, np.float64, seed=34)
    mat_a = DistributedMatrix.from_global(grid, np.tril(a), (args.nb, args.nb))
    mat_b = DistributedMatrix.from_global(grid, np.tril(b), (args.nb, args.nb))
    res = hermitian_generalized_eigensolver("L", mat_a, mat_b)
    tol = tu.tol_for(np.float64, args.n, 500.0)
    v = res.eigenvectors.to_global()
    resid = a @ v - (b @ v) * res.eigenvalues[None, :]
    assert np.max(np.abs(resid)) < tol * max(1.0, np.abs(a).max()), np.max(np.abs(resid))
    ortho = v.conj().T @ b @ v - np.eye(v.shape[1])
    assert np.max(np.abs(ortho)) < tol, np.max(np.abs(ortho))


def case_heev_c128(grid, args):
    """Complex-Hermitian HEEV pipeline across processes."""
    import numpy as np

    import dlaf_tpu.testing as tu
    from dlaf_tpu.algorithms.eigensolver import hermitian_eigensolver
    from dlaf_tpu.matrix.matrix import DistributedMatrix

    a = tu.random_hermitian_pd(args.n, np.complex128, seed=35)
    mat = DistributedMatrix.from_global(grid, np.tril(a), (args.nb, args.nb))
    res = hermitian_eigensolver("L", mat, backend="pipeline")
    tol = tu.tol_for(np.complex128, args.n, 500.0)
    np.testing.assert_allclose(res.eigenvalues, np.linalg.eigvalsh(a), atol=tol)
    v = res.eigenvectors.to_global()
    resid = a @ v - v * res.eigenvalues[None, :]
    assert np.max(np.abs(resid)) < tol * max(1.0, np.abs(a).max()), np.max(np.abs(resid))
    ortho = v.conj().T @ v - np.eye(v.shape[1])
    assert np.max(np.abs(ortho)) < tol, np.max(np.abs(ortho))


def case_hdf5(grid, args):
    """HDF5 round-trip across processes: save_hdf5 is COLLECTIVE (every rank
    dispatches the per-slab gathers, only rank 0 writes the file, internal
    barrier before returning), then every rank streams it back through
    load_hdf5 — whose slab placement must go through matrix.place() (a bare
    ndarray into the jitted row update only reaches addressable devices and
    breaks exactly here, on a multi-process world)."""
    import os
    import tempfile

    import numpy as np
    from jax.experimental import multihost_utils

    import dlaf_tpu.testing as tu
    from dlaf_tpu.comm import multihost
    from dlaf_tpu.matrix import io as mio
    from dlaf_tpu.matrix.matrix import DistributedMatrix

    a = tu.random_matrix(args.n, args.n, np.float64, seed=51)
    path = os.path.join(tempfile.gettempdir(), f"dlaf_mp_hdf5_{args.nprocs}.h5")
    mat = DistributedMatrix.from_global(grid, a, (args.nb, args.nb))
    mio.save_hdf5(path, mat)  # collective; rank 0 does the file I/O
    got = mio.load_hdf5(path, grid)
    assert tuple(got.block_size) == (args.nb, args.nb)
    np.testing.assert_array_equal(got.to_global(), a)
    multihost_utils.sync_global_devices("multiproc_worker.case_hdf5.read")
    if multihost.process_info()[0] == 0:
        os.remove(path)


def case_potrf_ckpt(grid, args):
    """Preemption-safe checkpoint/restart across REAL processes: every rank
    simulates preemption at the same panel (the hook fires rank-locally but
    deterministically), then the resumed factorization — whose checkpoint
    was written by the COLLECTIVE save_hdf5 path and re-read by every rank —
    must be bit-identical to an uninterrupted run of the same cadence."""
    import os
    import tempfile

    import numpy as np
    from jax.experimental import multihost_utils

    import dlaf_tpu.testing as tu
    from dlaf_tpu.algorithms.cholesky import cholesky_factorization
    from dlaf_tpu.comm import multihost
    from dlaf_tpu.matrix.matrix import DistributedMatrix
    from dlaf_tpu.testing import faults

    a = tu.random_hermitian_pd(args.n, np.float32, seed=44)
    mk = lambda: DistributedMatrix.from_global(grid, np.tril(a), (args.nb, args.nb))
    ref = cholesky_factorization("L", mk(), checkpoint_every=2).to_global()
    path = os.path.join(tempfile.gettempdir(), f"dlaf_mp_ckpt_{args.nprocs}.h5")
    try:
        with faults.preempt_at(2, algo="cholesky"):
            cholesky_factorization(
                "L", mk(), checkpoint_every=2, checkpoint_path=path
            )
        raise AssertionError("preempt_at(2) did not fire")
    except faults.PreemptedError:
        pass
    out = cholesky_factorization(
        "L", mk(), checkpoint_every=2, checkpoint_path=path, resume_from=path
    )
    np.testing.assert_array_equal(ref, out.to_global())
    multihost_utils.sync_global_devices("multiproc_worker.case_potrf_ckpt")
    if multihost.process_info()[0] == 0:
        os.remove(path)


def case_serve_batched(grid, args):
    """dlaf_tpu.serve batched drivers with the BATCH axis sharded across
    the processes' devices: every process submits the same host batch,
    each rank's devices factor/solve their local batch elements, and the
    replicated gather hands every process the full result stack."""
    import numpy as np

    import dlaf_tpu.testing as tu
    from dlaf_tpu import serve, tune
    from dlaf_tpu.serve.bucketing import CompiledCache

    tune.initialize(serve_buckets=str(args.n))
    B, n, nb = 8, args.n, args.nb
    a = np.stack(
        [tu.random_hermitian_pd(n, np.float32, seed=60 + i) for i in range(B)]
    )
    rng = np.random.default_rng(61)
    b = rng.standard_normal((B, n, 2)).astype(np.float32)
    cache = CompiledCache()
    tol = tu.tol_for(np.float32, n, 100.0)

    ell, info = serve.batched_cholesky_factorization(
        "L", a, grid, block_size=nb, shard_batch=True, cache=cache
    )
    assert info.shape == (B,) and np.all(info == 0), info
    for i in range(B):
        low = np.tril(ell[i])
        res = np.max(np.abs(low @ low.T - a[i]))
        assert res < tol * np.abs(a[i]).max(), (i, res)

    x, info = serve.batched_positive_definite_solver(
        "L", a, b, grid, block_size=nb, shard_batch=True, cache=cache
    )
    assert np.all(info == 0), info
    for i in range(B):
        res = np.max(np.abs(a[i] @ x[i] - b[i]))
        scale = np.abs(a[i]).max() * max(np.abs(x[i]).max(), 1.0)
        assert res < tol * scale, (i, res)

    # cached executable, same inputs: the service path is deterministic
    x2, _ = serve.batched_positive_definite_solver(
        "L", a, b, grid, block_size=nb, shard_batch=True, cache=cache
    )
    np.testing.assert_array_equal(x, x2)
    assert cache.counters["miss"] == 2 and cache.counters["hit"] == 1


def case_spans(grid, args):
    """Multi-rank span merge: every rank emits request spans under ONE
    shared trace id into the rank-aware metrics stream, ``close()``
    world-syncs and rank 0 merges the part files, then rank 0 re-reads the
    merged stream and runs the Perfetto exporter — every rank must land on
    its own process row and the trace id must survive the merge."""
    import os
    import tempfile

    from jax.experimental import multihost_utils

    from dlaf_tpu.comm import multihost
    from dlaf_tpu.obs import export as oexport
    from dlaf_tpu.obs import metrics as om
    from dlaf_tpu.obs import spans

    rank = multihost.process_info()[0]
    path = os.path.join(tempfile.gettempdir(), f"dlaf_mp_spans_{args.nprocs}.jsonl")
    if rank == 0 and os.path.exists(path):
        os.remove(path)
    multihost_utils.sync_global_devices("multiproc_worker.case_spans.clean")
    om.enable(path)
    spans.enable()
    trace_id = "mp-shared-trace-0123"
    try:
        with spans.bind((trace_id, None)):
            with spans.span(f"rank{rank}.work", rank_attr=rank):
                with spans.span("child"):
                    pass
    finally:
        spans.disable()
        om.close()  # world-sync, then rank 0 appends the rank part files
    if rank == 0:
        recs = om.read_jsonl(path)
        sp = [r for r in recs if r["kind"] == "span"]
        assert {r["rank"] for r in sp} == set(range(args.nprocs)), sp
        assert {r["trace_id"] for r in sp} == {trace_id}, sp
        doc = oexport.to_chrome_trace(recs)
        xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert {e["pid"] for e in xs} == set(range(args.nprocs)), xs
        assert all(e["args"]["trace_id"] == trace_id for e in xs), xs
        names = [e for e in doc["traceEvents"]
                 if e.get("ph") == "M" and e.get("name") == "process_name"]
        assert {m["pid"] for m in names} == set(range(args.nprocs)), names
        os.remove(path)
    multihost_utils.sync_global_devices("multiproc_worker.case_spans.done")


CASES = {
    "roundtrip": case_roundtrip,
    "hdf5": case_hdf5,
    "potrf": case_potrf,
    "potrf_ckpt": case_potrf_ckpt,
    "potrf_src": case_potrf_src,
    "heev": case_heev,
    "hegv": case_hegv,
    "heev_c128": case_heev_c128,
    "scalapack_local": case_scalapack_local,
    "serve_batched": case_serve_batched,
    "spans": case_spans,
}


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--coordinator", required=True)
    p.add_argument("--nprocs", type=int, required=True)
    p.add_argument("--rank", type=int, required=True)
    p.add_argument("--local-devices", type=int, required=True)
    p.add_argument("--case", required=True, choices=sorted(CASES))
    p.add_argument("--n", type=int, default=32)
    p.add_argument("--nb", type=int, default=8)
    p.add_argument("--grid-rows", type=int, default=2)
    args = p.parse_args()

    _env_setup(args.local_devices)

    import jax

    jax.config.update("jax_enable_x64", True)

    from dlaf_tpu.comm import multihost

    multihost.initialize(args.coordinator, args.nprocs, args.rank)
    pid, pcount = multihost.process_info()
    assert (pid, pcount) == (args.rank, args.nprocs), (pid, pcount)
    ndev = jax.device_count()
    assert ndev == args.nprocs * args.local_devices, ndev
    assert jax.local_device_count() == args.local_devices

    from dlaf_tpu.comm.grid import Grid
    from dlaf_tpu.common.index import Size2D

    pr = args.grid_rows
    grid = Grid.create(Size2D(pr, ndev // pr))
    CASES[args.case](grid, args)
    # unambiguous success marker (exit codes can be eaten by launcher wrappers)
    print(f"MPWORKER_OK rank={args.rank} case={args.case}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
