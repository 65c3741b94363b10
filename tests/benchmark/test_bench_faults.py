"""The benchmark's comparison catches a broken timed path.

Each test drives a whole rehearsal-size run in this process (past the
harness's look for a chip) with the library broken underneath, and sees
``correct`` come out false: a solve that returns its state unchanged, an
exchange between chips left out, an answer altered where it is produced,
and an input laid out wrong on the device.  The cells have no batch, so no
fault leaves half of one out.
"""
import argparse
import importlib.util
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def bench():
    path = os.path.join(ROOT, "benchmark", "run.py")
    spec = importlib.util.spec_from_file_location("bench_run_faults", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def isolated(monkeypatch):
    """Library and JAX state the run touches, restored afterwards."""
    import jax

    from dlaf_tpu import tune
    from dlaf_tpu.plan import core as plan

    monkeypatch.setattr(tune, "_params", None)
    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    plan.reset()
    yield monkeypatch
    plan.reset()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_s)


def _run(bench, workload, seed=11):
    cell = bench.resolve(workload)
    args = argparse.Namespace(seed=seed, seconds=0.2, trace=0)
    return bench.run_cell(cell, args, rehearse=True)


POTRF = "potrf-f32-n16384.2x2"
HEEV = "heev-f32-n4096.1chip"


def test_sound_runs_are_correct(bench, isolated):
    assert _run(bench, POTRF)["correct"] is True
    assert _run(bench, HEEV)["correct"] is True


def test_potrf_state_unchanged(bench, isolated):
    import dlaf_tpu as dt

    isolated.setattr(dt, "cholesky_factorization", lambda uplo, mat, **kw: mat)
    r = _run(bench, POTRF)
    assert r["correct"] is False
    assert r["checks"]["offdiag_max_err"]["value"] > 1


def test_potrf_exchange_left_out(bench, isolated):
    """The panel broadcast between rank columns returns the local panel."""
    from dlaf_tpu.comm import collectives

    isolated.setattr(collectives, "bcast", lambda x, root, axis, **kw: x)
    r = _run(bench, POTRF)
    assert r["correct"] is False


def test_potrf_answer_altered(bench, isolated):
    import dlaf_tpu as dt

    solve = dt.cholesky_factorization

    def altered(uplo, mat, **kw):
        out = solve(uplo, mat, **kw)
        # one sub-diagonal entry of the first column, off by 1%
        return out.like(out.data.at[0, 0, 1, 0, 0, 0].multiply(1.01))

    isolated.setattr(dt, "cholesky_factorization", altered)
    r = _run(bench, POTRF)
    assert r["correct"] is False


def test_potrf_input_laid_out_wrong(bench, isolated):
    """The library lays the input out as half of itself.  Its own read-back
    agrees with that layout, so a reference fed from it would too; the
    reference makes its input on the host and sees the factor wrong."""
    from dlaf_tpu.matrix import layout

    pack = layout.pack
    isolated.setattr(layout, "pack", lambda a, dist: pack(0.5 * a, dist))
    r = _run(bench, POTRF)
    assert r["correct"] is False


def test_heev_state_unchanged(bench, isolated):
    import dlaf_tpu as dt

    isolated.setattr(dt, "hermitian_eigensolver", lambda uplo, mat, **kw: dt.EigResult(
        np.zeros(mat.size.rows, np.float32), mat))
    assert _run(bench, HEEV)["correct"] is False


def test_heev_answer_altered(bench, isolated):
    import dlaf_tpu as dt

    solve = dt.hermitian_eigensolver

    def altered(uplo, mat, **kw):
        out = solve(uplo, mat, **kw)
        lam = np.array(out.eigenvalues)
        lam[len(lam) // 2] += 1e-3 * np.max(np.abs(lam))
        return dt.EigResult(lam, out.eigenvectors)

    isolated.setattr(dt, "hermitian_eigensolver", altered)
    r = _run(bench, HEEV)
    assert r["correct"] is False
