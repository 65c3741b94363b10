"""Resilience subsystem tests: deadline-bounded execution, the device
watchdog, and preemption-safe checkpoint/restart.

The bit-exactness contract under test is the one resilience.py documents:
a factorization killed between panels and resumed from its checkpoint
produces EXACTLY the bytes of an uninterrupted run of the SAME
``checkpoint_every`` cadence — both replay the single compiled range
kernel over identical panel ranges.  Against the default (bucketed /
lookahead) kernels the segmented variant is only allclose, and the tests
keep those two comparisons separate.

Timing faults enter through dlaf_tpu.testing.faults (hang /
slow_collective / preempt_at) so detection runs the production
resilience paths — nothing inside dlaf_tpu is patched."""
import os
import time

import numpy as np
import pytest

import dlaf_tpu.testing as tu
from dlaf_tpu import health, resilience
from dlaf_tpu.algorithms.cholesky import cholesky_factorization
from dlaf_tpu.algorithms.reduction_to_band import reduction_to_band
from dlaf_tpu.health import (
    DeadlineExceededError,
    DeviceUnresponsiveError,
    DistributionError,
)
from dlaf_tpu.matrix.matrix import DistributedMatrix
from dlaf_tpu.testing import faults

N, MB = 24, 4


@pytest.fixture(scope="module", autouse=True)
def _drop_range_kernels():
    """Free this module's compiled range kernels on teardown: the
    checkpoint cadences compile per-(dtype, grid) executables into
    module-level caches, and the tier-1 suite runs as ONE process where
    accumulated executables are the memory ceiling (see conftest's
    compile-cache note)."""
    yield
    from dlaf_tpu.plan import core as plan_core

    plan_core.reset()


def _mat(grid, a, mb=MB):
    return DistributedMatrix.from_global(grid, a, (mb, mb))


def _ckpt(tmp_path, name="ckpt.h5"):
    return str(tmp_path / name)


# ------------------------------------------------------------- deadlines


def test_run_with_deadline_bounds_a_hang():
    """A host call that blocks forever raises within 2x the budget — the
    ISSUE acceptance bound (thread handoff + Event.wait jitter stay well
    under one budget-width)."""
    budget = 0.4
    t0 = time.monotonic()
    with pytest.raises(DeadlineExceededError) as exc:
        resilience.run_with_deadline(time.sleep, 30.0, seconds=budget, label="t")
    elapsed = time.monotonic() - t0
    assert elapsed < 2 * budget, elapsed
    assert exc.value.budget_s == budget
    assert exc.value.label == "t"


def test_run_with_deadline_passes_through_value_and_errors():
    assert resilience.run_with_deadline(lambda x: x + 1, 2, seconds=5.0) == 3

    def boom():
        raise ValueError("inner")

    with pytest.raises(ValueError, match="inner"):
        resilience.run_with_deadline(boom, seconds=5.0)


def test_deadline_context_remaining_and_nesting():
    assert resilience.remaining() is None
    with resilience.deadline(10.0):
        r = resilience.remaining()
        assert r is not None and 8.0 < r <= 10.0
        with resilience.deadline(1.0):
            # innermost (tightest) expiry wins
            assert resilience.remaining() <= 1.0
        assert resilience.remaining() > 8.0
    assert resilience.remaining() is None


def test_check_deadline_raises_after_expiry():
    with resilience.deadline(0.05, label="tiny"):
        time.sleep(0.12)
        with pytest.raises(DeadlineExceededError):
            resilience.check_deadline("panel")


def test_driver_hang_detected_within_two_deadlines(grid_2x4):
    """THE acceptance criterion: a driver hung by testing.faults.hang
    raises DeadlineExceededError within 2x the configured deadline.  The
    kernel is warmed first so compile time does not eat the budget."""
    a = tu.random_hermitian_pd(N, np.float32, seed=2)
    mk = lambda: _mat(grid_2x4, np.tril(a))
    cholesky_factorization("L", mk(), checkpoint_every=2)  # warm the range kernel
    budget = 1.0
    t0 = time.monotonic()
    with pytest.raises(DeadlineExceededError):
        with faults.hang(30.0), resilience.deadline(budget):
            cholesky_factorization("L", mk(), checkpoint_every=2)
    assert time.monotonic() - t0 < 2 * budget


def test_slow_collective_drains_deadline(grid_2x4):
    """slow_collective delays every panel boundary; with more panels than
    the budget covers, the loop must stop mid-factorization."""
    a = tu.random_hermitian_pd(N, np.float32, seed=3)
    mk = lambda: _mat(grid_2x4, np.tril(a))
    cholesky_factorization("L", mk(), checkpoint_every=1)  # warm
    with pytest.raises(DeadlineExceededError):
        with faults.slow_collective(0.3), resilience.deadline(0.5):
            cholesky_factorization("L", mk(), checkpoint_every=1)


# ------------------------------------------------------------- watchdog


def test_watchdog_probe_alive_and_event():
    wd = resilience.DeviceWatchdog(budget_s=60.0)
    with health.capture_events() as ev:
        dt = wd.probe()
    assert dt >= 0.0
    assert wd.alive()
    assert any(e["event"] == "device_probe" for e in ev)


def test_watchdog_classifies_hang_as_unresponsive():
    wd = resilience.DeviceWatchdog(budget_s=60.0)
    wd.probe()  # compile outside the faulted window
    with health.capture_events() as ev:
        with pytest.raises(DeviceUnresponsiveError) as exc:
            with faults.hang(30.0):
                wd.probe(budget_s=0.3)
    assert exc.value.budget_s == 0.3
    assert any(e["event"] == "device_unresponsive" for e in ev)


def test_dead_probe_skips_the_work():
    """A dead probe records device_unresponsive and the wrapped work never
    runs: nothing is re-dispatched to another platform."""
    wd = resilience.DeviceWatchdog(budget_s=0.3)
    wd._ensure_compiled()  # compile outside the faulted window
    ran = []
    with health.capture_events() as ev:
        with pytest.raises(DeviceUnresponsiveError):
            with faults.hang(30.0):
                resilience.run_with_watchdog(lambda: ran.append(1), watchdog=wd)
    assert not ran
    assert any(e["event"] == "device_unresponsive" for e in ev)


def test_dead_probe_raises_through_run_with_watchdog():
    wd = resilience.DeviceWatchdog(budget_s=0.3)
    wd._ensure_compiled()
    with pytest.raises(DeviceUnresponsiveError):
        with faults.hang(30.0):
            resilience.run_with_watchdog(lambda: 0, watchdog=wd)


# ------------------------------------- checkpoint/restart: cholesky


@pytest.mark.parametrize("dtype", [np.float32, np.complex64])
def test_potrf_ckpt_resume_bit_exact(grid_2x4, tmp_path, dtype):
    """Kill at panel k, restart with resume_from= -> bit-identical factor
    vs an uninterrupted run of the same cadence (ISSUE acceptance)."""
    a = tu.random_hermitian_pd(N, dtype, seed=5)
    mk = lambda: _mat(grid_2x4, np.tril(a))
    ref = cholesky_factorization("L", mk(), checkpoint_every=2).to_global()
    path = _ckpt(tmp_path)
    with pytest.raises(faults.PreemptedError):
        with faults.preempt_at(4, algo="cholesky"):
            cholesky_factorization("L", mk(), checkpoint_every=2, checkpoint_path=path)
    assert os.path.exists(path)
    with health.capture_events() as ev:
        out = cholesky_factorization(
            "L", mk(), checkpoint_every=2, checkpoint_path=path, resume_from=path
        )
    assert np.array_equal(ref, out.to_global())
    assert any(e["event"] == "checkpoint_restored" for e in ev)


def test_potrf_segmented_matches_default_kernel(grid_2x4):
    """Cross-variant agreement is allclose (different reduction orders),
    checked against the ground truth as the repo's other tests do."""
    a = tu.random_hermitian_pd(N, np.float64, seed=6)
    out = cholesky_factorization("L", _mat(grid_2x4, np.tril(a)), checkpoint_every=3)
    tu.assert_near(out, np.linalg.cholesky(a), tu.tol_for(np.float64, N, 40.0), uplo="L")


def test_potrf_ckpt_upper(grid_2x4, tmp_path):
    a = tu.random_hermitian_pd(N, np.float32, seed=7)
    mk = lambda: _mat(grid_2x4, np.triu(a))
    ref = cholesky_factorization("U", mk(), checkpoint_every=2).to_global()
    path = _ckpt(tmp_path)
    with pytest.raises(faults.PreemptedError):
        with faults.preempt_at(3, algo="cholesky"):
            cholesky_factorization("U", mk(), checkpoint_every=2, checkpoint_path=path)
    out = cholesky_factorization(
        "U", mk(), checkpoint_every=2, checkpoint_path=path, resume_from=path
    )
    assert np.array_equal(ref, out.to_global())


def test_potrf_info_survives_resume(grid_2x4, tmp_path):
    """A failure planted AFTER the preemption point must still be named by
    info on the resumed run (info is checkpointed with the panel index)."""
    pivot = 17
    a = faults.break_spd(tu.random_hermitian_pd(N, np.float32, seed=8), pivot)
    mk = lambda: _mat(grid_2x4, np.tril(a))
    path = _ckpt(tmp_path)
    with pytest.raises(faults.PreemptedError):
        with faults.preempt_at(3, algo="cholesky"):
            cholesky_factorization("L", mk(), checkpoint_every=1, checkpoint_path=path)
    _, info = cholesky_factorization(
        "L", mk(), checkpoint_every=1, checkpoint_path=path,
        resume_from=path, return_info=True,
    )
    assert int(info) == pivot + 1


def test_potrf_ckpt_1x1_grid(grid_1x1):
    """Checkpoint cadence must force the distributed kernel even on the
    1x1 grid (the dense fast path has no panel loop to re-enter)."""
    n = 16
    a = tu.random_hermitian_pd(n, np.float32, seed=9)
    out = cholesky_factorization("L", _mat(grid_1x1, np.tril(a)), checkpoint_every=2)
    tu.assert_near(out, np.linalg.cholesky(a), tu.tol_for(np.float32, n, 60.0), uplo="L")


def test_ckpt_rejects_geometry_and_algo_mismatch(grid_2x4, tmp_path):
    a = tu.random_hermitian_pd(N, np.float32, seed=10)
    path = _ckpt(tmp_path)
    with pytest.raises(faults.PreemptedError):
        with faults.preempt_at(3, algo="cholesky"):
            cholesky_factorization(
                "L", _mat(grid_2x4, np.tril(a)), checkpoint_every=1,
                checkpoint_path=path,
            )
    big = tu.random_hermitian_pd(32, np.float32, seed=11)
    with pytest.raises(DistributionError):
        cholesky_factorization(
            "L", _mat(grid_2x4, np.tril(big), mb=MB), checkpoint_every=1,
            resume_from=path,
        )
    with pytest.raises(DistributionError):
        reduction_to_band(
            _mat(grid_2x4, np.tril(a)), band=MB, checkpoint_every=1,
            resume_from=path,
        )


def test_ckpt_excludes_shift_recovery(grid_2x4):
    a = tu.random_hermitian_pd(N, np.float32, seed=12)
    with pytest.raises(DistributionError):
        cholesky_factorization(
            "L", _mat(grid_2x4, np.tril(a)), checkpoint_every=2, shift_recovery=True
        )


def test_ckpt_events_reach_metrics_stream(grid_2x4, tmp_path):
    from dlaf_tpu.obs import metrics as om

    mpath = str(tmp_path / "m.jsonl")
    path = _ckpt(tmp_path)
    a = tu.random_hermitian_pd(N, np.float32, seed=13)
    mk = lambda: _mat(grid_2x4, np.tril(a))
    om.enable(mpath)
    try:
        with pytest.raises(faults.PreemptedError):
            with faults.preempt_at(3, algo="cholesky"):
                cholesky_factorization(
                    "L", mk(), checkpoint_every=1, checkpoint_path=path
                )
        cholesky_factorization(
            "L", mk(), checkpoint_every=1, checkpoint_path=path, resume_from=path
        )
    finally:
        om.close()
    evs = [r["event"] for r in om.read_jsonl(mpath) if r["kind"] == "health"]
    assert "checkpoint_written" in evs
    assert "checkpoint_restored" in evs


# ------------------------------------- checkpoint/restart: red2band


def test_red2band_ckpt_resume_bit_exact(grid_2x4, tmp_path):
    n, mb, band = 32, 8, 4
    a = tu.random_hermitian_pd(n, np.float32, seed=20)
    mk = lambda: _mat(grid_2x4, np.tril(a), mb=mb)
    ref, ref_taus = reduction_to_band(mk(), band=band, checkpoint_every=1)
    path = _ckpt(tmp_path)
    with pytest.raises(faults.PreemptedError):
        with faults.preempt_at(2, algo="reduction_to_band"):
            reduction_to_band(mk(), band=band, checkpoint_every=1,
                              checkpoint_path=path)
    out, taus = reduction_to_band(
        mk(), band=band, checkpoint_every=1, checkpoint_path=path, resume_from=path
    )
    assert np.array_equal(ref.to_global(), out.to_global())
    assert np.array_equal(np.asarray(ref_taus), np.asarray(taus))


def test_red2band_ckpt_rejects_band_mismatch(grid_2x4, tmp_path):
    n, mb = 32, 8
    a = tu.random_hermitian_pd(n, np.float32, seed=21)
    mk = lambda: _mat(grid_2x4, np.tril(a), mb=mb)
    path = _ckpt(tmp_path)
    with pytest.raises(faults.PreemptedError):
        with faults.preempt_at(2, algo="reduction_to_band"):
            reduction_to_band(mk(), band=4, checkpoint_every=1,
                              checkpoint_path=path)
    with pytest.raises(DistributionError):
        reduction_to_band(mk(), band=8, checkpoint_every=1, resume_from=path)


# ------------------------------------------------------------- satellites


def test_check_finite_single_sync_names_operand(grid_2x4, monkeypatch):
    """The fused level-2 check stacks all operand flags into ONE host sync
    and still attributes the first non-finite operand."""
    import jax.numpy as jnp

    from dlaf_tpu.common import checks

    monkeypatch.setattr(checks, "_LEVEL", 2)  # restored on teardown
    ok = jnp.ones((4, 4))
    bad = jnp.full((3, 3), np.nan)
    health.check_finite("stage", ok, ok)  # clean pass
    with health.capture_events() as ev:
        with pytest.raises(health.NonFiniteError):
            health.check_finite("stage", ok, None, bad, ok)
    rec = [e for e in ev if e["event"] == "nonfinite"]
    assert rec and rec[0]["operand"] == 1  # None operands are skipped


def test_multihost_plumbs_initialization_timeout(monkeypatch):
    """initialize(initialization_timeout=) and deadline_s both reach
    jax.distributed.initialize as its initialization_timeout kwarg."""
    import inspect

    import jax

    from dlaf_tpu.comm import multihost

    calls = {}
    real = jax.distributed.initialize

    def fake(coordinator_address=None, num_processes=None, process_id=None,
             initialization_timeout=None, **kw):
        calls["timeout"] = initialization_timeout
        raise ValueError("stop-after-capture")

    fake.__signature__ = inspect.signature(real)
    monkeypatch.setattr(jax.distributed, "initialize", fake)
    monkeypatch.setattr(multihost, "_initialized", False)
    with pytest.raises(ValueError, match="stop-after-capture"):
        multihost.initialize("127.0.0.1:1", 2, 0, initialization_timeout=17)
    assert calls["timeout"] == 17
    monkeypatch.setattr(multihost, "_initialized", False)
    with pytest.raises(ValueError, match="stop-after-capture"):
        multihost.initialize("127.0.0.1:1", 2, 0, deadline_s=40.0)
    # remaining time at call instant: deadline minus sub-second setup
    assert calls["timeout"] in (39, 40)
    monkeypatch.setattr(multihost, "_initialized", False)


def test_append_records_validates_before_writing(tmp_path):
    from dlaf_tpu.obs import metrics as om

    path = str(tmp_path / "a.jsonl")
    om.append_records(path, [{"kind": "health", "event": "device_probe"}])
    assert len(om.read_jsonl(path)) == 1
    # one bad record -> nothing at all is appended
    with pytest.raises(Exception):
        om.append_records(
            path,
            [{"kind": "health", "event": "x"}, {"kind": "health"}],
        )
    assert len(om.read_jsonl(path)) == 1


def test_miniapp_cholesky_ckpt_flags(tmp_path):
    """The miniapp wires --checkpoint-every/--checkpoint-path/--deadline
    through to the driver (exit 0 == residual check passed)."""
    from dlaf_tpu.miniapp import miniapp_cholesky

    times = miniapp_cholesky.main([
        "--m", "16", "--mb", "4", "--grid-rows", "1", "--grid-cols", "1",
        "--nruns", "1", "--check", "last", "--type", "s",
        "--checkpoint-every", "2",
        "--checkpoint-path", str(tmp_path / "mini.h5"),
        "--deadline", "600",
    ])
    assert len(times) == 1  # one timed run completed; check() already passed
