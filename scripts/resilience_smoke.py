#!/usr/bin/env python
"""Drive every resilience mechanism once and write the events to a metrics file.

Usage: python scripts/resilience_smoke.py out.jsonl

CI runs this as the resilience lane's artifact step: each timing fault from
dlaf_tpu.testing.faults (hang, slow_collective, preempt_at) goes through the
PRODUCTION bounded-execution / watchdog / checkpoint-restart paths and the
resulting ``health`` records land in ``out.jsonl`` for
``scripts/report_metrics.py``.  Exit is nonzero if any detection misses its
bound or a resumed factorization is not bit-identical.
"""
from __future__ import annotations

import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np

from dlaf_tpu import resilience
from dlaf_tpu.algorithms.cholesky import cholesky_factorization
from dlaf_tpu.comm.grid import Grid
from dlaf_tpu.health import DeadlineExceededError, DeviceUnresponsiveError
from dlaf_tpu.matrix.matrix import DistributedMatrix
from dlaf_tpu.obs import metrics as om
from dlaf_tpu.testing import faults, random_hermitian_pd

N, MB = 24, 4


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    path = argv[0] if argv else "resilience.jsonl"
    om.enable(path)
    om.emit_run_meta("resilience_smoke")
    grid = Grid.create((1, 1))
    failures = []

    def expect(cond, what):
        print(("ok  " if cond else "FAIL") + f"  {what}")
        if not cond:
            failures.append(what)

    a = random_hermitian_pd(N, np.float64, seed=0)

    def mk():
        return DistributedMatrix.from_global(grid, np.tril(a), (MB, MB))

    # 1. deadline bound: a hung blocking call is detected within 2x budget
    budget = 0.5
    t0 = time.monotonic()
    try:
        resilience.run_with_deadline(time.sleep, 30.0, seconds=budget,
                                     label="smoke_hang")
        expect(False, "DeadlineExceededError raised")
    except DeadlineExceededError:
        expect(time.monotonic() - t0 < 2 * budget,
               f"hang detected within 2x the {budget}s deadline")

    # 2. driver-level bound: hang injected under the ambient deadline
    cholesky_factorization("L", mk(), checkpoint_every=2)  # warm the kernel
    t0 = time.monotonic()
    try:
        with faults.hang(30.0), resilience.deadline(1.0):
            cholesky_factorization("L", mk(), checkpoint_every=2)
        expect(False, "hung driver raised DeadlineExceededError")
    except DeadlineExceededError:
        expect(time.monotonic() - t0 < 2.0, "hung driver bounded within 2x")

    # 3. watchdog: live probe, then a hang classified as unresponsive
    wd = resilience.DeviceWatchdog(budget_s=60.0)
    dt = wd.probe()
    expect(wd.alive(), f"watchdog probe ok ({dt * 1e3:.1f} ms)")
    try:
        with faults.hang(30.0):
            wd.probe(budget_s=0.3)
        expect(False, "DeviceUnresponsiveError raised")
    except DeviceUnresponsiveError:
        expect(True, "watchdog classified the hang as device-unresponsive")

    # 4. a dead probe raises through run_with_watchdog; the work never runs
    ran = []
    try:
        with faults.hang(30.0):
            resilience.run_with_watchdog(
                lambda: ran.append(1), watchdog=resilience.DeviceWatchdog(budget_s=0.3)
            )
        expect(False, "DeviceUnresponsiveError raised through run_with_watchdog")
    except DeviceUnresponsiveError:
        expect(not ran, "dead probe raised and the workload never ran")

    # 5. preemption-safe checkpoint/restart, bit-exact resume
    ref = cholesky_factorization("L", mk(), checkpoint_every=2).to_global()
    ckpt = os.path.join(tempfile.gettempdir(), "dlaf_resilience_smoke.h5")
    try:
        with faults.preempt_at(3, algo="cholesky"):
            cholesky_factorization("L", mk(), checkpoint_every=2,
                                   checkpoint_path=ckpt)
        expect(False, "simulated preemption fired")
    except faults.PreemptedError:
        expect(os.path.exists(ckpt), "checkpoint written before preemption")
    out = cholesky_factorization("L", mk(), checkpoint_every=2,
                                 checkpoint_path=ckpt, resume_from=ckpt)
    expect(np.array_equal(ref, out.to_global()),
           "resumed factor is bit-identical to the uninterrupted run")
    os.remove(ckpt)

    om.close()
    print(f"resilience events written to {path}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
