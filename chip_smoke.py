#!/usr/bin/env python3
"""Smoke run of dlaf_tpu's main path on a TPU chip (or a 2x2 host).

Drives the public entry points once at the sizes DLA-Future's miniapps run
(``Grid.create``, ``DistributedMatrix.from_global``,
``cholesky_factorization``, ``hermitian_eigensolver``) and checks every
result on the host in f64:

* POTRF f32 N=16384 nb=512 -- the dense XLA route (``backend='auto'`` on a
  1x1 grid) and the SPMD kernel with the Pallas diagonal-tile potrf
  (``backend='distributed'``);
* HEEV f32 N=4096 nb=256 -- the full pipeline: red2band, SBR band shrink,
  device band chase, distributed D&C, the back-transforms (N=8192 took
  880 s for two runs with the device band chase: CHANGES.md, PR 21);
* POTRF f64 N=4096 (BASELINE.json's first configuration; f64 is emulated on
  the TPU) and POTRF c64 N=2048.

Every check is a ratio residual / (N * eps(dtype)) held under a bound set
from the sound chip runs with a margin (``BOUNDS``; PERF.md, PR 21).

``--chips 4`` runs only POTRF f32 and HEEV f32 on a 2x2 ``Grid.create()``
and prints each device's bytes in use before and after placement.

Timings printed here are smoke timings, not benchmark results: POTRF is
timed on a second run after a warm-up run that compiles; HEEV runs once
(the device chase makes a run minutes long) and its seconds exclude the
host time JAX spent tracing and compiling.  The last stdout line is one JSON object
naming the device; any failure exits non-zero before it is printed.  There
is no CPU fallback: ``--rehearse`` runs the same script on virtual CPU
devices at a tiny size and never prints that line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--rehearse", action="store_true",
        help="run on virtual CPU devices at a tiny size (no chip, no result line)",
    )
    return p.parse_args(argv)


# (op, dtype, N, nb, backend) per phase; --rehearse divides N by 16, nb by 4.
# The 64-bit phases come last: they turn on jax_enable_x64.
PHASES_1 = (
    ("potrf", "float32", 16384, 512, "auto"),
    ("potrf", "float32", 16384, 512, "distributed"),
    ("heev", "float32", 4096, 256, "pipeline"),
    ("potrf", "complex64", 2048, 256, "distributed"),
    ("potrf", "float64", 4096, 256, "auto"),
)
PHASES_4 = (
    ("potrf", "float32", 16384, 512, "auto"),
    ("heev", "float32", 4096, 256, "auto"),
)
PROBES = 4  # random probe vectors of the POTRF check
# bound on residual / (N * eps) per check and precision bits: the sound chip
# readings times a margin; a HEEV with bf16 matmuls read 116, 51.8, 0.011
# and 0.979 (PERF.md, PR 21)
BOUNDS = {
    ("potrf", 32): 1e-2,
    ("potrf", 64): 30.0,
    ("heev residual", 32): 3.0,
    ("heev orthogonality", 32): 3.0,
    ("heev trace", 32): 1e-3,
    ("heev frobenius", 32): 5e-2,
}
# jax.monitoring spans of host time spent tracing, lowering and compiling
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


def _potrf_input(np, n, dtype, seed):
    """The reference miniapp's SPD input: uniform [-1, 1] Hermitian with
    2N added to the diagonal (DLA-Future set_random_hermitian_positive_definite)."""
    a = _heev_input(np, n, dtype, seed)
    a[np.diag_indices(n)] += 2 * n
    return a


def _heev_input(np, n, dtype, seed):
    """Uniform [-1, 1] Hermitian (DLA-Future set_random_hermitian)."""
    rng = np.random.default_rng(seed)
    r = rng.uniform(-1.0, 1.0, (n, n)).astype(dtype)
    if np.dtype(dtype).kind == "c":
        r = r + 1j * rng.uniform(-1.0, 1.0, (n, n)).astype(dtype)
    a = np.tril(r, -1)
    return a + a.conj().T + np.diag(np.diagonal(r).real).astype(dtype)


class Smoke:
    def __init__(self, jax, np, dt, grid):
        self.jax, self.np, self.dt, self.grid = jax, np, dt, grid
        self.devices = list(grid.mesh.devices.flat)
        self.spans = []
        jax.monitoring.register_event_time_span_listener(self._span)

    def _span(self, event, start, end, **_):
        if event in COMPILE_EVENTS:
            self.spans.append((start, end))

    def compile_seconds(self, t0):
        """Host seconds after ``t0`` (time.time) inside a compile span; nested
        spans (a trace inside a trace) count once."""
        total, reach = 0.0, t0
        for start, end in sorted(self.spans):
            start = max(start, reach)
            if end > start:
                total += end - start
                reach = end
        return total

    def log(self, msg):
        print(msg, flush=True)

    def bytes_in_use(self, key="bytes_in_use"):
        return [(d.memory_stats() or {}).get(key, 0) for d in self.devices]

    def place(self, a, nb):
        return self.dt.DistributedMatrix.from_global(self.grid, a, (nb, nb))

    def timed(self, run, make, data, warmup=True):
        """Timed run, after a warm-up run that compiles if ``warmup``, else
        less the host time spent compiling; returns (result, seconds).
        ``data`` picks the device array to wait for."""
        if warmup:
            self.jax.block_until_ready(data(run(make())))
        before = self.bytes_in_use()
        mat = make()
        self.jax.block_until_ready(mat.data)
        if len(self.devices) > 1:
            self.log(f"  bytes_in_use per device before placement {before}")
            self.log(f"  bytes_in_use per device after placement  {self.bytes_in_use()}")
        t0, wall0 = time.perf_counter(), time.time()
        out = run(mat)
        self.jax.block_until_ready(data(out))
        sec = time.perf_counter() - t0
        if not warmup:
            comp = self.compile_seconds(wall0)
            self.log(f"  one run {sec:.6f} s, of which {comp:.6f} s tracing and compiling")
            sec -= comp
        return out, sec

    def check(self, check, what, value, n, dtype):
        """value / (N * eps) within BOUNDS[check], else the run fails."""
        finfo = self.np.finfo(dtype)
        limit = BOUNDS[(check, finfo.bits)]
        ratio = value / (n * float(finfo.eps))
        name = f"{check} {what}"
        ok = bool(self.np.isfinite(value)) and ratio <= limit
        self.log(f"  {name} = {value:.3e} (ratio {ratio:.3g}, bound {limit:g}) "
                 f"{'ok' if ok else 'FAIL'}")
        if not ok:
            raise SystemExit(f"check failed: {name} = {value!r}, ratio {ratio!r} > {limit!r}")

    def potrf(self, dtype, n, nb, backend, seed):
        np = self.np
        a = _potrf_input(np, n, dtype, seed)
        fac, sec = self.timed(
            lambda m: self.dt.cholesky_factorization("L", m, backend=backend),
            lambda: self.place(a, nb),
            lambda m: m.data,
        )
        wide = np.complex128 if dtype.kind == "c" else np.float64
        el = np.tril(fac.to_global()).astype(wide)
        del fac
        # ||A x - L (L^H x)|| / (||A||_1 ||x||) on random probes: O(N^2)
        x = np.random.default_rng(seed + 1).standard_normal((n, PROBES))
        a64 = a.astype(wide)
        res = np.linalg.norm(a64 @ x - el @ (el.conj().T @ x), axis=0)
        rel = float(np.max(res / np.linalg.norm(x, axis=0)) / np.max(np.abs(a64).sum(0)))
        self.check("potrf", "residual |Ax-LL^Hx|/(|A|_1|x|)", rel, n, dtype)
        return sec

    def heev(self, dtype, n, nb, backend, seed):
        np = self.np
        a = _heev_input(np, n, dtype, seed)
        r, sec = self.timed(
            lambda m: self.dt.hermitian_eigensolver("L", m, backend=backend),
            lambda: self.place(a, nb),
            lambda r: r.eigenvectors.data,
            warmup=False,
        )
        lam = np.asarray(r.eigenvalues, np.float64)
        v = r.eigenvectors.to_global()
        del r
        if lam.shape != (n,) or v.shape != (n, n):
            raise SystemExit(f"heev: shapes {lam.shape}, {v.shape}, expected ({n},), ({n}, {n})")
        a64 = a.astype(np.float64)
        # every eigenpair: a 64-column sample missed the worst ones (PERF.md)
        vs = v.astype(np.float64)
        anorm = float(np.max(np.abs(lam)))  # ||A||_2
        resid = np.linalg.norm(a64 @ vs - vs * lam, axis=0).max() / anorm
        orth = np.abs(vs.T @ vs - np.eye(n)).max()
        tr = abs(lam.sum() - np.trace(a64)) / (n * anorm)
        fro2 = np.sum(a64 * a64)
        fro = abs(np.sum(lam * lam) - fro2) / fro2
        self.check("heev residual", "max|Av-lv|/|A|_2", float(resid), n, dtype)
        self.check("heev orthogonality", "max|V^TV-I|", float(orth), n, dtype)
        self.check("heev trace", "|sum(l)-tr(A)|/(N|A|_2)", float(tr), n, dtype)
        self.check("heev frobenius", "|sum(l^2)-|A|_F^2|/|A|_F^2", float(fro), n, dtype)
        return sec


def main(argv=None):
    args = _parse(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}"
        )
        # the eigensolver's accelerator defaults (auto is CPU-specific)
        os.environ.setdefault("DLAF_TPU_EIGENSOLVER_SBR_BAND", "32")
        os.environ.setdefault("DLAF_TPU_EIGENSOLVER_MIN_BAND", "100")
    import jax
    import numpy as np

    import dlaf_tpu as dt
    from dlaf_tpu import tune

    tune.setup_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        raise SystemExit(f"no TPU: jax.devices()[0].platform is {platform!r}")
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips}: only {len(devices)} device(s)")
    if args.chips == 1:
        grid = dt.Grid.create(dt.Size2D(1, 1), devices[:1])
        phases = PHASES_1
    else:
        grid = dt.Grid.create()
        phases = PHASES_4
        if tuple(grid.grid_size) != (2, 2):
            raise SystemExit(f"--chips 4: Grid.create() gave {grid.grid_size}, expected 2x2")
    kind = devices[0].device_kind
    smoke = Smoke(jax, np, dt, grid)
    from dlaf_tpu.algorithms.band_to_tridiag import resolve_chase_backend

    smoke.log(f"smoke timings, not benchmark results: device_kind={kind!r} "
              f"devices={len(devices)} grid={grid} jax={jax.__version__} "
              f"band_chase={resolve_chase_backend()} "
              f"compile_cache={jax.config.jax_compilation_cache_dir}")
    for r, row in enumerate(grid.mesh.devices):
        for c, d in enumerate(row):
            smoke.log(f"  grid ({r},{c}) -> device {d.id} coords {getattr(d, 'coords', None)}")
    for op, dtype, n, nb, backend in phases:
        if args.rehearse:
            n, nb = n // 16, nb // 4
        if dtype == "float64" and not jax.config.jax_enable_x64:
            jax.config.update("jax_enable_x64", True)
        smoke.log(f"phase {op} {dtype} N={n} nb={nb} backend={backend}")
        sec = getattr(smoke, op)(np.dtype(dtype), n, nb, backend, args.seed)
        peak = max(smoke.bytes_in_use("peak_bytes_in_use"))
        smoke.log(f"  {op} {dtype} N={n} nb={nb} backend={backend}: {sec:.6f} s "
                  f"(compile excluded), peak_bytes_in_use {peak}, device_kind {kind!r}")
    if args.rehearse:
        print(f"rehearsal passed on {len(devices)} {platform} device(s); no chip result")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
