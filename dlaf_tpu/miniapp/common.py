"""Shared miniapp infrastructure.

Analogue of the reference miniapp harness
(reference: miniapp/include/dlaf/miniapp/options.h:201 MiniappOptions,
miniapp/miniapp_cholesky.cpp:106-195): parse options, build the grid, run the
algorithm ``nruns`` times, print per-run ``[i] time GFlop/s`` lines, optional
correctness check on the last run.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import numpy as np

# persistent XLA compilation cache: repeated miniapp/bench invocations skip
# recompiles.  tune.setup_compile_cache keeps it where
# JAX_COMPILATION_CACHE_DIR says, else in the checkout's .jax_cache/.
from dlaf_tpu import tune as _tune

_tune.setup_compile_cache()

from dlaf_tpu.comm.grid import Grid
from dlaf_tpu.common.index import Size2D

DTYPES = {
    "s": np.float32,
    "d": np.float64,
    "c": np.complex64,
    "z": np.complex128,
}


def ops_add_mul(dtype, add: float, mul: float) -> float:
    """reference types.h:160 total_ops: complex mul = 6 flops, add = 2."""
    if np.dtype(dtype).kind == "c":
        return 2.0 * add + 6.0 * mul
    return add + mul


def miniapp_parser(desc: str) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=desc)
    p.add_argument("--matrix-size", "--m", type=int, default=4096, dest="m")
    p.add_argument("--block-size", "--mb", type=int, default=256, dest="mb")
    p.add_argument("--grid-rows", type=int, default=1)
    p.add_argument("--grid-cols", type=int, default=1)
    p.add_argument("--nruns", type=int, default=3)
    p.add_argument("--nwarmups", type=int, default=1)
    p.add_argument("--type", choices="sdcz", default="d")
    p.add_argument("--uplo", choices=["L", "U"], default="L",
                   help="triangle holding the input (reference MiniappOptions --uplo)")
    p.add_argument("--check", choices=["none", "last", "all"], default="none")
    p.add_argument(
        "--trace", default="", metavar="DIR",
        help="capture a jax.profiler trace of timed run 0 into DIR (view "
        "with TensorBoard / xprof; the per-stage analogue of the reference's "
        "pika/APEX instrumentation hooks — SURVEY §5 tracing row)",
    )
    p.add_argument(
        "--input-file", default="", metavar="FILE",
        help="read the input matrix from FILE (.h5 dataset 'a', or .npz) "
        "instead of generating one; the matrix size overrides --m "
        "(reference MiniappOptions --input-file; supported by the "
        "cholesky and eigensolver drivers)",
    )
    p.add_argument(
        "--output-file", default="", metavar="FILE",
        help="save the final timed run's output matrix to FILE "
        "(.h5/.npz via matrix.io)",
    )
    p.add_argument(
        "--print-config", action="store_true",
        help="dump the effective tune configuration + runtime facts before "
        "running (reference --dlaf:print-config, src/init.cpp:377-383)",
    )
    p.add_argument(
        "--spectrum", default="", metavar="IL:IU",
        help="partial eigenvalue window, 0-based inclusive indices (e.g. "
        "0:99 = the 100 smallest); honored by the eigensolver drivers and "
        "the heev_mixed subcommand (reference --eigensolver-min-band style "
        "partial-spectrum runs, eigensolver.h:39-256)",
    )
    p.add_argument(
        "--metrics", default="", metavar="PATH",
        help="write a schema-versioned JSONL metrics stream to PATH: run "
        "metadata, the tune config snapshot, per-run wall times, per-stage "
        "breakdowns (with --stage-times), per-collective message/byte "
        "accounting, and jit compile/cache events (summarize with "
        "scripts/report_metrics.py; multi-process ranks merge into PATH)",
    )
    p.add_argument(
        "--stage-times", action="store_true",
        help="print a per-stage wall-time breakdown after each timed run "
        "(syncs at stage boundaries — slightly serializes async dispatch); "
        "instrumented pipelines: eigensolver / gen_eigensolver",
    )
    return p


def parse_spectrum(args) -> "tuple[int, int] | None":
    """(il, iu) from ``--spectrum IL:IU``, or None when unset."""
    if not getattr(args, "spectrum", ""):
        return None
    try:
        il, iu = (int(v) for v in args.spectrum.split(":"))
    except ValueError:
        raise SystemExit(
            f"--spectrum must be IL:IU, got {args.spectrum!r}"
        ) from None
    if not (0 <= il <= iu < args.m):
        raise SystemExit(f"--spectrum {il}:{iu} outside [0, {args.m})")
    return (il, iu)


def tri(uplo: str):
    """The triangle extractor for ``uplo`` ('L' -> np.tril, 'U' -> np.triu)."""
    return np.tril if uplo == "L" else np.triu


def host_input(args, dtype, gen):
    """The driver's input matrix: ``--input-file`` (h5/npz, via
    matrix.io.load_global) when given — its size overrides ``--m``, like
    the reference's miniapp input files — else the generated matrix from
    ``gen()``."""
    path = getattr(args, "input_file", "")
    if not path:
        return gen()
    from dlaf_tpu.matrix.io import load_global

    a = load_global(path)
    if a.shape[0] != a.shape[1]:
        raise ValueError(f"--input-file matrix must be square, got {a.shape}")
    args.m = int(a.shape[0])
    return np.asarray(a, dtype=dtype)


def reject_input_file(args, driver: str) -> None:
    """Fail loudly in drivers whose input is not a single matrix — silently
    benchmarking a generated matrix while the user passed --input-file
    would report numbers for the wrong input."""
    if getattr(args, "input_file", ""):
        raise SystemExit(
            f"--input-file is not supported by the {driver} driver "
            "(its input is not a single square matrix)"
        )


def make_grid(args) -> Grid:
    if args.type in ("d", "z"):  # 64-bit real parts need x64; c (c64) does not
        jax.config.update("jax_enable_x64", True)
    if getattr(args, "print_config", False):
        from dlaf_tpu.tune import print_config

        print_config()
    return Grid.create(Size2D(args.grid_rows, args.grid_cols))


def run_timed(args, make_input, run, check=None, flops_fn=None, name="miniapp",
              extra_fields=None):
    """Warmup + timed runs with per-run report lines.  With ``--trace DIR``
    the first timed run is captured by the JAX profiler (host + device
    timelines; XLA op breakdown per pipeline stage).

    ``extra_fields`` (optional thunk -> dict) is called after each timed run
    and its entries ride along on the report line and the ``run`` metrics
    record — drivers use it to surface solver info (refinement iterations,
    convergence, fallbacks) next to the timing it explains."""
    trace_dir = getattr(args, "trace", "")
    stage_times = getattr(args, "stage_times", False)
    if stage_times:
        from dlaf_tpu.common import stagetimer
    metrics_path = getattr(args, "metrics", "")
    if metrics_path:
        # enable BEFORE the warmup compiles so the jax.monitoring compile
        # listeners see them; comms accounting likewise counts each trace
        from dlaf_tpu.obs import comms as ocomms
        from dlaf_tpu.obs import metrics as om

        om.enable(metrics_path)
        om.emit_run_meta(name)
        om.emit_config()
        ocomms.start()
    results = []
    for i in range(-args.nwarmups, args.nruns):
        mat = make_input()
        jax.block_until_ready(mat.data)
        tracing = trace_dir and i == 0
        if tracing:
            jax.profiler.start_trace(trace_dir)
        if stage_times and i >= 0:
            stagetimer.start()
        t0 = time.perf_counter()
        out = run(mat)
        jax.block_until_ready(out.data)
        dt = time.perf_counter() - t0
        if stage_times and i >= 0:
            br = stagetimer.stop()
            if br:
                print(f"[{i}] stages: {stagetimer.report(br, dt)}")
            else:
                print(f"[{i}] stages: none recorded (this driver's "
                      "algorithm has no stage instrumentation)")
            if metrics_path:
                om.emit_stages(br, total=dt)
        if tracing:
            jax.profiler.stop_trace()
            print(f"[0] trace written to {trace_dir}")
        if i < 0:
            continue
        gflops = (flops_fn(args) / dt / 1e9) if flops_fn else float("nan")
        extra = dict(extra_fields()) if extra_fields else {}
        tail = "".join(f" {k}={v}" for k, v in extra.items())
        print(f"[{i}] {name} {dt:.6f}s {gflops:.3f}GFlop/s"
              f" ({args.m}, {args.m}) ({args.mb}, {args.mb}) ({args.grid_rows}, {args.grid_cols})"
              + tail)
        results.append((dt, gflops))
        if metrics_path:
            om.emit(
                "run", name=name, run_index=i, seconds=dt, gflops=gflops,
                m=args.m, mb=args.mb,
                grid=[args.grid_rows, args.grid_cols], dtype=args.type,
                **extra,
            )
        if check and (args.check == "all" or (args.check == "last" and i == args.nruns - 1)):
            check(out)
            print(f"[{i}] check passed")
        if getattr(args, "output_file", "") and i == args.nruns - 1:
            from dlaf_tpu.matrix import io as mio

            mio.save(args.output_file, out)
            print(f"[{i}] output written to {args.output_file}")
    if metrics_path:
        om.emit_comms(ocomms.stop())
        om.close()
        print(f"metrics written to {metrics_path}")
    return results
