#!/usr/bin/env python
"""Collectives-tier A/B: psum vs v2 vs pallas (vs fused) POTRF throughput.

Usage: python scripts/collectives_ab.py [--m 4096] [--mb 512] [--nruns 2]
           [--grid RxC] [--tiers psum,v2,pallas,fused] [--probe-budget 20]
           [--out ab.json] [--metrics ab.jsonl]

The ``fused`` leg is the pallas collectives tier PLUS
``trailing_update_impl='fused'`` (ops/pallas_trailing_update): the
trailing GEMM consumes the exchanged row panel straight out of the
ring-DMA landing slots.  Its row A/Bs against the plain ``pallas`` leg —
the measurement that gates promoting ``trailing_update_impl='auto'`` to
the fused tier (tpu_day stage 5h).

For each tier: one ``DeviceWatchdog`` probe (the bench.py liveness
protocol — a dead TPU window classifies as ``DeviceUnresponsiveError``
and the tier's row is stale-flagged instead of hanging the campaign),
then ``nruns`` timed lookahead-POTRF factorizations with trace-time comms
accounting.  Every tier's row carries GFlop/s next to the modeled wire
split (payload / wire / overlapped) so the overlap win the pallas tier
claims is printed beside the throughput it buys.  Rows land in ``--out``
as JSON (one dict per tier) and, with
``--metrics``, in the obs.metrics JSONL stream ('run' + 'comms' + 'bench'
records per tier) for scripts/report_metrics.py.

Runs on the CPU mesh too (where pallas takes the interpret-mode ring and
the numbers only validate the harness) — the real A/B is stage 5f of
scripts/tpu_day.sh.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TIERS = ("psum", "v2", "pallas")
#: pseudo-tier: pallas collectives + the fused Pallas trailing-update
#: consumer (``tune.trailing_update_impl='fused'``)
FUSED_TIER = "fused"


#: benchable consumers and their approximate flop counts (for a relative
#: A/B the absolute constant matters less than using the SAME one per op)
OPS = ("potrf", "gen_to_std", "trtri", "red2band")
_FLOPS = {
    "potrf": lambda m: m**3 / 3,
    "gen_to_std": lambda m: m**3,
    "trtri": lambda m: m**3 / 3,
    "red2band": lambda m: 4 * m**3 / 3,
}


def _op_runner(op, grid, args):
    """(fresh-input factory, driver) for one benchable op."""
    import numpy as np

    import dlaf_tpu.testing as tu
    from dlaf_tpu.matrix.matrix import DistributedMatrix

    spd = tu.random_hermitian_pd(args.m, np.float32, seed=11)
    mb = (args.mb, args.mb)
    dist = lambda arr: DistributedMatrix.from_global(grid, arr, mb)
    if op == "potrf":
        from dlaf_tpu.algorithms.cholesky import cholesky_factorization

        a = np.tril(spd)
        return (lambda: dist(a)), lambda m: cholesky_factorization("L", m)
    if op == "gen_to_std":
        from dlaf_tpu.algorithms.gen_to_std import generalized_to_standard

        a = np.tril(spd)
        fac = np.linalg.cholesky(tu.random_hermitian_pd(args.m, np.float32,
                                                        seed=12))
        return ((lambda: (dist(a), dist(fac))),
                lambda ms: generalized_to_standard("L", *ms))
    if op == "trtri":
        from dlaf_tpu.algorithms.inverse import triangular_inverse

        l = np.linalg.cholesky(spd)
        return (lambda: dist(l)), lambda m: triangular_inverse("L", "N", m)
    if op == "red2band":
        from dlaf_tpu.algorithms.reduction_to_band import reduction_to_band

        a = np.tril(spd)
        return (lambda: dist(a)), lambda m: reduction_to_band(m)[0]
    raise SystemExit(f"collectives_ab: unknown --op {op!r}; use {OPS}")


def _bench_tier(tier, grid, args, om, ocomms):
    from dlaf_tpu import tune
    from dlaf_tpu.health import DeviceUnresponsiveError
    from dlaf_tpu.resilience import DeviceWatchdog

    row = {"tier": tier, "op": args.op, "m": args.m, "mb": args.mb,
           "grid": list(grid.grid_size), "nruns": args.nruns}
    try:
        row["probe_s"] = DeviceWatchdog(budget_s=args.probe_budget).probe()
    except DeviceUnresponsiveError as exc:
        row.update(alive=False, stale=True, error=str(exc))
        print(f"[{tier}] device unresponsive, row stale-flagged: {exc}")
        return row
    row["alive"] = True

    if tier == FUSED_TIER:
        tune.get_tune_parameters().update(
            collectives_impl="pallas", trailing_update_impl="fused")
    else:
        tune.get_tune_parameters().update(
            collectives_impl=tier, trailing_update_impl="xla")
    make_inputs, driver = _op_runner(args.op, grid, args)
    ocomms.start()
    times = []
    for i in range(-1, args.nruns):  # one warmup (the compile) + timed runs
        inputs = make_inputs()
        mats = inputs if isinstance(inputs, tuple) else (inputs,)
        for m_ in mats:
            m_.data.block_until_ready()
        t0 = time.perf_counter()
        out = driver(inputs)
        out.data.block_until_ready()
        dt = time.perf_counter() - t0
        if i >= 0:
            times.append(dt)
    acc = ocomms.stop()
    rows = ocomms.as_records(acc)
    best = min(times)
    gflops = _FLOPS[args.op](args.m) / best / 1e9
    wire = sum(r["modeled_wire_bytes"] for r in rows)
    overlapped = sum(r["overlapped_wire_bytes"] for r in rows)
    row.update(
        seconds=best, gflops=gflops,
        payload_bytes=sum(r["bytes"] for r in rows),
        modeled_wire_bytes=wire,
        overlapped_wire_bytes=overlapped,
        exposed_wire_bytes=wire - overlapped,
    )
    print(f"[{tier}] {best:.4f}s {gflops:.2f} GFlop/s  wire {wire}B "
          f"(exposed {wire - overlapped}B, overlapped {overlapped}B)")
    if om is not None:
        om.emit("run", name=f"{args.op}_{tier}", run_index=0, seconds=best,
                gflops=gflops, m=args.m, mb=args.mb,
                grid=list(grid.grid_size), dtype="s")
        om.emit_comms(acc)
        om.emit("bench", record={"metric": f"{args.op}_gflops_{tier}",
                                 "value": gflops, "unit": "GFlop/s",
                                 "wire_bytes": wire,
                                 "overlapped_wire_bytes": overlapped})
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--op", default="potrf", choices=OPS,
                    help="consumer to A/B (each gets its own artifact)")
    ap.add_argument("--m", type=int, default=4096)
    ap.add_argument("--mb", type=int, default=512)
    ap.add_argument("--nruns", type=int, default=2)
    ap.add_argument("--grid", default="", help="RxC (default: most-square)")
    ap.add_argument("--tiers", default=",".join(TIERS))
    ap.add_argument("--probe-budget", type=float, default=20.0)
    ap.add_argument("--out", default="")
    ap.add_argument("--metrics", default="")
    ap.add_argument("--flight-dir", default="",
                    help="enable the crash flight recorder; a failed "
                         "watchdog probe drops flight_*.json here")
    args = ap.parse_args(argv)

    from dlaf_tpu import tune
    from dlaf_tpu.comm.grid import Grid, Size2D
    from dlaf_tpu.obs import comms as ocomms
    from dlaf_tpu.obs import metrics as om_mod

    if args.flight_dir:
        from dlaf_tpu.obs import flight

        flight.enable(dump_dir=args.flight_dir)

    om = None
    if args.metrics:
        om_mod.enable(args.metrics)
        om_mod.emit_run_meta("collectives_ab")
        om_mod.emit_config()
        om = om_mod

    if args.grid:
        r, c = (int(v) for v in args.grid.lower().split("x"))
        grid = Grid.create(Size2D(r, c))
    else:
        grid = Grid.create()

    # lookahead is the consumer the pallas tier exists for — pin it on, and
    # restore the caller's knobs afterwards
    tp = tune.get_tune_parameters()
    saved = (tp.collectives_impl, tp.cholesky_lookahead,
             tp.trailing_update_impl, tp.trsm_lookahead,
             tp.gen_to_std_backend)
    tp.update(cholesky_lookahead=True)
    if args.op == "gen_to_std":
        # the her2k backend + lookahead'd solves are where the fused
        # consumer applies; the composed backend would A/B nothing
        tp.update(gen_to_std_backend="fused", trsm_lookahead=True)
    try:
        results = [
            _bench_tier(t.strip(), grid, args, om, ocomms)
            for t in args.tiers.split(",") if t.strip()
        ]
    finally:
        tp.update(collectives_impl=saved[0], cholesky_lookahead=saved[1],
                  trailing_update_impl=saved[2], trsm_lookahead=saved[3],
                  gen_to_std_backend=saved[4])
        if om is not None:
            om_mod.close()
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(results, fh, indent=2)
        print(f"rows written to {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
