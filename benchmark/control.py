#!/usr/bin/env python3
"""Readings that the limits of a cell's comparison are set from.

    python benchmark/control.py --workload <name> --seeds 11,12,13 [--seconds 1]

For each seed, one process runs the cell's configuration as it is stated
(``sound``) and then with the configuration's ``control`` knobs (the
library's own path one precision step down), each for a short closed-loop
window at the cell's own size, and compares the kept outputs with the host
reference exactly as a benchmark run does.  One JSON line per seed and
variant: ``{"seed", "variant", "checks": {name: value}}``.  A limit lies
above every sound reading and below every control reading (PERF.md).

The benchmark's own runs never run the control.  ``--rehearse`` runs it on
virtual CPU devices at a sixteenth of the size (the CPU ignores matmul
precision, so there the two variants read alike).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as bench  # noqa: E402


def _parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated seeds")
    p.add_argument("--seconds", type=float, default=1.0)
    p.add_argument("--rehearse", action="store_true")
    return p.parse_args(argv)


def readings(cell, seed: int, variant: str, seconds: float, rehearse: bool) -> dict:
    """One short window of ``variant`` on ``seed``; the compared numbers."""
    import jax

    args = argparse.Namespace(seed=seed, seconds=seconds, trace=0)
    run = bench.Run(cell, args, jax, jax.devices()[:cell.chips], rehearse,
                    control=variant == "control")
    run.setup()
    w = run.loop(seconds, int(cell.traffic.get("checked_solves", 1)))
    checks = run.check(w.kept)
    return {"seed": seed, "variant": variant, "solves": w.attempted, "failed": w.failed,
            "solve_s": min(w.times), "checks": {k: v for k, (v, _) in checks.items()}}


def main(argv=None) -> int:
    args = _parse(argv)
    cell = bench.resolve(args.workload)
    bench._environment(args.rehearse, cell.chips)
    import jax

    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        raise bench.BenchError("no TPU")
    for seed in (int(s) for s in args.seeds.split(",")):
        for variant in ("sound", "control"):
            print(json.dumps(readings(cell, seed, variant, args.seconds, args.rehearse)),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
