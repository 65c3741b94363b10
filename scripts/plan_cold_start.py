#!/usr/bin/env python
"""Cross-process zero-compile cold-start gate (ISSUE 13 acceptance).

Runs the same warmup twice in SEPARATE processes sharing one persistent
compilation cache dir:

  process 1 (cold): compiles the serve bucket ladder, populating the cache
  process 2 (warm): replays the ladder — must perform ZERO backend
                    compiles (every executable AOT-loads from disk) and
                    serve its first request under the latency gate

Usage: python scripts/plan_cold_start.py [--buckets 16,32,48]
           [--ops potrf,posv] [--max-first-request-s 12]
           [--cache-dir DIR] [--metrics out.jsonl]

Exit 0 when the warm process reports compiles == 0, aot_loads > 0 and
first_request_s under the gate; 1 otherwise.  The in-process variant of
this oracle is tests/test_plan.py::test_zero_recompile_warm_cache; this
script is the honest version — nothing in-memory survives between the
two passes.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPORT_TAG = "PLAN_COLD_START_REPORT:"


def child(args) -> int:
    """One process's half: warm the ladder, time one request, report."""
    # DLAF_TPU_COMPILE_CACHE is in the env (set by the parent) so this
    # exercises the promoted tune.initialize wiring, not an explicit call.
    from dlaf_tpu import tune
    from dlaf_tpu.obs import metrics as om
    from dlaf_tpu.plan import core as plan_core
    from dlaf_tpu.serve import bucketing

    tune.initialize()
    if args.metrics:
        om.enable(args.metrics)
        om.emit_run_meta("plan_cold_start")
    buckets = tuple(int(b) for b in args.buckets.split(","))
    ops = tuple(o.strip() for o in args.ops.split(",") if o.strip())
    summary = plan_core.warmup(buckets=buckets, ops=ops,
                               cache=bucketing.CompiledCache())

    # the "first request": one solve on the smallest bucket, timed
    # end-to-end the way a fresh replica's first caller sees it
    import numpy as np

    from dlaf_tpu.serve import batched

    n = buckets[0]
    spd = np.eye(n, dtype=np.float32)[None] * 2.0
    t0 = time.perf_counter()
    batched.batched_cholesky_factorization("L", spd, None,
                                           cache=bucketing.CompiledCache())
    first_request_s = time.perf_counter() - t0

    report = {
        "plans": summary["plans"],
        "compiles": summary["compiles"],
        "aot_loads": summary["aot_loads"],
        "warmup_s": summary["seconds"],
        "first_request_s": first_request_s,
        "cache_dir": tune.compile_cache_dir(),
    }
    if args.metrics:
        om.close()
    print(REPORT_TAG + json.dumps(report), flush=True)
    return 0


def run_child(argv, env, label):
    out = subprocess.run([sys.executable, os.path.abspath(__file__),
                          "--as-child"] + argv,
                         env=env, capture_output=True, text=True)
    sys.stderr.write(out.stderr)
    for line in out.stdout.splitlines():
        if line.startswith(REPORT_TAG):
            rep = json.loads(line[len(REPORT_TAG):])
            print(f"{label}: plans={rep['plans']} compiles={rep['compiles']} "
                  f"aot_loads={rep['aot_loads']} warmup={rep['warmup_s']:.2f}s "
                  f"first_request={rep['first_request_s'] * 1e3:.1f}ms")
            return rep
    print(out.stdout)
    raise SystemExit(f"{label}: child produced no report "
                     f"(exit {out.returncode})")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--buckets", default="16,32,48")
    p.add_argument("--ops", default="potrf,posv,eigh",
               help="the scenario-library baseline op mix")
    p.add_argument("--max-first-request-s", type=float, default=12.0)
    p.add_argument("--cache-dir", default="")
    p.add_argument("--metrics", default="")
    p.add_argument("--as-child", action="store_true", help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    if args.as_child:
        return child(args)

    from dlaf_tpu import tune

    # the first pass must start cold: the script empties only the fixed
    # directory it owns and refuses a caller's directory that holds entries
    if args.cache_dir:
        cache_dir = args.cache_dir
        if os.path.isdir(cache_dir) and os.listdir(cache_dir):
            raise SystemExit(f"--cache-dir {cache_dir} is not empty; the cold "
                             "pass needs an empty compile cache")
    else:
        cache_dir = os.path.join(tune.DEFAULT_COMPILE_CACHE, "plan_cold_start")
        shutil.rmtree(cache_dir, ignore_errors=True)
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)  # the gate measures cache_dir
    env["DLAF_TPU_COMPILE_CACHE"] = cache_dir
    env["DLAF_TPU_COMPILE_CACHE_MIN_S"] = "0"
    env.setdefault("JAX_PLATFORMS", "cpu")
    passthrough = ["--buckets", args.buckets, "--ops", args.ops]

    cold = run_child(passthrough, env, "cold")
    warm = run_child(
        passthrough + (["--metrics", args.metrics] if args.metrics else []),
        env, "warm")

    failures = []
    if cold["compiles"] <= 0:
        failures.append(f"cold pass compiled nothing ({cold['compiles']}) — "
                        "the persistent cache never engaged")
    if warm["compiles"] != 0:
        failures.append(f"warm pass performed {warm['compiles']} backend "
                        "compiles (want 0)")
    if warm["aot_loads"] <= 0:
        failures.append("warm pass AOT-loaded nothing")
    if warm["first_request_s"] >= args.max_first_request_s:
        failures.append(f"warm first request took {warm['first_request_s']:.2f}s "
                        f">= gate {args.max_first_request_s}s")
    for f in failures:
        print(f"FAIL: {f}")
    if not failures:
        print(f"PASS: zero-compile cold start "
              f"({warm['aot_loads']} AOT loads, first request "
              f"{warm['first_request_s'] * 1e3:.1f}ms, cache {cache_dir})")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
