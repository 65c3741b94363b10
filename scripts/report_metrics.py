#!/usr/bin/env python
"""Summarize a dlaf_tpu.obs metrics JSONL file on the terminal.

Usage: python scripts/report_metrics.py out.jsonl [more.jsonl ...]

Renders, per file: the run identity, the tune config snapshot (non-default
knobs first is not attempted — the snapshot is small), per-run wall times,
the per-stage breakdown, the per-collective message/byte accounting, and
jit compile totals with persistent-cache hit/miss counts.  Every record is
schema-validated on read (obs.metrics.validate_record), so a malformed or
foreign file fails loudly instead of summarizing garbage.

``dlaf_tpu.obs/6`` streams additionally carry the fleet telemetry plane:
``telemetry`` records (the merged counter/gauge/histogram snapshot the
fleet emits at close) render as a roll-up table, ``slo_burn`` events as
the per-tenant burn-rate story, and the service-time harvest (``plan``
``harvest`` / ``profile_loaded`` events) as one line each.
"""
from __future__ import annotations

import os
import signal
import sys
from collections import defaultdict

# die quietly when piped to head & co.
try:
    signal.signal(signal.SIGPIPE, signal.SIG_DFL)
except (AttributeError, ValueError):  # pragma: no cover - non-POSIX
    pass

# runnable as `python scripts/report_metrics.py` from a checkout (the
# common case) without an install
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _fmt_bytes(b: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(b) < 1024 or unit == "GiB":
            return f"{b:.1f}{unit}" if unit != "B" else f"{int(b)}B"
        b /= 1024
    return f"{b:.1f}GiB"


def _summarize_analysis(path: str, doc: dict) -> int:
    """Roll-up for a ``python -m dlaf_tpu.analysis --format json`` findings
    file (a single JSON object, not a metrics JSONL stream)."""
    from dlaf_tpu.analysis.rules import RULES

    counts = doc.get("counts_by_rule", {})
    total = sum(counts.values())
    print(f"== {path}: {doc['tool']} findings "
          f"(schema {doc.get('schema', '?')}, {doc.get('files', '?')} files)")
    print(f"-- findings: {total} total, {len(doc.get('new', []))} new, "
          f"{len(doc.get('suppressed', []))} suppressed, "
          f"{len(doc.get('stale_baseline', []))} stale baseline entries")
    summaries = {r.RULE: r.SUMMARY for r in RULES}
    for rule in sorted(set(counts) | set(doc.get("rules", []))):
        print(f"   {rule}: {counts.get(rule, 0):4d}  "
              f"{summaries.get(rule, '')}")
    worst = doc.get("findings", [])[:10]
    for f in worst:
        print(f"   {f['rule']} {f['path']}:{f['line']} [{f['symbol']}] "
              f"{f['message']}")
    if len(doc.get("findings", [])) > 10:
        print(f"   ... {len(doc['findings']) - 10} more (see the JSON)")
    ok = doc.get("ok", total == 0)
    print(f"-- analysis: {'clean' if ok else 'FINDINGS OUTSIDE BASELINE'}")
    return 0 if ok else 1


def _load_analysis_doc(path: str):
    """The parsed findings object when ``path`` is a dlaf_tpu.analysis JSON
    report, else None (JSONL metrics streams and anything else fall through
    to the schema-validated reader)."""
    import json

    try:
        with open(path) as fh:
            doc = json.load(fh)
    except (OSError, ValueError):
        return None
    if isinstance(doc, dict) and doc.get("tool") == "dlaf_tpu.analysis":
        return doc
    return None


def summarize(path: str) -> int:
    from dlaf_tpu.obs import metrics

    doc = _load_analysis_doc(path)
    if doc is not None:
        return _summarize_analysis(path, doc)
    recs = metrics.read_jsonl(path)
    schemas = sorted({r.get("schema", "?") for r in recs}) or [metrics.SCHEMA]
    print(f"== {path}: {len(recs)} records ({', '.join(schemas)})")
    by_kind = defaultdict(list)
    for r in recs:
        by_kind[r["kind"]].append(r)

    for r in by_kind.get("run_meta", []):
        print(f"-- run: {r.get('name', '?')}  rank {r['rank']}  "
              f"jax {r['jax_version']}  backend {r['backend']}  "
              f"{r['process_count']} proc x {r.get('local_device_count', '?')} dev "
              f"({r['device_count']} total)")
        # self-identifying artifacts: scenario name + seed (+ sizing) when
        # the run stamped them (loadgen/scenario/replay runs do)
        ident = "  ".join(f"{k}={r[k]}" for k in
                          ("scenario", "seed", "requests", "replicas")
                          if k in r)
        if ident:
            print(f"   {ident}")
        print(f"   argv: {' '.join(r['argv'])}")

    for r in by_kind.get("config", []):
        cfg = r["config"]
        keys = sorted(cfg)
        print(f"-- config ({len(keys)} knobs):")
        line = []
        for k in keys:
            line.append(f"{k}={cfg[k]}")
            if len(line) == 4:
                print("   " + "  ".join(line))
                line = []
        if line:
            print("   " + "  ".join(line))

    runs = by_kind.get("run", [])
    if runs:
        print(f"-- runs ({len(runs)}):")
        for r in runs:
            gf = r.get("gflops", float("nan"))
            print(f"   [{r.get('run_index', '?')}] {r['name']:24s} "
                  f"{r['seconds']:10.6f}s {gf:10.3f} GFlop/s  rank {r['rank']}")

    kernels = by_kind.get("kernel", [])
    if kernels:
        print(f"-- kernels ({len(kernels)}):")
        for r in kernels:
            print(f"   {r['name']:20s} {r['seconds'] * 1e3:9.3f} ms "
                  f"{r.get('gflops', float('nan')):10.1f} GFlop/s")

    for r in by_kind.get("stages", []):
        total = r.get("total_s")
        print(f"-- stages (rank {r['rank']}"
              + (f", total {total:.3f}s" if total else "") + "):")
        for name, secs in sorted(r["stages"].items(), key=lambda kv: -kv[1]):
            pct = f" {100 * secs / total:5.1f}%" if total else ""
            print(f"   {name:24s} {secs:10.3f}s{pct}")

    comms = by_kind.get("comms", [])
    if comms:
        from dlaf_tpu.obs.comms import wire_model

        # aggregate across ranks/records: same key -> summed counts
        agg = defaultdict(lambda: [0, 0, 0, 0])
        for r in comms:
            for row in r["rows"]:
                k = (row["collective"], row["dtype"], row["axis"], row["axis_size"])
                agg[k][0] += row["messages"]
                agg[k][1] += row["bytes"]
                # pre-wire-model files lack the column: model it here
                agg[k][2] += row.get(
                    "modeled_wire_bytes", wire_model(k[0], k[3], row["bytes"])
                )
                # pre-overlap files: everything exposed
                agg[k][3] += row.get("overlapped_wire_bytes", 0)
        print(f"-- comms ({len(agg)} collective classes, trace-time counts):")
        print(f"   {'collective':22s} {'dtype':10s} {'axis':5s} "
              f"{'P':>3s} {'msgs':>8s} {'payload':>10s} {'wire(model)':>11s} "
              f"{'overlapped':>10s}")
        total_wire = 0
        total_overlap = 0
        saved = 0
        for (kind, dtype, axis, p), (msgs, nbytes, wire, overlap) in sorted(
            agg.items()
        ):
            print(f"   {kind:22s} {dtype:10s} {axis or '-':5s} "
                  f"{p:3d} {msgs:8d} {_fmt_bytes(nbytes):>10s} "
                  f"{_fmt_bytes(wire):>11s} "
                  f"{_fmt_bytes(overlap) if overlap else '-':>10s}")
            total_wire += wire
            total_overlap += overlap
            for suffix in ("_v2", "_pallas"):
                if kind.endswith(suffix):
                    # what the same payload would cost on the reduce tier
                    saved += wire_model(kind[: -len(suffix)], p, nbytes) - wire
                    break
        print(f"   modeled wire bytes total: {_fmt_bytes(total_wire)}"
              f"  (exposed {_fmt_bytes(total_wire - total_overlap)}, "
              f"overlapped {_fmt_bytes(total_overlap)})"
              + (f"  (saved {_fmt_bytes(saved)} vs reduce-tier collectives)"
                 if saved else ""))

    compiles = by_kind.get("compile", [])
    if compiles:
        tot = sum(r["duration_s"] for r in compiles)
        print(f"-- jit compiles: {len(compiles)} events, {tot:.2f}s total")
        slow = sorted(compiles, key=lambda r: -r["duration_s"])[:5]
        for r in slow:
            print(f"   {r['duration_s']:8.2f}s  {r['event']}")

    cache = by_kind.get("compile_cache", [])
    if cache:
        counts = defaultdict(int)
        for r in cache:
            counts[r["event"]] += 1
        hits = sum(n for e, n in counts.items() if "hit" in e)
        misses = sum(n for e, n in counts.items() if "miss" in e)
        print(f"-- compile cache: {hits} hits / {misses} misses "
              f"({len(cache)} cache/compile events)")
        for e, n in sorted(counts.items()):
            print(f"   {n:6d}  {e}")

    benches = by_kind.get("bench", [])
    for r in benches:
        rec = r["record"]
        print(f"-- bench: {rec.get('metric', '?')} = {rec.get('value', '?')} "
              f"{rec.get('unit', '')}  mfu={rec.get('mfu', 'n/a')}")
        if "heev" in rec:
            h = rec["heev"]
            print(f"   heev: {h.get('metric', '?')} {h.get('seconds', '?')}s "
                  f"{h.get('gflops', '?')} GFlop/s")

    # precision roll-up: any record that carries a gemm_precision label
    # (precision_ab rows, bench posv_precision columns) lands in one table:
    # measured GFlop/s, the modeled emulation GFlop/s (the tier's
    # GEMM_TIER_FLOP_MULTIPLIER x as many bf16 products), and the residual
    # the throughput was bought at
    prec = []
    for r in by_kind.get("run", []):
        if "gemm_precision" in r:
            prec.append({"label": r.get("name", "?"),
                         "tier": r["gemm_precision"],
                         "gflops": r.get("gflops"),
                         "refined": r.get("refined", False)})
    for r in benches:
        rec = r["record"]
        if "gemm_precision" in rec:
            prec.append({"label": rec.get("metric", "?"),
                         "tier": rec["gemm_precision"],
                         "gflops": rec.get("value"),
                         "modeled": rec.get("modeled_gflops"),
                         "residual": rec.get("residual"),
                         "refined": rec.get("refined", False)})
        for col in ("default", "bf16x3_refined"):
            sub = rec.get("posv_precision", {}).get(col)
            if sub:
                prec.append({"label": f"{rec['posv_precision'].get('metric', '?')}:{col}",
                             "tier": sub.get("gemm_precision", "?"),
                             "gflops": sub.get("gflops"),
                             "residual": sub.get("residual"),
                             "refined": sub.get("refine_to") is not None})
    if prec:
        tiers = defaultdict(int)
        for p in prec:
            tiers[p["tier"]] += 1
        print(f"-- precision ({len(prec)} records: "
              + ", ".join(f"{t} x{n}" for t, n in sorted(tiers.items())) + "):")
        print(f"   {'label':36s} {'tier':8s} {'GFlop/s':>9s} "
              f"{'modeled':>9s} {'residual':>10s} {'refined':>7s}")
        for p in prec:
            gf = f"{p['gflops']:9.2f}" if p.get("gflops") is not None else f"{'-':>9s}"
            md = f"{p['modeled']:9.2f}" if p.get("modeled") is not None else f"{'-':>9s}"
            rs = f"{p['residual']:10.2e}" if p.get("residual") is not None else f"{'-':>10s}"
            print(f"   {p['label']:36s} {p['tier']:8s} {gf} {md} {rs} "
                  f"{'yes' if p['refined'] else 'no':>7s}")

    health = by_kind.get("health", [])
    if health:
        counts = defaultdict(int)
        for r in health:
            counts[r["event"]] += 1
        print(f"-- health events ({len(health)}):")
        for e, n in sorted(counts.items()):
            print(f"   {n:6d}  {e}")
        # resilience roll-up: the bounded-time/restart story in three
        # lines (deadlines that fired, probe outcomes, checkpoint traffic)
        # — see dlaf_tpu/resilience.py EVENTS
        res = {e: n for e, n in counts.items()
               if e in ("deadline_exceeded", "deadline_expired", "device_probe",
                        "device_unresponsive",
                        "checkpoint_written", "checkpoint_restored",
                        "checkpoint_config_mismatch")}
        if res:
            print("-- resilience:")
            dl = res.get("deadline_exceeded", 0) + res.get("deadline_expired", 0)
            print(f"   deadlines hit: {dl} "
                  f"(exceeded {res.get('deadline_exceeded', 0)}, "
                  f"monitor-expired {res.get('deadline_expired', 0)})")
            print(f"   watchdog probes: {res.get('device_probe', 0)} ok, "
                  f"{res.get('device_unresponsive', 0)} unresponsive")
            print(f"   checkpoints: {res.get('checkpoint_written', 0)} written, "
                  f"{res.get('checkpoint_restored', 0)} restored"
                  + (f", {res['checkpoint_config_mismatch']} config drifts"
                     if res.get("checkpoint_config_mismatch") else ""))
        for r in health:
            detail = "  ".join(
                f"{k}={r[k]}"
                for k in sorted(r)
                if k not in ("schema", "kind", "ts", "rank", "event")
            )
            print(f"   rank {r['rank']}  {r['event']}" + (f"  {detail}" if detail else ""))

    serve = by_kind.get("serve", [])
    if serve:
        counts = defaultdict(int)
        for r in serve:
            counts[r["event"]] += 1
        hits, misses = counts.get("cache_hit", 0), counts.get("cache_miss", 0)
        rate = hits / (hits + misses) if hits + misses else 0.0
        print(f"-- serve ({len(serve)} events):")
        print(f"   compile cache: {hits} hits / {misses} misses "
              f"({100 * rate:.0f}% hit rate), {counts.get('compile', 0)} compiles, "
              f"{counts.get('cache_evict', 0)} evictions")
        lat = sorted(r["queue_s"] for r in serve
                     if r["event"] == "request_done" and "queue_s" in r)
        if lat:
            p50 = lat[len(lat) // 2]
            p95 = lat[min(len(lat) - 1, int(len(lat) * 0.95))]
            print(f"   queue latency: p50 {p50 * 1e3:.1f} ms  "
                  f"p95 {p95 * 1e3:.1f} ms  ({len(lat)} requests)")
        # per-bucket roll-up: requests and fused-dispatch throughput
        per_bucket = defaultdict(lambda: [0, 0, 0.0])  # reqs, batches, seconds
        for r in serve:
            if r["event"] == "request_done":
                per_bucket[r.get("bucket", "?")][0] += 1
            elif r["event"] == "batch":
                pb = per_bucket[r.get("bucket", "?")]
                pb[1] += 1
                pb[2] += float(r.get("seconds", 0.0))
        rows = {b: v for b, v in per_bucket.items() if v[0] or v[1]}
        if rows:
            print(f"   {'bucket':>10s} {'requests':>9s} {'batches':>8s} "
                  f"{'problems/s':>11s}")
            for b, (nreq, nbatch, secs) in sorted(rows.items()):
                thr = f"{nreq / secs:11.1f}" if secs and nreq else f"{'-':>11s}"
                print(f"   {b:>10s} {nreq:9d} {nbatch:8d} {thr}")
        # cache churn attribution: hit/miss/evict per (op, n, dtype) labels
        # carried by the bucketing events since the gateway PR
        churn = defaultdict(lambda: [0, 0, 0])  # hits, misses, evicts
        for r in serve:
            if r["event"] in ("cache_hit", "cache_miss", "cache_evict") and "op" in r:
                k = (r["op"], r.get("n", "?"), r.get("dtype", "?"))
                idx = ("cache_hit", "cache_miss", "cache_evict").index(r["event"])
                churn[k][idx] += 1
        if churn:
            print(f"   {'op':>8s} {'n':>6s} {'dtype':>6s} {'hits':>7s} "
                  f"{'misses':>7s} {'evicts':>7s}")
            for (op, n, dt), (h, m, e) in sorted(churn.items(), key=str):
                print(f"   {op:>8s} {n!s:>6s} {dt:>6s} {h:7d} {m:7d} {e:7d}")
        if counts.get("compile_grace"):
            print(f"   cold-start compile grace consumed: "
                  f"{counts['compile_grace']} dispatches")
        # gateway roll-up: per-tenant SLO latencies + QoS action counts
        gw_done = [r for r in serve if r["event"] == "gw_done"]
        if gw_done:
            per_tenant = defaultdict(lambda: {"lat": [], "ok": 0, "err": 0})
            for r in gw_done:
                t = per_tenant[r.get("tenant", "?")]
                if r.get("outcome") == "ok":
                    t["ok"] += 1
                    t["lat"].append(float(r.get("latency_s", 0.0)))
                else:
                    t["err"] += 1
            print(f"-- gateway ({len(gw_done)} completed requests):")
            print(f"   {'tenant':>12s} {'ok':>7s} {'err':>6s} {'p50 ms':>8s} "
                  f"{'p95 ms':>8s} {'p99 ms':>8s}")
            for name, t in sorted(per_tenant.items()):
                lat = sorted(t["lat"])

                def pct(q, lat=lat):
                    if not lat:
                        return float("nan")
                    return lat[min(len(lat) - 1, int(q * len(lat)))] * 1e3

                print(f"   {name:>12s} {t['ok']:7d} {t['err']:6d} "
                      f"{pct(0.50):8.1f} {pct(0.95):8.1f} {pct(0.99):8.1f}")
            batches = [r for r in serve if r["event"] == "gw_batch"]
            if batches:
                fill = sum(float(r.get("fill", 0.0)) for r in batches) / len(batches)
                print(f"   batches: {len(batches)}  mean fill {fill:.2f}  "
                      f"dispatched {sum(int(r.get('batch', 0)) for r in batches)}")
            qos_counts = {e: n for e, n in sorted(counts.items())
                          if e.startswith(("gw_shed", "gw_evict", "gw_hold"))}
            if qos_counts:
                print("   qos: " + "  ".join(f"{e}={n}" for e, n in qos_counts.items()))
            fo = {e: n for e, n in counts.items()
                  if e.startswith("replica_") and n}
            if fo:
                print("   failover: "
                      + "  ".join(f"{e}={n}" for e, n in sorted(fo.items())))

    fleet = by_kind.get("fleet", [])
    if fleet:
        counts = defaultdict(int)
        for r in fleet:
            counts[r["event"]] += 1
        print(f"-- fleet ({len(fleet)} events):")
        life = "  ".join(f"{e}={counts[e]}" for e in
                         ("worker_spawn", "worker_ready", "worker_exit",
                          "worker_restart", "circuit_open")
                         if counts.get(e))
        if life:
            print(f"   lifecycle: {life}")
        # per-worker roll-up (worker_stats is emitted once per handle at
        # fleet close; generation > 1 means the supervisor restarted it)
        wstats = [r for r in fleet if r["event"] == "worker_stats"]
        if wstats:
            print(f"   {'worker':>10s} {'gen':>4s} {'served':>7s} "
                  f"{'failures':>9s} {'circuit':>8s}")
            for r in sorted(wstats, key=lambda r: str(r.get("worker", "?"))):
                print(f"   {r.get('worker', '?'):>10s} {r.get('gen', 0):4d} "
                      f"{r.get('served', 0):7d} {r.get('failures', 0):9d} "
                      f"{'OPEN' if r.get('circuit_open') else 'closed':>8s}")
        # warmup attribution: the zero-compile restart contract in one line
        readies = [r for r in fleet if r["event"] == "worker_ready"]
        if readies:
            wc = sum(int(r.get("warm_compiles", 0)) for r in readies)
            wa = sum(int(r.get("warm_aot_loads", 0)) for r in readies)
            zero = sum(1 for r in readies if not int(r.get("warm_compiles", 0)))
            print(f"   warmups: {len(readies)} worker readies — "
                  f"{wc} compiles, {wa} AOT loads "
                  f"({zero} zero-compile starts)")
        drains = [r for r in fleet if r["event"] == "failover_drain"]
        if drains:
            by_mode = defaultdict(lambda: [0, 0])
            for r in drains:
                bm = by_mode[r.get("mode", "?")]
                bm[0] += 1
                bm[1] += int(r.get("count", 0))
            print("   failover drains: " + "  ".join(
                f"{m}={n} ({c} requests)" for m, (n, c)
                in sorted(by_mode.items())))
        if counts.get("partition") or counts.get("partition_heal"):
            print(f"   partitions: {counts.get('partition', 0)} injected, "
                  f"{counts.get('partition_heal', 0)} healed")
        if counts.get("flight_collected"):
            print(f"   child flight dumps collected: "
                  f"{counts['flight_collected']}")
        scales = [r for r in fleet
                  if r["event"] in ("scale_up", "scale_down",
                                    "scale_up_joined", "scale_up_failed",
                                    "scale_down_retired")]
        if scales:
            print(f"   autoscale decisions ({len(scales)}):")
            for r in scales:
                sig = "  ".join(f"{k}={r[k]}" for k in
                                ("p95_s", "queued", "workers", "worker",
                                 "shed") if k in r)
                print(f"      {r['event']:20s} {sig}")

    plan = by_kind.get("plan", [])
    if plan:
        counts = defaultdict(int)
        for r in plan:
            counts[r["event"]] += 1
        hits, misses = counts.get("hit", 0), counts.get("miss", 0)
        rate = hits / (hits + misses) if hits + misses else 0.0
        builds = [r for r in plan if r["event"] == "build"]
        compiled = sum(int(r.get("compiles", 0)) for r in builds)
        aot = sum(int(r.get("aot_loads", 0)) for r in builds)
        bsecs = sum(float(r.get("seconds", 0.0)) for r in builds)
        print(f"-- plan ({len(plan)} events):")
        print(f"   registry: {hits} hits / {misses} misses "
              f"({100 * rate:.0f}% hit rate), {counts.get('evict', 0)} evictions")
        print(f"   builds: {len(builds)} in {bsecs:.2f}s — "
              f"{compiled} backend compiles, {aot} AOT loads"
              + ("  [zero-compile]" if builds and not compiled else ""))
        warm = [r for r in plan if r["event"] == "warmup"]
        if warm:
            wc = sum(int(r.get("compiles", 0)) for r in warm)
            wa = sum(int(r.get("aot_loads", 0)) for r in warm)
            ws = sum(float(r.get("seconds", 0.0)) for r in warm)
            print(f"   warmup: {len(warm)} plans in {ws:.2f}s — "
                  f"{wc} compiles, {wa} AOT loads")
            print(f"   {'op':>8s} {'n':>6s} {'dtype':>6s} {'seconds':>8s} "
                  f"{'compiles':>9s} {'aot':>5s}")
            for r in warm:
                print(f"   {r.get('op', '?'):>8s} {r.get('n', '?')!s:>6s} "
                      f"{r.get('dtype', '?'):>6s} "
                      f"{float(r.get('seconds', 0.0)):8.2f} "
                      f"{int(r.get('compiles', 0)):9d} "
                      f"{int(r.get('aot_loads', 0)):5d}")
        decs = [r for r in plan if r["event"] == "decision"]
        if decs:
            src = defaultdict(int)
            for r in decs:
                src[r.get("source", "?")] += 1
            print(f"   autotune decisions: {len(decs)} ("
                  + ", ".join(f"{s} x{n}" for s, n in sorted(src.items())) + ")")
        # service-time harvest: fleet telemetry rolled into a reusable
        # plan profile, and profiles loaded back into the autotuner
        for r in plan:
            if r["event"] == "harvest":
                print(f"   harvest: {r.get('entries', '?')} profile entries "
                      f"from {r.get('geometries_seen', '?')} geometries "
                      f"-> {r.get('path', '?')}")
            elif r["event"] == "profile_loaded":
                print(f"   profile loaded: {r.get('entries', '?')} entries "
                      f"from {r.get('path', '?')}"
                      + ("  [harvested]" if r.get("harvested") else ""))

    tel = by_kind.get("telemetry", [])
    if tel:
        from dlaf_tpu.obs import telemetry as tlm

        # the LAST snapshot is the authoritative one (the fleet emits its
        # merged parent+worker view once at close)
        snap = tel[-1].get("snapshot", {})
        counters = snap.get("counters", {})
        gauges = snap.get("gauges", {})
        hists = snap.get("hists", {})
        print(f"-- telemetry ({len(tel)} snapshot(s), scope "
              f"{tel[-1].get('scope', '?')}): {len(counters)} counters, "
              f"{len(gauges)} gauges, {len(hists)} histograms")
        for k, v in sorted(counters.items()):
            print(f"   {k:44s} {v:>12g}")
        for k, v in sorted(gauges.items()):
            print(f"   {k:44s} {v:>12g}")
        for k, h in sorted(hists.items()):
            cnt = int(h.get("count", 0))
            p50 = tlm.percentile(h, 0.50)
            p95 = tlm.percentile(h, 0.95)
            print(f"   {k:44s} n={cnt:<8d} p50<={p50:g} p95<={p95:g}")

    burns = by_kind.get("slo_burn", [])
    if burns:
        per_tenant = defaultdict(lambda: [0, 0])  # firings, clears
        for r in burns:
            per_tenant[r.get("tenant", "?")][0 if r.get("firing") else 1] += 1
        print(f"-- slo burn ({len(burns)} transitions):")
        for t, (fired, cleared) in sorted(per_tenant.items()):
            print(f"   {t:>12s} fired {fired}x, cleared {cleared}x")
        last = burns[-1]
        print(f"   last: tenant {last.get('tenant', '?')} "
              f"fast {last.get('fast_burn', 0.0):.1f}x / "
              f"slow {last.get('slow_burn', 0.0):.1f}x "
              f"{'FIRING' if last.get('firing') else 'cleared'}")

    for r in by_kind.get("scenario", []):
        if r["event"] == "result":
            counts = r.get("counts", {})
            outcome = "  ".join(f"{k}={v}" for k, v in counts.items() if v)
            print(f"-- scenario {r.get('scenario', '?')!r} (seed "
                  f"{r.get('seed', '?')}): "
                  f"{'PASS' if r.get('passed') else 'FAIL'}  "
                  f"{r.get('requests', '?')} requests in "
                  f"{r.get('elapsed_s', 0.0):.1f}s, "
                  f"fill {r.get('batch_fill', 0.0):.2f}")
            if outcome:
                print(f"   outcomes: {outcome}")
            for f in r.get("failures", []):
                print(f"   SLO FAIL: {f}")
        elif r["event"] == "trace_chains":
            print(f"-- trace chains ({'fleet' if r.get('fleet') else 'local'}): "
                  f"{r.get('full', 0)}/{r.get('roots', 0)} complete "
                  f"({100 * r.get('frac', 0.0):.0f}%) over {r.get('need', [])}")
        elif r["event"] == "replay":
            print(f"-- replay of {r.get('source', '?')} "
                  f"(scenario {r.get('scenario', '?')!r}): "
                  f"{'MATCH' if r.get('matched') else 'DIVERGED'}  "
                  f"{r.get('total', '?')} requests, "
                  f"{r.get('outcome_mismatches', 0)} outcome / "
                  f"{r.get('group_mismatches', 0)} group-key divergences")

    cap_recs = by_kind.get("capacity", [])
    if cap_recs:
        fits = [r for r in cap_recs if r["event"] == "fit"]
        preds = [r for r in cap_recs if r["event"] == "prediction"]
        print(f"-- capacity model ({len(fits)} service classes, "
              f"{len(preds)} predictions):")
        if fits:
            print(f"   {'op':>8s} {'bucket':>7s} {'a ms':>8s} {'b ms/req':>9s} "
                  f"{'mean/req ms':>12s} {'batches':>8s}")
            for r in sorted(fits, key=lambda r: (r.get("op", ""),
                                                 r.get("bucket", 0))):
                print(f"   {r.get('op', '?'):>8s} {r.get('bucket', 0):7d} "
                      f"{r.get('a_s', 0.0) * 1e3:8.2f} "
                      f"{r.get('b_s', 0.0) * 1e3:9.3f} "
                      f"{r.get('per_req_s', 0.0) * 1e3:12.2f} "
                      f"{r.get('batches', 0):8d}")
        for r in preds:
            print(f"   replicas_needed(req_s={r.get('req_s', 0.0):.0f}, "
                  f"p99<={r.get('p99_target_s', 0.0) * 1e3:.1f} ms) = "
                  f"{r.get('replicas_needed', '?')} "
                  f"(observed {r.get('observed_replicas', '?')}, "
                  f"predicted p99 {r.get('predicted_p99_s', 0.0) * 1e3:.1f} ms, "
                  f"rho {r.get('rho', 0.0):.2f}, "
                  f"confidence {r.get('confidence', '?')})")

    span_recs = by_kind.get("span", [])
    if span_recs:
        def pctl(sorted_vals, q):
            if not sorted_vals:
                return float("nan")
            return sorted_vals[min(len(sorted_vals) - 1, int(q * len(sorted_vals)))]

        by_name = defaultdict(list)
        for r in span_recs:
            by_name[r["name"]].append(float(r["dur_s"]))
        print(f"-- spans ({len(span_recs)} spans, {len(by_name)} names):")
        print(f"   {'name':28s} {'count':>7s} {'total s':>9s} "
              f"{'p50 ms':>8s} {'p95 ms':>8s}")
        for name, durs in sorted(by_name.items(), key=lambda kv: -sum(kv[1])):
            ds = sorted(durs)
            print(f"   {name:28s} {len(ds):7d} {sum(ds):9.3f} "
                  f"{pctl(ds, 0.50) * 1e3:8.1f} {pctl(ds, 0.95) * 1e3:8.1f}")
        # per-request breakdown: where the gateway requests' latency went —
        # the direct children of each gw.request root tile its interval
        # (queue -> batch -> dispatch -> pool queue -> solve)
        roots = {r["span_id"]: r for r in span_recs if r["name"] == "gw.request"}
        if roots:
            phase_tot = defaultdict(float)
            for r in span_recs:
                if r.get("parent_id") in roots:
                    phase_tot[r["name"]] += float(r["dur_s"])
            total_lat = sum(float(r["dur_s"]) for r in roots.values())
            print(f"   request breakdown ({len(roots)} requests, "
                  f"{total_lat:.3f}s summed latency):")
            for name, tot in sorted(phase_tot.items(), key=lambda kv: -kv[1]):
                pct = f" {100 * tot / total_lat:5.1f}%" if total_lat else ""
                print(f"      {name:24s} {tot:9.3f}s{pct}")
            per_tenant = defaultdict(list)
            for r in roots.values():
                per_tenant[str(r.get("tenant", "?"))].append(float(r["dur_s"]))
            print(f"   per-tenant critical path:")
            print(f"   {'tenant':>12s} {'requests':>9s} {'p50 ms':>8s} {'p95 ms':>8s}")
            for t, durs in sorted(per_tenant.items()):
                ds = sorted(durs)
                print(f"   {t:>12s} {len(ds):9d} "
                      f"{pctl(ds, 0.50) * 1e3:8.1f} {pctl(ds, 0.95) * 1e3:8.1f}")

    for r in by_kind.get("flight", []):
        print(f"-- flight dump (rank {r['rank']}): {r['reason']} -> "
              f"{r['path']} ({r['events']} events)")

    for r in by_kind.get("note", []):
        print(f"-- note (rank {r['rank']}): {r['text']}")
    return 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__.strip())
        return 0 if argv else 2
    rc = 0
    for path in argv:
        rc = max(rc, summarize(path))
    return rc


if __name__ == "__main__":
    sys.exit(main())
