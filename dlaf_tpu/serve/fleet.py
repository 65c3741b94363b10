"""Serve v3 composition root: a cross-process elastic replica fleet.

:class:`Fleet` assembles the whole stack behind one object:

* **Workers** — ``workers`` separate OS processes (one PJRT client each,
  so the single-process ``_EXEC_LOCK`` serialization in ``pool.py``
  finally stops being the ceiling), spawned by the
  :class:`~dlaf_tpu.serve.supervisor.Supervisor` with the compile cache
  (``DLAF_TPU_COMPILE_CACHE``) and forced device count routed through
  their environment, warmed at spawn over the serve bucket ladder — a
  restarted replica AOT-loads its executables (0 jit compiles) and is
  serving within the restart backoff budget.

* **Routing** — each worker's :class:`~dlaf_tpu.serve.supervisor.
  WorkerHandle` duck-types a pool, so the v2 ``Replica`` / ``Router`` /
  ``Gateway`` stack composes unchanged; watchdog probes travel the wire
  (:class:`~dlaf_tpu.serve.supervisor.WireWatchdog`) and failover is
  checkpoint-carried drain/adopt (HDF5, see ``serve.wire``) — a killed
  worker loses ZERO admitted requests: its outstanding queue re-dispatches
  to siblings and late duplicate results are dropped first-result-wins.

* **Supervision** — heartbeat health checks, exponential-backoff
  restarts, a crash-loop circuit breaker, and child flight-dump
  collection, all as ``fleet`` obs events.

* **Elasticity** — with ``autoscale=True`` an
  :class:`~dlaf_tpu.serve.supervisor.Autoscaler` watches gateway
  p95/queue-depth and grows/shrinks the fleet between ``min_workers`` and
  ``max_workers`` with hysteresis; scale-down drains the retiring worker
  gracefully and re-adopts its queue before the process exits.

Drive it like a gateway (``fleet.gateway.submit_nowait(...)``), pump
:meth:`tick` periodically (the scenario runner's sweep does), and
``close()`` merges each worker's JSONL metrics into the parent stream so
one artifact holds the whole fleet's audit trail.
"""
from __future__ import annotations

import glob
import os
import re
import signal as _signal
import tempfile
import threading
import time

from dlaf_tpu import tune
from dlaf_tpu.health import DeviceUnresponsiveError, DistributionError
from dlaf_tpu.obs import metrics as om
from dlaf_tpu.obs import telemetry as tlm
from dlaf_tpu.serve.gateway import Gateway
from dlaf_tpu.serve.router import Replica, Router
from dlaf_tpu.serve.supervisor import (
    Autoscaler,
    Supervisor,
    WireWatchdog,
    WorkerHandle,
    xla_flags_with_device_count,
)

#: captures ``<name>-g<gen>`` — merged records (and the export's process
#: rows) identify the worker INCARNATION, so a respawned replica's spans
#: land on their own timeline row instead of aliasing its predecessor's.
_WORKER_METRICS_RE = re.compile(r"worker-(.+-g\d+)\.jsonl$")


class Fleet:
    """Elastic cross-process serve fleet (see module docstring).

    ``tenants`` goes straight to the :class:`Gateway`; ``buckets`` /
    ``block_size`` / ``max_batch`` / ``warm_ops`` / ``nrhs`` shape each
    worker's pool and warmup; ``worker_devices`` forces the per-worker
    host device count (children REPLACE the parent's
    ``--xla_force_host_platform_device_count``).  ``base_dir`` (default: a
    fresh temp dir) holds the shared compile cache, request checkpoints,
    per-worker metrics and collected flight dumps."""

    def __init__(self, tenants, *, workers: int = 2,
                 buckets: str | None = None, block_size: int | None = None,
                 max_batch: int | None = None, max_queue: int | None = None,
                 gw_max_queue: int | None = None,
                 linger_ms: float | None = None, worker_devices: int = 1,
                 base_dir: str | None = None, autoscale: bool = False,
                 min_workers: int = 1, max_workers: int = 4,
                 probe_budget_s: float = 5.0,
                 warm_ops=("potrf", "posv", "eigh"), nrhs: int = 1,
                 fake: str | None = None, ready_timeout_s: float = 300.0,
                 autoscale_kwargs: dict | None = None, **supervisor_kwargs):
        if workers < 1:
            raise DistributionError("fleet: need at least one worker")
        self.base_dir = base_dir or tempfile.mkdtemp(prefix="dlaf-fleet-")
        os.makedirs(self.base_dir, exist_ok=True)
        # one compile cache shared by every worker: the operator's (workers
        # inherit JAX_COMPILATION_CACHE_DIR, which wins), else the caller's
        # base_dir, else the checkout's fixed default
        cache_dir = os.environ.get("DLAF_TPU_COMPILE_CACHE") or (
            os.path.join(base_dir, "compile-cache") if base_dir
            else tune.DEFAULT_COMPILE_CACHE
        )
        env = {
            "DLAF_TPU_COMPILE_CACHE": cache_dir,
            # persist even sub-second CPU executables: the zero-compile
            # restart contract is the point, not disk frugality
            "DLAF_TPU_COMPILE_CACHE_MIN_S": "0",
            "XLA_FLAGS": xla_flags_with_device_count(
                os.environ.get("XLA_FLAGS"), worker_devices
            ),
        }
        if tlm.enabled():
            # a telemetry-on parent turns its workers on too: their
            # tune.initialize flips the registry from this env, and their
            # snapshots ride heartbeat acks back into Fleet.stats()
            env["DLAF_TPU_TELEMETRY"] = "1"
        self.probe_budget_s = float(probe_budget_s)
        self.ready_timeout_s = float(ready_timeout_s)
        self._warm_ops = tuple(warm_ops)
        self._fake = fake
        self._max_queue = max_queue
        self._lock = threading.Lock()
        self._next_idx = 0
        self._closed = False
        self.supervisor = Supervisor(
            base_dir=self.base_dir, env=env,
            worker_kwargs={
                "buckets": buckets, "block_size": block_size,
                "max_batch": max_batch, "warm_ops": tuple(warm_ops),
                "nrhs": int(nrhs), "probe_budget_s": self.probe_budget_s,
            },
            on_worker_dead=self._on_worker_dead, **supervisor_kwargs,
        )
        # spawn the initial complement concurrently (each pays a full
        # package import + warmup; serializing would multiply cold start)
        handles = [self._new_handle() for _ in range(int(workers))]
        for h in handles:
            self.supervisor.spawn(h)
        replicas = []
        for h in handles:
            self.supervisor.wait_ready(h, timeout=self.ready_timeout_s)
            replicas.append(self._replica_for(h))
        self.router = Router(replicas)
        self.gateway = Gateway(self.router, tenants,
                               max_queue=gw_max_queue, max_batch=max_batch,
                               linger_ms=linger_ms)
        # SLO burn-rate monitor (obs.telemetry): the gateway feeds it every
        # shed/completion; tick() evaluates it; its latched verdict is the
        # autoscaler's third input next to p95 and queue depth
        from dlaf_tpu.tune import get_tune_parameters

        p = get_tune_parameters()
        self.burn_monitor = tlm.SloBurnMonitor(
            p95_target_s=p.slo_burn_target_p95_s, budget=p.slo_burn_budget,
            fast_s=p.slo_burn_fast_s, slow_s=p.slo_burn_slow_s,
            threshold=p.slo_burn_threshold,
        )
        self.gateway.burn_monitor = self.burn_monitor
        self.profile_path: str | None = None  # written by close() harvest
        self.supervisor.start_monitor()
        self.autoscaler = None
        if autoscale:
            self.autoscaler = Autoscaler(
                self._signals, self.live_workers,
                self.scale_up, self.scale_down,
                min_workers=int(min_workers), max_workers=int(max_workers),
                **{"burn_fn": self.burn_monitor.hot,
                   **(autoscale_kwargs or {})},
            )
        # idle-replica shadow sweeps (plan.shadow): when the fleet sits
        # quiet past the knob, measure a few harvested geometries on the
        # least-loaded replica and fold them into the persistent profile
        self.shadow = None
        if p.telemetry_shadow_idle_s > 0:
            from dlaf_tpu.plan.shadow import ShadowSweeper

            self.shadow = ShadowSweeper(
                self._shadow_busy, self._shadow_measure,
                self._shadow_geometries, self._shadow_fold,
                idle_s=p.telemetry_shadow_idle_s,
            )

    # -------------------------------------------------------------- workers

    def _new_handle(self) -> WorkerHandle:
        with self._lock:
            name = f"replica{self._next_idx}"
            self._next_idx += 1
        handle = WorkerHandle(
            name, max_queue=self._max_queue,
            ckpt_dir=os.path.join(self.base_dir, "ckpt"), fake=self._fake,
        )
        return self.supervisor.add_handle(handle)

    def _replica_for(self, handle: WorkerHandle) -> Replica:
        return Replica(handle.name, handle,
                       watchdog=WireWatchdog(handle, self.probe_budget_s))

    def handle(self, name: str) -> WorkerHandle:
        h = self.supervisor.get(name)
        if h is None:
            raise DistributionError(f"fleet: no worker named {name!r}")
        return h

    def live_workers(self) -> int:
        """Capacity slots that still count: not retired, circuit closed
        (a slot waiting out its restart backoff still counts — it will be
        back; scaling up because of it would double-provision)."""
        return sum(1 for h in self.supervisor.handles()
                   if not h.retired and not h.circuit_open)

    # ------------------------------------------------------ fault injection

    def kill_worker(self, name: str, sig: int = _signal.SIGKILL) -> None:
        """Hard-kill a worker process (``testing.faults.process_kill``);
        the supervisor notices on its next pass and the restart/failover
        machinery takes over."""
        self.handle(name).kill(sig)

    def partition_worker(self, name: str) -> None:
        """Block parent→worker traffic (simulated network partition —
        asymmetric: results the worker already computed are still
        processed when they arrive, matching a one-way link failure)."""
        self.handle(name).partitioned = True
        om.emit("fleet", event="partition", worker=name)

    def heal_worker(self, name: str) -> None:
        self.handle(name).partitioned = False
        om.emit("fleet", event="partition_heal", worker=name)

    # ------------------------------------------------------------- failover

    def _on_worker_dead(self, handle: WorkerHandle) -> None:
        """Supervisor death callback: take the replica out of routing and
        migrate its outstanding queue NOW (dead-path drain: everything
        re-dispatches; solves are idempotent and first-result-wins drops
        late duplicates), rather than waiting for the next probe sweep."""
        try:
            self.router.mark_down(handle.name)
        except DistributionError:
            return  # scaled away already
        self.gateway.check_replicas(self.probe_budget_s)

    def tick(self) -> dict:
        """One fleet maintenance pass: probe/drain/revive sweep, a burn-
        rate evaluation (emitting ``slo_burn`` transitions), then an
        autoscaler step over all three signals.  The scenario runner (and
        any serving loop) calls this periodically."""
        summary = self.gateway.check_replicas(self.probe_budget_s)
        self.burn_monitor.check()
        if self.autoscaler is not None:
            self.autoscaler.step()
        if self.shadow is not None:
            self.shadow.tick()
        return summary

    # ------------------------------------------------------------ elasticity

    def scale_up(self) -> None:
        """Spawn one more worker; it joins routing when its warmup-backed
        ``ready`` frame lands (async — the autoscaler must not block on a
        process cold start)."""
        handle = self._new_handle()
        self.supervisor.spawn(handle)

        def _join():
            try:
                self.supervisor.wait_ready(handle, timeout=self.ready_timeout_s)
            except DeviceUnresponsiveError:
                handle.retired = True
                om.emit("fleet", event="scale_up_failed", worker=handle.name)
                return
            self.router.add(self._replica_for(handle))
            om.emit("fleet", event="scale_up_joined", worker=handle.name)

        threading.Thread(target=_join, name=f"dlaf-fleet-join-{handle.name}",
                         daemon=True).start()

    def scale_down(self) -> None:
        """Retire the healthy worker with the least queued work: out of
        routing first, then a graceful checkpoint-carried drain re-adopted
        onto the survivors, then process shutdown."""
        live = [r for r in self.router.healthy()]
        if len(live) <= 1:
            return
        victim = min(live, key=lambda r: r.pending())
        try:
            self.router.remove(victim.name)
        except DistributionError:
            return
        handle: WorkerHandle = victim.pool
        handle.retired = True
        remaining = handle.drain()
        for sib in sorted(self.router.healthy(), key=lambda r: r.pending()):
            if not remaining:
                break
            remaining = sib.pool.adopt(remaining)
        for req in remaining:
            if not req.future.done():
                req.future.set_exception(DeviceUnresponsiveError(
                    device=handle.name,
                    message=(f"fleet: worker {handle.name} retired with no "
                             f"sibling capacity for this request"),
                ))
        om.emit("fleet", event="scale_down_retired", worker=handle.name,
                shed=len(remaining))
        self.supervisor.remove_handle(handle.name)
        threading.Thread(target=handle.close,
                         name=f"dlaf-fleet-retire-{handle.name}",
                         daemon=True).start()
        # the retiring worker's batch records would otherwise sit in its
        # JSONL until close(); harvest now so a long-lived fleet's profile
        # tracks the traffic it has actually served, not just the finale
        self._harvest_service_times(include_worker_files=True)

    # ------------------------------------------------------------- signals

    def _signals(self) -> tuple:
        """Autoscaler inputs: (worst per-tenant p95, total backlog).
        Backlog counts the gateway's admission queue PLUS every routed
        worker's outstanding frames — the gateway dispatches eagerly, so
        under overload the depth lives on the workers, not in the
        gateway.  Backlog is the primary scale-down signal — the p95 is
        cumulative over the run, so it ratchets up under load and only
        the backlog draining proves recovery."""
        st = self.gateway.stats()
        p95 = max((t["p95_s"] for t in st["tenants"].values()), default=0.0)
        return p95, st["queued"] + self.router.pending()

    # ------------------------------------------------------------ lifecycle

    def stats(self) -> dict:
        st = self.gateway.stats()
        st["workers"] = {
            h.name: {"gen": h.gen, "alive": h.alive, "served": h.served,
                     "failures": h.failures, "circuit_open": h.circuit_open,
                     "pending": h.pending(), "hb_rtt_p95_s": h.rtt_p95_s()}
            for h in self.supervisor.handles()
        }
        st["slo_burn"] = self.burn_monitor.check()
        if tlm.enabled():
            st["telemetry"] = self.merged_telemetry()
        return st

    def merged_telemetry(self) -> dict:
        """One fleet-wide instrument view: the parent registry folded with
        every worker's latest heartbeat-carried snapshot."""
        snaps = [h.last_telemetry for h in self.supervisor.handles()
                 if h.last_telemetry]
        return tlm.merge(tlm.snapshot(), *snaps)

    def close(self, timeout: float | None = 60.0) -> None:
        if self._closed:
            return
        self._closed = True
        self.gateway.close(timeout=timeout)
        for h in self.supervisor.handles():
            om.emit("fleet", event="worker_stats", worker=h.name,
                    served=h.served, gen=h.gen, failures=h.failures,
                    circuit_open=h.circuit_open, rtt_p95_s=h.rtt_p95_s())
        if tlm.enabled():
            om.emit("telemetry", snapshot=self.merged_telemetry(),
                    scope="fleet")
        self.supervisor.close()
        self._merge_worker_metrics()
        self._harvest_service_times()

    def _merge_worker_metrics(self) -> None:
        """Fold each worker's JSONL (written in the child) into the parent
        stream, stamped with the worker name — one artifact for the whole
        fleet.  Original timestamps/ranks are preserved (emit's field
        update overrides the fresh stamp)."""
        em = om.get()
        if em is None:
            return
        for path in sorted(glob.glob(os.path.join(self.base_dir,
                                                  "worker-*.jsonl"))):
            m = _WORKER_METRICS_RE.search(os.path.basename(path))
            worker = m.group(1) if m else os.path.basename(path)
            try:
                recs = om.read_jsonl(path)
            except (OSError, ValueError):
                continue
            for rec in recs:
                fields = {k: v for k, v in rec.items()
                          if k not in ("schema", "kind")}
                fields.setdefault("worker", worker)
                om.emit(rec["kind"], **fields)

    def _harvest_service_times(self, include_worker_files: bool = False) -> None:
        """Roll the merged stream's completed-batch records (the workers'
        ``serve``/``batch`` events carry geometry + launch choice) into a
        persisted ``plan`` profile.  Point ``DLAF_TPU_PLAN_PROFILE`` at
        ``profile_path`` and the next run's ``plan/autotune.decide``
        resolves those geometries with ``source='profile'`` — real fleet
        data steering the analytic model.

        ``include_worker_files`` reads the per-worker JSONLs directly —
        the mid-run (scale-down) harvest, where the parent stream does not
        yet carry the merged worker records.  At close() the merge has
        already folded them in, so the flag stays False there or every
        batch would count twice."""
        em = om.get()
        if em is None:
            return
        from dlaf_tpu.tune import get_tune_parameters

        harvester = tlm.ServiceTimeHarvester(
            min_samples=get_tune_parameters().telemetry_harvest_min_samples)
        paths = [em.path]
        if include_worker_files:
            paths.extend(sorted(glob.glob(os.path.join(self.base_dir,
                                                       "worker-*.jsonl"))))
        fed = 0
        for path in paths:
            try:
                fed += harvester.ingest(om.read_jsonl(path))
            except (OSError, ValueError):
                continue
        if not fed:
            return
        path = os.path.join(self.base_dir, "harvested-profile.json")
        if harvester.write(path) is not None:
            self.profile_path = path

    # -------------------------------------------------------- shadow sweeps

    def _shadow_busy(self) -> bool:
        """Real work the sweep would compete with: any gateway backlog or
        outstanding worker frame (the autoscaler's own backlog signal)."""
        return self._signals()[1] > 0

    def _shadow_geometries(self):
        """Micro-geometries worth measuring: the ``(op, n, dtype)`` mix
        the fleet has actually served (one pass of the harvester over the
        parent stream AND the live worker JSONLs, min_samples=1 — this is
        discovery, not statistics).  A fleet idle since birth probes the
        smallest serve bucket for each warmed op instead."""
        import numpy as np

        harvester = tlm.ServiceTimeHarvester(min_samples=1)
        em = om.get()
        paths = [em.path] if em is not None else []
        paths.extend(sorted(glob.glob(os.path.join(self.base_dir,
                                                   "worker-*.jsonl"))))
        for path in paths:
            try:
                harvester.ingest(om.read_jsonl(path))
            except (OSError, ValueError):
                continue
        geoms = [(e["op"], int(e["n"]), e["dtype"])
                 for e in harvester.profile()["entries"]]
        if not geoms:
            from dlaf_tpu.serve import bucketing

            b0 = bucketing.bucket_table()[0]
            f4 = np.dtype(np.float32).str
            geoms = [(op, b0, f4) for op in self._warm_ops]
        return geoms

    def _shadow_measure(self, geom) -> float:
        """Run ONE micro-batch of ``(op, n, dtype)`` on the least-loaded
        healthy replica and return its wall seconds (wire round trip
        included — that is the latency serving actually sees)."""
        import numpy as np

        from dlaf_tpu.serve import pool as serve_pool

        op, n, dtype_str = geom
        dt = np.dtype(dtype_str)
        rng = np.random.default_rng(int(n))
        r = rng.standard_normal((n, n))
        if dt.kind == "c":
            r = r + 1j * rng.standard_normal((n, n))
        a = (r @ np.conj(r.T) + n * np.eye(n)).astype(dt)
        b = rng.standard_normal((n, 1)).astype(dt) if op == "posv" else None
        req = serve_pool.make_request(op, "L", a, b)
        live = self.router.healthy()
        if not live:
            raise DistributionError("shadow sweep: no healthy replica")
        target = min(live, key=lambda rep: rep.pending())
        t0 = time.monotonic()
        if target.pool.adopt([req]):
            raise DistributionError(
                f"shadow sweep: replica {target.name} refused the probe")
        req.future.result(timeout=max(self.probe_budget_s * 12, 60.0))
        return time.monotonic() - t0

    def _shadow_fold(self, results) -> None:
        """Upsert sweep measurements into ``harvested-profile.json`` with
        ``source='shadow_sweep'`` provenance, re-install the profile, and
        audit every ``autotune.decide`` answer the new entries changed as
        a ``plan``/``autotune_flip`` event."""
        import json

        from dlaf_tpu.algorithms import _spmd
        from dlaf_tpu.plan import autotune

        before = {geom: autotune.decide(*geom).source for geom, _ in results}
        path = os.path.join(self.base_dir, "harvested-profile.json")
        doc = None
        try:
            with open(path) as fh:
                doc = json.load(fh)
        except (OSError, ValueError):
            doc = None
        if not isinstance(doc, dict) or doc.get("schema") != autotune.PROFILE_SCHEMA:
            doc = {"schema": autotune.PROFILE_SCHEMA, "entries": []}
        doc["harvest"] = {**doc.get("harvest", {}), "source": "shadow_sweep",
                          "shadow_sweeps": int(doc.get("harvest", {})
                                               .get("shadow_sweeps", 0)) + 1}
        impl = _spmd.trailing_update_trace_key()
        entries = {(e.get("op"), int(e.get("n", 0)), e.get("dtype")): e
                   for e in doc.get("entries", ()) if isinstance(e, dict)}
        for geom, seconds in results:
            op, n, ds = geom
            e = entries.setdefault((op, int(n), ds),
                                   {"op": op, "n": int(n), "dtype": ds})
            meas = e.setdefault("measured", {})
            batches = int(meas.get("batches", 0)) + 1
            total = float(meas.get("mean_batch_s", 0.0)) * (batches - 1) + seconds
            meas.update(batches=batches, items=int(meas.get("items", 0)) + 1,
                        mean_batch_s=total / batches,
                        mean_item_s=total / batches)
            e["source"] = "shadow_sweep"
            e["trailing_update_impl"] = impl
            e.setdefault("choice", {})
        doc["entries"] = [entries[k] for k in sorted(entries)]
        tmp = path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(doc, fh, indent=2, sort_keys=True)
            fh.write("\n")
        os.replace(tmp, path)
        self.profile_path = path
        autotune.load_profile(path)
        for geom in before:  # unique geometries: one audit row each
            after = autotune.decide(*geom).source
            if after != before[geom]:
                op, n, ds = geom
                om.emit("plan", event="autotune_flip", op=op, n=int(n),
                        dtype=ds, before=before[geom], after=after,
                        trailing_update_impl=impl)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
