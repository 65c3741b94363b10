"""Multi-mesh replica routing for the serve gateway.

One :class:`~dlaf_tpu.serve.pool.SolverPool` serves one device mesh; a
production deployment runs several (one per slice, or per host fallback
mesh) and must keep serving when a mesh wedges — a hung runtime, not a
crashed process, so the pool's queue is still intact when the device
stops answering.  The
router's job is to notice (bounded
:class:`~dlaf_tpu.resilience.DeviceWatchdog` probes), classify
(:class:`~dlaf_tpu.health.DeviceUnresponsiveError`), and MIGRATE: drain
the downed pool's queued-but-undispatched requests and adopt them on a
healthy sibling, futures intact — the client never learns its request
changed meshes.  Requests that no sibling can take are shed with the same
typed error, never dropped silently.

* :class:`Replica` — one named pool + its liveness watchdog.
* :class:`Router` — placement (healthy replica with the shortest queue)
  and the probe/drain/adopt failover loop (:meth:`Router.check`).

Every probe, downing, revival and migration is a ``serve`` obs event
(``replica_probe`` / ``replica_down`` / ``replica_up`` /
``replica_drain``), so the JSONL audit trail shows which mesh served
which era of traffic.
"""
from __future__ import annotations

import threading
import time

from dlaf_tpu import resilience
from dlaf_tpu.health import DeviceUnresponsiveError, DistributionError
from dlaf_tpu.obs import metrics as om


class Replica:
    """One serving mesh: a named pool plus its liveness watchdog.

    ``healthy`` is the router's routing eligibility bit — flipped by
    :meth:`Router.check` probes (or manually via
    :meth:`Router.mark_down` / :meth:`Router.revive` in tests and
    planned-maintenance drains)."""

    def __init__(self, name: str, pool, *, watchdog=None,
                 probe_budget_s: float = 5.0, warm: bool = False,
                 warmup_kwargs: dict | None = None):
        self.name = str(name)
        self.pool = pool
        self.watchdog = (
            watchdog
            if watchdog is not None
            else resilience.DeviceWatchdog(budget_s=float(probe_budget_s))
        )
        self.healthy = True
        self.warm_summary: dict | None = None
        if warm:
            self.warmup(**(warmup_kwargs or {}))

    def warmup(self, **kwargs) -> dict:
        """Prefetch this replica's executables through ``plan.warmup`` on
        the pool's own grid and bucket cache, so the first request a
        fresh mesh serves hits a populated plan (and, with the persistent
        compilation cache configured, AOT-loads instead of compiling).
        Every plan the fused trailing-update tier registers flows through
        the same path — its executables warm like any other.  Keyword
        arguments pass straight to ``plan.warmup`` (buckets, ops, dtypes,
        nrhs).  Stores and returns the warmup summary, and emits a
        ``serve`` ``replica_warmup`` event with the compile attribution."""
        from dlaf_tpu.plan import core as plan_core

        kwargs.setdefault("grid", self.pool.grid)
        kwargs.setdefault("cache", self.pool.cache)
        self.warm_summary = plan_core.warmup(**kwargs)
        om.emit(
            "serve", event="replica_warmup", replica=self.name,
            plans=self.warm_summary["plans"],
            compiles=self.warm_summary["compiles"],
            aot_loads=self.warm_summary["aot_loads"],
            seconds=self.warm_summary["seconds"],
        )
        return self.warm_summary

    def pending(self) -> int:
        return self.pool.pending()


class Router:
    """Health-scored placement across replicas, with drain failover.

    :meth:`route` places new work on the healthy replica with the fewest
    queued requests (join-shortest-queue — with identical meshes this is
    the latency-optimal greedy policy and it self-corrects after a
    failover dogpiles one sibling).  :meth:`check` is the failover sweep:
    probe every replica, down the unresponsive ones, drain their queues
    to siblings, revive the ones that answer again."""

    def __init__(self, replicas):
        replicas = list(replicas)
        if not replicas:
            raise DistributionError("router: need at least one replica")
        names = [r.name for r in replicas]
        if len(set(names)) != len(names):
            raise DistributionError(f"router: replica names must be unique, got {names}")
        self._replicas = replicas
        self._lock = threading.Lock()

    @property
    def replicas(self) -> tuple:
        return tuple(self._replicas)

    def get(self, name: str) -> Replica:
        for r in self._replicas:
            if r.name == name:
                return r
        raise DistributionError(f"router: no replica named {name!r}")

    def healthy(self) -> list:
        with self._lock:
            return [r for r in self._replicas if r.healthy]

    def route(self) -> Replica | None:
        """The healthy replica with the fewest queued requests, or None
        when every replica is down (callers hold or shed)."""
        live = self.healthy()
        if not live:
            return None
        return min(live, key=lambda r: r.pending())

    def mark_down(self, name: str) -> None:
        with self._lock:
            self.get(name).healthy = False

    def revive(self, name: str) -> None:
        with self._lock:
            self.get(name).healthy = True

    # ------------------------------------------------------------- elastic

    def add(self, replica: Replica) -> Replica:
        """Bring a new replica into routing (fleet scale-up).  Name
        uniqueness is enforced against the live set; the replica is
        eligible for placement as soon as this returns."""
        with self._lock:
            if any(r.name == replica.name for r in self._replicas):
                raise DistributionError(
                    f"router: replica name {replica.name!r} already routed"
                )
            self._replicas = self._replicas + [replica]
        return replica

    def remove(self, name: str) -> Replica:
        """Take a replica out of routing (fleet scale-down) and return it;
        its queued requests are NOT migrated here — the caller drains the
        returned replica's pool and re-adopts (the scale-down path does
        exactly that).  The last replica cannot be removed: a router with
        nothing to route to would strand every future the gateway holds."""
        with self._lock:
            if len(self._replicas) <= 1:
                raise DistributionError(
                    "router: cannot remove the last replica"
                )
            rep = self.get(name)
            self._replicas = [r for r in self._replicas if r.name != name]
        return rep

    # ----------------------------------------------------------- failover

    def check(self, probe_budget_s: float | None = None) -> dict:
        """One failover sweep: probe every replica, drain the downed.

        For each replica the watchdog probe either confirms liveness
        (reviving a previously-downed replica) or raises
        :class:`DeviceUnresponsiveError`, in which case the replica is
        taken out of routing and its queued-but-undispatched requests are
        drained and adopted — futures intact — on the healthy sibling
        with the shortest queue.  Requests no sibling can hold are shed
        with the same typed error.  The in-flight dispatch on a downed
        pool is NOT interrupted (it may still complete; its deadline
        bounds it if not).

        Returns ``{"probed", "down", "revived", "migrated", "shed"}``.
        """
        summary = {"probed": 0, "down": [], "revived": [], "migrated": 0, "shed": 0}
        for rep in self._replicas:
            summary["probed"] += 1
            t0 = time.monotonic()
            try:
                rep.watchdog.probe(probe_budget_s)
                ok = True
            except DeviceUnresponsiveError:
                ok = False
            om.emit("serve", event="replica_probe", replica=rep.name, ok=ok,
                    seconds=time.monotonic() - t0)
            with self._lock:
                was_healthy, rep.healthy = rep.healthy, ok
            if ok and not was_healthy:
                summary["revived"].append(rep.name)
                om.emit("serve", event="replica_up", replica=rep.name)
            elif not ok and was_healthy:
                summary["down"].append(rep.name)
                om.emit("serve", event="replica_down", replica=rep.name)
                migrated, shed = self._drain_to_sibling(rep)
                summary["migrated"] += migrated
                summary["shed"] += shed
            elif not ok and rep.pending():
                # still down from a prior sweep, yet holding work: route()
                # and adopt() are not synchronized with this sweep, so a
                # batch can land on a replica right after it was downed and
                # drained — keep draining until the queue stays empty,
                # otherwise those futures strand on the wedged pool forever
                migrated, shed = self._drain_to_sibling(rep)
                summary["migrated"] += migrated
                summary["shed"] += shed
        return summary

    def _drain_to_sibling(self, downed: Replica) -> tuple:
        """Migrate ``downed``'s queued requests to healthy siblings.

        Retries the remainder across every healthy sibling (a sibling may
        be at capacity); only what NO sibling can hold is shed, with the
        failure typed as the mesh outage that caused it."""
        reqs = downed.pool.drain()
        if not reqs:
            return 0, 0
        remaining = reqs
        adopted_by = []
        for sib in sorted(self.healthy(), key=lambda r: r.pending()):
            if not remaining:
                break
            before = len(remaining)
            remaining = sib.pool.adopt(remaining)
            if len(remaining) != before:
                adopted_by.append(sib.name)
        migrated = len(reqs) - len(remaining)
        om.emit("serve", event="replica_drain", replica=downed.name,
                drained=len(reqs), migrated=migrated, shed=len(remaining),
                to=",".join(adopted_by))
        for req in remaining:
            if not req.future.done():
                req.future.set_exception(DeviceUnresponsiveError(
                    budget_s=downed.watchdog.budget_s, device=downed.name,
                    message=(
                        f"replica {downed.name!r} went unresponsive and no "
                        f"healthy sibling had queue capacity for this request"
                    ),
                ))
        return migrated, len(remaining)

    # ---------------------------------------------------------- lifecycle

    def pending(self) -> int:
        return sum(r.pending() for r in self._replicas)

    def close(self) -> None:
        for r in self._replicas:
            r.pool.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
