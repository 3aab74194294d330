"""Cross-process fleet router: one API front-end over many worker daemons.

PR 11 scaled the daemon to a device fleet INSIDE one process (virtual
devices timeslicing one host core — no compute scaling was ever
measured there). This module is the horizontal remainder:
a :class:`Router` is a front-end PROCESS that speaks the SAME
JSON-lines API as the daemon (serve/api.py: submit / status / cancel /
drain / migrate / metrics / metrics_full / ping) and owns a WORKER
REGISTRY instead of a device:

- **Workers** are ordinary daemons started as ``python -m
  sagecal_tpu.serve --worker --router ADDR``: each serves its own job
  API on its own (usually ephemeral) port and keeps ONE persistent
  control connection to the router — no per-op reconnect — over which
  it registers (worker id, API address, capacity = devices x
  max_inflight, pid) and then heartbeats every ``heartbeat_s`` (the
  interval is granted by the router at registration, so cadence is
  fleet policy, not per-worker config). Each heartbeat renews the
  worker's LEASE and carries its live job snapshots, its compile-cache
  bucket INVENTORY (scheduler.bucket_inventory: which affinity tokens
  have warm programs, per device ordinal) and its cache hit counters.
  A worker whose lease expires — crash, hang, partition; the
  ``worker_crash`` fault point (sagecal_tpu.faults) is the
  deterministic chaos lever — is EVICTED and its jobs recovered.

- **Routing** generalizes the PR 11 ``Placer`` one level up: a job's
  ``job_bucket`` affinity token (serve/fleet.py) routes it to the
  worker whose caches already hold its compiled programs (the
  reported inventory first, then the router's own sticky
  bucket->worker map), then least-load with lowest registration order
  as the tie-break. Capacity is budgeted PER WORKER (its registered
  capacity) and admission is strict head-of-line FLEET-WIDE — the
  serve/queue.py discipline at router scale: a head job blocked on
  every worker blocks the line, a job pinned by a migration only
  admits on its pinned worker, and recovering (resuming) jobs
  re-admit ahead of every queued job.

- **Cross-process migration and worker-death recovery** both ride the
  PR 9 ``.ckpt.npz`` checkpoint sidecar, which lands next to the
  solutions file and must live on a filesystem every worker can read
  — the shared-filesystem contract (MIGRATION.md "Multi-process
  fleet"). Migration: the router CANCELS the job on its source worker
  (the daemon yields at the next tile boundary; its teardown drains
  the ordered writer, so the checkpoint watermark is durable before
  the cancel reads terminal), then re-submits it to the target with
  ``resume=true`` — completed tiles are skipped and outputs are
  bit-identical to an unmigrated run (the PR 9 resume gates, now
  across process boundaries; gated in tests/test_router.py).
  Recovery is the same re-queue triggered by lease expiry, unpinned.
  Every hop records its measured cost on the job (``hops``:
  src/dst/reason/t_yield/resumed_t/wall_s/tiles_at_yield/resume_tile/
  tiles_rerun).

Because terminal job registries are per worker process, a job's
re-dispatch uses a hop-suffixed worker-side id (``<job_id>~h<N>``) so
a migrate-back or same-worker recovery can never collide with the
job's earlier, now-terminal incarnation in that worker's registry;
the router re-maps snapshots to the client-visible id.

Layering: stdlib + serve.api (Client) + serve.fleet (job_bucket) +
serve.queue (state names) + obs.metrics; **no jax** — the router
process never touches a device, so it stays cheap to run next to an
LB or on a head node.
"""

from __future__ import annotations

import itertools
import json
import os
import socketserver
import threading
import time
import uuid

from sagecal_tpu import faults
from sagecal_tpu.analysis import threadsan
from sagecal_tpu.obs import export as oexport
from sagecal_tpu.obs import metrics as ometrics
from sagecal_tpu.serve import api as sapi
from sagecal_tpu.serve import queue as jq

#: router-side job states (worker-side states pass through verbatim —
#: jq.QUEUED/RUNNING/... — so a client polling `status` sees one state
#: machine whether it talks to a daemon or a router)
DISPATCHED = "dispatched"     # forwarded to a worker, snapshot pending


class WorkerInfo:
    """One registered worker: address, lease, inventory, live stats."""

    def __init__(self, worker_id: str, addr: dict, capacity: int,
                 devices: int = 1, pid: int | None = None):
        self.worker_id = worker_id
        self.addr = dict(addr)          # {"port": N} | {"socket": PATH}
        self.capacity = max(1, int(capacity))
        self.devices = int(devices)
        self.pid = pid
        self.registered_t = time.time()
        self.lease_t = 0.0              # expiry; set by register/heartbeat
        self.evicted = False
        self.last_hb_t = 0.0
        self.heartbeats = 0
        self.buckets: dict = {}         # token -> [device ordinals]
        self.priors: set = set()        # solution prior store keys held
        self.cache: dict = {}           # worker PROGRAMS.stats()
        self.counts: dict = {}          # worker queue counts()
        self.tiles_done = 0
        self.jobs: dict = {}            # worker_job_id -> last snapshot
        # ONE persistent data client per worker (submit/cancel/status
        # proxying); api.Client is not thread-safe, so every use takes
        # the per-worker lock — never the router-wide lock (network I/O
        # must not serialize the registry)
        self.client: sapi.Client | None = None
        self.clock = threadsan.make_lock("WorkerInfo.clock")

    def alive(self, now: float | None = None) -> bool:
        return (not self.evicted
                and (now or time.time()) < self.lease_t)

    def get_client(self) -> sapi.Client:
        """Lock held (self.clock)."""
        if self.client is None:
            self.client = sapi.Client(
                socket_path=self.addr.get("socket"),
                port=self.addr.get("port"), timeout=60.0)
        return self.client

    def snapshot(self, now: float) -> dict:
        n = self.cache.get("hits", 0) + self.cache.get("misses", 0)
        return {
            "worker_id": self.worker_id, "addr": self.addr,
            "alive": self.alive(now), "evicted": self.evicted,
            "capacity": self.capacity, "devices": self.devices,
            "pid": self.pid,
            "lease_remaining_s": round(max(0.0, self.lease_t - now), 3),
            "heartbeat_age_s": (round(now - self.last_hb_t, 3)
                                if self.last_hb_t else None),
            "heartbeats": self.heartbeats,
            "buckets": len(self.buckets),
            "priors": len(self.priors),
            "cache": dict(self.cache,
                          hit_rate=(self.cache.get("hits", 0) / n)
                          if n else 0.0),
            "counts": dict(self.counts),
            "tiles_done": self.tiles_done,
        }


class RJob:
    """One router-level job: the submit payload + fleet lifecycle."""

    def __init__(self, job_id: str, payload: dict, seq: int):
        self.job_id = job_id
        self.payload = dict(payload)    # the client's submit request
        self.priority = int(payload.get("priority", 0))
        self.seq = seq
        self.submitted_t = time.time()
        d = payload.get("deadline_s")
        self.deadline_t = (None if d is None
                           else self.submitted_t + float(d))
        self.state = jq.QUEUED          # router-side view
        self.worker_id: str | None = None
        self.pinned_worker: str | None = None
        self.migrate_to: str | None = None
        self.resume = False             # next dispatch is a resume hop
        self.hops: list = []            # completed + in-flight hop records
        self.n_dispatches = 0
        self.bucket: str | None = None
        # dedicated placement token (= bucket except for stream jobs)
        # and the solution prior store key — the prior-affinity
        # routing signal; routed_by records which signal won placement
        self.bucket_place: str | None = None
        self.prior: str | None = None
        self.routed_by: str | None = None
        self._bucket_done = False
        self.started_t: float | None = None
        self.finished_t: float | None = None
        self.snap: dict | None = None   # last worker snapshot (remapped)
        self.error: str | None = None
        self._mig_cancel_sent = False

    @property
    def worker_job_id(self) -> str:
        """Worker-side id of the CURRENT hop (see module docstring)."""
        if self.n_dispatches <= 1:
            return self.job_id
        return f"{self.job_id}~h{self.n_dispatches - 1}"

    def terminal(self) -> bool:
        return self.state in jq.TERMINAL

    def expired(self, now: float | None = None) -> bool:
        if self.deadline_t is None:
            return False
        return (now or time.time()) >= self.deadline_t

    def client_snapshot(self) -> dict:
        """The `status` reply row: the latest worker snapshot remapped
        to the client-visible id + router fields, or a synthesized row
        for jobs the fleet has not started yet. Reads ``self.snap``
        ONCE — a concurrent requeue nulls it under the router lock,
        and a check-then-copy would race to ``dict(None)``."""
        src = self.snap
        snap = dict(src) if src else {
            "job_id": self.job_id, "state": self.state,
            "kind": None, "priority": self.priority,
            "tiles_done": 0, "n_tiles": None,
            "started_t": None, "finished_t": None,
            "device": None, "migrations": [], "error": self.error,
        }
        snap["job_id"] = self.job_id
        # queue-wait is measured from the ROUTER submission and the
        # first hop's start — a recovery's re-dispatch is not a second
        # arrival (the jq._mark_running_locked discipline, one level up)
        snap["submitted_t"] = self.submitted_t
        if self.started_t is not None:
            snap["started_t"] = self.started_t
        if self.finished_t is not None:
            snap["finished_t"] = self.finished_t
        snap["state"] = self.state
        snap["worker"] = self.worker_id
        snap["hops"] = [dict(h) for h in self.hops]
        if self.error and not snap.get("error"):
            snap["error"] = self.error
        return snap


def _affinity_tokens(payload: dict):
    """(program bucket, placement bucket, prior key) of a submit
    payload — the same ``fleet._job_tokens`` digests the in-process
    placer and the prior store use, computed against the shared
    filesystem (dataset HEADER only, one open for all three). All-None
    (opaque mpi jobs, unreadable datasets) routes by load alone."""
    cfg_dict = payload.get("config")
    if not cfg_dict or payload.get("mpi_argv") is not None:
        return None, None, None
    try:
        from sagecal_tpu.serve import fleet
        cfg = sapi.config_from_dict(cfg_dict)
        job = jq.Job("_probe", cfg, kind=sapi.job_kind(cfg))
        return (fleet.job_bucket(job),
                fleet.job_placement_bucket(job),
                fleet.job_prior_token(job))
    except Exception:
        return None, None, None


def _bucket_token(payload: dict) -> str | None:
    """The program-bucket half of :func:`_affinity_tokens` (kept for
    probe/test callers that only price program sharing)."""
    return _affinity_tokens(payload)[0]


class Router:
    """The front-end process: worker registry + fleet job table +
    the JSON-lines listener. ``lease_s``/``heartbeat_s`` are fleet
    policy: every registering worker is granted them in its register
    reply (heartbeat cadence defaults to lease/3 so a single dropped
    heartbeat never costs a healthy worker its lease)."""

    def __init__(self, socket_path: str | None = None,
                 port: int | None = None, lease_s: float = 5.0,
                 heartbeat_s: float | None = None,
                 poll_s: float = 0.05, log=print):
        if (socket_path is None) == (port is None):
            raise ValueError("exactly one of socket_path/port")
        self.socket_path = socket_path
        self.port = port
        self.lease_s = float(lease_s)
        self.heartbeat_s = (float(heartbeat_s) if heartbeat_s
                            else max(0.05, self.lease_s / 3.0))
        self.poll_s = float(poll_s)
        self.log = log
        self.registry = ometrics.enable()
        self.t0 = time.time()
        # reentrant: route/recover paths re-enter through helpers that
        # take the registry lock themselves
        self._lock = threadsan.make_rlock("Router._lock")
        self.workers: dict[str, WorkerInfo] = {}
        self.jobs: dict[str, RJob] = {}
        self._seq = itertools.count()
        self._affinity: dict[str, str] = {}   # bucket -> worker_id (sticky)
        self._draining = False
        self._drained = threading.Event()
        self._stop = threading.Event()
        self.dispatches = 0
        self.migrations = 0
        self.recoveries = 0
        # prior-affinity placement accounting: of the placements that
        # HAD a prior key, how many landed on a worker holding it
        self.prior_place_hits = 0
        self.prior_place_total = 0
        self.lease_evictions = 0
        self._srv = None
        self._dispatcher = threading.Thread(
            target=self._run_dispatcher, name="router-dispatch",
            daemon=True)

    # -- control-plane ops (worker side of the protocol) --------------------

    def _register(self, req: dict) -> dict:
        wid = req["worker_id"]
        with self._lock:
            w = self.workers.get(wid)
            if w is None or w.evicted:
                # an evicted id re-registering is a NEW incarnation
                # (its old jobs were already recovered elsewhere)
                w = WorkerInfo(wid, req["addr"],
                               int(req.get("capacity", 1)),
                               devices=int(req.get("devices", 1)),
                               pid=req.get("pid"))
                self.workers[wid] = w
            else:
                w.addr = dict(req["addr"])
                w.capacity = max(1, int(req.get("capacity", w.capacity)))
            w.lease_t = time.time() + self.lease_s
            ometrics.inc("router_registrations_total")
            self.log(f"router: worker {wid} registered "
                     f"(addr {w.addr}, capacity {w.capacity})")
        return {"ok": True, "lease_s": self.lease_s,
                "heartbeat_s": self.heartbeat_s}

    def _heartbeat(self, req: dict) -> dict:
        wid = req["worker_id"]
        with self._lock:
            w = self.workers.get(wid)
            if w is None or w.evicted:
                # stale incarnation: tell the worker to re-register —
                # its jobs were recovered, it must not keep a dead lease
                return {"ok": False, "error": "unknown or evicted "
                        f"worker {wid!r}; re-register"}
            now = time.time()
            w.lease_t = now + self.lease_s
            w.last_hb_t = now
            w.heartbeats += 1
            if "buckets" in req:
                w.buckets = dict(req["buckets"])
            if "priors" in req:
                w.priors = set(req["priors"])
            if "cache" in req:
                w.cache = dict(req["cache"])
            if "counts" in req:
                w.counts = dict(req["counts"])
            w.tiles_done = int(req.get("tiles_done", w.tiles_done))
            if "jobs" in req:
                # wholesale REPLACE, not upsert: each heartbeat
                # carries the worker's full registry, and upserting
                # would grow this mirror without bound on a
                # long-lived router
                w.jobs = {snap["job_id"]: snap
                          for snap in req["jobs"]}
            ometrics.inc("router_heartbeats_total")
        return {"ok": True, "lease_s": self.lease_s}

    # -- client-plane ops ----------------------------------------------------

    def handle_request(self, req: dict) -> dict:
        op = req.get("op")
        if op == "ping":
            return {"ok": True, "pong": True, "router": True}
        if op == "worker_register":
            return self._register(req)
        if op == "worker_heartbeat":
            return self._heartbeat(req)
        if op == "submit":
            if not req.get("config") and req.get("mpi_argv") is None:
                raise ValueError("submit needs a config (or mpi_argv)")
            with self._lock:
                if self._draining:
                    ometrics.inc("router_admission_rejections_total",
                                 reason="draining")
                    raise RuntimeError(
                        "router is draining; submission refused")
                jid = req.get("job_id") or uuid.uuid4().hex[:12]
                if jid in self.jobs:
                    ometrics.inc("router_admission_rejections_total",
                                 reason="duplicate_id")
                    raise ValueError(f"duplicate job id {jid!r}")
                rj = RJob(jid, req, next(self._seq))
                self.jobs[jid] = rj
                ometrics.inc("router_jobs_submitted_total")
            self.log(f"router: [{jid}] queued "
                     f"(priority {rj.priority})")
            return {"ok": True, "job_id": jid}
        if op == "status":
            jid = req.get("job_id")
            if jid:
                return {"ok": True, "job": self._status_one(jid)}
            with self._lock:
                # snapshots built UNDER the lock: they read mutable
                # hop/snap state the dispatcher rewrites mid-requeue
                return {"ok": True,
                        "jobs": [rj.client_snapshot()
                                 for rj in self.jobs.values()]}
        if op == "cancel":
            return {"ok": True, "state": self._cancel(req["job_id"])}
        if op == "migrate":
            return {"ok": True,
                    "state": self._request_migration(
                        req["job_id"],
                        req.get("worker") or req.get("device"))}
        if op == "metrics":
            return {"ok": True, "metrics": self.metrics()}
        if op == "metrics_full":
            m = self.metrics()
            return {"ok": True, "metrics": m,
                    "registry": self.registry.dump(),
                    "health": self.healthz(m)}
        if op == "drain":
            self.drain()
            if req.get("wait"):
                self._drained.wait()
            return {"ok": True, "draining": True}
        raise ValueError(f"unknown op {op!r}")

    def _status_one(self, job_id: str) -> dict:
        with self._lock:
            rj = self.jobs[job_id]
            w = self.workers.get(rj.worker_id) if rj.worker_id else None
            live = (not rj.terminal() and rj.state != jq.QUEUED
                    and w is not None and w.alive())
        if live:
            # proxy for freshness (terminal transitions land here at
            # client-poll latency instead of heartbeat latency); a
            # worker that died since the check falls back to the
            # heartbeat snapshot the dispatcher will recover from
            try:
                with w.clock:
                    snap = w.get_client().status(rj.worker_job_id)
                self._fold_snapshot(rj, snap)
            except Exception:
                pass
        with self._lock:
            return rj.client_snapshot()

    def _cancel(self, job_id: str) -> str:
        with self._lock:
            rj = self.jobs[job_id]
            if rj.terminal():
                return rj.state
            # a user cancel overrides any pending migration — with
            # migrate_to left set, the worker's CANCELLED snapshot
            # would read as the migration yield and RESURRECT the job
            # as a resume on the target
            rj.migrate_to = None
            if rj.state == jq.QUEUED or rj.worker_id is None:
                self._finish_locked(rj, jq.CANCELLED)
                return rj.state
            w = self.workers.get(rj.worker_id)
            wjid = rj.worker_job_id
        if w is not None:
            try:
                with w.clock:
                    w.get_client().cancel(wjid)
            except Exception:
                pass            # worker gone: lease eviction cancels it
        return rj.state

    def _request_migration(self, job_id: str, target) -> str:
        """The api `migrate` op at router scale: `worker` names the
        target worker id. Validates the job is dispatched+running, the
        target is a DIFFERENT alive worker, and the job has a
        solutions file (no checkpoint sidecar, no cross-process
        resume)."""
        with self._lock:
            rj = self.jobs[str(job_id)]
            t = str(target)
            if t not in self.workers or not self.workers[t].alive():
                raise ValueError(f"no alive worker {t!r}")
            cfg = rj.payload.get("config") or {}
            if not cfg.get("solutions_file"):
                raise ValueError(
                    "cross-process migration needs a solutions_file "
                    "(the checkpoint sidecar rides next to it on the "
                    "shared filesystem)")
            if rj.terminal() or rj.state == jq.QUEUED \
                    or rj.worker_id is None:
                raise ValueError(f"job {job_id} is {rj.state}, not "
                                 "running on a worker")
            if t == rj.worker_id:
                raise ValueError(f"job {job_id} is already on {t!r}")
            rj.migrate_to = t
            rj._mig_cancel_sent = False
            return jq.MIGRATING

    # -- snapshots / terminal accounting -------------------------------------

    def _fold_snapshot(self, rj: RJob, snap: dict) -> None:
        """Fold a worker snapshot of rj's CURRENT hop into the router
        record (locks internally)."""
        with self._lock:
            if rj.terminal():
                return
            rj.snap = dict(snap)
            state = snap.get("state")
            if state == jq.RUNNING:
                if rj.started_t is None \
                        and snap.get("started_t") is not None:
                    rj.started_t = snap["started_t"]
                    ometrics.observe(
                        "router_job_queue_wait_seconds",
                        rj.started_t - rj.submitted_t)
                rj.state = jq.RUNNING
                self._close_hop(rj, snap)
            elif state == jq.CANCELLED and rj.migrate_to is not None:
                # the yield half of a cross-process migration: the
                # worker cancelled at a tile boundary and drained its
                # writer — the checkpoint watermark is durable. Requeue
                # pinned to the target as a resume.
                target, rj.migrate_to = rj.migrate_to, None
                self._requeue_locked(rj, target, reason="migrate",
                                     tiles_at_yield=snap.get("tiles_done"))
                self.migrations += 1
                ometrics.inc("router_migrations_total")
                self.log(f"router: [{rj.job_id}] yielded on "
                         f"{rj.hops[-1]['src']} at tile "
                         f"{snap.get('tiles_done')} -> {target}")
            elif state in jq.TERMINAL:
                # a hop can race straight to terminal (a short resumed
                # run finishing between polls): close it from the final
                # snapshot before the books shut
                self._close_hop(rj, snap, final=True)
                self._finish_locked(rj, state,
                                    error=snap.get("error"))

    def _close_hop(self, rj: RJob, snap: dict,
                   final: bool = False) -> None:
        """Lock held. Close the in-flight hop once the resumed run has
        published its start tile (``resume_start_tile`` is set by the
        worker's ``_start_job`` — a snapshot taken between admission
        and stepper construction does not carry it yet, so we wait for
        the next poll rather than record an unknown). ``tiles_rerun``
        is (completed tiles observed at yield) - (resume start tile);
        heartbeat observation can only UNDER-count progress on a
        crashed worker, so the clamp at 0 never hides a real re-run —
        both raw fields ride the record."""
        if not rj.hops or "resumed_t" in rj.hops[-1]:
            return
        rt = snap.get("resume_start_tile")
        if rt is None and not final:
            return
        hop = rj.hops[-1]
        hop["resumed_t"] = time.time()
        hop["wall_s"] = round(hop["resumed_t"] - hop["t_yield"], 6)
        hop["dst"] = rj.worker_id
        hop["resume_tile"] = rt
        if rt is not None and hop.get("tiles_at_yield") is not None:
            hop["tiles_rerun"] = max(
                0, int(hop["tiles_at_yield"]) - int(rt))

    def _requeue_locked(self, rj: RJob, target: str | None, *,
                        reason: str, tiles_at_yield) -> None:
        """Lock held. RUNNING/DISPATCHED -> QUEUED as a RESUME hop
        (pinned to ``target`` when the move was chosen; None for
        recovery — any surviving worker may take it)."""
        rj.hops.append(dict(
            src=rj.worker_id, dst=target, reason=reason,
            t_yield=time.time(), tiles_at_yield=tiles_at_yield))
        rj.state = jq.QUEUED
        rj.worker_id = None
        rj.pinned_worker = target
        rj.resume = True
        rj.snap = None

    def _finish_locked(self, rj: RJob, state: str,
                       error: str | None = None) -> None:
        rj.state = state
        rj.finished_t = time.time()
        rj.error = error or rj.error
        rj.migrate_to = None
        ometrics.inc("router_jobs_total", state=state)
        ometrics.observe("router_job_e2e_seconds",
                         rj.finished_t - rj.submitted_t)
        if self._draining and all(j.terminal()
                                  for j in self.jobs.values()):
            self._drained.set()

    # -- placement -----------------------------------------------------------

    def _place(self, rj: RJob) -> str | None:
        """Lock held. Target worker id for ``rj``, or None (blocked).
        Mirrors fleet.Placer one level up: pin > prior-affinity >
        placement-bucket affinity (live inventory, then the stream
        program-token fallback, then the sticky map) > least-load;
        capacity budgeted per worker. Prior affinity ranks ABOVE the
        bucket: a worker holding this field's banked priors saves
        solver sweeps on EVERY tile, which dominates the one-time
        compile a warm program set saves. ``rj.routed_by`` records
        which signal won (the prior-affinity hit-rate source)."""
        now = time.time()
        assigned: dict[str, int] = {}
        for j in self.jobs.values():
            if j.worker_id and not j.terminal() \
                    and j.state != jq.QUEUED:
                assigned[j.worker_id] = assigned.get(j.worker_id, 0) + 1
        cands = [w for w in self.workers.values() if w.alive(now)]
        cands.sort(key=lambda w: w.registered_t)
        free = [w for w in cands
                if assigned.get(w.worker_id, 0) < w.capacity]
        if rj.pinned_worker is not None:
            pw = self.workers.get(rj.pinned_worker)
            if pw is None or not pw.alive(now):
                # the pinned target died while the job was queued:
                # DROP the pin (the checkpoint resume works on any
                # worker) rather than head-of-line-block the whole
                # fleet behind a pin that can never be satisfied
                rj.pinned_worker = None
            else:
                rj.routed_by = "pin"
                return rj.pinned_worker if any(
                    w.worker_id == rj.pinned_worker for w in free) \
                    else None
        if not free:
            return None
        if not rj._bucket_done:
            # computed ONCE per job (dataset header I/O must not run
            # per dispatch pass), outside no lock contention concerns:
            # the dispatcher is the only caller
            rj._bucket_done = True
            rj.bucket, rj.bucket_place, rj.prior = \
                _affinity_tokens(rj.payload)
        if rj.prior is not None:
            for w in free:
                if rj.prior in w.priors:
                    rj.routed_by = "prior"
                    return w.worker_id
        if rj.bucket_place is not None:
            # live inventory beats the sticky map: a worker that
            # REPORTS warm programs for this token is the affinity home
            for w in free:
                if rj.bucket_place in w.buckets:
                    rj.routed_by = "bucket"
                    return w.worker_id
        if rj.bucket is not None and rj.bucket != rj.bucket_place:
            # stream fallback: no worker hosted this stream family yet
            # — any worker with warm same-shape BATCH programs still
            # beats a cold one (the pre-dedicated-token behavior)
            for w in free:
                if rj.bucket in w.buckets:
                    rj.routed_by = "bucket_prog"
                    return w.worker_id
        for tok in (rj.bucket_place, rj.bucket):
            if tok is None:
                continue
            home = self._affinity.get(tok)
            if home is not None and any(
                    w.worker_id == home for w in free):
                rj.routed_by = "sticky"
                return home
        free.sort(key=lambda w: (assigned.get(w.worker_id, 0),
                                 w.registered_t))
        rj.routed_by = "load"
        return free[0].worker_id

    # -- the dispatcher loop -------------------------------------------------

    def _dispatch_pass(self) -> None:
        """One admission pass: expire dead leases, expire deadlines,
        then route the head of the queue (recovering jobs first, then
        priority-FIFO, strict head-of-line fleet-wide)."""
        self._evict_stale()
        # bucket tokens price a dataset-HEADER read: computed here,
        # OUTSIDE the router lock — holding the lock across shared-
        # filesystem I/O would stall heartbeats behind a slow NFS
        # read, and a stalled heartbeat path fabricates lease
        # evictions (the dispatcher is the only bucket writer, so the
        # unlocked flag/value stores race nothing)
        with self._lock:
            need = [rj for rj in self.jobs.values()
                    if rj.state == jq.QUEUED and not rj._bucket_done]
        for rj in need:
            rj.bucket, rj.bucket_place, rj.prior = \
                _affinity_tokens(rj.payload)
            rj._bucket_done = True
        to_submit = []
        with self._lock:
            now = time.time()
            queued = [rj for rj in self.jobs.values()
                      if rj.state == jq.QUEUED]
            for rj in queued:
                if rj.expired(now):
                    self._finish_locked(rj, jq.DEADLINE_EXCEEDED)
            queued = [rj for rj in queued if rj.state == jq.QUEUED]
            # priority strictly first (a high-priority STREAM job must
            # admit before a batch job it preempted can resume — the
            # same discipline as jq._next_admissible_solo); among equal
            # priorities, resuming hops re-admit ahead of every queued
            # job (they already held a slot — the jq.MIGRATING
            # discipline)
            queued.sort(key=lambda rj: (-rj.priority, not rj.resume,
                                        rj.seq))
            for rj in queued:
                target = self._place(rj)
                if target is None:
                    break               # strict head-of-line
                if rj.prior is not None:
                    # prior-affinity hit rate: of placements that HAD
                    # a prior key, how many the prior signal routed
                    self.prior_place_total += 1
                    if rj.routed_by == "prior":
                        self.prior_place_hits += 1
                        ometrics.inc(
                            "router_prior_affinity_hits_total")
                rj.state = DISPATCHED
                rj.worker_id = target
                rj.pinned_worker = None
                rj.n_dispatches += 1
                to_submit.append((rj, self.workers[target]))
        for rj, w in to_submit:
            self._forward_submit(rj, w)

    def _forward_submit(self, rj: RJob, w: WorkerInfo) -> None:
        req = {k: v for k, v in rj.payload.items()
               if k in ("config", "mpi_argv", "priority", "trace",
                        "on_diverge")}
        if rj.deadline_t is not None:
            req["deadline_s"] = max(0.0, rj.deadline_t - time.time())
        if rj.resume and req.get("config") is not None:
            req = dict(req, config=dict(req["config"], resume=True))
        try:
            with w.clock:
                w.get_client().request(op="submit",
                                       job_id=rj.worker_job_id, **req)
            with self._lock:
                self.dispatches += 1
                ometrics.inc("router_dispatches_total",
                             worker=w.worker_id)
                for tok in (rj.bucket, rj.bucket_place):
                    if tok is not None:
                        self._affinity[tok] = w.worker_id
            self.log(f"router: [{rj.job_id}] -> {w.worker_id}"
                     + (" (resume)" if rj.resume else ""))
        except Exception as e:
            # the worker refused or vanished between the pass and the
            # forward: back to the queue; a dead worker's lease expiry
            # will stop it being picked again
            self.log(f"router: [{rj.job_id}] dispatch to "
                     f"{w.worker_id} failed ({type(e).__name__}: {e}); "
                     "re-queueing")
            with self._lock:
                if not rj.terminal():
                    rj.state = jq.QUEUED
                    rj.worker_id = None
                    rj.n_dispatches -= 1

    def _evict_stale(self) -> None:
        """Lease expiry -> eviction -> recovery: every non-terminal
        job of the dead worker re-queues as a RESUME from its durable
        checkpoint watermark (zero completed tiles re-run; a job that
        never checkpointed restarts from tile 0 — same durability
        contract as the in-process ``migrate_abort`` recovery)."""
        with self._lock:
            now = time.time()
            for w in self.workers.values():
                if w.evicted or w.lease_t == 0.0 or now < w.lease_t:
                    continue
                w.evicted = True
                self.lease_evictions += 1
                ometrics.inc("router_lease_evictions_total")
                lost = [rj for rj in self.jobs.values()
                        if rj.worker_id == w.worker_id
                        and not rj.terminal()]
                self.log(f"router: worker {w.worker_id} lease expired "
                         f"({len(lost)} job(s) to recover)")
                for rj in lost:
                    hb = w.jobs.get(rj.worker_job_id) or {}
                    self._requeue_locked(
                        rj, None, reason="worker_lost",
                        tiles_at_yield=hb.get("tiles_done"))
                    # detection latency: how stale the dead worker's
                    # last heartbeat was when the lease ran out — the
                    # un-hideable half of recovery cost (wall_s only
                    # starts at eviction)
                    rj.hops[-1]["detect_s"] = round(
                        now - w.last_hb_t, 3) if w.last_hb_t else None
                    self.recoveries += 1
                    ometrics.inc("router_recoveries_total")

    def _poll_workers(self) -> None:
        """Refresh the snapshot of every active dispatched job with
        ONE pipelined status batch per worker (the api.Client
        request-pipelining satellite, used by the router itself)."""
        with self._lock:
            by_worker: dict[str, list[RJob]] = {}
            for rj in self.jobs.values():
                if rj.worker_id and not rj.terminal() \
                        and rj.state != jq.QUEUED:
                    by_worker.setdefault(rj.worker_id, []).append(rj)
            targets = [(self.workers[wid], rjs)
                       for wid, rjs in by_worker.items()
                       if wid in self.workers
                       and not self.workers[wid].evicted]
        for w, rjs in targets:
            try:
                with w.clock:
                    resps = w.get_client().pipeline(
                        [{"op": "status", "job_id": rj.worker_job_id}
                         for rj in rjs])
            except Exception:
                continue        # lease expiry owns dead-worker handling
            for rj, resp in zip(rjs, resps):
                if resp.get("ok"):
                    self._fold_snapshot(rj, resp["job"])

    def _start_migrations(self) -> None:
        """Send the cancel half of every requested migration (the
        resume half happens when the cancelled snapshot folds in)."""
        with self._lock:
            pending = [(rj, self.workers.get(rj.worker_id))
                       for rj in self.jobs.values()
                       if rj.migrate_to is not None
                       and not rj.terminal()
                       and rj.state in (jq.RUNNING, DISPATCHED)
                       and not getattr(rj, "_mig_cancel_sent", False)]
            for rj, _ in pending:
                rj._mig_cancel_sent = True
        for rj, w in pending:
            if w is None:
                continue
            try:
                with w.clock:
                    w.get_client().cancel(rj.worker_job_id)
            except Exception:
                pass            # worker gone: lease eviction recovers it

    def _run_dispatcher(self) -> None:
        while not self._stop.is_set():
            try:
                self._dispatch_pass()
                self._start_migrations()
                self._poll_workers()
            except Exception as e:      # the loop must survive anything
                self.log(f"router: dispatcher error ignored: "
                         f"{type(e).__name__}: {e}")
            with self._lock:
                if self._draining and all(j.terminal()
                                          for j in self.jobs.values()):
                    self._drained.set()
            time.sleep(self.poll_s)

    # -- metrics / health ----------------------------------------------------

    def metrics(self) -> dict:
        with self._lock:
            now = time.time()
            out: dict = {s: 0 for s in
                         (jq.QUEUED, jq.RUNNING, jq.MIGRATING, jq.DONE,
                          jq.FAILED, jq.CANCELLED, jq.DEADLINE_EXCEEDED)}
            out[DISPATCHED] = 0
            for rj in self.jobs.values():
                st = rj.state if rj.state in out else jq.QUEUED
                out[st] += 1
                if rj.migrate_to is not None and not rj.terminal():
                    out[jq.MIGRATING] += 1
            workers = [w.snapshot(now) for w in
                       sorted(self.workers.values(),
                              key=lambda w: w.registered_t)]
            alive = [w for w in workers if w["alive"]]
            rates = [w["cache"]["hit_rate"] for w in alive
                     if w["cache"].get("hits", 0)
                     + w["cache"].get("misses", 0) > 0]
            out.update(
                wall_s=now - self.t0,
                n_workers=len(workers), n_alive=len(alive),
                capacity_total=sum(w["capacity"] for w in alive),
                workers=workers,
                dispatches=self.dispatches,
                migrations=self.migrations,
                recoveries=self.recoveries,
                lease_evictions=self.lease_evictions,
                tiles_done=sum(w["tiles_done"] for w in workers),
                cache_hit_rate_min=min(rates, default=0.0),
                bucket_affinity=dict(self._affinity),
                prior_affinity={
                    "hits": self.prior_place_hits,
                    "total": self.prior_place_total,
                    "hit_rate": (self.prior_place_hits
                                 / self.prior_place_total)
                    if self.prior_place_total else 0.0},
                draining=self._draining,
            )
            # refresh point-in-time gauges alongside the snapshot so
            # pull-style readers (metrics_full) see fresh values
            ometrics.set_gauge("router_workers_alive",
                               float(len(alive)))
            for s in (jq.QUEUED, jq.RUNNING, jq.DONE, jq.FAILED):
                ometrics.set_gauge("router_jobs", float(out[s]),
                                   state=s)
            return out

    def healthz(self, m: dict | None = None) -> dict:
        m = m or self.metrics()
        return {
            "status": "ok" if (m["n_alive"] > 0 or not self.jobs)
            else "degraded",
            "n_alive": m["n_alive"], "queued": m[jq.QUEUED],
            "running": m[jq.RUNNING] + m[DISPATCHED],
            "draining": m["draining"],
        }

    # -- lifecycle -----------------------------------------------------------

    def drain(self) -> None:
        with self._lock:
            if not self._draining:
                self.log("router: draining — refusing new submissions")
            self._draining = True
            if all(j.terminal() for j in self.jobs.values()):
                self._drained.set()

    def start(self) -> None:
        router = self

        class Handler(socketserver.StreamRequestHandler):
            # same NODELAY discipline as the daemon listener (a
            # handler-class attribute; TCP only — setup() raises
            # OSError 95 setsockopt'ing an AF_UNIX socket): the
            # router both serves pipelined batches and issues them
            disable_nagle_algorithm = router.socket_path is None

            def handle(self):
                for line in self.rfile:
                    line = line.strip()
                    if not line:
                        continue
                    # same chaos seam as the daemon listener: the
                    # raise drops the connection; Client reconnect
                    # (and the worker agent's re-register loop) must
                    # recover
                    faults.inject("socket_drop")
                    try:
                        resp = router.handle_request(json.loads(line))
                    except Exception as e:
                        resp = {"ok": False,
                                "error": f"{type(e).__name__}: {e}"}
                    self.wfile.write(
                        (json.dumps(resp) + "\n").encode())
                    self.wfile.flush()

        if self.socket_path:
            if os.path.exists(self.socket_path):
                os.unlink(self.socket_path)

            class Srv(socketserver.ThreadingUnixStreamServer):
                daemon_threads = True
                allow_reuse_address = True
            self._srv = Srv(self.socket_path, Handler)
        else:
            class Srv(socketserver.ThreadingTCPServer):
                daemon_threads = True
                allow_reuse_address = True
            self._srv = Srv(("127.0.0.1", self.port), Handler)
            self.port = self._srv.server_address[1]
        self._accept = threading.Thread(
            target=self._srv.serve_forever,
            kwargs={"poll_interval": 0.1}, name="router-accept",
            daemon=True)
        self._accept.start()
        self._dispatcher.start()

    def serve_forever(self) -> None:
        try:
            self._drained.wait()
            # one last pass so late snapshots/metrics are consistent
            time.sleep(self.poll_s)
        finally:
            self.close()

    def close(self) -> None:
        self._stop.set()
        if self._srv is not None:
            self._srv.shutdown()
            self._srv.server_close()
            self._srv = None
        with self._lock:
            for w in self.workers.values():
                if w.client is not None:
                    try:
                        w.client.close()
                    except Exception:
                        pass
                    w.client = None
        if self.socket_path and os.path.exists(self.socket_path):
            try:
                os.unlink(self.socket_path)
            except OSError:
                pass

    def stop(self) -> None:
        """Hard stop (tests): no drain, just exit."""
        self._drained.set()
        self.close()


# ---------------------------------------------------------------------------
# worker side: the control-connection agent
# ---------------------------------------------------------------------------

def parse_router_addr(addr: str) -> dict:
    """``HOST:PORT`` -> ``{"host", "port"}``; anything else is a unix
    socket path. The router's data-plane Client is loopback-only, so a
    worker on another host must share both the filesystem AND a
    loopback tunnel to be routable — documented in MIGRATION.md
    "Multi-process fleet"."""
    if ":" in addr and not os.sep in addr:
        host, port = addr.rsplit(":", 1)
        return {"host": host or "127.0.0.1", "port": int(port)}
    return {"socket": addr}


class WorkerAgent:
    """Worker half of the control protocol: ONE persistent connection
    to the router (no per-op reconnect), ``worker_register`` first,
    then a ``worker_heartbeat`` every interval the router granted.
    Any socket failure — or an "evicted, re-register" refusal — drops
    the connection and re-registers with bounded backoff; the worker
    keeps serving its current jobs throughout (the router recovers
    them onto peers only when the LEASE expires, so a transient
    control blip costs nothing)."""

    def __init__(self, server, router_addr: str,
                 worker_id: str | None = None, log=print):
        import socket as _socket
        self.server = server
        self.addr = parse_router_addr(router_addr)
        self.worker_id = worker_id or (
            f"w-{_socket.gethostname()}-{os.getpid()}")
        self.log = log
        self._stop = threading.Event()
        self._sock = None
        self._f = None
        self._thread = threading.Thread(
            target=self._run, name="worker-agent", daemon=True)

    # -- payloads ------------------------------------------------------------

    def _register_payload(self) -> dict:
        srv = self.server
        n_dev = len(srv.scheduler.workers)
        addr = ({"socket": srv.socket_path} if srv.socket_path
                else {"port": srv.port})
        return {"op": "worker_register", "worker_id": self.worker_id,
                "addr": addr, "devices": n_dev,
                "capacity": srv.queue.max_inflight * n_dev,
                "pid": os.getpid()}

    def _heartbeat_payload(self) -> dict:
        from sagecal_tpu.serve import cache as pcache
        from sagecal_tpu.serve import priors as ppriors
        srv = self.server
        return {"op": "worker_heartbeat", "worker_id": self.worker_id,
                "jobs": [j.snapshot() for j in srv.queue.jobs()],
                "buckets": srv.scheduler.bucket_inventory(),
                # solution prior store inventory (serve/priors.py):
                # the router routes repeat fields at the worker
                # already holding their warm-start priors
                "priors": ppriors.PRIORS.inventory(),
                "cache": pcache.PROGRAMS.stats(),
                "counts": srv.queue.counts(),
                "tiles_done": srv.scheduler.tiles_done}

    # -- the persistent connection -------------------------------------------

    def _connect(self) -> None:
        import socket as _socket
        if "socket" in self.addr:
            s = _socket.socket(_socket.AF_UNIX)
            s.connect(self.addr["socket"])
        else:
            s = _socket.create_connection(
                (self.addr.get("host", "127.0.0.1"),
                 self.addr["port"]))
            s.setsockopt(_socket.IPPROTO_TCP,
                         _socket.TCP_NODELAY, 1)
        s.settimeout(30.0)
        self._sock = s
        self._f = s.makefile("rwb")

    def _interrupt(self) -> None:
        """Close the connection WITHOUT rebinding the refs — the only
        socket operation another thread may perform. ``stop()`` uses
        it to unblock a ``readline`` on the agent thread (closing a
        socket from another thread is the documented interruption
        idiom); the agent thread observes the OSError and runs its own
        :meth:`_drop`. Rebinding here instead raced the agent
        mid-roundtrip with an uncaught AttributeError (threadlint
        shared-state, round 19)."""
        for o in (self._f, self._sock):
            try:
                if o is not None:
                    o.close()
            except OSError:
                pass

    # thread-role: worker-agent
    def _drop(self) -> None:
        self._interrupt()
        self._f = self._sock = None

    def _roundtrip(self, obj: dict) -> dict:
        self._f.write((json.dumps(obj) + "\n").encode())
        self._f.flush()
        line = self._f.readline()
        if not line:
            raise ConnectionError("router closed the control connection")
        return json.loads(line)

    def _run(self) -> None:
        backoff = 0.1
        hb_s = 1.0
        while not self._stop.is_set():
            try:
                if self._f is None:
                    self._connect()
                    r = self._roundtrip(self._register_payload())
                    if not r.get("ok"):
                        raise ConnectionError(
                            f"register refused: {r.get('error')}")
                    hb_s = float(r.get("heartbeat_s", hb_s))
                    backoff = 0.1
                    self.log(f"worker {self.worker_id}: registered "
                             f"(lease {r.get('lease_s')}s, heartbeat "
                             f"{hb_s}s)")
                if self._stop.wait(hb_s):
                    break
                r = self._roundtrip(self._heartbeat_payload())
                if not r.get("ok"):
                    # evicted incarnation: the router already
                    # recovered this worker's jobs onto peers, so any
                    # still running HERE are split-brain orphans —
                    # cancel them (tile-boundary cooperative) before
                    # re-registering fresh. The overlap window is one
                    # heartbeat; both writers are deterministic and
                    # identical for MS tiles, but the solutions file
                    # append must not be contested longer than that
                    self._cancel_orphans()
                    raise ConnectionError(
                        f"heartbeat refused: {r.get('error')}")
            except (ConnectionError, OSError, ValueError) as e:
                self._drop()
                if self._stop.is_set():
                    break
                self.log(f"worker {self.worker_id}: control "
                         f"connection lost ({type(e).__name__}: {e}); "
                         f"re-registering in {backoff:.1f}s")
                if self._stop.wait(backoff):
                    break
                backoff = min(backoff * 2, 5.0)
        self._drop()

    def _cancel_orphans(self) -> None:
        """Cancel every non-terminal local job (the router evicted
        this incarnation, so they are re-running elsewhere)."""
        for j in self.server.queue.jobs():
            if j.state not in jq.TERMINAL:
                try:
                    self.server.queue.cancel(j.job_id)
                    self.log(f"worker {self.worker_id}: cancelled "
                             f"orphaned job {j.job_id} (evicted "
                             "incarnation; the router re-homed it)")
                except KeyError:
                    pass

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._interrupt()       # agent thread owns (and nulls) the refs


# ---------------------------------------------------------------------------
# CLI: `python -m sagecal_tpu.serve.router`
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    import argparse
    import signal
    import sys
    p = argparse.ArgumentParser(
        prog="python -m sagecal_tpu.serve.router",
        description="fleet router: the serve JSON-lines API fronting "
                    "worker daemons (python -m sagecal_tpu.serve "
                    "--worker --router ADDR) with leased heartbeats, "
                    "bucket-affinity routing and checkpoint-based "
                    "cross-process migration/recovery")
    g = p.add_mutually_exclusive_group(required=True)
    g.add_argument("--socket", metavar="PATH",
                   help="unix socket path to listen on")
    g.add_argument("--port", type=int,
                   help="TCP port on 127.0.0.1 (0 = ephemeral)")
    p.add_argument("--lease-s", type=float, default=5.0,
                   help="worker lease duration; a worker silent this "
                        "long is evicted and its jobs recovered onto "
                        "surviving workers from their checkpoint "
                        "watermarks (default 5)")
    p.add_argument("--heartbeat-s", type=float, default=None,
                   help="heartbeat cadence granted to workers "
                        "(default lease/3)")
    args = p.parse_args(argv)
    r = Router(socket_path=args.socket, port=args.port,
               lease_s=args.lease_s, heartbeat_s=args.heartbeat_s)
    signal.signal(signal.SIGTERM, lambda *a: r.drain())
    signal.signal(signal.SIGINT, lambda *a: r.drain())
    r.start()
    where = args.socket or f"127.0.0.1:{r.port}"
    print(f"sagecal-router: listening on {where} "
          f"(lease {r.lease_s}s, heartbeat {r.heartbeat_s}s)",
          flush=True)
    r.serve_forever()
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
