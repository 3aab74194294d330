"""Zero-dependency JSON-lines protocol over a local socket.

One request object per line, one response object per line (stdlib
``socket`` + ``json`` only — a casacore-less cluster node can drive
the server with ``nc``). Requests:

===========  ==============================================================
op           request fields / reply
===========  ==============================================================
``submit``   ``config``: RunConfig field dict (CLI-long names, e.g.
             ``{"ms": ..., "sky_model": ..., "cluster_file": ...}``);
             optional ``priority`` (int, higher first), ``trace``
             (per-job --diag JSONL path), ``job_id``, ``deadline_s``
             (seconds from submission; an expired job stops at its
             next tile boundary as ``deadline_exceeded``),
             ``on_diverge`` (``none`` advisory / ``fail``
             circuit-break / ``quarantine`` per-tile last-good
             fallback). ``config`` may carry ``resume: true`` to
             re-enter a killed/failed job from its checkpoint
             sidecar. Reply ``{"ok": true, "job_id": ...}``.
             Refused while draining.
``status``   optional ``job_id``; reply one snapshot or all of them
``cancel``   ``job_id``; queued cancels now, running at its next tile
             boundary (reply carries the state observed)
``migrate``  ``job_id`` + ``device``: yield a running fullbatch job at
             its next tile boundary and resume it on the target device
             from its checkpoint watermark (zero tiles re-run,
             bit-identical — MIGRATION.md "Fleet mode"); the fleet
             controller work-steals with the same machinery
``metrics``  queue depths, compile-cache hits/misses/hit_rate,
             device-busy fraction, tiles/jobs done, last-progress
             watermark, unhealthy jobs, and in fleet mode a
             ``devices`` list (per-device busy/running/tiles/cache
             hit rate/watermark) + migration counters
``metrics_full``  the ``metrics`` payload PLUS the full obs registry
             dump: every counter/gauge, and per-job SLO histograms
             (queue-wait / run / end-to-end latency) with
             p50/p90/p99 readout (obs/metrics.py)
``drain``    refuse new submissions, finish accepted jobs, then exit;
             ``wait: true`` blocks the reply until drained
``ping``     liveness
===========  ==============================================================

HTTP observability (``metrics_port=`` / ``--metrics-port``): a stdlib
HTTP listener on localhost serving ``GET /metrics`` (Prometheus text
format — the same registry, scrapeable by stock tooling) and ``GET
/healthz`` (JSON: queue depth, device-busy fraction, last-progress
watermark, stalled/diverging jobs; HTTP 200 healthy / 503 degraded).
Point-in-time gauges are refreshed from the scheduler on each scrape.

SIGTERM == ``drain``: in-flight tiles finish, writers flush, new
submissions are refused, the process exits when idle (MIGRATION.md
"Service mode"). Bad requests get ``{"ok": false, "error": ...}`` on
their own line; the connection stays up.
"""

from __future__ import annotations

import dataclasses
import json
import os
import socket
import socketserver
import threading
import time
import uuid

from sagecal_tpu import faults
from sagecal_tpu.config import (BeamMode, RunConfig, SimulationMode,
                                SolverMode)
from sagecal_tpu.obs import export as oexport
from sagecal_tpu.obs import metrics as ometrics
from sagecal_tpu.serve import queue as jq
from sagecal_tpu.serve.scheduler import Scheduler

_ENUMS = {"solver_mode": SolverMode, "simulation": SimulationMode,
          "beam_mode": BeamMode}
_FIELDS = {f.name for f in dataclasses.fields(RunConfig)} - {"precision"}


def config_from_dict(d: dict) -> RunConfig:
    """RunConfig from a request's ``config`` dict; unknown keys are an
    error (a typo'd flag silently calibrating with defaults is exactly
    the failure mode a service must refuse)."""
    bad = set(d) - _FIELDS
    if bad:
        raise ValueError(f"unknown config fields: {sorted(bad)}")
    kw = dict(d)
    for k, enum in _ENUMS.items():
        if k in kw:
            kw[k] = enum(int(kw[k]))
    if "spatialreg" in kw and kw["spatialreg"] is not None:
        kw["spatialreg"] = tuple(kw["spatialreg"])
    return RunConfig(**kw)


#: default submit priority of a streaming job: above the batch default
#: (0) so the queue's priority-FIFO admits streams first and the
#: scheduler's preemption policy has a priority gap to act on; an
#: explicit submit priority always wins
STREAM_DEFAULT_PRIORITY = 10


def job_kind(cfg: RunConfig) -> str:
    """Same dispatch as cli.main: stochastic if -N>0, simulation for
    -a modes, stream for live ingest, fullbatch (tile-interleaved)
    otherwise."""
    if getattr(cfg, "stream_source", None):
        return "stream"
    if cfg.n_epochs > 0:
        return "stochastic"
    if cfg.simulation != SimulationMode.OFF:
        return "sim"
    return "fullbatch"


class Server:
    """Queue + scheduler + socket listener, one process, one device."""

    def __init__(self, socket_path: str | None = None,
                 port: int | None = None, max_inflight: int = 2,
                 max_staged_bytes: int = 2 << 30, log=print,
                 metrics_port: int | None = None,
                 devices: int | None = None):
        if (socket_path is None) == (port is None):
            raise ValueError("exactly one of socket_path/port")
        self.socket_path = socket_path
        self.port = port
        self.log = log
        # the daemon is the production surface: the obs registry is
        # always live here (solo CLI runs keep the disabled default —
        # MIGRATION.md "Observability")
        self.registry = ometrics.enable()
        # fleet mode (--devices): one owner loop per device, jobs
        # routed by shape-bucket affinity, per-device admission
        # budgets. None/1 = the single-device pre-fleet daemon,
        # bit- and compile-count-identical (MIGRATION.md "Fleet mode")
        self.queue = jq.JobQueue(max_inflight=max_inflight,
                                 max_staged_bytes=max_staged_bytes)
        from sagecal_tpu.serve import fleet
        self.scheduler = Scheduler(self.queue, log=log,
                                   devices=fleet.fleet_devices(devices))
        self.metrics_port = metrics_port
        self._obs_http = None
        self._drained = threading.Event()
        self._sched_thread = threading.Thread(
            target=self._run_scheduler, name="device-owner", daemon=True)
        self._srv = None

    # -- scheduler thread ---------------------------------------------------

    def _run_scheduler(self):
        try:
            self.scheduler.run()
        finally:
            self._drained.set()

    # -- request handling ---------------------------------------------------

    def handle_request(self, req: dict) -> dict:
        op = req.get("op")
        if op == "ping":
            return {"ok": True, "pong": True}
        if op == "submit":
            if req.get("mpi_argv") is not None:
                # the cli_mpi consensus interval loop as a submittable
                # job: the raw argv, run as one opaque isolated unit.
                # Flags that mutate PROCESS-global state are refused:
                # --platform/--cpu-devices would re-point every
                # tenant's device, and --diag installs (then closes)
                # the process tracer, killing server-level tracing —
                # per-job tracing is the submit 'trace' field.
                # --metrics joins the ban for the same reason as
                # --diag: it would dump-and-DISABLE the daemon's
                # process registry when the job ends; --faults would
                # install a process-global fault plan under every
                # tenant
                argv = [str(a) for a in req["mpi_argv"]]
                banned = {"--platform", "--cpu-devices", "--diag",
                          "--metrics", "--faults"}
                bad = sorted(banned & {a.split("=", 1)[0] for a in argv})
                if bad:
                    raise ValueError(
                        f"mpi_argv flags {bad} mutate process-global "
                        "state inside a multi-tenant server; per-job "
                        "tracing uses the submit 'trace' field")
                job = jq.Job(req.get("job_id") or uuid.uuid4().hex[:12],
                             cfg=None,
                             priority=int(req.get("priority", 0)),
                             trace_path=req.get("trace"), kind="mpi",
                             argv=argv,
                             deadline_s=req.get("deadline_s"))
                self.queue.submit(job)
                self.log(f"[{job.job_id}] queued (mpi)")
                return {"ok": True, "job_id": job.job_id}
            cfg = config_from_dict(req.get("config") or {})
            if (not cfg.ms and not cfg.ms_list) \
                    or not cfg.sky_model or not cfg.cluster_file:
                raise ValueError("config needs ms (or ms_list), "
                                 "sky_model and cluster_file")
            kind = job_kind(cfg)
            # streams are latency-SLO work: they default ABOVE batch
            # priority so they admit first and may preempt batch at a
            # tile boundary (serve/scheduler.py preemption policy)
            default_prio = (STREAM_DEFAULT_PRIORITY
                            if kind == "stream" else 0)
            job = jq.Job(req.get("job_id") or uuid.uuid4().hex[:12],
                         cfg,
                         priority=int(req.get("priority",
                                              default_prio)),
                         trace_path=req.get("trace"),
                         kind=kind,
                         deadline_s=req.get("deadline_s"),
                         on_diverge=req.get("on_diverge", "none"))
            self.queue.submit(job)
            self.log(f"[{job.job_id}] queued ({job.kind}, "
                     f"priority {job.priority})")
            return {"ok": True, "job_id": job.job_id}
        if op == "status":
            jid = req.get("job_id")
            if jid:
                return {"ok": True, "job": self.queue.get(jid).snapshot()}
            return {"ok": True,
                    "jobs": [j.snapshot() for j in self.queue.jobs()]}
        if op == "cancel":
            state = self.queue.cancel(req["job_id"])
            return {"ok": True, "state": state}
        if op == "migrate":
            # manual tile-boundary migration (the automatic path is
            # the controller's work stealing): the owning device-owner
            # loop yields the job at its next boundary, the target
            # re-admits it as a checkpoint resume — zero tiles re-run,
            # bit-identical outputs (MIGRATION.md "Fleet mode")
            state = self.scheduler.request_migration(
                req["job_id"], int(req["device"]))
            return {"ok": True, "state": state}
        if op == "metrics":
            return {"ok": True, "metrics": self.scheduler.metrics()}
        if op == "metrics_full":
            # scheduler snapshot + the full registry dump (counters,
            # gauges, per-job SLO histograms with p50/p90/p99); ONE
            # snapshot feeds both views so they cannot disagree
            m = self._refresh_gauges()
            return {"ok": True, "metrics": m,
                    "registry": self.registry.dump(),
                    "health": self.healthz(m)}
        if op == "drain":
            self.drain()
            if req.get("wait"):
                self._drained.wait()
            return {"ok": True, "draining": True}
        raise ValueError(f"unknown op {op!r}")

    # -- observability (obs/export.py endpoint) -----------------------------

    def _refresh_gauges(self) -> dict:
        """Fold the scheduler's point-in-time snapshot into registry
        gauges (runs per scrape / metrics_full request, so pull-style
        readers always see fresh depths); returns the snapshot."""
        m = self.scheduler.metrics()
        for state in (jq.QUEUED, jq.RUNNING, jq.MIGRATING, jq.DONE,
                      jq.FAILED, jq.CANCELLED):
            ometrics.set_gauge("serve_jobs", float(m[state]),
                               state=state)
        ometrics.set_gauge("serve_staged_bytes", m["staged_bytes"])
        ometrics.set_gauge("serve_device_busy_frac",
                           m["device_busy_frac"])
        ometrics.set_gauge("serve_program_cache_hit_rate",
                           m["hit_rate"])
        ometrics.set_gauge("serve_last_progress_age_seconds",
                           max(0.0, time.time() - m["last_progress_t"]))
        ometrics.set_gauge("serve_unhealthy_jobs",
                           float(len(m["unhealthy_jobs"])))
        # per-device fleet snapshot (the unlabeled aggregates above
        # stay — single-device scrape output is a superset of PR 8's)
        now = time.time()
        for d in m["devices"]:
            dev = str(d["device"])
            ometrics.set_gauge("serve_device_busy_frac",
                               d["busy_frac"], device=dev)
            ometrics.set_gauge("serve_device_running_jobs",
                               float(d["running"]), device=dev)
            ometrics.set_gauge("serve_device_tiles_done",
                               float(d["tiles_done"]), device=dev)
            ometrics.set_gauge(
                "serve_last_progress_age_seconds",
                max(0.0, now - d["last_progress_t"]), device=dev)
            ometrics.set_gauge("serve_program_cache_hit_rate",
                               d["cache"]["hit_rate"], device=dev)
        return m

    def render_metrics(self) -> str:
        self._refresh_gauges()
        return oexport.render_prometheus(self.registry)

    def healthz(self, m: dict | None = None) -> dict:
        """Liveness/degradation snapshot. ``unhealthy_jobs`` lists
        every running stalled/diverging job (visible BEFORE the job
        burns its tile budget), but ``status`` degrades — and the
        HTTP endpoint answers 503 — only on DIVERGING
        (obs/health.DEGRADED): a converged job's flat residual reads
        stalled by construction and must not page the LB probe.
        ``m``: an already-taken scheduler snapshot to reuse."""
        from sagecal_tpu.obs import health as ohealth
        if m is None:
            m = self.scheduler.metrics()
        unhealthy = m["unhealthy_jobs"]
        degraded = any(j["health"] in ohealth.DEGRADED
                       for j in unhealthy)
        now = time.time()
        return {
            "status": "degraded" if degraded else "ok",
            "queued": m[jq.QUEUED], "running": m[jq.RUNNING],
            "migrating": m[jq.MIGRATING],
            "device_busy_frac": m["device_busy_frac"],
            "last_progress_t": m["last_progress_t"],
            "last_progress_age_s":
                max(0.0, now - m["last_progress_t"]),
            # per-device liveness: a wedged device stops moving ITS
            # watermark while the fleet aggregate keeps advancing
            "devices": [
                {"device": d["device"], "busy_frac": d["busy_frac"],
                 "running": d["running"],
                 "last_progress_age_s":
                     max(0.0, now - d["last_progress_t"])}
                for d in m["devices"]],
            "unhealthy_jobs": unhealthy,
            "draining": self.queue.draining,
        }

    # -- lifecycle ----------------------------------------------------------

    def drain(self) -> None:
        """Graceful: refuse submissions, let accepted jobs finish; the
        scheduler loop (and serve_forever) exits once idle."""
        if not self.queue.draining:
            self.log("drain: refusing new submissions, finishing "
                     "in-flight jobs")
        self.queue.start_drain()

    def start(self) -> None:
        server = self

        class Handler(socketserver.StreamRequestHandler):
            # reply batches must not sit out Nagle/delayed-ACK stalls
            # (the Client pipelining contract; a handler-class
            # attribute — setting it on the server class does
            # nothing). TCP ONLY: setup() would raise OSError 95
            # setsockopt'ing an AF_UNIX socket, killing every
            # unix-socket connection before handle() ran
            disable_nagle_algorithm = server.socket_path is None

            def handle(self):
                for line in self.rfile:
                    line = line.strip()
                    if not line:
                        continue
                    # socket_drop: the connection-loss chaos seam —
                    # the raise escapes handle(), socketserver closes
                    # the connection, and the Client's bounded
                    # reconnect-with-backoff must recover
                    faults.inject("socket_drop")
                    try:
                        resp = server.handle_request(json.loads(line))
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
        self._accept_thread = threading.Thread(
            target=self._srv.serve_forever,
            kwargs={"poll_interval": 0.1}, name="accept", daemon=True)
        self._accept_thread.start()
        if self.metrics_port is not None:
            self._obs_http = oexport.ObsHTTPServer(
                self.metrics_port, self.render_metrics, self.healthz)
            self.metrics_port = self._obs_http.port
            self.log(f"observability: /metrics and /healthz on "
                     f"127.0.0.1:{self.metrics_port}")
        self._sched_thread.start()

    def serve_forever(self) -> None:
        """Block until drained (SIGTERM or the drain op)."""
        try:
            self._drained.wait()
        finally:
            self.close()

    def close(self) -> None:
        if self._obs_http is not None:
            self._obs_http.close()
            self._obs_http = None
        if self._srv is not None:
            self._srv.shutdown()
            self._srv.server_close()
            self._srv = None
        if self.socket_path and os.path.exists(self.socket_path):
            try:
                os.unlink(self.socket_path)
            except OSError:
                pass

    def stop(self) -> None:
        """Hard stop (tests): cancel running jobs, exit now."""
        self.queue.start_drain()
        self.scheduler.stop()
        self._drained.wait(timeout=30.0)
        self.close()


class Client:
    """Line-oriented client for the protocol above (tests, loadgen,
    embedders). One socket, requests answered in order.

    Robustness: a transient socket failure (connection reset, dropped
    connection, EOF mid-reply) no longer raises on the first
    ``ConnectionError`` — the client reconnects with bounded
    exponential backoff and re-sends the request, up to
    ``reconnects`` total tries, then re-raises. Re-sending is made
    safe for the one non-idempotent op by :meth:`submit` always
    attaching a client-generated ``job_id``: a retry whose first send
    actually landed gets the server's "duplicate job id" refusal and
    treats it as success."""

    def __init__(self, socket_path: str | None = None,
                 port: int | None = None, timeout: float = 600.0,
                 reconnects: int = 3, reconnect_base_s: float = 0.1):
        self._addr = (socket_path, port)
        self._timeout = float(timeout)
        self._reconnects = max(1, int(reconnects))
        self._reconnect_base_s = float(reconnect_base_s)
        self._sock = None
        self._f = None
        self._connect()

    def _connect(self) -> None:
        socket_path, port = self._addr
        if socket_path:
            s = socket.socket(socket.AF_UNIX)
            s.connect(socket_path)
        else:
            s = socket.create_connection(("127.0.0.1", port))
            # without NODELAY a pipelined batch loses to Nagle +
            # delayed-ACK (~40 ms stalls that dwarf the round-trips
            # pipelining removes); the protocol is line-delimited
            # JSON, so there is nothing for Nagle to usefully coalesce
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        s.settimeout(self._timeout)
        self._sock = s
        self._f = s.makefile("rwb")

    def _drop(self) -> None:
        for o in (self._f, self._sock):
            try:
                if o is not None:
                    o.close()
            except OSError:
                pass
        self._f = self._sock = None

    def request(self, **req) -> dict:
        payload = (json.dumps(req) + "\n").encode()
        self._last_request_resent = False
        for attempt in range(self._reconnects):
            try:
                if self._f is None:
                    self._connect()
                if attempt > 0:
                    # the request body went out more than once — the
                    # signal submit() needs to tell a retry-induced
                    # duplicate-id refusal from a genuine one
                    self._last_request_resent = True
                self._f.write(payload)
                self._f.flush()
                line = self._f.readline()
                if not line:
                    raise ConnectionError(
                        "server closed the connection")
                break
            except (ConnectionError, OSError):
                # transient socket failure: drop the dead socket and
                # reconnect with bounded backoff; the last attempt
                # re-raises (the caller's fail-stop path)
                self._drop()
                if attempt == self._reconnects - 1:
                    raise
                time.sleep(self._reconnect_base_s * (2 ** attempt))
        resp = json.loads(line)
        if not resp.get("ok"):
            raise RuntimeError(resp.get("error", "request failed"))
        return resp

    def submit(self, config: dict, **kw) -> str:
        # a client-side job_id makes submit idempotent under the
        # reconnect-and-resend path (see the class docstring)
        kw.setdefault("job_id", uuid.uuid4().hex[:12])
        try:
            return self.request(op="submit", config=config,
                                **kw)["job_id"]
        except RuntimeError as e:
            # only a RESENT request may read the duplicate refusal as
            # "the first send landed" — on a clean first attempt it is
            # a genuine collision the caller must see
            if self._last_request_resent \
                    and "duplicate job id" in str(e):
                return kw["job_id"]
            raise

    def pipeline(self, reqs: list) -> list:
        """Request PIPELINING on the persistent connection: write all
        ``reqs`` before reading any reply, collapsing N network
        round-trips into one (the server answers a connection's lines
        strictly in order, daemon and router alike). Returns the raw
        response dicts IN ORDER — per-request errors come back as
        ``{"ok": false, ...}`` rows, not raises (a batch reader must
        see which row failed). Only for ops that are idempotent under
        resend (status/metrics/ping): a transient socket failure
        reconnects and re-sends the WHOLE batch, up to the same
        ``reconnects`` budget as :meth:`request`."""
        payload = b"".join((json.dumps(r) + "\n").encode()
                           for r in reqs)
        if not reqs:
            return []
        for attempt in range(self._reconnects):
            try:
                if self._f is None:
                    self._connect()
                self._f.write(payload)
                self._f.flush()
                lines = []
                for _ in reqs:
                    line = self._f.readline()
                    if not line:
                        raise ConnectionError(
                            "server closed the connection mid-batch")
                    lines.append(line)
                return [json.loads(ln) for ln in lines]
            except (ConnectionError, OSError):
                self._drop()
                if attempt == self._reconnects - 1:
                    raise
                time.sleep(self._reconnect_base_s * (2 ** attempt))

    def status_many(self, job_ids) -> list:
        """Snapshots of many jobs in ONE pipelined round-trip (the
        loadgen's post-replay sweep, the router's per-worker poll)."""
        out = []
        for r in self.pipeline([{"op": "status", "job_id": j}
                                for j in job_ids]):
            if not r.get("ok"):
                raise RuntimeError(r.get("error", "status failed"))
            out.append(r["job"])
        return out

    def status(self, job_id: str | None = None):
        r = self.request(op="status",
                         **({"job_id": job_id} if job_id else {}))
        return r["job"] if job_id else r["jobs"]

    def cancel(self, job_id: str) -> str:
        return self.request(op="cancel", job_id=job_id)["state"]

    def migrate(self, job_id: str, device: int) -> str:
        return self.request(op="migrate", job_id=job_id,
                            device=int(device))["state"]

    def metrics(self) -> dict:
        return self.request(op="metrics")["metrics"]

    def metrics_full(self) -> dict:
        """Scheduler snapshot + registry dump + health (the full
        observability payload; registry histograms carry p50/p90/p99)."""
        r = self.request(op="metrics_full")
        return {k: r[k] for k in ("metrics", "registry", "health")}

    def drain(self, wait: bool = False) -> None:
        self.request(op="drain", wait=wait)

    def wait(self, job_id: str, timeout_s: float = 600.0,
             poll_s: float = 0.05) -> dict:
        """Block until the job reaches a terminal state. Elapsed time
        is measured with ``time.monotonic`` — a wall-clock jump (NTP
        step, suspend/resume) must neither fire the timeout early nor
        stretch it."""
        t0 = time.monotonic()
        while True:
            snap = self.status(job_id)
            if snap["state"] in jq.TERMINAL:
                return snap
            if time.monotonic() - t0 > timeout_s:
                raise TimeoutError(
                    f"job {job_id} still {snap['state']} "
                    f"after {timeout_s}s")
            time.sleep(poll_s)

    def close(self) -> None:
        self._drop()

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()
