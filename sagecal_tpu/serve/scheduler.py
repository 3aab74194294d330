"""Device-owner loops: many jobs' tiles through a device FLEET.

One :class:`_Worker` per fleet device, each driving ITS device from
exactly one thread (per-device owner loop). Per job the owning worker
holds a stepper (``pipeline.TileStepper`` for fullbatch jobs, the
ISSUE 12 ``stochastic.StochasticStepper`` for minibatch jobs — both
expose the same ``stage``/``step``/``close`` contract), a per-job
``sched.Prefetcher`` (read + host-stage on the job's reader thread)
and the stepper's ordered ``sched.AsyncWriter``. Each loop round-
robins over its running jobs and steps whichever has a staged tile
READY (``Prefetcher.poll``), so one job's slow IO never parks the
device while another job has work.

Placement (serve/fleet.py): queued jobs are routed to a device by
shape-bucket affinity — the device whose compile cache already holds
the job's program set (per-device hit rates are exported by
``metrics``) — then by least load; capacity (inflight jobs + staged
bytes) is budgeted PER DEVICE. With one device the whole layer
degenerates to the PR 7 single-owner-loop behavior bit- and
compile-count-identically (no jax device context is even entered).

Migration (tile boundaries only): a running fullbatch job with a
checkpoint sidecar can move to another device — the owner yields it
at the next boundary (flush writes, land the PR 9 ``.ckpt.npz``
watermark, tear down its threads), the job re-queues pinned to the
target, and the target's owner re-admits it as a RESUME. Zero
completed tiles re-run (resume starts at watermark + 1) and the final
outputs are bit-identical to an unmigrated run — both gated, in
tests/test_serve.py. The ``migrate_abort`` chaos seam
(sagecal_tpu.faults) kills the handoff between the checkpoint flush
and the re-admission; recovery drops the pin and re-queues from the
durable watermark, so an aborted migration loses zero tiles
(tests/test_faults.py). The fleet controller thread work-steals with
the same machinery: an idle device pulls a migratable job off the
busiest one.

Bit-identity argument: a job's tiles are staged and stepped strictly
in its own tile order; its warm-start Jones chain, divergence resets,
and the ``fold_in(199, tile_idx)`` PRNG stream live inside its
stepper and never observe the interleaving, the device it runs on
(virtual CPU devices share one ALU; on real hardware the solver
programs are deterministic per backend), or a mid-stream migration
(resume restores the exact chain state from the full-precision
checkpoint). Program *compilations* are shared through
``serve.cache``, keyed per device ordinal.

Failure model (fail-stop, per job): any exception out of a job's
stage/step/write path — after the sched layer's bounded transient
retries gave up — moves THAT job to ``failed`` with the original
traceback recorded, tears down its threads, and the loop keeps
serving its neighbours. Per-job deadlines, cancel, migration and the
divergence circuit-breaker (``on_diverge=fail``) all take effect at
tile boundaries.

Simulation / mpi / tile-batch / consensus-stochastic jobs reuse their
existing whole-run drivers as one OPAQUE unit on their placed
worker's thread: correct and isolated, but not tile-interleaved
(plain minibatch-stochastic jobs ARE tile-interleaved since ISSUE 12;
documented in MIGRATION.md "Fleet mode").
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time

import numpy as np

from sagecal_tpu import faults, sched
from sagecal_tpu.analysis import threadsan
from sagecal_tpu.diag import trace as dtrace
from sagecal_tpu.obs import health as ohealth
from sagecal_tpu.obs import metrics as obs
from sagecal_tpu.serve import cache as pcache
from sagecal_tpu.serve import fleet
from sagecal_tpu.serve import priors as ppriors
from sagecal_tpu.serve import queue as jq


def job_telemetry_ctx(tracer, job_id, ordinal: int = 0, device=None):
    """Zero-arg factory for ONE job's telemetry + device context:
    routes the entering thread's diag emits to the job tracer
    (``dtrace.scope``), labels its obs metric emissions with the
    owning job (``obs.scope_labels``), and binds it to the owning
    worker's device (``fleet.device_scope`` — a no-op for the
    single-device daemon, where ``device`` is None). The SAME factory
    serves the device-owner thread around a step, the job's reader
    thread (Prefetcher ``context=``), and its writer thread
    (TileStepper ``trace_ctx=``) — one definition, so per-job
    attribution AND device placement cannot drift between the three
    thread roles (a reader staging onto the wrong device would force
    a silent cross-device copy per tile)."""
    @contextlib.contextmanager
    def ctx():
        with fleet.device_scope(ordinal, device), \
                fleet.job_scope(job_id), \
                dtrace.scope(tracer), obs.scope_labels(job=job_id):
            yield
    return ctx


class _RunningJob:
    """Worker-side live state of one running tile-interleaved job."""

    def __init__(self, job, pipe, stepper, prefetcher, tracer, ctx,
                 stream=None):
        self.job = job
        self.pipe = pipe
        self.stepper = stepper
        self.pf = prefetcher
        self.tracer = tracer
        self.ctx = ctx                  # per-job telemetry context
        self.stream = stream            # live TileStream (stream jobs)
        # live convergence health over the per-tile residual stream
        self.health = ohealth.ConvergenceHealth()

    def teardown(self, raise_pending: bool = False):
        self.pf.close()                 # stops the reader thread first:
        if self.stream is not None:     # nobody is inside wait_next/
            try:                        # take when the transport closes
                self.stream.close()
            except Exception:
                pass
        try:
            self.stepper.close(raise_pending=raise_pending)
        finally:
            if self.tracer is not None:
                self.tracer.close()


def estimate_staged_bytes(job) -> int:
    """Admission-control price of a job's staged working set: the
    overlap machinery holds up to ``prefetch + 2`` (ring) + 1
    (in-flight) tiles, each carrying the solve input [B, 8], the
    staged residual rows [B, F, 8] and uvw [B, 3]. Meta comes from the
    dataset header only (cheap); an unreadable dataset prices at 0 and
    fails properly at job start instead of blocking admission."""
    try:
        from sagecal_tpu.io import dataset as ds
        cfg = job.cfg
        ms = ds.open_dataset(cfg.ms, cfg.ms_list, tilesz=cfg.tile_size,
                             data_column=cfg.input_column,
                             out_column=cfg.output_column)
        meta = ms.meta
        rows = int(meta["tilesz"]) * int(meta["nbase"])
        F = len(meta["freqs"])
        from sagecal_tpu import dtypes as dtp
        itemsize = np.dtype(dtp.storage_dtype(
            getattr(cfg, "dtype_policy", "f32"), np.float32)).itemsize
        per_tile = rows * (8 + 8 * F) * itemsize + rows * 3 * 4
        live = int(getattr(cfg, "prefetch", 1)) + 3
        return per_tile * live
    except Exception:
        return 0


class _Worker:
    """One device's owner-loop state (stepped by its own thread in
    fleet mode; inline on the scheduler thread for a single device)."""

    def __init__(self, ix: int, device):
        self.ix = int(ix)
        self.device = device            # jax Device, or None (default)
        self.running: list[_RunningJob] = []
        # set by every owned job's reader thread after staging a tile:
        # the idle path waits on it (then re-polls) instead of
        # sleeping a fixed quantum
        self.ready = threading.Event()
        self.busy_s = 0.0
        self.tiles_done = 0
        self.jobs_done = 0
        self.last_progress_t = time.time()

    def snapshot(self, wall: float) -> dict:
        return {"device": self.ix,
                "name": "default" if self.device is None
                else str(self.device),
                "busy_s": self.busy_s,
                "busy_frac": (self.busy_s / wall) if wall else 0.0,
                "running": len(self.running),
                "tiles_done": self.tiles_done,
                "jobs_done": self.jobs_done,
                "last_progress_t": self.last_progress_t}


class Scheduler:
    """Owns the device fleet; drives :class:`serve.queue.JobQueue`
    jobs. ``devices``: the ``fleet.fleet_devices`` list — ``[None]``
    (default) is the single-device pre-fleet identity path."""

    #: a stolen/migrated job must have at least this many tiles left —
    #: yielding a nearly-done job costs a teardown + resume for no win
    MIGRATE_MIN_REMAINING_TILES = 2

    def __init__(self, queue: jq.JobQueue, log=print,
                 idle_sleep_s: float = 0.002, devices=None):
        self.q = queue
        self.log = log
        self.idle_sleep_s = float(idle_sleep_s)
        self._stop = threading.Event()
        devices = devices if devices is not None else [None]
        self.workers = [_Worker(i, d) for i, d in enumerate(devices)]
        # the placement layer only exists for a real fleet: a single
        # device keeps the PR 7 admission path bit-for-bit
        self.placer = None
        if len(self.workers) > 1:
            self.placer = fleet.Placer(
                len(self.workers), queue.max_inflight,
                queue.max_staged_bytes)
        # server-level accounting (the metrics op). Counters written
        # from worker threads live on the workers (each is touched by
        # exactly one thread) and aggregate via properties; the
        # migration counters are only written by the yielding/
        # resuming owner under no contention worth a lock
        self.t0 = time.time()
        self.migrations_done = 0
        self.migrations_aborted = 0
        # compile-cache bucket INVENTORY: which job affinity tokens
        # have warm programs, and on which device ordinals — written
        # at every job start, exported to the cross-process router
        # (serve/router.py) via the worker heartbeat so fleet-level
        # placement can follow warm caches across PROCESS boundaries
        # the way the in-process Placer follows them across devices
        self._bucket_lock = threadsan.make_lock("Scheduler._bucket_lock")
        self._buckets: dict = {}        # token -> set of ordinals

    # -- lifecycle ----------------------------------------------------------

    def stop(self) -> None:
        """Hard stop: every loop exits at its next boundary. Running
        jobs are torn down as CANCELLED (graceful drain is the queue's
        ``start_drain`` + letting the loops run dry instead)."""
        self._stop.set()

    # -- metrics ------------------------------------------------------------

    @property
    def busy_s(self) -> float:
        return sum(w.busy_s for w in self.workers)

    @property
    def tiles_done(self) -> int:
        return sum(w.tiles_done for w in self.workers)

    @property
    def jobs_done(self) -> int:
        return sum(w.jobs_done for w in self.workers)

    @property
    def last_progress_t(self) -> float:
        return max(w.last_progress_t for w in self.workers)

    def metrics(self) -> dict:
        wall = time.time() - self.t0
        out = dict(self.q.counts())
        out.update(pcache.PROGRAMS.stats())
        busy = self.busy_s
        n_dev = len(self.workers)
        by_dev = pcache.PROGRAMS.stats_by_device()
        # mesh/mpi jobs run opaquely on ONE owner thread but their
        # SPMD programs span a device mesh (fleet.note_mesh, fed from
        # cli_mpi under the job scope): list each such job under every
        # device its mesh covers, so the fleet view stops reading a
        # multi-device consensus job as single-device use
        spans = fleet.mesh_spans()
        default_name = None
        if spans:
            try:
                import jax
                default_name = str(jax.devices()[0])
            except Exception:
                pass
        devices = []
        for w in self.workers:
            snap = w.snapshot(wall)
            snap["cache"] = by_dev.get(
                w.ix, {"hits": 0, "misses": 0, "hit_rate": 0.0})
            if spans:
                wname = (default_name if w.device is None
                         else str(w.device))
                snap["mesh_jobs"] = sorted(
                    j for j, sp in spans.items()
                    if wname in sp.get("devices", ()))
            devices.append(snap)
        out.update(wall_s=wall, busy_s=busy,
                   # the fleet's busy fraction is per-device-averaged:
                   # with one device this is exactly the pre-fleet
                   # busy/wall, and a 2-device fleet at 0.5 means each
                   # device idles half the time
                   device_busy_frac=(busy / (wall * n_dev))
                   if wall else 0.0,
                   tiles_done=self.tiles_done, jobs_done=self.jobs_done,
                   running=sum(len(w.running) for w in self.workers),
                   last_progress_t=self.last_progress_t,
                   n_devices=n_dev, devices=devices,
                   migrations=self.migrations_done,
                   migrations_aborted=self.migrations_aborted,
                   unhealthy_jobs=self.unhealthy_jobs(),
                   # warm-start prior store (serve/priors.py):
                   # process-wide hit/bank/refusal accounting — the
                   # serve half of a warm-vs-cold comparison
                   priors=ppriors.PRIORS.stats())
        if spans:
            out["mesh_spans"] = spans
        return out

    def bucket_inventory(self) -> dict:
        """``{bucket_token: [device ordinals]}`` of every affinity
        token this process has compiled programs for (the worker
        heartbeat's routing signal; sticky like the Placer's map —
        eviction from the LRU program cache is rare enough that a
        stale claim costs one cold compile, never correctness)."""
        with self._bucket_lock:
            threadsan.guard(self._bucket_lock, "Scheduler._buckets")
            return {b: sorted(s) for b, s in self._buckets.items()}

    def _note_bucket(self, job, ordinal: int) -> None:
        b = fleet.job_bucket(job)
        bp = fleet.job_placement_bucket(job)
        with self._bucket_lock:
            threadsan.guard(self._bucket_lock, "Scheduler._buckets")
            if b is not None:
                self._buckets.setdefault(b, set()).add(int(ordinal))
            if bp is not None and bp != b:
                # a stream job's DEDICATED placement token is claimed
                # alongside its normalized program token, so the
                # router can route a repeat stream at the worker that
                # hosted the stream itself — not just any worker with
                # warm same-shape batch programs (ROADMAP item-1
                # remainder)
                self._buckets.setdefault(bp, set()).add(int(ordinal))

    def unhealthy_jobs(self) -> list:
        """RUNNING jobs whose convergence health is stalled/diverging
        (the /healthz degradation signal)."""
        return [{"job_id": j.job_id, "health": j.health}
                for j in self.q.jobs()
                if j.state == jq.RUNNING and j.health in ohealth.UNHEALTHY]

    # -- job start ----------------------------------------------------------

    def _job_log(self, job):
        return lambda *a: self.log(f"[{job.job_id}]", *a)

    def _is_consensus_stochastic(self, cfg) -> bool:
        return cfg.n_admm > 1 and cfg.channel_avg_per_band > 1

    def _start_job(self, w: _Worker, job) -> _RunningJob | None:
        """Open the dataset, build (or cache-hit) the job's stepper on
        THIS worker's device, wire the per-job reader thread. Raises
        propagate to the caller's fail-stop handler."""
        from sagecal_tpu import pipeline, skymodel, stochastic
        from sagecal_tpu.io import dataset as ds
        cfg = job.cfg
        tracer = None
        if job.trace_path:
            tracer = dtrace.Tracer(job.trace_path, entry="serve",
                                   job=job.job_id)
        # ONE per-job context factory for every thread role (device-
        # owner, reader, writer) — entered here so the pipeline build
        # and opaque run bodies attribute to the job AND land on the
        # owning worker's device
        ctx = job_telemetry_ctx(tracer, job.job_id, ordinal=w.ix,
                                device=w.device)
        self._note_bucket(job, w.ix)
        # opaque kinds — sim/mpi, fullbatch with tile_batch > 1 (the
        # batched driver's warm start is BATCH-granular), and
        # consensus-stochastic (its ADMM epoch chain has no tile
        # boundary the scheduler owns). Plain minibatch-stochastic
        # jobs are tile-interleaved like fullbatch since ISSUE 12.
        # Dispatched OUTSIDE ctx: the queue's terminal transitions
        # (finish -> SLO histograms) must aggregate un-labeled
        opaque = (job.kind in ("sim", "mpi")
                  or (job.kind == "fullbatch"
                      and int(getattr(cfg, "tile_batch", 1) or 1) > 1)
                  or (job.kind == "stochastic"
                      and self._is_consensus_stochastic(cfg)))
        if opaque:
            self._run_opaque(w, job, tracer, ctx)
            return None
        with ctx():
            strm = None
            if job.kind == "stochastic":
                st = stochastic.stepper(cfg, log=self._job_log(job),
                                        trace_ctx=ctx)
                ms = st.ms
            else:
                if job.kind == "stream":
                    # live ingest: the transport owns arrival; tiles
                    # land in a normal SimMS spool so staging, write-
                    # back and the solve chain below are IDENTICAL to
                    # a batch job over the same tiles (the bit-identity
                    # gate, tests/test_stream.py)
                    from sagecal_tpu import stream as tstream
                    strm, ms = tstream.open_stream(
                        cfg, log=self._job_log(job))
                else:
                    ms = ds.open_dataset(cfg.ms, cfg.ms_list,
                                         tilesz=cfg.tile_size,
                                         data_column=cfg.input_column,
                                         out_column=cfg.output_column)
                meta = ms.meta
                sky = skymodel.read_sky_cluster(
                    cfg.sky_model, cfg.cluster_file, meta["ra0"],
                    meta["dec0"], meta["freq0"], cfg.format_3)
                pipe = pipeline.FullBatchPipeline(cfg, ms, sky,
                                                  log=self._job_log(job))
                st = pipe.stepper(
                    write_residuals=True,
                    solution_path=cfg.solutions_file,
                    max_tiles=(None if strm is not None
                               else cfg.max_timeslots or None),
                    log=self._job_log(job), trace_ctx=ctx,
                    open_ended=strm is not None,
                    # divergence quarantine is the stepper's policy;
                    # the job-level "fail" circuit-breaker lives in
                    # _step_ready
                    on_diverge=("quarantine"
                                if job.on_diverge == "quarantine"
                                else "reset"))
            job.n_tiles = st.n_tiles
            # checkpoint resume (resume=true, incl. a migration's
            # re-admission): completed tiles are already on disk —
            # report them done and only produce the remainder. The
            # start tile is surfaced in the snapshot so a CROSS-PROCESS
            # router can price a recovery hop (tiles_rerun =
            # tiles-at-yield - resume_start_tile) without guessing
            job.tiles_done = st.start_tile
            job.resume_start_tile = st.start_tile
            if job.migrations and "resumed_t" not in job.migrations[-1]:
                # close the books on the migration that re-queued this
                # job: wall cost and — the zero-rerun gate's number —
                # how many already-completed tiles the resume re-runs
                mrec = job.migrations[-1]
                mrec["resumed_t"] = time.time()
                mrec["wall_s"] = round(
                    mrec["resumed_t"] - mrec["t_yield"], 6)
                mrec["resume_tile"] = st.start_tile
                mrec["tiles_rerun"] = (mrec["tile"] + 1) - st.start_tile
                mrec["dst_actual"] = w.ix
                self.migrations_done += 1
                obs.inc("serve_migrations_total")

            if strm is not None:
                # open-ended reader clocked by the transport: the
                # arrive hook blocks on wait_next (attributed as the
                # arrival_wait phase, not io bubble) and take() hands
                # over the already-arrived tile; the arrival stamp
                # rides the staged dict to the stepper, which closes
                # the arrival->durable-write latency loop
                def produce(j, _st=st, _strm=strm):
                    i, tile, t_arr = _strm.take()
                    stg = _st.stage(i, tile)
                    stg["_t_arrival"] = t_arr
                    return i, tile, stg

                pf = sched.Prefetcher(
                    produce, None, depth=st.depth,
                    name=f"job-{job.job_id}", context=ctx,
                    ready_event=w.ready, arrive=strm.wait_next)
            else:
                def produce(j, _ms=ms, _st=st):
                    i = _st.start_tile + j
                    tile = _ms.read_tile(i)
                    return i, tile, _st.stage(i, tile)

                pf = sched.Prefetcher(
                    produce, st.n_tiles - st.start_tile, depth=st.depth,
                    name=f"job-{job.job_id}", context=ctx,
                    ready_event=w.ready,
                    pace_s=float(getattr(cfg, "tile_arrival_s", 0.0)
                                 or 0.0))
        return _RunningJob(job, getattr(st, "p", None), st, pf, tracer,
                           ctx, stream=strm)

    def _run_opaque(self, w: _Worker, job, tracer, ctx) -> None:
        """Simulation / mpi / tile-batch / consensus-stochastic jobs:
        the existing whole-run drivers as one opaque, isolated unit on
        the PLACED worker's thread. An opaque job has no tile boundary
        the scheduler owns, so a cancel/deadline/migration arriving
        AFTER this point cannot take effect until the run completes
        (documented limitation, MIGRATION.md "Fleet mode"); one
        arriving before it is honoured here. Only the run BODY enters
        the per-job telemetry context; the queue's terminal
        transitions stay outside it so the SLO histograms aggregate
        un-labeled, same as the tile-interleaved path."""
        t0 = time.perf_counter()
        try:
            if job.cancel_requested:
                self.q.finish(job, jq.CANCELLED)
                return
            if job.expired():
                self.q.finish(job, jq.DEADLINE_EXCEEDED)
                return
            cfg = job.cfg
            with ctx():
                if job.kind == "mpi":
                    # the consensus interval loop, reused verbatim as
                    # a job (cli_mpi.main owns its own diag/--platform
                    # flags). NOTE: an mpi job builds its own mesh
                    # over the process's visible devices — placement
                    # gives it an owner THREAD; its device usage is
                    # fleet-wide by construction (MIGRATION.md)
                    from sagecal_tpu import cli_mpi
                    rc = cli_mpi.main(job.argv)
                    if rc:
                        raise RuntimeError(f"cli_mpi exited rc={rc}")
                elif job.kind == "stochastic":
                    from sagecal_tpu import stochastic
                    job.history = stochastic.run_minibatch_consensus(
                        cfg, log=self._job_log(job)) or []
                else:
                    from sagecal_tpu import pipeline
                    pipeline.run(cfg, log=self._job_log(job))
            self.q.finish(job, jq.DONE)
            w.jobs_done += 1
        except BaseException as e:
            self.q.finish(job, jq.FAILED, exc=e)
            self.log(f"[{job.job_id}] FAILED: {job.error}")
        finally:
            dt = time.perf_counter() - t0
            w.busy_s += dt
            w.last_progress_t = time.time()
            fleet.clear_mesh_span(job.job_id)
            obs.inc("serve_device_busy_seconds_total", dt,
                    device=str(w.ix))
            if tracer is not None:
                tracer.close()

    # -- the per-worker loop ------------------------------------------------

    def _admit(self, w: _Worker) -> bool:
        admitted = False
        while True:
            job = self.q.next_admissible(estimate_staged_bytes,
                                         worker_ix=w.ix,
                                         placer=self.placer)
            if job is None:
                return admitted
            try:
                rj = self._start_job(w, job)
            except BaseException as e:
                self.q.finish(job, jq.FAILED, exc=e)
                self.log(f"[{job.job_id}] FAILED at start: {job.error}")
                continue
            if rj is not None:          # opaque jobs already finished
                w.running.append(rj)
                ntxt = ("live stream" if job.n_tiles is None
                        else f"{job.n_tiles} tiles")
                self.log(f"[{job.job_id}] running on device {w.ix} "
                         f"({ntxt}, "
                         f"~{job.staged_bytes / 1e6:.0f} MB staged)")
            admitted = True

    def _finish(self, w: _Worker, rj, state, exc=None) -> None:
        w.running.remove(rj)
        if state == jq.DONE:
            try:
                # close raises a still-pending async-write failure:
                # the job's LAST tiles' writes must land before "done"
                rj.teardown(raise_pending=True)
            except BaseException as e:
                state, exc = jq.FAILED, e
        else:
            try:
                rj.teardown(raise_pending=False)
            except BaseException as e:
                # a failed/cancelled job's teardown (writer flush on a
                # full disk, tracer close) must not escape and kill
                # the device-owner thread — the job is already
                # terminal; record the teardown error alongside
                self.log(f"[{rj.job.job_id}] teardown error ignored: "
                         f"{type(e).__name__}: {e}")
        job = rj.job
        # accumulate (don't assign): a migrated job's earlier legs
        # already contributed their tiles at yield time
        job.history.extend(rj.stepper.history)
        self.q.finish(job, state, exc=exc)
        if state == jq.DONE:
            w.jobs_done += 1
        self.log(f"[{job.job_id}] {state}"
                 + (f": {job.error}" if exc is not None else ""))

    def _yield_for_migration(self, w: _Worker, rj,
                             reason: str = "migrate") -> None:
        """Tile-boundary half of a migration: flush this job's writes
        (the checkpoint sidecar lands LAST on the ordered writer
        queue, so the watermark names only durably-written tiles),
        tear down its threads on this device, and re-queue it pinned
        to the target as a RESUME. The ``migrate_abort`` chaos seam
        fires between the durable flush and the re-queue; recovery is
        the same re-queue with the pin dropped — the checkpoint is
        already on disk, so an aborted handoff loses zero tiles.

        ``reason="preempt"`` is the stream-priority path: the target
        is None (re-queue UNPINNED on this same device's queue, behind
        the higher-priority stream in the priority FIFO) and the
        migrations record carries the reason so a zero-rerun
        gate (tests/test_stream.py) can find the preemption legs."""
        job = rj.job
        target = job.migrate_to
        job.migrate_to = None
        t0 = time.perf_counter()
        w.running.remove(rj)
        job.history.extend(rj.stepper.history)
        try:
            rj.teardown(raise_pending=True)
        except BaseException as e:
            # the flush itself failed: fail-stop, like any write
            # failure at a boundary — a job whose outputs may not have
            # landed must not resume as if they had
            self.q.finish(job, jq.FAILED, exc=e)
            self.log(f"[{job.job_id}] FAILED during migration flush: "
                     f"{job.error}")
            return
        job.cfg = dataclasses.replace(job.cfg, resume=True)
        job.migrations.append(dict(
            src=w.ix, dst=target, tile=rj.stepper._last_tile,
            yield_s=round(time.perf_counter() - t0, 6),
            t_yield=time.time(), reason=reason))
        self.log(f"[{job.job_id}] yielded at tile "
                 f"{rj.stepper._last_tile} for {reason} "
                 f"{w.ix} -> {target}")
        try:
            faults.inject("migrate_abort", key=job.job_id)
            self.q.requeue_for_migration(job, target)
            if self.placer is not None and target is not None:
                self.placer.rehome(fleet.job_bucket(job), target)
        except BaseException as e:
            # mid-migration death: the handoff is gone but the
            # watermark is durable — recover by re-queueing UNPINNED
            # (any device may resume it from the checkpoint)
            self.migrations_aborted += 1
            obs.inc("serve_migrations_aborted_total")
            self.log(f"[{job.job_id}] migration aborted "
                     f"({type(e).__name__}: {e}); re-queueing from "
                     "the checkpoint watermark")
            self.q.requeue_for_migration(job, None)

    def _step_ready(self, w: _Worker) -> bool:
        """One pass over this worker's running jobs; True if any made
        progress.

        STICKY within the pass, BOUNDED: a job steps up to
        ``depth + 1`` consecutive tiles while they are already staged,
        then the pass moves on even if more are ready. Jobs in
        different shape buckets run different compiled programs, so
        per-tile alternation thrashes the host's code/data caches
        (measured +5% on a CPU host, 2026-08) — but UNbounded stickiness
        would let a job whose reader keeps pace with the device run to
        completion, starving its neighbours' staged tiles and
        deferring cancel/stop/drain/migration for its whole runtime.
        The bound keeps the alternation win while guaranteeing every
        running job (and every control signal) is visited at least
        once per ``depth + 1`` tiles."""
        progressed = False
        for rj in list(w.running):
            job = rj.job
            for _ in range(rj.stepper.depth + 1):
                if job.cancel_requested:
                    self._finish(w, rj, jq.CANCELLED)
                    progressed = True
                    break
                if job.expired():
                    # per-job deadline at the tile boundary: stop
                    # dispatching this job's tiles, release its
                    # admission budget, record deadline_exceeded
                    # through the same _finish accounting as cancel
                    self._finish(w, rj, jq.DEADLINE_EXCEEDED)
                    progressed = True
                    break
                if job.migrate_to is not None:
                    if job.migrate_to == w.ix:
                        job.migrate_to = None      # already home
                    else:
                        self._yield_for_migration(w, rj)
                        progressed = True
                        break
                if job.preempt_requested:
                    # stream-priority preemption: yield this batch job
                    # to its checkpoint at this tile boundary so the
                    # queued higher-priority stream admits; it resumes
                    # from the watermark (zero tiles re-run) once the
                    # priority FIFO reaches it again
                    job.preempt_requested = False
                    self._yield_for_migration(w, rj, reason="preempt")
                    progressed = True
                    break
                try:
                    with rj.ctx():
                        r = rj.pf.poll()
                        if r is sched.Prefetcher.EMPTY:
                            break
                        if r is not sched.Prefetcher.DONE:
                            _j, (ti, tile, stg), wait = r
                            # worker_crash: the cross-process chaos
                            # seam — kill THIS WHOLE PROCESS at the
                            # boundary entering tile ti (tiles < ti
                            # completed; with prefetch=0 their
                            # checkpoint is durably on disk). The
                            # router's lease eviction must recover the
                            # job onto a surviving worker as a resume
                            # with zero completed tiles re-run
                            # (tests/test_router.py). Keyed
                            # "<job_id>:<tile>" so a plan pins the
                            # exact boundary deterministically. Only a
                            # process started with --faults can ever
                            # fire it (single-tenant worker processes).
                            if faults.fires("worker_crash",
                                            key=f"{job.job_id}:{ti}"):
                                import os as _os
                                _os._exit(17)
                            degrade = False
                            if job.kind == "stream":
                                # per-tile deadline check at the last
                                # host moment before the solve: a late
                                # tile is counted, and (late_policy=
                                # degrade) skips the solve in favour
                                # of a last-good-Jones writeback so
                                # the stream never stalls behind it
                                from sagecal_tpu import pipeline as _pl
                                late, degrade = _pl.stream_tile_late(
                                    job.cfg, ti, stg,
                                    key=f"{job.job_id}:{ti}")
                                if late:
                                    job.tiles_late += 1
                                if degrade:
                                    job.tiles_degraded += 1
                            t0 = time.perf_counter()
                            # the degrade kwarg is TileStepper-only
                            # (the stochastic stepper shares the step
                            # contract but has no deadline policy)
                            kw = ({"degrade": degrade}
                                  if job.kind == "stream" else {})
                            rec = rj.stepper.step(ti, tile, stg, wait,
                                                  **kw)
                            dt = time.perf_counter() - t0
                            w.busy_s += dt
                    if r is sched.Prefetcher.DONE:
                        # outside the job label scope: the queue's SLO
                        # histograms (run / e2e latency) aggregate
                        # across jobs un-labeled
                        self._finish(w, rj, jq.DONE)
                        progressed = True
                        break
                    # live convergence health: fold this tile's final
                    # residual into the job's stall/divergence monitor
                    # and annotate the job for status/healthz readers.
                    # A QUARANTINED tile's poisoned residual never
                    # entered the chain, so it must not poison the
                    # health watermark either — it is already counted
                    # in tiles_quarantined_total and the diag trace.
                    # a DEGRADED tile never solved: its nan residual
                    # is a lateness artifact, not a convergence signal
                    if not rec.get("quarantined") \
                            and not rec.get("degraded"):
                        job.health = rj.health.update(rec["res_1"])
                        job.health_detail = rj.health.snapshot()
                    w.last_progress_t = time.time()
                    obs.inc("serve_device_busy_seconds_total", dt,
                            device=str(w.ix))
                    obs.inc("serve_tiles_done_total", job=job.job_id)
                    job.tiles_done += 1
                    job.solver_iters += int(
                        rec.get("solver_iters") or 0)
                    w.tiles_done += 1
                    progressed = True
                    if job.health == ohealth.DIVERGING \
                            and job.on_diverge == "fail":
                        # divergence circuit-breaker: the advisory
                        # health signal wired into action — this job
                        # stops at the boundary instead of burning its
                        # remaining tile budget on a diverged chain
                        self._finish(w, rj, jq.FAILED, exc=RuntimeError(
                            "divergence circuit-breaker: residual "
                            f"{rec['res_1']:.6g} against best "
                            f"{rj.health.best}"))
                        break
                except BaseException as e:
                    # fail-stop isolation: THIS job only; neighbours
                    # keep solving and the loop keeps serving
                    self._finish(w, rj, jq.FAILED, exc=e)
                    progressed = True
                    break
        return progressed

    def _maybe_preempt(self, w: _Worker) -> None:
        """Stream-priority preemption policy. Runs AFTER an admission
        pass: a stream job still QUEUED at that point is blocked on
        capacity, not placement. If its priority beats a running,
        checkpointable batch job on this worker, ask the lowest-
        priority such victim to yield at its next tile boundary
        (``preempt_requested`` -> ``_yield_for_migration(reason=
        "preempt")``). The victim re-queues UNPINNED behind the stream
        in the priority FIFO and resumes from its durable watermark —
        zero completed tiles re-run, outputs bit-identical (the same
        guarantees the migration machinery already gates). At most one
        yield is in flight fleet-wide, mirroring ``_rebalance``."""
        jobs = self.q.jobs()
        waiting = [j for j in jobs
                   if j.state == jq.QUEUED and j.kind == "stream"]
        if not waiting:
            return
        if any(j.state == jq.MIGRATING or j.migrate_to is not None
               or j.preempt_requested for j in jobs):
            return                      # a handoff is already in flight
        top = max(waiting, key=lambda j: j.priority)
        cands = [rj for rj in w.running
                 if rj.job.priority < top.priority
                 and self._migratable(rj)]
        if not cands:
            return
        victim = min(cands, key=lambda rj: rj.job.priority)
        victim.job.preempt_requested = True
        self.log(f"[{victim.job.job_id}] preempting on device {w.ix} "
                 f"for stream job {top.job_id} "
                 f"(priority {victim.job.priority} < {top.priority})")

    def _worker_loop(self, w: _Worker) -> None:
        """Drive one device until stopped, or — when the queue is
        draining — until everything accepted has finished."""
        while True:
            if self._stop.is_set():
                for rj in list(w.running):
                    self._finish(w, rj, jq.CANCELLED)
                return
            self._admit(w)
            self._maybe_preempt(w)
            progressed = self._step_ready(w)
            if not w.running:
                if self.q.draining and self.q.idle():
                    return
                if not progressed:
                    time.sleep(self.idle_sleep_s * 5)
            elif not progressed:
                # every running job is waiting on its reader thread:
                # genuine pipeline bubble at device level. Wait for a
                # producer's ready signal (with a timeout backstop),
                # then clear and re-poll — a tile staged during the
                # poll pass leaves the event set, so nothing is lost
                w.ready.wait(timeout=0.05)
                w.ready.clear()

    # -- work stealing (the fleet controller's rebalance pass) --------------

    def _migratable(self, rj) -> bool:
        st = rj.stepper
        return (rj.job.kind == "fullbatch"
                and getattr(st, "ckpt_path", None) is not None
                and (st.n_tiles - 1 - st._last_tile)
                >= self.MIGRATE_MIN_REMAINING_TILES)

    def request_migration(self, job_id: str, target: int) -> str:
        """Manual migration (the api ``migrate`` op, and the tests'
        deterministic lever): ask the owner loop to yield the job to
        ``target`` at its next tile boundary. Validates the job is a
        RUNNING migratable fullbatch job and the target exists."""
        if not 0 <= int(target) < len(self.workers):
            raise ValueError(f"no device {target} in a fleet of "
                             f"{len(self.workers)}")
        job = self.q.get(job_id)
        if job.state != jq.RUNNING:
            raise ValueError(f"job {job_id} is {job.state}, not running")
        for w in self.workers:
            for rj in list(w.running):
                if rj.job is job:
                    if not self._migratable(rj):
                        raise ValueError(
                            f"job {job_id} is not migratable (needs a "
                            "solutions-file checkpoint, a sequential "
                            "fullbatch stepper, and >= "
                            f"{self.MIGRATE_MIN_REMAINING_TILES} "
                            "remaining tiles)")
                    job.migrate_to = int(target)
                    return jq.RUNNING
        raise ValueError(f"job {job_id} is running opaquely and cannot "
                         "be migrated mid-run")

    def _rebalance(self) -> None:
        """Work stealing at tile boundaries: when a device sits idle
        with an empty queue while another runs >= 2 interleaved jobs,
        migrate one (the one with the most remaining tiles) to the
        idle device. At most one migration is in flight fleet-wide —
        rebalancing is a trickle, not a thundering herd."""
        jobs = self.q.jobs()
        if any(j.state == jq.MIGRATING or j.migrate_to is not None
               for j in jobs):
            return
        if any(j.state == jq.QUEUED for j in jobs):
            return          # placement will feed the idle device
        idle = [w for w in self.workers if not w.running]
        donors = [w for w in self.workers if len(w.running) >= 2]
        if not idle or not donors:
            return
        donor = max(donors, key=lambda w: len(w.running))
        cands = [rj for rj in list(donor.running) if self._migratable(rj)]
        if not cands:
            return
        pick = max(cands, key=lambda rj:
                   rj.stepper.n_tiles - 1 - rj.stepper._last_tile)
        pick.job.migrate_to = idle[0].ix
        self.log(f"[{pick.job.job_id}] work-steal: device {donor.ix} "
                 f"-> idle device {idle[0].ix}")

    # -- the fleet ----------------------------------------------------------

    def run(self) -> None:
        """Single device: the owner loop runs on THIS thread (the
        pre-fleet identity path — no extra threads, no jax device
        contexts). Fleet: one owner thread per device plus this
        thread as the controller (work stealing + liveness)."""
        if len(self.workers) == 1:
            self._worker_loop(self.workers[0])
        else:
            threads = [threading.Thread(
                target=self._worker_loop, args=(w,),
                name=f"device-owner-{w.ix}", daemon=True)
                for w in self.workers]
            for t in threads:
                t.start()
            while any(t.is_alive() for t in threads):
                if not self._stop.is_set():
                    self._rebalance()
                time.sleep(self.idle_sleep_s * 10)
            for t in threads:
                t.join()
        # queued (or mid-migration) jobs will never run after a hard
        # stop: leave none stranded in a non-terminal state a client
        # would poll forever
        if self._stop.is_set():
            for job in self.q.jobs():
                if job.state in (jq.QUEUED, jq.MIGRATING):
                    self.q.finish(job, jq.CANCELLED)
