"""Fleet plumbing: device scopes, job shape-buckets, placement.

The serve daemon (PR 7) drove ONE device behind one owner loop. Fleet
mode generalizes that to one owner loop PER visible (or virtual)
device; this module holds the three pieces that are about *which
device*, not about stepping tiles:

- **Device scope** (:func:`device_scope` / :func:`current_ordinal`):
  a strictly thread-local (ordinal, jax device) pair entered by a
  worker thread — and by every thread a job spawns (reader/writer,
  via the job's telemetry context) — so staging, pipeline builds and
  solve dispatches land on the owning worker's device. The ordinal is
  part of every program-cache key (``pipeline._jit_cached``), making
  compile-cache hits *per-device* facts: a wrapper warmed on device 0
  is a MISS on device 1 (jax would quietly recompile per device
  underneath one shared wrapper; keying per ordinal makes that cost
  visible and lets the placer route around it). With no scope entered
  the ordinal is 0 and no jax context is touched — the single-device
  daemon and every solo CLI run are bit- and compile-count-identical
  to the pre-fleet behavior.

- **Shape buckets** (:func:`job_bucket`): a cheap content digest of
  everything that determines a job's compiled-program set (dataset
  header shapes at the effective tile bucket, sky/cluster inputs,
  solver flags, dtype policy) WITHOUT building a pipeline. Jobs with
  equal buckets share programs on the same device; the token is
  cached on the job.

- **Placement** (:class:`Placer`): routes an admissible job to a
  device. Policy, in order: a migration pin wins outright; then
  bucket AFFINITY — the device that already hosts this job's bucket
  (maximize per-device compile-cache hit rate, which the scheduler
  exports per device); then the least-loaded device with free
  capacity (fewest running jobs, then fewest claimed buckets, then
  lowest ordinal). Capacity is per-device (``max_inflight`` running
  jobs and ``max_staged_bytes`` of staged tiles EACH — the budgets
  are device-memory bounds, so a fleet scales them linearly); a job
  too large for the budget still admits on an otherwise-empty device
  (the lone-job no-starvation rule, now per device). A job is only
  blocked when NO device can take it — strict head-of-line is
  preserved fleet-wide, not per device.

Layering: stdlib + numpy + serve.cache (token); jax is imported
lazily inside :func:`device_scope` only when a real device is bound,
so the module stays importable from the queue/placement layer.
"""

from __future__ import annotations

import contextlib
import threading

import numpy as np

from sagecal_tpu.analysis import threadsan
from sagecal_tpu.serve import cache as pcache

_tls = threading.local()


def current_ordinal() -> int:
    """The entering worker's device ordinal (0 outside any scope —
    the single-device / solo-CLI identity path)."""
    return getattr(_tls, "ordinal", 0)


@contextlib.contextmanager
def device_scope(ordinal: int, device=None):
    """Bind this thread to fleet slot ``ordinal`` (and, when
    ``device`` is given, make it jax's default device for the scope).
    Strictly thread-local, like ``dtrace.scope``: threads spawned
    inside the scope do NOT inherit it — each job thread role enters
    its own via the job telemetry context."""
    prev = getattr(_tls, "ordinal", None)
    _tls.ordinal = int(ordinal)
    try:
        if device is None:
            yield
        else:
            import jax
            with jax.default_device(device):
                yield
    finally:
        if prev is None:
            del _tls.ordinal
        else:
            _tls.ordinal = prev


@contextlib.contextmanager
def job_scope(job_id: str):
    """Bind this thread to a serve job id (strictly thread-local,
    like :func:`device_scope`). Entered by ``job_telemetry_ctx``
    alongside the device scope so code deep inside a job's run body —
    cli_mpi building its consensus mesh — can attribute process-wide
    facts (the mesh span) to the owning job without threading the id
    through every layer."""
    prev = getattr(_tls, "job_id", None)
    _tls.job_id = str(job_id)
    try:
        yield
    finally:
        if prev is None:
            del _tls.job_id
        else:
            _tls.job_id = prev


def current_job() -> str | None:
    """The entering job's id, or None outside any job scope (solo CLI
    runs; the scheduler's own threads between jobs)."""
    return getattr(_tls, "job_id", None)


# -- mesh spans: which devices a mesh/mpi job's SPMD programs cover ---------

_spans_lock = threadsan.make_lock("fleet._spans_lock")
_MESH_SPANS: dict = {}     # job_id -> {"devices": [...], "axes": ...}


def note_mesh(mesh) -> None:
    """Record the device span of a consensus mesh built INSIDE a serve
    job (cli_mpi calls this right after constructing its Mesh; a
    no-op outside any job scope, so solo CLI runs never touch the
    registry). An mpi job runs opaquely on ONE owner thread, but its
    SPMD programs span every mesh device — before this record, that
    fleet-wide device use was invisible to the fleet view
    (``metrics_full`` per-device snapshots now list the job under
    every device its mesh covers)."""
    job = current_job()
    if job is None:
        return
    try:
        devs = [str(d) for d in np.asarray(mesh.devices).flat]
        span = {"devices": devs,
                "axes": list(getattr(mesh, "axis_names", ())),
                "shape": list(np.asarray(mesh.devices).shape)}
    except Exception:
        return
    with _spans_lock:
        _MESH_SPANS[job] = span


def clear_mesh_span(job_id: str) -> None:
    """Drop a finished job's span (the scheduler's opaque-run finally)."""
    with _spans_lock:
        _MESH_SPANS.pop(str(job_id), None)


def mesh_spans() -> dict:
    """Snapshot of the live {job_id: span} registry."""
    with _spans_lock:
        return {k: dict(v) for k, v in _MESH_SPANS.items()}


def fleet_devices(n: int | None):
    """The devices a fleet of size ``n`` drives: ``None``/1 -> a
    single worker bound to NO explicit device (the pre-fleet identity
    path), ``0`` -> every visible device, else the first ``n``."""
    if n is not None and int(n) < 0:
        raise ValueError(f"devices={n}: expected >= 0 "
                         "(0 = every visible device)")
    if n is None or int(n) == 1:
        return [None]
    import jax
    devs = jax.devices()
    n = int(n)
    if n == 0 or n >= len(devs):
        return list(devs)
    return list(devs[:n])


# -- job shape-buckets -------------------------------------------------------


def _job_tokens(job) -> None:
    """Compute + cache the job's affinity tokens in ONE dataset-header
    open, cheap enough for the admission path (HEADER only — never
    the data). Computed ONCE per job (success, no-config and
    unreadable-dataset outcomes all cached — the admission path runs
    under the queue lock, and re-opening a broken dataset on every
    pass would serialize the whole API behind filesystem errors).
    Three tokens land:

    - ``job.bucket`` — the compiled-PROGRAM set token. A stream job
      runs the same programs as a fullbatch job of its shape (the
      transport only changes who clocks the reader), so its kind is
      normalized to fullbatch here.
    - ``job.bucket_place`` — the PLACEMENT token. For stream jobs this
      is a DEDICATED token (real kind, same shape parts): a live
      stream's placement identity is stronger than program sharing —
      the router prefers the worker already hosting this stream
      family's programs AND priors, and only falls back to the
      normalized program token (ROADMAP item-1 remainder).
    - ``job.prior_token`` — the solution prior store key
      (serve/priors.py): sky/cluster content + station set + band +
      solver family. Routes repeat fields at the worker holding their
      warm-start priors.
    """
    if getattr(job, "_bucket_done", False):
        return
    job._bucket_done = True
    cfg = job.cfg
    if cfg is None:
        return
    try:
        from sagecal_tpu.io import dataset as ds
        ms = ds.open_dataset(cfg.ms, cfg.ms_list, tilesz=cfg.tile_size,
                             data_column=cfg.input_column,
                             out_column=cfg.output_column)
        meta = ms.meta
        tilesz = int(meta["tilesz"])
        tb = int(getattr(cfg, "tile_bucket", 0) or 0)
        if tb:
            tilesz = pcache.resolve_bucket(tilesz, tb)
        parts = (
            tilesz, int(meta["nbase"]),
            int(meta["n_stations"]), list(meta["freqs"]),
            cfg.sky_model, cfg.cluster_file,
            int(cfg.solver_mode), cfg.max_em_iter, cfg.max_iter,
            cfg.max_lbfgs, cfg.lbfgs_m, cfg.linsolv,
            getattr(cfg, "solver_inner", "chol"),
            getattr(cfg, "jones_mode", "full"),
            getattr(cfg, "dtype_policy", "f32"),
            int(cfg.beam_mode), bool(cfg.per_channel_bfgs),
            int(getattr(cfg, "tile_batch", 1) or 1),
            int(cfg.simulation))
        kind = "fullbatch" if job.kind == "stream" else job.kind
        job.bucket = pcache.token(kind, *parts)
        job.bucket_place = (pcache.token(job.kind, *parts)
                            if job.kind == "stream" else job.bucket)
        from sagecal_tpu.serve import priors as ppriors
        fam = ppriors.solver_family(cfg.solver_mode,
                                    getattr(cfg, "jones_mode", "full"))
        job.prior_token = ppriors.prior_key(
            cfg.sky_model, cfg.cluster_file,
            int(meta["n_stations"]), meta["freq0"], fam)
    except Exception:
        return


def job_bucket(job) -> str | None:
    """The compiled-program affinity token (see :func:`_job_tokens`);
    None places by load alone, and an unreadable dataset fails
    properly at job start, not at placement."""
    if getattr(job, "bucket", None) is not None:
        return job.bucket
    _job_tokens(job)
    return getattr(job, "bucket", None)


def job_placement_bucket(job) -> str | None:
    """The placement token: the program token for batch jobs, a
    DEDICATED same-shape token for stream jobs (see
    :func:`_job_tokens`)."""
    if getattr(job, "bucket_place", None) is not None:
        return job.bucket_place
    _job_tokens(job)
    return getattr(job, "bucket_place", None)


def job_prior_token(job) -> str | None:
    """The solution prior store key of this job's field/band/solver
    family (serve/priors.py; header-only — see :func:`_job_tokens`)."""
    if getattr(job, "prior_token", None) is not None:
        return job.prior_token
    _job_tokens(job)
    return getattr(job, "prior_token", None)


# -- placement ---------------------------------------------------------------


class Placer:
    """Routes admissible jobs to device ordinals (see module doc).

    ``state_fn()`` must return the live per-device view — a list of
    dicts ``{"running": int, "staged_bytes": int}`` indexed by
    ordinal — computed by the caller under ITS lock (the queue holds
    its lock across admission, so the snapshot and the decision are
    atomic). The bucket->device affinity map is sticky: it remembers
    where a bucket's programs were compiled even after its jobs
    finish, because the warm compile cache on that device is exactly
    what affinity exists to reuse.
    """

    def __init__(self, n_devices: int, max_inflight: int,
                 max_staged_bytes: int):
        self.n = max(1, int(n_devices))
        self.max_inflight = max(1, int(max_inflight))
        self.max_staged_bytes = int(max_staged_bytes)
        # placement decisions run under the queue lock, but rehome()
        # is called from a yielding owner thread outside it — the
        # affinity map carries its own lock so a mid-iteration insert
        # can never corrupt a concurrent place()
        self._lock = threadsan.make_lock("Placer._lock")
        self._affinity: dict[str, int] = {}     # bucket -> ordinal

    def _fits(self, st: dict, est_bytes: int) -> bool:
        if st["running"] >= self.max_inflight:
            return False
        if st["running"] == 0:
            return True                 # lone job always admits
        return st["staged_bytes"] + est_bytes <= self.max_staged_bytes

    def place(self, job, state) -> int | None:
        """Target ordinal for ``job`` given per-device ``state``, or
        None when no device has capacity (head-of-line block). Does
        NOT claim the slot — the caller marks the job running and then
        calls :meth:`assign`."""
        pin = getattr(job, "pinned_device", None)
        if pin is not None:
            # migration pin: the target was chosen at yield time; its
            # capacity was checked then and its slot is the one the
            # job just released, so only the hard inflight cap applies
            return int(pin) if state[int(pin)]["running"] \
                < self.max_inflight else None
        est = int(getattr(job, "est_bytes", None) or 0)
        fits = [i for i in range(self.n) if self._fits(state[i], est)]
        if not fits:
            return None
        bucket = job_bucket(job)
        with self._lock:
            if bucket is not None:
                home = self._affinity.get(bucket)
                if home is not None and home in fits:
                    return home
            owned = {}      # ordinal -> buckets currently claimed
            for b, i in self._affinity.items():
                owned[i] = owned.get(i, 0) + 1
        fits.sort(key=lambda i: (state[i]["running"],
                                 owned.get(i, 0), i))
        return fits[0]

    def assign(self, job, ordinal: int) -> None:
        """Record the placement (sticky bucket affinity)."""
        bucket = job_bucket(job)
        with self._lock:
            if bucket is not None and bucket not in self._affinity:
                self._affinity[bucket] = int(ordinal)

    def rehome(self, bucket: str, ordinal: int) -> None:
        """Move a bucket's affinity (migration moved its programs)."""
        with self._lock:
            if bucket is not None:
                self._affinity[bucket] = int(ordinal)

    def affinity(self) -> dict:
        with self._lock:
            return dict(self._affinity)
