"""Structured JSONL tracing: phase timers + convergence records.

Zero-dependency by design (stdlib only — no jax, no numpy): the solver
and pipeline layers import this module unconditionally, and an import
that pulled in jax from inside ``sagecal_tpu.solvers.sage`` would be a
layering inversion. Emitters call :func:`emit`/:func:`phase` freely;
until :func:`enable` installs a live :class:`Tracer` both are no-ops
costing one attribute load and one ``is None`` test.

File format: one JSON object per line. Every record carries

- ``t``   — unix epoch seconds (float) at emit time,
- ``tm``  — ``time.perf_counter()`` at emit time: the monotonic clock
  that ``dur_s`` is timed on (and a benchmark's window runs on), so a
  record can be placed inside an interval taken around it. A ``phase``
  record's ``tm`` is the span's END; its start is ``tm - dur_s``,
- ``ev``  — the event name (str),

plus event-specific fields. The emitting sites keep a small stable
vocabulary so downstream tooling can rely on it:

===============  ============================================================
event            meaning / required extra fields
===============  ============================================================
``run_start``    first record; run metadata (argv, entry point)
``phase``        a timed host phase (a span): ``name``, ``dur_s``, ``id``
                 (unique in the process, taken as the span is entered),
                 ``parent`` (the ``id`` of the span open on the SAME
                 thread when this one was entered, else null),
                 ``thread`` (the thread's name); optional ``tile`` (what
                 the spans of one tile share: a span without one takes
                 its parent's), ``prog`` (a ``dispatch``'s program),
                 ``sub`` (a ``put``'s subband, cli_mpi),
                 ``cause`` (the ``id`` of the span ON ANOTHER THREAD
                 that handed this span its work or produced what it
                 waited for: a writer job's root names the ``submit``
                 that queued it, a consumer's ``io`` the producer's
                 ``read``) and ``queued_s`` (the seconds between that
                 hand-over and this span's entry: how long the job lay
                 in the writer's queue, how long the staged tile had
                 lain ready when the loop took it, 0 where the loop
                 was already waiting).  Both or neither; absent on the
                 inline ``--prefetch 0`` path, where nothing is handed
                 over.  How they reach a span: a thread that runs
                 handed-over work does so inside :func:`handed`
                 (``sched.AsyncWriter``'s worker), and every ROOT span
                 entered there takes them; a span that learns its
                 cause inside its body (``io``, out of the queue's
                 ``get``) is told by :meth:`_Phase.caused_by`, as
                 ``set_tile`` tells it its tile.  A hand-over is
                 ``(id, instant, tile)``, made by :meth:`_Phase.hand`
                 (``None`` from the null phase: no tracer, nothing
                 handed).
                 ``bg`` (True when the phase ran on
                 a background prefetch/writeback thread — under
                 overlapped execution the "io" phase records the
                 host's WAIT for the next tile, the bubble, while the
                 thread's own read/stage time carries ``bg``).
                 ``arrival_wait`` is time spent waiting for a tile to
                 ARRIVE (ingest pacing or a live stream transport,
                 sched.Prefetcher) — the tenant's data rate, NEVER
                 counted as io/bubble; producer-side waits carry
                 ``bg``, the consumer's overlapping block does not
``em_sweep``     one SAGE EM sweep (solvers/sage.py host driver):
                 ``sweep``, ``wall_s``, ``fused``, ``err_reduction``,
                 ``solver_iters`` (cumulative executed inner trips)
``tile``         one solve interval's convergence summary (pipeline.py /
                 cli_mpi.py): ``tile``, ``res_0``, ``res_1`` (a
                 simulated tile, ``run_simulation``, solves nothing and
                 carries ``tile``, the overlap pair, ``mode``,
                 ``clusters_in_model`` and the ``coh_path`` ..
                 ``shapelet_slots`` fields below); optional
                 ``mean_nu``, ``solver_iters``, ``cg_iters`` (inner CG
                 trips under them: LM's PCG, RTR's truncated-CG
                 bodies), ``row_passes`` (RTR's evaluations of the
                 row model: solvers/rtr.py), ``lbfgs_iters``,
                 ``refine_passes`` (passes through the model the joint
                 refine made: solvers/lbfgs.py), ``refine_rows`` (the
                 row layout those passes worked on, "periodic" or
                 "flat": solvers/sage.py), ``sweep_rows`` (the row
                 layout the sweeps carried their running residual and
                 evaluated the cluster models on, "periodic" or
                 "flat": sage.sweep_rows), ``assemble_rows`` (the
                 row layout the sweeps' Gauss-Newton matrix was
                 assembled from, "periodic" or "generic":
                 sage.assemble_rows), ``kmax``, ``chunk_slots`` and
                 ``chunk_slots_live`` (the hybrid time chunks of the
                 cluster file: the most a cluster has, the slots
                 ``M * kmax`` that the Jones ``[M, kmax, N, 2, 2]``
                 carries and every cluster's solve factorises, and
                 the ``sum(nchunk)`` of them that are solutions:
                 pipeline.py), ``coh_path`` ("xla" or "pallas": what
                 the run's ``Coherency path:`` line says),
                 ``beam_mode`` (``-B``) and, with a beam,
                 ``beam_elements`` (``Emax``, the element slots a
                 station carries) and ``beam_sources`` (the live
                 sources whose gains the tables hold),
                 ``sources_point``, ``sources_gaussian``,
                 ``sources_disk``, ``sources_ring``,
                 ``sources_shapelet`` (the model's live sources by
                 kind), ``shapelet_n0max`` (the largest shapelet
                 order, 0 without one) and ``shapelet_slots`` (the
                 source slots for which the compiled source sum
                 evaluates the shapelet basis: ``M x S_sh``, the
                 model's compact pack of its shapelet sources, ``S_sh``
                 the most any cluster holds, 0 without one:
                 pipeline.source_kinds),
                 ``plan`` and
                 ``solve_dispatches`` (what sagefit_host's last sweep
                 executed, "promoted", "fused" or "per_cluster", and
                 the device executions the solve issued), ``minutes``,
                 ``primal``, and the
                 overlap accounting pair ``bubble_s`` (host seconds
                 blocked on data movement for this tile: io wait +
                 write wait/backpressure) / ``overlap`` (the prefetch
                 depth; 0 = synchronous reference loop)
``admm_iter``    one consensus-ADMM iteration: ``iter``, ``r1_mean``,
                 ``dual``; optional ``interval``, ``rho_mean``,
                 ``primal``, ``deferred`` (True when the record was
                 emitted in one batched fetch AFTER the host loop —
                 the overlap-preserving path: no per-iteration sync)
``minibatch``    one stochastic minibatch solve: ``epoch``, ``minibatch``,
                 ``res_0``, ``res_1``; optional ``admm``, ``iters``
``stage_bytes``  host->device staging accounting: ``bytes``, ``what``;
                 optional ``tile``
``compile``      one XLA backend compile (or persistent-cache read) that
                 ran while a tracer was active (diag/guard.py's duration
                 listener): ``fun`` (the jitted function's name),
                 ``dur_s``; ``tm`` is the compile's end
``admm_stale``   bounded-staleness consensus (consensus/admm.py): the
                 subbands that sat an iteration out: ``interval``,
                 ``iter``, ``skipped``, ``dead``. Its reader is the
                 operator of a ``--staleness`` run (MIGRATION.md
                 "Bounded staleness"); no program reads it
``run_end``      last record; ``wall_s`` for the whole run
===============  ============================================================

Span names (the three tile loops: ``pipeline.TileStepper.step``,
``cli_mpi.ConsensusStepper.step``, ``FullBatchPipeline.run_simulation``).
The loop's thread is at every instant of a tile's cycle inside one of two
ROOT spans: ``io`` (the consumer's wait for the next tile) or ``step``.
Under ``step``: ``carry`` (the warm-start Jones made ready and copied to
the device), ``solve`` (the whole solve, to its read-backs), ``residual``
(the residual program's ``carry`` and ``dispatch``), ``fetch``
(read-backs), ``submit`` (``sched.AsyncWriter.submit``: a job handed to
the ordered writer, back-pressure included), ``record`` (info read-backs,
history, ``tile`` and ``admm_iter`` records, log lines), ``primal`` (the
consensus loop's ``B Z`` and norms on the host), and the simulation
loop's ``stage``, ``predict`` (a tile's program dispatched), ``fetch``
(the wait for the program and the copy: under ``--prefetch`` that of the
tile BEFORE, whose program ran while this one was queued behind it; the
last tile's is under a third root, ``drain``, once a run, after the
dataset has ended) and ``write`` (``stage`` and ``write`` are the
reader's and the writer's, ``bg``, unless ``--prefetch 0``).
``dispatch`` is one
device execution enqueued (``solvers/sage.py:_call`` makes one per
execution, ``prog=`` its label; the mesh runner and the residual
programs have theirs).  ``wait`` is the ONE name under which a host
thread is blocked on the device's execution, on the loop's thread (under
``solve``, ``fetch``) and on a background one (under ``stage``,
``write``; ``sched.wait_device``): a span's seconds less its ``wait``
children are the host's own.  Background threads keep ``read``,
``stage``, ``write``, ``arrival_wait`` with ``bg``.  Under ``stage``
with ``-B``: ``beam`` (``pipeline._tile_beam``: the tile's ``gmst`` track
made on the host and copied; the beam's other leaves were staged at
construction).  A span's self time
is its ``dur_s`` less its children's, by ``id``/``parent``.

Inside the reader's and the writer's jobs (the threads that set the
pace where the device does not).  The writer's thread runs every job
under a root that carries ``cause`` and ``queued_s``: ``write`` (a
residual or simulated tile), ``solutions`` (a solutions file's rows: a
root where that is the whole job, a child where another job writes
them), ``put`` (a tile handed to the dataset with nothing to convert),
``job`` (anything else: made by :func:`handed` for a job that opened no
root of its own).  Under ``write``: ``wait`` (as before), ``convert``
(the read-back's pairs made complex, the cast to the dataset's
complex128; cli_mpi: all subbands at once) and ``put`` (the dataset's
``write_tile``; cli_mpi: one a subband, ``sub=``).  Under ``put``, inside
``io.dataset.SimMS.write_tile``: ``keep`` (the ``np.load`` of the file
that is there and the copy of its other columns), ``savez``,
``replace``; inside ``io.casams.CasaMS.write_tile``: ``keep`` (the
``getcol`` of what the rows hold) and ``putcol``.  On the reader's
thread, under ``read``: ``load`` (``SimMS.read_tile``: the ``np.load``
and the columns read out of it) and ``stage``, under which ``pack`` (host
arithmetic: ``solve_input``, padding, the uv cut and the weights where
they are numpy, ``c2r``), ``copy`` (the ONE name under which a host
thread hands arrays to the device: the ``jnp.asarray`` / ``device_put``
group; the runtime may hold the thread there behind a running program),
``dispatch`` (``prog="weights"``: the uv cut and the weights where they
are eager device operations, ``TileStepper``) and ``beam``.  On the
loop's thread ``solve`` holds ``dispatch`` with ``prog="coh"`` (the
coherencies' program, ``pipeline._build_solver``) beside ``sage._call``'s,
and ``consensus/admm._emit_deferred`` blocks on its batched fetch under
``wait``.

Records are kept in memory and written by :meth:`Tracer.close` (so
:func:`disable`), whenever :data:`FLUSH_AT` of them are held, and by an
``atexit`` hook for a run that ends without closing: an emit on a hot
path appends to a list and returns.

Profiler annotations: :func:`phase` wraps its body in an annotation
``sagecal/<name>`` (with ``tile=`` where the site has one) on the
profiler's clock, so the same span can be read from the JSONL and found
in a ``jax.profiler`` trace. This module stays importable without jax:
the annotation class is handed in once by ``utils.setup_backend``
(:func:`set_annotator`). Annotations are made while a tracer is active
or while ``cli --profile`` has a trace running (:func:`set_profiling`);
otherwise :func:`phase` returns the shared null context.

Values must be JSON-serializable scalars/strings (callers convert device
arrays with ``float(...)``/``int(...)`` *after* checking :func:`active`,
so the disabled path never forces a device sync).
"""

from __future__ import annotations

import atexit
import itertools
import json
import threading
import time

from sagecal_tpu.analysis import threadsan

# record fields guaranteed on every line (the schema tests key on this)
REQUIRED_FIELDS = ("t", "ev")

_TRACER = None          # module-level singleton; None = disabled

#: records a tracer holds before it writes them out (a constant, not an
#: option: an operator's ``--diag`` over hours stays bounded, a
#: benchmark's traced window never reaches it)
FLUSH_AT = 1 << 16

# span ids (``next`` on a count is atomic) and, per thread, its name and
# the stack of the spans open on it: a span's parent is the one entered
# before it on its OWN thread, never the spawning thread's
_IDS = itertools.count(1)
_OPEN = threading.local()

# profiler annotations: the class (``jax.profiler.TraceAnnotation``) is
# handed in by utils.setup_backend so that this module never imports
# jax; _PROFILING is True while ``cli --profile`` has a trace running
# with no tracer installed
_ANNOTATOR = None
_PROFILING = False
ANNOTATION_PREFIX = "sagecal/"


def set_annotator(cls) -> None:
    """``cls(name, **kwargs)`` must be a context manager that marks a
    span on the profiler's clock (``jax.profiler.TraceAnnotation``)."""
    global _ANNOTATOR
    _ANNOTATOR = cls


def set_profiling(on: bool) -> None:
    """While on, :func:`phase` annotates even with no tracer active."""
    global _PROFILING
    _PROFILING = bool(on)

# thread-scoped tracer override (serve: per-job --diag routing). The
# server runs many jobs through one process; each job's records go to
# its OWN trace file. A scope installed on a thread (the device-owner
# thread around a job's step, the job's reader thread, its writer-
# thread jobs) routes that thread's emits to the job tracer; threads
# without a scope keep the process tracer. Stored as a stack so scopes
# nest (a server-level tracer can wrap a job-level one).
#
# CONTRACT (metrics-era, tests/test_diag.py pins it): scope stacks are
# STRICTLY thread-local. Entering a scope on thread A changes nothing
# about thread B's routing — not even when B was spawned by A while
# the scope was live (threading.local starts empty per thread; a new
# thread that must attribute to a job enters the job's own scope via
# the sched context= / trace_ctx= factories, see
# serve.scheduler.job_telemetry_ctx). obs.metrics.scope_labels keeps
# the identical stack semantics, so a metric emitted inside a scoped
# thread attributes to the owning job exactly when a trace record
# routed there would.
_SCOPED = threading.local()


def _current():
    st = getattr(_SCOPED, "stack", None)
    return st[-1] if st else _TRACER


class _Scope:
    __slots__ = ("_t",)

    def __init__(self, tracer):
        self._t = tracer

    def __enter__(self):
        st = getattr(_SCOPED, "stack", None)
        if st is None:
            st = _SCOPED.stack = []
        st.append(self._t)
        return self._t

    def __exit__(self, *exc):
        _SCOPED.stack.pop()
        return False


def scope(tracer):
    """Route THIS thread's emits to ``tracer`` while the context is
    live (``None`` silences them). Per-job trace routing for the serve
    scheduler; nests, and never touches other threads."""
    return _Scope(tracer)


class Tracer:
    """JSONL event writer with monotonic phase timers: records are kept
    in memory and appended to ``path`` at :meth:`close`, whenever
    :data:`FLUSH_AT` of them are held, and at interpreter exit."""

    def __init__(self, path, **run_meta):
        self.path = path
        self._f = open(path, "a")
        # overlapped execution (sagecal_tpu.sched) emits from the
        # prefetch and writer threads concurrently with the main loop:
        # one lock keeps the buffer whole across a flush
        self._lock = threadsan.make_lock("Tracer._lock")
        self._buf = []
        self._t0 = time.time()
        atexit.register(self.flush)     # what a crashed run holds
        self.emit("run_start", **run_meta)

    def emit(self, ev: str, **fields) -> None:
        rec = {"t": time.time(), "tm": time.perf_counter(), "ev": ev}
        rec.update(fields)          # a phase passes its own end as tm
        with self._lock:
            self._buf.append(rec)
            full = len(self._buf) >= FLUSH_AT
        if full:
            self.flush()

    def flush(self) -> None:
        """Serialise and write what is held (no-op once closed)."""
        with self._lock:
            if self._f.closed:
                return
            buf, self._buf = self._buf, []
            for rec in buf:
                try:
                    line = json.dumps(rec)
                except (TypeError, ValueError):
                    # a non-serializable field must not kill a
                    # calibration run; keep the record with offenders
                    # stringified
                    line = json.dumps({
                        k: (v if isinstance(v, (int, float, str, bool,
                                                type(None))) else repr(v))
                        for k, v in rec.items()})
                self._f.write(line + "\n")
            self._f.flush()

    def close(self) -> None:
        if self._f.closed:
            return
        self.emit("run_end", wall_s=time.time() - self._t0)
        self.flush()
        self._f.close()
        atexit.unregister(self.flush)


def _open_stack() -> list:
    """This thread's stack of open spans (made, with the thread's name,
    at its first span)."""
    stack = getattr(_OPEN, "stack", None)
    if stack is None:
        stack = _OPEN.stack = []
        _OPEN.thread = threading.current_thread().name
        _OPEN.hand = None
    return stack


class _Phase:
    """Context manager timing one host phase: a profiler annotation
    ``sagecal/<name>`` around the body (when an annotator is set) and
    one ``phase`` record on exit (when a tracer is live), with the
    span's ``id``, its ``parent`` on this thread and the ``thread``."""

    __slots__ = ("_tr", "_name", "_fields", "_t0", "_ann", "_less",
                 "_id", "_parent", "_hand", "dur_s")

    def __init__(self, tracer, name, fields):
        self._tr = tracer
        self._name = name
        self._fields = fields
        self._less = 0.0
        self._hand = None       # (cause, instant, tile) handed to it
        self.dur_s = 0.0        # the span's seconds, once it has ended

    def hand(self, at: float):
        """What a thread that takes work from this span is given: this
        span's id, the instant ``at`` (``time.perf_counter()``) of the
        hand-over, and its tile."""
        return self._id, at, self._fields.get("tile")

    def caused_by(self, hand) -> None:
        """The span learned inside its body which span of another
        thread produced what it waited for (``hand``: that span's
        :meth:`hand`, or None)."""
        self._hand = hand

    def drop(self) -> None:
        """Emit no record for this span (the wait turned out to be for
        the end of input, not for a tile)."""
        self._tr = None

    def set_tile(self, tile) -> None:
        """The span learned its tile inside its body (the tile id comes
        out of the ``next()`` an ``io`` span waits in)."""
        self._fields["tile"] = tile

    def carve(self, name: str, dur_s: float) -> None:
        """``dur_s`` of this span's seconds belong to phase ``name``
        (a consumer's block that overlapped the producer's wait for a
        tile to arrive): they are emitted as that phase, beside this
        span (its parent, its fields), and taken off this span's own
        ``dur_s``."""
        self._less += dur_s
        if self._tr is not None:
            self._tr.emit("phase", name=name, dur_s=dur_s, id=next(_IDS),
                          parent=self._parent, thread=_OPEN.thread,
                          **self._fields)

    def __enter__(self):
        stack = _open_stack()
        f = self._fields
        self._id = next(_IDS)
        self._parent = None
        if stack:
            above = stack[-1]
            self._parent = above._id
            if "tile" not in f and "tile" in above._fields:
                f["tile"] = above._fields["tile"]
        else:
            # a root on a thread that runs handed-over work
            self._hand = _OPEN.hand
            if self._hand is not None:
                _OPEN.taken = True
                if "tile" not in f and self._hand[2] is not None:
                    f["tile"] = self._hand[2]
        stack.append(self)
        self._ann = None
        if _ANNOTATOR is not None:
            self._ann = _ANNOTATOR(ANNOTATION_PREFIX + self._name,
                                   **({"tile": f["tile"]} if "tile" in f
                                      else {}))
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _OPEN.stack.pop()
        self.dur_s = t1 - self._t0 - self._less
        if self._tr is not None:
            if self._hand is not None:
                self._fields["cause"] = self._hand[0]
                self._fields["queued_s"] = max(
                    0.0, self._t0 - self._hand[1])
            self._tr.emit("phase", name=self._name, dur_s=self.dur_s,
                          tm=t1, id=self._id, parent=self._parent,
                          thread=_OPEN.thread, **self._fields)
        return False


class _NullPhase:
    """Shared do-nothing context manager for the disabled path."""

    __slots__ = ()
    dur_s = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def drop(self) -> None:
        pass

    def set_tile(self, tile) -> None:
        pass

    def carve(self, name, dur_s) -> None:
        pass

    def hand(self, at):
        return None

    def caused_by(self, hand) -> None:
        pass


_NULL_PHASE = _NullPhase()


class _Handed:
    """Context manager around handed-over work on the thread that runs
    it: every root span entered inside takes the hand-over's ``cause``
    and ``queued_s``, and work that entered none is recorded as one
    root ``job`` of its own (a record alone: no annotation)."""

    __slots__ = ("_hand", "_t0")

    def __init__(self, hand):
        self._hand = hand

    def __enter__(self):
        _open_stack()
        _OPEN.hand, _OPEN.taken = self._hand, False
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        _OPEN.hand = None
        tr = _current()
        if not _OPEN.taken and tr is not None:
            cause, at, tile = self._hand
            tr.emit("phase", name="job", dur_s=t1 - self._t0, tm=t1,
                    id=next(_IDS), parent=None, thread=_OPEN.thread,
                    cause=cause, queued_s=max(0.0, self._t0 - at),
                    **({} if tile is None else {"tile": tile}))
        return False


def enable(path, **run_meta) -> Tracer:
    """Open ``path`` for appending and make it the process tracer."""
    global _TRACER
    if _TRACER is not None:
        _TRACER.close()
    _TRACER = Tracer(path, **run_meta)
    return _TRACER


def disable() -> None:
    """Close and uninstall the process tracer (no-op when disabled)."""
    global _TRACER
    if _TRACER is not None:
        _TRACER.close()
        _TRACER = None


def get() -> Tracer | None:
    return _current()


def active() -> bool:
    """True when a tracer is installed (process-wide or scoped onto
    this thread). Emitting sites whose field conversion is itself
    costly (device->host syncs) gate on this."""
    return _current() is not None


def emit(ev: str, **fields) -> None:
    """Module-level emit: one line when enabled, no-op otherwise."""
    t = _current()
    if t is not None:
        t.emit(ev, **fields)


def phase(name: str, **fields):
    """THE way to time a host phase: ``with dtrace.phase("solve",
    tile=ti): ...``. A shared null context when no tracer is active
    and no ``--profile`` trace is running."""
    t = _current()
    if t is None and not _PROFILING:
        return _NULL_PHASE
    return _Phase(t, name, fields)


def handed(hand):
    """Around work another thread handed over (``hand``: the handing
    span's :meth:`_Phase.hand`): the roots entered inside carry its
    ``cause`` and ``queued_s``. The shared null context for ``None``,
    what the null phase hands: no tracer was active at the hand-over."""
    if hand is None:
        return _NULL_PHASE
    return _Handed(hand)


def overlap_stats(recs: list) -> dict:
    """Pipeline-bubble accounting over one run's records.

    Classifies host wall-clock into device-driving time (solve +
    residual dispatch phases) vs bubble (host blocked on data
    movement): per-tile ``bubble_s`` when the tile records carry the
    overlap fields, else the synchronous attribution io + write +
    residual phase sums. Background (``bg``) phase records are the
    prefetch/writeback threads' own time and never count as bubble.

    Arrival waits (the ``arrival_wait`` phase — ingest pacing / live
    stream transports) are the TENANT'S data rate, not a pipeline
    bubble: they are summed separately into ``arrival_wait_s`` and
    excluded from both busy and bubble.

    Returns ``{"tiles", "wall_s", "busy_s", "bubble_s",
    "arrival_wait_s", "busy_frac", "bubble_frac", "overlap"}`` —
    fractions are of ``wall_s`` (run_end when present, else the
    record time span).
    """
    tiles = [r for r in recs if r.get("ev") == "tile"]
    phases = [r for r in recs if r.get("ev") == "phase"
              and not r.get("bg")]
    wall = None
    for r in recs:
        if r.get("ev") == "run_end" and "wall_s" in r:
            wall = float(r["wall_s"])
    if wall is None and recs:
        wall = float(recs[-1]["t"]) - float(recs[0]["t"])
    busy = sum(r.get("dur_s", 0.0) for r in phases
               if r.get("name") in ("solve", "residual"))
    overlap = max([int(r.get("overlap", 0)) for r in tiles], default=0)
    if any("bubble_s" in r for r in tiles):
        bubble = sum(float(r.get("bubble_s", 0.0)) for r in tiles)
    else:
        # sync attribution: io (inline read) + write (blocking fetch +
        # disk) are the host's data-movement stalls
        bubble = sum(r.get("dur_s", 0.0) for r in phases
                     if r.get("name") in ("io", "write"))
    arrival = sum(r.get("dur_s", 0.0) for r in phases
                  if r.get("name") == "arrival_wait")
    wall = wall or 0.0
    return {
        "tiles": len(tiles), "wall_s": wall, "busy_s": busy,
        "bubble_s": bubble, "arrival_wait_s": arrival,
        "overlap": overlap,
        "busy_frac": (busy / wall) if wall else 0.0,
        "bubble_frac": (bubble / wall) if wall else 0.0,
    }


def read(path) -> list:
    """Parse a trace file back into a list of records (for tests and
    post-run analysis). Raises ValueError on a malformed line or a
    record missing the required fields."""
    out = []
    with open(path) as f:
        for i, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                raise ValueError(f"{path}:{i + 1}: malformed JSONL: {e}")
            for k in REQUIRED_FIELDS:
                if k not in rec:
                    raise ValueError(
                        f"{path}:{i + 1}: record missing '{k}': {rec}")
            out.append(rec)
    return out
