"""Overlapped host execution: prefetch, ordered async writeback, rings.

The calibration host loops (pipeline.py, stochastic.py, cli_mpi.py)
execute io -> stage -> solve -> residual-fetch -> write per solve
interval. Whatever bounds the solve, it runs on the device, so
the device idles through every host-side phase of that chain. This
module holds the three primitives that hide those phases behind the
solve without changing a single computed bit:

- :class:`Prefetcher` — a bounded-depth background producer: tile t+1
  is read (and host-prepared, when the caller's ``produce`` stages too)
  on a reader thread while tile t solves. The consumer observes only
  its *wait* for each item — the pipeline bubble — which is what the
  diag "io" phase must record under overlap (the thread's own
  production time is emitted separately, tagged ``bg``).
- :class:`AsyncWriter` — one writer thread executing submitted jobs
  strictly in submission order (MS residual tiles, solution rows). An
  exception in any job fails the run at the next tile boundary with
  the original traceback — never swallowed; ``--prefetch 0`` is the
  debugging escape hatch that runs every job inline.
- :class:`DonatedRing` — an N-slot ring for staged device buffers
  whose consumer DONATES them (the per-tile residual input, PR 2's
  contract). Under overlap the next tile's buffer is staged while the
  previous one is still in flight; the ring guarantees a donated slot
  is never read again and a live slot is never overwritten.

Ordering guarantees (the embedder contract, MIGRATION.md "Overlapped
execution"): items are produced and consumed strictly in index order;
write jobs execute strictly in submission order; the warm-start solve
chain stays sequential — only data movement overlaps. Memory cost is
bounded: ``depth`` extra staged tiles plus the writer queue.

Fault tolerance (MIGRATION.md "Fault tolerance"): producer calls and
writer jobs run under ``faults.retry_transient`` — a transient
read/write failure retries with bounded exponential backoff before
the fail-stop paths above fire with the original traceback — and the
``reader_thread``/``writer_thread`` injection points let the chaos
harness kill either thread deterministically. Expired thread joins at
close() are LOUD (stderr warning + ``thread_join_timeouts_total``).

Layering: stdlib + faults + diag.trace only. Device arrays pass
through opaquely; the non-blocking device->host copy
(``copy_to_host_async``) is started by callers before submitting a
fetch job here.
"""

from __future__ import annotations

import queue
import sys
import threading
import time

from sagecal_tpu import faults
from sagecal_tpu.analysis import threadsan
from sagecal_tpu.diag import trace as dtrace
from sagecal_tpu.obs import metrics as obs


def _warn_join_timeout(role: str, name: str, timeout_s: float) -> None:
    """A ``join(timeout=...)`` that expired used to abandon the hung
    thread SILENTLY — the leak was invisible until the process ran out
    of threads. Now it is loud (stderr) and counted
    (``thread_join_timeouts_total{role=}``) so leaked threads show up
    in /metrics (MIGRATION.md "Fault tolerance")."""
    obs.inc("thread_join_timeouts_total", role=role)
    print(f"WARNING: {role} thread {name!r} did not exit within "
          f"{timeout_s:.0f}s; abandoning it (leak counted in "
          f"thread_join_timeouts_total)", file=sys.stderr)


def start_host_copy(*arrays) -> None:
    """Start the non-blocking device->host copy of jax arrays (the
    blessed async-readback API — see analysis/hostsync.py): the DMA
    overlaps with subsequent dispatches, so the writer thread's later
    ``np.asarray`` finds the bytes already on host. A backend without
    the method just pays the copy at fetch time."""
    for a in arrays:
        fn = getattr(a, "copy_to_host_async", None)
        if fn is not None:
            fn()


def wait_device(*arrays) -> None:
    """While a tracer is on, block until the arrays' execution has
    ended, under a "wait" span (diag/trace.py: the one name for a host
    thread blocked on the device), so that the read-back that follows
    is its caller's own time. Without a tracer: nothing, the read-back
    waits as it always did."""
    if dtrace.active():
        with dtrace.phase("wait"):
            for a in arrays:
                a.block_until_ready()


class EndOfStream(Exception):
    """Raised by an open-ended producer (``n=None``) — by ``fn`` or by
    the ``arrive`` hook — to signal clean end of input. NOT an error:
    the Prefetcher converts it into normal iterator/poll() completion,
    exactly as if a known ``n`` had been reached."""


class Prefetcher:
    """Produce ``fn(i)`` for ``i in range(n)`` ``depth`` items ahead.

    Iterating yields ``(i, item, wait_s)`` in index order; ``wait_s``
    is the host time spent BLOCKED on the item, EXCLUDING any
    arrival/pacing wait (attributed separately — see below).
    ``depth <= 0`` runs ``fn`` inline (the synchronous reference path)
    and ``wait_s`` is then the full production time. Producer
    exceptions re-raise in the consumer with the original traceback;
    abandoning the iterator (``close()``/GC) cancels the thread.

    ``n=None`` runs OPEN-ENDED: items are produced for i = 0, 1, ...
    until ``fn`` (or the ``arrive`` hook) raises :class:`EndOfStream`
    — the live-ingest regime where the tile count is not known at
    start (sagecal_tpu.stream).

    Arrival attribution (diag phase ``arrival_wait``): time spent
    waiting for an item to BECOME AVAILABLE — the ``pace_s`` ingest
    clock or the ``arrive`` hook's block-until-arrival — is its own
    phase, never folded into the ``read`` production phase or the
    consumer's io wait. The producer side emits it ``bg``-tagged; the
    consumer side emits the portion of its own block that overlapped
    the wait-for-arrival (so the io bubble stays an honest measure of
    read/stage cost, not of the tenant's data rate).

    The hand-over (diag/trace.py): a produced item carries the
    producer's span and the instant it was put, and the consumer's
    ``io`` records them as ``cause`` and ``queued_s``: how long the item
    had lain ready when the loop took it (0 where the loop was already
    waiting). ``poll()`` has no ``io`` span and records neither.
    """

    #: poll() sentinels (serve scheduler protocol)
    EMPTY = object()    # production still in flight — try again later
    DONE = object()     # all n items consumed

    def __init__(self, fn, n: int | None, depth: int = 1,
                 name: str = "read", context=None, ready_event=None,
                 join_timeout_s: float = 5.0, pace_s: float = 0.0,
                 arrive=None, tile0: int = 0):
        self.fn = fn
        self.n = None if n is None else int(n)
        self.depth = int(depth)
        self.name = name
        # item i is the caller's tile tile0 + i (checkpoint resume
        # starts past 0): the diag phases emitted here carry that id
        self.tile0 = int(tile0)
        self.join_timeout_s = float(join_timeout_s)
        # streaming-ingest model (--tile-arrival): item i becomes
        # producible no earlier than start + i * pace_s, as if tiles
        # arrived from a rate-limited tenant stream (the LOFAR/SKA
        # quasi-real-time regime, arXiv:1410.2101). Pure wait — the
        # produced bytes, and therefore every output, are unchanged.
        self.pace_s = max(0.0, float(pace_s))
        # true-streaming arrival hook (sagecal_tpu.stream): a callable
        # ``arrive(cancel_event) -> t_arrival`` that blocks until the
        # NEXT item is available and returns its arrival timestamp
        # (time.monotonic domain), or raises EndOfStream. Supersedes
        # pace_s when set. Must honor the cancel event so close()
        # stays prompt.
        self._arrive = arrive
        self._t0 = time.monotonic()
        # zero-arg context-manager factory entered for the producer
        # thread's lifetime (serve: routes the thread's diag emits to
        # the owning job's tracer via dtrace.scope)
        self._ctx = context
        # optional shared Event set after every successful production:
        # a poll()-driven consumer (the serve device-owner loop) waits
        # on it instead of sleeping a fixed quantum, so a staged tile
        # wakes the device immediately — the poll-path equivalent of
        # the iterator's blocking get()
        self._ready = ready_event
        self._cancel = threading.Event()
        self._q: queue.Queue = queue.Queue(maxsize=max(self.depth, 1))
        self._thread = None
        self._poll_next = 0       # inline (depth<=0) poll cursor
        self._poll_done = False
        if self.depth > 0:
            self._thread = threading.Thread(
                target=self._producer, name=f"prefetch-{name}",
                daemon=True)
            self._thread.start()

    # -- producer thread ---------------------------------------------------

    def _wait_arrival(self, i, bg):
        """Block until item ``i`` is AVAILABLE (the pace_s ingest
        clock, or the ``arrive`` transport hook). Returns the arrival
        stamp in the time.monotonic domain; raises :class:`EndOfStream`
        when the arrive hook reports end of input. The wait is its own
        diag phase, ``arrival_wait`` (+ metric) — NEVER read/io time:
        it measures the tenant's data rate, not our cost. ``bg``: the
        wait ran on the producer thread."""
        if self._arrive is None and self.pace_s <= 0.0:
            return time.monotonic()
        with dtrace.phase("arrival_wait", tile=self.tile0 + i,
                          bg=bg) as ph:
            t0 = time.monotonic()
            if self._arrive is not None:
                try:
                    t_arr = self._arrive(self._cancel)
                except EndOfStream:
                    ph.drop()       # the end of input is no wait
                    raise
            else:
                # ingest pacing: wait out the synthetic arrival time
                # (the cancel event bounds the wait so close() stays
                # prompt)
                due = self._t0 + i * self.pace_s
                while not self._cancel.is_set():
                    delay = due - time.monotonic()
                    if delay <= 0:
                        break
                    self._cancel.wait(min(delay, 0.2))
                t_arr = max(due, t0)
            waited = time.monotonic() - t0
        if waited > 0.0:
            obs.observe("tile_arrival_wait_seconds", waited)
        return t_arr

    def _call(self, i):
        """One production, with the fault-tolerance layer around it:
        the ``reader_thread`` injection point (thread-death chaos
        lever), then bounded transient retry — a flaky read/stage
        recovers here with backoff instead of killing the run; a
        non-transient or budget-exhausted failure re-raises with its
        original traceback into the existing propagation path.
        Retrying the whole ``fn(i)`` is safe by the staging contract:
        reads are pure and a producer's only durable side effect
        (``DonatedRing.stage``) is its final statement."""
        faults.inject("reader_thread", key=i)
        return faults.retry_transient(self.fn, (i,), what="read", key=i)

    def _put(self, item) -> bool:
        while not self._cancel.is_set():
            try:
                self._q.put(item, timeout=0.2)
                if self._ready is not None:
                    self._ready.set()
                return True
            except queue.Full:
                continue
        return False

    def _producer(self):
        if self._ctx is not None:
            with self._ctx():
                return self._produce_loop()
        return self._produce_loop()

    def _produce_loop(self):
        try:
            i = 0
            while self.n is None or i < self.n:
                if self._cancel.is_set():
                    return
                try:
                    t_arr = self._wait_arrival(i, bg=True)
                except EndOfStream:
                    break
                if self._cancel.is_set():
                    return
                # the background production time — NOT the consumer's
                # io wait, and NOT the arrival wait (its own phase
                # above); tagged bg so attribution stays honest
                t0 = time.perf_counter()
                with dtrace.phase(self.name, tile=self.tile0 + i,
                                  bg=True) as ph:
                    try:
                        item = self._call(i)
                    except EndOfStream:
                        ph.drop()
                        break
                t1 = time.perf_counter()
                obs.observe("prefetch_read_seconds", t1 - t0)
                # the hand-over: the consumer's "io" names this span as
                # its cause and says how long the item lay ready
                if not self._put((i, item, t_arr, ph.hand(t1))):
                    return
                i += 1
        except BaseException as e:      # surface in the consumer
            self._put((None, e, 0.0, None))
            return
        self._put((None, None, 0.0, None))

    # -- consumer ----------------------------------------------------------

    def __iter__(self):
        if self.depth <= 0:
            i = 0
            while self.n is None or i < self.n:
                try:
                    self._wait_arrival(i, bg=False)
                except EndOfStream:
                    return
                # inline production: the consumer's "io" phase is the
                # whole read + stage
                with dtrace.phase("io", tile=self.tile0 + i) as ph:
                    t0 = time.perf_counter()
                    try:
                        item = self._call(i)
                    except EndOfStream:
                        ph.drop()
                        return
                    wait = time.perf_counter() - t0
                yield i, item, wait
                i += 1
            return
        try:
            k = 0
            while True:
                # the consumer's "io" phase: its wait for the next item
                with dtrace.phase("io", tile=self.tile0 + k) as ph:
                    t0 = time.monotonic()
                    i, item, t_arr, hand = self._q.get()
                    wait = time.monotonic() - t0
                    if i is None:
                        ph.drop()       # the end marker is no tile
                        if item is not None:
                            raise item
                        return
                    ph.caused_by(hand)
                    # split the block: the part spent while the item
                    # had not yet ARRIVED is arrival wait (the tenant's
                    # data rate, counted by the producer's metric
                    # already), only the remainder is the io bubble
                    # (our read/stage cost)
                    arr = min(max(t_arr - t0, 0.0), wait)
                    if arr > 0.0:
                        ph.carve("arrival_wait", arr)
                yield i, item, wait - arr
                k += 1
        finally:
            self.close()

    def poll(self):
        """Non-blocking consumption for the serve scheduler's
        device-owner loop: returns ``(i, item, wait_s)`` when the next
        item is ready, :attr:`EMPTY` while production is still in
        flight (the scheduler moves on to another job's ready tile
        instead of blocking the device here), or :attr:`DONE` after
        item ``n - 1``. Producer exceptions re-raise at the poll that
        would have returned their item. ``depth <= 0`` produces inline
        (always "ready"; ``wait_s`` is then the production time).
        Items arrive strictly in index order, same as iteration — a
        consumer uses EITHER the iterator OR poll(), never both."""
        if self._poll_done:
            return self.DONE
        if self.depth <= 0:
            if self.n is not None and self._poll_next >= self.n:
                self._poll_done = True
                return self.DONE
            i = self._poll_next
            try:
                self._wait_arrival(i, bg=False)
                t0 = time.perf_counter()
                item = self._call(i)
            except EndOfStream:
                self._poll_done = True
                return self.DONE
            self._poll_next += 1
            return i, item, time.perf_counter() - t0
        try:
            i, item, _t_arr, _hand = self._q.get_nowait()
        except queue.Empty:
            return self.EMPTY
        if i is None:
            self._poll_done = True
            if item is not None:
                raise item
            return self.DONE
        return i, item, 0.0

    def close(self):
        self._cancel.set()
        while True:                     # unblock a full queue
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        if self._thread is not None:
            self._thread.join(timeout=self.join_timeout_s)
            if self._thread.is_alive():
                _warn_join_timeout("reader", f"prefetch-{self.name}",
                                   self.join_timeout_s)
            self._thread = None


class AsyncWriter:
    """Strictly ordered background execution of write jobs.

    ``submit(fn, *args)`` enqueues; one writer thread runs jobs in
    submission order. After a job raises, no later job executes: the
    exception re-raises (original traceback) at the caller's next
    :meth:`check` — pipelines call it at every tile boundary — or at
    :meth:`close`. ``enabled=False`` degrades to inline execution
    (identical semantics, zero threads): the ``--prefetch 0`` path.

    ``submit`` returns the seconds it spent blocked on a full queue
    (writer backpressure — bubble time for the caller's accounting).

    While a tracer is on, a queued job carries its hand-over (the
    caller's ``submit`` span, the instant of the put) and the worker
    runs it inside ``dtrace.handed``: every root span the job opens on
    the writer's thread says which ``submit`` caused it and how long it
    lay queued (``cause``, ``queued_s``: diag/trace.py), a job that
    opens none is recorded as one root ``job``. Inline, nothing is
    handed over: the job's spans are children of ``submit``.
    """

    _STOP = object()

    def __init__(self, enabled: bool = True, maxsize: int = 4,
                 context=None, join_timeout_s: float = 10.0):
        self.enabled = bool(enabled)
        self.join_timeout_s = float(join_timeout_s)
        # zero-arg context-manager factory entered for the writer
        # thread's lifetime (serve: per-job diag scope, as Prefetcher)
        self._ctx = context
        # _exc has TWO writers — the writer thread (job failure) and
        # the closing caller (flush timeout) — and first-failure-wins
        # semantics; the lock makes that race a rule instead of luck
        # (threadlint shared-state; instrumented under
        # --sanitize-threads)
        self._exc_lock = threadsan.make_lock("AsyncWriter._exc")
        self._exc = None
        self._raised = False
        self._q: queue.Queue = queue.Queue(maxsize=max(maxsize, 1))
        self._thread = None
        if self.enabled:
            self._thread = threading.Thread(
                target=self._worker, name="async-writer", daemon=True)
            self._thread.start()

    def _worker(self):
        if self._ctx is not None:
            with self._ctx():
                return self._work_loop()
        return self._work_loop()

    def _work_loop(self):
        while True:
            job = self._q.get()
            try:
                if job is self._STOP:
                    return
                with self._exc_lock:
                    failed = self._exc is not None
                if not failed:          # fail-stop: drain, don't run
                    fn, args, kwargs, hand = job
                    # every root span of the job carries the "submit"
                    # that queued it and the seconds it lay queued; a
                    # job that opens none is one root "job" (diag/
                    # trace.py; the null context without a tracer)
                    with dtrace.handed(hand):
                        # writer_thread: the thread-death injection
                        # point; then bounded transient retry —
                        # submitted jobs are idempotent (atomic MS tile
                        # writes, single-call solution/checkpoint
                        # writes), so a flaky disk recovers here instead
                        # of failing the run
                        faults.inject("writer_thread")
                        faults.retry_transient(fn, args, kwargs,
                                               what="write")
            except BaseException as e:
                with self._exc_lock:
                    if self._exc is None:   # first failure wins
                        self._exc = e
            finally:
                self._q.task_done()

    def check(self) -> None:
        """Re-raise a pending writer failure (original traceback).
        Raises once: after it fired, the run is already unwinding and
        the cleanup-path re-check must not mask the original."""
        with self._exc_lock:
            exc = self._exc
        if exc is not None and not self._raised:
            self._raised = True
            raise exc

    def submit(self, fn, *args, **kwargs) -> float:
        # the caller's "submit" span: the hand-over, back-pressure and,
        # at depth 0, the inline job
        with dtrace.phase("submit") as ph:
            return self._submit(fn, args, kwargs, ph)

    def _submit(self, fn, args, kwargs, ph) -> float:
        self.check()
        if not self.enabled:
            # inline (--prefetch 0) execution keeps the SAME transient
            # retry as the writer thread; a non-transient failure
            # raises here at the call site (the debugging contract)
            faults.retry_transient(fn, args, kwargs, what="write")
            return 0.0
        t0 = time.perf_counter()
        # the hand-over: this "submit" span and the instant of the put
        # (None from the null phase: no tracer, nothing handed)
        self._q.put((fn, args, kwargs, ph.hand(t0)))
        wait = time.perf_counter() - t0
        if wait > 1e-3:
            # writer backpressure: the producer outran the disk and
            # blocked on a full queue — bubble time for the caller and
            # an SLO signal for the serve daemon. The 1 ms floor keeps
            # the lock-free fast path (sub-µs put) out of the counter.
            obs.inc("writer_backpressure_seconds_total", wait)
        return wait

    def drain(self) -> float:
        """Block until every submitted job ran; returns the wait."""
        t0 = time.perf_counter()
        if self.enabled:
            self._q.join()
        self.check()
        return time.perf_counter() - t0

    def _join_queue(self, timeout_s: float) -> bool:
        """``Queue.join`` with a deadline (the stdlib one has none): a
        writer job hung on dead storage must not hang ``close`` — and
        the whole run's teardown — forever. Uses the queue's own
        ``all_tasks_done`` condition, the documented synchronization
        primitive behind ``join``."""
        deadline = time.perf_counter() + timeout_s
        with self._q.all_tasks_done:
            while self._q.unfinished_tasks:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    return False
                self._q.all_tasks_done.wait(remaining)
        return True

    def close(self, raise_pending: bool = True) -> None:
        if self._thread is not None:
            flushed = self._join_queue(self.join_timeout_s)
            if flushed:
                self._q.put(self._STOP)
                self._thread.join(timeout=self.join_timeout_s)
            if not flushed or self._thread.is_alive():
                _warn_join_timeout("writer", "async-writer",
                                   self.join_timeout_s)
                with self._exc_lock:
                    if self._exc is None:
                        # an abandoned flush means submitted writes
                        # may never have landed: that is a FAILURE the
                        # raise_pending path must surface — a run
                        # whose last writes hang must not report
                        # success (and must not delete its resume
                        # checkpoint). The hung writer may still fail
                        # later; whichever lands first under the lock
                        # wins, neither is silently lost
                        self._exc = TimeoutError(
                            "async-writer failed to flush within "
                            f"{self.join_timeout_s:.0f}s; submitted "
                            "writes may not have landed")
            self._thread = None
        if raise_pending:
            self.check()


class DonatedRing:
    """N-slot ring of staged device buffers consumed by DONATION.

    The per-tile residual program donates its staged visibility input
    (PR 2's buffer-donation contract). Under overlap the producer
    stages tile t+1's buffer while tile t's is still in flight, so the
    donated buffer must alternate slots instead of aliasing in-flight
    memory. The ring enforces the two safety rules statically checked
    nowhere else:

    - :meth:`take` hands the buffer out exactly once (the donating
      call); a second read of the slot RAISES instead of touching
      memory XLA may already have reclaimed;
    - :meth:`stage` refuses to overwrite a slot whose buffer was never
      consumed (an in-flight donation would alias).

    Slot choice is ``tag % depth``; sizing is the caller's prefetch
    depth + 1 (two slots for the default double-buffered loop).
    """

    def __init__(self, depth: int = 2):
        self.depth = max(int(depth), 1)
        self._bufs = [None] * self.depth
        self._live = [False] * self.depth
        self._tags = [None] * self.depth
        self._lock = threadsan.make_lock("DonatedRing._lock")

    # thread-role: prefetch, caller
    def stage(self, tag: int, buf) -> None:
        with self._lock:
            threadsan.guard(self._lock, "DonatedRing slots")
            i = tag % self.depth
            if self._live[i]:
                raise RuntimeError(
                    f"DonatedRing: staging tag {tag} would overwrite "
                    f"slot {i} (tag {self._tags[i]}) whose buffer was "
                    f"never taken — in-flight donation would alias")
            self._bufs[i] = buf
            self._live[i] = True
            self._tags[i] = tag

    def take(self, tag: int):
        """The buffer for ``tag``, exactly once (caller donates it)."""
        with self._lock:
            threadsan.guard(self._lock, "DonatedRing slots")
            i = tag % self.depth
            if not self._live[i] or self._tags[i] != tag:
                raise RuntimeError(
                    f"DonatedRing: tag {tag} not staged in slot {i} "
                    f"(slot holds tag {self._tags[i]}, "
                    f"live={self._live[i]}) — read after donation?")
            buf, self._bufs[i] = self._bufs[i], None
            self._live[i] = False
            return buf
