"""Live tile ingest: streaming sources behind the Prefetcher seam.

Everything before this package assumed the MeasurementSet was on disk
before the job started. The "fast gain calibration" regime the source
paper targets (arXiv:1410.2101, sec. "quasi-real-time") is ONLINE:
tiles arrive on the wire, and the number that matters is the latency
from a tile's ARRIVAL to its residual DURABLY WRITTEN, per solution
interval — not job makespan. This package is the arrival side of that
contract; the serve scheduler owns the deadline/lateness policy and
the batch-preemption policy (serve/scheduler.py, MIGRATION.md
"Streaming mode").

A :class:`TileStream` delivers ``(index, VisTile, t_arrival)`` events
in index order, with gaps where the transport dropped a tile. It
plugs into :class:`sagecal_tpu.sched.Prefetcher` through two calls
that split WAITING from READING so latency attribution stays honest:

- :meth:`TileStream.wait_next` blocks until the next event is
  available and returns its arrival timestamp (``time.monotonic``
  domain) — this is the Prefetcher's ``arrive`` hook, attributed as
  the ``arrival_wait`` diag phase, never as io;
- :meth:`TileStream.take` returns that event WITHOUT blocking (and is
  idempotent until the next ``wait_next``, so the Prefetcher's
  transient-retry layer can safely re-run the producing ``fn``).

Three transports:

- :class:`GeneratorStream` — seeded in-process generator over an
  on-disk SimMS, releasing tile i at ``start + i * interval_s`` (the
  tests' transport: deterministic arrivals, and bit-identity
  against the same MS run as a batch job is trivially checkable);
- :class:`~sagecal_tpu.stream.transport.TailStream` — follow a spool
  directory that a feeder writes SimMS tile files into (atomic
  write-then-rename makes visibility the arrival event);
- :class:`~sagecal_tpu.stream.transport.SocketStream` —
  length-prefixed npz tile frames over TCP; arriving tiles spool into
  the local MS directory, so residual write-back and the bit-identity
  audit work exactly as in batch mode.

In every transport the arriving tile bytes end up in / come from a
normal SimMS directory, so ``write_tile`` (residual write-back), the
program cache bucket, and checkpoint-free open-ended stepping need no
new storage format. Outputs are BIT-IDENTICAL to running the same
tiles as a batch job unless a late tile is explicitly degraded
(``late_policy="degrade"`` + a missed ``tile_deadline_s``).
"""

from __future__ import annotations

import time

from sagecal_tpu import faults
from sagecal_tpu.obs import metrics as obs
from sagecal_tpu.sched import EndOfStream

__all__ = [
    "EndOfStream", "TileStream", "GeneratorStream", "open_stream",
    "declare_stream_metrics",
]


def declare_stream_metrics() -> None:
    """Declare the streaming histograms with the TILE-scale ladder
    (first declaration wins — must run before the first observe, or
    the default job-scale buckets clamp sub-100ms percentiles)."""
    reg = obs.get()
    if reg is not None:
        reg.histogram(
            "stream_tile_latency_seconds",
            help="per-tile latency, arrival -> residual durably "
                 "written (the streaming SLO)",
            buckets=obs.TILE_LAT_BUCKETS)


class TileStream:
    """Ordered delivery of ``(index, VisTile, t_arrival)`` events.

    Contract (all transports):

    - events come out in strictly increasing tile index order; a
      DROPPED tile is an index gap, counted in
      ``stream_tiles_dropped_total`` by the transport, never a stall;
    - ``wait_next(cancel)`` advances to the next event, blocking until
      it is available; returns its arrival timestamp; raises
      :class:`EndOfStream` at clean end of input (also when
      ``cancel`` is set — a cancelled consumer just stops);
    - ``take()`` returns the current event ``(i, VisTile, t_arr)``
      without blocking; repeatable until the next ``wait_next``;
    - ``close()`` is idempotent and prompt.
    """

    def wait_next(self, cancel=None) -> float:
        raise NotImplementedError

    def take(self):
        raise NotImplementedError

    def close(self) -> None:
        pass

    def __iter__(self):
        """Convenience for tests/simple consumers: iterate events."""
        try:
            while True:
                self.wait_next()
                yield self.take()
        except EndOfStream:
            return

    # -- shared helpers ----------------------------------------------------

    @staticmethod
    def _cancel_wait(cancel, seconds: float) -> bool:
        """Sleep up to ``seconds``; True if ``cancel`` fired."""
        if cancel is not None:
            return cancel.wait(seconds)
        time.sleep(seconds)
        return False

    @staticmethod
    def _check_cancel(cancel) -> None:
        if cancel is not None and cancel.is_set():
            raise EndOfStream("stream consumer cancelled")


class GeneratorStream(TileStream):
    """Seeded in-process arrival generator over an on-disk SimMS.

    Tile i "arrives" at ``start_time + i * interval_s`` — before that
    instant it does not exist as far as the consumer can tell, after
    it the tile is readable from the backing dataset. The arrival
    timestamp is the SCHEDULED arrival (the tile was on the wire from
    that moment), so a consumer that falls behind correctly sees its
    lag in the arrival-to-write latency.

    The ``tile_dropped`` fault point is queried at each arrival: a
    dropped tile is skipped (index gap) and counted, exactly like a
    transport loss.
    """

    def __init__(self, ms, interval_s: float = 0.0, start: int = 0,
                 n_tiles: int | None = None):
        self.ms = ms
        self.interval_s = max(0.0, float(interval_s))
        self.start = int(start)
        n = ms.n_tiles if n_tiles is None else int(n_tiles)
        self.n_tiles = int(n)
        self._t0 = time.monotonic()
        self._k = self.start          # next tile index to deliver
        self._cur = None              # (i, t_arr) of the current event

    def wait_next(self, cancel=None) -> float:
        while True:
            self._check_cancel(cancel)
            k = self._k
            if k >= self.n_tiles:
                raise EndOfStream
            due = self._t0 + (k - self.start) * self.interval_s
            delay = due - time.monotonic()
            if delay > 0:
                if self._cancel_wait(cancel, min(delay, 0.2)):
                    raise EndOfStream("stream consumer cancelled")
                continue
            self._k = k + 1
            if faults.fires("tile_dropped", key=k):
                obs.inc("stream_tiles_dropped_total")
                continue
            self._cur = (k, due)
            return due

    def take(self):
        i, t_arr = self._cur
        return i, self.ms.read_tile(i), t_arr


def open_stream(cfg, log=None):
    """Open the transport named by ``cfg.stream_source`` and return
    ``(stream, ms)`` with ``ms`` the (possibly just-materialized)
    SimMS the stream's tiles live in — residual write-back and the
    program-cache bucket both key off it, same as batch mode.

    Specs: ``gen[:interval_s]`` | ``tail[:path]`` |
    ``socket:host:port`` (see the module docstring). Blocks until the
    transport has a dataset header (tail: meta.json visible; socket:
    meta frame received) so the caller can build the pipeline
    immediately.
    """
    from sagecal_tpu.io import dataset as ds

    spec = (cfg.stream_source or "").strip()
    kind, _, rest = spec.partition(":")
    declare_stream_metrics()

    def _log(msg):
        if log is not None:
            log(msg)

    def _open(path):
        return ds.open_dataset(path, None, tilesz=cfg.tile_size,
                               data_column=cfg.input_column,
                               out_column=cfg.output_column)

    if kind == "gen":
        interval = float(rest) if rest else float(
            getattr(cfg, "tile_arrival_s", 0.0) or 0.0)
        ms = _open(cfg.ms)
        _log(f"stream: generator over {cfg.ms} "
             f"({ms.n_tiles} tiles @ {interval * 1e3:.0f} ms)")
        return GeneratorStream(ms, interval), ms
    if kind == "tail":
        from sagecal_tpu.stream import transport as tr
        path = rest or cfg.ms
        tr.wait_for_meta(path)
        ms = _open(path)
        _log(f"stream: tailing spool {path}")
        return tr.TailStream(ms), ms
    if kind == "socket":
        from sagecal_tpu.stream import transport as tr
        host, _, port = rest.rpartition(":")
        strm = tr.SocketStream(host or "127.0.0.1", int(port), cfg.ms)
        strm.handshake()              # meta frame -> cfg.ms/meta.json
        ms = _open(cfg.ms)
        strm.ms = ms
        _log(f"stream: socket {host}:{port} -> spool {cfg.ms}")
        return strm, ms
    raise ValueError(
        f"unknown stream_source spec {spec!r} "
        "(want gen[:interval_s] | tail[:path] | socket:host:port)")
