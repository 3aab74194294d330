"""Wire transports for live tile ingest: file-tail and TCP socket.

Both transports land arriving tile bytes in a normal SimMS directory
(the job's ``--ms``), so everything downstream — residual write-back,
program-cache bucketing, the bit-identity audit against a batch run —
works unchanged. The only new storage artifact is the end-of-stream
marker (``stream.end``, a one-line JSON ``{"n": <final index>}``).

Framing (socket): every frame is an 8-byte big-endian length followed
by a UTF-8 JSON header, then a second length-prefixed binary body
(empty for meta/end frames). The schema is VERSIONED: the meta frame
(always first on the wire) carries ``magic`` + ``v``, and the
consumer's handshake refuses a missing/foreign magic or a version it
does not speak — loudly, with both sides' versions named — instead of
mis-parsing frames from an incompatible peer. Header kinds::

    {"kind": "meta", "magic": "sagecal-tile-stream", "v": 1,
     "meta": {...}}                        # SimMS meta.json content
    {"kind": "tile", "i": 7}               # body = tile npz bytes
    {"kind": "end",  "n": 12}              # final next-index

Version history: v1 = the frame kinds above (ISSUE 16 wire format,
stamped since ISSUE 17). Bump ``FRAME_VERSION`` on ANY change to the
header fields or body encoding — the handshake is exact-match, not
ranged: a reader that could half-parse a newer writer is the failure
mode the refusal exists to prevent.

The feeders (:class:`SocketFeeder`, :class:`TailFeeder`) are the
tests' harness side: they replay an existing on-disk SimMS on an
arrival clock, applying the ``tile_dropped`` fault point so loss is a
first-class, deterministic chaos lever. A dropped tile is an index
gap on the wire; the consumer transports count the gap
(``stream_tiles_dropped_total``) and keep going — a live stream must
survive loss without stalling.
"""

from __future__ import annotations

import json
import os
import socket
import struct
import threading
import time

from sagecal_tpu import faults
from sagecal_tpu.obs import metrics as obs
from sagecal_tpu.sched import EndOfStream
from sagecal_tpu.stream import TileStream

END_MARKER = "stream.end"
_LEN = struct.Struct(">Q")
#: socket frame schema identity: the meta handshake's magic string and
#: exact-match version (module docstring "Framing"). A mismatch is a
#: refusal, never a best-effort parse.
FRAME_MAGIC = "sagecal-tile-stream"
FRAME_VERSION = 1
#: polling quantum for file-tail waits: small enough that visibility
#: latency is noise against any real tile cadence, large enough that
#: an idle tail is not a busy loop
POLL_S = 0.003


def _tile_name(i: int) -> str:
    return f"tile{i:05d}.npz"


def wait_for_meta(path: str, timeout_s: float = 30.0) -> None:
    """Block until the spool directory has a dataset header (the
    feeder writes meta.json FIRST, before any tile): the consumer can
    then open the SimMS and build its pipeline while tiles are still
    arriving."""
    deadline = time.monotonic() + timeout_s
    meta = os.path.join(path, "meta.json")
    while not os.path.exists(meta):
        if time.monotonic() > deadline:
            raise TimeoutError(
                f"stream spool {path}: no meta.json after "
                f"{timeout_s:.0f}s — is the feeder running?")
        time.sleep(POLL_S)


class TailStream(TileStream):
    """Follow a spool directory a feeder writes SimMS tiles into.

    Arrival = the tile file becoming VISIBLE (the feeder's
    write-then-rename makes that atomic). End = the ``stream.end``
    marker. A gap — tile k absent while tile j>k (or the end marker)
    exists — means the feeder dropped k: counted, skipped, never
    waited on, because the feeder writes strictly in index order.
    """

    def __init__(self, ms, start: int = 0):
        self.ms = ms
        self._k = int(start)
        self._cur = None
        self._end_n = None            # parsed stream.end, once seen

    def _final_n(self):
        if self._end_n is None:
            p = os.path.join(self.ms.path, END_MARKER)
            if os.path.exists(p):
                with open(p) as f:
                    self._end_n = int(json.load(f)["n"])
        return self._end_n

    def _later_tile_exists(self, k: int) -> bool:
        for name in os.listdir(self.ms.path):
            if name.startswith("tile") and name.endswith(".npz"):
                try:
                    if int(name[4:9]) > k:
                        return True
                except ValueError:
                    continue
        return False

    def wait_next(self, cancel=None) -> float:
        while True:
            self._check_cancel(cancel)
            k = self._k
            n = self._final_n()
            if n is not None and k >= n:
                raise EndOfStream
            if os.path.exists(os.path.join(self.ms.path,
                                           _tile_name(k))):
                self._k = k + 1
                self._cur = (k, time.monotonic())
                return self._cur[1]
            # strictly-ordered feeder: anything past k on disk (or a
            # final count above k) proves k was dropped, not late
            if n is not None or self._later_tile_exists(k):
                obs.inc("stream_tiles_dropped_total")
                self._k = k + 1
                continue
            self._cancel_wait(cancel, POLL_S)

    def take(self):
        i, t_arr = self._cur
        return i, self.ms.read_tile(i), t_arr


class SocketStream(TileStream):
    """Consume length-prefixed npz tile frames over TCP, spooling each
    into the local MS directory as it lands (so residual write-back
    and the batch bit-identity audit see a normal SimMS).

    Arrival = the frame fully received. Reads happen in
    :meth:`wait_next` (socket timeouts keep it cancel-prompt); a
    consumer that falls behind therefore sees kernel-buffered frames
    "arrive" when it drains them — latency honesty at single-process
    test scale; a real deployment stamps on a receiver thread.
    """

    def __init__(self, host: str, port: int, spool: str,
                 connect_timeout_s: float = 10.0):
        self.spool = spool
        self.ms = None                # set by open_stream after meta
        self._cur = None
        self._expect = 0              # next index the WIRE should send
        self._sock = None
        deadline = time.monotonic() + connect_timeout_s
        while True:
            try:
                self._sock = socket.create_connection(
                    (host, port), timeout=1.0)
                break
            except OSError:
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.05)
        self._sock.settimeout(0.2)

    def _read_exact(self, n: int, cancel=None) -> bytes:
        buf = b""
        while len(buf) < n:
            self._check_cancel(cancel)
            try:
                chunk = self._sock.recv(n - len(buf))
            except socket.timeout:
                continue
            if not chunk:
                raise ConnectionError(
                    "stream socket closed mid-frame (no end frame)")
            buf += chunk
        return buf

    def _read_frame(self, cancel=None):
        hdr = json.loads(self._read_exact(
            _LEN.unpack(self._read_exact(_LEN.size, cancel))[0],
            cancel).decode("utf-8"))
        body = self._read_exact(
            _LEN.unpack(self._read_exact(_LEN.size, cancel))[0],
            cancel)
        return hdr, body

    def handshake(self) -> dict:
        """Read the meta frame and materialize the spool directory's
        meta.json (first contact only — an existing header wins, so
        re-pointing a stream at a live dataset cannot clobber it)."""
        hdr, _ = self._read_frame()
        if hdr.get("kind") != "meta":
            raise ValueError(
                f"stream socket: expected meta frame, got {hdr!r}")
        if hdr.get("magic") != FRAME_MAGIC:
            raise ValueError(
                f"stream socket: frame magic {hdr.get('magic')!r} is "
                f"not {FRAME_MAGIC!r} — the peer is not a sagecal "
                "tile-stream feeder (or predates the versioned "
                "schema); refusing to parse its frames")
        if hdr.get("v") != FRAME_VERSION:
            raise ValueError(
                f"stream socket: frame schema v{hdr.get('v')} from "
                f"the feeder, this consumer speaks v{FRAME_VERSION} "
                "exactly — upgrade the older side; mixed versions "
                "would mis-parse tile frames, not degrade gracefully")
        os.makedirs(self.spool, exist_ok=True)
        mp = os.path.join(self.spool, "meta.json")
        if not os.path.exists(mp):
            tmp = mp + ".tmp"
            with open(tmp, "w") as f:
                json.dump(hdr["meta"], f, indent=1)
            os.replace(tmp, mp)
        return hdr["meta"]

    def wait_next(self, cancel=None) -> float:
        while True:
            hdr, body = self._read_frame(cancel)
            kind = hdr.get("kind")
            if kind == "end":
                # gaps at the tail are drops too
                n = int(hdr.get("n", self._expect))
                for _ in range(max(0, n - self._expect)):
                    obs.inc("stream_tiles_dropped_total")
                raise EndOfStream
            if kind != "tile":
                raise ValueError(f"stream socket: bad frame {hdr!r}")
            i = int(hdr["i"])
            t_arr = time.monotonic()
            for _ in range(max(0, i - self._expect)):
                obs.inc("stream_tiles_dropped_total")
            self._expect = i + 1
            path = os.path.join(self.spool, _tile_name(i))
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                f.write(body)
            os.replace(tmp, path)
            self._cur = (i, t_arr)
            return t_arr

    def take(self):
        i, t_arr = self._cur
        return i, self.ms.read_tile(i), t_arr

    def close(self) -> None:
        if self._sock is not None:
            try:
                self._sock.close()
            finally:
                self._sock = None


# -- feeders (the harness side) ----------------------------------------------


class _FeederBase:
    """Replay an existing on-disk SimMS on an arrival clock; tile k is
    released at ``start + k * interval_s``, or dropped when the
    ``tile_dropped`` point fires for key k."""

    def __init__(self, src_path: str, interval_s: float = 0.0):
        self.src = src_path
        self.interval_s = max(0.0, float(interval_s))
        with open(os.path.join(src_path, "meta.json")) as f:
            self.meta = json.load(f)
        self.n_tiles = int(self.meta["n_tiles"])
        self._thread = None
        self._stop = threading.Event()

    def start(self) -> "_FeederBase":
        self._thread = threading.Thread(
            target=self._run, name="stream-feeder", daemon=True)
        self._thread.start()
        return self

    def join(self, timeout_s: float = 30.0) -> None:
        if self._thread is not None:
            self._thread.join(timeout=timeout_s)

    def close(self) -> None:
        self._stop.set()
        self.join(timeout_s=5.0)

    def _pace(self, k: int, t0: float) -> bool:
        due = t0 + k * self.interval_s
        while not self._stop.is_set():
            delay = due - time.monotonic()
            if delay <= 0:
                return True
            self._stop.wait(min(delay, 0.2))
        return False

    def _run(self):
        raise NotImplementedError


class TailFeeder(_FeederBase):
    """Spool tiles into a directory for :class:`TailStream`:
    meta.json first, then tile files in strict index order (atomic
    rename = the arrival event), then the ``stream.end`` marker."""

    def __init__(self, src_path: str, spool: str,
                 interval_s: float = 0.0):
        super().__init__(src_path, interval_s)
        self.spool = spool

    def _run(self):
        os.makedirs(self.spool, exist_ok=True)
        for name in ("meta.json", "beam.npz"):
            sp = os.path.join(self.src, name)
            if not os.path.exists(sp):
                continue
            tmp = os.path.join(self.spool, name + ".tmp")
            with open(sp, "rb") as f:
                blob = f.read()
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, os.path.join(self.spool, name))
        t0 = time.monotonic()
        for k in range(self.n_tiles):
            if not self._pace(k, t0):
                return
            if faults.fires("tile_dropped", key=k):
                continue
            dst = os.path.join(self.spool, _tile_name(k))
            tmp = dst + ".tmp"
            with open(os.path.join(self.src, _tile_name(k)),
                      "rb") as f:
                blob = f.read()
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, dst)
        tmp = os.path.join(self.spool, END_MARKER + ".tmp")
        with open(tmp, "w") as f:
            json.dump({"n": self.n_tiles}, f)
        os.replace(tmp, os.path.join(self.spool, END_MARKER))


class SocketFeeder(_FeederBase):
    """Serve one :class:`SocketStream` connection: meta frame, tile
    frames on the arrival clock, end frame. ``port=0`` binds an
    ephemeral port (read :attr:`port` after construction)."""

    def __init__(self, src_path: str, interval_s: float = 0.0,
                 host: str = "127.0.0.1", port: int = 0):
        super().__init__(src_path, interval_s)
        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind((host, port))
        self._srv.listen(1)
        self._srv.settimeout(0.2)
        self.host, self.port = self._srv.getsockname()[:2]

    @staticmethod
    def _send_frame(conn, hdr: dict, body: bytes = b"") -> None:
        blob = json.dumps(hdr).encode("utf-8")
        conn.sendall(_LEN.pack(len(blob)) + blob +
                     _LEN.pack(len(body)) + body)

    def _run(self):
        conn = None
        try:
            while not self._stop.is_set():
                try:
                    conn, _addr = self._srv.accept()
                    break
                except socket.timeout:
                    continue
            if conn is None:
                return
            self._send_frame(conn, {"kind": "meta",
                                    "magic": FRAME_MAGIC,
                                    "v": FRAME_VERSION,
                                    "meta": self.meta})
            t0 = time.monotonic()
            for k in range(self.n_tiles):
                if not self._pace(k, t0):
                    return
                if faults.fires("tile_dropped", key=k):
                    continue
                with open(os.path.join(self.src, _tile_name(k)),
                          "rb") as f:
                    body = f.read()
                self._send_frame(conn, {"kind": "tile", "i": k}, body)
            self._send_frame(conn, {"kind": "end",
                                    "n": self.n_tiles})
        finally:
            if conn is not None:
                conn.close()
            self._srv.close()

    def close(self) -> None:
        super().close()
        try:
            self._srv.close()
        except OSError:
            pass
