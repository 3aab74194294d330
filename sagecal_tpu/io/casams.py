"""CASA MeasurementSet backend over python-casacore.

Capability parity with the reference MS reader/writer
(``src/MS/data.cpp``): ``readAuxData`` (:138, beam overload :194),
``loadData`` (:522) and ``writeData`` (:1259), re-expressed behind the
same dataset interface SimMS implements (meta / n_tiles / read_tile /
write_tile / beam_info / tiles_prefetch), so the rest of the framework is
backend-agnostic:

- tiles iterate the main table sorted by TIME, ANTENNA1, ANTENNA2
  (loadData :525-529), dropping autocorrelations (:556);
- channel averaging with the strictly-more-than-half unflagged rule,
  uv-cut flag=2 and the short-baseline taper are NOT done here — they
  live in :meth:`VisTile.solve_input`/:meth:`VisTile.pack` (the native
  pack kernel), which this backend feeds with the raw per-channel data
  and flags; a row is pre-flagged only when every channel is flagged or
  the row is absent from the MS (tail padding, loadData :643-657);
- residual write-back targets the output data column per channel
  (writeData :1286-1297);
- ``beam_info`` reads the LOFAR_ANTENNA_FIELD subtable: station field
  centers ITRF->(lon, lat), ELEMENT_OFFSET rotated into the local frame
  by COORDINATE_AXES, dipoles with either polarization flagged in
  ELEMENT_FLAG dropped, HBA tiles expanded to 16 positions per dipole
  via TILE_ELEMENT_OFFSET (readAuxData :269-380).

One deliberate deviation, documented: the reference packs surviving rows
*sequentially* and tail-pads, so a timeslot with missing baselines shifts
every later row's (timeslot, baseline) identity by one (data.cpp:540-543
warns and carries on). Here each row is placed at its true
``slot*nbase + baseline_index`` position and missing rows stay flagged —
identical for complete data, and correct instead of shifted for gappy MSs.

python-casacore is an optional dependency (absent in this image — the
install attempt is recorded in README.md); the module imports lazily and
:func:`have_casacore` gates it. Tests inject a fake ``tables`` module
implementing the same API surface (see ``tests/test_casams.py``), which
exercises every code path except casacore itself.
"""

from __future__ import annotations

import os

import numpy as np

from sagecal_tpu.analysis import threadsan
from sagecal_tpu.diag import trace as dtrace
from sagecal_tpu.io.dataset import (VisTile, generate_baselines,
                                    _tiles_prefetch_impl, C_M_S)

_TABLES = None


def _tables():
    """Resolve the casacore.tables module (memoized)."""
    global _TABLES
    if _TABLES is None:
        import casacore.tables as ct
        _TABLES = ct
    return _TABLES


def have_casacore() -> bool:
    try:
        _tables()
        return True
    except ImportError:
        return False


def is_ms_path(path: str) -> bool:
    """A CASA table is a directory containing table.dat."""
    return os.path.isdir(path) and os.path.exists(
        os.path.join(path, "table.dat"))


def _llh(pos_xyz: np.ndarray):
    """[N, 3] ITRF (m) -> (lon, lat) rad, host-side (transforms.c:35)."""
    from sagecal_tpu import coords
    lon, lat, _ = coords.xyz2llh(pos_xyz[:, 0], pos_xyz[:, 1],
                                 pos_xyz[:, 2])
    return np.asarray(lon, float), np.asarray(lat, float)


class CasaMS:
    """A CASA MeasurementSet as a streaming tile dataset.

    Parameters mirror the reference app's globals: ``tilesz`` rows of
    ``-t``, ``data_column`` ``-d``'s DATA/MODEL_DATA choice
    (Data::DataField), ``out_column`` the residual target
    (Data::OutField, default CORRECTED_DATA).
    """

    def __init__(self, path: str, tilesz: int = 10,
                 data_column: str = "DATA",
                 out_column: str = "CORRECTED_DATA",
                 tables_mod=None):
        self._ct = tables_mod or _tables()
        self.path = path
        # overlapped execution (sagecal_tpu.sched) reads tile t+N on
        # a prefetch thread while the writer thread writes tile t;
        # python-casacore table objects are NOT thread-safe, so all
        # column access on this MS serializes through one lock
        # (SimMS needs none: per-tile npz files, distinct paths)
        self._io_lock = threadsan.make_lock("CasaMS._io_lock")
        self._t = self._ct.table(path, readonly=False, ack=False)
        self._ts = self._t.sort("TIME,ANTENNA1,ANTENNA2")
        self.data_column = data_column
        if out_column not in self._t.colnames():
            # the reference errors on a missing OutField rather than
            # writing over the input (writeData data.cpp:1271); silently
            # demoting to the data column would destroy the observation
            raise RuntimeError(
                f"{path}: output column {out_column!r} does not exist; "
                f"create it (e.g. with casacore addImagingColumns) or "
                f"pass out_column explicitly")
        self.out_column = out_column
        self._has_ddid = "DATA_DESC_ID" in self._t.colnames()
        if self._has_ddid:
            dd = np.unique(np.asarray(self._t.getcol("DATA_DESC_ID")))
            if len(dd) > 1:
                import warnings
                warnings.warn(
                    f"{path}: {len(dd)} spectral windows present; only "
                    f"DATA_DESC_ID==0 is calibrated (the reference "
                    f"assumes a single-SPW MS per subband)")

        ant = self._sub("ANTENNA")
        n = ant.nrows()
        ant.close()
        nbase = n * (n - 1) // 2
        p, q = generate_baselines(n)
        # (p, q) -> baseline slot index within a timeslot
        self._blidx = np.full((n, n), -1, np.int64)
        self._blidx[p, q] = np.arange(nbase)

        field = self._sub("FIELD")
        # beam overload reads PHASE_DIR ("old REFERENCE_DIR", data.cpp:212)
        col = ("PHASE_DIR" if "PHASE_DIR" in field.colnames()
               else "REFERENCE_DIR")
        ra0, dec0 = np.asarray(field.getcol(col))[0].ravel()[:2]
        field.close()

        spw = self._sub("SPECTRAL_WINDOW")
        freqs = np.asarray(spw.getcol("CHAN_FREQ"))[0].ravel()
        chan_w = float(np.asarray(spw.getcol("CHAN_WIDTH"))[0].ravel()[0])
        spw.close()

        tdelta = float(self._ts.getcol("INTERVAL", 0, 1)[0])

        # slot boundaries: scan TIME chunked, record change points. Exact
        # even with missing/extra rows (the reference infers totalt from
        # nrow/(Nbase+N), data.cpp:149, which assumes complete data).
        nrow = self._ts.nrows()
        starts = [0]
        slot_times = []
        prev = None
        CH = 1 << 20
        for r0 in range(0, nrow, CH):
            tcol = np.asarray(self._ts.getcol("TIME", r0,
                                              min(CH, nrow - r0)))
            if prev is not None and tcol[0] != prev:
                starts.append(r0)
                slot_times.append(prev)
            chg = np.nonzero(np.diff(tcol))[0]
            for c in chg:
                starts.append(r0 + int(c) + 1)
                slot_times.append(tcol[c])
            prev = tcol[-1]
        if nrow:
            slot_times.append(prev)
        starts.append(nrow)
        self._slot_starts = np.asarray(starts, np.int64)
        self._slot_times = np.asarray(slot_times, float)    # MJD seconds
        totalt = len(slot_times)

        self.tilesz = int(tilesz)
        self.meta = {
            "n_tiles": (totalt + self.tilesz - 1) // self.tilesz,
            "n_stations": n, "nbase": int(nbase), "tilesz": self.tilesz,
            "freqs": list(map(float, freqs)),
            "freq0": float(freqs.mean()),
            "fdelta": float(len(freqs)) * chan_w,   # readAuxData :191
            "tdelta": tdelta,
            "ra0": float(ra0), "dec0": float(dec0),
            "total_timeslots": totalt,
        }

    def _sub(self, name: str):
        return self._ct.table(f"{self.path}::{name}", ack=False)

    @property
    def n_tiles(self) -> int:
        return self.meta["n_tiles"]

    def _tile_rows(self, i: int):
        """(startrow, nrow, slot0, nslots) of tile i in the sorted table."""
        t0 = i * self.tilesz
        t1 = min(t0 + self.tilesz, len(self._slot_times))
        r0 = int(self._slot_starts[t0])
        return r0, int(self._slot_starts[t1]) - r0, t0, t1 - t0

    def _row_positions(self, a1, a2, r0, slot0, ddid=None):
        """Map sorted-table rows to [tilesz*nbase] tile positions; -1 for
        autocorrelations and rows of other spectral windows. Also returns
        the a1 > a2 mask: such rows hold V_qp = V_pq^H with negated uvw
        and are conjugate-transposed into the canonical slot."""
        nbase = self.meta["nbase"]
        # slot index of each row via the precomputed boundaries
        slot = np.searchsorted(self._slot_starts,
                               np.arange(r0, r0 + len(a1)),
                               side="right") - 1 - slot0
        lo, hi = np.minimum(a1, a2), np.maximum(a1, a2)
        keep = a1 != a2
        if ddid is not None:
            keep = keep & (ddid == 0)
        pos = np.where(keep, slot * nbase + self._blidx[lo, hi], -1)
        return pos, (a1 > a2) & keep

    def _ddid(self, r0, nr):
        if not self._has_ddid:
            return None
        return np.asarray(self._ts.getcol("DATA_DESC_ID", r0, nr))

    def read_tile(self, i: int) -> VisTile:
        with self._io_lock:
            return self._read_tile_locked(i)

    def _read_tile_locked(self, i: int) -> VisTile:
        m = self.meta
        r0, nr, slot0, nslots = self._tile_rows(i)
        nbase, F = m["nbase"], len(m["freqs"])
        B = self.tilesz * nbase

        a1 = np.asarray(self._ts.getcol("ANTENNA1", r0, nr))
        a2 = np.asarray(self._ts.getcol("ANTENNA2", r0, nr))
        data = np.asarray(self._ts.getcol(self.data_column, r0, nr))
        uvw = np.asarray(self._ts.getcol("UVW", r0, nr))
        flag = np.asarray(self._ts.getcol("FLAG", r0, nr))
        frow = (np.asarray(self._ts.getcol("FLAG_ROW", r0, nr))
                if "FLAG_ROW" in self._t.colnames()
                else np.zeros(nr, bool))

        pos, swapped = self._row_positions(a1, a2, r0, slot0,
                                           self._ddid(r0, nr))
        sel = pos >= 0
        sw = swapped[sel]
        pos = pos[sel]

        x = np.zeros((B, F, 2, 2), np.complex128)
        # DATA is [row, chan, corr(XX,XY,YX,YY)] in python-casacore
        xr = data[sel].reshape(-1, F, 2, 2).astype(np.complex128)
        # a1 > a2 rows store V_qp: canonical V_pq = V_qp^H, uvw negated
        xr[sw] = np.conj(np.swapaxes(xr[sw], -1, -2))
        x[pos] = xr
        sgn = np.where(sw, -1.0, 1.0)
        u = np.zeros(B)
        v = np.zeros(B)
        w = np.zeros(B)
        u[pos], v[pos], w[pos] = (sgn * uvw[sel, 0] / C_M_S,
                                  sgn * uvw[sel, 1] / C_M_S,
                                  sgn * uvw[sel, 2] / C_M_S)
        # a channel is bad when ANY correlation is flagged (loadData :585)
        cflags = np.ones((B, F), np.uint8)
        cflags[pos] = (flag[sel].reshape(-1, F, 4).any(axis=2)
                       | frow[sel, None]).astype(np.uint8)
        # rows absent from the MS or with every channel flagged: flag=1
        # (tail padding :643-657 / all-flagged :617-620); partial rows and
        # the uv-cut are resolved later by VisTile.pack
        flags = np.where(cflags.all(axis=1), np.int8(1), np.int8(0))

        sta1_1, sta2_1 = generate_baselines(m["n_stations"])
        times = np.full(self.tilesz, np.nan)
        times[:nslots] = self._slot_times[slot0:slot0 + nslots]
        if nslots and nslots < self.tilesz:     # tail tile: repeat last
            times[nslots:] = times[nslots - 1]
        return VisTile(
            u=u, v=v, w=w, x=x, flags=flags,
            sta1=np.tile(sta1_1, self.tilesz),
            sta2=np.tile(sta2_1, self.tilesz),
            freqs=np.asarray(m["freqs"]), freq0=m["freq0"],
            fdelta=m["fdelta"], tdelta=m["tdelta"],
            dec0=m["dec0"], ra0=m["ra0"],
            n_stations=m["n_stations"], nbase=nbase, tilesz=self.tilesz,
            time_mjd=times, cflags=cflags)

    def write_tile(self, i: int, tile: VisTile) -> None:
        """Write tile.x (residuals, [B, F, 2, 2]) to the output column at
        the rows present in the MS (writeData :1280-1299). Serialized
        against concurrent prefetch reads (see __init__'s lock)."""
        with self._io_lock:
            self._write_tile_locked(i, tile)

    def _write_tile_locked(self, i: int, tile: VisTile) -> None:
        r0, nr, slot0, _ = self._tile_rows(i)
        # "keep": what the rows hold is read, the rows absent from the
        # tile keep it
        with dtrace.phase("keep"):
            a1 = np.asarray(self._ts.getcol("ANTENNA1", r0, nr))
            a2 = np.asarray(self._ts.getcol("ANTENNA2", r0, nr))
            ddid = self._ddid(r0, nr)
            out = np.asarray(self._ts.getcol(self.out_column, r0, nr))
        pos, swapped = self._row_positions(a1, a2, r0, slot0, ddid)
        sel = pos >= 0
        F = len(self.meta["freqs"])
        xw = tile.x[pos[sel]]
        sw = swapped[sel]
        xw[sw] = np.conj(np.swapaxes(xw[sw], -1, -2))  # back to V_qp
        out[sel] = xw.reshape(-1, F, 4).astype(out.dtype)
        with dtrace.phase("putcol"):
            self._ts.putcol(self.out_column, out, r0, nr)

    def beam_info(self):
        """LOFAR_ANTENNA_FIELD -> BeamInfo, or None for a non-LOFAR MS
        (readAuxData beam overload, data.cpp:264-380)."""
        from sagecal_tpu.rime import beam as bm
        m = self.meta
        try:
            af = self._sub("LOFAR_ANTENNA_FIELD")
        except RuntimeError:
            return None
        n = m["n_stations"]
        pos = np.zeros((n, 3))
        elems = []
        for ci in range(n):
            pos[ci] = np.asarray(af.getcell("POSITION", ci)).ravel()[:3]
            off = np.asarray(af.getcell("ELEMENT_OFFSET", ci))
            off = off.reshape(-1, 3)                    # [E, 3] ITRF-ish
            axes = np.asarray(af.getcell("COORDINATE_AXES", ci))
            axes = axes.reshape(3, 3)
            ef = np.asarray(af.getcell("ELEMENT_FLAG", ci)).reshape(-1, 2)
            # drop a dipole when either polarization is flagged (:326-330)
            good = ~ef.any(axis=1)
            local = off[good] @ axes.T                  # rotate to local
            toff = None
            try:
                toff = np.asarray(af.getcell("TILE_ELEMENT_OFFSET", ci))
            except RuntimeError:
                pass
            if toff is not None and toff.size:          # HBA (:303-351)
                tl = toff.reshape(-1, 3) @ axes.T       # [16, 3] local
                local = (local[:, None, :] + tl[None, :, :]).reshape(-1, 3)
            elems.append(local)
        af.close()
        emax = max((e.shape[0] for e in elems), default=0)
        exyz = np.zeros((n, emax, 3))
        emask = np.zeros((n, emax), bool)
        for ci, e in enumerate(elems):
            exyz[ci, :e.shape[0]] = e
            emask[ci, :e.shape[0]] = True
        lon, lat = _llh(pos)
        time_jd = self._slot_times / 86400.0 + 2400000.5
        return bm.BeamInfo(
            longitude=lon, latitude=lat, time_jd=time_jd,
            ra0=m["ra0"], dec0=m["dec0"], freq0=m["freq0"],
            elem_xyz=exyz, elem_mask=emask,
            ecoeff=bm.default_element_coeffs(
                bm.band_for_freq(m["freq0"])))

    def tiles(self):
        for i in range(self.n_tiles):
            yield i, self.read_tile(i)

    def tiles_prefetch(self, depth: int = 2):
        return _tiles_prefetch_impl(self, depth)

    def close(self):
        self._ts.close()
        self._t.close()
