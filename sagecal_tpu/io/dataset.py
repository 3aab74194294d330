"""Visibility containers, the SimMS on-disk format, and synthetic generation.

Toward parity with reference ``src/MS/data.cpp`` semantics (loadData:522:
TIME/ANT sort, autocorrelation drop, channel averaging, flag ratio),
re-expressed over an abstract dataset. Per-channel flags and the
more-than-half-unflagged channel-averaging rule (data.cpp:601) belong to the
casacore MS backend and are not represented here yet — VisTile flags are
per-row:

- :class:`VisTile` — one solve interval of device-ready arrays.
- :class:`SimMS` — a minimal columnar on-disk dataset (npz per tile group)
  standing in for a CASA MeasurementSet: the image has no casacore, so
  MS access is a backend interface; SimMS is the native backend and a
  python-casacore backend can slot in where available.
- :func:`simulate_dataset` — the analogue of the reference test harness
  (test/Calibration/Generate_sources.py + Change_freq.py): synthesize
  uvw tracks for an array, predict a sky, corrupt with known Jones + noise.
  This is the round-trip oracle for calibration tests.
"""

from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

from sagecal_tpu import faults
from sagecal_tpu.diag import trace as dtrace

C_M_S = 299792458.0
OMEGA_E = 7.2921150e-5  # earth angular velocity rad/s


@dataclasses.dataclass
class VisTile:
    """One solve interval (tile) of visibilities, host-side numpy.

    Layout matches the reference data model (SURVEY.md section 1): rows are
    ordered [tilesz, nbase] flattened, i.e. row = t*nbase + bl; u,v,w in
    seconds; ``x`` is the multi-channel data [B, F, 2, 2] complex;
    ``flags`` per row (0 ok, 1 flagged, 2 uv-cut).
    """

    u: np.ndarray            # [B] seconds
    v: np.ndarray
    w: np.ndarray
    x: np.ndarray            # [B, F, 2, 2] complex
    flags: np.ndarray        # [B] int8
    sta1: np.ndarray         # [B] int32
    sta2: np.ndarray         # [B] int32
    freqs: np.ndarray        # [F] Hz
    freq0: float             # reference (mean) frequency Hz
    fdelta: float            # total bandwidth Hz
    tdelta: float            # integration time s
    dec0: float              # phase-center declination rad
    ra0: float               # phase-center RA rad
    n_stations: int
    nbase: int               # baselines per timeslot
    tilesz: int              # timeslots in this tile
    time_mjd: np.ndarray | None = None   # [tilesz] time centroid (s, MJD)
    cflags: np.ndarray | None = None     # [B, F] per-channel flags (u8)

    @property
    def nrows(self) -> int:
        return self.u.shape[0]

    @property
    def flag_ratio(self) -> float:
        """Fraction of flagged rows (data.cpp:659-663 ``fratio``)."""
        return float(np.mean(self.flags == 1))

    @property
    def time_jd(self) -> np.ndarray:
        """Per-timeslot Julian date in days (predict_model.cu:1372
        ``kernel_convert_time``: MS TIME is MJD seconds)."""
        if self.time_mjd is None:
            return np.full(self.tilesz, 2451545.0)  # J2000 placeholder
        return np.asarray(self.time_mjd) / 86400.0 + 2400000.5

    @property
    def tslot(self) -> np.ndarray:
        """[nrows] row -> timeslot index (rows ordered [tilesz, nbase])."""
        return row_tslot(self.nrows, self.nbase)

    def averaged(self):
        """Channel-average data -> [B, 2, 2]; flagged rows zeroed.

        Mirrors loadData's averaging into ``x`` while ``xo`` keeps channels
        (data.cpp:594-610). Weighting is a plain mean over channels; with
        per-channel flags use :meth:`pack` instead.
        """
        xa = self.x.mean(axis=1)
        xa[self.flags == 1] = 0.0
        return xa

    def solve_input(self, uvtaper_m: float = 0.0):
        """(x8 [B, 8], rowflags [B], good_fraction) — the channel-averaged
        solve input with loadData semantics: native per-channel-flag
        packing (more-than-half rule) when ``cflags`` exist or a taper is
        requested, else the plain channel mean. Stored uv-cut rows
        (flag == 2) survive either path; this is the ONE staging decision
        shared by the fullbatch pipeline and the distributed CLI.
        """
        if self.cflags is not None or uvtaper_m > 0.0:
            x8, rowflags, fr = self.pack(uvtaper_m=uvtaper_m)
            rowflags = np.where((self.flags == 2) & (rowflags == 0),
                                np.int8(2), rowflags.astype(np.int8))
            return x8, rowflags, 1.0 - fr
        from sagecal_tpu import utils
        return (utils.vis_to_x8(self.averaged()), self.flags,
                1.0 - self.flag_ratio)

    def pack(self, uvmin_m: float = 0.0, uvmax_m: float = 1e30,
             uvtaper_m: float = 0.0):
        """Full loadData-semantics packing via the native kernel
        (src/native/tile_pack.cc; data.cpp:552-664): per-channel-flag
        averaging (strictly-more-than-half channels rule), uv-cut/partial
        rows flag=2,
        short-baseline taper, fratio. u/v are stored in seconds ->
        meters via c. Returns (x8 [B, 8] f64, rowflags [B] u8, fratio);
        rows already flagged in ``self.flags`` stay flagged.
        """
        from sagecal_tpu.io import native
        cf = self.cflags
        if cf is None:
            cf = np.zeros((self.nrows, len(self.freqs)), np.uint8)
        cf = cf | (self.flags == 1)[:, None]
        x8, rowflags, fratio = native.pack_tile(
            self.x, cf, self.u * C_M_S, self.v * C_M_S, self.nrows,
            uvmin=uvmin_m, uvmax=uvmax_m, uvtaper_m=uvtaper_m,
            freq0=self.freq0)
        return x8, rowflags, fratio


def row_tslot(nrows: int, nbase: int) -> np.ndarray:
    """[nrows] row -> timeslot index for [tilesz, nbase]-ordered rows."""
    return (np.arange(nrows) // nbase).astype(np.int32)


def generate_baselines(n_stations: int):
    """All cross-correlation pairs (p < q), reference generate_baselines."""
    p, q = np.triu_indices(n_stations, k=1)
    return p.astype(np.int32), q.astype(np.int32)


def uvw_tracks(xyz: np.ndarray, dec0: float, ha: np.ndarray):
    """Baseline uvw (meters) for source hour angles ``ha`` [T] given station
    ITRF-like positions ``xyz`` [N, 3]. Standard synthesis rotation; the
    phase-center RA enters only through ha = LST - ra0, which the caller
    supplies."""
    p, q = generate_baselines(xyz.shape[0])
    bl = xyz[q] - xyz[p]  # [B0, 3]
    sh, ch = np.sin(ha), np.cos(ha)
    sd, cd = np.sin(dec0), np.cos(dec0)
    # [T, B0]
    u = sh[:, None] * bl[None, :, 0] + ch[:, None] * bl[None, :, 1]
    v = (-sd * ch[:, None] * bl[None, :, 0] + sd * sh[:, None] * bl[None, :, 1]
         + cd * bl[None, :, 2])
    w = (cd * ch[:, None] * bl[None, :, 0] - cd * sh[:, None] * bl[None, :, 1]
         + sd * bl[None, :, 2])
    return u, v, w, p, q


def random_array(n_stations: int, extent_m: float = 3000.0,
                 seed: int = 7) -> np.ndarray:
    """Pseudo-random LOFAR-like station layout: dense core + outliers."""
    rng = np.random.default_rng(seed)
    r = extent_m * rng.random(n_stations) ** 2
    th = 2 * np.pi * rng.random(n_stations)
    x = r * np.cos(th)
    y = r * np.sin(th)
    z = rng.normal(0.0, extent_m * 0.01, n_stations)
    return np.stack([x, y, z], axis=1)


def random_jones(n_clusters: int, n_chunks, n_stations: int, seed: int = 3,
                 scale: float = 0.3, diag_dominant: bool = True):
    """Random smooth per-(cluster, chunk, station) 2x2 Jones, padded
    [M, Kmax, N, 2, 2] complex."""
    rng = np.random.default_rng(seed)
    n_chunks = np.asarray(n_chunks)
    kmax = int(n_chunks.max())
    M = n_clusters
    J = (rng.normal(size=(M, kmax, n_stations, 2, 2))
         + 1j * rng.normal(size=(M, kmax, n_stations, 2, 2))) * scale
    if diag_dominant:
        J = J + np.eye(2)[None, None, None]
    return J


def simulate_dataset(sky_arrays, n_stations: int, tilesz: int,
                     freqs, ra0: float, dec0: float, tdelta: float = 10.0,
                     jones: np.ndarray | None = None, nchunk=None,
                     noise_sigma: float = 0.0, seed: int = 11,
                     extent_m: float = 3000.0,
                     flag_fraction: float = 0.0,
                     chan_flag_fraction: float = 0.0,
                     chan_width: float | None = None,
                     beam=None, dobeam: int = 0,
                     start_mjd_s: float = 4.93e9) -> VisTile:
    """Synthesize a corrupted dataset from a device sky model.

    This is the test oracle (SURVEY.md section 4): model visibilities are
    predicted per channel with full spectral scaling, corrupted by ``jones``
    (if given) per cluster, noise added, and packed into a VisTile.
    """
    import jax.numpy as jnp
    from sagecal_tpu.rime import predict as rime_predict

    freqs = np.atleast_1d(np.asarray(freqs, np.float64))
    xyz = random_array(n_stations, extent_m=extent_m, seed=seed)
    ha = np.linspace(0.0, OMEGA_E * tdelta * tilesz, tilesz, endpoint=False)
    u, v, w, p, q = uvw_tracks(xyz, dec0, ha)
    nbase = p.shape[0]
    # flatten [T, B0] row-major: row = t*nbase + bl; seconds
    us = (u / C_M_S).reshape(-1)
    vs = (v / C_M_S).reshape(-1)
    ws = (w / C_M_S).reshape(-1)
    sta1 = np.tile(p, tilesz)
    sta2 = np.tile(q, tilesz)

    if chan_width is None:
        chan_width = (float(freqs[1] - freqs[0]) if len(freqs) > 1
                      else 0.18e6)  # LOFAR-like default channel width
    fdelta_tot = float(freqs[-1] - freqs[0]) + chan_width
    fdelta_chan = fdelta_tot / len(freqs)

    time_mjd = start_mjd_s + tdelta * (np.arange(tilesz) + 0.5)

    from sagecal_tpu.utils import to_np_complex
    beam_kw = {}
    if beam is not None and dobeam:
        if beam.gmst.shape[0] != tilesz:
            raise ValueError(
                f"beam staged with {beam.gmst.shape[0]} timeslots but "
                f"tilesz={tilesz}; out-of-range tslot gathers would "
                f"silently clamp under jit")
        beam_kw = dict(beam=beam, dobeam=dobeam,
                       tslot=jnp.asarray(row_tslot(us.shape[0], nbase)),
                       sta1=jnp.asarray(sta1), sta2=jnp.asarray(sta2))
    coh = rime_predict.coherencies(
        sky_arrays, jnp.asarray(us), jnp.asarray(vs), jnp.asarray(ws),
        jnp.asarray(freqs), fdelta_chan, per_channel_flux=True, **beam_kw)
    coh = to_np_complex(coh)  # [M, B, F, 2, 2]

    M = coh.shape[0]
    if nchunk is None:
        nchunk = np.ones(M, np.int32)
    if jones is not None:
        cidx = rime_predict.chunk_indices(tilesz, nbase, nchunk)
        vis = np.zeros(coh.shape[1:], coh.dtype)
        for m in range(M):
            # host-side einsum: complex arrays cannot cross to device here
            Jp = jones[m][cidx[m], sta1]
            Jq = jones[m][cidx[m], sta2]
            vis += np.einsum("bij,bfjk,blk->bfil", Jp, coh[m], Jq.conj())
    else:
        vis = coh.sum(axis=0)

    rng = np.random.default_rng(seed + 1)
    if noise_sigma > 0:
        vis = vis + noise_sigma * (
            rng.normal(size=vis.shape) + 1j * rng.normal(size=vis.shape))

    flags = np.zeros(us.shape[0], np.int8)
    if flag_fraction > 0:
        nf = int(flag_fraction * len(flags))
        flags[rng.choice(len(flags), nf, replace=False)] = 1
    cflags = None
    if chan_flag_fraction > 0:
        cflags = (rng.random((us.shape[0], len(freqs)))
                  < chan_flag_fraction).astype(np.uint8)

    return VisTile(
        u=us, v=vs, w=ws, x=vis.astype(np.complex128), flags=flags,
        sta1=sta1, sta2=sta2, freqs=freqs, freq0=float(freqs.mean()),
        fdelta=fdelta_tot, tdelta=tdelta, dec0=dec0, ra0=ra0,
        n_stations=n_stations, nbase=nbase, tilesz=tilesz,
        time_mjd=time_mjd, cflags=cflags)


# ---------------------------------------------------------------------------
# SimMS: minimal columnar on-disk dataset (the native MS stand-in)
# ---------------------------------------------------------------------------

class SimMS:
    """Directory dataset: meta.json + per-tile npz files.

    Stands in for a CASA MeasurementSet where casacore is unavailable.
    Supports the reference's streaming tile iteration (MSIter analogue,
    fullbatch_mode.cpp:297) and write-back of residuals
    (Data::writeData, data.cpp:1259).

    Column semantics follow the reference (data.cpp:43-44, -I/-O):
    ``data_column`` (default DATA) is what :meth:`read_tile` returns in
    ``VisTile.x``; :meth:`write_tile` lands in ``out_column`` (default
    CORRECTED_DATA) and NEVER clobbers other columns — so calibrating a
    dataset leaves its DATA intact and re-runs see pristine input,
    exactly like a CASA MeasurementSet.
    """

    META = "meta.json"

    @staticmethod
    def _col_key(column: str) -> str:
        """Column name -> npz key. DATA is the original ``x``; every
        other column gets its own namespaced key in the same npz.
        Names are case-folded (casacore columns are case-insensitive in
        practice), so ``data``/``Data`` alias DATA rather than silently
        naming a different key."""
        norm = "".join(c if c.isalnum() else "_" for c in column.upper())
        if norm == "DATA":
            return "x"
        return "x_" + norm.lower()

    def __init__(self, path: str, data_column: str = "DATA",
                 out_column: str = "CORRECTED_DATA"):
        self.path = path
        self.data_column = data_column
        self.out_column = out_column
        with open(os.path.join(path, self.META)) as f:
            self.meta = json.load(f)

    @classmethod
    def create(cls, path: str, tiles: list[VisTile],
               beam_info=None) -> "SimMS":
        os.makedirs(path, exist_ok=True)
        t0 = tiles[0]
        meta = {
            "n_tiles": len(tiles), "n_stations": t0.n_stations,
            "nbase": t0.nbase, "tilesz": t0.tilesz,
            "freqs": list(map(float, t0.freqs)), "freq0": t0.freq0,
            "fdelta": t0.fdelta, "tdelta": t0.tdelta,
            "ra0": t0.ra0, "dec0": t0.dec0,
        }
        with open(os.path.join(path, cls.META), "w") as f:
            json.dump(meta, f, indent=1)
        ms = cls(path)
        for i, t in enumerate(tiles):
            ms.write_tile(i, t, column="DATA")
        if beam_info is not None:
            from sagecal_tpu.rime import beam as bm
            bm.save_beaminfo(os.path.join(path, "beam.npz"), beam_info)
        return ms

    def beam_info(self):
        """Stored beam metadata (LOFAR_ANTENNA_FIELD analogue) or None."""
        p = os.path.join(self.path, "beam.npz")
        if not os.path.exists(p):
            return None
        from sagecal_tpu.rime import beam as bm
        return bm.load_beaminfo(p)

    @property
    def n_tiles(self) -> int:
        return self.meta["n_tiles"]

    def read_tile(self, i: int) -> VisTile:
        # ms_read: the transient-read chaos seam (sagecal_tpu.faults);
        # recovery lives in the caller's retry layer (sched.Prefetcher)
        faults.inject("ms_read", key=i)
        # "load": the file opened and the columns read out of it
        with dtrace.phase("load", tile=i):
            z = np.load(os.path.join(self.path, f"tile{i:05d}.npz"))
            key = self._col_key(self.data_column)
            if key not in z.files:
                have = [k for k in z.files
                        if k == "x" or k.startswith("x_")]
                raise ValueError(
                    f"{self.path}: column {self.data_column!r} not "
                    f"present in tile {i} (stored data keys: {have})")
            m = self.meta
            return VisTile(
                u=z["u"], v=z["v"], w=z["w"], x=z[key], flags=z["flags"],
                sta1=z["sta1"], sta2=z["sta2"],
                freqs=np.asarray(m["freqs"]), freq0=m["freq0"],
                fdelta=m["fdelta"], tdelta=m["tdelta"], dec0=m["dec0"],
                ra0=m["ra0"], n_stations=m["n_stations"],
                nbase=m["nbase"], tilesz=m["tilesz"],
                time_mjd=z["time_mjd"] if "time_mjd" in z.files else None,
                cflags=z["cflags"] if "cflags" in z.files else None)

    def write_tile(self, i: int, tile: VisTile,
                   column: str | None = None) -> None:
        """Write ``tile.x`` into ``column`` (default: this dataset's
        ``out_column``). Any other data columns already stored in the
        tile file are preserved (Data::writeData writes only OutField,
        data.cpp:1259)."""
        # ms_write: the transient-write chaos seam; the write below is
        # write-then-rename atomic, so the AsyncWriter retry layer can
        # safely re-run this whole method
        faults.inject("ms_write", key=i)
        key = self._col_key(column or self.out_column)
        kw = {}
        path = os.path.join(self.path, f"tile{i:05d}.npz")
        with dtrace.phase("keep"):
            if os.path.exists(path):
                with np.load(path) as z:
                    # keep every other data column AND stored per-tile
                    # metadata the caller's VisTile may not carry
                    kw = {k: z[k] for k in z.files
                          if ((k == "x" or k.startswith("x_"))
                              and k != key)
                          or k in ("time_mjd", "cflags")}
        if tile.time_mjd is not None:
            kw["time_mjd"] = tile.time_mjd
        if tile.cflags is not None:
            kw["cflags"] = tile.cflags
        kw[key] = tile.x
        # write-then-rename: a crash mid-writeback must not truncate the
        # tile file and take the pristine DATA column with it (the tmp
        # name ends in .npz so np.savez does not append a suffix)
        tmp = path + ".tmp.npz"
        with dtrace.phase("savez"):
            np.savez(tmp, u=tile.u, v=tile.v, w=tile.w, flags=tile.flags,
                     sta1=tile.sta1, sta2=tile.sta2, **kw)
        with dtrace.phase("replace"):
            os.replace(tmp, path)

    def tiles(self):
        for i in range(self.n_tiles):
            yield i, self.read_tile(i)

    def tiles_prefetch(self, depth: int = 2):
        return _tiles_prefetch_impl(self, depth)


class MultiSimMS:
    """Several SimMS datasets presented as ONE dataset with the combined
    channel axis — the ``-f MSlist`` multi-MS joint calibration (P8):
    ``Data::loadDataList`` (src/MS/data.cpp:835) channel-averages across
    every MS's channels into one solve vector (the more-than-half rule
    counts unflagged channels over ALL MSs), and ``writeDataList``
    (data.cpp:1304) splits residual channels back per MS.

    All parts must agree on stations/baselines/tile structure — the same
    consistency requirement the MPI master enforces
    (sagecal_master.cpp:239-284). Parts are ordered by mean frequency so
    the combined channel axis is monotone.
    """

    def __init__(self, paths, tilesz: int = 10, data_column: str = "DATA",
                 out_column: str = "CORRECTED_DATA"):
        if isinstance(paths, str):
            paths = [paths]
        if not paths:
            raise ValueError("MultiSimMS: empty dataset list")
        parts = [open_part(p, tilesz, data_column, out_column)
                 for p in paths]
        parts.sort(key=lambda m: float(np.mean(m.meta["freqs"])))
        m0 = parts[0].meta
        for mx in parts[1:]:
            for key in ("n_stations", "nbase", "tilesz", "n_tiles",
                        "tdelta", "ra0", "dec0"):
                if mx.meta[key] != m0[key]:
                    raise ValueError(
                        f"dataset {mx.path}: {key} mismatch "
                        f"({mx.meta[key]} vs {m0[key]})")
        self.parts = parts
        self.path = ",".join(p.path for p in parts)
        freqs = np.concatenate([np.asarray(p.meta["freqs"], float)
                                for p in parts])
        self._nchan = [len(p.meta["freqs"]) for p in parts]
        self.meta = dict(m0)
        self.meta["freqs"] = list(map(float, freqs))
        # reference freq0 = mean over ALL channels of all MSs
        # (readAuxDataList data.cpp:487-505 accumulates every channel of
        # every MS and divides by the total channel count)
        self.meta["freq0"] = float(freqs.mean())
        self.meta["fdelta"] = float(sum(p.meta["fdelta"] for p in parts))

    @property
    def n_tiles(self) -> int:
        return self.meta["n_tiles"]

    def beam_info(self):
        return self.parts[0].beam_info()

    def read_tile(self, i: int) -> VisTile:
        tiles = [p.read_tile(i) for p in self.parts]
        t0 = tiles[0]
        x = np.concatenate([t.x for t in tiles], axis=1)
        flags = np.zeros(t0.nrows, np.int8)
        # a row is flagged only if flagged in every MS; uv-cut (2) wins
        # only when nothing is plain-flagged
        allf = np.stack([t.flags for t in tiles])
        flags[np.all(allf == 1, axis=0)] = 1
        uvcut = np.any(allf == 2, axis=0) & (flags == 0)
        flags[uvcut] = 2
        # per-channel flags: a row flagged in ONE MS must not leak into
        # the channel average (loadDataList's nflag counts unflagged
        # channels across ALL MSs, data.cpp:899-921) — synthesize cflags
        # from each part's row flags whenever parts disagree or any part
        # carries channel flags
        flags_differ = not all(
            np.array_equal(t.flags, tiles[0].flags) for t in tiles[1:])
        cfl = None
        if flags_differ or any(t.cflags is not None for t in tiles):
            cfl = np.concatenate(
                [((t.cflags if t.cflags is not None
                   else np.zeros((t.nrows, len(t.freqs)), np.uint8))
                  | (t.flags == 1)[:, None].astype(np.uint8))
                 for t in tiles], axis=1)
        return VisTile(
            u=t0.u, v=t0.v, w=t0.w, x=x, flags=flags,
            sta1=t0.sta1, sta2=t0.sta2,
            freqs=np.asarray(self.meta["freqs"]),
            freq0=self.meta["freq0"], fdelta=self.meta["fdelta"],
            tdelta=t0.tdelta, dec0=t0.dec0, ra0=t0.ra0,
            n_stations=t0.n_stations, nbase=t0.nbase, tilesz=t0.tilesz,
            time_mjd=t0.time_mjd, cflags=cfl)

    def write_tile(self, i: int, tile: VisTile) -> None:
        """Split the combined channel axis back per MS (writeDataList)."""
        lo = 0
        for p, nc in zip(self.parts, self._nchan):
            part_tile = p.read_tile(i)
            # only residual data is written back; each part keeps its own
            # flags (writeDataList writes the data column only)
            part_tile.x = tile.x[:, lo:lo + nc]
            p.write_tile(i, part_tile)
            lo += nc

    def tiles(self):
        for i in range(self.n_tiles):
            yield i, self.read_tile(i)

    def tiles_prefetch(self, depth: int = 2):
        return _tiles_prefetch_impl(self, depth)


def open_part(path: str, tilesz: int = 10, data_column: str = "DATA",
              out_column: str = "CORRECTED_DATA"):
    """One dataset path -> CasaMS (casacore table) or SimMS. Every place
    that consumes a subband path (cli_mpi slaves, federated slaves,
    MultiSimMS parts) dispatches through here so real MeasurementSets
    work wherever SimMS directories do."""
    from sagecal_tpu.io import casams
    if casams.is_ms_path(path):
        if not casams.have_casacore():
            raise RuntimeError(
                f"{path} is a CASA table but python-casacore is not "
                f"installed; install it or convert to a SimMS directory")
        return casams.CasaMS(path, tilesz=tilesz, data_column=data_column,
                             out_column=out_column)
    return SimMS(path, data_column=data_column, out_column=out_column)


def open_dataset(ms: str | None, ms_list: str | None = None,
                 tilesz: int = 10, data_column: str = "DATA",
                 out_column: str = "CORRECTED_DATA"):
    """Resolve -d/-f into a dataset: a CASA MeasurementSet (python-casacore
    backend) when the path is a casacore table, a single SimMS, or a
    MultiSimMS from a glob pattern / list file (fullbatch_mode.cpp:255-262
    dispatch).

    ``-f``/``ms_list`` takes precedence over ``-d`` when both are given
    (the reference's loadDataList dispatch order)."""
    if ms and not ms_list:
        return open_part(ms, tilesz, data_column, out_column)
    if ms_list:
        import glob as globmod
        if os.path.isfile(ms_list):
            with open(ms_list) as f:
                stripped = (ln.strip() for ln in f)
                paths = [ln for ln in stripped
                         if ln and not ln.startswith("#")]
        else:
            paths = sorted(globmod.glob(ms_list))
        if not paths:
            raise ValueError(f"-f {ms_list}: no datasets found")
        if len(paths) == 1:
            return open_part(paths[0], tilesz, data_column, out_column)
        return MultiSimMS(paths, tilesz=tilesz, data_column=data_column,
                          out_column=out_column)
    raise ValueError("open_dataset: need -d dataset or -f list")


def _tiles_prefetch_impl(dataset, depth: int = 2):
    """Tile iterator with background read-ahead: the host overlaps
    disk I/O with the device solve of the previous tile (the
    streaming analogue of the reference's synchronous per-tile MSIter
    loop; SURVEY.md section 5 'host streaming'). ``depth <= 0`` reads
    inline (the synchronous reference path). Built on
    :class:`sagecal_tpu.sched.Prefetcher`, which also propagates
    reader-thread exceptions with their original traceback."""
    from sagecal_tpu import sched

    for i, tile, _wait in sched.Prefetcher(dataset.read_tile,
                                           dataset.n_tiles, depth=depth):
        yield i, tile
