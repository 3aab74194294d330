"""The plain reference of the array-beam deployment (``-B 1``): numpy,
float64, nothing of the program (and no jax).  Beside ``reference.py``,
which it uses for the array, the uvw tracks, the sky's text, the Jones
sandwich and the solutions text, it holds what upstream's ``-B 1`` adds to
a calibration (``arraybeam``, stationbeam.c:44-110, as
``precalculate_coherencies_withbeam`` folds it into the source sum,
predict_withbeam.c:522):

    V_pq(t) = sum_m J_pm ( sum_s g_s(t, p) g_s(t, q) I_s e^{i phi_pqs(t)}
                           |sinc| ) J_qm^H ,

    g_s(t, n) = | 1/K_n sum_k exp(-i 2 pi / c (f0 s0(t, n) - f s_s(t, n))
                                  . p_nk) |   , 0 under the horizon,

with ``p_nk`` the ``K_n`` live elements of station ``n``, ``s`` the unit
vector towards the source and ``s0`` towards the beam's pointing as
station ``n`` sees them at time ``t``, ``f`` the channel's frequency and
``f0`` the beamformer's.  Everything is derived here from published
descriptions; what is taken, in words:

- **Frame of a station.**  A direction at azimuth ``az`` (from north
  through east) and elevation ``el`` has the components
  ``(cos el cos az, -cos el sin az, sin el)``: x points to the local
  north, y to the local WEST, z up.  Element offsets ``p_nk`` are in that
  frame (it is the one in which upstream's ``theta = pi/2 - el, phi =
  -az`` are the polar angles, stationbeam.c:63-67).
- **Hour angle.**  ``H = GMST + longitude - ra`` (east longitudes
  positive), so a source east of the meridian has ``H < 0`` and an
  azimuth between 0 and pi.  ``sin el = sin lat sin dec + cos lat cos dec
  cos H``; north ``= sin dec cos lat - cos dec sin lat cos H``, east
  ``= -cos dec sin H`` (spherical triangle pole-zenith-source).
- **GMST** is the IAU 1982 expression in degrees (Meeus, Astronomical
  Algorithms, 12.4), of UT taken equal to the time stamp.
- **Precession** of J2000 positions to the epoch of date is the IAU 1976
  rotation by the three angles zeta, z, theta (Lieske 1979; Meeus 21.2-4).
  Upstream applies its precession once a run, at the first tile's mid
  timeslot (``precess_source_locations``, data.cpp:1473, called at
  fullbatch_mode.cpp:325 only with the beam on), to the sources'
  positions as the beam sees them and to the pointing; the fringe phases
  keep the catalogue's direction cosines.  So does this file.

Departures, each followed or stated:

1. **Element layouts are assumed**, no ``LOFAR_ANTENNA_FIELD`` is at hand:
   HBA tiles on a 5.15 m square grid (centred between four grid points),
   the ``K`` nearest the centre kept (a disc; ties go to the larger
   ``|x|``), 24 for each of the core's "ears" (``reference.station_layout``
   puts the core first), 48 for a remote station, each station turned by
   an angle drawn from ``layout_seed``, ``z = 0``.  A tile's own
   sixteen-dipole beamformer is not in ``arraybeam`` and not here.
2. **Longitude and latitude** of a station are the layout's east and north
   offsets (``reference.station_layout``: x east, y north) about the LOFAR
   core, 6.869 deg E, 52.915 deg N, on a sphere of 6371 km.  The uvw
   tracks keep reading the same three numbers as an equatorial frame
   (``reference.tile_uvw``): the two readings are not one geometry, and
   neither the program nor this file needs them to be.
3. **Time stamps.**  The observation starts at the first instant after
   MJD-second 4.93e9 (``datagen.vis_tile``'s epoch, 2015) at which the
   hour angle of the pointing (of date) at the core is the seed's
   ``ha0``, so that uvw, GMST and beam describe one sky; timeslot ``k``
   is stamped at its centre.  ``reference.tile_uvw`` advances the hour
   angle by ``OMEGA_E`` a second and GMST by 1.2e-8 more; over the 32
   tiles of a run that is 3e-8 rad.
4. **Unpolarised points at the catalogue frequency**, as ``reference.py``.
5. ``gains(..., precessed=False)`` and ``dtype`` / ``passes`` exist for
   the CONTROLS only: the gains of a program that skipped precession, and
   the Jones products in a narrower type (``reference.product``).
6. Tiles are independent draws (``[seed, 2, tile]``), so ``make_tiles``
   may compute them in a pool of processes; the numbers are those of the
   serial call.
"""

from __future__ import annotations

import math
import multiprocessing
import os

import numpy as np

import reference

C_M_S = reference.C_M_S
LON0 = math.radians(6.869)          # the LOFAR core
LAT0 = math.radians(52.915)
EARTH_R_M = 6371000.0
PITCH_M = 5.15                      # HBA tile pitch
EPOCH_MJD_S = 4.93e9                # datagen.vis_tile's epoch
ASEC = math.pi / (180 * 3600)


# -- stations and their elements ---------------------------------------------

def grid_disc(count: int) -> np.ndarray:
    """[count, 2]: the ``count`` points nearest the centre of a square
    grid of pitch 1 whose centre lies between four points; ties in
    distance go to the larger ``|x|``, then by ``(x, y)``."""
    half = int(math.ceil(math.sqrt(count))) + 1
    ax = np.arange(-half, half) + 0.5
    x, y = (a.reshape(-1) for a in np.meshgrid(ax, ax, indexing="ij"))
    order = np.lexsort((y, x, np.abs(y) > np.abs(x), x * x + y * y))
    return np.stack([x, y], axis=1)[order[:count]]


def station_elements(n_stations: int, layout_seed: int, n_core_elem: int,
                     n_remote_elem: int):
    """(xyz [N, Emax, 3] metres in the station's (north, west, up) frame,
    mask [N, Emax]): departure 1.  Masked slots hold zeros."""
    n_core = max(1, (n_stations * 48) // 62)    # reference.station_layout
    counts = [n_core_elem] * n_core + [n_remote_elem] * (n_stations - n_core)
    emax = max(counts)
    turn = np.random.default_rng([int(layout_seed), 48]).uniform(
        0, 2 * np.pi, n_stations)
    xyz = np.zeros((n_stations, emax, 3))
    mask = np.zeros((n_stations, emax), bool)
    for n, k in enumerate(counts):
        g = PITCH_M * grid_disc(k)
        c, s = math.cos(turn[n]), math.sin(turn[n])
        xyz[n, :k, 0] = c * g[:, 0] - s * g[:, 1]
        xyz[n, :k, 1] = s * g[:, 0] + c * g[:, 1]
        mask[n, :k] = True
    return xyz, mask


def station_lonlat(layout_xyz: np.ndarray):
    """(longitude [N], latitude [N]) in radians: departure 2."""
    east, north = layout_xyz[:, 0], layout_xyz[:, 1]
    return (LON0 + east / (EARTH_R_M * math.cos(LAT0)),
            LAT0 + north / EARTH_R_M)


# -- time, sidereal angle, precession, horizon coordinates -------------------

def gmst_rad(mjd_s) -> np.ndarray:
    """Greenwich mean sidereal angle in radians, [0, 2 pi), of a time in
    MJD seconds (Meeus 12.4)."""
    d = np.asarray(mjd_s, np.float64) / 86400.0 - 51544.5   # days of J2000
    t = d / 36525.0
    # 360.98564736629 d = 360 d + 0.98564736629 d: whole turns dropped
    # before they cost digits
    deg = (280.46061837 + 360.0 * (d % 1.0) + 0.98564736629 * d
           + t * t * (0.000387933 - t / 38710000.0))
    return np.deg2rad(deg % 360.0)


#: d GMST / dt in rad/s (the linear term above)
GMST_RATE = math.radians(360.98564736629) / 86400.0


def precess(ra, dec, mjd_s: float):
    """(ra, dec) of date from J2000 (IAU 1976: Meeus 21.2-21.4)."""
    t = (mjd_s / 86400.0 - 51544.5) / 36525.0
    zeta = (2306.2181 * t + 0.30188 * t * t + 0.017998 * t ** 3) * ASEC
    z = (2306.2181 * t + 1.09468 * t * t + 0.018203 * t ** 3) * ASEC
    theta = (2004.3109 * t - 0.42665 * t * t - 0.041833 * t ** 3) * ASEC
    ra, dec = np.asarray(ra, np.float64), np.asarray(dec, np.float64)
    a = np.cos(dec) * np.sin(ra + zeta)
    b = (math.cos(theta) * np.cos(dec) * np.cos(ra + zeta)
         - math.sin(theta) * np.sin(dec))
    c = (math.sin(theta) * np.cos(dec) * np.cos(ra + zeta)
         + math.cos(theta) * np.sin(dec))
    return np.arctan2(a, b) + z, np.arcsin(np.clip(c, -1.0, 1.0))


def direction(ra, dec, lon, lat, gmst):
    """(north, west, up) components of the unit vector towards (ra, dec)
    as seen from (lon, lat) at sidereal angle ``gmst``; arguments
    broadcast."""
    h = gmst + lon - ra
    north = np.sin(dec) * np.cos(lat) - np.cos(dec) * np.sin(lat) * np.cos(h)
    east = -np.cos(dec) * np.sin(h)
    up = np.sin(lat) * np.sin(dec) + np.cos(lat) * np.cos(dec) * np.cos(h)
    return north, -east, up


def azel(ra, dec, lon, lat, gmst):
    """(azimuth from north through east in [0, 2 pi), elevation)."""
    north, west, up = direction(ra, dec, lon, lat, gmst)
    return np.arctan2(-west, north) % (2 * np.pi), np.arcsin(
        np.clip(up, -1.0, 1.0))


def array_factor(ra, dec, ra0, dec0, lon, lat, gmst, elem, mask,
                 freq: float, freq0: float) -> np.ndarray:
    """[S, T, N]: the array-factor gain of sources (ra, dec) [S] at the
    sidereal angles ``gmst`` [T] for stations at (lon, lat) [N] with
    elements ``elem`` [N, E, 3] (``mask`` [N, E]), beamformed at ``freq0``
    towards (ra0, dec0) and evaluated at ``freq``."""
    ra, dec = np.asarray(ra)[:, None, None], np.asarray(dec)[:, None, None]
    g, lo, la = gmst[None, :, None], lon[None, None, :], lat[None, None, :]
    s = direction(ra, dec, lo, la, g)                   # 3 x [S, T, N]
    s0 = direction(ra0, dec0, lo, la, g)                # 3 x [1, T, N]
    r = [freq0 * b - freq * a for a, b in zip(s, s0)]
    out = np.empty(r[0].shape)
    k = 2 * np.pi / C_M_S
    for n in range(elem.shape[0]):                      # [S, T, E] a station
        p = elem[n, mask[n]]
        ph = -k * (r[0][:, :, n, None] * p[:, 0] + r[1][:, :, n, None]
                   * p[:, 1] + r[2][:, :, n, None] * p[:, 2])
        out[:, :, n] = np.abs(np.exp(1j * ph).mean(axis=-1))
    return np.where(s[2] >= 0.0, out, 0.0)


# -- the sky's positions ------------------------------------------------------

def radec_from_text(sky_lines, cluster_lines):
    """(ra [M, S], dec [M, S]) J2000, radians: this file's own reading of
    the LSM text's second to seventh columns (RA h m s, Dec d m s)."""
    src = {}
    for ln in sky_lines:
        t = ln.split()
        ra = (abs(float(t[1])) + float(t[2]) / 60 + float(t[3]) / 3600) \
            * math.pi / 12
        sign = -1.0 if t[4].startswith("-") else 1.0
        dec = sign * (abs(float(t[4])) + float(t[5]) / 60
                      + float(t[6]) / 3600) * math.pi / 180
        src[t[0]] = (ra, dec)
    a = np.asarray([[src[nm] for nm in ln.split()[2:]]
                    for ln in cluster_lines], np.float64)
    return a[..., 0], a[..., 1]


# -- the observation ----------------------------------------------------------

class Observation(reference.Observation):
    """``reference.Observation`` (same array, sky, hour angle, Jones and
    noise from the same seed) seen through the stations' array beams.

    The configuration gives ``beam_elements_core`` and
    ``beam_elements_remote`` (live elements a station) and optionally
    ``beam_freq_hz`` (the beamformer's frequency; the channel's when
    absent)."""

    def __init__(self, cfg: dict, seed: int):
        super().__init__(cfg, seed)
        self.elem, self.mask = station_elements(
            self.n_sta, int(cfg["layout_seed"]),
            int(cfg["beam_elements_core"]), int(cfg["beam_elements_remote"]))
        self.lon, self.lat = station_lonlat(self.xyz)
        self.freq0 = float(cfg.get("beam_freq_hz", self.freq))
        self.ra_j2000, self.dec_j2000 = radec_from_text(self.sky_lines,
                                                        self.cluster_lines)
        # departure 3: the pointing (of date) stands at hour angle ha0 at
        # the core in the middle of timeslot 0
        first = EPOCH_MJD_S + 0.5 * self.tdelta
        ra0_date, _ = precess(self.ra0, self.dec0, first)
        ahead = self.ha0 - (float(gmst_rad(first)) + LON0 - float(ra0_date))
        first += (ahead % (2 * np.pi)) / GMST_RATE      # under a sidereal day
        # GMST's quadratic term over that day, 1e-12 rad: once more, signed
        ahead = self.ha0 - (float(gmst_rad(first)) + LON0 - float(ra0_date))
        first += ((ahead + np.pi) % (2 * np.pi) - np.pi) / GMST_RATE
        self.t_start = first - 0.5 * self.tdelta
        self.epoch = float(self.time_mjd(0)[self.tilesz // 2])
        self.ra_date, self.dec_date = precess(self.ra_j2000, self.dec_j2000,
                                              self.epoch)
        self.point_date = tuple(float(a) for a in precess(
            self.ra0, self.dec0, self.epoch))
        self.row_slot = np.repeat(np.arange(self.tilesz), self.nbase)
        self.kept = {}          # tile -> [M, B] beam-weighted coherencies

    def time_mjd(self, tile: int) -> np.ndarray:
        """[tilesz] MJD seconds, each timeslot's centre."""
        return self.t_start + self.tdelta * (
            tile * self.tilesz + np.arange(self.tilesz) + 0.5)

    def gains(self, tile: int, precessed: bool = True) -> np.ndarray:
        """[M, S, T, N] array-factor gains of every source at every
        timeslot of ``tile`` and station.  ``precessed`` False (the
        control): catalogue positions and pointing in place of those of
        date."""
        ra, dec = ((self.ra_date, self.dec_date) if precessed
                   else (self.ra_j2000, self.dec_j2000))
        ra0, dec0 = self.point_date if precessed else (self.ra0, self.dec0)
        gmst = gmst_rad(self.time_mjd(tile))
        return np.stack([array_factor(
            ra[m], dec[m], ra0, dec0, self.lon, self.lat, gmst, self.elem,
            self.mask, self.freq, self.freq0) for m in range(self.n_dir)])

    def coherencies(self, tile: int, precessed: bool = True) -> np.ndarray:
        """[M, B] complex: each direction's scalar coherency with
        ``g_p g_q`` folded into the source sum.  Those of date are kept
        (``kept``) for the check."""
        if precessed and tile in self.kept:
            return self.kept[tile]
        u, v, w, s1, s2 = self.geometry(tile)
        ll, mm, nn, flux = self.sky
        gains = self.gains(tile, precessed)
        out = np.empty((self.n_dir, self.nrows), np.complex128)
        for m in range(self.n_dir):
            gt = np.moveaxis(gains[m], 0, -1)           # [T, N, S]
            for r in range(0, self.nrows, _ROW_BLOCK):
                b = slice(r, r + _ROW_BLOCK)
                g = 2 * np.pi * (u[b, None] * ll[m] + v[b, None] * mm[m]
                                 + w[b, None] * nn[m])  # [B', S] seconds
                smear = np.abs(np.sinc(g * (0.5 * self.fdelta) / np.pi))
                amp = flux[m] * smear * gt[self.row_slot[b], s1[b]] \
                    * gt[self.row_slot[b], s2[b]]
                out[m, b] = np.sum(amp * np.exp(1j * g * self.freq), axis=1)
        if precessed:
            self.kept[tile] = out
        return out

    def model(self, tile: int, jones: np.ndarray, rows=None, dtype=None,
              passes: int = 1, precessed: bool = True) -> np.ndarray:
        """Model visibilities [B', 2, 2] of ``tile`` under ``jones`` with
        the beam's gains, on all rows or on ``rows``."""
        _, _, _, s1, s2 = self.geometry(tile)
        coh = self.coherencies(tile, precessed)
        if rows is not None:
            coh, s1, s2 = coh[:, rows], s1[rows], s2[rows]
        return reference.model(jones, coh, s1, s2, dtype=dtype,
                               passes=passes)

    def apparent_flux(self, tile: int = 0) -> np.ndarray:
        """[M]: each cluster's summed flux weighted by the squared gain,
        mean over timeslots and stations: what the array sees of it."""
        g = self.gains(tile)
        return np.sum(self.sky[3][:, :, None, None] * g * g,
                      axis=1).mean(axis=(1, 2))


# -- tiles in a pool of processes ---------------------------------------------

_WORKER = None
#: rows a block of the source sum: [B', S] temporaries of a few MB
_ROW_BLOCK = 2048
_THREAD_VARS = ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS", "MKL_NUM_THREADS")


def _pool_start(cfg, seed):
    global _WORKER
    _WORKER = Observation(cfg, seed)


def _pool_tile(tile):
    return tile, _WORKER.coherencies(tile), _WORKER.data(tile)


def make_tiles(obs: Observation, n_tiles: int, workers: int = 0):
    """The observed visibilities [B, 2, 2] of tiles 0 .. ``n_tiles`` - 1,
    with each tile's beam-weighted coherencies kept on ``obs``.
    ``workers`` > 1: that many fresh processes (spawned: they import
    numpy and this file, nothing the caller has loaded) compute a tile
    each; the numbers are the serial call's (departure 6)."""
    if workers <= 1:
        return [obs.data(t) for t in range(n_tiles)]
    # few workers, one thread each, each gone after four tiles: on the chip's
    # machine 13 workers took 28 GB of its 40 that no process showed as
    # resident, until the pool ended (PERF.md section 7, Open after PR 48)
    threads = {k: os.environ.get(k) for k in _THREAD_VARS}
    os.environ.update(dict.fromkeys(_THREAD_VARS, "1"))
    try:
        ctx = multiprocessing.get_context("spawn")
        with ctx.Pool(min(workers, n_tiles), _pool_start,
                      (obs.cfg, obs.seed), maxtasksperchild=4) as pool:
            out = [None] * n_tiles
            for tile, coh, x in pool.imap_unordered(_pool_tile,
                                                    range(n_tiles)):
                obs.kept[tile], out[tile] = coh, x
    finally:
        for k, v in threads.items():
            os.environ.pop(k) if v is None else os.environ.update({k: v})
    return out
