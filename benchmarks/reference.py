"""The benchmark's plain reference: numpy, float64, nothing of the program.

It holds everything that decides ``correct`` and every input the cells
are fed with:

- the array (a LOFAR-NL-like layout: 48 core + 14 remote stations when
  N = 62) and its uvw tracks, advancing in hour angle from tile to tile;
- the point-source sky in the LSM text format the program reads, and its
  direction cosines as this file works them out from that same text;
- the radio interferometer measurement equation for point sources,

      V_pq = sum_m J_pm ( sum_s I_s e^{+2 pi i f (u l + v m + w (n-1))}
                          |sinc(pi fdelta (u l + v m + w (n-1)))| ) J_qm^H

  with u, v, w in seconds.  The sign of the phase, the ``n - 1`` and the
  channel-smearing factor are upstream SAGECal's (predict.c:270-415);
- the visibilities of an observation (true Jones, noise), and the
  upstream solutions-file text format, read and written.

Nothing here imports jax or ``sagecal_tpu``.  ``dtype`` and ``passes``
arguments exist for the control: the same arithmetic with the products
of the Jones sandwich made in a lower precision (see ``product``).
"""

from __future__ import annotations

import math

import numpy as np

C_M_S = 299792458.0
OMEGA_E = 7.2921150e-5          # earth rotation, rad/s


# -- array and tracks --------------------------------------------------------

def station_layout(n_stations: int, seed: int) -> np.ndarray:
    """[N, 3] station positions in metres (local east, north, up -> used
    as an ITRF-like frame).  Four fifths of the stations (48 of 62) form
    a core inside 2 km, dense towards the centre; the rest are remote
    stations at 3-30 km, log-uniform."""
    rng = np.random.default_rng(seed)
    n_core = max(1, (n_stations * 48) // 62)
    r = np.concatenate([
        2000.0 * rng.random(n_core) ** 2 + 30.0,
        np.exp(rng.uniform(math.log(3e3), math.log(3e4),
                           n_stations - n_core))])
    th = 2 * np.pi * rng.random(n_stations)
    z = rng.normal(0.0, 5.0, n_stations)
    return np.stack([r * np.cos(th), r * np.sin(th), z], axis=1)


def baselines(n_stations: int):
    """All pairs p < q, in the order rows are stored."""
    p, q = np.triu_indices(n_stations, k=1)
    return p.astype(np.int32), q.astype(np.int32)


def tile_uvw(xyz: np.ndarray, dec0: float, ha0: float, tile: int,
             tilesz: int, tdelta: float):
    """u, v, w of one tile in SECONDS, rows ordered [timeslot, baseline],
    with the stations of each row.  Tile ``tile`` starts at hour angle
    ``ha0 + tile * tilesz * tdelta * OMEGA_E``."""
    p, q = baselines(xyz.shape[0])
    ha = ha0 + OMEGA_E * tdelta * (tile * tilesz + np.arange(tilesz))
    bl = (xyz[q] - xyz[p]) / C_M_S                      # [B0, 3] seconds
    sh, ch = np.sin(ha)[:, None], np.cos(ha)[:, None]
    sd, cd = math.sin(dec0), math.cos(dec0)
    u = sh * bl[:, 0] + ch * bl[:, 1]
    v = -sd * ch * bl[:, 0] + sd * sh * bl[:, 1] + cd * bl[:, 2]
    w = cd * ch * bl[:, 0] - cd * sh * bl[:, 1] + sd * bl[:, 2]
    return (u.reshape(-1), v.reshape(-1), w.reshape(-1),
            np.tile(p, tilesz), np.tile(q, tilesz))


# -- sky ---------------------------------------------------------------------

def draw_sky(n_clusters: int, n_sources: int, seed: int, ra0: float,
             dec0: float, log_flux_mean: float, freq0: float, fmt: int = 0):
    """LSM text lines and cluster-file lines of ``n_clusters`` directions
    of ``n_sources`` point sources each: direction centres about 1.7
    degrees (0.03 rad) from the phase centre, sources 0.2 degrees around
    their centre, log-normal fluxes, spectral index -0.7 at ``freq0``.
    ``fmt`` 1 writes the three-term spectral index of ``-F 1`` (the
    second and third terms zero), 0 the single one."""
    rng = np.random.default_rng(seed)
    spec = "-0.7 0 0" if fmt else "-0.7"
    sky, clusters = [], []
    for m in range(n_clusters):
        cra = ra0 + rng.normal(0, 0.03) / math.cos(dec0)
        cdec = dec0 + rng.normal(0, 0.03)
        names = []
        for s in range(n_sources):
            name = f"P{m:02d}_{s:03d}"           # leading P: a point source
            ra = cra + rng.normal(0, 0.0035) / math.cos(dec0)
            dec = cdec + rng.normal(0, 0.0035)
            flux = math.exp(rng.normal(log_flux_mean, 0.8))
            h = (ra % (2 * math.pi)) * 12 / math.pi
            hh, hm = int(h), int((h - int(h)) * 60)
            hs = ((h - hh) * 60 - hm) * 60
            d = math.degrees(dec)
            dd, dm = int(d), int((d - int(d)) * 60)
            dsec = ((d - dd) * 60 - dm) * 60
            sky.append(f"{name} {hh} {hm} {hs:.9f} {dd} {dm} {dsec:.8f} "
                       f"{flux:.8f} 0 0 0 {spec} 0 0 0 0 {freq0:.1f}")
            names.append(name)
        clusters.append(f"{m + 1} 1 " + " ".join(names))
    return sky, clusters


def sky_from_text(sky_lines, cluster_lines, ra0: float, dec0: float):
    """(l, m, n-1, I) each [M, S] from the text the program is given:
    this file's own reading of the LSM format (name, RA h m s, Dec d m s,
    I Q U V, spectral index in one term or three, RM, extent x3, f0).
    Fluxes are taken at f0: every cell observes at the catalogue
    frequency, so neither format's spectral terms are read."""
    src = {}
    for ln in sky_lines:
        t = ln.split()
        ra = (abs(float(t[1])) + float(t[2]) / 60 + float(t[3]) / 3600) \
            * math.pi / 12
        sign = -1.0 if t[4].startswith("-") else 1.0
        dec = sign * (abs(float(t[4])) + float(t[5]) / 60
                      + float(t[6]) / 3600) * math.pi / 180
        ll = math.cos(dec) * math.sin(ra - ra0)
        mm = (math.sin(dec) * math.cos(dec0)
              - math.cos(dec) * math.sin(dec0) * math.cos(ra - ra0))
        src[t[0]] = (ll, mm, math.sqrt(1 - ll * ll - mm * mm) - 1.0,
                     float(t[7]))
    rows = [[src[nm] for nm in ln.split()[2:]] for ln in cluster_lines]
    a = np.asarray(rows, np.float64)                   # [M, S, 4]
    return a[..., 0], a[..., 1], a[..., 2], a[..., 3]


# -- measurement equation ----------------------------------------------------

def coherencies(sky, u, v, w, freq: float, fdelta: float) -> np.ndarray:
    """[M, B] complex: each direction's scalar coherency (unpolarised
    point sources: the 2x2 coherency is this number times the identity)."""
    ll, mm, nn, flux = sky
    out = np.empty((ll.shape[0], u.shape[0]), np.complex128)
    for m in range(ll.shape[0]):
        g = 2 * np.pi * (u[:, None] * ll[m] + v[:, None] * mm[m]
                         + w[:, None] * nn[m])         # [B, S] seconds
        x = g * (0.5 * fdelta)
        smear = np.abs(np.sinc(x / np.pi))
        out[m] = np.sum(flux[m] * smear * np.exp(1j * g * freq), axis=1)
    return out


def round_to(a: np.ndarray, dtype) -> np.ndarray:
    """``a`` rounded to ``dtype`` and back to float64/complex128 (real
    and imaginary parts apart).  ``None`` leaves it alone."""
    if dtype is None:
        return a
    if np.iscomplexobj(a):
        return (a.real.astype(dtype).astype(np.float64)
                + 1j * a.imag.astype(dtype).astype(np.float64))
    return a.astype(dtype).astype(np.float64)


def split(a: np.ndarray, dtype, passes: int):
    """``a`` as a sum of ``passes`` terms of ``dtype``, leading term
    first: what a matrix unit that multiplies in several passes of a
    narrow type is fed."""
    terms, rest = [], a
    for _ in range(passes):
        terms.append(round_to(rest, dtype))
        rest = rest - terms[-1]
    return terms


def product(f, a: np.ndarray, b: np.ndarray, dtype, passes: int):
    """``f(a, b)``, bilinear, as a matrix unit computes it: exactly when
    ``dtype`` is None; else from operands rounded to ``dtype``, in one
    pass, or in three (``a1 b1 + a1 b2 + a2 b1``, the TPU's ``high``:
    the terms of the order of the narrow type's rounding squared are
    dropped).  Sums stay in float64."""
    if dtype is None:
        return f(a, b)
    if passes == 1:
        return f(round_to(a, dtype), round_to(b, dtype))
    (a1, a2), (b1, b2) = split(a, dtype, 2), split(b, dtype, 2)
    return f(a1, b1) + f(a1, b2) + f(a2, b1)


def model(jones: np.ndarray, coh: np.ndarray, sta1, sta2,
          dtype=None, passes: int = 1) -> np.ndarray:
    """sum_m coh[m, b] J[m, p_b] J[m, q_b]^H -> [B, 2, 2] complex.

    ``dtype`` and ``passes`` (the control): both products of the
    sandwich as ``product`` makes them."""
    out = np.zeros((coh.shape[1], 2, 2), np.complex128)
    for m in range(coh.shape[0]):
        left = product(lambda j, c: j * c[:, None, None],
                       jones[m][sta1], coh[m], dtype, passes)
        out += product(lambda a, j: np.einsum("bij,bkj->bik", a, j.conj()),
                       left, jones[m][sta2], dtype, passes)
    return out


# -- observations ------------------------------------------------------------

def draw_jones(n_clusters: int, n_stations: int, scale: float, rng):
    """I + scale * CN(0, 1) per (direction, station): [M, N, 2, 2]."""
    shape = (n_clusters, n_stations, 2, 2)
    return (np.eye(2) + scale * (rng.normal(size=shape)
                                 + 1j * rng.normal(size=shape)))


def draw_noise(n_rows: int, sigma: float, rng) -> np.ndarray:
    shape = (n_rows, 2, 2)
    return sigma * (rng.normal(size=shape) + 1j * rng.normal(size=shape))


def rms(a: np.ndarray) -> float:
    return float(np.sqrt(np.mean(np.abs(a) ** 2)))


# -- the upstream solutions text format --------------------------------------
#
# '#' comment lines; one header line "freq(MHz) bandwidth(MHz)
# time_interval(min) stations clusters effective_clusters"; then per solve
# interval 8N rows "counter col..." with one column per direction, the
# LAST direction first.  A station's 8 reals are
# [S0+jS1, S4+jS5; S2+jS3, S6+jS7]  (upstream README, "Solution format").

#: (row, column) of the Jones matrix held by each pair of a station's 8 reals
_PAIRS = ((0, 0), (1, 0), (0, 1), (1, 1))


def write_solutions(path: str, jones_per_interval, freq0: float,
                    fdelta: float, interval_min: float) -> None:
    j0 = jones_per_interval[0]
    n_dir, n_sta = j0.shape[:2]
    with open(path, "w") as f:
        f.write("# solution file (benchmarks/reference.py)\n")
        f.write("# freq(MHz) bandwidth(MHz) time_interval(min) stations "
                "clusters effective_clusters\n")
        f.write(f"{freq0 * 1e-6:f} {fdelta * 1e-6:f} {interval_min:f} "
                f"{n_sta} {n_dir} {n_dir}\n")
        for jones in jones_per_interval:
            cols = np.empty((8 * n_sta, n_dir))
            for c, m in enumerate(range(n_dir - 1, -1, -1)):
                jm = jones[m]
                for k, (a, b) in enumerate(_PAIRS):
                    cols[2 * k::8, c] = jm[:, a, b].real
                    cols[2 * k + 1::8, c] = jm[:, a, b].imag
            f.write("".join(
                f"{r} " + " ".join(f"{x:.9e}" for x in cols[r]) + "\n"
                for r in range(8 * n_sta)))


def read_solutions(path: str):
    """List of [M, N, 2, 2] complex, one per solve interval."""
    header, rows, out = None, [], []
    with open(path) as f:
        for ln in f:
            t = ln.split()
            if not t or t[0].startswith("#"):
                continue
            if header is None:
                header = t
                n_sta, n_dir = int(t[3]), int(t[5])
                continue
            rows.append([float(x) for x in t[1:]])
            if len(rows) == 8 * n_sta:
                cols = np.asarray(rows)
                jones = np.empty((n_dir, n_sta, 2, 2), np.complex128)
                for c, m in enumerate(range(n_dir - 1, -1, -1)):
                    for k, (a, b) in enumerate(_PAIRS):
                        jones[m, :, a, b] = (cols[2 * k::8, c]
                                             + 1j * cols[2 * k + 1::8, c])
                out.append(jones)
                rows = []
    if rows:
        raise ValueError(f"{path}: ends inside an interval "
                         f"({len(rows)} of {8 * n_sta} rows)")
    return out


class Observation:
    """One observation of a deployment, drawn from ``seed``.

    The array and the sky belong to the deployment (``layout_seed`` and
    ``sky_seed`` in its configuration file): every seed observes the same
    field with the same stations.  The seed draws what changes from one
    observation to the next: the starting hour angle (and with it every
    uvw), the true Jones matrices and the noise.
    """

    def __init__(self, cfg: dict, seed: int):
        self.cfg, self.seed = cfg, int(seed)
        self.n_sta = int(cfg["n_stations"])
        self.n_dir = int(cfg["n_clusters"])
        self.tilesz = int(cfg["tilesz"])
        self.tdelta = float(cfg["tdelta_s"])
        self.freq = float(cfg["freq_hz"])
        self.fdelta = float(cfg["chan_width_hz"])
        self.ra0, self.dec0 = float(cfg["ra0_rad"]), float(cfg["dec0_rad"])
        self.xyz = station_layout(self.n_sta, int(cfg["layout_seed"]))
        self.sky_lines, self.cluster_lines = draw_sky(
            self.n_dir, int(cfg["n_sources_per_cluster"]),
            int(cfg["sky_seed"]), self.ra0, self.dec0,
            float(cfg["log_flux_mean"]), self.freq,
            int(cfg.get("sky_format", 0)))
        self.sky = sky_from_text(self.sky_lines, self.cluster_lines,
                                 self.ra0, self.dec0)
        rng = np.random.default_rng([self.seed, 0])
        self.ha0 = float(rng.uniform(-1.0, 0.5))
        self.nbase = self.n_sta * (self.n_sta - 1) // 2
        self.nrows = self.nbase * self.tilesz

    def jones(self, interval: int = 0) -> np.ndarray:
        """True Jones [M, N, 2, 2].  ``jones_per_interval`` false (the
        calibrate deployment): one draw for the whole observation."""
        k = interval if self.cfg.get("jones_per_interval") else 0
        rng = np.random.default_rng([self.seed, 1, k])
        return draw_jones(self.n_dir, self.n_sta,
                          float(self.cfg["jones_scale"]), rng)

    def geometry(self, tile: int):
        return tile_uvw(self.xyz, self.dec0, self.ha0, tile, self.tilesz,
                        self.tdelta)

    def short_rows(self, max_m: float) -> np.ndarray:
        """Rows (all timeslots) of the baselines shorter than ``max_m``
        metres (of the shortest one, where none is), the same in every
        tile.  Their fringe phases are small, so float32 rounds them
        finely and what is left of a comparison there is the arithmetic
        of the Jones products."""
        p, q = baselines(self.n_sta)
        length = np.linalg.norm(self.xyz[q] - self.xyz[p], axis=1)
        bl = np.flatnonzero(length <= max(max_m, length.min()))
        return np.sort((np.arange(self.tilesz)[:, None] * self.nbase
                        + bl[None, :]).reshape(-1))

    def noise(self, tile: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, 2, tile])
        return draw_noise(self.nrows, float(self.cfg["noise_sigma"]), rng)

    def model(self, tile: int, jones: np.ndarray, rows=None,
              dtype=None, passes: int = 1) -> np.ndarray:
        """Model visibilities [B', 2, 2] of ``tile`` under ``jones``, on
        all rows or on the row subset ``rows``."""
        u, v, w, s1, s2 = self.geometry(tile)
        if rows is not None:
            u, v, w, s1, s2 = u[rows], v[rows], w[rows], s1[rows], s2[rows]
        coh = coherencies(self.sky, u, v, w, self.freq, self.fdelta)
        return model(jones, coh, s1, s2, dtype=dtype, passes=passes)

    def data(self, tile: int) -> np.ndarray:
        """Observed visibilities [B, 2, 2]: model under the true Jones
        plus noise."""
        return self.model(tile, self.jones(tile)) + self.noise(tile)
