"""The plain reference of the extended-source deployment: numpy, float64,
nothing of the program (and no jax).  Beside ``reference.py``, which it
uses for the array, the uvw tracks, the Jones sandwich and the solutions
text, it holds a sky that is not points, as upstream SAGECal's README
("Sky model format", "-F 1") and ``src/lib/Radio/predict.c:142-245``
describe it:

    V_pq = sum_m J_pm ( sum_s I_s(f) E_s(u, v, w) e^{i phi_pqs} |sinc| ) J_qm^H

with ``I_s(f)`` the source's flux at the channel's frequency and ``E_s``
the envelope of its kind, 1 for a point.  The coherency stays a scalar
times the identity (unpolarised sources), so ``reference.model`` makes
the sandwich as it is.  Everything is written here from the published
descriptions; what is taken, in words:

- **The text.**  A line is ``name h m s d m s I Q U V si [si1 si2] RM eX eY
  eP f0``: one spectral term in format 0, three in format 1 (``-F 1``).
  The first letter of the name is the kind: ``G`` Gaussian, ``D`` disk,
  ``R`` ring, ``S`` shapelet, anything else a point.  An ``S`` source's
  modes are in ``<name>.fits.modes`` beside the sky file: six numbers of a
  position (not read), ``n0``, ``beta``, then ``n0^2`` lines ``index
  value``; the value at index ``n2 * n0 + n1`` is ``c_{n1 n2}``.
- **The flux law.**  ``I(f) = I0 exp(si r + si1 r^2 + si2 r^3)``, ``r =
  ln(f / f0)``, the sign of ``I0`` kept, applied where ``si != 0`` and only
  there.  This is the rule of the per-channel model (``residual.c:453-478``
  as ``rime/predict._spectral_flux`` cites it), the path ``-a 1`` runs.
  The program has a second rule, at parse (``readsky.c:347-370``,
  ``skymodel._scaled_flux``, the solve's fluxes): scaled where ANY of the
  three terms is non-zero.  The two are not one; ``draw_sky`` gives one
  source in sixteen ``si = 0`` with ``si1``, ``si2`` non-zero, on which
  they differ by parts in a thousand of that source.
- **Gaussian** (``predict.c:193``; the doubling is ``readsky.c:412``):
  ``(pi / 2) exp(-(ut^2 + vt^2))``, ``ut = 2 eX (cos eP u' - sin eP v')``,
  ``vt = 2 eY (sin eP u' + cos eP v')``, u', v' in wavelengths.  The image
  it transforms has a full width at half maximum of 1.06 eX along its
  major axis.
- **Ring and disk** (``predict.c:222,237``): ``J0(2 pi eX |u'v'|)`` and
  ``J1(2 pi eX |u'v'|)``.  The Bessel functions are Bessel's integral,
  ``J_n(x) = 1/(2 pi) int_0^{2 pi} cos(n t - x sin t) dt``, by the
  trapezoid rule over the whole period, which is exact to rounding once
  the points outnumber ``2 x`` (the integrand is periodic and entire);
  not the rational approximations the program uses.  (A uniform disk's
  own transform is ``2 J1(x) / x`` and a Gaussian's carries no ``pi / 2``:
  both factors are upstream's as cited, and followed.)
- **Shapelet** (``predict.c:142``; Refregier 2003, MNRAS 338, 35, eq.
  1-9).  The image-domain basis is ``phi_n(x; b) = H_n(x / b) exp(-x^2 / 2
  b^2) / sqrt(2^n sqrt(pi) n! b) = psi_n(x / b) / sqrt(b)`` with ``psi_n``
  the dimensionless Hermite function, which is its own Fourier transform
  up to ``i^n``: under this file's sign of the phase, ``int phi_n(x; b)
  e^{+2 pi i u x} dx = sqrt(2 pi b) i^n psi_n(2 pi b u)``.  So the PLAIN
  transform of ``g(x, y) = sum c_{n1 n2} phi_n1(x; b) phi_n2(y; b)`` is
  ``G(u, v) = 2 pi b sum c_{n1 n2} i^(n1 + n2) psi_n1(2 pi b u) psi_n2(2 pi
  b v)``.  Upstream's envelope is ``2 pi a b' (Re + i Im)`` of ``sum c
  B_n1(-beta ut) B_n2(beta vt)``, ``B_n = H_n(x) e^{-x^2 / 2} / sqrt(2^(n+1)
  n!) = psi_n(x) pi^(1/4) / sqrt 2``, ``a = 1 / eX``, ``b' = 1 / eY`` (0
  reads as 1), ``ut = a (cos eP u' - sin eP v')``, ``vt = b' (sin eP u' +
  cos eP v')``.  ``shapelet_image`` is the image whose plain transform,
  times ONE constant, this is:

      E(u, v) = (pi^(3/2) / beta) FT[ g(-eX (cos eP l - sin eP m),
                                         eY (sin eP l + cos eP m)) ](u, v),
      g built at the scale b = beta / (2 pi).

  The departures from the plain transform of ``sum c phi phi`` at the
  file's ``beta``, each followed:

  1. the scale: the basis is evaluated at ``beta u``, not ``2 pi beta u``,
     so the image's scale is ``beta / 2 pi``.  Nothing at hand says why
     (PERF.md section 7);
  2. the mirror ``l -> -l``: upstream decomposes ``f(-l, m)``
     (``predict.c:156``), so the first argument is ``-ut``;
  3. the constant ``pi^(3/2) / beta`` (upstream's ``2 pi`` times ``B B / psi
     psi = sqrt(pi) / 2``, over the plain transform's ``2 pi b = beta``);
  4. ``eX, eY, eP`` stretch and turn the IMAGE (``x = eX (...)``): the
     factor ``a b'`` is the Jacobian of that, no departure;
  5. beyond ``PROJ_CUT`` the projected ``u', v'`` are NEGATED
     (``predict.c:152-158``), which conjugates the envelope: a shapelet
     that crosses the cut is mirrored through its centre.
- **The projection** ``(u, v, w) -> (u', v')`` for disks and rings always,
  for Gaussians and shapelets only where the source's ``n`` is under
  ``PROJ_CUT`` (0.998, 3.6 degrees from the phase centre: a followed
  convention, ``readsky.c:420-424``).  With ``r = sqrt(l^2 + m^2)``:
  ``t = v n + w r`` (a tilt about the u axis by ``acos n``), then ``u' =
  (u m - t l) / r``, ``v' = (u l + t m) / r`` (a turn by the azimuth
  ``atan2(-l, m)``).  This is the program's port of ``predict.c:168-180``
  and is FOLLOWED, not derived: the plane perpendicular to the source
  would need the turn before the tilt (PERF.md section 7).  For disks and
  rings only ``u'^2 + v'^2 = u^2 + t^2`` matters.

The keywords ``points``, ``no_shapelets``, ``at_f0`` and ``any_term``
exist for the CONTROLS only: the same sky with every envelope 1, with the
shapelet sources left out, with every flux at its catalogue value, with
the flux law's parse rule in the per-channel rule's place.
"""

from __future__ import annotations

import math

import numpy as np

import reference

PROJ_CUT = 0.998
ASEC = math.pi / (180 * 3600)
POINT, GAUSSIAN, DISK, RING, SHAPELET = range(5)
KIND_OF = {"G": GAUSSIAN, "D": DISK, "R": RING, "S": SHAPELET}
KIND_NAMES = ("point", "gaussian", "disk", "ring", "shapelet")


# -- the text ----------------------------------------------------------------

def source_line(name, ra, dec, flux, spec, ext, f0, fmt):
    """One LSM line.  ``spec`` (si, si1, si2), ``ext`` (eX, eY, eP)."""
    h = (ra % (2 * math.pi)) * 12 / math.pi
    hh, hm = int(h), int((h - int(h)) * 60)
    hs = ((h - hh) * 60 - hm) * 60
    d = math.degrees(abs(dec))
    dd, dm = int(d), int((d - int(d)) * 60)
    dsec = ((d - dd) * 60 - dm) * 60
    sign = "-" if dec < 0 else ""
    terms = spec if fmt else spec[:1]
    return (f"{name} {hh} {hm} {hs:.9f} {sign}{dd} {dm} {dsec:.8f} "
            f"{flux:.8f} 0 0 0 " + " ".join(f"{s:.6f}" for s in terms)
            + " 0 " + " ".join(f"{e:.9e}" for e in ext) + f" {f0:.1f}")


def modes_text(n0, beta, coeff):
    """The text of a ``.fits.modes`` file; ``coeff[n2, n1]`` = c_{n1 n2}."""
    flat = np.asarray(coeff).reshape(-1)
    return ("0 0 0 0 0 0\n" + f"{n0} {beta:.9e}\n"
            + "".join(f"{i} {c:.9e}\n" for i, c in enumerate(flat)))


def read_modes(text):
    """(n0, beta, c [n2, n1]) of a ``.fits.modes`` text."""
    t = text.split()
    n0, beta = int(t[6]), float(t[7])
    c = np.zeros(n0 * n0)
    for k in range(n0 * n0):
        c[int(t[8 + 2 * k])] = float(t[9 + 2 * k])
    return n0, beta, c.reshape(n0, n0)


class Sky:
    """A sky read from its text: arrays [M, S] (``modes`` a list of lists
    of ``c [n2, n1]`` or None), extents as the text has them."""

    FIELDS = ("ll", "mm", "nn", "flux", "si", "si1", "si2", "f0", "kind",
              "eX", "eY", "eP", "n0", "beta")

    def __init__(self, rows, names):
        self.names = names
        for k in self.FIELDS:
            a = np.asarray([[s[k] for s in row] for row in rows])
            setattr(self, k, a)
        self.modes = [[s["modes"] for s in row] for row in rows]

    @property
    def n_clusters(self):
        return self.ll.shape[0]

    def counts(self):
        """{kind name: number of sources}."""
        return {n: int(np.sum(self.kind == k))
                for k, n in enumerate(KIND_NAMES)}


def read_sky(sky_lines, cluster_lines, modes, ra0, dec0, fmt) -> Sky:
    """This file's own reading of the LSM text in format ``fmt`` (0 or
    1), every column; ``modes`` {source name: the text of its
    ``.fits.modes``}."""
    src = {}
    for ln in sky_lines:
        t = ln.split()
        if not t or t[0].startswith("#"):
            continue
        ra = (abs(float(t[1])) + float(t[2]) / 60 + float(t[3]) / 3600) \
            * math.pi / 12
        sign = -1.0 if t[4].startswith("-") else 1.0
        dec = sign * (abs(float(t[4])) + float(t[5]) / 60
                      + float(t[6]) / 3600) * math.pi / 180
        ll = math.cos(dec) * math.sin(ra - ra0)
        mm = (math.sin(dec) * math.cos(dec0)
              - math.cos(dec) * math.sin(dec0) * math.cos(ra - ra0))
        si = [float(x) for x in t[11:14]] if fmt else [float(t[11]), 0., 0.]
        at = 15 if fmt else 13              # past the rotation measure
        s = dict(ll=ll, mm=mm, nn=math.sqrt(1 - ll * ll - mm * mm) - 1.0,
                 flux=float(t[7]), si=si[0], si1=si[1], si2=si[2],
                 eX=float(t[at]), eY=float(t[at + 1]), eP=float(t[at + 2]),
                 f0=float(t[at + 3]), kind=KIND_OF.get(t[0][0].upper(),
                                                       POINT),
                 n0=0, beta=1.0, modes=None)
        if s["kind"] == SHAPELET:
            s["n0"], s["beta"], s["modes"] = read_modes(modes[t[0]])
        src[t[0]] = s
    names = [ln.split()[2:] for ln in cluster_lines]
    return Sky([[src[nm] for nm in row] for row in names], names)


# -- the flux law ------------------------------------------------------------

def flux_at(sky: Sky, m: int, freq: float, at_f0: bool = False,
            any_term: bool = False):
    """[S]: cluster ``m``'s fluxes at ``freq`` by the per-channel rule
    (``any_term``: by the parse rule, scaled where any term is not 0)."""
    i0 = sky.flux[m]
    if at_f0:
        return i0
    r = np.log(freq / sky.f0[m])
    law = np.exp(sky.si[m] * r + sky.si1[m] * r ** 2 + sky.si2[m] * r ** 3)
    scaled = sky.si[m] != 0.0
    if any_term:
        scaled = scaled | (sky.si1[m] != 0.0) | (sky.si2[m] != 0.0)
    return np.where(scaled, i0 * law, i0)


# -- special functions -------------------------------------------------------

def bessel_j(n: int, x: np.ndarray) -> np.ndarray:
    """``J_n(x)`` of integer order by Bessel's integral (docstring)."""
    x = np.asarray(x, np.float64)
    top = float(np.max(np.abs(x))) if x.size else 0.0
    k = 2 * int(math.ceil(top)) + 64
    out = np.zeros(x.shape)
    for t in 2 * np.pi * np.arange(k) / k:
        out += np.cos(n * t - x * math.sin(t))
    return out / k


def hermite_functions(x: np.ndarray, count: int) -> np.ndarray:
    """``psi_0 .. psi_{count-1}`` at ``x``: [count, ...].  The normalised
    three-term recurrence ``psi_{n+1} = sqrt(2 / (n+1)) x psi_n - sqrt(n /
    (n+1)) psi_{n-1}``, ``psi_0 = pi^(-1/4) exp(-x^2 / 2)``."""
    x = np.asarray(x, np.float64)
    out = np.empty((count,) + x.shape)
    out[0] = math.pi ** -0.25 * np.exp(-0.5 * x * x)
    if count > 1:
        out[1] = math.sqrt(2.0) * x * out[0]
    for n in range(1, count - 1):
        out[n + 1] = (math.sqrt(2.0 / (n + 1)) * x * out[n]
                      - math.sqrt(n / (n + 1.0)) * out[n - 1])
    return out


# -- envelopes ---------------------------------------------------------------

def project(u, v, w, ll, mm, nn):
    """(u', v') of [B] baselines for ONE source (docstring, "The
    projection"); ``nn`` carries the -1."""
    n = nn + 1.0
    r = math.hypot(ll, mm)
    t = v * n + w * r
    if r == 0.0:
        return u, t
    return (u * mm - t * ll) / r, (u * ll + t * mm) / r


def stretched(up, vp, sx, sy, eP):
    c, s = math.cos(eP), math.sin(eP)
    return sx * (c * up - s * vp), sy * (s * up + c * vp)


def shapelet_envelope(ut, vt, beta, coeff):
    """``pi^(3/2) sum c_{n1 n2} i^(n1+n2) psi_n1(-beta ut) psi_n2(beta vt)``
    (without the Jacobian ``a b'``); ``coeff[n2, n1]``."""
    n0 = coeff.shape[0]
    pu = hermite_functions(-beta * ut, n0)          # [n1, B]
    pv = hermite_functions(beta * vt, n0)           # [n2, B]
    turn = 1j ** (np.arange(n0)[:, None] + np.arange(n0)[None, :])
    return math.pi ** 1.5 * np.einsum("ab,ab,bx,ax->x", coeff, turn, pu, pv)


def shapelet_image(l, m, eX, eY, eP, beta, coeff):
    """The image-domain sum that ``envelope`` claims to transform (times
    ``pi^(3/2) / beta``): ``g(-eX (cos eP l - sin eP m), eY (sin eP l +
    cos eP m))`` at the scale ``beta / 2 pi``, for a source at the phase
    centre (``l``, ``m`` offsets in radians, any shape)."""
    b = beta / (2 * math.pi)
    x, y = stretched(l, m, eX or 1.0, eY or 1.0, eP)
    n0 = coeff.shape[0]
    px = hermite_functions(-x / b, n0)
    py = hermite_functions(y / b, n0)
    return np.einsum("ab,b...,a...->...", coeff, px, py) / b


def envelope(sky: Sky, m: int, s: int, u, v, w) -> np.ndarray:
    """[B]: source ``s`` of cluster ``m`` at baselines in WAVELENGTHS."""
    kind = sky.kind[m, s]
    if kind == POINT:
        return np.ones(u.shape)
    ll, mm, nn = sky.ll[m, s], sky.mm[m, s], sky.nn[m, s]
    eX, eY, eP = sky.eX[m, s], sky.eY[m, s], sky.eP[m, s]
    far = nn + 1.0 < PROJ_CUT
    if kind in (DISK, RING):
        up, vp = project(u, v, w, ll, mm, nn)
        x = 2 * math.pi * eX * np.hypot(up, vp)
        return bessel_j(0 if kind == RING else 1, x)
    up, vp = project(u, v, w, ll, mm, nn) if far else (u, v)
    if kind == GAUSSIAN:
        ut, vt = stretched(up, vp, 2 * eX, 2 * eY, eP)
        return (math.pi / 2) * np.exp(-(ut * ut + vt * vt))
    if far:
        up, vp = -up, -vp                           # departure 5
    a, b = 1.0 / (eX or 1.0), 1.0 / (eY or 1.0)
    ut, vt = stretched(up, vp, a, b, eP)
    return a * b * shapelet_envelope(ut, vt, sky.beta[m, s],
                                     sky.modes[m][s])


def coherencies(sky: Sky, u, v, w, freq: float, fdelta: float,
                points: bool = False, no_shapelets: bool = False,
                at_f0: bool = False, any_term: bool = False) -> np.ndarray:
    """[M, B] complex: each direction's scalar coherency, u, v, w in
    seconds.  The keywords are the controls'."""
    out = np.empty((sky.n_clusters, u.shape[0]), np.complex128)
    ul, vl, wl = u * freq, v * freq, w * freq
    for m in range(sky.n_clusters):
        g = 2 * np.pi * (u[:, None] * sky.ll[m] + v[:, None] * sky.mm[m]
                         + w[:, None] * sky.nn[m])      # [B, S] seconds
        amp = (flux_at(sky, m, freq, at_f0, any_term)
               * np.abs(np.sinc(g * (0.5 * fdelta) / np.pi))).astype(complex)
        for s in np.flatnonzero(sky.kind[m] != POINT):
            if no_shapelets and sky.kind[m, s] == SHAPELET:
                amp[:, s] = 0.0
            elif not points:
                amp[:, s] *= envelope(sky, m, s, ul, vl, wl)
        out[m] = np.sum(amp * np.exp(1j * g * freq), axis=1)
    return out


# -- the deployment's sky ----------------------------------------------------

def _log_uniform(rng, lo, hi):
    return math.exp(rng.uniform(math.log(lo), math.log(hi)))


def draw_modes(rng, n0):
    """``c[n2, n1] = N(0, 1) / (1 + n1 + n2)``, drawn again while the sum
    at zero spacing (even modes alone) is under 0.3: the source's total
    flux is set from it.  (c, that sum)."""
    order = np.arange(n0)[:, None] + np.arange(n0)[None, :]
    zero = hermite_functions(np.zeros(1), n0)[:, 0]
    while True:
        c = rng.normal(size=(n0, n0)) / (1.0 + order)
        total = float(np.real(np.einsum(
            "ab,ab,b,a->", c, 1j ** order, zero, zero)))
        if abs(total) >= 0.3:
            return c, math.pi ** 1.5 * total


def draw_sky(cfg: dict):
    """(sky lines, cluster lines, {name: modes text}) of the deployment
    ``cfg`` describes: ``n_clusters`` directions laid out as
    ``reference.draw_sky`` lays them, each with ``sources`` {kind: count}
    components (shuffled), the first ``len(shapelet_n0)`` directions one
    shapelet each in place of a point.  Sizes are the configuration's
    (``extents``, ``spectra``)."""
    rng = np.random.default_rng(int(cfg["sky_seed"]))
    ra0, dec0 = float(cfg["ra0_rad"]), float(cfg["dec0_rad"])
    ext, spec = cfg["extents"], cfg["spectra"]
    fmt = int(cfg.get("sky_format", 1))
    n0s = list(cfg["shapelet_n0"])
    sky, clusters, modes = [], [], {}
    for m in range(int(cfg["n_clusters"])):
        cra = ra0 + rng.normal(0, 0.03) / math.cos(dec0)
        cdec = dec0 + rng.normal(0, 0.03)
        kinds = [k for k, n in cfg["sources"].items() for _ in range(n)]
        if m < len(n0s):
            kinds[kinds.index("P")] = "S"
        rng.shuffle(kinds)
        drawn = []
        for s, kind in enumerate(kinds):
            ra = cra + rng.normal(0, 0.0035) / math.cos(dec0)
            dec = cdec + rng.normal(0, 0.0035)
            flux = math.exp(rng.normal(float(cfg["log_flux_mean"]), 0.8))
            si = 0.0 if rng.random() < spec["flat_share"] else rng.normal(
                spec["si"][0], spec["si"][1])
            terms = (si, rng.normal(*spec["si1"]), rng.normal(*spec["si2"]))
            e = (0.0, 0.0, 0.0)
            if kind == "G":
                major = _log_uniform(rng, *ext["gaussian_major_asec"]) * ASEC
                e = (major, major * rng.uniform(*ext["gaussian_axis_ratio"]),
                     rng.uniform(0, math.pi))
            elif kind in "DR":
                e = (_log_uniform(rng, *ext["disk_ring_radius_asec"]) * ASEC,
                     0.0, 0.0)
            drawn.append([f"{kind}{m:02d}_{s:03d}", ra, dec, flux, terms, e])
        for d in drawn:
            if d[0][0] != "S":
                continue
            # an A-team source: the cluster's brightest at zero spacing
            n0 = int(n0s[m])
            beta = _log_uniform(rng, *ext["shapelet_beta_asec"]) * ASEC
            c, total = draw_modes(rng, n0)
            stretch = ext["shapelet_stretch"][m % len(ext["shapelet_stretch"])]
            d[5] = tuple(stretch)
            a_b = 1.0 / ((stretch[0] or 1.0) * (stretch[1] or 1.0))
            d[3] = max(x[3] for x in drawn if x is not d) / (total * a_b)
            modes[d[0]] = modes_text(n0, beta, c)
        sky += [source_line(*d, float(cfg["f0_hz"]), fmt) for d in drawn]
        clusters.append(f"{m + 1} 1 " + " ".join(d[0] for d in drawn))
    return sky, clusters, modes


class Observation(reference.Observation):
    """``reference.Observation`` (array, uvw, Jones, noise, solutions)
    looking at ``draw_sky``'s sky."""

    def __init__(self, cfg: dict, seed: int):
        super().__init__(cfg, seed)     # its point sky is replaced below
        self.fmt = int(cfg.get("sky_format", 1))
        self.sky_lines, self.cluster_lines, self.modes = draw_sky(cfg)
        self.sky = read_sky(self.sky_lines, self.cluster_lines, self.modes,
                            self.ra0, self.dec0, self.fmt)

    def _at(self, tile: int, rows):
        """u, v, w, sta1, sta2 of ``tile``, all rows or ``rows``."""
        return tuple(a if rows is None else a[rows]
                     for a in self.geometry(tile))

    def coherencies(self, tile: int, rows=None, **control) -> np.ndarray:
        u, v, w = self._at(tile, rows)[:3]
        return coherencies(self.sky, u, v, w, self.freq, self.fdelta,
                           **control)

    def model(self, tile: int, jones: np.ndarray, rows=None, dtype=None,
              passes: int = 1, **control) -> np.ndarray:
        """Model visibilities [B', 2, 2] of ``tile`` under ``jones``, on
        all rows or on ``rows``; ``control``: ``coherencies``' keywords."""
        u, v, w, s1, s2 = self._at(tile, rows)
        coh = coherencies(self.sky, u, v, w, self.freq, self.fdelta,
                          **control)
        return reference.model(jones, coh, s1, s2, dtype=dtype,
                               passes=passes)
