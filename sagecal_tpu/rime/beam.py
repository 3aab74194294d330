"""Station beam models: geometric array factor + spherical element beam.

Capability parity with reference ``src/lib/Radio``:
- ``arraybeam`` (stationbeam.c:44): per-(source, time, station[, freq])
  scalar array-factor gain — geometric-delay beamforming over station
  elements, beamformed at ``f0`` toward (ra0, dec0), evaluated at ``f``
  toward the source; gain = |mean_k exp(-i 2pi/c r.p_k)|, 0 below horizon.
- ``element_beam`` / ``array_element_beam`` (stationbeam.c:119-260):
  per-(source, time, station) 2x2 complex E-Jones from a dual-pol
  Zernike-like polar basis (elementbeam.c ``eval_elementcoeffs``):
  mode (n, m), m = -n..n step 2, basis = preamble * (pi/4+r)^|m|
  * L_{(n-|m|)/2}^{|m|}(r^2/b^2) * exp(-r^2/2b^2) * exp(-i m theta),
  E = [[X.theta, X.phi], [Y.theta, Y.phi]] with X at (zd, az-pi/4) and
  Y at (zd, az+pi/4).
- ``set_elementcoeffs`` (elementbeam.c:39): frequency interpolation of the
  per-band coefficient tables. The reference hardcodes LOFAR LBA/HBA
  characterization tables; this framework treats coefficients as DATA —
  loadable from .npz — and ships synthetic dipole-fit defaults with the
  same basis/order so the full code path runs without proprietary tables
  (convert real tables with :func:`save_element_coeffs`).

TPU-first design: everything is batched over (source, time, station)
and jit-traceable; the element-basis mode loop (28 modes for order 7)
unrolls at trace time into fused elementwise ops. Beam tables feed the
coherency product in :mod:`sagecal_tpu.rime.predict` exactly where the
reference's precomputed ``beamgain``/``elementgain`` tables feed
predict_withbeam.c:139-187.

Beam modes follow Dirac_common.h:97-109: NONE=0, ARRAY=1, FULL=2,
ELEMENT=3.
"""

from __future__ import annotations

import dataclasses
import math
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from sagecal_tpu import coords

C_M_S = 299792458.0

DOBEAM_NONE = 0
DOBEAM_ARRAY = 1
DOBEAM_FULL = 2
DOBEAM_ELEMENT = 3

BEAM_ELEM_MODES = 7     # polynomial order M; Nmodes = M(M+1)/2 = 28
BEAM_ELEM_BETA = 0.5


# ---------------------------------------------------------------------------
# element-beam coefficient tables (host side)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ElementCoeffs:
    """Dual-pol element-pattern coefficients on a frequency grid.

    theta/phi: [Nfreq, Nmodes] complex; freqs in Hz.
    """

    freqs: np.ndarray
    theta: np.ndarray
    phi: np.ndarray
    M: int = BEAM_ELEM_MODES
    beta: float = BEAM_ELEM_BETA

    @property
    def n_modes(self) -> int:
        return self.M * (self.M + 1) // 2


def mode_table(M: int):
    """(n, m, p=(n-|m|)/2, |m|) per mode, the basis enumeration of
    elementbeam.c:147-158."""
    n_l, m_l = [], []
    for n in range(M):
        for m in range(-n, n + 1, 2):
            n_l.append(n)
            m_l.append(m)
    n_a = np.asarray(n_l)
    m_a = np.asarray(m_l)
    absm = np.abs(m_a)
    return n_a, m_a, (n_a - absm) // 2, absm


def mode_preamble(M: int, beta: float) -> np.ndarray:
    """Per-mode normalization (elementbeam.c:146-159):
    (-1)^((n-|m|)/2) sqrt(((n-|m|)/2)! / (pi ((n+|m|)/2)!)) / beta^(1+|m|).
    """
    n_a, _, p_a, absm = mode_table(M)
    out = np.empty(len(n_a))
    for i, (p, q) in enumerate(zip(p_a, (n_a + absm) // 2)):
        out[i] = math.sqrt(math.factorial(p) / (math.pi * math.factorial(q)))
        if p % 2:
            out[i] = -out[i]
        out[i] *= beta ** (-1.0 - absm[i])
    return out


def _laguerre(p: int, q: int, x):
    """Generalized Laguerre L_p^q(x), ascending recursion
    (elementbeam.c:176-196). p is a small static int."""
    if p == 0:
        return jnp.ones_like(x)
    lm2 = jnp.ones_like(x)
    lm1 = 1.0 + q - x
    if p == 1:
        return lm1
    for i in range(2, p + 1):
        inv = 1.0 / i
        cur = (2.0 + inv * (q - 1.0 - x)) * lm1 - (1.0 + inv * (q - 1)) * lm2
        lm2, lm1 = lm1, cur
    return lm1


def element_basis(r, theta, M: int, beta: float):
    """Basis functions at polar (r=zenith angle, theta=rotated azimuth).

    Returns [..., Nmodes] complex (eval_elementcoeffs, elementbeam.c:198-235).
    """
    _, m_a, p_a, absm = mode_table(M)
    pre = mode_preamble(M, 1.0)  # beta-free part; beta applied via jnp below
    rb = (r / beta) ** 2
    ex = jnp.exp(-0.5 * rb)
    cols = []
    for i in range(len(m_a)):
        lg = _laguerre(int(p_a[i]), int(absm[i]), rb)
        rm = (jnp.pi / 4.0 + r) ** int(absm[i])
        bscale = beta ** (-1.0 - int(absm[i]))
        pr = rm * lg * ex * (pre[i] * bscale)
        ang = -float(m_a[i]) * theta
        cols.append(pr * jax.lax.complex(jnp.cos(ang), jnp.sin(ang)))
    return jnp.stack(cols, axis=-1)


def synthetic_element_coeffs(band: str = "lba", M: int = BEAM_ELEM_MODES,
                             beta: float = BEAM_ELEM_BETA,
                             n_freqs: int = 10) -> ElementCoeffs:
    """Fit the polar basis to an analytic crossed-dipole pattern.

    Stand-in for the hardcoded LOFAR characterization tables
    (elementcoeff.h): E_theta ~ cos(zd) cos(phi), E_phi ~ -sin(phi) with a
    gentle frequency taper, projected onto the same (M, beta) basis by
    least squares, so evaluation exercises the identical code path.
    """
    if band == "lba":
        freqs = np.linspace(10e6, 100e6, n_freqs)
    else:
        freqs = np.linspace(110e6, 250e6, n_freqs)
    rr = np.linspace(0.0, np.pi / 2, 24)
    tt = np.linspace(0.0, 2 * np.pi, 33)[:-1]
    Rg, Tg = np.meshgrid(rr, tt, indexing="ij")
    A = np.asarray(element_basis(jnp.asarray(Rg.ravel()),
                                 jnp.asarray(Tg.ravel()), M, beta))
    th_tab = np.empty((n_freqs, A.shape[1]), complex)
    ph_tab = np.empty((n_freqs, A.shape[1]), complex)
    fmid = freqs.mean()
    for i, f in enumerate(freqs):
        taper = np.cos(Rg.ravel()) ** (1.0 + 0.5 * (f - fmid) / fmid)
        e_th = taper * np.cos(Tg.ravel()) * (1.0 + 0.1j * (f - fmid) / fmid)
        e_ph = -np.sin(Tg.ravel()) * (1.0 - 0.05j * (f - fmid) / fmid)
        th_tab[i], *_ = np.linalg.lstsq(A, e_th, rcond=None)[:1]
        ph_tab[i], *_ = np.linalg.lstsq(A, e_ph, rcond=None)[:1]
    return ElementCoeffs(freqs=freqs, theta=th_tab, phi=ph_tab,
                         M=M, beta=beta)


_DATA_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def lofar_element_coeffs(band: str) -> ElementCoeffs:
    """Measured LOFAR LBA/HBA element characterization tables.

    Converted from the reference's auto-generated coefficient data
    (elementcoeff.h: 10 LBA / 15 HBA frequencies x 28 modes, M=7,
    beta=0.5) by tools_dev/convert_elementcoeff.py; frequencies stored in
    Hz. Selection by band follows the <100 MHz LBA/HBA split of the
    callers (fullbatch_mode.cpp:71).
    """
    return load_element_coeffs(
        os.path.join(_DATA_DIR, f"lofar_elem_{band}.npz"))


def default_element_coeffs(band: str) -> ElementCoeffs:
    """The LOFAR characterization tables; synthetic dipole fit only if
    the packaged data files are missing."""
    try:
        return lofar_element_coeffs(band)
    except (FileNotFoundError, OSError):        # pragma: no cover
        return synthetic_element_coeffs(band)


def save_element_coeffs(path: str, ecoeff: ElementCoeffs) -> None:
    np.savez(path, freqs=ecoeff.freqs, theta=ecoeff.theta, phi=ecoeff.phi,
             M=ecoeff.M, beta=ecoeff.beta)


def load_element_coeffs(path: str) -> ElementCoeffs:
    z = np.load(path)
    return ElementCoeffs(freqs=z["freqs"], theta=z["theta"], phi=z["phi"],
                         M=int(z["M"]), beta=float(z["beta"]))


def element_pattern_at(ecoeff: ElementCoeffs, freq_hz: float):
    """Interpolate pattern vectors to ``freq_hz`` (set_elementcoeffs
    elementbeam.c:80-103: linear blend of the two bracketing table rows,
    clamped at the ends)."""
    f = ecoeff.freqs
    if freq_hz <= f[0]:
        return ecoeff.theta[0].copy(), ecoeff.phi[0].copy()
    if freq_hz >= f[-1]:
        return ecoeff.theta[-1].copy(), ecoeff.phi[-1].copy()
    ih = int(np.searchsorted(f, freq_hz))
    il = ih - 1
    wl = freq_hz - f[il]
    wh = f[ih] - freq_hz
    w1 = wl / (wl + wh)
    th = (1.0 - w1) * ecoeff.theta[il] + w1 * ecoeff.theta[ih]
    ph = (1.0 - w1) * ecoeff.phi[il] + w1 * ecoeff.phi[ih]
    return th, ph


# ---------------------------------------------------------------------------
# beam geometry (host container + device arrays)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BeamInfo:
    """Host-side station/beam metadata (readAuxData with beam,
    src/MS/data.cpp:194: station long/lat, element offsets, times)."""

    longitude: np.ndarray        # [N] rad
    latitude: np.ndarray         # [N] rad
    time_jd: np.ndarray          # [T] JD (days)
    ra0: float                   # beam pointing (rad)
    dec0: float
    freq0: float                 # beamformer reference freq (Hz)
    elem_xyz: np.ndarray         # [N, Emax, 3] element positions (m)
    elem_mask: np.ndarray        # [N, Emax] bool
    ecoeff: ElementCoeffs | None = None


class BeamArrays(NamedTuple):
    """Device-resident beam model (pytree)."""

    longitude: jax.Array         # [N]
    latitude: jax.Array          # [N]
    gmst: jax.Array              # [T] degrees (precomputed from time_jd)
    ra0: jax.Array
    dec0: jax.Array
    freq0: jax.Array
    elem_xyz: jax.Array          # [N, Emax, 3]
    elem_mask: jax.Array         # [N, Emax]
    n_elem: jax.Array            # [N]
    patt_theta: jax.Array        # [Nmodes, 2] re/im (at data freq0) —
    patt_phi: jax.Array          # stored real: only real arrays cross
    elem_beta: jax.Array         # host<->device (utils.py)


def beam_to_device(info: BeamInfo, data_freq0: float | None = None,
                   real_dtype=jnp.float32, time_jd=None) -> BeamArrays:
    """Stage beam metadata onto the device. The element pattern is
    interpolated once at the data reference frequency (fullbatch_mode.cpp:70
    calls set_elementcoeffs with iodata.freq0). ``time_jd`` overrides the
    stored times (per-tile staging in the streaming pipeline)."""
    f = lambda a: jnp.asarray(a, real_dtype)
    f0ref = data_freq0 or info.freq0
    ecoeff = info.ecoeff or default_element_coeffs(band_for_freq(f0ref))
    th, ph = element_pattern_at(ecoeff, f0ref)
    th = np.stack([th.real, th.imag], axis=-1)
    ph = np.stack([ph.real, ph.imag], axis=-1)
    gmst = coords.jd2gmst_np(
        info.time_jd if time_jd is None else time_jd)
    return BeamArrays(
        longitude=f(info.longitude), latitude=f(info.latitude),
        gmst=f(gmst),
        ra0=f(info.ra0), dec0=f(info.dec0), freq0=f(info.freq0),
        elem_xyz=f(info.elem_xyz), elem_mask=jnp.asarray(info.elem_mask, bool),
        n_elem=jnp.sum(info.elem_mask, axis=1).astype(real_dtype),
        patt_theta=f(th), patt_phi=f(ph),
        elem_beta=f(ecoeff.beta),
    )


def synthetic_beam(n_stations: int, time_jd, ra0: float, dec0: float,
                   freq0: float, n_elem: int = 24, extent_m: float = 30.0,
                   band: str = "lba", seed: int = 5,
                   ecoeff: ElementCoeffs | None = None) -> BeamInfo:
    """LOFAR-like synthetic beam metadata for simulation/tests: stations
    scattered near the LOFAR core, elements on a horizontal disc."""
    rng = np.random.default_rng(seed)
    lon0, lat0 = 0.12, 0.92   # ~LOFAR core (rad)
    longitude = lon0 + 1e-4 * rng.normal(size=n_stations)
    latitude = lat0 + 1e-4 * rng.normal(size=n_stations)
    r = extent_m * np.sqrt(rng.random((n_stations, n_elem)))
    th = 2 * np.pi * rng.random((n_stations, n_elem))
    elem = np.stack([r * np.cos(th), r * np.sin(th),
                     np.zeros_like(r)], axis=-1)
    mask = np.ones((n_stations, n_elem), bool)
    return BeamInfo(longitude=longitude, latitude=latitude,
                    time_jd=np.atleast_1d(np.asarray(time_jd, float)),
                    ra0=ra0, dec0=dec0, freq0=freq0,
                    elem_xyz=elem, elem_mask=mask,
                    ecoeff=ecoeff or default_element_coeffs(band))


def band_for_freq(freq_hz: float) -> str:
    """LBA below the ~100 MHz FM gap, HBA above (elementbeam.c table
    selection by ELEM_LBA/ELEM_HBA)."""
    return "lba" if freq_hz < 105e6 else "hba"


def resolve_beaminfo(dobeam: int, ms, meta: dict, log=print):
    """Beam metadata for a dataset: stored beam.npz, else a synthetic
    layout (loudly — a fabricated array is fine for simulation and tests
    but meaningless for real instrument data)."""
    if not dobeam:
        return None
    info = ms.beam_info()
    if info is None:
        log("WARNING: beam enabled (-B) but the dataset stores no beam "
            "metadata (beam.npz); using a SYNTHETIC station/element "
            "layout — solutions will not correspond to a real instrument")
        info = synthetic_beam(
            meta["n_stations"], np.array([2451545.0]), meta["ra0"],
            meta["dec0"], meta["freq0"], band=band_for_freq(meta["freq0"]))
    return info


def save_beaminfo(path: str, info: BeamInfo) -> None:
    """Persist beam metadata next to a dataset (the SimMS analogue of the
    MS's LOFAR_ANTENNA_FIELD subtable, data.cpp:194-300)."""
    ec = info.ecoeff or default_element_coeffs(band_for_freq(info.freq0))
    np.savez(path, longitude=info.longitude, latitude=info.latitude,
             time_jd=info.time_jd, ra0=info.ra0, dec0=info.dec0,
             freq0=info.freq0, elem_xyz=info.elem_xyz,
             elem_mask=info.elem_mask, ec_freqs=ec.freqs, ec_theta=ec.theta,
             ec_phi=ec.phi, ec_M=ec.M, ec_beta=ec.beta)


def load_beaminfo(path: str) -> BeamInfo:
    z = np.load(path)
    ec = ElementCoeffs(freqs=z["ec_freqs"], theta=z["ec_theta"],
                       phi=z["ec_phi"], M=int(z["ec_M"]),
                       beta=float(z["ec_beta"]))
    return BeamInfo(longitude=z["longitude"], latitude=z["latitude"],
                    time_jd=z["time_jd"], ra0=float(z["ra0"]),
                    dec0=float(z["dec0"]), freq0=float(z["freq0"]),
                    elem_xyz=z["elem_xyz"], elem_mask=z["elem_mask"],
                    ecoeff=ec)


# ---------------------------------------------------------------------------
# device-side evaluation
# ---------------------------------------------------------------------------

def _direction_components(az, el):
    """(sin t cos p, sin t sin p, cos t) with t=pi/2-el, p=-az
    (stationbeam.c:63-67)."""
    theta = jnp.pi / 2 - el
    st, ct = jnp.sin(theta), jnp.cos(theta)
    sp, cp = jnp.sin(-az), jnp.cos(-az)
    return st * cp, st * sp, ct


def array_factor(beam: BeamArrays, ra, dec, freq):
    """Array-factor gains [S, T, N] for source directions (ra, dec) [S] at
    one frequency (arraybeam, stationbeam.c:44-110)."""
    az, el = coords.radec2azel_gmst(
        ra[:, None, None], dec[:, None, None],
        beam.longitude[None, None, :], beam.latitude[None, None, :],
        beam.gmst[None, :, None])                       # [S, T, N]
    az0, el0 = coords.radec2azel_gmst(
        beam.ra0, beam.dec0,
        beam.longitude[None, None, :], beam.latitude[None, None, :],
        beam.gmst[None, :, None])                       # [1, T, N]
    sx, sy, sz = _direction_components(az, el)
    s0x, s0y, s0z = _direction_components(az0, el0)
    r1 = beam.freq0 * s0x - freq * sx                   # [S, T, N]
    r2 = beam.freq0 * s0y - freq * sy
    r3 = beam.freq0 * s0z - freq * sz
    tpc = 2.0 * jnp.pi / C_M_S
    # phase over elements: [S, T, N, E]
    ph = -tpc * (r1[..., None] * beam.elem_xyz[None, None, :, :, 0]
                 + r2[..., None] * beam.elem_xyz[None, None, :, :, 1]
                 + r3[..., None] * beam.elem_xyz[None, None, :, :, 2])
    m = beam.elem_mask[None, None]
    cs = jnp.sum(jnp.where(m, jnp.cos(ph), 0.0), axis=-1)
    sn = jnp.sum(jnp.where(m, jnp.sin(ph), 0.0), axis=-1)
    gain = jnp.sqrt(cs * cs + sn * sn) / beam.n_elem[None, None, :]
    return jnp.where(el >= 0.0, gain, 0.0)


def element_jones(beam: BeamArrays, ra, dec):
    """Element-beam E-Jones [S, T, N, 2, 2] complex for source directions
    (ra, dec) [S] (element_beam, stationbeam.c:215-260):
    E = [[X.theta, X.phi], [Y.theta, Y.phi]], X at (zd, az-pi/4),
    Y rotated +pi/2; zero below horizon."""
    az, el = coords.radec2azel_gmst(
        ra[:, None, None], dec[:, None, None],
        beam.longitude[None, None, :], beam.latitude[None, None, :],
        beam.gmst[None, :, None])                       # [S, T, N]
    zd = jnp.pi / 2 - el
    # Nmodes = M(M+1)/2 -> recover the (static) basis order from the shape
    M = int(round((math.isqrt(8 * beam.patt_theta.shape[0] + 1) - 1) / 2))
    bx = element_basis(zd, az - jnp.pi / 4, M, beam.elem_beta)
    by = element_basis(zd, az + jnp.pi / 4, M, beam.elem_beta)
    patt_t = jax.lax.complex(beam.patt_theta[:, 0], beam.patt_theta[:, 1])
    patt_p = jax.lax.complex(beam.patt_phi[:, 0], beam.patt_phi[:, 1])
    ex_t = jnp.sum(bx * patt_t, axis=-1)
    ex_p = jnp.sum(bx * patt_p, axis=-1)
    ey_t = jnp.sum(by * patt_t, axis=-1)
    ey_p = jnp.sum(by * patt_p, axis=-1)
    E = jnp.stack([jnp.stack([ex_t, ex_p], -1),
                   jnp.stack([ey_t, ey_p], -1)], -2)
    return jnp.where((el >= 0.0)[..., None, None], E,
                     jnp.zeros_like(E))


def cluster_beam(beam: BeamArrays, ra_s, dec_s, freqs, dobeam: int):
    """Per-cluster beam tables: (af [F, S, T, N] or None,
    E [S, T, N, 2, 2] or None), the analogue of the reference's
    ``beamgain``/``elementgain`` precompute (predict_withbeam.c:476-510)."""
    af = None
    E = None
    if dobeam in (DOBEAM_ARRAY, DOBEAM_FULL):
        af = jax.vmap(lambda f: array_factor(beam, ra_s, dec_s, f))(
            jnp.atleast_1d(freqs))
    if dobeam in (DOBEAM_ELEMENT, DOBEAM_FULL):
        E = element_jones(beam, ra_s, dec_s)
    return af, E
