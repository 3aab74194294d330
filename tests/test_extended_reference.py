"""``benchmarks/reference_extended.py`` and ``rime/predict.coherencies``
held together at a small size on the CPU: 7 stations, 3 clusters x 8
sources of all five kinds, shapelets of ``n0`` 1, 4 and 7 (padded to
``n0max`` 7), a phase centre moved so that the sources lie on both sides
of ``PROJ_CUT``, two channels away from ``f0`` with three spectral terms
and with ``si = 0``, in float64 and float32.

The reference was written from the published description (its docstring);
the program is a port with upstream's lines cited.  What anchors BOTH to
something no Fourier-domain code wrote is the quadrature case: the
reference's shapelet envelope against a numerical Fourier transform of
the image-domain sum it claims to transform.  The mutation cases break
the program one way each (an envelope dropped, the mode grid transposed,
``i^(n1+n2)`` conjugated, the ``n0max`` padding read as live modes, a
spectral term dropped, the parse rule of the flux law in the per-channel
one's place) and the comparison has to fail every time.
"""

import math
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
sys.path.insert(0, ROOT)

import reference                    # noqa: E402
import reference_extended as rx     # noqa: E402
from sagecal_tpu import skymodel    # noqa: E402
from sagecal_tpu.rime import envelopes, predict as rp   # noqa: E402

CFG = dict(
    n_stations=7, n_clusters=3, n_sources_per_cluster=8, tilesz=2,
    tdelta_s=10.0, freq_hz=150e6, chan_width_hz=180e3, ra0_rad=1.2,
    dec0_rad=0.7, layout_seed=62, sky_seed=51, log_flux_mean=-1.5,
    jones_scale=0.15, noise_sigma=0.02, sky_format=1, f0_hz=130e6,
    sources={"P": 3, "G": 3, "D": 1, "R": 1}, shapelet_n0=[1, 4, 7],
    extents=dict(gaussian_major_asec=[20, 240],
                 gaussian_axis_ratio=[0.3, 1.0],
                 disk_ring_radius_asec=[30, 180],
                 shapelet_beta_asec=[20, 60],
                 shapelet_stretch=[[0.9, 1.2, 0.4], [0, 0, 0],
                                   [1.1, 0.85, 2.0]]),
    spectra=dict(si=[-0.7, 0.2], si1=[0, 0.1], si2=[0, 0.05],
                 flat_share=0.25))
#: the phase centre both readers are given: 3.6 degrees from the one the
#: sky was drawn around, which is where ``PROJ_CUT`` lies
RA0, DEC0 = CFG["ra0_rad"] + 0.05 / math.cos(CFG["dec0_rad"]), 0.74
FREQS = np.array([148e6, 152e6])
FDELTA = 90e3
#: relative to the rms of the reference: float64 is the Bessel
#: approximations' 1e-7 (a ring or a disk alone: 1.1e-6 of its rms);
#: float32 the fringe phases of a 30 km baseline
TOL = {jnp.float64: 3e-6, jnp.float32: 1e-3}
SEED = 3


def write_files(obs, out_dir, kinds=None):
    """The sky, cluster and modes files of ``obs`` under ``out_dir``;
    ``kinds``: only the sources whose name starts with one of them."""
    os.makedirs(out_dir, exist_ok=True)
    clusters = []
    for ln in obs.cluster_lines:
        t = ln.split()
        clusters.append(" ".join(t[:2] + [n for n in t[2:] if kinds is None
                                          or n[0] in kinds]))
    sky = os.path.join(out_dir, "sky.txt")
    with open(sky, "w") as f:
        f.write("\n".join(obs.sky_lines) + "\n")
    with open(sky + ".cluster", "w") as f:
        f.write("\n".join(clusters) + "\n")
    for name, text in obs.modes.items():
        with open(os.path.join(out_dir, name + ".fits.modes"), "w") as f:
            f.write(text)
    return sky, sky + ".cluster", clusters


@pytest.fixture(scope="module")
def obs():
    return rx.Observation(CFG, SEED)


@pytest.fixture(scope="module")
def uvw(obs):
    """Baselines of the 7-station layout (two of its stations remote),
    and the same ten times shorter: resolved and barely resolved."""
    u, v, w = obs.geometry(0)[:3]
    return tuple(np.concatenate([a, 0.1 * a]) for a in (u, v, w))


def both(obs, tmp_path, uvw, dtype, kinds=None):
    """(program [M, B, F], reference [M, B, F]) through the text."""
    sky_path, cluster_path, clusters = write_files(obs, str(tmp_path), kinds)
    sky = skymodel.read_sky_cluster(sky_path, cluster_path, RA0, DEC0,
                                    float(FREQS.mean()), format_3=True)
    ref_sky = rx.read_sky(obs.sky_lines, clusters, obs.modes, RA0, DEC0, 1)
    u, v, w = uvw
    got = np.asarray(rp.coherencies(
        rp.sky_to_device(sky, dtype), *(jnp.asarray(a, dtype) for a in uvw),
        jnp.asarray(FREQS, dtype), FDELTA, per_channel_flux=True))
    assert not got[..., 0, 1].any() and not got[..., 1, 0].any()
    np.testing.assert_array_equal(got[..., 0, 0], got[..., 1, 1])
    want = np.stack([rx.coherencies(ref_sky, u, v, w, f, FDELTA)
                     for f in FREQS], axis=-1)
    return got[..., 0, 0], want, sky, ref_sky


def off(got, want):
    return float(np.abs(got - want).max() / reference.rms(want))


# -- the program against the reference ---------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float64, jnp.float32],
                         ids=["float64", "float32"])
@pytest.mark.parametrize("kinds", [None, "P", "G", "D", "R", "S"],
                         ids=["all", "points", "gaussians", "disks", "rings",
                              "shapelets"])
def test_program_against_reference(obs, tmp_path, uvw, dtype, kinds):
    got, want, sky, ref_sky = both(obs, tmp_path, uvw, dtype, kinds)
    assert off(got, want) < TOL[dtype]
    # what the sky holds: both sides of the cut, three orders padded to
    # the largest, flat and curved spectra away from f0
    live = sky.smask
    if kinds in (None, "G", "S"):
        far = sky.use_projection[live & (sky.stype != skymodel.STYPE_POINT)]
        assert far.any() and not far.all()
    if kinds in (None, "S"):
        assert sorted(sky.sh_n0[sky.sh_n0 > 0]) == [1, 4, 7]
        assert sky.sh_modes.shape[-1] == 49
    assert (ref_sky.si == 0).any() and (ref_sky.si != 0).any()
    assert (ref_sky.si1[ref_sky.si == 0] != 0).all()
    assert (ref_sky.f0 == 130e6).all()


def test_each_control_is_far_from_the_sound_reference(obs, uvw):
    """The three falsifications of the benchmark's ``[control]`` line
    move the reference itself by far more than any tolerance here."""
    u, v, w = uvw
    sky = rx.read_sky(obs.sky_lines, obs.cluster_lines, obs.modes, RA0,
                      DEC0, 1)
    want = rx.coherencies(sky, u, v, w, FREQS[0], FDELTA)
    for control in ("points", "no_shapelets", "at_f0"):
        other = rx.coherencies(sky, u, v, w, FREQS[0], FDELTA,
                               **{control: True})
        assert reference.rms(other - want) > 0.05 * reference.rms(want)


# -- the program broken one way each -----------------------------------------

def _ones(*a, **k):
    return jnp.ones(jnp.broadcast_shapes(*(jnp.shape(x) for x in a[:4])))


def _flux_without(term):
    def flux(s0, spec_idx, spec_idx1, spec_idx2, f0, freq):
        z = jnp.zeros_like(spec_idx)
        return _SOUND_FLUX(s0, spec_idx, z if term == 1 else spec_idx1,
                           z if term == 2 else spec_idx2, f0, freq)
    return flux


def _flux_by_the_parse_rule(s0, spec_idx, spec_idx1, spec_idx2, f0, freq):
    """Scaled where ANY term is non-zero (``skymodel._scaled_flux``)."""
    fr = jnp.log(freq / f0)
    law = jnp.exp(spec_idx * fr + spec_idx1 * fr * fr + spec_idx2 * fr ** 3)
    any_term = (spec_idx != 0) | (spec_idx1 != 0) | (spec_idx2 != 0)
    return jnp.where(any_term, s0 * law, s0)


_SOUND_FLUX = rp._spectral_flux
_SOUND_TABLES = envelopes.shapelet_sign_tables
_SOUND_BUILD = skymodel.build_cluster_sky


def _tables_conjugated(n0max):
    sign, is_imag = _SOUND_TABLES(n0max)
    return np.where(is_imag == 1, -sign, sign), is_imag


def _build_transposed(sources, clusters, dtype=np.float64):
    """The file's ``c[n2, n1]`` read as ``c[n1, n2]``."""
    sky = _SOUND_BUILD(sources, clusters, dtype=dtype)
    n = int(round(math.sqrt(sky.sh_modes.shape[-1])))
    grid = sky.sh_modes.reshape(sky.sh_modes.shape[:2] + (n, n))
    sky.sh_modes = np.swapaxes(grid, -1, -2).reshape(sky.sh_modes.shape)
    return sky


def _build_unpadded(sources, clusters, dtype=np.float64):
    """A source's ``n0^2`` values laid at the head of the ``n0max^2``
    slots: the padding read as live modes of an ``n0max`` grid."""
    sky = _SOUND_BUILD(sources, clusters, dtype=dtype)
    for m, names in enumerate(sky.names):
        for s, name in enumerate(names):
            src = sources[name]
            if src.sh_n0:
                sky.sh_modes[m, s] = 0.0
                sky.sh_modes[m, s, :src.sh_n0 ** 2] = src.sh_modes
    return sky


MUTATIONS = {
    "gaussian-dropped": (envelopes, "gaussian", _ones, 10),
    "ring-dropped": (envelopes, "ring", _ones, 10),
    "disk-dropped": (envelopes, "disk", _ones, 10),
    "shapelet-dropped": (envelopes, "shapelet", _ones, 10),
    "sign-table-conjugated": (envelopes, "shapelet_sign_tables",
                              _tables_conjugated, 10),
    "mode-grid-transposed": (skymodel, "build_cluster_sky",
                             _build_transposed, 10),
    "padding-read-as-modes": (skymodel, "build_cluster_sky",
                              _build_unpadded, 10),
    # exp(si1 r^2 + si2 r^3) - 1 with r = ln(150 / 130): parts in a
    # thousand of a source, where the envelopes are the source itself
    "second-term-dropped": (rp, "_spectral_flux", _flux_without(1), 1),
    "third-term-dropped": (rp, "_spectral_flux", _flux_without(2), 0.1),
    "parse-rule-per-channel": (rp, "_spectral_flux",
                               _flux_by_the_parse_rule, 1),
}


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_a_broken_program_is_caught(obs, tmp_path, uvw, monkeypatch, name):
    """Against the WHOLE sky, in float64, by a multiple of the FLOAT32
    tolerance (the third spectral term a tenth of it, which is 30 times
    float64's): each mutation is seen with every other kind of source
    beside it.  (The sign table is symmetric in ``n1``, ``n2``, so
    transposing IT cannot matter; what can be transposed is the mode
    grid.)"""
    module, attr, broken, times = MUTATIONS[name]
    monkeypatch.setattr(module, attr, broken)
    got, want, _, _ = both(obs, tmp_path, uvw, jnp.float64)
    assert off(got, want) > times * TOL[jnp.float32], name


# -- the reference against a numerical Fourier transform ---------------------

@pytest.mark.parametrize("stretch", [(0.0, 0.0, 0.0), (0.9, 1.2, 0.4)],
                         ids=["as-decomposed", "stretched-and-turned"])
def test_shapelet_envelope_is_the_transform_of_its_image(stretch, tmp_path):
    """One source at the phase centre, ``n0 = 3``: ``pi^(3/2) / beta``
    times the trapezoid sum of ``shapelet_image(l, m) e^{2 pi i (u l + v
    m)}`` over a grid of 10 image scales either way (the image falls as a
    Gaussian, so the sum is exact to rounding) is the reference's
    envelope: sign, mirror, orientation of ``eP``, scale and constant.
    And the program gives the same number."""
    rng = np.random.default_rng(33)
    n0, beta = 3, 40 * rx.ASEC
    c = rng.normal(size=(n0, n0))
    ra0, dec0 = 1.2, 0.7
    line = rx.source_line("S0", ra0, dec0, 1.0, (0.0, 0.0, 0.0), stretch,
                          150e6, 1)
    modes = {"S0": rx.modes_text(n0, beta, c)}
    sky = rx.read_sky([line], ["1 1 S0"], modes, ra0, dec0, 1)
    assert abs(sky.ll[0, 0]) < 1e-12 and abs(sky.mm[0, 0]) < 1e-12
    coeff = sky.modes[0][0]
    np.testing.assert_allclose(coeff, c, rtol=1e-9)
    u = rng.uniform(-2.5, 2.5, 24) / beta
    v = rng.uniform(-2.5, 2.5, 24) / beta
    got = rx.envelope(sky, 0, 0, u, v, np.zeros_like(u))

    b = beta / (2 * np.pi)
    half = 10 * b / min(stretch[0] or 1.0, stretch[1] or 1.0)
    ax = np.linspace(-half, half, 601)
    ll, mm = np.meshgrid(ax, ax, indexing="ij")
    image = rx.shapelet_image(ll, mm, *stretch, beta, coeff)
    step = ax[1] - ax[0]
    kernel_l = np.exp(2j * np.pi * np.outer(u, ax))         # [K, L]
    kernel_m = np.exp(2j * np.pi * np.outer(v, ax))
    plain = np.einsum("kl,lm,km->k", kernel_l, image, kernel_m) * step ** 2
    want = math.pi ** 1.5 / beta * plain
    assert np.abs(got - want).max() < 1e-9 * np.abs(want).max()
    assert np.abs(got.imag).max() > 0.1 * np.abs(got).max()    # odd modes

    # the program, through the text
    sky_path = tmp_path / "sky.txt"
    sky_path.write_text(line + "\n")
    (tmp_path / "sky.txt.cluster").write_text("1 1 S0\n")
    (tmp_path / "S0.fits.modes").write_text(modes["S0"])
    psky = skymodel.read_sky_cluster(str(sky_path), str(sky_path) +
                                     ".cluster", ra0, dec0, 150e6, True)
    prog = np.asarray(rp.coherencies(
        rp.sky_to_device(psky, jnp.float64), jnp.asarray(u / 150e6),
        jnp.asarray(v / 150e6), jnp.zeros(len(u)), jnp.asarray([150e6]),
        0.0))[0, :, 0, 0, 0]
    assert np.abs(prog - want).max() < 1e-9 * np.abs(want).max()


def test_hermite_functions_are_orthonormal():
    x = np.linspace(-12, 12, 4001)
    psi = rx.hermite_functions(x, 10)
    gram = psi @ psi.T * (x[1] - x[0])
    assert np.abs(gram - np.eye(10)).max() < 1e-12


# -- Bessel functions ---------------------------------------------------------

@pytest.mark.parametrize("order, approx", [(0, envelopes._bessel_j0),
                                           (1, envelopes._bessel_j1)],
                         ids=["J0", "J1"])
def test_bessel_integral_against_the_programs_approximations(order, approx):
    """Bessel's integral (the reference) and the rational approximations
    (the program, float64) on both sides of their switch at 8, to the
    1e-7 the approximations state; and the integral against the power
    series where that converges without cancellation."""
    x = np.concatenate([np.linspace(-20, 20, 801), np.linspace(20, 110, 901)])
    want = rx.bessel_j(order, x)
    assert np.abs(np.asarray(approx(jnp.asarray(x))) - want).max() < 1e-7
    small = np.linspace(0, 6, 61)
    series = sum((-1) ** k * (small / 2) ** (2 * k + order)
                 / (math.factorial(k) * math.factorial(k + order))
                 for k in range(40))
    assert np.abs(rx.bessel_j(order, small) - series).max() < 1e-13


# -- the text, round trip -----------------------------------------------------

def test_the_programs_reader_gives_the_references_sky(obs, tmp_path):
    sky_path, cluster_path, _ = write_files(obs, str(tmp_path))
    sky = skymodel.read_sky_cluster(sky_path, cluster_path, RA0, DEC0, 150e6,
                                    format_3=True)
    ref = rx.read_sky(obs.sky_lines, obs.cluster_lines, obs.modes, RA0, DEC0,
                      1)
    assert sky.smask.all() and sky.names == ref.names
    for mine, theirs in (("ll", "ll"), ("mm", "mm"), ("nn", "nn"),
                         ("sI0", "flux"), ("spec_idx", "si"),
                         ("spec_idx1", "si1"), ("spec_idx2", "si2"),
                         ("f0", "f0"), ("sh_n0", "n0")):
        np.testing.assert_allclose(getattr(sky, mine), getattr(ref, theirs),
                                   rtol=0, atol=1e-15, err_msg=mine)
    np.testing.assert_array_equal(sky.stype, ref.kind)
    assert ref.counts() == {"point": 6, "gaussian": 9, "disk": 3, "ring": 3,
                            "shapelet": 3}
    gauss, shp = ref.kind == rx.GAUSSIAN, ref.kind == rx.SHAPELET
    round_ = (ref.kind == rx.DISK) | (ref.kind == rx.RING)
    # the parser doubles a Gaussian's axes and reads a shapelet's 0 as 1
    np.testing.assert_array_equal(sky.eX[gauss], 2 * ref.eX[gauss])
    np.testing.assert_array_equal(sky.eY[gauss], 2 * ref.eY[gauss])
    np.testing.assert_array_equal(sky.eX[round_], ref.eX[round_])
    np.testing.assert_array_equal(sky.eX[shp], np.where(
        ref.eX[shp] == 0, 1.0, ref.eX[shp]))
    assert (ref.eX[shp] == 0).any() and (ref.eX[shp] != 0).any()
    np.testing.assert_array_equal(sky.eP[ref.kind != rx.POINT],
                                  ref.eP[ref.kind != rx.POINT])
    np.testing.assert_array_equal(sky.sh_beta[shp], ref.beta[shp])
    np.testing.assert_array_equal(
        sky.use_projection,
        (ref.nn + 1 < rx.PROJ_CUT) & (ref.kind != rx.POINT))
    for m, s in zip(*np.nonzero(shp)):
        n0 = ref.n0[m, s]
        grid = sky.sh_modes[m, s].reshape(7, 7)
        np.testing.assert_array_equal(grid[:n0, :n0], ref.modes[m][s])
        assert not grid[n0:].any() and not grid[:, n0:].any()
    # every shapelet's zero-spacing flux is its cluster's brightest other
    for m, s in zip(*np.nonzero(shp)):
        total = ref.flux[m, s] * rx.envelope(
            ref, m, s, np.zeros(1), np.zeros(1), np.zeros(1))[0]
        others = np.delete(ref.flux[m], s)
        if ref.nn[m, s] + 1 >= rx.PROJ_CUT:
            assert total.real == pytest.approx(others.max(), rel=1e-6)
        assert abs(total.imag) < 1e-12
