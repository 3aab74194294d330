"""The program against ``benchmarks/reference_beam.py`` at a tiny size:
upstream's ``-B 1`` (the stations' array-factor gains on every source,
folded into the source sum) through ``cli`` and ``FullBatchPipeline`` from
files on disk that carry the reference's time stamps and its stations as
``beam.npz``.

8 stations (6 core "ears" of 4 live elements, 2 remote stations of 6,
``Emax`` 6), 2 clusters of 3 point sources, 4 timeslots a tile.  The
reference is numpy in float64 from published formulae of its own choice
(GMST: Meeus 12.4; precession: IAU 1976; horizon coordinates from the
spherical triangle) and imports nothing of the program; the suite runs
the program in float64 too (conftest turns x64 on), so each tolerance
below is about a difference between two published formulae or about text
formats, not about f32, and says which.

    JAX_PLATFORMS=cpu python -m pytest tests/test_beam_reference.py -q
"""

import ast
import math
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from sagecal_tpu import cli, coords, pipeline, skymodel
from sagecal_tpu.io import dataset as ds
from sagecal_tpu.rime import beam as bm, predict as rp

BENCH = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "drivers"))
import datagen                          # noqa: E402
import reference                        # noqa: E402
import reference_beam as refb           # noqa: E402
import calibrate_beam                   # noqa: E402  (the cell's driver)

CFG = {
    "n_stations": 8, "n_clusters": 2, "n_sources_per_cluster": 3,
    "tilesz": 4, "tdelta_s": 10.0, "freq_hz": 150e6,
    "chan_width_hz": 180e3, "ra0_rad": 1.2, "dec0_rad": 0.7,
    "layout_seed": 62, "sky_seed": 83, "sky_format": 1,
    "log_flux_mean": 0.5, "jones_scale": 0.15, "jones_per_interval": False,
    "noise_sigma": 0.02, "beam_elements_core": 4, "beam_elements_remote": 6,
}
SEED = 2 ** 31 + 48
N_TILES = 3
FLAGS = ["-t", "4", "-e", "4", "-g", "2", "-l", "10", "-m", "7", "-F", "1",
         "-j", "5"]


@pytest.fixture(scope="module")
def obs():
    return refb.Observation(CFG, SEED)


@pytest.fixture(scope="module")
def files(obs, tmp_path_factory):
    """The observation as the files a user has: sky, cluster file, and a
    SimMS with the reference's time stamps and ``beam.npz``, written by
    the benchmark's own driver."""
    root = str(tmp_path_factory.mktemp("beam"))
    sky_path, cluster_path = datagen.write_sky(obs, root)
    ms_path = calibrate_beam.write_observation(obs, root, N_TILES)
    return {"root": root, "sky": sky_path, "cluster": cluster_path,
            "ms": ms_path}


def solve(files, beam, name):
    sol = os.path.join(files["root"], name + ".solutions")
    assert cli.main(["-d", files["ms"], "-s", files["sky"],
                     "-c", files["cluster"], "-p", sol, "--platform", "cpu",
                     *FLAGS, "-B", str(beam)]) == 0
    written = reference.read_solutions(sol)
    residual = [datagen.read_column(files["ms"], t, "x_corrected_data")
                for t in range(N_TILES)]
    return written, residual


@pytest.fixture(scope="module")
def solved(files):
    """``python -m sagecal_tpu.cli -B 1`` on those files, then ``-B 0``
    on the same data (the output column is overwritten: each is read
    back before the next run)."""
    return {1: solve(files, 1, "beam"), 0: solve(files, 0, "nobeam")}


# -- the reference alone ------------------------------------------------------

def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference_beam.py")) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add(node.module.split(".")[0])
    assert names == {"__future__", "math", "multiprocessing", "numpy", "os",
                     "reference"}


def test_the_layouts_are_what_the_file_says(obs):
    """Six core ears of 4 live elements and two remote stations of 6, on
    the 5.15 m grid (nearest neighbours 5.15 m apart whatever the turn),
    horizontal, the masked slots zero; 24 and 48 cut a disc."""
    assert obs.elem.shape == (8, 6, 3) and obs.mask.shape == (8, 6)
    assert list(obs.mask.sum(axis=1)) == [4] * 6 + [6] * 2
    assert not obs.elem[~obs.mask].any() and not obs.elem[..., 2].any()
    for n in range(8):
        p = obs.elem[n, obs.mask[n], :2]
        d = np.linalg.norm(p[:, None] - p[None], axis=-1)
        assert np.min(d[d > 0]) == pytest.approx(refb.PITCH_M)
        assert len(p) == 6 or np.abs(p.mean(axis=0)).max() < 1e-9
    for count, radius in ((24, math.sqrt(6.5)), (48, math.sqrt(14.5))):
        g = refb.grid_disc(count)
        assert len({tuple(r) for r in g}) == count
        assert np.hypot(g[:, 0], g[:, 1]).max() == pytest.approx(radius)
        assert np.abs(g.sum(axis=0)).max() == 0      # symmetric
    # stations lie within a few km of the core
    assert np.abs(obs.lon - refb.LON0).max() < 0.01
    assert np.abs(obs.lat - refb.LAT0).max() < 0.01


def test_signs_of_the_horizon_frame_without_the_program():
    """A source on the meridian north of the zenith stands at azimuth 0;
    one six hours past the meridian on the equator sets due west; one
    east of the meridian has an azimuth under pi; twelve hours from the
    meridian at declination 0 is under the horizon by the colatitude."""
    lat, lon, gmst = refb.LAT0, refb.LON0, 1.0
    lst = gmst + lon
    az, el = refb.azel(lst, lat + math.radians(60), lon, lat, gmst)
    assert (az + 1e-9) % (2 * np.pi) < 1e-6
    assert el == pytest.approx(math.radians(30))
    az, el = refb.azel(lst - np.pi / 2, 0.0, lon, lat, gmst)    # H = +6 h
    assert az == pytest.approx(1.5 * np.pi) and abs(el) < 1e-12
    az, _ = refb.azel(lst + 0.3, 0.5, lon, lat, gmst)           # H < 0
    assert 0 < az < np.pi
    _, el = refb.azel(lst + np.pi, 0.0, lon, lat, gmst)
    assert el == pytest.approx(lat - np.pi / 2)


def test_the_array_factor_at_the_pointing_in_the_north_and_under_ground():
    """Two elements 5 m apart along the NORTH axis, beamformed at the
    zenith: a source at elevation ``el`` due north sees ``|cos(pi f d
    cos(el) / c)|``, the same source due east sees 1 (no baseline along
    east), the pointing itself sees 1, and a source under the horizon 0.
    Elements along the WEST axis swap the two."""
    lat, lon, gmst, f = refb.LAT0, refb.LON0, 1.0, 150e6
    lst = gmst + lon
    elem = np.zeros((2, 2, 3))
    elem[0, :, 0] = [-2.5, 2.5]         # station 0: along north
    elem[1, :, 1] = [-2.5, 2.5]         # station 1: along west
    mask = np.ones((2, 2), bool)
    el = math.radians(25.0)
    north = (lst, lat + (np.pi / 2 - el))           # over the pole side
    # due east at elevation el: solve the triangle for (H, dec)
    dec_e = math.asin(math.sin(lat) * math.sin(el))
    h_e = -math.acos((math.sin(el) - math.sin(lat) * math.sin(dec_e))
                     / (math.cos(lat) * math.cos(dec_e)))
    east = (lst - h_e, dec_e)
    az, el_e = refb.azel(*east, lon, lat, gmst)
    assert az == pytest.approx(np.pi / 2) and el_e == pytest.approx(el)
    ra = np.array([lst, north[0], east[0], lst + np.pi])
    dec = np.array([lat, north[1], east[1], 0.0])
    g = refb.array_factor(ra, dec, lst, lat, np.full(2, lon),
                          np.full(2, lat), np.array([gmst]), elem, mask,
                          f, f)[:, 0]
    fringe = abs(math.cos(np.pi * f * 5.0 * math.cos(el) / refb.C_M_S))
    assert 0.05 < fringe < 0.95
    assert g[0] == pytest.approx([1.0, 1.0])
    assert g[1] == pytest.approx([fringe, 1.0])
    assert g[2] == pytest.approx([1.0, fringe])
    assert list(g[3]) == [0.0, 0.0]


def test_time_stamps_put_the_pointing_at_the_seeds_hour_angle(obs):
    """The first timeslot's centre is the first instant after the epoch
    at which the pointing of date stands at ``ha0`` at the core, and
    later slots follow ``tdelta`` apart, tile after tile."""
    t0 = obs.time_mjd(0)
    assert 0 <= t0[0] - 0.5 * obs.tdelta - refb.EPOCH_MJD_S < 86164.1
    h = refb.gmst_rad(t0[0]) + refb.LON0 - obs.point_date[0]
    assert (h - obs.ha0 + np.pi) % (2 * np.pi) - np.pi \
        == pytest.approx(0.0, abs=1e-6)     # the epoch of the precession
    both = np.concatenate([t0, obs.time_mjd(1)])
    assert np.diff(both) == pytest.approx(obs.tdelta)
    _, el = refb.azel(*obs.point_date, refb.LON0, refb.LAT0,
                      refb.gmst_rad(both))
    assert el.min() > math.radians(45)


def test_pool_tiles_equal_the_serial_ones(obs):
    serial = refb.Observation(CFG, SEED)
    x_serial = refb.make_tiles(serial, 2, workers=0)
    pooled = refb.Observation(CFG, SEED)
    x_pooled = refb.make_tiles(pooled, 2, workers=2)
    for t in range(2):
        assert np.array_equal(x_serial[t], x_pooled[t])
        assert np.array_equal(serial.kept[t], pooled.kept[t])
    assert sorted(pooled.kept) == [0, 1]


# -- the program against it ---------------------------------------------------

def test_gmst_and_horizon_coordinates(obs):
    """GMST: the program's series in seconds (Vallado) and the
    reference's in degrees (Meeus) are the same IAU 1982 expression; what
    is left is the program's Julian date in float64 (4e-5 s of time, 3e-9
    rad).  Azimuth and elevation: one spherical triangle, two formulae."""
    t = obs.time_mjd(1)
    prog = np.deg2rad(coords.jd2gmst_np(t / 86400.0 + 2400000.5))
    assert np.abs((prog - refb.gmst_rad(t) + np.pi) % (2 * np.pi)
                  - np.pi).max() < 2e-8
    gmst = refb.gmst_rad(t)
    ra = obs.ra_date.reshape(-1)[:, None, None]
    dec = obs.dec_date.reshape(-1)[:, None, None]
    lon, lat = obs.lon[None, None, :], obs.lat[None, None, :]
    az, el = refb.azel(ra, dec, lon, lat, gmst[None, :, None])
    paz, pel = coords.radec2azel_gmst(ra, dec, lon, lat,
                                      np.rad2deg(gmst)[None, :, None])
    assert np.abs(np.asarray(pel) - el).max() < 1e-10
    assert np.abs((np.asarray(paz) - az + np.pi) % (2 * np.pi)
                  - np.pi).max() < 1e-10


def test_precessed_positions(obs):
    """The program precesses with the IAU 2006 four-angle rotation
    (Capitaine et al. 2003), the reference with the IAU 1976 three
    angles: IAU 2000 corrected the rate of precession in longitude by
    -0.3 arcseconds a century, 45 mas over the fifteen years to the
    observation (2.2e-7 rad, the same for every source and the pointing),
    against the 3.6e-3 rad that precession moves a source."""
    jd = obs.epoch / 86400.0 + 2400000.5
    pra, pdec = coords.precess_radec_std(
        jnp.asarray(obs.ra_j2000.reshape(-1)),
        jnp.asarray(obs.dec_j2000.reshape(-1)),
        coords.precession_matrix(jd))
    moved = np.hypot((obs.ra_date - obs.ra_j2000) * np.cos(obs.dec_j2000),
                     obs.dec_date - obs.dec_j2000)
    assert 2e-3 < moved.min() and moved.max() < 5e-3
    assert np.abs((np.asarray(pra) - obs.ra_date.reshape(-1))
                  * np.cos(obs.dec_date.reshape(-1))).max() < 5e-7
    assert np.abs(np.asarray(pdec) - obs.dec_date.reshape(-1)).max() < 5e-7


@pytest.fixture(scope="module")
def program_beam(obs):
    """The reference's stations as the program's device beam, at tile 1's
    time stamps and the pointing of date."""
    info = bm.BeamInfo(
        longitude=obs.lon, latitude=obs.lat,
        time_jd=obs.time_mjd(1) / 86400.0 + 2400000.5,
        ra0=obs.point_date[0], dec0=obs.point_date[1], freq0=obs.freq0,
        elem_xyz=obs.elem, elem_mask=obs.mask)
    return info, bm.beam_to_device(info, obs.freq, jnp.float64)


def test_the_array_factor(obs, program_beam):
    """Same positions in, so this is the frame's signs, the elements and
    the mask: float64 rounding of a sum of at most six unit phasors, and
    the program's sidereal angle from a Julian date (3e-9 rad)."""
    info, beam = program_beam
    want = obs.gains(1)                                 # [M, S, T, N]
    for m in range(obs.n_dir):
        got = np.asarray(bm.array_factor(
            beam, jnp.asarray(obs.ra_date[m]), jnp.asarray(obs.dec_date[m]),
            obs.freq))
        assert np.abs(got - want[m]).max() < 1e-7
    assert 0.5 < want.min() and want.max() < 1.0 and np.ptp(want) > 0.01
    # unity at the pointing, nothing under the horizon
    ra = jnp.asarray([obs.point_date[0], obs.point_date[0] + np.pi])
    dec = jnp.asarray([obs.point_date[1], -obs.point_date[1]])
    got = np.asarray(bm.array_factor(beam, ra, dec, obs.freq))
    assert got[0] == pytest.approx(1.0) and not got[1].any()
    assert refb.array_factor(
        np.asarray(ra), np.asarray(dec), *obs.point_date, obs.lon, obs.lat,
        refb.gmst_rad(obs.time_mjd(1)), obs.elem, obs.mask, obs.freq,
        obs.freq0)[0] == pytest.approx(1.0)
    # masked slots are left out: what they hold does not matter
    junk = obs.elem.copy()
    junk[~obs.mask] = 1e3
    beam_junk = bm.beam_to_device(
        bm.BeamInfo(**{**vars(info), "elem_xyz": junk}), obs.freq,
        jnp.float64)
    again = np.asarray(bm.array_factor(
        beam_junk, jnp.asarray(obs.ra_date[0]), jnp.asarray(obs.dec_date[0]),
        obs.freq))
    assert np.abs(again - want[0]).max() < 1e-7


def test_the_model_with_gains(obs, files):
    """The pipeline as ``cli -B 1`` builds it from the files: its
    precessed sky, its tile beam and ``rp.coherencies`` against the
    reference's beam-weighted coherencies.  1e-6 of the rms: the two
    precessions' 2e-7 rad, nearly the same for a source and the pointing,
    on gains that change by 10 a radian, the sky text's nine decimals of
    a second, and the smearing's sinc."""
    cfg = cli.config_from_args(cli.build_parser().parse_args(
        ["-d", files["ms"], "-s", files["sky"], "-c", files["cluster"],
         *FLAGS, "-B", "1"]))
    ms = ds.open_dataset(cfg.ms, cfg.ms_list, tilesz=cfg.tile_size)
    sky = skymodel.read_sky_cluster(cfg.sky_model, cfg.cluster_file,
                                    obs.ra0, obs.dec0, obs.freq, True)
    log = []
    pipe = pipeline.FullBatchPipeline(cfg, ms, sky, log=log.append)
    assert not any("SYNTHETIC" in ln for ln in log), log
    assert pipe.precessed and pipe.coh_record == {
        "coh_path": "xla", "beam_mode": 1, "beam_elements": 6,
        "beam_sources": 6, "sources_point": 6, "sources_gaussian": 0,
        "sources_disk": 0, "sources_ring": 0, "sources_shapelet": 0,
        "shapelet_n0max": 0, "shapelet_slots": 0}
    tile = ms.read_tile(1)
    assert np.array_equal(tile.time_mjd, obs.time_mjd(1))
    beam = pipe._tile_beam(tile, 1)
    u, v, w, s1, s2 = obs.geometry(1)
    f = pipe.rdt
    got = np.asarray(rp.coherencies(
        pipe.dsky, jnp.asarray(u, f), jnp.asarray(v, f), jnp.asarray(w, f),
        jnp.asarray([obs.freq], f), obs.fdelta, beam=beam, dobeam=1,
        tslot=jnp.asarray(pipe.tslot), sta1=jnp.asarray(s1),
        sta2=jnp.asarray(s2)))[:, :, 0]                 # [M, B, 2, 2]
    want = obs.coherencies(1)
    tol = (1e-6 if f == jnp.float64 else 1e-3) * reference.rms(want)
    assert np.abs(got[..., 0, 0] - want).max() < tol
    assert np.abs(got[..., 1, 1] - want).max() < tol
    assert not got[..., 0, 1].any() and not got[..., 1, 0].any()
    # and the beam is in it: the same sum without gains is far away
    bare = reference.coherencies(obs.sky, u, v, w, obs.freq, obs.fdelta)
    assert reference.rms(want - bare) > 0.02 * reference.rms(want)


def compared(obs, files, written, residual):
    """(a, b) as the cell's check reads them, worst tile."""
    j_true = obs.jones()
    worst_a = worst_b = 0.0
    for t in range(N_TILES):
        x = datagen.read_column(files["ms"], t, "x")
        r_ref = x - obs.model(t, written[t])
        floor = reference.rms(x - obs.model(t, j_true))
        worst_a = max(worst_a, reference.rms(residual[t] - r_ref)
                      / reference.rms(r_ref))
        worst_b = max(worst_b, reference.rms(r_ref) / floor)
    return worst_a, worst_b


def test_written_residual_and_solutions_through_cli(obs, files, solved):
    """``cli.main -B 1`` from files on disk: the written residual is the
    data minus the reference's model with the beam's gains under the
    WRITTEN solutions (1e-4 of the noise-like rest, which is 1e-6 of the
    model: float64 on both sides, the two precessions, the solutions'
    nine decimals; it read 2.6e-5), and the solve reaches the noise
    floor (2 x 8 x 8 = 128 real parameters fitted to 28 x 4 x 8 = 896
    real data leave sqrt(1 - 128/896) = 0.93 of the noise; under 1.05 as
    the cells hold it)."""
    written, residual = solved[1]
    assert len(written) == N_TILES and written[0].shape == (2, 8, 2, 2)
    a, b = compared(obs, files, written, residual)
    assert a < 1e-4, a
    assert 0.8 < b < 1.05, b


def test_the_same_data_under_B0_is_not_inside_the_tolerance(
        obs, files, solved):
    """Solved without the beam the Jones absorb each cluster's mean gain
    (0.9 here), the reference's model with the beam then applies it
    twice, and a gain that differs from source to source and from
    timeslot to timeslot cannot be absorbed at all: both numbers are far
    outside what ``-B 1`` reads (they read 1.0 and 11.9)."""
    written, residual = solved[0]
    a, b = compared(obs, files, written, residual)
    assert a > 0.5 and b > 2.0, (a, b)
