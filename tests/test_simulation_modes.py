"""The simulation modes through ``cli`` and ``pipeline.run``: ``-a`` 1
(replace), 2 (add), 3 (subtract), each with and without a solutions
file (``-p``) and an ignore list (``-z``), against the measurement
equation written out here in numpy float64.

The output column is compared with ``m``, ``x + m``, ``x - m``, where
``m`` is the model of the clusters the ignore list leaves, under that
tile's interval of the solutions file (or uncorrupted); DATA has to be
what it was, and every ``tile`` record has to say the mode and how many
clusters its model held.
"""

import math

import numpy as np
import jax.numpy as jnp
import pytest

from sagecal_tpu import cli, pipeline, skymodel
from sagecal_tpu.diag import trace as dtrace
from sagecal_tpu.io import dataset as ds, solutions as sol
from sagecal_tpu.rime import predict as rp

RA0 = (0 + 41 / 60) * math.pi / 12
DEC0 = 40 * math.pi / 180
FREQ, CHAN = 150e6, 0.18e6
N_STA, TILESZ, N_TILES = 6, 3, 2
#: cluster id -> [(ra h m s, dec d m s, flux Jy)], one chunk each
SOURCES = {
    0: [((0, 40, 0), (40, 0, 0), 3.0), ((0, 42, 0), (40, 30, 0), 2.0)],
    1: [((0, 44, 0), (39, 10, 0), 2.5)],
    2: [((0, 38, 30), (41, 0, 0), 1.5), ((0, 39, 0), (40, 45, 0), 0.7)],
}
IGNORED = 1


def lmn(ra_hms, dec_dms):
    ra = (ra_hms[0] + ra_hms[1] / 60 + ra_hms[2] / 3600) * math.pi / 12
    dec = (dec_dms[0] + dec_dms[1] / 60 + dec_dms[2] / 3600) * math.pi / 180
    ll = math.cos(dec) * math.sin(ra - RA0)
    mm = (math.sin(dec) * math.cos(DEC0)
          - math.cos(dec) * math.sin(DEC0) * math.cos(ra - RA0))
    return ll, mm, math.sqrt(1 - ll * ll - mm * mm) - 1.0


def model_f64(tile, jones, keep):
    """sum over the clusters in ``keep`` of J_p C J_q^H, [B, 2, 2]:
    point sources, flat spectrum, the channel's smearing; ``jones``
    [M, N, 2, 2] or None."""
    u, v, w = tile.u, tile.v, tile.w         # seconds
    out = np.zeros((u.shape[0], 2, 2), np.complex128)
    for cid in keep:
        coh = np.zeros(u.shape[0], np.complex128)
        for ra, dec, flux in SOURCES[cid]:
            ll, mm, nn = lmn(ra, dec)
            g = 2 * np.pi * (u * ll + v * mm + w * nn)
            half = 0.5 * g * CHAN
            smear = np.abs(np.where(half == 0, 1.0, np.sin(half)
                                    / np.where(half == 0, 1.0, half)))
            coh += flux * smear * np.exp(1j * g * FREQ)
        c22 = coh[:, None, None] * np.eye(2)
        if jones is None:
            out += c22
        else:
            jp, jq = jones[cid][tile.sta1], jones[cid][tile.sta2]
            out += np.einsum("bij,bjk,blk->bil", jp, c22, jq.conj())
    return out


@pytest.fixture(scope="module")
def observation(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("simmodes")
    lines, clusters = [], []
    for cid, srcs in SOURCES.items():
        names = []
        for k, (ra, dec, flux) in enumerate(srcs):
            names.append(f"P{cid}{'ABC'[k]}")
            lines.append(f"{names[-1]} {ra[0]} {ra[1]} {ra[2]} {dec[0]} "
                         f"{dec[1]} {dec[2]} {flux} 0 0 0 0 0 0 0 0 "
                         f"{FREQ:g}")
        clusters.append(f"{cid} 1 " + " ".join(names))
    sky_path = tmp / "sky.txt"
    sky_path.write_text("\n".join(lines) + "\n")
    clus_path = tmp / "sky.txt.cluster"
    clus_path.write_text("\n".join(clusters) + "\n")
    sky = skymodel.read_sky_cluster(str(sky_path), str(clus_path), RA0,
                                    DEC0, FREQ)
    dsky = rp.sky_to_device(sky, jnp.float64)
    # DATA: any sky will do for an input column; this one is the model
    # under Jones of its own plus noise
    tiles = [ds.simulate_dataset(
        dsky, n_stations=N_STA, tilesz=TILESZ, freqs=[FREQ], ra0=RA0,
        dec0=DEC0, jones=ds.random_jones(len(SOURCES), sky.nchunk, N_STA,
                                         seed=40 + i, scale=0.2),
        nchunk=sky.nchunk, noise_sigma=0.05, seed=3, chan_width=CHAN)
        for i in range(N_TILES)]
    ms_path = tmp / "sim.ms"
    ds.SimMS.create(str(ms_path), tiles)
    # the solutions file: seeded Jones, another draw for each interval
    jones = [ds.random_jones(len(SOURCES), sky.nchunk, N_STA, seed=7 + i,
                             scale=0.3) for i in range(N_TILES)]
    sol_path = tmp / "given.solutions"
    with sol.SolutionWriter(str(sol_path), FREQ, CHAN, 0.5, N_STA,
                            len(SOURCES), len(SOURCES)) as wr:
        for j in jones:
            wr.write_interval(j, sky.nchunk)
    # what the program will read of them: the file holds six digits
    _, blocks = sol.read_solutions(str(sol_path), sky.nchunk)
    ignore_path = tmp / "ignore.txt"
    ignore_path.write_text(f"# the target\n{IGNORED}\n")
    return {"tmp": tmp, "ms": str(ms_path), "sky": str(sky_path),
            "clusters": str(clus_path), "solutions": str(sol_path),
            "ignore": str(ignore_path),
            "jones": [np.asarray(b)[:, 0] for b in blocks]}


@pytest.mark.parametrize("ignore", [False, True], ids=["all", "z"])
@pytest.mark.parametrize("solutions", [False, True], ids=["plain", "p"])
@pytest.mark.parametrize("mode", [1, 2, 3])
def test_simulation_mode(observation, mode, solutions, ignore):
    o = observation
    argv = ["-d", o["ms"], "-s", o["sky"], "-c", o["clusters"],
            "-t", str(TILESZ), "-a", str(mode)]
    if solutions:
        argv += ["-p", o["solutions"]]
    if ignore:
        argv += ["-z", o["ignore"]]
    cfg = cli.config_from_args(cli.build_parser().parse_args(argv))
    diag = o["tmp"] / f"diag-{mode}-{solutions}-{ignore}.jsonl"
    data_before = [ds.SimMS(o["ms"]).read_tile(i).x for i in range(N_TILES)]
    dtrace.enable(str(diag), entry="test_simulation_modes")
    try:
        pipeline.run(cfg, log=lambda *a: None)
    finally:
        dtrace.disable()

    keep = [cid for cid in SOURCES if not (ignore and cid == IGNORED)]
    written = ds.SimMS(o["ms"], data_column="CORRECTED_DATA")
    for i in range(N_TILES):
        tile = ds.SimMS(o["ms"]).read_tile(i)
        np.testing.assert_array_equal(tile.x, data_before[i])
        x = tile.x[:, 0]
        m = model_f64(tile, o["jones"][i] if solutions else None, keep)
        want = {1: m, 2: x + m, 3: x - m}[mode]
        got = written.read_tile(i).x[:, 0]
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-8)
        # the model is there to be told from nothing: a subtract that
        # did nothing would pass a loose comparison
        assert np.abs(m).mean() > 1.0

    records = [r for r in dtrace.read(str(diag)) if r.get("ev") == "tile"]
    assert [r["tile"] for r in records] == list(range(N_TILES))
    for r in records:
        assert r["mode"] == mode
        assert r["clusters_in_model"] == len(keep)
