"""The program against ``benchmarks/reference_hybrid.py`` at a tiny size:
upstream's hybrid cluster file (chunk counts in the second column, a
negative id for the direction that is solved and kept) through ``cli``
and ``FullBatchPipeline`` from files on disk.

8 stations, 4 clusters of 2 point sources with chunk counts 3, 2, 1, 1 by
summed flux (7 effective clusters, kmax 3), the brightest one's id
negative, SEVEN timeslots a tile: seven is divided by neither 3 nor 2, so
upstream's ``ceil`` rule (``lmfit.c:893-899``: chunks of 3, 3, 1 and of 4,
3 timeslots) and a ``floor`` rule (2, 2, 3 and 3, 4) cut both chunked
clusters differently and a test can tell them apart.  The reference is
numpy in float64 and imports nothing of the program; the suite runs the
program in float64 too (conftest turns x64 on), so tolerances here are
about text formats and summation order, not about f32.

    JAX_PLATFORMS=cpu python -m pytest tests/test_hybrid_reference.py -q
"""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from sagecal_tpu import cli, skymodel
from sagecal_tpu.diag import trace as dtrace
from sagecal_tpu.io import solutions
from sagecal_tpu.rime import predict as rp, residual as rr

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))
import datagen                          # noqa: E402
import reference                        # noqa: E402
import reference_hybrid as refh         # noqa: E402

CFG = {
    "n_stations": 8, "n_clusters": 4, "n_sources_per_cluster": 2,
    "tilesz": 7, "tdelta_s": 10.0, "freq_hz": 150e6,
    "chan_width_hz": 180e3, "ra0_rad": 1.2, "dec0_rad": 0.7,
    "layout_seed": 62, "sky_seed": 83, "sky_format": 1,
    "log_flux_mean": 0.5, "jones_scale": 0.15, "jones_per_interval": False,
    "noise_sigma": 0.02, "nchunk_by_flux_rank": [3, 2, 1, 1],
    "kept_flux_ranks": [0], "chunk_jones_scale": 0.05,
}
SEED = 2 ** 31 + 44
N_TILES = 4
FLAGS = ["-t", "7", "-e", "4", "-g", "2", "-l", "10", "-m", "7", "-F", "1",
         "-j", "5"]


@pytest.fixture(scope="module")
def hyb():
    return refh.Observation(CFG, SEED)


@pytest.fixture(scope="module")
def files(hyb, tmp_path_factory):
    """The observation as the files a user has: sky, cluster file, SimMS."""
    root = str(tmp_path_factory.mktemp("hybrid"))
    sky_path, cluster_path = datagen.write_sky(hyb, root)
    ms_path = datagen.write_observation(hyb, root, N_TILES, "calibrate")
    return {"root": root, "sky": sky_path, "cluster": cluster_path,
            "ms": ms_path}


@pytest.fixture(scope="module")
def program_sky(hyb, files):
    return skymodel.read_sky_cluster(files["sky"], files["cluster"],
                                     hyb.ra0, hyb.dec0, hyb.freq, True)


@pytest.fixture(scope="module")
def solved(files):
    """``python -m sagecal_tpu.cli`` on those files, with ``--diag``."""
    sol = os.path.join(files["root"], "out.solutions")
    diag = os.path.join(files["root"], "diag.jsonl")
    assert cli.main(["-d", files["ms"], "-s", files["sky"],
                     "-c", files["cluster"], "-p", sol, "--diag", diag,
                     "--platform", "cpu", *FLAGS]) == 0
    return {**files, "sol": sol, "diag": diag}


def program_geometry(hyb, tile):
    u, v, w, s1, s2 = hyb.geometry(tile)
    f64, i32 = jnp.float64, jnp.int32
    return (jnp.asarray(u, f64), jnp.asarray(v, f64), jnp.asarray(w, f64),
            jnp.asarray(s1, i32), jnp.asarray(s2, i32))


def program_residual(hyb, sky, tile, x, jones, mask):
    """The program's residual of ``x`` [B, 2, 2] under ``jones`` (NaN in
    dead slots replaced as the program's own reader does), subtracting
    the clusters of ``mask``."""
    u, v, w, s1, s2 = program_geometry(hyb, tile)
    jones = np.where(np.isnan(jones), 1.0, jones)
    cidx = rp.chunk_indices(hyb.tilesz, hyb.nbase, sky.nchunk)
    out = rr.calculate_residuals_multifreq(
        rp.sky_to_device(sky, jnp.float64), jnp.asarray(jones),
        jnp.asarray(x)[:, None], u, v, w, jnp.asarray([hyb.freq]),
        hyb.fdelta, s1, s2, jnp.asarray(cidx), jnp.asarray(mask),
        row_period=hyb.nbase)
    return np.asarray(out)[:, 0]


def test_the_program_reads_the_cluster_file_as_the_reference_wrote_it(
        hyb, program_sky):
    """Ids, chunk counts, effective clusters and the subtract mask."""
    assert list(hyb.nchunk[np.argsort(refh.flux_ranks(hyb.sky))]) \
        == CFG["nchunk_by_flux_rank"]
    assert sorted(hyb.nchunk) == [1, 1, 2, 3] and (hyb.ids < 0).sum() == 1
    assert hyb.nchunk[hyb.ids < 0] == [3]        # the brightest is kept
    assert list(program_sky.cluster_ids) == list(hyb.ids)
    assert list(program_sky.nchunk) == list(hyb.nchunk)
    assert program_sky.n_eff_clusters == hyb.n_eff == 7
    assert list(np.flatnonzero(program_sky.subtract_mask())) \
        == list(hyb.subtracted)


@pytest.mark.parametrize("tilesz", [7, 10, 120])
def test_the_row_to_chunk_map_is_the_published_rule(hyb, tilesz):
    """``ceil(tilesz / K)`` timeslots a chunk, the last chunk taking the
    rest; not ``floor``, which cuts 7 and 10 timeslots elsewhere for 3
    chunks (and agrees where K divides tilesz: 120)."""
    nchunk = np.asarray([3, 2, 1, 4])
    prog = rp.chunk_indices(tilesz, hyb.nbase, nchunk)
    assert np.array_equal(prog, refh.chunk_of_row(tilesz, hyb.nbase, nchunk))
    floor = refh.chunk_of_row(tilesz, hyb.nbase, nchunk, rule="floor")
    assert np.array_equal(prog, floor) == (tilesz == 120)
    if tilesz == 10:
        assert list(refh.chunk_of_slot(10, 3)) == [0] * 4 + [1] * 4 + [2] * 2
        assert list(refh.chunk_of_slot(10, 3, "floor")) \
            == [0] * 3 + [1] * 3 + [2] * 4


def test_the_reference_refuses_a_count_that_leaves_a_chunk_empty():
    with pytest.raises(ValueError):
        refh.chunk_of_slot(6, 4)        # ceil(6 / 4) = 2: chunks 2, 2, 2, 0
    with pytest.raises(ValueError):
        refh.chunk_of_slot(6, 7)


@pytest.mark.parametrize("which", ["all", "subtracted"])
def test_the_model_under_given_per_chunk_jones(hyb, program_sky, which):
    """The program's model (data zero, so minus its residual) against
    the reference's, under the true Jones of every (cluster, chunk); with
    the file's subtract mask the kept cluster is absent from it, and its
    absence is no rounding: it is the kept cluster's whole model.
    Tolerance 1e-9 of the model's rms: both are float64 and differ in
    the order of their sums and in how the phase is formed."""
    jones = hyb.jones()
    zero = np.zeros((hyb.nrows, 2, 2), np.complex128)
    mask = (np.ones(hyb.n_dir, bool) if which == "all"
            else program_sky.subtract_mask())
    clusters = None if which == "all" else hyb.subtracted
    prog = -program_residual(hyb, program_sky, 1, zero, jones, mask)
    ref = hyb.model(1, jones, clusters)
    assert reference.rms(prog - ref) < 1e-9 * reference.rms(ref)
    if which == "subtracted":
        kept = np.flatnonzero(hyb.ids < 0)
        everything = hyb.model(1, jones)
        assert reference.rms(everything - prog) == pytest.approx(
            reference.rms(hyb.model(1, jones, kept)), rel=1e-9)
        assert reference.rms(everything - prog) > 0.1 * reference.rms(ref)


def test_a_chunks_jones_reaches_its_own_timeslots_only(hyb, program_sky):
    """Changing ONE (cluster, chunk)'s Jones changes the program's model
    on that chunk's rows and nowhere else, and the rows are the
    reference's."""
    jones = hyb.jones()
    zero = np.zeros((hyb.nrows, 2, 2), np.complex128)
    every = np.ones(hyb.n_dir, bool)
    before = program_residual(hyb, program_sky, 0, zero, jones, every)
    m = int(np.argmax(hyb.nchunk))
    for k in range(hyb.nchunk[m]):
        other = jones.copy()
        other[m, k] *= 1.5
        moved = np.abs(program_residual(hyb, program_sky, 0, zero, other,
                                        every) - before).sum(axis=(1, 2)) > 0
        assert np.array_equal(moved, hyb.chunk_of_row()[m] == k)


def test_the_written_residual_is_the_reference_under_the_written_solutions(
        hyb, solved):
    """What ``cli`` wrote, from disk: the residual column against the
    data minus the reference's model of the subtracted clusters, by
    chunk, under the solutions file as the REFERENCE reads it.
    Tolerance 1e-3 of the residual's rms: the solutions file is text
    with seven digits (``%e``), the program subtracted under the Jones
    it had in full precision, and the model is some 30 times the
    residual (7e-5 as run).  The kept cluster is still in the written
    residual: taking it out as well misses by far more."""
    written = refh.read_solutions(solved["sol"], hyb.nchunk)
    assert len(written) == N_TILES
    kept = np.flatnonzero(hyb.ids < 0)
    for t in range(N_TILES):
        x = datagen.read_column(solved["ms"], t, "x")
        r_prog = datagen.read_column(solved["ms"], t, "x_corrected_data")
        r_ref = x - hyb.model(t, written[t], hyb.subtracted)
        assert reference.rms(r_prog - r_ref) < 1e-3 * reference.rms(r_ref)
        wrong = r_ref - hyb.model(t, written[t], kept)
        assert reference.rms(r_prog - wrong) > reference.rms(wrong)
        floor = x - hyb.model(t, written[t], hyb.subtracted, rule="floor")
        assert reference.rms(r_prog - floor) > 0.05 * reference.rms(floor)


def test_the_solve_follows_the_chunks(hyb, solved):
    """The data minus the reference's model of ALL clusters under the
    written solutions is at the noise on the chain's fourth tile (0.907
    as run, the second still 1.22; 1.05 is the benchmark's limit, and
    7 x 8 x 8 real parameters fitted to 1568 real data leave under the
    noise itself), where the same solutions with every chunk given the
    cluster's FIRST chunk's Jones, one solution a tile, leave many times
    the noise: the per-chunk truth is visible."""
    written = refh.read_solutions(solved["sol"], hyb.nchunk)
    t = N_TILES - 1
    x = datagen.read_column(solved["ms"], t, "x")
    noise = reference.rms(x - hyb.model(t, hyb.jones()))
    assert reference.rms(x - hyb.model(t, written[t])) < 1.05 * noise
    first = np.where(np.isnan(written[t]), np.nan,
                     np.broadcast_to(written[t][:, :1], written[t].shape))
    assert reference.rms(x - hyb.model(t, first)) > 5 * noise


def test_the_programs_solutions_file_in_the_references_reader(hyb, solved):
    """Header and columns: 4 clusters, 7 effective, the last cluster
    first and a cluster's chunks in time order.  The two readers agree
    to the last bit on every live slot (they parse the same text)."""
    with open(solved["sol"]) as f:
        header = [ln.split() for ln in f if not ln.startswith("#")][0]
    assert header[3:6] == ["8", "4", "7"]
    ref = refh.read_solutions(solved["sol"], hyb.nchunk)
    _, prog = solutions.read_solutions(solved["sol"], hyb.nchunk)
    assert len(ref) == len(prog) == N_TILES
    live = np.arange(hyb.kmax)[None, :] < hyb.nchunk[:, None]
    for a, b in zip(ref, prog):
        assert np.array_equal(a[live], b[live])
        assert np.isnan(a[~live]).all()
    with pytest.raises(ValueError):
        refh.read_solutions(solved["sol"], np.ones(4, int))


def test_the_references_solutions_file_in_the_programs_reader(hyb, files):
    """The reverse: the true Jones written by the reference's writer,
    read by the program's reader and by ``-q``'s warm start.  1e-9: the
    writer prints ten digits."""
    path = os.path.join(files["root"], "true.solutions")
    truth = hyb.jones()
    refh.write_solutions(path, [truth, 2 * truth], hyb.nchunk, hyb.freq,
                         hyb.fdelta, hyb.tilesz * hyb.tdelta / 60.0)
    header, blocks = solutions.read_solutions(path, hyb.nchunk)
    assert (header["n_stations"], header["n_clusters"],
            header["n_eff_clusters"]) == (8, 4, 7)
    live = np.arange(hyb.kmax)[None, :] < hyb.nchunk[:, None]
    for k, block in enumerate(blocks):
        assert np.allclose(block[live], (k + 1) * truth[live], rtol=1e-9,
                           atol=0)
    back = refh.read_solutions(path, hyb.nchunk)
    assert np.allclose(back[1][live], 2 * truth[live], rtol=1e-9, atol=0)


def test_the_tile_record_counts_the_chunk_slots(solved):
    """``kmax``, ``chunk_slots`` (M * kmax) and ``chunk_slots_live``
    (sum(nchunk)) beside the layouts the solve ran on: the ``[tilesz,
    nbase]`` planes, a chunk being a run of whole timeslots (PR 45; flat
    rows and the generic assembly before)."""
    tiles = [r for r in dtrace.read(solved["diag"]) if r.get("ev") == "tile"]
    assert len(tiles) == N_TILES
    for r in tiles:
        assert (r["kmax"], r["chunk_slots"], r["chunk_slots_live"]) \
            == (3, 12, 7)
        assert r["sweep_rows"] == r["refine_rows"] == "periodic"
        assert r["assemble_rows"] == "periodic"
        assert r["res_1"] < r["res_0"]


def test_the_reference_imports_nothing_of_the_program():
    """numpy and ``reference.py`` (which imports math and numpy)."""
    import ast
    found = set()
    for mod in (refh, reference):
        with open(mod.__file__) as f:
            for node in ast.walk(ast.parse(f.read())):
                if isinstance(node, ast.Import):
                    found |= {a.name.split(".")[0] for a in node.names}
                elif isinstance(node, ast.ImportFrom):
                    found.add(node.module.split(".")[0])
    assert found == {"__future__", "math", "numpy", "reference"}
