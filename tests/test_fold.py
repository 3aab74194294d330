"""More subbands than devices: ``cli_mpi`` folds ``ceil(F / ndev)``
subbands on each device of its mesh and runs their J updates under
``jax.vmap`` (``consensus/admm.py``, ``_per_subband``).  The fold is the
mesh: four subbands of the benchmark's tiny consensus observation
calibrated (a) all on one device, (b) two a device on two, (c) one a
device on four write the same J per subband, the same Z and the same
residuals, each passes the four checks of the benchmark's plain
reference (``benchmarks/reference_consensus.py`` through
``benchmarks/drivers/consensus.check``), and the interval's ``tile``
record says what ran: ``fold``, ``ndev``, ``plan``, the useful loop
bodies of the J updates (``jupdate_trips``) and the share of executed
bodies spent on a subband that had ended (``lockstep_pct``).

The suite runs x64 on the CPU, so a subband's arithmetic is float64
either way and only the order of a few reductions differs between the
batched and the axis-free J update: ``RTOL`` below, a float32
tolerance (1e-6 of the largest magnitude) that float64 meets with ten
digits to spare.  The three layouts are not compared in float32 here;
on the chip the folded run is held to the reference, not to the mesh.
"""

import os
import shutil
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import datagen                          # noqa: E402
import harness                          # noqa: E402
import reference                        # noqa: E402
import reference_consensus as refc      # noqa: E402

from sagecal_tpu import cli_mpi, skymodel, utils    # noqa: E402
from sagecal_tpu.consensus import admm as cadmm, poly as cpoly  # noqa: E402
from sagecal_tpu.diag import trace as dtrace        # noqa: E402
from sagecal_tpu.rime import predict as rp          # noqa: E402
from sagecal_tpu.solvers import lm as lm_mod        # noqa: E402

SEED, N_TILES, N_ADMM = 2 ** 31 + 11, 2, 3
#: J, Z and residuals of two runs, relative to the largest magnitude
RTOL = 1e-6
#: devices of the mesh -> subbands a device
LAYOUTS = {"folded": (1, 4), "mixed": (2, 2), "mesh": (4, 1)}


def tiny_conf():
    """The rehearsal cell's configuration (four subbands of 8 stations)
    at ``-A 3`` and two intervals, less its ``--mesh-devices 1``: how
    many devices is what these cases vary."""
    conf = dict(harness.load_config(
        "benchmarks/tests/rehearsal/tiny-lofar62-f8-fold-m8x3.json"))
    cli = list(conf["cli"])
    at = cli.index("--mesh-devices")
    del cli[at:at + 2]
    cli[cli.index("-A") + 1] = str(N_ADMM)
    conf["cli"], conf["n_tiles_on_disk"] = cli, N_TILES
    return conf


def make_observation(root, conf):
    """The files the benchmark's consensus driver makes, by its own
    generator: sky, one SimMS a subband, the list, the rho file."""
    os.makedirs(root)
    subs = refc.subbands(conf, SEED)
    datagen.write_sky(subs[0], root)
    for k, sub in enumerate(subs):
        os.makedirs(os.path.join(root, f"sb{k}"))
        datagen.write_observation(sub, os.path.join(root, f"sb{k}"),
                                  N_TILES, "calibrate")
    with open(os.path.join(root, "regularization_factors.txt"), "w") as f:
        for ln in subs[0].cluster_lines:
            f.write(f"{ln.split()[0]} 1 {float(conf['cluster_rho'])}\n")
    return subs


def argv(root, conf, ndev):
    ms = [os.path.join(root, f"sb{k}", "obs.ms")
          for k in range(len(conf["subband_freqs_hz"]))]
    with open(os.path.join(root, "subbands.txt"), "w") as f:
        f.write("\n".join(ms) + "\n")
    return ms, ["-f", os.path.join(root, "subbands.txt"),
                "-s", os.path.join(root, "sky.txt"),
                "-c", os.path.join(root, "sky.txt.cluster"),
                "-G", os.path.join(root, "regularization_factors.txt"),
                "-p", os.path.join(root, "global.solutions"),
                "--diag", os.path.join(root, "diag.jsonl"),
                "--mesh-devices", str(ndev), *conf["cli"]]


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """One observation, calibrated under each layout by ``main()``:
    {layout: what it wrote and said}."""
    conf = tiny_conf()
    base = tmp_path_factory.mktemp("fold")
    src = str(base / "src")
    make_observation(src, conf)
    out = {}
    for name, (ndev, _) in LAYOUTS.items():
        root = str(base / name)
        shutil.copytree(src, root)
        ms, args = argv(root, conf, ndev)
        assert cli_mpi.main(args) == 0
        out[name] = types.SimpleNamespace(
            root=root, ms=ms, conf=conf,
            z=np.stack(refc.read_z_file(
                os.path.join(root, "global.solutions"), 2)),
            j=np.stack([np.stack(reference.read_solutions(
                refc.subband_solutions_path(p))) for p in ms]),
            res=np.stack([[datagen.read_column(p, t, "x_corrected_data")
                           for t in range(N_TILES)] for p in ms]),
            tiles=[r for r in dtrace.read(os.path.join(root, "diag.jsonl"))
                   if r.get("ev") == "tile"])
    return out


@pytest.mark.parametrize("a,b", [("folded", "mesh"), ("mixed", "mesh"),
                                 ("folded", "mixed")])
@pytest.mark.parametrize("what", ["j", "z", "res"])
def test_layouts_write_the_same(runs, a, b, what):
    """J per subband, Z and the residual columns of two layouts."""
    x, y = getattr(runs[a], what), getattr(runs[b], what)
    assert x.shape == y.shape and np.isfinite(x).all()
    assert np.abs(x - y).max() <= RTOL * np.abs(y).max()


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_each_layout_passes_the_references_four_checks(runs, layout):
    """``drivers/consensus.check`` on what the run wrote: both
    intervals read back from disk, against the tiny cell's limits."""
    r = runs[layout]
    driver = harness.load_module("drivers", "consensus")
    fake = types.SimpleNamespace(
        config=r.conf, seed=SEED, ms_paths=r.ms,
        z_path=os.path.join(r.root, "global.solutions"),
        counters={"stepped": N_TILES}, traffic={"check_tiles": 64},
        window=types.SimpleNamespace(tiles=list(range(N_TILES))))
    checks = driver.check(fake)
    assert [c.name for c in checks] == [
        "residual_vs_reference", "residual_over_noise",
        "consensus_over_noise", "consensus_primal"]
    assert all(c.ok for c in checks), [c.line() for c in checks]


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_tile_records_say_what_ran(runs, layout):
    ndev, fold = LAYOUTS[layout]
    recs = runs[layout].tiles
    assert len(recs) == N_TILES
    for r in recs:
        assert (r["fold"], r["ndev"], r["plan"]) == (fold, ndev, "traced")
        assert r["jupdate_trips"] > 0
        assert 0.0 <= r["lockstep_pct"] < 100.0
        if fold == 1:
            assert r["lockstep_pct"] == 0.0


def test_useful_trips_do_not_depend_on_the_fold(runs):
    """A subband's own count is its own under the batch axis: the
    interval's useful trips are those of the unfolded run."""
    want = [r["jupdate_trips"] for r in runs["mesh"].tiles]
    for layout in ("folded", "mixed"):
        got = [r["jupdate_trips"] for r in runs[layout].tiles]
        assert got == want, layout
    # four different subbands do not all end on the same trip
    assert any(r["lockstep_pct"] > 0.0 for r in runs["folded"].tiles)


# -- the arithmetic ------------------------------------------------------------

def trips(rows):
    """[n_admm, F, 2] from per-iteration per-subband (outer, inner)."""
    return np.asarray(rows, np.int32)


@pytest.mark.parametrize("rows,nf,group,useful,pct", [
    # one subband an execution: nothing is dragged, whatever it counted
    ([[(3, 9), (5, 20)]], 2, 1, 37, 0.0),
    # two alike: no body wasted
    ([[(4, 10), (4, 10)]], 2, 2, 28, 0.0),
    # one ends at 10 of the other's 20 bodies: a quarter of 40 executed
    ([[(2, 8), (4, 16)]], 2, 2, 30, 25.0),
    # two groups of two (two devices): mean of 25 % and 0 %
    ([[(2, 8), (4, 16), (3, 3), (3, 3)]], 4, 2, 42, 12.5),
    # by iteration, then the mean: 25 % and 50 %
    ([[(2, 8), (4, 16)], [(5, 5), (0, 0)]], 2, 2, 40, 37.5),
    # a padded slot (3 real of 4) ran subband 0's trips for nothing
    ([[(1, 9), (1, 9), (1, 9), (1, 9)]], 3, 4, 30, 25.0),
    # a ragged last block of the blocked plan: 3 subbands in blocks of 2
    ([[(1, 9), (1, 9), (1, 9)]], 3, 2, 30, 25.0),
    # a skipped iteration (the stale plan) executes nothing
    ([[(0, 0), (0, 0)]], 2, 2, 0, 0.0),
])
def test_lockstep_arithmetic(rows, nf, group, useful, pct):
    got = cadmm.lockstep(trips(rows), nf, group)
    assert got[0] == useful and got[1] == pytest.approx(pct)


# -- an easy subband beside hard ones -----------------------------------------

@pytest.fixture(scope="module")
def easy_and_hard():
    """The runner itself on interval 0 of the tiny observation, subband
    0 warm-started AT its true Jones and the other three at the
    identity: ``{Fl: trips}`` with all four on one device and with one a
    device."""
    conf = tiny_conf()
    subs = refc.subbands(conf, SEED)
    nf = len(subs)
    import tempfile
    with tempfile.TemporaryDirectory() as tmp:
        sky_path, cl_path = datagen.write_sky(subs[0], tmp)
        freqs = np.asarray([s.freq for s in subs])
        sky = skymodel.read_sky_cluster(
            sky_path, cl_path, subs[0].ra0, subs[0].dec0,
            float(freqs.mean()), True)
    rdt = jnp.float64
    dsky = rp.sky_to_device(sky, rdt)
    n, nbase, tilesz = subs[0].n_sta, subs[0].nbase, subs[0].tilesz
    kmax = int(sky.nchunk.max())
    cmask = np.arange(kmax)[None, :] < sky.nchunk[:, None]
    cidx = rp.chunk_indices(tilesz, nbase, sky.nchunk)
    u, v, w, s1, s2 = subs[0].geometry(0)
    x8 = np.stack([utils.vis_to_x8(s.data(0)) for s in subs])
    wt = np.asarray(lm_mod.make_weights(
        jnp.zeros(len(s1), jnp.int32), rdt))
    eye = np.tile(np.eye(2, dtype=complex),
                  (nf, sky.n_clusters, kmax, n, 1, 1))
    eye[0, :, 0] = subs[0].jones()      # [M, N, 2, 2], one chunk each
    J0 = utils.jones_c2r_np(eye)
    Bpoly = cpoly.setup_polynomials(freqs, float(freqs.mean()), 2, 2)
    cfg = cadmm.ADMMConfig(
        n_admm=N_ADMM, npoly=2, rho=5.0,
        sage=cli_mpi.sage_config(cli_mpi.build_parser().parse_args(
            ["-f", "x", "-s", "x", "-c", "x", *conf["cli"]])))
    out = {}
    for ndev in (1, nf):
        mesh = Mesh(np.array(jax.devices()[:ndev]), ("freq",))
        runner = cadmm.make_admm_runner(
            dsky, s1, s2, cidx, cmask, n, subs[0].fdelta, Bpoly, cfg,
            mesh, nf, nbase=nbase)
        sh = NamedSharding(mesh, P("freq"))
        args = [jax.device_put(jnp.asarray(a, rdt), sh) for a in (
            x8, np.tile(u, (nf, 1)), np.tile(v, (nf, 1)),
            np.tile(w, (nf, 1)), freqs, np.tile(wt, (nf, 1, 1)),
            np.ones(nf), J0)]
        got = runner(*args)
        assert len(got) == 9
        out[nf // ndev] = (np.asarray(got[8]), np.asarray(got[3]))
    return out


def test_a_subband_unlike_the_others_drags_or_is_dragged(easy_and_hard):
    """Subband 0 starts at its true Jones, so its residual starts at the
    noise, and it needs ANOTHER number of loop bodies than the three
    that start at the identity.  Not fewer, as one might expect of an
    easy problem: its trust-region steps are short, truncated CG is not
    stopped at the region's boundary and runs on to its residual target
    (286 inner trips against 144-178 in iteration 0 when this was
    written), so here the three hard ones are dragged through the easy
    one's trips.  Either way the fold pays the slowest: a share above
    zero."""
    tk, res0 = easy_and_hard[4]
    assert tk.shape == (N_ADMM, 4, 2) and tk.dtype == np.int32
    assert res0[0] < 0.5 * res0[1:].min()
    t = tk.sum(axis=-1)                 # [n_admm, F]
    # iteration 0 is where the warm start shows
    assert np.abs(t[0, 1:] - t[0, 0]).min() > 0.2 * t[0].min()
    useful, pct = cadmm.lockstep(tk, 4, 4)
    assert useful == int(t.sum()) and pct > 0.0


def test_at_one_subband_a_device_nothing_is_dragged(easy_and_hard):
    """``Fl`` = 1: the share is zero and the count is the folded run's:
    each subband's own ``sagefit`` counted it either way."""
    tk1, _ = easy_and_hard[1]
    tk4, _ = easy_and_hard[4]
    useful, pct = cadmm.lockstep(tk1, 4, 1)
    assert pct == 0.0
    assert useful == cadmm.lockstep(tk4, 4, 4)[0]
    np.testing.assert_array_equal(tk1, tk4)
