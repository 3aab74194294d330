"""Driver ``calibrate_beam``: ``drivers/calibrate.py``'s loop on an
observation seen through the stations' array beams (``-B 1``).

The loop is the same public per-tile seam (``cli.config_from_args`` ->
``FullBatchPipeline.stepper()`` -> ``sched.Prefetcher`` ->
``TileStepper.step`` -> ``TileStepper.close()``); data, truth, time
stamps, the stations' elements and ``check`` come from
``reference_beam.py``, built here from the same configuration file and
seed (``run.obs``, which the harness builds for every cell, is left alone
and unused).  The tiles are written with the reference's ``time_mjd`` and
a ``beam.npz`` beside them through the program's own ``SimMS.create(...,
beam_info=)``: every number that ``-B 1`` reads is the reference's (the
element-pattern table that ``save_beaminfo`` stores with them is the
program's, and ``-B 1`` never reads it), so ``resolve_beaminfo`` finds
stored metadata and never its synthetic layout.  A dataset that came out
without ``beam.npz`` is refused.

Whether the PROGRAM applies a beam is the configuration's ``cli`` alone:
the control that solves the same data under ``-B 0``
(``tests/rehearsal/beam-readings.json``) is this driver under a ``cli``
without ``-B 1``.  The controls that need no run of their own (the
reference's gains without precession, its Jones products in bfloat16, in
the written residual's place) are computed by ``check`` on the first
checked tile of every run and printed as a ``[control]`` line; a
``[chain]`` line beside it says how far the chain of warm starts has come
down on that tile, apart for the rows under the ``-x`` cut.
"""

import dataclasses
import os

import numpy as np

import datagen
import harness
import reference
import reference_beam

#: ``run.enter_tile`` is called between two steps, in no span of the
#: program's: a mix of this driver may set ``profile_tiles``
BOUNDARY_OUTSIDE_SPANS = True


def observation(run):
    """The beam observation of this run: data, truth and the beam."""
    if not hasattr(run, "beam_obs"):
        run.beam_obs = reference_beam.Observation(run.config, run.seed)
    return run.beam_obs


#: rows of an observation from which its tiles are made in a pool of
#: processes and not one after another (the tiny rehearsal cells stay
#: under it, the cell is 32 x 18 910)
POOL_FROM_ROWS = 100_000


def write_observation(obs, out_dir: str, n_tiles: int) -> str:
    """SimMS of ``n_tiles`` tiles at ``out_dir``/obs.ms with the
    reference's time stamps and its stations as ``beam.npz``."""
    from sagecal_tpu.io import dataset as ds
    from sagecal_tpu.rime import beam as bm
    rows = n_tiles * obs.nrows
    workers = min(os.cpu_count() or 1, 6) if rows >= POOL_FROM_ROWS else 1
    data = reference_beam.make_tiles(obs, n_tiles, workers)
    tiles = [dataclasses.replace(datagen.vis_tile(obs, t, data[t]),
                                 time_mjd=obs.time_mjd(t))
             for t in range(n_tiles)]
    info = bm.BeamInfo(
        longitude=obs.lon, latitude=obs.lat,
        time_jd=obs.time_mjd(0) / 86400.0 + 2400000.5, ra0=obs.ra0,
        dec0=obs.dec0, freq0=obs.freq0, elem_xyz=obs.elem,
        elem_mask=obs.mask)
    path = os.path.join(out_dir, "obs.ms")
    ds.SimMS.create(path, tiles, beam_info=info)
    if not os.path.exists(os.path.join(path, "beam.npz")):
        raise RuntimeError(
            f"{path} has no beam.npz: with -B the program would make up a "
            f"station layout of its own, which is not the reference's")
    return path


def run(run):
    from sagecal_tpu import cli, pipeline, sched, skymodel
    from sagecal_tpu.io import dataset as ds

    conf = run.config
    obs = observation(run)
    sky_path, cluster_path = datagen.write_sky(obs, run.work)
    ms_path = write_observation(obs, run.work, int(conf["n_tiles_on_disk"]))
    sol_path = os.path.join(run.work, "out.solutions")
    run.clock.mark("data")
    # the configuration as a user of the CLI gets it
    cfg = cli.config_from_args(cli.build_parser().parse_args(
        ["-d", ms_path, "-s", sky_path, "-c", cluster_path,
         "-p", sol_path, *conf["cli"]]))
    ms = ds.open_dataset(cfg.ms, cfg.ms_list, tilesz=cfg.tile_size,
                         data_column=cfg.input_column,
                         out_column=cfg.output_column)
    meta = ms.meta
    sky = skymodel.read_sky_cluster(cfg.sky_model, cfg.cluster_file,
                                    meta["ra0"], meta["dec0"],
                                    meta["freq0"], cfg.format_3)
    pipe = pipeline.FullBatchPipeline(cfg, ms, sky, log=run.log)
    st = pipe.stepper(True, sol_path, log=run.log)

    def produce(j):
        with run.annotate("read_stage"):
            tile = ms.read_tile(j)
            return j, tile, st.stage(j, tile)

    warm = int(run.traffic["warmup_tiles"])
    pf = sched.Prefetcher(produce, ms.n_tiles, depth=st.depth)
    try:
        for _j, (ti, tile, stg), wait in pf:
            if ti >= warm:
                if run.window.due():
                    break
                run.enter_tile(
                    ti, int((tile.flags == 0).sum()) * len(tile.freqs),
                    left=ms.n_tiles - 1 - ti)
            with run.annotate("step"):
                st.step(ti, tile, stg, wait)
    finally:
        pf.close()
        with run.annotate("drain"):
            st.close()
    run.drain()

    run.ms_path, run.sol_path = ms_path, sol_path
    in_window = set(run.window.tiles)
    recs = [r for r in st.history if r["tile"] in in_window]
    run.counters["history"] = recs
    # what the program made of -B, for the tests
    run.counters["dobeam"] = int(pipe.dobeam)
    failed = sum(1 for r in recs
                 if not (np.isfinite(r["res_0"]) and np.isfinite(r["res_1"])
                         and r["res_1"] < r["res_0"]))
    return {"attempted": len(recs), "failed": failed}


def compare(run, tiles, precessed=True, low=None, passes=1):
    """(worst a, worst b, worst b of the settled tiles, notes) over
    ``tiles``, from disk, as ``drivers/calibrate.py`` reads them with the
    reference's beam in ``model_ref``.

    a: rms of the written residual less its reference (the data minus
    the reference's model WITH the array-beam gains under the written
    solutions), over the rms of that reference.
    b: that reference over the same under the true Jones (the noise);
    once over every tile and once over those from the mix's
    ``settled_from_tile`` on (0.0 where ``tiles`` holds none of them).

    ``precessed`` False and ``low`` are controls: in the written
    residual's place stands the data minus the reference's OWN model with
    gains of the catalogue positions (no precession), or with its Jones
    products made in the numpy dtype ``low`` in ``passes`` passes."""
    obs = observation(run)
    if not hasattr(run, "written"):     # parsed once, asked several times
        run.written = reference.read_solutions(run.sol_path)
    written, j_true = run.written, obs.jones()
    settled_from = int(run.traffic["settled_from_tile"])
    worst_a = worst_b = worst_settled = 0.0
    notes = []
    for t in tiles:
        x = datagen.read_column(run.ms_path, t, "x")
        r_prog = datagen.read_column(run.ms_path, t, "x_corrected_data")
        r_ref = x - obs.model(t, written[t])
        if low is not None or not precessed:
            r_prog = x - obs.model(t, written[t], dtype=low, passes=passes,
                                   precessed=precessed)
        floor = reference.rms(x - obs.model(t, j_true))
        a = reference.rms(r_prog - r_ref) / reference.rms(r_ref)
        b = reference.rms(r_ref) / floor
        notes.append(f"tile {t}: {a:.4g}, {b:.5g}")
        worst_a, worst_b = harness.worse(a, worst_a), harness.worse(b, worst_b)
        if t >= settled_from:
            worst_settled = harness.worse(b, worst_settled)
    return worst_a, worst_b, worst_settled, notes


def cut_split(run, tile):
    """(rows, b of them, b of the others) on ``tile``: ``compare``'s b
    apart for the rows under the ``cli``'s ``-x`` uv cut (in wavelengths),
    which the solve never sees and the written residual holds, and for
    the others.  None for a ``cli`` without the cut, and where it cuts
    no row."""
    cli = run.config["cli"]
    if "-x" not in cli:
        return None
    obs = observation(run)
    u, v = obs.geometry(tile)[:2]
    cut = np.hypot(u, v) * obs.freq < float(cli[cli.index("-x") + 1])
    if not cut.any():
        return None
    x = datagen.read_column(run.ms_path, tile, "x")
    r_ref = x - obs.model(tile, run.written[tile])
    floor = x - obs.model(tile, obs.jones())
    return (int(cut.sum()),
            reference.rms(r_ref[cut]) / reference.rms(floor[cut]),
            reference.rms(r_ref[~cut]) / reference.rms(floor[~cut]))


def check(run):
    """For ``check_tiles`` tiles of the window (all of them, where the mix
    asks for as many): ``compare``'s three numbers against the
    configuration's limits; and on the first of those tiles what the
    reference-side controls read, printed."""
    limits = run.config["limits"]
    tiles = harness.pick_tiles(run.window.tiles,
                               int(run.traffic["check_tiles"]))
    a, b, settled, notes = compare(run, tiles)
    if tiles:
        from ml_dtypes import bfloat16      # numpy's, installed with jax
        unprecessed = compare(run, tiles[:1], precessed=False)[0]
        one = compare(run, tiles[:1], low=bfloat16)[0]
        three = compare(run, tiles[:1], low=bfloat16, passes=3)[0]
        # a line of its own: limits.py prints no notes
        print(f"[control] seed {run.seed}, controls on tile {tiles[0]}: the "
              f"reference's gains without precession {unprecessed:.4g}; its "
              f"products in bfloat16, one pass {one:.4g}, three {three:.4g}",
              flush=True)
        split = cut_split(run, tiles[0])
        if split:
            print(f"[chain] tile {tiles[0]}, residual_over_noise apart: the "
                  f"{split[0]} rows under the -x cut (not in the solve) "
                  f"{split[1]:.5g}, the others {split[2]:.5g}", flush=True)
    return [
        harness.Comparison("residual_vs_reference", a,
                           limits["residual_vs_reference"]["limit"],
                           "; ".join(notes)),
        harness.Comparison("residual_over_noise", b,
                           limits["residual_over_noise"]["limit"]),
        harness.Comparison("residual_over_noise.settled", settled,
                           limits["residual_over_noise.settled"]["limit"]),
    ]
