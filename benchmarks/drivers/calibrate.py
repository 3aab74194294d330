"""Driver ``calibrate``: one observation calibrated tile by tile.

Closed loop through the program's public per-tile seam, the one
``FullBatchPipeline.run`` and the serve scheduler drive:
``FullBatchPipeline.stepper()``, a ``sched.Prefetcher`` of the depth the
configuration has (``--prefetch``), ``TileStepper.step`` per tile and
``TileStepper.close()`` to drain the ordered writer.  Residuals go to
the output column, solutions to a solutions file.

Warm-up is the mix's ``warmup_tiles`` first tiles; the window opens as
the next tile's step is entered and closes at the first tile boundary
after ``--seconds``, or with the observation's last tile where that comes
first (``left`` tells the harness how far that is); then the writer is
drained.
"""

import os

import numpy as np

import datagen
import harness
import reference

#: ``run.enter_tile`` is called between two steps, in no span of the
#: program's: a mix of this driver may set ``profile_tiles``
BOUNDARY_OUTSIDE_SPANS = True


def run(run):
    from sagecal_tpu import cli, pipeline, sched, skymodel
    from sagecal_tpu.io import dataset as ds

    obs, conf = run.obs, run.config
    sky_path, cluster_path = datagen.write_sky(obs, run.work)
    ms_path = datagen.write_observation(
        obs, run.work, int(conf["n_tiles_on_disk"]), "calibrate")
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
    failed = sum(1 for r in recs
                 if not (np.isfinite(r["res_0"]) and np.isfinite(r["res_1"])
                         and r["res_1"] < r["res_0"]))
    return {"attempted": len(recs), "failed": failed}


def check(run):
    """For ``check_tiles`` tiles of the window (all of them, where the
    mix asks for as many), from disk: (a) the written residual against
    data minus the reference's model under the WRITTEN solutions; (b)
    that residual over the residual under the true Jones, which is the
    noise."""
    obs = run.obs
    limits = run.config["limits"]
    written = reference.read_solutions(run.sol_path)
    j_true = obs.jones()
    worst_a = worst_b = 0.0
    notes = []
    for t in harness.pick_tiles(run.window.tiles,
                                int(run.traffic["check_tiles"])):
        x = datagen.read_column(run.ms_path, t, "x")
        r_prog = datagen.read_column(run.ms_path, t, "x_corrected_data")
        r_ref = x - obs.model(t, written[t])
        floor = reference.rms(x - obs.model(t, j_true))
        a = reference.rms(r_prog - r_ref) / reference.rms(r_ref)
        b = reference.rms(r_ref) / floor
        notes.append(f"tile {t}: {a:.4g}, {b:.5g}")
        worst_a, worst_b = harness.worse(a, worst_a), harness.worse(b, worst_b)
    return [
        harness.Comparison("residual_vs_reference", worst_a,
                           limits["residual_vs_reference"]["limit"],
                           "; ".join(notes)),
        harness.Comparison("residual_over_noise", worst_b,
                           limits["residual_over_noise"]["limit"]),
    ]
