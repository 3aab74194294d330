"""Driver ``calibrate_hybrid``: ``drivers/calibrate.py``'s loop on an
observation under a HYBRID cluster file (``reference_hybrid.py``: chunk
counts in the second column, a negative id for the direction that is
solved and kept in the data).

The loop is the same public per-tile seam (``cli.config_from_args`` ->
``FullBatchPipeline.stepper()`` -> ``sched.Prefetcher`` ->
``TileStepper.step`` -> ``TileStepper.close()``); data, truth and
``check`` come from the hybrid reference, built here from the same
configuration file and seed (``run.obs``, which the harness builds for
every cell, is left alone and unused).

The configuration's optional ``control`` key (no cell of
``BENCHMARK.json`` has it; ``tests/rehearsal/hybrid-readings.json``
does) is the control that needs a run of its own:
``"all_ones_cluster_file"`` hands the program the same sky with every
chunk count 1 and every id positive, on the SAME hybrid data.  The two
controls that need none (what ``check`` would read of a program that
subtracted the kept cluster, that cut chunks at ``floor(tilesz / K)``,
or whose Jones products were made in bfloat16) are computed by ``check``
on the first checked tile of every run and printed in its note.
"""

import os
import types

import numpy as np

import datagen
import harness
import reference
import reference_hybrid

#: ``run.enter_tile`` is called between two steps, in no span of the
#: program's: a mix of this driver may set ``profile_tiles``
BOUNDARY_OUTSIDE_SPANS = True


def observation(run):
    """The hybrid observation of this run: data and truth."""
    if not hasattr(run, "hyb"):
        run.hyb = reference_hybrid.Observation(run.config, run.seed)
    return run.hyb


def handed(run):
    """(lines, ids, nchunk) of the cluster file the program is handed:
    the observation's own, or under the control the same clusters with
    every chunk count 1 and every id positive."""
    lines = observation(run).cluster_lines
    if run.config.get("control") == "all_ones_cluster_file":
        lines = reference_hybrid.cluster_text(
            lines, [1] * len(lines), [False] * len(lines))
    return (lines, *reference_hybrid.read_cluster_text(lines))


def run(run):
    from sagecal_tpu import cli, pipeline, sched, skymodel
    from sagecal_tpu.io import dataset as ds

    conf = run.config
    hyb = observation(run)
    sky_path, cluster_path = datagen.write_sky(types.SimpleNamespace(
        sky_lines=hyb.sky_lines, cluster_lines=handed(run)[0]), run.work)
    ms_path = datagen.write_observation(
        hyb, run.work, int(conf["n_tiles_on_disk"]), "calibrate")
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
    # what the program read of the cluster file, for the tests
    run.counters["nchunk"] = [int(k) for k in sky.nchunk]
    run.counters["cluster_ids"] = [int(c) for c in sky.cluster_ids]
    failed = sum(1 for r in recs
                 if not (np.isfinite(r["res_0"]) and np.isfinite(r["res_1"])
                         and r["res_1"] < r["res_0"]))
    return {"attempted": len(recs), "failed": failed}


def compare(run, tiles, rule="ceil", keep=True, low=None, passes=1):
    """(worst a, worst b, notes) over ``tiles``, from disk.

    a: the written residual less its reference (the data minus the
    reference's model of the SUBTRACTED clusters under the written
    solutions, the kept cluster left in the data), over what is left of
    the data when the reference's model of ALL clusters is taken out:
    the error of what was written as a share of the noise-like rest, as
    ``cal-m8x3`` reads it, and not of a residual that still holds the
    kept cluster's Janskys.  The worst of the tile's ``kmax`` equal runs
    of timeslots (the chunks of the cluster that has most), so that one
    chunk's fault is not averaged over the tile.
    b: that rest, the data minus the reference's model of all clusters
    under the written solutions, over the same under the true Jones
    (the noise).

    ``rule`` "floor" and ``keep`` False are controls: the reference of a
    program that cut its chunks at ``floor(tilesz / K)``, or that
    subtracted the kept cluster too.  ``low`` (a numpy dtype, in
    ``passes`` passes) is the control of the precision: in the written
    residual's place stands the data minus the reference's OWN model
    with its Jones products made in that type."""
    hyb = observation(run)
    _, ids, nchunk = handed(run)
    if not hasattr(run, "written"):     # parsed once, asked three times
        run.written = reference_hybrid.read_solutions(run.sol_path, nchunk)
    written, j_true = run.written, hyb.jones()
    every = np.arange(hyb.n_dir)
    subtracted = np.flatnonzero(ids >= 0) if keep else every
    kept = np.setdiff1d(every, subtracted)
    part = reference_hybrid.chunk_of_row(hyb.tilesz, hyb.nbase,
                                         [hyb.kmax])[0]
    worst_a = worst_b = 0.0
    notes = []
    for t in tiles:
        x = datagen.read_column(run.ms_path, t, "x")
        r_prog = datagen.read_column(run.ms_path, t, "x_corrected_data")
        coh = hyb.coherencies(t)
        r_ref = x - hyb.model(t, written[t], subtracted, rule, nchunk, coh)
        if low is not None:
            r_prog = x - hyb.model(t, written[t], subtracted, rule, nchunk,
                                   coh, low, passes)
        r_all = r_ref - hyb.model(t, written[t], kept, rule, nchunk, coh)
        floor = reference.rms(x - hyb.model(t, j_true, coh=coh))
        a = max(reference.rms((r_prog - r_ref)[part == k])
                / reference.rms(r_all[part == k]) for k in range(hyb.kmax))
        b = reference.rms(r_all) / floor
        notes.append(f"tile {t}: {a:.4g}, {b:.5g}")
        worst_a, worst_b = harness.worse(a, worst_a), harness.worse(b, worst_b)
    return worst_a, worst_b, notes


def check(run):
    """For ``check_tiles`` tiles of the window (all of them, where the
    mix asks for as many): ``compare``'s two numbers against the
    configuration's limits; and on the first of those tiles what the
    reference-side controls read, in the note."""
    limits = run.config["limits"]
    tiles = harness.pick_tiles(run.window.tiles,
                               int(run.traffic["check_tiles"]))
    a, b, notes = compare(run, tiles)
    controls = ""
    if tiles:
        ka, kb, _ = compare(run, tiles[:1], keep=False)
        fa, fb, _ = compare(run, tiles[:1], rule="floor")
        controls = (f"controls on tile {tiles[0]}: kept cluster subtracted "
                    f"{ka:.4g}, {kb:.5g}; floor boundaries {fa:.4g}, "
                    f"{fb:.5g}")
        from ml_dtypes import bfloat16      # numpy's, installed with jax
        one, _, _ = compare(run, tiles[:1], low=bfloat16)
        three, _, _ = compare(run, tiles[:1], low=bfloat16, passes=3)
        controls += (f"; the reference's products in bfloat16, one pass "
                     f"{one:.4g}, three {three:.4g}")
    return [
        harness.Comparison("residual_vs_reference", a,
                           limits["residual_vs_reference"]["limit"],
                           "; ".join(notes)),
        harness.Comparison("residual_over_noise", b,
                           limits["residual_over_noise"]["limit"],
                           controls),
    ]
