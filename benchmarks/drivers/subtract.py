"""Driver ``subtract``: the off-target directions taken out of the data
under a solutions file (``-a 3 -p -z``), tile after tile.

What an observer runs after calibration: the input column holds the
whole sky as the instrument saw it, the solutions file holds the Jones
matrices just found, the ignore file names the target's cluster, and
the output column is the input minus the model of every OTHER cluster
under that tile's interval of the solutions.  The input is left alone.

The loop is ``drivers/predict.py``'s (``run_simulation`` itself over
that driver's cycling ``SimMS``, which is imported from it, with the
rows it keeps of every cycle), on other files: the DATA column is
``Observation.data(tile)``, the sky under that tile's true Jones plus
noise, and the solutions file holds those same Jones, so what is left
of the subtracted clusters in the output is the program's arithmetic
alone.  The check is ``x - model``, not ``model``: the reference forms
the input and the model of the clusters not ignored at the kept rows,
and the program's output is held against their difference.
"""

import os
import sys

import numpy as np

import datagen
import harness
import reference


def require_seam(rr) -> None:
    """A tree whose ``rime/residual`` has no ``simulate_pairs`` cannot
    run the cell: said on stderr, and the process ends here, before the
    backend is opened and with no result line.  (Such a tree's ``-a 3``
    forms a complex input from the real pairs and restacks the result,
    which the TPU compiler answers with a SIGABRT half a minute into
    the warm-up: a run that is killed, not one that fails.)"""
    if not hasattr(rr, "simulate_pairs"):
        print("benchmarks/drivers/subtract.py: this tree's "
              "sagecal_tpu/rime/residual.py has no simulate_pairs; its "
              "-a 2 and -a 3 abort the TPU compiler (fusion_emitter: "
              "IsFusibleUnalignedDUS), so the cell cannot run on it (the "
              "seam arrives with PR 37)", file=sys.stderr)
        raise SystemExit(4)


# at import, which is when the harness looks the cell up: before the
# backend is opened
from sagecal_tpu.rime import residual as _rr    # noqa: E402
require_seam(_rr)

predict = harness.load_module("drivers", "predict")


def target_and_rest(obs):
    """(the target's cluster id, the indices of the other clusters in
    the order of the cluster file): the target is the file's first."""
    ids = [int(ln.split()[0]) for ln in obs.cluster_lines]
    return ids[0], np.arange(1, len(ids))


def run(run):
    from sagecal_tpu import cli, pipeline, skymodel

    obs, conf = run.obs, run.config
    n_disk = int(conf["n_tiles_on_disk"])
    run.short_rows = obs.short_rows(float(run.traffic["check_short_m"]))
    sky_path, cluster_path = datagen.write_sky(obs, run.work)
    ms_path = datagen.write_observation(obs, run.work, n_disk, "calibrate")
    sol_path = datagen.write_solutions(obs, run.work, n_disk)
    ignore_path = os.path.join(run.work, "ignore.txt")
    with open(ignore_path, "w") as f:
        f.write("# the target: left in the data\n"
                f"{target_and_rest(obs)[0]}\n")
    run.clock.mark("data")
    cfg = cli.config_from_args(cli.build_parser().parse_args(
        ["-d", ms_path, "-s", sky_path, "-c", cluster_path,
         "-p", sol_path, "-z", ignore_path, *conf["cli"]]))
    ms = predict.cycling_ms(ms_path, run, int(run.traffic["warmup_tiles"]))
    meta = ms.meta
    sky = skymodel.read_sky_cluster(cfg.sky_model, cfg.cluster_file,
                                    meta["ra0"], meta["dec0"],
                                    meta["freq0"], cfg.format_3)
    pipe = pipeline.FullBatchPipeline(cfg, ms, sky, log=run.log)
    pipe.run_simulation(log=run.log)
    run.drain()

    run.ms_path, run.sol_path = ms_path, sol_path
    run.counters["io_s"] = type(ms).io_s
    run.kept = type(ms).kept
    cycles = run.window.tiles
    failed = sum(1 for k in cycles if k not in run.kept
                 or not np.isfinite(run.kept[k]).all())
    return {"attempted": len(cycles), "failed": failed}


def expected(obs, tile, given, rest, rows):
    """At ``rows`` of disk tile ``tile``: (the input column as the
    reference made it, the model of the clusters ``rest`` under the
    solutions ``given``, the input's noise).  One pass over the sources:
    the input is the WHOLE sky under the tile's true Jones plus the
    noise, and the model is the same coherencies under the file's."""
    u, v, w, s1, s2 = (a[rows] for a in obs.geometry(tile))
    coh = reference.coherencies(obs.sky, u, v, w, obs.freq, obs.fdelta)
    noise = obs.noise(tile)[rows]
    x = reference.model(obs.jones(tile), coh, s1, s2) + noise
    return x, reference.model(given[rest], coh[rest], s1, s2), noise


def check(run):
    """Against the reference's ``x - model`` (``expected``): (a) the kept
    rows of every cycle of the window, the worst cycle, and
    ``check_rows`` seeded rows of ``check_tiles`` of the last cycles read
    back from disk, where DATA also has to be what was written there;
    (b) the kept rows of the short baselines, all cycles together, which
    reads the arithmetic of the Jones products; (c) the same error as
    (a) over the rms of the input's noise, the worst cycle: what an
    observer who images the output feels."""
    obs = run.obs
    n_disk = int(run.config["n_tiles_on_disk"])
    limits = run.config["limits"]
    given = reference.read_solutions(run.sol_path)
    rest = target_and_rest(obs)[1]
    cycles = run.window.tiles
    n = int(run.traffic["check_rows_per_cycle"])

    worst, worst_k, worst_noise = 0.0, None, 0.0
    short_err = short_ref = 0.0
    for k in cycles:
        i = k % n_disk
        x, m_ref, noise = expected(obs, i, given[i], rest,
                                   predict.kept_rows(run, k))
        want = x - m_ref
        d = run.kept[k] - want if k in run.kept else np.nan * want
        err = reference.rms(d[:n]) / reference.rms(m_ref[:n])
        if harness.worse(err, worst) is err:
            worst, worst_k = err, k
        worst_noise = harness.worse(
            reference.rms(d[:n]) / reference.rms(noise[:n]), worst_noise)
        short_err += float(np.sum(np.abs(d[n:]) ** 2))
        short_ref += float(np.sum(np.abs(m_ref[n:]) ** 2))
    notes = [f"{len(cycles)} cycles, {len(rest)} clusters subtracted, "
             f"worst cycle {worst_k}: {worst:.4g}"]

    n_rows = min(int(run.traffic["check_rows"]), obs.nrows)
    for k in harness.pick_tiles(cycles[-n_disk:],
                                int(run.traffic["check_tiles"])):
        i = k % n_disk
        rows = np.sort(np.random.default_rng([run.seed, 4, k]).choice(
            obs.nrows, n_rows, replace=False))
        x, m_ref, _ = expected(obs, i, given[i], rest, rows)
        out = datagen.read_column(run.ms_path, i, "x_corrected_data")[rows]
        err = reference.rms(out - (x - m_ref)) / reference.rms(m_ref)
        # the fourth guarantee: a cycle reads what the first one read
        moved = reference.rms(datagen.read_column(run.ms_path, i, "x")[rows]
                              - x) / reference.rms(m_ref)
        notes.append(f"cycle {k} from disk tile {i}: {err:.4g}, its DATA "
                     f"off what was written by {moved:.2g}")
        worst = harness.worse(harness.worse(err, moved), worst)
    return [
        harness.Comparison("residual_vs_reference", worst,
                           limits["residual_vs_reference"]["limit"],
                           "; ".join(notes)),
        harness.Comparison("short_residual_vs_reference",
                           (short_err / short_ref) ** 0.5
                           if short_ref else float("nan"),
                           limits["short_residual_vs_reference"]["limit"],
                           f"{n * len(cycles)} rows of the "
                           f"{len(run.short_rows) // obs.tilesz} baselines "
                           f"under {run.traffic['check_short_m']:g} m"),
        harness.Comparison("error_over_noise", worst_noise,
                           limits["error_over_noise"]["limit"],
                           "rms(out - (x - model_ref)) over rms(noise), "
                           "the worst cycle's kept rows"),
    ]
