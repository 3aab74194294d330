"""Driver ``predict``: the sky model predicted into the output column
under a solutions file (``-a 1 -p``), tile after tile.

Closed loop through ``FullBatchPipeline.run_simulation()`` itself.  The
dataset it is given is a subclass of the program's ``SimMS`` whose
``tiles()`` cycles the on-disk tiles (index modulo) until the window
closes, and whose ``read_tile``/``write_tile`` are timed by the
harness's clock: the program's loop, read, predict program, read-back
and write are all the program's own.

A disk tile is written again every ``n_tiles_on_disk`` cycles, so what a
cycle wrote is gone when the window closes.  ``write_tile`` therefore
keeps, of EVERY cycle of the window, a few rows drawn from the seed of
the array it is handed to write (``kept_rows``); the check compares all
of them, and reads back from disk the cycles that are still there.

Which cycle a written tile belongs to follows the tile, not the loop:
``tiles()`` queues each cycle as it reads and ``write_tile`` takes the
oldest one off the queue.  The program writes tiles in the order it read
them (an ordered writer's guarantee, and what a file on disk means), so a
loop that reads ahead of its writes, or writes from another thread, is
checked cycle by cycle all the same.
"""

import collections
import os
import time

import numpy as np

import datagen
import harness
import reference


def kept_rows(run, k):
    """The rows of cycle ``k`` that are kept and compared: ``check_rows``
    of all rows, then as many of the short baselines' rows."""
    n = int(run.traffic["check_rows_per_cycle"])
    rng = np.random.default_rng([run.seed, 3, k])
    return np.concatenate([rng.integers(0, run.obs.nrows, n),
                           rng.choice(run.short_rows, n)])


def cycling_ms(path, run, warm):
    from sagecal_tpu.io import dataset as ds

    class CyclingMS(ds.SimMS):
        io_s = 0.0          # read + write seconds inside the window
        out = collections.deque()   # the cycles read and not yet written
        kept = {}           # cycle -> [2 * check_rows_per_cycle, 2, 2]

        def _timed(self, name, fn, *a, **kw):
            t0 = time.perf_counter()
            with run.annotate(name):
                out = fn(*a, **kw)
            if run.window.t_open is not None:
                CyclingMS.io_s += time.perf_counter() - t0
            return out

        def read_tile(self, i):
            return self._timed("read_tile", super().read_tile, i)

        def write_tile(self, i, tile, column=None):
            k = CyclingMS.out.popleft()
            if k >= warm:
                CyclingMS.kept[k] = np.array(tile.x[kept_rows(run, k), 0])
            return self._timed("write_tile", super().write_tile, i, tile,
                               column)

        def tiles(self):
            k = 0
            while True:
                if k >= warm:
                    if run.window.due():
                        return
                    run.enter_tile(k, self.meta["nbase"]
                                   * self.meta["tilesz"]
                                   * len(self.meta["freqs"]))
                i = k % self.n_tiles
                CyclingMS.out.append(k)
                yield i, self.read_tile(i)
                k += 1

    return CyclingMS(path)


def run(run):
    from sagecal_tpu import cli, pipeline, skymodel

    obs, conf = run.obs, run.config
    n_disk = int(conf["n_tiles_on_disk"])
    run.short_rows = obs.short_rows(float(run.traffic["check_short_m"]))
    sky_path, cluster_path = datagen.write_sky(obs, run.work)
    ms_path = datagen.write_observation(obs, run.work, n_disk, "noise")
    sol_path = datagen.write_solutions(obs, run.work, n_disk)
    run.clock.mark("data")
    cfg = cli.config_from_args(cli.build_parser().parse_args(
        ["-d", ms_path, "-s", sky_path, "-c", cluster_path,
         "-p", sol_path, *conf["cli"]]))
    ms = cycling_ms(ms_path, run, int(run.traffic["warmup_tiles"]))
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


def check(run):
    """Against the reference's model under each tile's interval of the
    solutions file: (a) the kept rows of every cycle of the window, the
    worst cycle; (b) ``check_rows`` seeded rows of ``check_tiles`` of the
    last cycles, read back from disk, where the last ``n_tiles_on_disk``
    cycles still are; (c) the kept rows of the short baselines, all
    cycles together: float32 rounds their small phases finely, so this
    one reads the arithmetic of the Jones products."""
    obs = run.obs
    n_disk = int(run.config["n_tiles_on_disk"])
    limits = run.config["limits"]
    given = reference.read_solutions(run.sol_path)
    cycles = run.window.tiles
    n = int(run.traffic["check_rows_per_cycle"])

    worst, worst_k = 0.0, None
    short_err = short_ref = 0.0
    for k in cycles:
        i = k % n_disk
        v_ref = obs.model(i, given[i], rows=kept_rows(run, k))
        d = run.kept[k] - v_ref if k in run.kept else np.nan * v_ref
        err = reference.rms(d[:n]) / reference.rms(v_ref[:n])
        if harness.worse(err, worst) is err:
            worst, worst_k = err, k
        short_err += float(np.sum(np.abs(d[n:]) ** 2))
        short_ref += float(np.sum(np.abs(v_ref[n:]) ** 2))
    notes = [f"{len(cycles)} cycles, worst cycle {worst_k}: {worst:.4g}"]

    n_rows = min(int(run.traffic["check_rows"]), obs.nrows)
    for k in harness.pick_tiles(cycles[-n_disk:],
                                int(run.traffic["check_tiles"])):
        i = k % n_disk
        rows = np.sort(np.random.default_rng([run.seed, 4, k]).choice(
            obs.nrows, n_rows, replace=False))
        v_prog = datagen.read_column(run.ms_path, i,
                                     "x_corrected_data")[rows]
        v_ref = obs.model(i, given[i], rows=rows)
        err = reference.rms(v_prog - v_ref) / reference.rms(v_ref)
        notes.append(f"cycle {k} from disk tile {i}: {err:.4g}")
        worst = harness.worse(err, worst)
    return [
        harness.Comparison("model_vs_reference", worst,
                           limits["model_vs_reference"]["limit"],
                           "; ".join(notes)),
        harness.Comparison("short_model_vs_reference",
                           (short_err / short_ref) ** 0.5
                           if short_ref else float("nan"),
                           limits["short_model_vs_reference"]["limit"],
                           f"{n * len(cycles)} rows of the "
                           f"{len(run.short_rows) // obs.tilesz} baselines "
                           f"under {run.traffic['check_short_m']:g} m"),
    ]
