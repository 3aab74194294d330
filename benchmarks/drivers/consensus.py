"""Driver ``consensus``: the subbands of one observation calibrated
together, solution interval by solution interval.

Closed loop through ``python -m sagecal_tpu.cli_mpi``'s own interval
loop, the seam its ``main()`` drives too: a ``cli_mpi.ConsensusStepper``
built from the configuration's arguments as that program's parser gives
them, a ``sched.Prefetcher`` of the depth the configuration has
(``--prefetch``) that reads and stages the next interval while this one
solves, ``ConsensusStepper.step`` per interval (one mesh execution of all
ADMM iterations, the fetch, the residual program, the interval's ordered
writes) and ``close()`` to drain the writer.  One subband a device where
there are as many devices as subbands; fewer devices hold several each
(a CPU rehearsal folds all on one).

Warm-up is the mix's ``warmup_tiles`` first intervals; the window opens
as the next interval's step is entered and closes at the first interval
boundary after ``--seconds``, or with the observation's last interval
where that comes first (``left`` tells the harness how far that is); then
the writer is drained.  A tile of the
harness is an interval here: ``n_vis`` of it is the unflagged samples of
all subbands.
"""

import os
import sys

import numpy as np

import datagen
import harness
import reference
import reference_consensus as refc

#: ``run.enter_tile`` is called between two steps, in no span of the
#: program's: a mix of this driver may set ``profile_tiles``
BOUNDARY_OUTSIDE_SPANS = True


def require_seam(cli_mpi) -> None:
    """A tree whose ``cli_mpi`` cannot be stepped cannot run the cell:
    said on stderr, and the process ends here, before any device work
    and with no result line."""
    if not hasattr(cli_mpi, "ConsensusStepper"):
        print("benchmarks/drivers/consensus.py: this tree's "
              "sagecal_tpu/cli_mpi.py has no ConsensusStepper; its "
              "consensus interval loop is a closure of _main_consensus "
              "that nothing but main() can drive, so the cell cannot run "
              "on it (the seam arrives with PR 30)", file=sys.stderr)
        raise SystemExit(4)


# at import, which is when the harness looks the cell up: before the
# backend is opened
from sagecal_tpu import cli_mpi     # noqa: E402
require_seam(cli_mpi)


def npoly(conf) -> int:
    """``-P`` of the configuration's arguments."""
    return int(conf["cli"][conf["cli"].index("-P") + 1])


def run(run):
    from sagecal_tpu import sched

    conf = run.config
    subs = refc.subbands(conf, run.seed)
    sky_path, cluster_path = datagen.write_sky(subs[0], run.work)
    n_tiles = int(conf["n_tiles_on_disk"])
    ms_paths = []
    for k, sub in enumerate(subs):
        sb_dir = os.path.join(run.work, f"sb{k}")
        os.makedirs(sb_dir)
        ms_paths.append(datagen.write_observation(
            sub, sb_dir, n_tiles, "calibrate"))
    list_path = os.path.join(run.work, "subbands.txt")
    with open(list_path, "w") as f:
        f.write("\n".join(ms_paths) + "\n")
    rho_path = os.path.join(run.work, "regularization_factors.txt")
    with open(rho_path, "w") as f:      # "cluster_id hybrid rho"
        for ln in subs[0].cluster_lines:
            f.write(f"{ln.split()[0]} 1 {float(conf['cluster_rho'])}\n")
    z_path = os.path.join(run.work, "global.solutions")
    run.clock.mark("data")
    # the configuration as a user of the program gets it
    args = cli_mpi.build_parser().parse_args(
        ["-f", list_path, "-s", sky_path, "-c", cluster_path,
         "-G", rho_path, "-p", z_path, *conf["cli"]])
    st = cli_mpi.ConsensusStepper(args, log=run.log)
    print("[mesh] " + " ".join(str(d) for d in st.mesh.devices.flat)
          + f": {st.nf} subband(s) over {st.ndev} device(s)")

    def produce(i):
        with run.annotate("read_stage"):
            tiles = st.read(i)
            return tiles, st.stage(i, tiles)

    warm = int(run.traffic["warmup_tiles"])
    pf = sched.Prefetcher(produce, st.n_intervals, depth=st.depth,
                          tile0=st.start)
    try:
        for i, (tiles, stg), wait in pf:
            ti = st.start + i
            if ti >= warm:
                if run.window.due():
                    break
                run.enter_tile(ti, sum(
                    int((t.flags == 0).sum()) * len(t.freqs)
                    for t in tiles), left=st.n_intervals - 1 - i)
            with run.annotate("step"):
                st.step(ti, tiles, stg, wait)
    finally:
        pf.close()
        with run.annotate("drain"):
            st.close()
    run.drain()

    run.ms_paths, run.z_path = ms_paths, z_path
    run.counters["stepped"] = len(st.history)
    in_window = set(run.window.tiles)
    recs = [r for r in st.history if r["tile"] in in_window]
    run.counters["history"] = recs
    # an interval fails where the program itself would reset it: a
    # residual that is not finite, exactly zero, or five times its start
    failed = sum(1 for r in recs
                 if not (np.isfinite(r["res_0"]) and np.isfinite(r["res_1"])
                         and 0.0 < r["res_1"] <= 5.0 * r["res_0"]))
    return {"attempted": len(recs), "failed": failed}


def check(run):
    """For ``check_tiles`` intervals of the window (all of them, where
    the mix asks for as many), from disk, worst over intervals and
    subbands: (a) the written residual against data minus the
    reference's model under the WRITTEN solutions of that subband; (b)
    that residual over the residual under the true Jones, which is the
    noise; (c) the same under ``B_f Z`` of the written global file, by
    the reference's basis; (d) the consensus primal residual
    ``||J - B Z||`` of what was written.  A file that holds fewer
    intervals than were stepped is not correct, whatever it holds."""
    conf, limits = run.config, run.config["limits"]
    subs = refc.subbands(conf, run.seed)
    n_poly = npoly(conf)
    basis = refc.bernstein_basis(conf["subband_freqs_hz"], n_poly)
    written = [reference.read_solutions(refc.subband_solutions_path(p))
               for p in run.ms_paths]
    z_written = refc.read_z_file(run.z_path, n_poly)
    names = ("residual_vs_reference", "residual_over_noise",
             "consensus_over_noise", "consensus_primal")
    short = [f"{os.path.basename(os.path.dirname(p))}: {len(w)}"
             for p, w in zip(run.ms_paths, written)
             if len(w) != run.counters["stepped"]]
    if len(z_written) != run.counters["stepped"]:
        short.append(f"global Z: {len(z_written)}")
    if short:
        note = (f"{run.counters['stepped']} intervals were stepped, but "
                "the files hold " + ", ".join(short))
        return [harness.Comparison(n, float("nan"), limits[n]["limit"], note)
                for n in names]
    worst = dict.fromkeys(names, 0.0)
    notes = []
    j_true = [sub.jones() for sub in subs]
    for t in harness.pick_tiles(run.window.tiles,
                                int(run.traffic["check_tiles"])):
        j_t = np.stack([w[t] for w in written])     # [F, M, N, 2, 2]
        bz_t = refc.bz(basis, z_written[t])
        vals = dict.fromkeys(names, 0.0)
        vals["consensus_primal"] = refc.primal_residual(
            j_t, basis, z_written[t])
        for k, (sub, ms) in enumerate(zip(subs, run.ms_paths)):
            x = datagen.read_column(ms, t, "x")
            r_prog = datagen.read_column(ms, t, "x_corrected_data")
            # the coherencies once, the three models from them
            u, v, w, s1, s2 = sub.geometry(t)
            coh = reference.coherencies(sub.sky, u, v, w, sub.freq,
                                        sub.fdelta)
            r_ref = x - reference.model(j_t[k], coh, s1, s2)
            floor = reference.rms(x - reference.model(j_true[k], coh, s1, s2))
            for name, val in (
                    ("residual_vs_reference",
                     reference.rms(r_prog - r_ref) / reference.rms(r_ref)),
                    ("residual_over_noise", reference.rms(r_ref) / floor),
                    ("consensus_over_noise", reference.rms(
                        x - reference.model(bz_t[k], coh, s1, s2)) / floor)):
                vals[name] = harness.worse(val, vals[name])
        notes.append(f"interval {t}: " + ", ".join(
            f"{vals[n]:.5g}" for n in names))
        for n in names:
            worst[n] = harness.worse(vals[n], worst[n])
    return [harness.Comparison(n, worst[n], limits[n]["limit"],
                               "; ".join(notes) if n == names[0] else "")
            for n in names]
