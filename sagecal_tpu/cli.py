"""``sagecal-tpu`` command line: flag parity with the reference binary.

Reference: ``src/MS/main.cpp:107-257`` (ParseCmdLine). Single-letter flags
keep their reference meaning so existing invocations translate directly;
long aliases are added for readability. Dispatch mirrors main.cpp:288-299:
stochastic-consensus if -N>0 and -A>1 and -w>1; stochastic if -N>0;
otherwise full batch.
"""

from __future__ import annotations

import argparse
import sys

from sagecal_tpu import utils
from sagecal_tpu.config import (BeamMode, RunConfig, SimulationMode,
                                SolverMode)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="sagecal-tpu",
        description="TPU-native direction-dependent calibration "
                    "(capability parity with sagecal)")
    a = p.add_argument
    a("-d", "--ms", help="dataset (SimMS directory or MS)")
    a("-f", "--ms-list", help="file/glob listing multiple datasets")
    a("-s", "--sky-model", required=False)
    a("-c", "--cluster-file", required=False)
    a("-p", "--solutions-file", help="solutions out (or in, for -a modes)")
    a("-q", "--init-solutions", help="warm-start solutions file")
    a("-F", "--format", type=int, default=0,
      help="1: sky model has 3rd-order spectral indices")
    a("-t", "--tile-size", type=int, default=120)
    a("-e", "--max-em-iter", type=int, default=3)
    a("-g", "--max-iter", type=int, default=10,
      help="max iterations within single EM (main.cpp -g; reference "
           "default 2 — the batched solvers converge per-sweep, so 10)")
    a("-l", "--max-lbfgs", type=int, default=10,
      help="max LBFGS iterations (main.cpp -l)")
    a("-m", "--lbfgs-m", type=int, default=7,
      help="LBFGS memory size (main.cpp -m)")
    a("-n", "--n-threads", type=int, default=4)
    a("-j", "--solver-mode", type=int, default=5,
      help="0 OSLM, 1 LM, 2 RLM, 3 OSRLM, 4 RTR, 5 RRTR (default), "
           "6 NSD (reference Dirac.h:1533 SM_* numbering)")
    a("-L", "--nulow", type=float, default=2.0)
    a("-H", "--nuhigh", type=float, default=30.0)
    a("--linsolv", type=int, default=1,
      help="0 Cholesky 1 QR 2 SVD (no reference letter; Data::linsolv)")
    a("-R", "--randomize", type=int, default=1)
    a("-x", "--uvmin", type=float, default=0.0,
      help="exclude baselines shorter than this (lambda; main.cpp -x)")
    a("-y", "--uvmax", type=float, default=1e9,
      help="exclude baselines longer than this (lambda; main.cpp -y)")
    a("-I", "--input-column", default="DATA",
      help="data column to calibrate (Data::DataField)")
    a("-O", "--output-column", default="CORRECTED_DATA",
      help="column receiving residuals/sim output (Data::OutField)")
    a("-o", "--mmse-rho", type=float, default=1e-9,
      help="robust rho for MMSE inversion during correction "
           "(Data::rho, residual.c)")
    a("-W", "--whiten", type=int, default=0)
    a("-D", "--diagnostics", type=int, default=0,
      help="accepted for parity; the reference's Jacobian-leverage "
           "call is disabled in v0.7.8 (fullbatch_mode.cpp:520)")
    a("--profile", default=None, metavar="DIR",
      help="write a jax.profiler trace of one warm solve interval "
           "(the first tile after a tile that compiled nothing)")
    a("--diag", default=None, metavar="PATH",
      help="write a JSONL diagnostic trace (phase timers + per-iteration "
           "convergence records, sagecal_tpu.diag.trace) to PATH")
    a("--metrics", default=None, metavar="PATH",
      help="enable the obs metrics registry for this run and dump it "
           "as JSON to PATH at exit (counters, gauges, latency "
           "histograms with p50/p90/p99 — sagecal_tpu.obs.metrics; "
           "off = zero overhead, bit-identical)")
    a("--tile-batch", type=int, default=1,
      help=">1: solve this many intervals as one batched device program "
           "(throughput lever; warm start becomes batch-granular)")
    a("--solve-fuse", choices=("auto", "on", "off"), default="auto",
      help="EM-sweep fusion: learn from timed sweeps (auto) or force")
    a("--solve-promote", choices=("auto", "on", "off"), default="auto",
      help="full-trace solve promotion: learn (auto) or force")
    a("--inflight", type=int, default=1,
      help="clusters solved concurrently per SAGE sweep step (block-"
           "Jacobi groups); 1 = reference Gauss-Seidel sequencing")
    a("--tile-bucket", type=int, default=0, metavar="T",
      help="pad each solve interval to T timeslots with zero-weight "
           "rows so bucket-compatible jobs share compiled programs "
           "(sagecal_tpu.serve compile cache; 0 = exact shapes, "
           "-1 = next power of two; outputs are bit-identical to any "
           "solo run at the SAME bucket)")
    a("--resume", action="store_true",
      help="re-enter a killed/failed run from its tile-boundary "
           "checkpoint (the <solutions>.ckpt.npz sidecar next to -p): "
           "completed tiles are skipped and the final residuals + "
           "solutions are bit-identical to an uninterrupted run "
           "(sequential fullbatch driver; MIGRATION.md 'Fault "
           "tolerance'). No checkpoint = start fresh")
    a("--faults", default=None, metavar="SPEC",
      help="deterministic fault-injection plan (sagecal_tpu.faults): "
           "a JSON list of rules, {'seed':..,'rules':[..]}, or a path/"
           "@path to a file holding either — chaos testing only; "
           "absent = zero cost, bit-identical")
    a("--prefetch", type=int, default=1, metavar="N",
      help="overlapped execution depth (sagecal_tpu.sched): read + "
           "host-prepare tile t+N on a background thread while tile t "
           "solves, residual/solution writes on an ordered writer "
           "thread (bit-identical outputs; default 1 = double-"
           "buffered). The simulation modes -a 1|2|3 overlap the same "
           "way (two tiles ahead at most), and keep one tile's program "
           "queued behind the one that runs. 0 = fully synchronous "
           "reference loop — the debugging escape hatch")
    a("--prior-cache", choices=("off", "read", "readwrite"),
      default="off",
      help="warm-start solution prior store (serve/priors.py): read = "
           "seed J0 from a banked same-key solution (sky/cluster "
           "content + station set + band + solver family), readwrite "
           "= also bank this run's final chain. Changes iteration "
           "counts, never the convergence target; off (default) is "
           "bit- and compile-count-identical to pre-prior behavior")
    a("--dtype-policy", choices=("f32", "bf16", "f16"), default="f32",
      help="storage dtype for the [B]-data (visibilities, weights, "
           "staged residual tiles, Wirtinger factors) with f32 "
           "accumulation everywhere; f32 = the default, held to the "
           "references' limits and not to bits (MIGRATION.md 'Dtype "
           "policy' for the per-policy tolerance envelopes)")
    a("--inner", choices=("chol", "cg"), default="chol",
      help="inner linear solver for the damped Gauss-Newton step: "
           "chol = dense [K,8N,8N] assembly + batched Cholesky "
           "(the default); cg = matrix-free preconditioned CG "
           "(never forms the normal matrix; MIGRATION.md 'Inner "
           "linear solver')")
    a("--jones", choices=("full", "diag", "phase"), default="full",
      help="Jones parameterization for the solve: full = 2x2 complex "
           "per station (the default); diag = diagonal-only "
           "(4 real params/station, 4x4 Gram blocks); phase = "
           "phase-only per polarization (2 real params/station, 2x2 "
           "Gram blocks, retraction J*exp(i*theta)). Distinct from "
           "-J/--phase-only, which phase-projects the CORRECTION "
           "after a full solve (MIGRATION.md 'Jones modes')")
    a("--shard-baselines", action="store_true",
      help="shard the baseline row axis of the (single) subband over "
           "all devices (P1 intra-subband parallelism)")
    # platform overrides (utils.setup_backend)
    a("--platform", default=None,
      help="force the jax platform, e.g. 'cpu' for a virtual host mesh")
    a("--cpu-devices", type=int, default=0,
      help="virtual CPU device count (with --platform cpu)")
    a("-w", "--nsolbw", type=int, default=1,
      help="frequency mini-bands for bandpass consensus")
    a("-b", "--per-channel", type=int, default=0)
    a("-a", "--simulation", type=int, default=0,
      help="1 simulate, 2 add model, 3 subtract model")
    a("-z", "--ignore-clusters", help="file of cluster ids to ignore")
    a("-k", "--correct-cluster", type=int, default=None,
      help="cluster id whose solutions correct the residual")
    a("-J", "--phase-only", type=int, default=0,
      help=">0: phase-only correction (joint-diagonalized phases)")
    a("-B", "--beam", type=int, default=0)
    a("-N", "--epochs", type=int, default=0,
      help=">0 enables stochastic (minibatch) calibration")
    a("--loss", choices=("robust", "huber"), default="robust",
      help="stochastic minibatch loss (Student's t or Huber)")
    a("-M", "--minibatches", type=int, default=1)
    a("-A", "--admm", type=int, default=1)
    a("-P", "--npoly", type=int, default=2)
    a("-Q", "--polytype", type=int, default=2)
    a("-r", "--rho", type=float, default=5.0)
    a("-G", "--rho-file", default=None)
    a("-T", "--max-timeslots", type=int, default=0)
    a("-V", "--verbose", action="store_true")
    return p


def warn_legacy_flags(args, err=sys.stderr) -> list:
    """One-time startup warning for short-option values that suggest a
    pre-remap command line. The reference-parity remap is silent by
    design (same letters, same meanings), which also means a command
    line written for a DIFFERENT tool or an old habit fails silently:
    a ``-y`` under 10 lambda excludes essentially every baseline, and
    an ``-o`` (MMSE rho) above 1 is far outside the regularization
    regime (reference default 1e-9) — both almost certainly meant
    something else. The run proceeds; the warning names the flag."""
    warnings = []
    if args.uvmax < 10.0:
        warnings.append(
            f"-y/--uvmax={args.uvmax:g} lambda excludes nearly all "
            "baselines; the reference -y is an upper uv-distance cut in "
            "lambda (default 1e9) — was this meant for another tool?")
    if args.mmse_rho > 1.0:
        warnings.append(
            f"-o/--mmse-rho={args.mmse_rho:g} is far above the MMSE "
            "regularization regime (reference default 1e-9); the "
            "reference -o is the robust rho for residual correction — "
            "not an output path or a solver knob")
    for w in warnings:
        print(f"WARNING: suspicious legacy option value: {w}", file=err)
    return warnings


def config_from_args(args) -> RunConfig:
    return RunConfig(
        ms=args.ms, ms_list=args.ms_list, sky_model=args.sky_model,
        cluster_file=args.cluster_file, solutions_file=args.solutions_file,
        init_solutions=args.init_solutions, format_3=bool(args.format),
        tile_size=args.tile_size, max_em_iter=args.max_em_iter,
        max_iter=args.max_iter,
        max_lbfgs=args.max_lbfgs, lbfgs_m=args.lbfgs_m,
        input_column=args.input_column, output_column=args.output_column,
        mmse_rho=args.mmse_rho,
        n_threads=args.n_threads, solver_mode=SolverMode(args.solver_mode),
        robust_nulow=args.nulow, robust_nuhigh=args.nuhigh,
        linsolv=args.linsolv, randomize=bool(args.randomize),
        uvmin=args.uvmin, uvmax=args.uvmax, whiten=bool(args.whiten),
        channel_avg_per_band=args.nsolbw,
        per_channel_bfgs=bool(args.per_channel),
        simulation=SimulationMode(args.simulation),
        ignore_clusters_file=args.ignore_clusters,
        correct_cluster=args.correct_cluster,
        phase_only=bool(args.phase_only), beam_mode=BeamMode(args.beam),
        n_epochs=args.epochs, n_minibatches=args.minibatches,
        stochastic_loss=args.loss,
        n_admm=args.admm, n_poly=args.npoly, poly_type=args.polytype,
        admm_rho=args.rho, rho_file=args.rho_file,
        max_timeslots=args.max_timeslots, verbose=args.verbose,
        profile_dir=args.profile,
        tile_batch=args.tile_batch, solve_fuse=args.solve_fuse,
        solve_promote=args.solve_promote,
        cluster_inflight=args.inflight,
        solver_inner=args.inner,
        jones_mode=args.jones,
        dtype_policy=args.dtype_policy,
        tile_bucket=args.tile_bucket,
        prefetch=args.prefetch,
        prior_cache=args.prior_cache,
        resume=bool(args.resume),
        shard_baselines=bool(args.shard_baselines))


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    utils.setup_backend(args.platform, args.cpu_devices)
    cfg = config_from_args(args)
    if (not cfg.ms and not cfg.ms_list) or not cfg.sky_model \
            or not cfg.cluster_file:
        print("need -d dataset (or -f list), -s sky model, -c cluster file",
              file=sys.stderr)
        return 2
    warn_legacy_flags(args)

    if args.diag:
        from sagecal_tpu.diag import trace as dtrace
        dtrace.enable(args.diag, entry="sagecal-tpu",
                      argv=list(argv) if argv is not None else sys.argv[1:])
    if args.metrics:
        from sagecal_tpu.obs import metrics as ometrics
        ometrics.enable()
    if args.faults:
        from sagecal_tpu import faults
        faults.enable_spec(args.faults)

    from sagecal_tpu import pipeline
    try:
        if cfg.n_epochs > 0:
            from sagecal_tpu import stochastic
            if cfg.n_admm > 1 and cfg.channel_avg_per_band > 1:
                stochastic.run_minibatch_consensus(cfg)
            else:
                stochastic.run_minibatch(cfg)
        else:
            pipeline.run(cfg)
    finally:
        if args.diag:
            dtrace.disable()
        if args.metrics:
            ometrics.dump_to(args.metrics)
    return 0


if __name__ == "__main__":
    sys.exit(main())
