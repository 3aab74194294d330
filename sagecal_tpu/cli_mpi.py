"""``sagecal-tpu-mpi``: distributed consensus calibration across subbands.

Capability parity with the reference ``sagecal-mpi`` binary
(``src/MPI/main.cpp``): one invocation calibrates F frequency-subband
datasets jointly with consensus ADMM and a smooth polynomial-in-frequency
prior. Where the reference spreads ranks over hosts with mpirun and a tag
protocol (SURVEY.md section 3.3), this runs ONE SPMD program over the JAX
device mesh — multi-host TPU pods get the same program via jax.distributed
initialization, subbands riding the "freq" mesh axis over ICI/DCN.

MPI-specific flags keep their reference meaning: -A ADMM iterations,
-P polynomial terms, -Q type, -r rho, -G per-cluster rho file, -C adaptive
rho, -T/-K timeslot limits, -U global-solution residuals, -V verbose.
"""

from __future__ import annotations

import argparse
import glob as globmod
import sys

import numpy as np

from sagecal_tpu import skymodel, utils
from sagecal_tpu.config import SolverMode
from sagecal_tpu.obs import metrics as obs
from sagecal_tpu.serve import priors as ppriors


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="sagecal-tpu-mpi",
        description="distributed consensus-ADMM calibration over subbands")
    a = p.add_argument
    a("-f", "--ms-pattern", required=True,
      help="glob pattern or file listing the subband datasets")
    a("-s", "--sky-model", required=True)
    a("-c", "--cluster-file", required=True)
    a("-p", "--solutions-file", help="global Z solution file")
    a("-F", "--format", type=int, default=0)
    a("-t", "--tile-size", type=int, default=120)
    a("-e", "--max-em-iter", type=int, default=3)
    a("-g", "--max-iter", type=int, default=10,
      help="max iterations within single EM (MPI/main.cpp -g)")
    a("-l", "--max-lbfgs", type=int, default=10,
      help="max LBFGS iterations (MPI/main.cpp -l)")
    a("-m", "--lbfgs-m", type=int, default=7,
      help="LBFGS memory size (MPI/main.cpp -m)")
    a("-x", "--uvmin", type=float, default=0.0,
      help="exclude baselines shorter than this (lambda; -x)")
    a("-y", "--uvmax", type=float, default=1e9,
      help="exclude baselines longer than this (lambda; -y)")
    a("-n", "--n-threads", type=int, default=4,
      help="accepted for reference parity; host threading is XLA's")
    a("-R", "--randomize", type=int, default=1,
      help="randomize cluster visiting order (MPI/main.cpp -R)")
    a("-W", "--whiten", type=int, default=0,
      help="uv-density whitening of the solve input (updatenu.c)")
    a("-k", "--correct-cluster", type=int, default=None,
      help="cluster id whose solutions correct the residual (-k)")
    a("-o", "--mmse-rho", type=float, default=1e-9,
      help="robust rho for MMSE inversion during correction (-o)")
    a("-J", "--phase-only", type=int, default=0,
      help=">0: phase-only correction (-J)")
    a("-q", "--init-solutions",
      help="warm-start J from this solution file (1 interval, J format)")
    a("-B", "--beam", type=int, default=0,
      help="0 none, 1 array factor, 2 array+element, 3 element "
           "(MPI/main.cpp -B; beam tables fold into the slave predict)")
    a("-j", "--solver-mode", type=int, default=5)
    a("-L", "--nulow", type=float, default=2.0)
    a("-H", "--nuhigh", type=float, default=30.0)
    a("-A", "--admm", type=int, default=10)
    a("-P", "--npoly", type=int, default=2)
    a("-Q", "--polytype", type=int, default=2)
    a("-r", "--rho", type=float, default=5.0)
    a("-G", "--rho-file", default=None)
    a("-C", "--adaptive-rho", type=int, default=0)
    a("--prior-cache", choices=("off", "read", "readwrite"),
      default="off",
      help="solution prior store (serve/priors.py): 'read' seeds J0 "
           "and the per-cluster rho schedule from a matching banked "
           "run, 'readwrite' also banks this run's final solutions; "
           "'off' (default) is the cold start, as without a store. An "
           "explicit -q/-G always wins over the prior.")
    a("-T", "--max-timeslots", type=int, default=0)
    a("-K", "--skip-timeslots", type=int, default=0)
    a("-U", "--use-global-solution", type=int, default=0)
    a("--mdl", action="store_true",
      help="report MDL/AIC consensus-polynomial model order (mdl.c:42; "
           "the reference's disabled -M meaning)")
    a("-N", "--epochs", type=int, default=0,
      help=">0: stochastic federated mode (sagecal_stochastic_*.cpp)")
    a("-M", "--minibatches", type=int, default=1,
      help="stochastic minibatches (MPI/main.cpp -M)")
    a("-w", "--bands", type=int, default=1,
      help="channels per mini-band in stochastic mode")
    a("-u", "--federated-alpha", type=float, default=0.0,
      help="federated/spatial prior strength (-u)")
    a("-X", "--spatialreg", default=None,
      help="spatial regularization: l2,l1,order,fista_iters,cadence")
    a("-V", "--verbose", action="store_true")
    a("-I", "--input-column", default="DATA",
      help="data column to calibrate (Data::DataField)")
    a("-O", "--output-column", default="CORRECTED_DATA",
      help="column receiving residuals (Data::OutField)")
    # multi-host execution (the mpirun analogue): same program on every
    # host, coordinated through jax.distributed; the mesh then spans all
    # hosts' devices and subband shards ride ICI/DCN
    a("--coordinator", default=None,
      help="host:port of process 0 for jax.distributed.initialize "
           "(multi-host pods; omit for single-process)")
    a("--num-processes", type=int, default=1)
    a("--process-id", type=int, default=0)
    # platform overrides (utils.setup_backend)
    a("--platform", default=None,
      help="force the jax platform, e.g. 'cpu' for a virtual host mesh")
    a("--cpu-devices", type=int, default=0,
      help="virtual CPU device count (with --platform cpu)")
    a("--mesh-devices", type=int, default=0,
      help="cap the consensus mesh to N of the visible devices "
           "(0 = all, up to F). Lets a run leave devices to other "
           "tenants")
    a("--block-f", type=int, default=0,
      help="single-device blocked J-update: subbands per device "
           "execution (bounds each program's wall-clock on "
           "north-star shapes); 0 = "
           "one mesh program")
    a("--time-shard", type=int, default=0, metavar="T",
      help="2-D ('freq', 'time') mesh: shard the solution intervals "
           "over T time-mesh devices IN ADDITION to the subband freq "
           "axis, solving the whole selected observation as one SPMD "
           "program (admm.make_admm_runner_2d; MIGRATION.md '2-D "
           "mesh'). Reads every interval up front; the warm-start J "
           "chain runs per time shard with a cold seam at each shard "
           "boundary. 0 = off (the per-interval loop)")
    a("--staleness", type=int, default=0, metavar="S",
      help="bounded-staleness consensus (single device, opt-in): a "
           "straggling subband — injected via the admm_subband_slow "
           "fault point — may skip its J-update while peers consume "
           "its duals up to S iterations stale "
           "(admm.make_admm_runner_stale). 0 = synchronous (default; "
           "bit-identical chain)")
    a("--inflight", type=int, default=1,
      help="clusters solved concurrently per SAGE sweep step (block-"
           "Jacobi groups; the reference GPU pipeline's 2-in-flight "
           "analogue, lmfit_cuda.c:450). 1 = strict sequencing")
    a("--dtype-policy", choices=("f32", "bf16", "f16"), default="f32",
      help="storage dtype for visibilities/weights/Wirtinger factors "
           "with f32 accumulation (sagecal_tpu.dtypes; MIGRATION.md "
           "'Dtype policy'). f32 = the default, held to the "
           "references' limits and not to bits")
    a("--inner", choices=("chol", "cg"), default="chol",
      help="inner linear solver for the per-cluster J-updates: chol = "
           "dense [K,8N,8N] assembly (the default); cg = matrix-free "
           "preconditioned Krylov — melts the B-independent "
           "factorization floor at north-star N/M (PERF.md round 7)")
    a("--jones", choices=("full", "diag", "phase"), default="full",
      help="Jones parameterization (MIGRATION.md 'Jones modes'). "
           "Consensus ADMM requires 'full': the y/bz consensus "
           "vectors are full-Jones parameters, so any constrained "
           "mode is refused at startup")
    a("--host-loop", action="store_true",
      help="one device execution per ADMM iteration instead of a fully "
           "traced n_admm-iteration program")
    a("--prefetch", type=int, default=1, metavar="N",
      help="overlapped execution depth (sagecal_tpu.sched): all "
           "subbands of interval t+N are read on a background thread "
           "while interval t solves; residual/solution writes run on "
           "an ordered writer thread (bit-identical outputs). 0 = "
           "fully synchronous loop — the debugging escape hatch")
    a("--diag", default=None, metavar="PATH",
      help="write a JSONL diagnostic trace (phase timers, per-ADMM-"
           "iteration convergence records, staging bytes-accounting; "
           "sagecal_tpu.diag.trace) to PATH")
    a("--metrics", default=None, metavar="PATH",
      help="enable the obs metrics registry for this run and dump it "
           "as JSON to PATH at exit (ADMM consensus residual gauges, "
           "latency histograms; sagecal_tpu.obs.metrics)")
    a("--faults", default=None, metavar="SPEC",
      help="deterministic fault-injection plan (sagecal_tpu.faults; "
           "JSON rules or a path to them) — chaos testing of the "
           "interval loop's read/write seams; absent = zero cost")
    return p


def discover_datasets(pattern: str) -> list:
    """Glob pattern or list file -> sorted dataset paths (master :61-221)."""
    import os
    if os.path.isfile(pattern):
        with open(pattern) as f:
            paths = [ln.strip() for ln in f if ln.strip()]
    else:
        paths = sorted(globmod.glob(pattern))
    if not paths:
        raise FileNotFoundError(f"no datasets match {pattern!r}")
    return paths


def main(argv=None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)

    if args.epochs > 0 and args.num_processes > 1:
        # fail fast on parsed arguments — before the distributed
        # handshake, which blocks until every peer shows up
        parser.error(
            "federated stochastic mode (-N) currently stages data "
            "single-process; run it per host or use the ADMM mode "
            "for multi-host")
    import jax
    utils.setup_backend(args.platform, args.cpu_devices)
    if args.coordinator:
        # multi-host SPMD: every process runs this same program; jax
        # coordinates device enumeration and collectives across hosts
        # (replaces mpirun rank dispatch, src/MPI/main.cpp:311-346)
        jax.distributed.initialize(
            coordinator_address=args.coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id)
    from sagecal_tpu.diag import trace as dtrace

    if args.diag:
        dtrace.enable(args.diag, entry="sagecal-tpu-mpi",
                      argv=list(argv) if argv is not None else sys.argv[1:])
    if args.metrics:
        obs.enable()
    if args.faults:
        from sagecal_tpu import faults
        faults.enable_spec(args.faults)
    try:
        return _main_consensus(args, dtrace)
    finally:
        if args.diag:
            dtrace.disable()
        if args.metrics:
            obs.dump_to(args.metrics)


def _main_consensus(args, dtrace) -> int:
    if getattr(args, "jones", "full") != "full":
        # the polynomial consensus state (y, Bz) is parameterized in
        # full-Jones coordinates; a constrained subspace would need its
        # own consensus algebra (lm.py/rtr.py raise the same refusal)
        raise ValueError(
            f"--jones {args.jones} is not supported with consensus "
            "ADMM: the y/bz consensus vectors are full-Jones "
            "parameters. Run the fullbatch CLI (sagecal_tpu.cli) for "
            "constrained-Jones solves.")

    paths = discover_datasets(args.ms_pattern)

    if args.epochs > 0:
        # stochastic federated mode (reference main.cpp:330-342 dispatch)
        if args.uvmin > 0.0 or args.uvmax < 1e9:
            print("Warning: -x/-y uv cuts are not applied in federated "
                  "stochastic mode; calibrating all baselines",
                  file=sys.stderr)
        from sagecal_tpu import federated
        from sagecal_tpu.config import RunConfig
        cfg = RunConfig(
            ms=paths[0], sky_model=args.sky_model,
            cluster_file=args.cluster_file,
            solutions_file=args.solutions_file,
            format_3=bool(args.format),
            n_epochs=args.epochs, n_minibatches=args.minibatches,
            channel_avg_per_band=args.bands,
            n_admm=args.admm, n_poly=args.npoly, poly_type=args.polytype,
            admm_rho=args.rho, rho_file=args.rho_file,
            federated_alpha=args.federated_alpha,
            use_global_solution=bool(args.use_global_solution),
            max_timeslots=args.max_timeslots,
            skip_timeslots=args.skip_timeslots,
            max_lbfgs=args.max_lbfgs, lbfgs_m=args.lbfgs_m,
            robust_nulow=args.nulow, robust_nuhigh=args.nuhigh,
            tile_size=args.tile_size,
            input_column=args.input_column,
            output_column=args.output_column,
            verbose=args.verbose)
        federated.run_federated(cfg, paths)
        return 0

    st = ConsensusStepper(args, paths)
    if args.time_shard > 1:
        return _consensus_time_sharded(
            args, dtrace, mss=st.mss, meta0=st.meta0, freqs=st.freqs,
            sky=st.sky, dsky=st.dsky, cfg=st.cfg, Bpoly=st.Bpoly,
            rdt=st.rdt, sdt=st.sdt, cidx=st.cidx, cmask=st.cmask, n=st.n,
            t0=st.t0, start=st.start, stop=st.stop, Jinit=st.Jinit,
            res_jit=st.res_jit, writer=st.writer,
            worker_writers=st.worker_writers, is_writer=st.is_writer,
            prep_tiles=st._prep_tiles)

    # overlapped execution (sagecal_tpu.sched): all subbands of interval
    # t+N are read and staged on a background thread while interval t
    # solves, and residual/solution writes drain on the stepper's
    # ordered writer thread; --prefetch 0 is the synchronous escape
    # hatch. Bit-identical: the warm-start chain (J0 carry) stays
    # sequential in step(), only data movement overlaps.
    from sagecal_tpu import sched

    def produce(i):
        tiles = st.read(i)
        return tiles, st.stage(i, tiles)

    source = sched.Prefetcher(produce, st.n_intervals, depth=st.depth,
                              tile0=st.start)
    try:
        for i, (tiles, staged), io_wait in source:
            st.step(st.start + i, tiles, staged, io_wait)
    finally:
        # a mid-loop failure (solver error, reader-thread or async
        # writer exception) must still cancel the prefetch thread and
        # drain/raise the ordered write queue — otherwise completed
        # intervals' queued writes are silently dropped, diverging
        # from the --prefetch 0 inline-write behavior
        source.close()
        st.close()
    return 0


def sage_config(args):
    """The J update's solver settings as the parsed arguments give them
    (``tests/test_chip_compile.py`` compiles the mesh program from the
    same)."""
    from sagecal_tpu.solvers import sage
    return sage.SageConfig(
        max_emiter=args.max_em_iter, max_iter=args.max_iter,
        max_lbfgs=args.max_lbfgs, lbfgs_m=args.lbfgs_m,
        solver_mode=int(SolverMode(args.solver_mode)),
        nulow=args.nulow, nuhigh=args.nuhigh,
        randomize=bool(args.randomize),
        inflight=args.inflight, inner=args.inner,
        dtype_policy=getattr(args, "dtype_policy", "f32"))


class ConsensusStepper:
    """The consensus interval loop behind a seam a driver can step: the
    ``cli_mpi`` twin of ``pipeline.TileStepper``.

    Built from parsed ``cli_mpi`` arguments (set-up: datasets, sky,
    mesh, the ADMM runner of the chosen execution plan, the residual
    program, the solution files). ``read(i)`` and ``stage(i, tiles)``
    may run on a background reader thread (``sched.Prefetcher``);
    ``step(ti, tiles, staged, io_wait)`` runs on the device-owner
    thread, strictly in interval order: runner, fetch, divergence
    reset, warm-start carry, residual program, and the interval's
    ordered writes (per-subband solutions, residual tiles, global Z)
    submitted to the stepper's ``AsyncWriter``; ``close()`` drains the
    writer, banks the prior and closes the solution files. All mutable
    solve state (the warm-start chain ``J0``, the writer) lives here,
    so ``main()`` and any other driver run ONE loop.

    ``i`` counts the selected intervals from 0 (``n_intervals`` of
    them); ``ti = start + i`` is the dataset's tile number (``-K``).
    ``--time-shard`` builds no runner: that plan has its own driver and
    takes only the set-up from here.
    """

    def __init__(self, args, paths=None, log=print):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from sagecal_tpu import dtypes as dtp, sched
        from sagecal_tpu.consensus import admm as cadmm
        from sagecal_tpu.consensus import poly as cpoly
        from sagecal_tpu.diag import trace as dtrace
        from sagecal_tpu.io import dataset as ds, solutions as sol
        from sagecal_tpu.rime import predict as rp
        from sagecal_tpu.rime import residual as rr
        from sagecal_tpu.solvers import sage

        self.args, self.log = args, log
        if paths is None:
            paths = discover_datasets(args.ms_pattern)
        # each subband path may be a SimMS directory or a real CASA table
        mss = [ds.open_part(p, tilesz=args.tile_size,
                            data_column=args.input_column,
                            out_column=args.output_column) for p in paths]
        nf = len(mss)
        meta0 = mss[0].meta
        # metadata consistency check (master :239-284)
        for msx in mss[1:]:
            if len(msx.meta["freqs"]) != len(meta0["freqs"]):
                raise ValueError(
                    f"dataset {msx.path}: channel count mismatch "
                    f'({len(msx.meta["freqs"])} vs {len(meta0["freqs"])}) '
                    "— the mesh program needs a uniform channel count per "
                    "subband")
            for key in ("n_stations", "nbase", "tilesz"):
                if msx.meta[key] != meta0[key]:
                    raise ValueError(
                        f"dataset {msx.path}: {key} mismatch "
                        f"({msx.meta[key]} != {meta0[key]})")
        freqs = np.array([m.meta["freq0"] for m in mss])
        order = np.argsort(freqs)
        mss = [mss[i] for i in order]
        freqs = freqs[order]
        self.mss, self.nf, self.meta0, self.freqs = mss, nf, meta0, freqs

        platform = jax.devices()[0].platform
        rdt = jnp.float64 if (
            platform == "cpu"
            and jax.config.read("jax_enable_x64")) else jnp.float32
        # --dtype-policy storage dtype for staged visibilities/weights and
        # the residual readback (sagecal_tpu.dtypes; "f32" -> sdt == rdt)
        if (getattr(args, "dtype_policy", "f32") != "f32"
                and rdt == jnp.float64):
            # reduced policies pair with the f32/c64 pipeline (accumulator
            # contract is f32; see pipeline.py)
            rdt = jnp.float32
        sdt = dtp.storage_dtype(getattr(args, "dtype_policy", "f32"), rdt)
        self.rdt, self.sdt = rdt, sdt

        sky = skymodel.read_sky_cluster(
            args.sky_model, args.cluster_file, meta0["ra0"], meta0["dec0"],
            float(freqs.mean()), bool(args.format))
        dsky = rp.sky_to_device(sky, rdt)
        self.sky, self.dsky = sky, dsky
        dobeam = self.dobeam = int(args.beam)
        beams_static = None
        if dobeam:
            from sagecal_tpu.rime import beam as bm
            beams_static = [
                bm.beam_to_device(bm.resolve_beaminfo(dobeam, m, m.meta),
                                  m.meta["freq0"], rdt)
                for m in mss]
        n = self.n = meta0["n_stations"]
        kmax = self.kmax = int(sky.nchunk.max())
        cmask = self.cmask = (np.arange(kmax)[None, :]
                              < sky.nchunk[:, None])
        cidx = self.cidx = rp.chunk_indices(
            meta0["tilesz"], meta0["nbase"], sky.nchunk)

        # mesh: use ALL devices up to Nf; when Nf doesn't divide (or, multi-
        # host, when Nf < the global device count), pad the subband axis to
        # Fl*ndev with masked zero-weight slots (admm.pad_subbands) instead
        # of shrinking the mesh to a divisor. Multi-host: never slice the
        # device list below a process boundary — every process must own mesh
        # devices or the SPMD programs desynchronize.
        multihost = self.multihost = args.num_processes > 1
        ndev_avail = len(jax.devices())
        if args.mesh_devices and not multihost:
            # --mesh-devices: never slice below a process boundary, so the
            # cap is single-process only (multi-host meshes must span all
            # processes' devices or the SPMD programs desynchronize)
            ndev_avail = min(ndev_avail, max(1, args.mesh_devices))
        ndev = ndev_avail if multihost else min(ndev_avail, nf)
        if args.staleness > 0 and not multihost:
            # bounded-staleness consensus is the single-device host-driven
            # plan (per-subband executions it can actually skip) — fold all
            # subbands onto one device regardless of what is visible
            ndev = 1
        self.ndev = ndev
        fpad = self.fpad = -(-max(nf, ndev) // ndev) * ndev
        mesh = self.mesh = Mesh(np.array(jax.devices()[:ndev]), ("freq",))
        # running as a serve job: surface the mesh's device span to the
        # fleet view (no-op outside a job scope — solo CLI runs)
        from sagecal_tpu.serve import fleet as _fleet
        _fleet.note_mesh(mesh)
        # mpirun-analogue output ownership
        is_writer = self.is_writer = args.process_id == 0
        if is_writer:
            log(utils.platform_line(ndev_avail))
            log(f"Subbands: {nf} over {ndev} device(s)"
                + (f" (padded to {fpad})" if fpad != nf else "")
                + f"; stations {n}, clusters {sky.n_clusters} "
                f"(Mt={sky.n_eff_clusters})")

        # --prior-cache read/readwrite: seed this run from the solution
        # prior store (serve/priors.py, family "admm"). All-or-nothing
        # across subbands — any band refusing (station-set/cluster
        # mismatch) cold-starts EVERY band, a prior never partially seeds.
        # An explicit -q solution file or -G rho file always wins.
        self.prior_mode = getattr(args, "prior_cache", "off")
        self.prior_k = None
        prior_J0 = None
        prior_rho = None
        if self.prior_mode != "off":
            self.prior_k = ppriors.prior_key(
                args.sky_model, args.cluster_file, n, float(freqs.mean()),
                "admm")
        if ppriors.reads(self.prior_mode) and not args.init_solutions:
            span = float(meta0["tilesz"]) * float(meta0["tdelta"])
            pt = (float(args.skip_timeslots)
                  + (np.arange(kmax) + 0.5) / kmax) * span
            seeds = []
            for f in range(nf):
                Jf, rho_p = ppriors.PRIORS.seed(
                    self.prior_k, pt, float(freqs[f]), n, sky.n_clusters)
                if Jf is None:
                    seeds = []
                    prior_rho = None
                    break
                seeds.append(Jf)
                if prior_rho is None:
                    prior_rho = rho_p
            if seeds:
                prior_J0 = np.stack(seeds)   # [nf, M, kmax, n, 2, 2]
                if is_writer:
                    log(f"prior-cache: J0 seeded for {nf} subband(s) "
                        "from the solution prior store")

        rho0 = args.rho
        if args.rho_file:
            # per-cluster regularization (readsky.c:780): passed through as an
            # [M] array; admm.py broadcasts it per subband
            rho0 = skymodel.read_cluster_rho(args.rho_file, sky.cluster_ids,
                                             default_rho=args.rho)
        elif prior_rho is not None:
            # banked per-cluster consensus rho seeds the schedule (the
            # previous run's converged regularization beats the scalar -r
            # default; -G stays authoritative when given)
            rho0 = prior_rho
        self.rho0 = rho0

        Bpoly = self.Bpoly = cpoly.setup_polynomials(
            freqs, float(freqs.mean()), args.npoly, args.polytype)
        # padded basis for the mesh program; Bpoly keeps the real rows for
        # host-side uses (use_global_solution, solution writing)
        _, Bpoly_pad, _ = cadmm.pad_subbands([], Bpoly, nf, ndev)
        spatialreg = None
        spatial_coords = None
        if args.spatialreg:
            from sagecal_tpu.consensus import spatial as csp
            vals = [float(x) for x in args.spatialreg.split(",")]
            if len(vals) != 5:
                raise ValueError("-X needs l2,l1,order,fista_iters,cadence")
            if args.federated_alpha <= 0.0:
                raise ValueError(
                    "-X spatial regularization couples into the consensus Z "
                    "only through the -u prior strength; give -u > 0 "
                    "(master :768-775 adds alpha*Zbar - X to the Z update)")
            spatialreg = (vals[0], vals[1], int(vals[2]), int(vals[3]),
                          max(int(vals[4]), 1))
            spatial_coords = csp.cluster_polar_coords(sky)
        self.spatialreg = spatialreg
        cfg = self.cfg = cadmm.ADMMConfig(
            n_admm=args.admm, npoly=args.npoly, poly_type=args.polytype,
            rho=rho0, adaptive_rho=bool(args.adaptive_rho),
            spatialreg=spatialreg, federated_alpha=args.federated_alpha,
            sage=sage_config(args))

        t0 = self.t0 = mss[0].read_tile(0)
        # host values for the interval's tile record: the row layouts
        # the J updates carry their running residual on and assemble
        # their Gauss-Newton matrix from
        cfg_rows = cfg.sage._replace(nbase=int(meta0["nbase"]))
        self.sweep_rows = sage.sweep_rows(cfg_rows, len(t0.sta1))
        self.assemble_rows = sage.assemble_rows(cfg_rows, len(t0.sta1))
        plans = [nm for nm, on in (("--block-f", args.block_f),
                                   ("--host-loop", args.host_loop),
                                   ("--time-shard", args.time_shard > 1),
                                   ("--staleness", args.staleness > 0))
                 if on]
        if len(plans) > 1:
            raise ValueError(f"{' and '.join(plans)} are different "
                             "execution plans; pick one")
        self.blk_timer = [] if args.block_f else None
        # what the interval's tile record says of the execution plan, and
        # how many subbands share one execution of a J update (the fold)
        self.plan = ("stale" if args.staleness > 0
                     else "blocked" if args.block_f
                     else "host-loop" if args.host_loop else "traced")
        self.fold = (1 if args.staleness > 0
                     else min(args.block_f, fpad) if args.block_f
                     else fpad // ndev)
        if args.time_shard == 1:
            raise ValueError("--time-shard 1 is ambiguous: use 0 (off, "
                             "the per-interval loop) or >= 2 time-mesh "
                             "devices")
        if args.time_shard > 1:
            # 2-D ('freq', 'time') mesh: handled by its own driver
            # (_consensus_time_sharded) — the whole selected observation
            # is one SPMD program, so the per-interval loop never runs
            if multihost:
                raise ValueError("--time-shard stages the whole "
                                 "observation from one host; it cannot "
                                 "run multi-host yet (the mesh would span "
                                 "non-addressable devices)")
            if dobeam:
                raise ValueError("--time-shard does not support -B beam "
                                 "tables yet; use the per-interval loop")
            if args.spatialreg:
                raise ValueError("--time-shard does not support -X spatial "
                                 "regularization; use the mesh runner")
            if args.mdl:
                raise ValueError("--time-shard does not support --mdl")
            self.runner = None
        elif args.staleness > 0:
            if multihost:
                raise ValueError("--staleness is a single-device host-"
                                 "driven plan; it cannot run multi-host "
                                 "(every process would redundantly drive "
                                 "the same chain)")
            if dobeam:
                raise ValueError("--staleness does not support -B beam "
                                 "tables")
            self.runner = cadmm.make_admm_runner_stale(
                dsky, t0.sta1, t0.sta2, cidx, cmask, n, meta0["fdelta"],
                Bpoly_pad, cfg, nf, staleness=args.staleness,
                nbase=meta0["nbase"])
        elif args.block_f:
            if args.block_f < 1:
                raise ValueError(f"--block-f {args.block_f}: must be >= 1")
            if ndev != 1:
                raise ValueError("--block-f is the single-device execution "
                                 "plan; it needs a 1-device mesh")
            self.runner = cadmm.make_admm_runner_blocked(
                dsky, t0.sta1, t0.sta2, cidx, cmask, n, meta0["fdelta"],
                Bpoly_pad, cfg, nf, block_f=args.block_f,
                dobeam=dobeam, nbase=meta0["nbase"], timer=self.blk_timer)
        else:
            self.runner = cadmm.make_admm_runner(
                dsky, t0.sta1, t0.sta2, cidx, cmask, n, meta0["fdelta"],
                Bpoly_pad, cfg, mesh, nf, spatial_coords=spatial_coords,
                host_loop=args.host_loop,
                dobeam=dobeam, nbase=meta0["nbase"])

        # residual program (per subband, local J); -k correction uses the
        # subband's own solutions (sagecal_slave.cpp residual path)
        correct_idx = skymodel.correct_cluster_index(
            sky, args.correct_cluster)

        tslot_rows = jnp.asarray(t0.tslot)

        def residual_fn(J_r8, x_r, u, v, w, freq, *beam_rest):
            # storage-dtype writeback emission (out_dtype): the d->h
            # readback ships sdt bytes; identity at "f32"
            return rr.calculate_residuals_pairs(
                dsky, J_r8, x_r, u, v, w, freq[None],
                meta0["fdelta"], jnp.asarray(t0.sta1), jnp.asarray(t0.sta2),
                jnp.asarray(cidx), jnp.asarray(sky.subtract_mask()),
                out_dtype=sdt, correct_idx=correct_idx,
                rho=args.mmse_rho, phase_only=bool(args.phase_only),
                beam=beam_rest[0] if beam_rest else None, dobeam=dobeam,
                tslot=tslot_rows, row_period=int(meta0["nbase"]))

        # constructed exactly once per stepper
        self.res_jit = jax.jit(jax.vmap(residual_fn))
        # its one input that no interval changes
        self._freqs_dev = jnp.asarray(freqs, rdt)

        self.writer = None
        if args.solutions_file and is_writer:
            self.writer = sol.SolutionWriter(
                args.solutions_file, float(freqs.mean()),
                float(freqs.max() - freqs.min()),
                meta0["tilesz"] * meta0["tdelta"] / 60.0, n, sky.n_clusters,
                sky.n_eff_clusters * args.npoly)

        self._sh = NamedSharding(mesh, P("freq"))

        # ragged real-MS subbands (a lost trailing scan) truncate to the
        # common prefix, like the federated path
        n_tiles = min(m.n_tiles for m in mss)
        if is_writer and any(m.n_tiles != n_tiles for m in mss):
            log(f"Warning: subband tile counts differ; calibrating the "
                f"common {n_tiles} tiles")
        self.start = args.skip_timeslots
        self.stop = n_tiles if not args.max_timeslots else min(
            n_tiles, self.start + args.max_timeslots)

        Jinit = utils.jones_c2r_np(np.tile(
            np.eye(2, dtype=complex), (nf, sky.n_clusters, kmax, n, 1, 1)))
        if args.init_solutions:
            # -q: warm-start every subband from one interval of J solutions
            # (MPI/main.cpp -q; J format, not the Z/polynomial output file)
            Jq = sol.read_warm_start(args.init_solutions, sky, n)
            if Jq is not None:
                Jinit = np.tile(utils.jones_c2r_np(np.asarray(Jq))[None],
                                (nf, 1, 1, 1, 1))
        self.Jinit = Jinit
        self.J0 = Jinit.copy()
        if prior_J0 is not None:
            # prior-cache warm chain start. Jinit stays the cold identity:
            # the per-subband divergence reset in step() still recovers
            # to the reference cold start, so a bad prior costs one reset,
            # never the run (same contract as pipeline.TileStepper).
            self.J0 = utils.jones_c2r_np(prior_J0)

        # spatial-model solution file ("spatial_"+solfile,
        # sagecal_master.cpp:472-498): header + two centroid-coordinate
        # rows, then per interval the global SH coefficient matrix Zspat
        # recomputed host-side from the final consensus Z (spatial_step's
        # FISTA is a pure function of Z, so no extra runner state).
        self.spatial_file = None
        if spatialreg is not None and args.solutions_file and is_writer:
            import os as _os
            d, b = _os.path.split(args.solutions_file)
            self.spatial_file = open(_os.path.join(d, "spatial_" + b), "w")
            G_sp = int(spatialreg[2]) ** 2
            rr_c, tt_c = spatial_coords
            self.spatial_file.write(
                "# spatial regularization solution file (Zspat)\n"
                "# Top two rows are the polar coordinates of the "
                "centroids (rad)\n"
                "# reference_freq(MHz) polynomial_order(freq) "
                "polynomial_order(spatial) stations clusters "
                "effective_clusters\n")
            self.spatial_file.write(
                f"{float(freqs.mean()) * 1e-6:f} {args.npoly} {G_sp} {n} "
                f"{sky.n_clusters} {sky.n_eff_clusters}\n")
            self.spatial_file.write(
                " ".join(f"{x:f}" for x in np.asarray(rr_c)) + "\n")
            self.spatial_file.write(
                " ".join(f"{x:f}" for x in np.asarray(tt_c)) + "\n")

        self._spatial_phi = None
        if self.spatial_file is not None:
            from sagecal_tpu.consensus import spatial as sp
            # loop-invariant basis: built once, kept for the writer
            self._spatial_phi = sp.phi_padded(cmask, *spatial_coords,
                                              spatialreg[2], spatialreg[0])

        # -B beam: the element/array-factor tables are tile-invariant, so
        # the static leaves are stacked + staged ONCE here; per interval
        # only the [tilesz] gmst time track is restaged (round-5
        # ADVICE: the old loop re-transferred every leaf each interval).
        # The diag stage_bytes records quantify the saving per tile.
        self._beamF_static = None
        self._beam_static_dev = None
        if dobeam:
            self._beamF_static = jax.tree.map(
                lambda *xs: np.stack([np.asarray(x) for x in xs]),
                *beams_static)
            beamF_pad = self._beamF_static
            if fpad > nf:       # padded mesh slots reuse subband 0's beam
                beamF_pad = jax.tree.map(lambda a: np.concatenate(
                    [a, np.repeat(a[:1], fpad - nf, axis=0)]),
                    self._beamF_static)
            self._beam_static_dev = jax.tree.map(self._to_device, beamF_pad)
            dtrace.emit("stage_bytes", what="beam_static",
                        bytes=int(sum(np.asarray(l).nbytes
                                      for l in jax.tree.leaves(beamF_pad))))

        # per-subband worker files, written unconditionally like the
        # reference slaves ("always create default solution file name
        # MS+'.solutions'", sagecal_slave.cpp:167-168). Opened only AFTER
        # -q is read: a previous run's worker file is a valid warm-start
        # source and must not be truncated before read_warm_start sees it.
        # Multi-host note: unlike the reference's per-node slave writes,
        # ONLY process 0 writes these files (shared-filesystem assumption;
        # see MIGRATION.md "per-subband worker files").
        self.worker_writers = []
        if is_writer:
            interval_min = meta0["tilesz"] * meta0["tdelta"] / 60.0
            self.worker_writers = [
                sol.SolutionWriter(
                    m.path.rstrip("/") + ".solutions",
                    float(m.meta["freq0"]), float(m.meta["fdelta"]),
                    interval_min, n, sky.n_clusters, sky.n_eff_clusters)
                for m in mss]

        # --prefetch N: read/stage depth of the caller's Prefetcher and
        # whether the ordered writer runs on its own thread; 0 = the
        # synchronous escape hatch (writes inline at their site)
        self.depth = max(0, int(getattr(args, "prefetch", 1)))
        self.aw = sched.AsyncWriter(enabled=self.depth > 0)
        self.history = []       # per interval: res_0, res_1, primal, dual
        self._rhoF = None       # the last interval's [F, M] rho schedule
        self._last = self.start - 1

    @property
    def n_intervals(self) -> int:
        return max(0, self.stop - self.start)

    # -- host <-> device ------------------------------------------------------

    def _to_device(self, a):
        """Host [Fpad, ...] -> sharded device array. Single process:
        device_put; multi-host: every process holds the full host array
        and each device picks out its shard via the callback (the
        multi-host-safe staging path)."""
        import jax
        if self.multihost:
            return jax.make_array_from_callback(
                a.shape, self._sh, lambda idx: a[idx])
        return jax.device_put(a, self._sh)

    def _fetch(self, a):
        """Device -> host numpy. Multi-host: runner outputs span
        non-addressable devices, so gather them to every process first
        (the master's Y-gather analogue, over ICI/DCN instead of MPI)."""
        if self.multihost:
            from jax.experimental import multihost_utils
            return np.asarray(
                multihost_utils.process_allgather(a, tiled=True))
        return np.asarray(a)

    def _write_spatial_model(self, Z_np):
        """One interval's Zspat rows — DELIBERATE format deviation from
        the reference (see MIGRATION.md "spatial_ solution files"):
        the reference (master :986-994) dumps the complex Zspat buffer
        column-major as N*8*Npoly rows of G raw doubles with centroid
        rows in REVERSE cluster order; here each of the 2*Npoly*N rows
        carries its row index then 2G re/im pairs in FORWARD cluster
        order — self-describing text instead of a memory-layout dump.
        tests/test_aux.py::test_admm_spatialreg_runs pins this format."""
        import jax.numpy as jnp
        from sagecal_tpu.consensus import spatial as sp
        _l2, sh_mu, _n0, fista_iters, _cad = self.spatialreg
        Phi, Phikk = self._spatial_phi
        Zb = sp.z_r8_to_blocks(jnp.asarray(Z_np)).astype(jnp.complex64)
        Zspat = np.asarray(sp.fista_spatialreg(
            Zb, jnp.asarray(Phikk, jnp.complex64),
            jnp.asarray(Phi, jnp.complex64), sh_mu, int(fista_iters)))
        for p in range(Zspat.shape[0]):
            self.spatial_file.write(
                f"{p} " + " ".join(f"{z.real:e} {z.imag:e}"
                                   for z in Zspat[p]) + "\n")

    # -- reader-thread half ---------------------------------------------------

    def _prep_tiles(self, tiles):
        """One interval's solve inputs from its subband tiles: the
        shared staging decision (VisTile.solve_input — per-channel
        packing when cflags exist, plain mean else), solve-scoped
        uv-cut flags (predict.c:876 rule; originals restored before
        write-back), optional -W whitening, and the per-subband
        unflagged fraction that scales rho (master :646-650). Numpy
        throughout but under -W: the uv cut and the weights run nothing
        on a device and read nothing back, so the reader's thread never
        stands behind the solve that is running."""
        from sagecal_tpu.diag import trace as dtrace
        from sagecal_tpu.rime import predict as rp
        from sagecal_tpu.solvers import lm as lm_mod
        args, rdt = self.args, self.rdt
        x8_l, wt_l, fr_l = [], [], []
        uvcut_on = args.uvmin > 0.0 or args.uvmax < 1e9
        orig_flags = [t.flags for t in tiles]
        with dtrace.phase("pack"):
            for t in tiles:
                if uvcut_on:
                    t.flags = rp.apply_uvcut(t.flags, t, args.uvmin,
                                             args.uvmax)
                x8_t, flags_t, good = t.solve_input()
                fr_l.append(good)
                x8_l.append(x8_t)
                wt_l.append(lm_mod.make_weights_np(flags_t, np.dtype(rdt)))
            if uvcut_on:
                for t, fl in zip(tiles, orig_flags):
                    t.flags = fl
        if args.whiten:
            # -W alone runs on a device: beside "pack", not under it
            import jax.numpy as jnp
            from sagecal_tpu.solvers import robust as rb
            for k, t in enumerate(tiles):
                with dtrace.phase("copy"):
                    x8_d, u_d, v_d = (jnp.asarray(x8_l[k], rdt),
                                      jnp.asarray(t.u, rdt),
                                      jnp.asarray(t.v, rdt))
                x8_d = rb.whiten_data(x8_d, u_d, v_d, t.freq0)
                with dtrace.phase("wait"):
                    x8_l[k] = np.asarray(x8_d)
        with dtrace.phase("pack"):
            return (np.stack(x8_l), np.stack([t.u for t in tiles]),
                    np.stack([t.v for t in tiles]),
                    np.stack([t.w for t in tiles]), np.stack(wt_l),
                    np.array(fr_l))

    def read(self, i):
        """All subbands' tiles of selected interval ``i``."""
        return [m.read_tile(self.start + i) for m in self.mss]

    def stage(self, i, tiles):
        """Interval ``i``'s inputs on the devices: the solve's
        (``_prep_tiles``, ``pad_subbands``, host to device) and those of
        the residual that do not wait for the solve (the data column and
        the geometry of the unpadded subbands). Everything but the
        warm-start J0 and the solved Jones, which are ``step``'s to
        carry. Copies only: no device execution, no read-back."""
        import jax.numpy as jnp
        from sagecal_tpu.consensus import admm as cadmm
        from sagecal_tpu.diag import trace as dtrace
        ti = self.start + i
        nf, rdt, sdt = self.nf, self.rdt, self.sdt
        with dtrace.phase("stage", tile=ti, bg=self.depth > 0):
            x8F, uF, vF, wF, wtF, fratioF = self._prep_tiles(tiles)
            with dtrace.phase("pack"):
                padded, _, _ = cadmm.pad_subbands(
                    (x8F, uF, vF, wF, self.freqs, wtF, fratioF),
                    self.Bpoly, nf, self.ndev)
                # dtype policy: visibilities + weights stage in the
                # storage dtype; geometry/frequencies keep the pipeline
                # dtype
                pdts = (sdt, rdt, rdt, rdt, rdt, sdt, rdt)
                padded = [np.asarray(a, np.dtype(d))
                          for a, d in zip(padded, pdts)]
                nbytes = int(sum(a.nbytes for a in padded))
                xF_r = None
                if self.is_writer:
                    xF_r = np.stack([utils.c2r(t.x) for t in tiles])
            with dtrace.phase("copy"):
                args_dev = [self._to_device(a) for a in padded]
                res_dev = None
                if xF_r is not None:
                    res_dev = [jnp.asarray(xF_r, sdt),
                               jnp.asarray(uF, rdt), jnp.asarray(vF, rdt),
                               jnp.asarray(wF, rdt)]
            gmstF = beam_dev = None
            if self.dobeam:
                # only the per-tile gmst time track crosses host->device
                # here; the static tables were staged once at set-up
                from sagecal_tpu import coords as _coords
                with dtrace.phase("beam"):
                    gmstF = np.stack(
                        [np.asarray(_coords.jd2gmst_np(t.time_jd))
                         for t in tiles]).astype(np.dtype(rdt))
                    if self.fpad > nf:  # padded mesh slots reuse subband 0's
                        gmstF = np.concatenate(
                            [gmstF, np.repeat(gmstF[:1], self.fpad - nf,
                                              axis=0)])
                    beam_dev = self._beam_static_dev._replace(
                        gmst=self._to_device(gmstF))
        return dict(args_dev=args_dev, nbytes=nbytes, res_dev=res_dev,
                    fratio=fratioF, gmst=gmstF, beam=beam_dev)

    # -- device-owner half ----------------------------------------------------

    def step(self, ti, tiles, staged, io_wait=0.0):
        """Solve interval ``ti`` (dataset tile number), carry the warm
        start, submit its writes in order. Returns the interval's
        history record."""
        from sagecal_tpu.diag import trace as dtrace
        # the root span of an interval's cycle on this thread: with the
        # consumer's "io" it covers the cycle (diag/trace.py)
        with dtrace.phase("step", tile=ti):
            return self._step(ti, tiles, staged, io_wait)

    def _step(self, ti, tiles, staged, io_wait):
        import jax
        import jax.numpy as jnp
        from sagecal_tpu import sched
        from sagecal_tpu.consensus import admm as cadmm
        from sagecal_tpu.diag import trace as dtrace
        args, cfg, sky, log = self.args, self.cfg, self.sky, self.log
        nf, n, kmax, rdt, sdt = self.nf, self.n, self.kmax, self.rdt, self.sdt
        Bpoly, freqs, is_writer = self.Bpoly, self.freqs, self.is_writer
        aw = self.aw
        aw.check()      # async write failure -> fail at this boundary
        bubble = io_wait
        fratioF = staged["fratio"]

        # the warm-start chain is this thread's: J0 is staged here, the
        # interval's data came staged from the reader
        with dtrace.phase("carry"):
            (J0p,), _, _ = cadmm.pad_subbands((self.J0,), Bpoly, nf,
                                              self.ndev)
            args_dev = list(staged["args_dev"]) + [self._to_device(
                np.asarray(J0p, np.dtype(rdt)))]
        if dtrace.active():
            dtrace.emit("stage_bytes", what="tile_inputs", tile=ti,
                        bytes=staged["nbytes"] + int(
                            np.asarray(J0p).size * np.dtype(rdt).itemsize))
        gmstF = staged["gmst"]
        if self.dobeam:
            args_dev.append(staged["beam"])
            dtrace.emit("stage_bytes", what="beam_gmst", tile=ti,
                        bytes=int(gmstF.nbytes))
        blk_timer = self.blk_timer
        if blk_timer is not None:
            blk_timer.clear()
        with dtrace.phase("solve", tile=ti):
            with dtrace.phase("dispatch", prog="admm"):
                (JF_r8, Z, rhoF, res0, res1, r1s, duals, Y0F,
                 trips) = self.runner(*args_dev)
            # the traced plan is ONE device execution per interval:
            # while tracing, time it to its end (the fetch below would
            # block on it anyway)
            sched.wait_device(JF_r8)
        self._rhoF = rhoF
        if (ti == self.start and is_writer
                and hasattr(JF_r8, "addressable_shards")):
            # where the subband shards of the solve's output live
            log("Shard devices: " + " ".join(sorted(
                {str(s.device) for s in JF_r8.addressable_shards})))
        if blk_timer is not None and is_writer:
            # per-ADMM-iteration wall-clock from the blocked runner's
            # per-execution telemetry (solve blocks + consensus); the
            # first tile's numbers include compilation
            nblk = -(-self.fpad // args.block_f)
            times = [t for _, t in blk_timer]
            per_iter = [sum(times[i * (nblk + 1):(i + 1) * (nblk + 1)])
                        for i in range(cfg.n_admm)]
            log("ADMM wall-clock/iter: "
                + " ".join(f"{t:.2f}s" for t in per_iter)
                + f" (blocks of {args.block_f} subbands, "
                f"{nblk} solve executions + 1 consensus each)")
        with dtrace.phase("fetch", tile=ti):
            outs = (JF_r8, Z, res0, res1, r1s, duals, Y0F, trips)
            # one process: every copy is started before the first is
            # waited for, where eight np.asarray were eight round trips
            JF_r8, Z, res0, res1, r1s, duals, Y0F, trips = (
                [self._fetch(a) for a in outs] if self.multihost
                else jax.device_get(outs))
            # slice padded subband rows off every per-subband output
            # (trips stays [n_admm, Fpad, 2], a few i32)
            JF_r8, res0, res1 = JF_r8[:nf], res0[:nf], res1[:nf]
            r1s, Y0F = r1s[:, :nf], Y0F[:nf]
        JF_r8_5 = np.asarray(JF_r8).reshape(nf, sky.n_clusters, kmax, n, 8)
        if self.worker_writers:
            J_all = utils.jones_r2c_np(JF_r8_5)

            def _write_workers(ti=ti, J_all=J_all):
                with dtrace.phase("solutions", tile=ti):
                    for f, ww in enumerate(self.worker_writers):
                        ww.write_interval(J_all[f], sky.nchunk)
            bubble += aw.submit(_write_workers)

        if args.mdl and ti == self.start and is_writer:
            # model-order report from iteration-0 rho*J (master
            # :815-822)
            from sagecal_tpu.consensus import mdl as mdlmod
            with dtrace.phase("primal"):
                res = mdlmod.minimum_description_length(
                    np.asarray(Y0F), np.broadcast_to(
                        np.asarray(self.rho0, float), (sky.n_clusters,)),
                    freqs, float(freqs.mean()), weight=fratioF,
                    polytype=args.polytype, kstart=1, kfinish=args.npoly)
                mdlmod.report(res)

        res0 = np.asarray(res0)
        res1_it0 = np.asarray(res1)     # iteration 0's plain solve
        res1 = np.asarray(r1s)[-1] if cfg.n_admm > 1 else res1_it0
        duals = np.asarray(duals)

        if ((dtrace.active() or obs.active()) and not args.host_loop
                and not args.block_f and not args.staleness):
            # per-ADMM-iteration convergence records from the fetched
            # telemetry. The host-loop, blocked and stale runners
            # already emit live per-iteration records (admm.py feeds
            # BOTH the trace and the obs gauges there), so only the
            # fully traced mesh program needs the post-hoc emission:
            # one record an ADMM iteration, iteration 0 (the plain
            # solve, no dual yet) included, as the host loop's
            with dtrace.phase("record"):
                r1_it = [res1_it0] + list(np.asarray(r1s))
                for k, r1k in enumerate(r1_it):
                    r1m = float(r1k.mean())
                    du = float(duals[k - 1]) if k else 0.0
                    dtrace.emit("admm_iter", interval=ti, iter=k,
                                r1_mean=r1m, dual=du)
                    if obs.active():
                        obs.inc("admm_iterations_total")
                        obs.set_gauge("admm_primal_residual", r1m)
                        obs.set_gauge("admm_dual_residual", du)

        # warm-start the next interval; per-subband divergence reset
        # (slave :680-683 res_ratio check; fullbatch warm-start analogue)
        J_new = np.asarray(JF_r8)
        bad = (~np.isfinite(res1)) | (res1 == 0.0) | (res1 > 5.0 * res0)
        for f in range(nf):
            self.J0[f] = self.Jinit[f] if bad[f] else J_new[f]
            if bad[f] and is_writer:
                log(f"  subband {f}: diverged; Resetting Solution")
        if is_writer:
            with dtrace.phase("record"):
                log(f"Timeslot:{ti} ADMM:{cfg.n_admm} residual "
                    f"initial={res0.mean():.6g} final={res1.mean():.6g} "
                    f"dual={duals[-1] if len(duals) else 0:.3g}")
                if args.verbose:
                    for f in range(nf):
                        log(f"  subband {f}: {res0[f]:.6g} -> "
                            f"{res1[f]:.6g}")

        # residuals + write back (slave :832-869); multi-host: process 0
        # owns all outputs (shared-filesystem assumption, like the
        # reference's slaves-glob-the-same-paths setup)
        BZf = None
        if is_writer:
            if args.use_global_solution:
                # evaluate BZ at each subband: smooth consensus solutions
                BZf = np.einsum("fp,mpknr->fmknr", Bpoly, np.asarray(Z))
                J_res = BZf.reshape(nf, sky.n_clusters, kmax, n, 8)
            else:
                J_res = JF_r8_5
            with dtrace.phase("residual", tile=ti):
                # host to device: the solved Jones alone, the rest came
                # staged with the interval
                with dtrace.phase("carry"):
                    rargs = [jnp.asarray(J_res, rdt), *staged["res_dev"],
                             self._freqs_dev]
                    if self.dobeam:
                        # residual beam: the UNPADDED nf subbands with
                        # this tile's gmst track
                        rargs.append(jax.tree.map(
                            lambda a: jnp.asarray(a),
                            self._beamF_static._replace(gmst=gmstF[:nf])))
                with dtrace.phase("dispatch", prog="residual"):
                    res_r = self.res_jit(*rargs)
            mss, bg = self.mss, self.depth > 0

            def _write_res(ti=ti, tiles=tiles, res_r=res_r):
                with dtrace.phase("write", tile=ti, bg=bg):
                    # blocked on the residual program's execution;
                    # the copy and the disk are write's own
                    sched.wait_device(res_r)
                    with dtrace.phase("convert"):
                        # fetch through float64 (numpy-side r2c has no
                        # ml_dtypes bf16 path; the MS is complex128)
                        res_np = utils.r2c(np.asarray(
                            res_r, np.float64)).astype(np.complex128)
                    for f, (msx, t) in enumerate(zip(mss, tiles)):
                        t.x = res_np[f]
                        with dtrace.phase("put", sub=f):
                            msx.write_tile(ti, t)
            # non-blocking d->h copy now; fetch + per-subband write on
            # the ordered writer thread
            sched.start_host_copy(res_r)
            bubble += aw.submit(_write_res)

        # numbers the history and the tile record hold and no log line
        # prints: made here, while the devices run the residual program,
        # and not between the solve's end and its dispatch
        with dtrace.phase("primal"):
            # the consensus primal residual ||J - BZ|| (the reference
            # master's convergence axis)
            if BZf is None:
                BZf = np.einsum("fp,mpknr->fmknr", Bpoly, np.asarray(Z))
            primal = float(np.linalg.norm(JF_r8_5 - BZf)
                           / np.sqrt(BZf.size))
            useful, lockstep_pct = cadmm.lockstep(trips, nf, self.fold)
        rec = {"tile": ti, "res_0": float(res0.mean()),
               "res_1": float(res1.mean()), "primal": primal,
               "dual": float(duals[-1]) if len(duals) else 0.0}
        self.history.append(rec)
        if obs.active():
            obs.inc("tiles_solved_total")
            obs.set_gauge("consensus_primal_residual", primal)

        if self.spatial_file is not None:
            self._write_spatial_model(np.asarray(Z))
        if self.writer:
            # Z coefficient columns: [M, P, K, N, 8] -> Jones-like blocks
            Zr = np.asarray(Z)
            Zj = utils.jones_r2c_np(
                Zr.transpose(0, 2, 1, 3, 4).reshape(
                    sky.n_clusters, kmax * args.npoly, n, 8))
            nchunk_poly = sky.nchunk * args.npoly
            def _write_z(ti=ti, Zj=Zj, nchunk_poly=nchunk_poly):
                with dtrace.phase("solutions", tile=ti):
                    self.writer.write_interval(Zj, nchunk_poly)
            bubble += aw.submit(_write_z)

        if dtrace.active():
            # interval summary: bubble_s is the host seconds this step
            # was blocked on data movement (the wait for the staged
            # interval, writer back-pressure), overlap the prefetch depth
            with dtrace.phase("record"):
                dtrace.emit("tile", tile=ti, res_0=rec["res_0"],
                            res_1=rec["res_1"], primal=primal,
                            bubble_s=float(bubble), overlap=self.depth,
                            fold=self.fold, ndev=self.ndev, plan=self.plan,
                            jupdate_trips=useful,
                            lockstep_pct=lockstep_pct,
                            sweep_rows=self.sweep_rows,
                            **({} if self.assemble_rows is None else
                               {"assemble_rows": self.assemble_rows}))
        self._last = ti
        return rec

    def close(self):
        """Drain the ordered writer (re-raising a pending write
        failure), bank the prior where every selected interval was
        stepped, close the solution files."""
        self.aw.close()
        if (ppriors.writes(self.prior_mode) and self.stop > self.start
                and self._last == self.stop - 1):
            # bank the last accepted chain (J0 already has the divergence
            # resets applied) + the final per-cluster rho, subband-mean of
            # the mesh's [F, M] schedule. Runs only after aw.close() — the
            # banked prior can only name durably written outputs.
            try:
                meta0, kmax = self.meta0, self.kmax
                span = float(meta0["tilesz"]) * float(meta0["tdelta"])
                pt = (float(self.stop - 1)
                      + (np.arange(kmax) + 0.5) / kmax) * span
                Jc = utils.jones_r2c_np(np.asarray(self.J0))
                rho_f = np.asarray(self._fetch(self._rhoF))[:self.nf]
                rho_m = rho_f.mean(axis=0) if rho_f.ndim == 2 else None
                ppriors.PRIORS.bank(
                    self.prior_k, np.transpose(Jc, (0, 2, 1, 3, 4, 5)), pt,
                    self.freqs.astype(np.float64), rho=rho_m)
            except Exception as e:
                if self.is_writer:
                    self.log(f"prior-cache: bank skipped ({e})")
        if self.writer:
            self.writer.close()
        if self.spatial_file is not None:
            self.spatial_file.close()
        for ww in self.worker_writers:
            ww.close()


def _consensus_time_sharded(args, dtrace, *, mss, meta0, freqs, sky,
                            dsky, cfg, Bpoly, rdt, sdt, cidx, cmask, n,
                            t0, start, stop, Jinit, res_jit, writer,
                            worker_writers, is_writer, prep_tiles) -> int:
    """``--time-shard T``: the 2-D ('freq', 'time') mesh driver. Every
    selected interval is read and prepped up front, the whole
    observation solves as ONE SPMD program over a ``ndev_f x T`` device
    mesh (admm.make_admm_runner_2d: per-interval J-updates shard-local,
    consensus a freq-axis collective per interval, the warm-start J
    chain a per-time-shard scan with the divergence reset in-program),
    then outputs write back per interval through the same writers as
    the sequential loop. Memory note: this is the pod batch mode —
    host staging holds all T intervals at once (MIGRATION.md '2-D
    mesh'). Writes are synchronous (no prefetch/AsyncWriter: there is
    no solve left to overlap them with)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from sagecal_tpu import utils
    from sagecal_tpu.consensus import admm as cadmm

    nf = len(mss)
    T = int(args.time_shard)
    ndev_avail = len(jax.devices())
    if args.mesh_devices:
        # honor the --mesh-devices cap here too (leave devices to
        # co-tenants)
        ndev_avail = min(ndev_avail, max(1, args.mesh_devices))
    if ndev_avail < T:
        raise ValueError(f"--time-shard {T} needs at least T devices; "
                         f"{ndev_avail} visible")
    ndev_f = min(nf, max(1, ndev_avail // T))
    mesh = Mesh(np.array(jax.devices()[:ndev_f * T]).reshape(ndev_f, T),
                ("freq", "time"))
    from sagecal_tpu.serve import fleet as _fleet
    _fleet.note_mesh(mesh)     # fleet-view span when run as a serve job
    nt_sel = stop - start
    if nt_sel < 1:
        raise ValueError("no intervals selected (-T/-K window is empty)")
    if is_writer:
        print(f"2-D mesh: {ndev_f} freq x {T} time devices, "
              f"{nf} subbands x {nt_sel} intervals")

    # read + prep every interval up front (pod batch mode)
    all_tiles = [[m.read_tile(start + i) for m in mss]
                 for i in range(nt_sel)]
    preps = [prep_tiles(tiles) for tiles in all_tiles]
    x8FT, uFT, vFT, wFT, wtFT = [
        np.stack([p[k] for p in preps], axis=1) for k in range(5)]
    frFT = np.stack([p[5] for p in preps], axis=1)       # [F, T]

    # subband padding (freq axis), then time padding — the two mesh
    # padding contracts in admm.py
    (x8FT, uFT, vFT, wFT, wtFT, frFT, freqsP, J0P), BpolyP, fpad = \
        cadmm.pad_subbands((x8FT, uFT, vFT, wFT, wtFT, frFT, freqs,
                            np.asarray(Jinit)), Bpoly, nf, ndev_f)
    (x8FT, uFT, vFT, wFT, wtFT, frFT), tpad = cadmm.pad_time(
        (x8FT, uFT, vFT, wFT, wtFT, frFT), nt_sel, T)

    timer: list = []
    runner = cadmm.make_admm_runner_2d(
        dsky, t0.sta1, t0.sta2, cidx, cmask, n, meta0["fdelta"],
        BpolyP, cfg, mesh, nf, nt_sel, nbase=meta0["nbase"],
        host_loop=True, timer=timer)

    # dtype policy: [B]-traffic stages in the storage dtype, geometry
    # and Jones keep the pipeline dtype — no f32 fallback on this path
    from sagecal_tpu import dtypes as dtp
    sd = dtp.storage_np(getattr(args, "dtype_policy", "f32"), rdt)
    rd = np.dtype(rdt)
    out = runner(x8FT.astype(sd), uFT.astype(rd), vFT.astype(rd),
                 wFT.astype(rd), freqsP.astype(rd), wtFT.astype(sd),
                 frFT.astype(rd), J0P.astype(rd))
    JT, ZT, rhoT, res0T, res1T, r1sT, dualsT, Y0T = [
        np.asarray(o) for o in out]
    if is_writer and timer:
        waves = [s for _, s in timer]
        print("2-D mesh wavefront wall-clock: "
              + " ".join(f"{s:.2f}s" for s in waves)
              + f" ({T} time devices/wavefront, "
              f"{max(cfg.n_admm, 1)} ADMM iters each; first includes "
              "compile)")

    kmax = int(np.asarray(cmask).shape[1])
    for i in range(nt_sel):
        ti = start + i
        JF_r8_5 = JT[i][:nf].reshape(nf, sky.n_clusters, kmax, n, 8)
        Z = ZT[i]
        res0 = res0T[i][:nf]
        r1s = r1sT[i][:, :nf]
        res1 = r1s[-1] if cfg.n_admm > 1 else res1T[i][:nf]
        duals = dualsT[i]
        if worker_writers:
            J_all = utils.jones_r2c_np(JF_r8_5)
            for f, ww in enumerate(worker_writers):
                ww.write_interval(J_all[f], sky.nchunk)
        if dtrace.active() or obs.active():
            for k in range(r1s.shape[0]):
                r1m = float(r1s[k].mean())
                du = float(duals[k]) if len(duals) else 0.0
                dtrace.emit("admm_iter", interval=ti, iter=k + 1,
                            r1_mean=r1m, dual=du)
                if obs.active():
                    obs.inc("admm_iterations_total")
                    obs.set_gauge("admm_primal_residual", r1m)
                    obs.set_gauge("admm_dual_residual", du)
            BZf = np.einsum("fp,mpknr->fmknr", Bpoly, Z)
            primal = float(np.linalg.norm(
                JF_r8_5 - BZf.reshape(JF_r8_5.shape))
                / np.sqrt(BZf.size))
            dtrace.emit("tile", tile=ti, res_0=float(res0.mean()),
                        res_1=float(res1.mean()), primal=primal)
            if obs.active():
                obs.inc("tiles_solved_total")
                obs.set_gauge("consensus_primal_residual", primal)
        if is_writer:
            print(f"Timeslot:{ti} ADMM:{cfg.n_admm} residual "
                  f"initial={res0.mean():.6g} final={res1.mean():.6g} "
                  f"dual={duals[-1] if len(duals) else 0:.3g}")
            if args.verbose:
                for f in range(nf):
                    print(f"  subband {f}: {res0[f]:.6g} -> "
                          f"{res1[f]:.6g}")
            if args.use_global_solution:
                BZ = np.einsum("fp,mpknr->fmknr", Bpoly, Z)
                J_res = BZ.reshape(nf, sky.n_clusters, kmax, n, 8)
            else:
                J_res = JF_r8_5
            tiles = all_tiles[i]
            xF_r = np.stack([utils.c2r(t.x) for t in tiles])
            uF, vF, wF = preps[i][1], preps[i][2], preps[i][3]
            res_r = res_jit(jnp.asarray(J_res, rdt),
                            jnp.asarray(xF_r, sdt),
                            jnp.asarray(uF, rdt), jnp.asarray(vF, rdt),
                            jnp.asarray(wF, rdt),
                            jnp.asarray(freqs, rdt))
            res_np = utils.r2c(np.asarray(res_r, np.float64))
            for f, (msx, t) in enumerate(zip(mss, tiles)):
                t.x = res_np[f].astype(np.complex128)
                msx.write_tile(ti, t)
        if writer:
            Zr = np.asarray(Z)
            Zj = utils.jones_r2c_np(
                Zr.transpose(0, 2, 1, 3, 4).reshape(
                    sky.n_clusters, kmax * args.npoly, n, 8))
            writer.write_interval(Zj, sky.nchunk * args.npoly)

    if writer:
        writer.close()
    for ww in worker_writers:
        ww.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
