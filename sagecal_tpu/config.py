"""Typed run configuration with CLI parity to the reference binaries.

The reference scatters getopt single-letter flags into mutable globals
(``src/MS/data.h:129-198``, ``src/MPI/main.cpp:107-242``). Here the whole
configuration is one frozen dataclass; the CLI maps the documented flags
onto its fields so reference invocations translate 1:1.
"""

from __future__ import annotations

import dataclasses
import enum

import jax.numpy as jnp


class SolverMode(enum.IntEnum):
    """Solver selection, parity with ``-j`` (reference Dirac.h:1533-1539 SM_*)."""

    OSLM_LBFGS = 0        # SM_OSLM_LBFGS: ordered-subsets LM + LBFGS
    LM_LBFGS = 1          # SM_LM_LBFGS: plain LM + LBFGS refine
    RLM_RLBFGS = 2        # SM_RLM_RLBFGS: robust LM (OS warmup iters)
    OSLM_OSRLM_RLBFGS = 3 # SM_OSLM_OSRLM_RLBFGS: OS everywhere + robust
    RTR_OSLM_LBFGS = 4    # Riemannian trust region
    RTR_OSRLM_RLBFGS = 5  # robust RTR (production default)
    NSD_RLBFGS = 6        # Nesterov accelerated steepest descent, robust


class BeamMode(enum.IntEnum):
    """Parity with ``-B`` (reference Dirac_common.h:97-109 DOBEAM_*)."""

    NONE = 0
    ARRAY = 1          # array (station) beam only
    FULL = 2           # array * element (DOBEAM_FULL, Dirac_common.h:105)
    ELEMENT = 3        # element beam only (DOBEAM_ELEMENT, :108)


class SimulationMode(enum.IntEnum):
    """Parity with ``-a`` (reference fullbatch_mode.cpp:524-578)."""

    OFF = 0
    SIMULATE = 1       # replace data with model (optionally corrupted by -p solutions)
    ADD = 2            # add model to data
    SUBTRACT = 3       # subtract model from data


@dataclasses.dataclass(frozen=True)
class Precision:
    """Device dtype policy.

    The reference CPU path is float64 end-to-end while its CUDA production
    path solves in float32 with float64 control state
    (``sagefit_visibilities_dual_pt_flt``, SURVEY.md section 2.6). On TPU we
    default to the same split: complex64/float32 bulk math, float64 only for
    small host-side control quantities.
    """

    real: jnp.dtype = jnp.float32
    complex: jnp.dtype = jnp.complex64

    @property
    def real_np(self):
        return jnp.dtype(self.real)


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """Full calibration run configuration (CLI flag in comments)."""

    # --- inputs (reference src/MS/main.cpp:115-257)
    ms: str | None = None              # -d : measurement set (or SimMS dir)
    ms_list: str | None = None         # -f : file listing multiple MSs / glob
    sky_model: str | None = None       # -s
    cluster_file: str | None = None    # -c
    solutions_file: str | None = None  # -p : output (or input for simulation)
    init_solutions: str | None = None  # -q : warm start
    format_3: bool = False             # -F 1 : 3rd-order spectral indices
    input_column: str = "DATA"         # Data::DataField (CasaMS backend)
    output_column: str = "CORRECTED_DATA"   # Data::OutField

    # --- solve shape
    tile_size: int = 120               # -t : timeslots per solve interval
    max_em_iter: int = 3               # -e : EM iterations
    max_iter: int = 10                 # -g : LM/RTR iterations per cluster solve
    max_lbfgs: int = 10                # -l : LBFGS iterations
    lbfgs_m: int = 7                   # -m : LBFGS memory size
    gpu_threads: int = 64              # -S (unused on TPU; kept for parity)
    n_threads: int = 4                 # -n : host threads for IO
    solver_mode: SolverMode = SolverMode.RTR_OSRLM_RLBFGS  # -j
    robust_nulow: float = 2.0          # -L
    robust_nuhigh: float = 30.0        # -H
    linsolv: int = 1                   # --linsolv : 0 Cholesky 1 QR 2 SVD
    randomize: bool = True             # -R : ordered-subsets randomization

    # --- data selection / conditioning
    uvmin: float = 0.0                 # -x (lambda)
    uvmax: float = 1e9                 # -y
    mmse_rho: float = 1e-9             # -o : correction MMSE rho (Data::rho)
    uvtaper: float = 0.0               # -A (MS app meaning: taper)
    whiten: bool = False               # -W : uv-density whitening
    channel_avg_per_band: int = 1      # -w : mini-bands (bandpass)
    per_channel_bfgs: bool = False     # -b 1 : per-channel re-solve

    # --- simulation
    simulation: SimulationMode = SimulationMode.OFF  # -a
    ignore_clusters_file: str | None = None          # -z
    correct_cluster: int | None = None               # -k : cluster id to correct residual by
    phase_only: bool = False                         # -J : phase-only correction

    # --- beam
    beam_mode: BeamMode = BeamMode.NONE              # -B

    # --- stochastic calibration (minibatch)
    n_epochs: int = 0                  # -N : >0 enables stochastic mode
    n_minibatches: int = 1             # -M
    # robust (Student's t) or huber minibatch loss
    # (robust_batchmode_lbfgs.c:66 func_huber_th vs :89 func_robust_th)
    stochastic_loss: str = "robust"

    # --- consensus / distributed (reference src/MPI/main.cpp:107-242)
    n_admm: int = 1                    # -A : ADMM iterations
    n_poly: int = 2                    # -P : polynomial terms
    poly_type: int = 2                 # -Q : 0/1 monomial, 2 Bernstein
    admm_rho: float = 5.0              # -r
    rho_file: str | None = None        # -G : per-cluster rho
    adaptive_rho: bool = False         # -C : Barzilai-Borwein rho
    max_timeslots: int = 0             # -T : 0 = all
    skip_timeslots: int = 0            # -K
    federated_alpha: float = 0.0       # -u
    spatialreg: tuple | None = None    # -X : (l2, l1, order, fista_iters, cadence)
    use_global_solution: bool = False  # -U
    mdl_report: bool = False           # -M (mpi app): model-order selection report
    verbose: bool = False              # -V

    # --- execution plan (host solve driver)
    # --tile-batch : solve intervals batched into one vmapped device
    # program (T>1 changes warm-start semantics: every tile in a batch
    # warm-starts from the last completed batch's solution instead of
    # the immediately preceding tile's — a deliberate throughput trade;
    # sage.sagefit_host_tiles)
    tile_batch: int = 1
    # --solve-fuse/--solve-promote : force ("on"/"off") or learn
    # ("auto") the wall-clock execution-plan heuristics
    # (sage.SageConfig.fuse/promote) so perf runs are reproducible
    solve_fuse: str = "auto"
    solve_promote: str = "auto"
    # --inflight : clusters solved concurrently per SAGE sweep step
    # (block-Jacobi groups, sage.SageConfig.inflight); 1 = reference
    # Gauss-Seidel sequencing
    cluster_inflight: int = 1
    # --inner : inner linear solver for the damped Gauss-Newton step /
    # RTR Hessian operator (sage.SageConfig.inner): "chol" dense
    # [K, 8N, 8N] assembly (the default), "cg" matrix-free
    # preconditioned Krylov — see MIGRATION.md "Inner linear solver"
    solver_inner: str = "chol"
    # --jones : constrained-Jones parameterization for every solver
    # path (sage.SageConfig.jones_mode; normal_eq.JONES_MODES): "full"
    # (2x2 complex, the default) | "diag" (diagonal Jones, 4
    # real params/station) | "phase" (phase-only diagonal, 2 real
    # params/station — retraction J = J0 * exp(i theta)). Non-full
    # modes shrink the per-baseline Gram blocks the assemblies emit
    # (8x8 -> 4x4 / 2x2 real) and join the program-cache/prior keys.
    # Distinct from ``phase_only`` (-J), which phase-projects the
    # CORRECTION applied to residuals after a full-Jones solve;
    # --jones phase constrains the SOLVE itself
    jones_mode: str = "full"
    # --dtype-policy : storage dtype for the [B]-proportional data
    # (visibilities, weights, staged residual tiles, Wirtinger
    # factors): "f32" (identity, the default) | "bf16" | "f16".
    # Accumulation stays f32 everywhere (sagecal_tpu.dtypes;
    # MIGRATION.md "Dtype policy" for the per-policy tolerance
    # envelopes and what never quantizes: solutions J, consensus
    # state, uvw geometry, the robust-nu root-find)
    dtype_policy: str = "f32"
    # --tile-bucket : pad each staged solve interval to this many
    # timeslots (whole zero-weight timeslot blocks; serve/cache.py) so
    # jobs whose shapes differ only in tilesz share one set of
    # compiled programs in the service's compile cache. 0 = off (exact
    # shapes, the default); -1 = next power of two; an
    # explicit value must be >= tilesz. Changing the bucket changes
    # the OS-subset partition, so outputs are bit-identical to a solo
    # run AT THE SAME BUCKET (MIGRATION.md "Service mode")
    tile_bucket: int = 0
    # --resume : re-enter a killed/failed/deadline-expired run from
    # its tile-boundary checkpoint (the <solutions>.ckpt.npz sidecar
    # written next to -p): completed tiles are skipped and the final
    # residuals + solutions are bit-identical to an uninterrupted run
    # (sequential fullbatch driver only; MIGRATION.md "Fault
    # tolerance"). No checkpoint found = start fresh.
    resume: bool = False
    # --prefetch : overlapped execution depth (sagecal_tpu.sched).
    # N>0: tile t+N is read + host-prepared on a background thread
    # while tile t solves, and residual/solution writes run on an
    # ordered writer thread (bit-identical outputs; memory cost = N
    # extra staged tiles). 0: the fully synchronous reference loop —
    # the debugging escape hatch (MIGRATION.md "Overlapped execution")
    prefetch: int = 1
    # streaming-ingest pacing (sched.Prefetcher pace_s): the k-th
    # interval this (re)start produces becomes readable no earlier
    # than (re)start + k * tile_arrival_s seconds, modeling a tenant
    # whose tiles arrive over the wire at a bounded data rate (the
    # quasi-real-time LOFAR/SKA regime, arXiv:1410.2101) instead of
    # sitting on local disk. A resumed/migrated job re-paces from its
    # resume point (the stream clock is per process run — original
    # job-start wall time does not survive a restart). Pure wait —
    # outputs are bit-identical at any pacing; serve.loadgen uses it
    # to make a replay ingest-limited (MIGRATION.md "Fleet
    # mode"). 0 = off (the default).
    tile_arrival_s: float = 0.0

    # --- streaming ingest (sagecal_tpu.stream; MIGRATION.md
    # "Streaming mode"): tiles arrive from a live source instead of a
    # complete on-disk MS, and the SLO is per-tile arrival->write
    # latency rather than job makespan.
    # stream_source : transport spec — "gen[:interval_s]" (seeded
    # in-process generator over the MS at --ms, released on an arrival
    # clock; the tests' transport), "tail[:path]" (follow a
    # spool directory a feeder writes tiles into; default path = the
    # MS itself), "socket:host:port" (length-prefixed npz tile frames
    # over TCP; tiles spool into the MS directory as they land).
    # None/"" = batch mode (everything before this PR).
    stream_source: str | None = None
    # per-tile deadline, seconds from tile ARRIVAL to its residual
    # durably written. 0 = no per-tile deadline (lateness still
    # counted against nothing). A late tile never stalls the stream:
    # it is counted (stream_tiles_late_total) and handled per
    # late_policy.
    tile_deadline_s: float = 0.0
    # what to do with a late tile: "degrade" (skip its solve, write
    # the residual from the last-good Jones via the quarantine
    # writeback path — bounded staleness, bounded latency) or "count"
    # (solve anyway; lateness is observability only, outputs stay
    # bit-identical to batch).
    late_policy: str = "degrade"

    # --prior-cache : warm-start solution prior store
    # (sagecal_tpu.serve.priors; MIGRATION.md "Solution prior cache").
    # "read": seed J0 (and the ADMM ρ schedule) from a banked solution
    # of the same sky/cluster content + station set + band + solver
    # family, interpolated onto this run's intervals/subbands;
    # "readwrite": additionally bank this run's final chain on
    # completion. Tolerance-work, not bit-work: seeding changes
    # iteration counts, never the convergence target (gated warm-vs-
    # cold in tests/test_priors.py). "off" (the default) never
    # touches the store — every bit-parity
    # gate stays frozen.
    prior_cache: str = "off"

    # --- observability
    profile_dir: str | None = None     # --profile : jax.profiler trace of
    #                                    the first solve interval

    # --- intra-subband distribution (P1): shard the baseline x time row
    # axis of ONE subband over all devices (GSPMD; parallel.py)
    shard_baselines: bool = False      # --shard-baselines

    # --- device policy
    precision: Precision = dataclasses.field(default_factory=Precision)

    def replace(self, **kw) -> "RunConfig":
        return dataclasses.replace(self, **kw)


DEFAULT = RunConfig()
