"""End-to-end calibration pipelines (the application layer).

Capability parity with reference ``src/MS/fullbatch_mode.cpp``
(``run_fullbatch_calibration``:38): stream solve intervals (tiles) from the
dataset, predict solve-path coherencies, run SAGE-EM, compute/write
residuals and solutions, with the reference's convergence heuristics:

- first-tile iteration boost: 4x EM iterations for arrays <= LMCUT (=40)
  stations, 6x otherwise (fullbatch_mode.cpp:397);
- LMCUT solver downgrade: RTR/NSD modes fall back to ordered-subsets LM
  for small arrays (fullbatch_mode.cpp:397,431; sagecalmain.h:24);
- divergence reset: residual 0 / non-finite / > 5x best resets solutions
  to the initial values and re-arms the first-tile boost
  (fullbatch_mode.cpp:605-621, res_ratio fullbatch_mode.cpp:239);
- simulation modes -a 1/2/3 with optional solutions replay + ignore list
  (fullbatch_mode.cpp:524-578).

Device policy: one jitted solve program reused across tiles (shapes are
static per dataset); host streams tiles and writes residuals back.
"""

from __future__ import annotations

import contextlib
import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from sagecal_tpu import coords, dtypes as dtp, faults, sched, skymodel, utils
from sagecal_tpu.config import RunConfig, SimulationMode, SolverMode
from sagecal_tpu.serve import cache as pcache
from sagecal_tpu.serve import fleet as pfleet
from sagecal_tpu.serve import priors as ppriors
from sagecal_tpu.diag import trace as dtrace
from sagecal_tpu.obs import metrics as obs
from sagecal_tpu.solvers import normal_eq as ne
from sagecal_tpu.io import dataset as ds
from sagecal_tpu.io import solutions as sol
from sagecal_tpu.rime import beam as bm
from sagecal_tpu.rime import predict as rp
from sagecal_tpu.rime import residual as rr
from sagecal_tpu.solvers import lm as lm_mod
from sagecal_tpu.solvers import sage

# the Jones real<->complex reshapes run inside jit (complex stays
# on-device; utils.py)
_jones_r2c_j = jax.jit(ne.jones_r2c)
_jones_c2r_j = jax.jit(ne.jones_c2r)


def _device_platform() -> str:
    """Platform of the device the solves run on."""
    return jax.devices()[0].platform


LMCUT = 40      # sagecalmain.h:24
RES_RATIO = 5.0  # fullbatch_mode.cpp:239


def _emit_tile_record(ti, res_0, res_1, mean_nu, info, minutes,
                      bubble_s=None, overlap=None, cmask=None,
                      coh=None):
    """Per-solve-interval convergence record (gated on an active tracer
    / metrics registry so the extra device->host syncs never run
    otherwise). ``bubble_s`` / ``overlap`` are the overlapped-execution
    accounting pair: host seconds blocked on data movement for this
    tile, and the prefetch depth it ran under (0 = synchronous
    reference loop). ``cmask`` is the pipeline's ``[M, kmax]`` mask of
    live hybrid chunks: the record says how many chunk slots every
    cluster's Jones carries and how many of them the cluster file
    asked for. ``coh`` is the pipeline's ``coh_record``: which coherency
    path it chose and, with a beam, the beam's sizes."""
    if not (dtrace.active() or obs.active()):
        return
    trips = lm_mod.executed_trips(info)
    if obs.active():
        obs.inc("tiles_solved_total")
        if bubble_s is not None:
            obs.inc("tile_bubble_seconds_total", float(bubble_s))
        for k, v in trips.items():
            name = "dispatches" if k == "solve_dispatches" else k
            obs.inc(f"solver_{name}_total", v)
    if not dtrace.active():
        return
    rec = dict(tile=ti, res_0=res_0, res_1=res_1, mean_nu=mean_nu,
               minutes=minutes)
    if bubble_s is not None:
        rec["bubble_s"] = float(bubble_s)
        rec["overlap"] = int(overlap or 0)
    # host-driver extras (the sharded solver reports only residuals):
    # the trace schema's two trip fields, the inner CG trips under them
    # (LM's PCG, RTR's truncated CG) and RTR's passes over the rows, and
    # the joint refine's passes through the model (lbfgs._lbfgs_loop),
    # which plan sagefit_host ran in how many device executions, and
    # the row layouts its refine, its sweeps and their assembly worked on
    for k in ("solver_iters", "cg_iters", "row_passes", "lbfgs_iters",
              "refine_passes", "solve_dispatches"):
        if k in trips:
            rec[k] = trips[k]
    if isinstance(info, dict):
        for k in ("plan", "refine_rows", "sweep_rows", "assemble_rows"):
            if k in info:
                rec[k] = info[k]
    if cmask is not None:
        # J is [M, kmax, N, 2, 2]: every cluster carries the chunk
        # slots of the one with the most
        rec["kmax"] = int(cmask.shape[1])
        rec["chunk_slots"] = int(cmask.size)
        rec["chunk_slots_live"] = int(cmask.sum())
    rec.update(coh or {})
    dtrace.emit("tile", **rec)


def source_kinds(sky: skymodel.ClusterSky) -> dict:
    """What kinds of source the model holds, for the ``tile`` records:
    the live sources of each kind, the largest shapelet order, and the
    source slots for which the XLA source sum evaluates the shapelet
    basis: ``M x S_sh``, the compact pack of the model's shapelet
    sources (``rime/predict.shapelet_slots``), ``S_sh`` the most any
    cluster holds and 0 where the model has none."""
    live = np.asarray(sky.smask, bool)
    stype = np.asarray(sky.stype)
    out = {f"sources_{name}": int(np.sum(live & (stype == code)))
           for name, code in (("point", skymodel.STYPE_POINT),
                              ("gaussian", skymodel.STYPE_GAUSSIAN),
                              ("disk", skymodel.STYPE_DISK),
                              ("ring", skymodel.STYPE_RING),
                              ("shapelet", skymodel.STYPE_SHAPELET))}
    n0 = np.asarray(sky.sh_n0)
    out["shapelet_n0max"] = int(n0.max()) if n0.size else 0
    out["shapelet_slots"] = int(rp.shapelet_slots(sky).size)
    return out


def effective_solver_mode(mode: int, n_stations: int) -> int:
    """LMCUT downgrade (fullbatch_mode.cpp:397)."""
    if n_stations <= LMCUT and mode == int(SolverMode.RTR_OSLM_LBFGS):
        return int(SolverMode.OSLM_LBFGS)
    if n_stations <= LMCUT and mode in (int(SolverMode.RTR_OSRLM_RLBFGS),
                                        int(SolverMode.NSD_RLBFGS)):
        return int(SolverMode.OSLM_OSRLM_RLBFGS)
    return mode


def first_tile_boost(n_stations: int) -> int:
    return 4 if n_stations <= LMCUT else 6


class FullBatchPipeline:
    """Reusable jitted solve over a SimMS-like dataset."""

    def __init__(self, cfg: RunConfig, ms: ds.SimMS, sky: skymodel.ClusterSky,
                 real_dtype=None, log=print):
        self.cfg = cfg
        self.ms = ms
        self.sky = sky
        self.log = log
        platform = _device_platform()
        log(utils.platform_line())
        if real_dtype is None:
            real_dtype = jnp.float64 if (
                platform == "cpu" and jax.config.read("jax_enable_x64")
            ) else jnp.float32
        self.rdt = real_dtype
        # --dtype-policy storage dtype for the staged [B]-data (x8, wt,
        # residual ring slots); "f32" keeps sdt == rdt (bit-frozen).
        # The sharded (GSPMD) path stages its [B]-rows in the storage
        # dtype too (the row-sharded solve reuses the same
        # storage/accumulate split inside sagefit) — the PR 6
        # policy-exemption melted in ISSUE 14, tolerance-gated by
        # tests/test_dtype_policy.py::test_sharded_path_applies_policy.
        policy = getattr(cfg, "dtype_policy", "f32")
        if policy != "f32" and real_dtype == jnp.float64:
            # a reduced storage policy pairs with the f32/c64 pipeline
            # (the accumulator contract is f32); keeping the f64/c128
            # CPU-test pipeline underneath would mix f64 model streams
            # into f32 solver state
            real_dtype = jnp.float32
            self.rdt = real_dtype
        self.dtype_policy = policy
        self.sdt = dtp.storage_dtype(policy, real_dtype)
        self.dsky = rp.sky_to_device(sky, real_dtype)
        meta = ms.meta
        self.kmax = int(sky.nchunk.max())
        self.cmask = np.arange(self.kmax)[None, :] < sky.nchunk[:, None]
        # --tile-bucket: pad each staged interval to a common timeslot
        # bucket (whole zero-WEIGHT timeslot blocks, serve/cache.py) so
        # bucket-compatible jobs share one set of compiled programs.
        # Every tilesz-derived static below (cidx, tslot, OS subsets)
        # is built at the BUCKET size; staging pads, residual write
        # slices the real rows back out. Exactness argument: a
        # zero-weight row contributes nothing to any weighted
        # reduction (the PR 6 OS-slicing / sharded-padding precedent).
        tb = int(getattr(cfg, "tile_bucket", 0) or 0)
        self.tilesz_eff = int(meta["tilesz"])
        if tb:
            unsupported = (cfg.per_channel_bfgs
                           or getattr(cfg, "shard_baselines", False)
                           or int(cfg.beam_mode)
                           or int(getattr(cfg, "tile_batch", 1)) > 1
                           or cfg.simulation != SimulationMode.OFF)
            if unsupported:
                log("tile-bucket: per-channel/sharded/beam/tile-batch/"
                    "simulation paths stage exact shapes; bucketing off")
            else:
                self.tilesz_eff = pcache.resolve_bucket(meta["tilesz"],
                                                        tb)
        self.pad_rows = (self.tilesz_eff - int(meta["tilesz"])) \
            * int(meta["nbase"])
        self.cidx = rp.chunk_indices(self.tilesz_eff, meta["nbase"],
                                     sky.nchunk)
        self.n = meta["n_stations"]
        self.tslot = ds.row_tslot(self.tilesz_eff * meta["nbase"],
                                  meta["nbase"])
        # beam (-B): stored metadata, else synthetic (set_elementcoeffs +
        # readAuxData-with-beam analogue; fullbatch_mode.cpp:56-70)
        self.dobeam = int(cfg.beam_mode)
        self.beam_info = bm.resolve_beaminfo(self.dobeam, ms, meta, log=log)
        self._warned_no_times = False
        # precess source + beam-pointing coordinates from J2000 to the
        # epoch of the first tile's mid timeslot, once per run
        # (precess_source_locations data.cpp:1473, called at
        # fullbatch_mode.cpp:325 only when the beam is on). Must happen
        # BEFORE any solver trace: the device sky is closure-captured as
        # jit constants.
        self.precessed = False
        if self.dobeam:
            self._precess_sources(log)
        # Pallas coherency kernel: point/gaussian f32 models on a TPU;
        # mixed models run hybrid (kernel + compact XLA rest,
        # skymodel.split_for_pallas). Chosen from what is known BEFORE
        # running — platform, dtype, beam, sharding, source types — and
        # never probed: a kernel that fails to compile or run fails the
        # solve, it does not quietly become the XLA path. The sharded
        # (GSPMD) solve path predicts with plain XLA.
        from sagecal_tpu.ops import coh_pallas
        self.use_pallas = bool(
            platform == "tpu" and not self.dobeam
            and not getattr(cfg, "shard_baselines", False)
            and self.rdt == jnp.float32
            and coh_pallas.any_supported(sky))
        self._pallas_skies = None
        coh_path = "xla"
        if self.use_pallas:
            sky_pg, sky_rest = skymodel.split_for_pallas(sky)
            self._pallas_skies = (
                rp.sky_to_device(sky_pg, self.rdt),
                None if sky_rest is None
                else rp.sky_to_device(sky_rest, self.rdt))
            coh_path = "pallas" if sky_rest is None else \
                "pallas (hybrid: shapelet/disk/ring via XLA)"
        log(f"Coherency path: {coh_path}")
        # host values for every ``tile`` record (diag/trace.py)
        self.coh_record = dict(
            coh_path="pallas" if self.use_pallas else "xla",
            beam_mode=self.dobeam, **source_kinds(sky))
        # the beam's static leaves (stations, elements, pattern, pointing)
        # staged ONCE, after the precession above; a tile restages its
        # gmst track alone (_tile_beam), as cli_mpi does
        self._beam_static = None
        if self.dobeam:
            self._beam_static = bm.beam_to_device(
                self.beam_info, meta["freq0"], self.rdt)
            self.coh_record.update(
                beam_elements=int(self.beam_info.elem_mask.shape[1]),
                beam_sources=int(np.sum(sky.smask)))
        mode = effective_solver_mode(int(cfg.solver_mode), self.n)
        self.base_cfg = sage.SageConfig(
            max_emiter=cfg.max_em_iter, max_iter=cfg.max_iter,
            max_lbfgs=0 if cfg.per_channel_bfgs else cfg.max_lbfgs,
            lbfgs_m=cfg.lbfgs_m, solver_mode=mode, nulow=cfg.robust_nulow,
            nuhigh=cfg.robust_nuhigh, randomize=cfg.randomize,
            linsolv=cfg.linsolv,
            fuse=getattr(cfg, "solve_fuse", "auto"),
            promote=getattr(cfg, "solve_promote", "auto"),
            inflight=max(1, int(getattr(cfg, "cluster_inflight", 1))),
            inner=getattr(cfg, "solver_inner", "chol"),
            jones_mode=getattr(cfg, "jones_mode", "full"),
            dtype_policy=self.dtype_policy,
            # rows are [tilesz, nbase] (io.dataset layout): lets the
            # solvers' normal-equation assembly take the baseline-major
            # aggregation for single-chunk clusters
            nbase=int(meta["nbase"]))
        self.boost = first_tile_boost(self.n)

        # process-wide program-cache key (serve/cache.py): tokens EVERY
        # closure constant the per-pipeline jitted programs capture —
        # the post-precession device sky, shape statics at the BUCKET
        # tilesz, dtype policy, solver flags, and the residual/
        # simulation knobs — so a second job with an equal key shares
        # the first job's warm-compiled wrappers (zero new compiles,
        # asserted via diag/guard) and an unequal key can never reuse a
        # stale closure. The cache may keep a prior pipeline (and its
        # dataset handle) alive through a cached bound method; the LRU
        # bound in serve.cache caps that retention.
        self._ckey = pcache.token(
            [np.asarray(a) for a in jax.tree.leaves(self.dsky)],
            dict(freq0=meta["freq0"], fdelta=meta["fdelta"],
                 freqs=list(meta["freqs"]), tilesz=self.tilesz_eff,
                 nbase=int(meta["nbase"]), n=self.n),
            self.cidx, self.cmask, sky.cluster_ids, sky.nchunk,
            str(np.dtype(self.rdt)), str(np.dtype(self.sdt)),
            self.dtype_policy, int(self.dobeam), bool(self.use_pallas),
            tuple(self.base_cfg),
            dict(mmse_rho=cfg.mmse_rho, correct=cfg.correct_cluster,
                 phase_only=bool(cfg.phase_only),
                 sim=int(cfg.simulation)))

        # --tile-batch: T>1 solves T intervals as one vmapped program
        # (sagefit_host_tiles) — the utilization lever for small solves.
        # The beam path batches too (only the per-tile gmst track
        # differs between tiles — it becomes a leading axis, VERDICT r5
        # item 7); the sharded path is its own program and per-channel
        # mode re-solves per channel.
        self.tile_batch = max(1, int(getattr(cfg, "tile_batch", 1)))
        self.batch_ok = (self.tile_batch > 1 and not cfg.per_channel_bfgs
                         and not getattr(cfg, "shard_baselines", False))
        if self.tile_batch > 1 and not self.batch_ok:
            log("tile-batch disabled (per-channel/sharded path); "
                "running sequentially")
        self._solve_tiles = (self._build_tiles_solver(self.tile_batch)
                             if self.batch_ok else None)

        self._solve_first = self._build_solver(self.boost)
        self._solve_rest = self._build_solver(1, warm=True)
        # the staged per-tile visibility buffer is DONATED: the residual
        # program writes the subtracted visibilities in place of its
        # input (same [B, F, ..] real shape) instead of allocating a
        # second tile-sized buffer per interval — callers stage x_r
        # fresh from tile.x and only ever read the output back
        self._residual_fn = self._jit_cached(
            "residual",
            lambda: jax.jit(self._residuals, donate_argnums=(1,)))
        self._sim_jit = None       # bound by run_simulation via the
        #                            program cache (keyed, not per-instance)
        self._chan_solver = None
        self._chan_residual_fn = None
        if cfg.per_channel_bfgs:
            self._chan_solver = self._build_chan_solver()
            self._chan_residual_fn = self._build_chan_residual()

    # NOTE on jit boundaries: solvers take/return Jones as [.., N, 8]
    # reals and visibilities as stacked [..., 2] real pairs (utils.c2r),
    # so only real arrays cross host<->device.

    def _jit_cached(self, kind: str, build, *extra):
        """A jit wrapper shared through the process-wide program cache:
        ``build()`` runs once per (kind, content key, device ordinal,
        extra); every later pipeline with an equal key — another job in
        the same server, or this pipeline rebuilt — reuses the warm
        wrapper instead of silently re-tracing (serve/cache.py). The
        fleet ordinal (serve/fleet.py; 0 outside any device scope, so
        solo keys are unchanged in meaning) keys programs PER DEVICE:
        jax would recompile per device underneath one shared wrapper
        anyway — separate keys make that cost a visible per-device
        cache miss the fleet placer can route around."""
        return pcache.PROGRAMS.get(
            ("prog", kind, self._ckey, pfleet.current_ordinal()) + extra,
            build)

    def _inflight_downgrade(self, log=print) -> None:
        """Divergence guard for --inflight (VERDICT r5 item 6): a
        divergence reset with block-Jacobi groups active is treated as
        evidence of group overcorrection, and the run falls back to the
        reference's strict sequential cluster updates for all remaining
        tiles — the same downgrade philosophy as the LMCUT solver
        fallback (fullbatch_mode.cpp:397). Sticky: groups never re-arm
        within the run. Callers skip it for res_1 == 0 resets (fully
        flagged data says nothing about group overcorrection); residual
        growth and non-finite blowups both count as evidence."""
        if self.base_cfg.inflight <= 1:
            return
        log("inflight downgrade: divergence reset with cluster groups "
            "active; falling back to sequential updates (G=1)")
        self.base_cfg = self.base_cfg._replace(inflight=1)
        self._solve_first = self._build_solver(self.boost)
        self._solve_rest = self._build_solver(1, warm=True)
        if self._solve_tiles is not None:
            self._solve_tiles = self._build_tiles_solver(self.tile_batch)

    def _build_solver(self, emiter_mult: int, warm: bool = False):
        scfg = self.base_cfg._replace(
            max_emiter=self.base_cfg.max_emiter * emiter_mult,
            # warm solves (J0 carried from the previous tile) skip the
            # cold-start inflight width restriction (sage.SageConfig)
            inflight_warm=warm)
        meta = self.ms.meta
        freq0 = meta["freq0"]
        fdelta = meta["fdelta"]
        cidx = jnp.asarray(self.cidx)
        cmask = jnp.asarray(self.cmask)

        if getattr(self.cfg, "shard_baselines", False):
            return self._build_sharded_solver(scfg, meta, freq0, fdelta)

        tslot = jnp.asarray(self.tslot)
        # ordered-subsets partition for solver modes 1/2/3 (P4,
        # clmfit.c:1074); harmless to pass for other modes. Built at
        # the BUCKET tilesz: staged rows are padded to it
        os_info = lm_mod.os_subset_ids(self.tilesz_eff, meta["nbase"])

        if self.use_pallas:
            pg, rest = self._pallas_skies
            coh_fn = self._jit_cached("coh", lambda: jax.jit(
                lambda u, v, w, sta1, sta2, beam: (
                    rp.coherencies_split(pg, rest, u, v, w,
                                         jnp.asarray([freq0], self.rdt),
                                         fdelta)[:, :, 0])))
        else:
            coh_fn = self._jit_cached("coh", lambda: jax.jit(
                lambda u, v, w, sta1, sta2, beam: (
                    rp.coherencies(self.dsky, u, v, w,
                                   jnp.asarray([freq0], self.rdt),
                                   fdelta, beam=beam, dobeam=self.dobeam,
                                   tslot=tslot, sta1=sta1,
                                   sta2=sta2)[:, :, 0])))

        def solve(x8, u, v, w, sta1, sta2, wt, J0_r8, beam, tile_idx=0):
            # host-driven EM: one bounded device execution per cluster
            # solve
            with dtrace.phase("dispatch", prog="coh"):
                coh = coh_fn(u, v, w, sta1, sta2, beam)
            # jitted conversion: complex stays on-device
            J0 = _jones_r2c_j(jnp.asarray(J0_r8, self.rdt))
            # fresh subset draws + cluster permutations per tile
            key = jax.random.fold_in(jax.random.PRNGKey(199), tile_idx)
            J, info = sage.sagefit_host(
                jnp.asarray(x8, self.rdt), coh, sta1, sta2, cidx, cmask,
                J0, self.n, wt, config=scfg, os_id=os_info, key=key)
            return _jones_c2r_j(J), info
        return solve

    def _build_tiles_solver(self, T: int):
        """Batched variant of :meth:`_build_solver` (emiter_mult=1): T
        staged tiles solve as one vmapped program. Per-tile PRNG keys are
        the SAME fold_in(199, tile_idx) stream as the sequential path, so
        each tile's subset draws/permutations match a sequential run —
        only the warm start differs (batch-granular instead of
        tile-granular)."""
        # batches always run after the solo boost tile, so they are
        # warm-started (the cold-start inflight restriction is the solo
        # first solve's job)
        scfg = self.base_cfg._replace(inflight_warm=True)
        meta = self.ms.meta
        freq0 = meta["freq0"]
        fdelta = meta["fdelta"]
        cidx = jnp.asarray(self.cidx)
        cmask = jnp.asarray(self.cmask)
        os_info = lm_mod.os_subset_ids(self.tilesz_eff, meta["nbase"])
        freq = jnp.asarray([freq0], self.rdt)

        tslot = jnp.asarray(self.tslot)

        if self.use_pallas:
            # pallas is never enabled together with the beam (see the
            # probe gating above), so the beam argument is ignored here
            pg, rest = self._pallas_skies

            def coh_one(u1, v1, w1, beam_t, s1, s2):
                return rp.coherencies_split(pg, rest, u1, v1, w1, freq,
                                            fdelta)[:, :, 0]
        else:
            def coh_one(u1, v1, w1, beam_t, s1, s2):
                return rp.coherencies(self.dsky, u1, v1, w1, freq,
                                      fdelta, beam=beam_t,
                                      dobeam=self.dobeam, tslot=tslot,
                                      sta1=s1, sta2=s2)[:, :, 0]

        # per-tile beam: only the gmst time track differs between tiles
        # (stations/elements/pattern are tile-invariant), so the batch
        # carries ONE BeamArrays with a [T, tilesz] gmst and each tile's
        # predict slices its row at trace time
        coh_fn = self._jit_cached("coh_tiles", lambda: jax.jit(
            lambda u, v, w, beamT, s1, s2: jnp.stack(
                [coh_one(u[t], v[t], w[t],
                         (None if beamT is None
                          else beamT._replace(gmst=beamT.gmst[t])), s1, s2)
                 for t in range(T)])), T)

        def solve(x8T, uT, vT, wT, sta1, sta2, wtT, J0_r8T, tile_ids,
                  beamT=None):
            coh = coh_fn(uT, vT, wT, beamT, sta1, sta2)
            keys = jnp.stack([
                jax.random.fold_in(jax.random.PRNGKey(199), int(ti))
                for ti in tile_ids])
            J, info = sage.sagefit_host_tiles(
                jnp.asarray(x8T, self.rdt), coh, sta1, sta2, cidx, cmask,
                _jones_r2c_j(jnp.asarray(J0_r8T, self.rdt)), self.n, wtT,
                config=scfg, os_id=os_info, keys=keys)
            return _jones_c2r_j(J), info
        return solve

    def _build_sharded_solver(self, scfg, meta, freq0, fdelta):
        """--shard-baselines: one subband spanning the whole mesh (P1).

        The predict + SAGE solve runs as ONE program with the row axis
        sharded over a "base" mesh axis and the solutions replicated —
        GSPMD places the all-reduces (parallel.sharded_sagefit). Rows
        pad to the mesh with zero weight; the OS-subset ids and per-tile
        PRNG key ride through so modes 1/2/3 keep the P4 acceleration;
        beam tables replicate while the row-indexed gathers shard."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from sagecal_tpu import parallel

        mesh = parallel.base_mesh()
        ndev = mesh.devices.size
        os_ids_np, os_nsub = lm_mod.os_subset_ids(meta["tilesz"],
                                                  meta["nbase"])
        # row-sharding (+ zero-weight padding) breaks the [tilesz,
        # nbase] period a shard-local normal-equation assembly would
        # assume — disable the baseline-major path here
        scfg = scfg._replace(nbase=0)
        solve_j = parallel.sharded_sagefit(mesh, self.dsky, fdelta,
                                           self.cmask, self.n,
                                           config=scfg, os_nsub=os_nsub,
                                           dobeam=self.dobeam)
        tslot_np = np.asarray(self.tslot)
        cidx_np = np.asarray(self.cidx)
        freq = np.asarray([freq0])
        repl = NamedSharding(mesh, P())

        def solve(x8, u, v, w, sta1, sta2, wt, J0_r8, beam, tile_idx=0):
            B = np.asarray(x8).shape[0]
            arrs, wtp, bpad = parallel.pad_rows(
                (x8, u, v, w, sta1, sta2), wt, B, ndev)
            cidxp = np.concatenate(
                [cidx_np, np.zeros((cidx_np.shape[0], bpad - B),
                                   cidx_np.dtype)], axis=1)
            # padded rows get subset id 0 / timeslot 0; their zero
            # weight already excludes them from every reduction
            osp = np.concatenate(
                [np.asarray(os_ids_np),
                 np.zeros(bpad - B, np.asarray(os_ids_np).dtype)])
            tsp = np.concatenate(
                [tslot_np, np.zeros(bpad - B, tslot_np.dtype)])
            # dtype policy: the [B]-proportional rows (x8, wt) stage in
            # the storage dtype; geometry (u, v, w) keeps the pipeline
            # dtype (the RIME phase needs every f32 bit). Identity when
            # the policy is "f32".
            x8p, geom = arrs[0], arrs[1:]
            args = parallel.shard_rows(
                mesh, np.asarray(x8p, np.dtype(self.sdt)),
                *[np.asarray(a, np.dtype(self.rdt)
                             if np.asarray(a).dtype.kind == "f"
                             else None) for a in geom])
            (cidx_d,) = parallel.shard_rows(mesh, cidxp, row_axis=1)
            (wt_d,) = parallel.shard_rows(
                mesh, np.asarray(wtp, np.dtype(self.sdt)))
            (os_d,) = parallel.shard_rows(mesh, osp)
            (ts_d,) = parallel.shard_rows(mesh, tsp)
            key = jax.random.fold_in(jax.random.PRNGKey(199), tile_idx)
            beam_d = (None if beam is None
                      else jax.device_put(beam, repl))
            J, r0, r1, mnu = solve_j(
                *args, cidx_d, wt_d,
                jax.device_put(jnp.asarray(J0_r8, self.rdt), repl),
                jax.device_put(jnp.asarray(freq, self.rdt), repl),
                os_d, jax.device_put(key, repl), ts_d, beam_d)
            return J, {"res_0": r0, "res_1": r1, "mean_nu": mnu}
        return solve

    def _precess_sources(self, log=print):
        """Apply J2000 -> epoch-of-date precession to the device sky's
        (ra, dec) and the beam pointing (data.cpp:1473 semantics: the
        rotation is evaluated at the first tile's mid-timeslot JD)."""
        import dataclasses
        try:
            t0 = self.ms.read_tile(0)
        except Exception:
            t0 = None
        tj = None if t0 is None else t0.time_jd
        if tj is None:
            return      # placeholder-epoch warning fires in _tile_beam
        jd = float(np.asarray(tj)[len(np.asarray(tj)) // 2])
        pmat = coords.precession_matrix(jd)
        ra_p, dec_p = coords.precess_radec_std(self.dsky.ra, self.dsky.dec,
                                               pmat)
        self.dsky = self.dsky._replace(ra=ra_p, dec=dec_p)
        b_ra, b_dec = coords.precess_radec_std(
            jnp.asarray(self.beam_info.ra0, self.rdt),
            jnp.asarray(self.beam_info.dec0, self.rdt), pmat)
        self.beam_info = dataclasses.replace(
            self.beam_info, ra0=float(b_ra), dec0=float(b_dec))
        self.precessed = True
        log(f"Precessed source/beam coordinates to JD {jd:.5f}")

    def _tile_beam(self, tile, ti=None):
        """A tile's device beam: the leaves staged at construction with
        this tile's ``gmst`` track, the one leaf that changes."""
        if not self.dobeam:
            return None
        if tile.time_mjd is None and not self._warned_no_times:
            self.log("WARNING: dataset tiles carry no timestamps; beam "
                     "az/el will be evaluated at the J2000 placeholder epoch")
            self._warned_no_times = True
        with dtrace.phase("beam", tile=ti):
            tj = tile.time_jd
            gmst = coords.jd2gmst_np(
                self.beam_info.time_jd if tj is None else tj)
            return self._beam_static._replace(
                gmst=jnp.asarray(gmst, self.rdt))

    def _correct_idx(self):
        """-k cluster id -> padded-array index (or None)."""
        from sagecal_tpu import skymodel
        return skymodel.correct_cluster_index(
            self.sky, self.cfg.correct_cluster)

    def _residuals(self, J_r8, x_r, u, v, w, sta1, sta2, beam=None,
                   freqs=None, out_dtype=None):
        """Residuals over ``freqs`` (default: all channels; a single
        [1] freq gives the per-channel -b 1 path, fullbatch_mode.cpp:483)."""
        meta = self.ms.meta
        if freqs is None:
            freqs = jnp.asarray(meta["freqs"], self.rdt)
        sub = jnp.asarray(self.sky.subtract_mask())
        # storage-dtype writeback emission: the donated x_r slot and
        # this output share shape AND dtype, so the ring keeps working
        # and the d->h readback ships storage bytes (rr doc)
        return rr.calculate_residuals_pairs(
            self.dsky, J_r8, x_r, u, v, w, freqs,
            meta["fdelta"] / len(meta["freqs"]), sta1, sta2,
            jnp.asarray(self.cidx), sub,
            out_dtype=self.sdt if out_dtype is None else out_dtype,
            correct_idx=self._correct_idx(), rho=self.cfg.mmse_rho,
            beam=beam, dobeam=self.dobeam, tslot=jnp.asarray(self.tslot),
            phase_only=self.cfg.phase_only,
            row_period=int(meta["nbase"]))

    def _chan_residual(self, J_r8, x_r, u, v, w, sta1, sta2, freq, beam):
        # the -b 1 channel path assembles its residuals host-side with
        # numpy (no ml_dtypes support), so it keeps the pipeline dtype
        return self._residuals(J_r8, x_r, u, v, w, sta1, sta2, beam,
                               freqs=freq[None], out_dtype=self.rdt)

    def _build_chan_residual(self):
        """All channels' residuals in one program (vmap over channels)."""
        return self._jit_cached("chan_residual", lambda: jax.jit(jax.vmap(
            self._chan_residual,
            in_axes=(0, 0, None, None, None, None, None, 0, None))))

    def _build_chan_solver(self):
        """Per-channel bandpass solve (-b 1, fullbatch_mode.cpp:442-488):
        LBFGS-only joint fit at ONE channel, warm-started from the joint
        solution. All channels are independent (each warm-starts from the
        same joint p, fullbatch_mode.cpp:456 memcpy) so the whole channel
        axis solves as ONE vmapped program instead of the reference's
        sequential per-channel loop."""
        meta = self.ms.meta
        fdelta_chan = meta["fdelta"] / len(meta["freqs"])
        cidx = jnp.asarray(self.cidx)
        cmask = jnp.asarray(self.cmask)
        scfg = self.base_cfg._replace(max_lbfgs=self.cfg.max_lbfgs)

        def solve(x8, wt, freq, u, v, w, sta1, sta2, J0_r8, beam):
            if self.use_pallas:
                pg, rest = self._pallas_skies
                coh = rp.coherencies_split(pg, rest, u, v, w, freq[None],
                                           fdelta_chan,
                                           per_channel_flux=True)[:, :, 0]
            else:
                coh = rp.coherencies(self.dsky, u, v, w, freq[None],
                                     fdelta_chan, per_channel_flux=True,
                                     beam=beam, dobeam=self.dobeam,
                                     tslot=jnp.asarray(self.tslot),
                                     sta1=sta1, sta2=sta2)[:, :, 0]
            J, info = sage.bfgsfit(x8, coh, sta1, sta2, cidx,
                                   ne.jones_r2c(J0_r8), self.n, wt,
                                   config=scfg, nu=self.cfg.robust_nulow)
            return ne.jones_c2r(J), info["res_0"], info["res_1"]

        return self._jit_cached(
            "chan_solver", lambda: jax.jit(jax.vmap(
                solve, in_axes=(0, 0, 0, None, None, None, None, None,
                                None, None))),
            int(self.cfg.max_lbfgs), float(self.cfg.robust_nulow))

    def initial_jones(self) -> np.ndarray:
        M = self.sky.n_clusters
        J0 = np.tile(np.eye(2, dtype=np.complex128),
                     (M, self.kmax, self.n, 1, 1))
        if self.cfg.init_solutions:
            Jq = sol.read_warm_start(self.cfg.init_solutions, self.sky,
                                     self.n)
            if Jq is not None:
                J0 = Jq
        return J0

    # -- warm-start prior store (sagecal_tpu.serve.priors) -----------------

    def _interval_times(self, ti: int) -> np.ndarray:
        """Mid-times (seconds from observation start) of tile ``ti``'s
        ``kmax`` solve intervals — the temporal axis the prior store
        interpolates stored chains on. Clusters with fewer than kmax
        chunks are seeded on the kmax grid anyway (their extra k
        columns are masked out of the solve by ``cmask``)."""
        meta = self.ms.meta
        span = float(meta["tilesz"]) * float(meta["tdelta"])
        return (float(ti)
                + (np.arange(self.kmax) + 0.5) / self.kmax) * span

    def prior_key(self) -> str | None:
        """This run's key in the solution prior store: sky/cluster
        content digest + station count + band center + solver family
        (priors.prior_key). Cached; None = unkeyable (no seeding, no
        banking — never an error)."""
        if not hasattr(self, "_prior_key"):
            self._prior_key = ppriors.prior_key(
                self.cfg.sky_model, self.cfg.cluster_file, self.n,
                self.ms.meta["freq0"],
                ppriors.solver_family(
                    self.cfg.solver_mode,
                    getattr(self.cfg, "jones_mode", "full")))
        return self._prior_key

    def prior_initial_jones(self, start_tile: int = 0):
        """Warm J0 seed [M, kmax, N, 2, 2] interpolated from a banked
        same-key solution, or None (cold start — a miss, a refusal,
        or prior_cache off). An explicit ``-q`` init_solutions file
        always wins: that is the operator's seed, not the cache's."""
        mode = getattr(self.cfg, "prior_cache", "off")
        if not ppriors.reads(mode) or self.cfg.init_solutions:
            return None
        J0, _rho = ppriors.PRIORS.seed(
            self.prior_key(), self._interval_times(start_tile),
            self.ms.meta["freq0"], self.n, self.sky.n_clusters,
            jones_mode=getattr(self.cfg, "jones_mode", "full"))
        return J0

    # -- overlapped execution (sagecal_tpu.sched) --------------------------

    def _prefetch_depth(self, prefetch) -> int:
        """Effective overlap depth: the per-call override, else the run
        config's --prefetch (default 1 = double-buffered)."""
        if prefetch is None:
            prefetch = getattr(self.cfg, "prefetch", 1)
        return max(0, int(prefetch))

    def _tile_source(self, stage_fn, max_tiles, depth, start=0,
                     stream=None):
        """Yield ``(ti, tile, staged, io_wait_s)`` with read + host
        staging running ``depth`` tiles ahead on a background thread
        (depth 0: inline — the synchronous reference path). The io
        wait is the consumer's bubble; the thread's own read+stage
        time is emitted as a ``bg``-tagged "read" phase and its
        wait-for-arrival (pacing or a live transport) as
        ``arrival_wait`` — never folded into io. ``start``: first tile
        to produce (checkpoint resume skips completed tiles); the
        produced payload carries the ABSOLUTE tile id. ``stream``: a
        :class:`sagecal_tpu.stream.TileStream` — production then runs
        OPEN-ENDED (tile count unknown; the transport's EndOfStream is
        the end) and each staged payload carries the tile's arrival
        stamp for the arrival-to-write latency SLO."""
        if stream is not None:
            def produce(_j, _strm=stream):
                i, tile, t_arr = _strm.take()
                stg = stage_fn(i, tile)
                stg["_t_arrival"] = t_arr
                return i, tile, stg

            pf = sched.Prefetcher(produce, None, depth=depth,
                                  arrive=stream.wait_next, tile0=start)
        else:
            n = self.ms.n_tiles
            if max_tiles is not None:
                n = min(n, max_tiles)

            def produce(j):
                i = start + j
                tile = self.ms.read_tile(i)
                return i, tile, stage_fn(i, tile)

            pf = sched.Prefetcher(
                produce, max(0, n - start), depth=depth, tile0=start,
                pace_s=getattr(self.cfg, "tile_arrival_s", 0.0))
        # the consumer's "io" phase (its wait for each item) is the
        # Prefetcher's own, tile = start + j
        for _j, (ti, tile, stg), wait in pf:
            yield ti, tile, stg, wait

    def _write_residual_tile(self, ti, tile, res_r, bg=True):
        """Fetch the residual buffer (already copy-to-host-async'd on
        the overlapped path) and write the MS tile. Runs as the
        writer-thread job under overlap (``bg=True``) or inline on the
        synchronous path; the "write" phase covers fetch + disk so the
        sync attribution shows the full data-movement stall."""
        t_write = time.perf_counter()
        with dtrace.phase("write", tile=ti, bg=bg):
            # residual_fetch: the d->h readback chaos seam; this whole
            # method runs as one idempotent writer job (pure fetch +
            # atomic MS write), so the writer retry layer recovers a
            # transient fault here
            faults.inject("residual_fetch", key=ti)
            # what blocks on the residual program's execution, apart
            # from the copy and the disk
            sched.wait_device(res_r)
            n_rows = tile.x.shape[0]
            with dtrace.phase("convert"):
                # fetch through float64: numpy-side r2c on ml_dtypes
                # bf16 arrays is not supported, and the MS stores
                # complex128
                x = utils.r2c(np.asarray(res_r, np.float64)).astype(
                    np.complex128)
                # tile-bucket padding rows (zero weight, never solved
                # on) are sliced off before the MS sees them
                tile.x = x[:n_rows]
            self._put_tile(ti, tile)
        obs.observe("tile_write_seconds", time.perf_counter() - t_write)

    def _put_tile(self, ti, tile):
        """A tile handed to the dataset under "put": a child of "write",
        the root of a writer job that has nothing to convert."""
        with dtrace.phase("put", tile=ti):
            self.ms.write_tile(ti, tile)

    def _run_batched(self, write_residuals, solution_path, max_tiles, log,
                     prefetch=None):
        """--tile-batch>1 fullbatch driver: tile 0 (and every re-armed
        boost tile after a divergence reset) solves solo, then groups of
        T tiles solve as ONE vmapped program (sagefit_host_tiles); the
        stream tail runs solo. Semantics vs the sequential driver: each
        tile in a group warm-starts from the solution carried into the
        group (batch-granular warm start) — everything else (PRNG
        streams, residual math, divergence resets, solution writing)
        matches tile for tile."""
        cfg, ms, sky = self.cfg, self.ms, self.sky
        meta = ms.meta
        from sagecal_tpu.solvers import robust as rb
        T = self.tile_batch
        depth = self._prefetch_depth(prefetch)
        pinit = self.initial_jones()
        writer = None
        if solution_path:
            writer = sol.SolutionWriter(
                solution_path, meta["freq0"], meta["fdelta"],
                meta["tilesz"] * meta["tdelta"] / 60.0, self.n,
                sky.n_clusters, sky.n_eff_clusters)
        history = []
        state = {"J": pinit.copy(), "first": True, "res_prev": None}
        pending = []
        # donated-staging ring: up to T pending + depth prefetched +
        # in-flight slots hold a staged residual input concurrently
        ring = sched.DonatedRing(T + depth + 2)
        aw = sched.AsyncWriter(enabled=depth > 0)

        def stage(ti, tile):
            t_stage = time.perf_counter()
            with dtrace.phase("stage", tile=ti, bg=depth > 0):
                out = stage_tile(ti, tile)
            obs.observe("tile_stage_seconds",
                        time.perf_counter() - t_stage)
            return out

        def stage_tile(ti, tile):
            u = jnp.asarray(tile.u, self.rdt)
            v = jnp.asarray(tile.v, self.rdt)
            w = jnp.asarray(tile.w, self.rdt)
            x8_np, rowflags, _good = tile.solve_input(uvtaper_m=cfg.uvtaper)
            # staged in the dtype-policy storage dtype: the prefetcher
            # and the solve both ship sdt bytes (sdt == rdt at "f32")
            x8 = jnp.asarray(x8_np, self.sdt)
            flags = rp.uvcut_flags(jnp.asarray(rowflags, jnp.int32), u, v,
                                   jnp.asarray(tile.freqs, self.rdt),
                                   cfg.uvmin, cfg.uvmax)
            if cfg.whiten:
                x8 = rb.whiten_data(x8, u, v, meta["freq0"])
            out = dict(ti=ti, tile=tile, u=u, v=v, w=w, x8=x8,
                       wt=lm_mod.make_weights(flags, self.sdt),
                       sta1=jnp.asarray(tile.sta1),
                       sta2=jnp.asarray(tile.sta2),
                       # staged once: solve + residual write reuse it
                       beam=self._tile_beam(tile, ti), bubble=0.0)
            if write_residuals:
                # the residual program DONATES its staged visibility
                # input; the ring keeps overlapped staging from ever
                # aliasing an in-flight donated buffer
                ring.stage(ti, jnp.asarray(utils.c2r(tile.x), self.sdt))
            return out

        def post(stg, res_0, res_1, mean_nu, Jnew, minutes):
            ti, tile = stg["ti"], stg["tile"]
            if res_1 == 0.0 or not np.isfinite(res_1) or (
                    state["res_prev"] is not None
                    and res_1 > RES_RATIO * state["res_prev"]):
                log(f"tile {ti}: Resetting Solution")
                if res_1 != 0.0:    # zero = flagged data, not divergence
                    self._inflight_downgrade(log)
                state["J"] = pinit.copy()
                state["first"] = True
                state["res_prev"] = res_1 if np.isfinite(res_1) else None
            else:
                state["J"] = Jnew
                state["res_prev"] = (res_1 if state["res_prev"] is None
                                     else min(state["res_prev"], res_1))
            if writer:
                stg["bubble"] += aw.submit(
                    _write_solutions, writer, ti,
                    state["J"] if state["first"] else Jnew, sky.nchunk)
            if write_residuals:
                with dtrace.phase("residual", tile=ti):
                    res_r = self._residual_fn(
                        jnp.asarray(utils.jones_c2r_np(
                            state["J"] if state["first"] else Jnew),
                            self.rdt),
                        ring.take(ti),
                        stg["u"], stg["v"], stg["w"], stg["sta1"],
                        stg["sta2"], stg["beam"])
                if depth > 0:
                    # start the non-blocking device->host copy, hand
                    # fetch + MS write to the ordered writer thread
                    sched.start_host_copy(res_r)
                # depth 0 runs the same job inline through submit —
                # one path, so the transient-retry layer covers both
                stg["bubble"] += aw.submit(
                    self._write_residual_tile, ti, tile, res_r,
                    bg=depth > 0)
            log(f"Timeslot: {ti} Residual: initial={res_0:.6g}, "
                f"final={res_1:.6g}, Time spent={minutes:.3g} minutes, "
                f"nu={mean_nu:.2f}")
            history.append({"tile": ti, "res_0": res_0, "res_1": res_1,
                            "mean_nu": mean_nu, "minutes": minutes})
            _emit_tile_record(ti, res_0, res_1, mean_nu, None, minutes,
                              bubble_s=stg["bubble"], overlap=depth,
                              cmask=self.cmask, coh=self.coh_record)

        def solve_solo(stg, boosted):
            t0 = time.time()
            solver = self._solve_first if boosted else self._solve_rest
            J_r8 = jnp.asarray(utils.jones_c2r_np(state["J"]), self.rdt)
            with dtrace.phase("solve", tile=stg["ti"]):
                Jd_r8, info = solver(
                    stg["x8"], stg["u"], stg["v"], stg["w"], stg["sta1"],
                    stg["sta2"], stg["wt"], J_r8, stg["beam"],
                    tile_idx=stg["ti"])
            obs.observe("tile_solve_seconds", time.time() - t0)
            state["first"] = False
            post(stg, float(info["res_0"]), float(info["res_1"]),
                 float(info["mean_nu"]),
                 utils.jones_r2c_np(np.asarray(Jd_r8)),
                 (time.time() - t0) / 60.0)

        def flush(group):
            if not group:
                return
            if len(group) < T:
                for stg in group:
                    solve_solo(stg, boosted=False)
                return
            t0 = time.time()
            J0 = np.broadcast_to(
                utils.jones_c2r_np(state["J"]),
                (T,) + utils.jones_c2r_np(state["J"]).shape).copy()
            beamT = None
            if self.dobeam:
                beamT = group[0]["beam"]._replace(
                    gmst=jnp.stack([g["beam"].gmst for g in group]))
            with dtrace.phase("solve", tiles=T):
                Jd, info = self._solve_tiles(
                    jnp.stack([g["x8"] for g in group]),
                    jnp.stack([g["u"] for g in group]),
                    jnp.stack([g["v"] for g in group]),
                    jnp.stack([g["w"] for g in group]),
                    group[0]["sta1"], group[0]["sta2"],
                    jnp.stack([g["wt"] for g in group]),
                    J0, [g["ti"] for g in group], beamT=beamT)
                Jd = np.asarray(Jd)
                r0 = np.asarray(info["res_0"])
                r1 = np.asarray(info["res_1"])
                mnu = np.asarray(info["mean_nu"])
            if obs.active():
                # one amortized observation PER TILE, so the histogram
                # count stays equal to tiles_solved_total under
                # --tile-batch too
                dur = (time.time() - t0) / T
                for _ in range(T):
                    obs.observe("tile_solve_seconds", dur)
            minutes = (time.time() - t0) / 60.0 / T
            for t, stg in enumerate(group):
                post(stg, float(r0[t]), float(r1[t]), float(mnu[t]),
                     utils.jones_r2c_np(Jd[t]), minutes)

        try:
            for ti, tile, stg, io_wait in self._tile_source(
                    stage, max_tiles, depth):
                aw.check()      # writer failure -> fail at the boundary
                stg["bubble"] += io_wait
                if state["first"]:
                    solve_solo(stg, boosted=True)
                    continue
                pending.append(stg)
                if len(pending) == T:
                    flush(pending)
                    pending = []
        finally:
            try:
                flush(pending)
            finally:
                aw.close()
                if writer:
                    writer.close()
        return history

    def stepper(self, write_residuals: bool = True, solution_path=None,
                max_tiles=None, log=print, prefetch=None,
                trace_ctx=None, on_diverge: str = "reset",
                open_ended: bool = False) -> "TileStepper":
        """The sequential driver as a resumable per-tile unit: the
        serve scheduler owns ``stage``/``step``/``close`` and may
        interleave many jobs' tiles through one device while each
        job's warm-start/PRNG chain stays sequential inside its own
        :class:`TileStepper`. ``on_diverge``: the divergence policy —
        "reset" (the reference's solution reset) or "quarantine" (keep
        the last-good chain, flag the tile; serve jobs select it per
        submission)."""
        return TileStepper(self, write_residuals=write_residuals,
                           solution_path=solution_path,
                           max_tiles=max_tiles, log=log,
                           depth=self._prefetch_depth(prefetch),
                           trace_ctx=trace_ctx, on_diverge=on_diverge,
                           open_ended=open_ended)

    def run(self, write_residuals: bool = True, solution_path=None,
            max_tiles=None, log=print, prefetch=None, stream=None):
        """``prefetch``: overlap depth override (None = cfg.prefetch;
        0 = the synchronous reference loop). Outputs are bit-identical
        across depths — only data movement overlaps; the warm-start
        solve chain stays sequential (tests/test_overlap.py).
        ``stream``: a live :class:`sagecal_tpu.stream.TileStream` —
        tiles come from the transport (open-ended, arrival-stamped)
        and each one is checked against the per-tile deadline at step
        entry (MIGRATION.md "Streaming mode")."""
        if stream is not None:
            return self._run_stream(stream, write_residuals,
                                    solution_path, log, prefetch)
        if getattr(self, "batch_ok", False):
            if getattr(self.cfg, "resume", False):
                # the batched driver's warm start is batch-granular;
                # a tile-granular checkpoint cannot reproduce it
                log("resume: unsupported on the --tile-batch driver; "
                    "starting fresh")
            return self._run_batched(write_residuals, solution_path,
                                     max_tiles, log, prefetch)
        depth = self._prefetch_depth(prefetch)
        st = self.stepper(write_residuals, solution_path, max_tiles,
                          log, prefetch=depth)
        # --profile: capture an XLA/device timeline of ONE WARM solve
        # interval (SURVEY.md section 5 tracing — the reference has only
        # wall-clock prints; a jax.profiler trace is the superset): the
        # first tile that follows a tile in which nothing was traced or
        # compiled. Bounded to one tile so trace size stays sane.
        prof = _WarmTileProfile(getattr(self.cfg, "profile_dir", None),
                                log)
        try:
            for ti, tile, stg, io_wait in self._tile_source(
                    st.stage, max_tiles, depth, start=st.start_tile):
                prof.enter_tile(ti)
                st.step(ti, tile, stg, io_wait)
                prof.leave_tile(ti)
        finally:
            try:
                st.close()
            finally:
                prof.stop()     # abnormal exit: close a live trace
        return st.history

    def _run_stream(self, stream, write_residuals=True,
                    solution_path=None, log=print, prefetch=None):
        """Direct (non-serve) streaming driver: open-ended stepping
        over a live :class:`TileStream`, with the per-tile deadline /
        lateness policy applied at each step entry. The serve
        scheduler runs the same seam through poll(); this path is the
        single-job reference (and the bit-identity audit target: with
        no late degradations the outputs match a batch run of the same
        tiles exactly)."""
        depth = self._prefetch_depth(prefetch)
        st = self.stepper(write_residuals, solution_path, None, log,
                          prefetch=depth, open_ended=True)
        try:
            for ti, tile, stg, io_wait in self._tile_source(
                    st.stage, None, depth, stream=stream):
                _late, degrade = stream_tile_late(self.cfg, ti, stg)
                st.step(ti, tile, stg, io_wait, degrade=degrade)
        finally:
            try:
                st.close()
            finally:
                stream.close()
        return st.history

    def run_simulation(self, log=print):
        """Simulation modes -a 1/2/3 (fullbatch_mode.cpp:524-578)."""
        cfg, ms, sky = self.cfg, self.ms, self.sky
        meta = ms.meta
        mode = int(cfg.simulation)
        blocks_iter = None
        ignore_mask = None
        if cfg.solutions_file:
            _, blocks = sol.read_solutions(cfg.solutions_file, sky.nchunk)
            blocks_iter = blocks
        # -z is honoured without -p too (upstream reads the list only
        # beside a solutions file: fullbatch_mode.cpp:524-578)
        if cfg.ignore_clusters_file:
            ignore = skymodel.read_ignore_list(cfg.ignore_clusters_file)
            ignore_mask = np.array(
                [int(cid) not in ignore for cid in sky.cluster_ids])
        clusters_in_model = (sky.n_clusters if ignore_mask is None
                             else int(ignore_mask.sum()))

        def sim_fn(x_r, u, v, w, sta1, sta2, J_r8, beam):
            # pairs in and pairs out: rr.simulate_pairs says why
            return rr.simulate_pairs(
                self.dsky, x_r, u, v, w,
                jnp.asarray(meta["freqs"], self.rdt),
                meta["fdelta"] / len(meta["freqs"]), sta1, sta2,
                mode=mode, J=J_r8,
                chunk_idx=jnp.asarray(self.cidx), ignore_mask=ignore_mask,
                beam=beam, dobeam=self.dobeam,
                tslot=jnp.asarray(self.tslot),
                row_period=int(meta["nbase"]))

        # keyed through the process-wide program cache (serve/cache.py)
        # instead of the old per-instance lazy attribute: a second job
        # in the same process used to re-trace every tile shape, and a
        # REUSED pipeline could serve a stale ignore_mask closure — the
        # key tokens the sim mode and the ignore mask (the content key
        # already covers sky/shape/dtype), so neither can happen
        self._sim_jit = self._jit_cached(
            "sim", lambda: jax.jit(sim_fn),
            pcache.token(ignore_mask, mode))
        sim_jit = self._sim_jit
        # the calibrate path's overlap under the calibrate path's
        # vocabulary. At --prefetch 0 a synchronous loop: "io" (the
        # next() on the dataset's tiles), then under the root "step"
        # stage / predict (a dispatch) / fetch (the wait for the device
        # and the copy) / write. At depth N the reader thread reads and
        # stages N tiles ahead ("read" and "stage", bg), a tile's
        # program is dispatched BEFORE the one before it is waited for
        # (one runs, one is queued behind it: the chip goes from one to
        # the next with no host in between), and the conversion and the
        # write go to the ordered writer ("write", bg). Either way the
        # loop's thread holds one root "io" and one root "step" a tile
        # (overlapped, the last tile's program is waited for under a
        # root "drain" once the dataset has ended), and a tile is on
        # disk, in the order read, when this returns. Per tile one
        # ``tile`` record: bubble_s is what the loop's thread was
        # blocked on data movement, the io wait plus the write (depth
        # 0) or plus the writer's back-pressure.
        # No more than two tiles ahead and one write queued, whatever
        # --prefetch says: with the tile being staged, the two whose
        # programs are dispatched and the one being written that is
        # depth + 5 <= 7 tiles between the read and the disk, under the
        # eight disk tiles the benchmark's smallest dataset cycles
        depth = min(self._prefetch_depth(None), 2)
        bg = depth > 0      # stage and write are other threads'
        scopes = _thread_scopes()
        aw = sched.AsyncWriter(enabled=bg, maxsize=1, context=scopes)

        def stage(ti, tile):
            # transfers alone: an eager jnp operation here would queue
            # behind the program that runs (sched.py, PERF.md section 5)
            with dtrace.phase("stage", tile=ti, bg=bg):
                with dtrace.phase("pack"):
                    J_r8 = None
                    if blocks_iter:
                        J_r8 = utils.jones_c2r_np(
                            blocks_iter[min(ti, len(blocks_iter) - 1)])
                    x_r = utils.c2r(tile.x)
                with dtrace.phase("copy"):
                    if J_r8 is not None:
                        J_r8 = jnp.asarray(J_r8, self.rdt)
                    args = (jnp.asarray(x_r, self.rdt),
                            jnp.asarray(tile.u, self.rdt),
                            jnp.asarray(tile.v, self.rdt),
                            jnp.asarray(tile.w, self.rdt),
                            jnp.asarray(tile.sta1),
                            jnp.asarray(tile.sta2), J_r8)
                return args + (self._tile_beam(tile, ti),)

        # ms.tiles() is the seam a dataset overrides, so the reader
        # pulls it and does not call read_tile(i)
        rows = iter(ms.tiles())
        pulled = []     # the tile read and not yet staged, or what
        #                 the read raised

        def produce(_j):
            # the Prefetcher tries a transient failure again: the read
            # is made once and kept until the tile is staged, so that a
            # second try stages the SAME tile; and a generator that
            # raised has ended, its next() would read as the end of the
            # data, so what it raised is raised again
            if not pulled:
                try:
                    pulled.append(next(rows))
                except StopIteration:
                    raise sched.EndOfStream from None
                except BaseException as e:
                    pulled.append(e)
            if isinstance(pulled[0], BaseException):
                raise pulled[0]
            ti, tile = pulled[0]
            # depth 0: the read alone is "io", the step stages
            args = stage(ti, tile) if bg else None
            pulled.clear()
            return ti, tile, args

        def write(ti, tile, out):
            with dtrace.phase("write", tile=ti, bg=bg) as ph:
                with dtrace.phase("convert"):
                    tile.x = utils.r2c(out).astype(np.complex128)
                self._put_tile(ti, tile)
            return ph.dur_s

        def finish(ti, tile, out_r, io_wait):
            with dtrace.phase("fetch", tile=ti):
                # blocked on the program's execution; the copy is
                # fetch's own (begun at the dispatch when overlapped)
                sched.wait_device(out_r)
                out = np.asarray(out_r)
            blocked = (aw.submit(write, ti, tile, out) if bg
                       else write(ti, tile, out))
            if dtrace.active():
                dtrace.emit("tile", tile=ti, overlap=depth,
                            bubble_s=io_wait + blocked, mode=mode,
                            clusters_in_model=clusters_in_model,
                            **self.coh_record)
            log(f"Timeslot: {ti} simulated (mode={mode})")

        source = sched.Prefetcher(produce, None, depth=depth,
                                  context=scopes)
        try:
            flying = None       # the tile dispatched and not yet fetched
            for _j, (ti, tile, args), io_wait in source:
                aw.check()      # writer failure -> fail at the boundary
                with dtrace.phase("step", tile=ti):
                    if args is None:
                        args = stage(ti, tile)
                    with dtrace.phase("predict"):
                        out_r = sim_jit(*args)
                        if bg:
                            # now, so that the copy does not queue
                            # behind the next tile's program
                            sched.start_host_copy(out_r)
                    if flying is not None:
                        finish(*flying)
                    flying = (ti, tile, out_r, io_wait)
                    if not bg:
                        finish(*flying)
                        flying = None
            if flying is not None:
                with dtrace.phase("drain", tile=flying[0]):
                    finish(*flying)
        finally:
            source.close()      # a loop that failed leaves the reader here
            # every write has run when this returns; a failed one raises
            aw.close()


def _write_solutions(writer, ti, J, nchunk):
    """Writer-queue job: one interval's rows of the solutions file,
    under "solutions"."""
    with dtrace.phase("solutions", tile=ti):
        writer.write_interval(J, nchunk)


def _thread_scopes():
    """A zero-arg context factory (``sched``'s ``context=``) that gives a
    reader or a writer thread what is thread-local on the CALLING one:
    the tracer its records go to (``serve`` routes a job's by
    ``dtrace.scope``) and jax's default device (``fleet.device_scope``:
    a reader that stages onto another device costs a silent copy a
    tile). ``run_simulation`` is called inside its job's scopes and
    starts its two threads itself."""
    tracer, device = dtrace.get(), jax.config.jax_default_device

    @contextlib.contextmanager
    def scopes():
        with dtrace.scope(tracer), jax.default_device(device):
            yield
    return scopes


class _WarmTileProfile:
    """``cli --profile DIR``: a ``jax.profiler`` trace of one warm tile.

    A tile is warm when the tile before it logged no trace, lowering or
    compile (``diag.guard.compiles_logged``): the first tile compiles,
    a promoted program's first run compiles again, and a trace of
    either is a trace of the compiler. With no directory every method
    returns at its first test. While the trace runs, ``dtrace.phase``
    annotates its spans (``sagecal/<name>``) even without ``--diag``.
    A run whose every tile compiled writes no trace, and says so."""

    def __init__(self, prof_dir, log):
        self.dir, self.log = prof_dir, log
        self.state = "cold" if prof_dir else "done"
        self._n = 0

    def enter_tile(self, ti):
        if self.state == "done":
            return
        from sagecal_tpu.diag import guard
        if self.state == "armed":
            import jax.profiler
            jax.profiler.start_trace(self.dir)
            dtrace.set_profiling(True)
            self.state = "live"
            self.log(f"profiling solve interval {ti} (the first after "
                     f"a tile that compiled nothing) -> {self.dir}")
        self._n = guard.compiles_logged()

    def leave_tile(self, ti):
        if self.state == "done":
            return
        from sagecal_tpu.diag import guard
        if self.state == "live":
            self.stop()
            self.log(f"profile trace written to {self.dir}")
        elif guard.compiles_logged() == self._n:
            self.state = "armed"

    def stop(self):
        if self.state == "live":
            import jax.profiler
            jax.profiler.stop_trace()
            dtrace.set_profiling(False)
        elif self.state != "done":
            self.log(f"--profile: no tile followed a tile that compiled "
                     f"nothing; no trace written to {self.dir}")
        self.state = "done"


def stream_tile_late(cfg, ti, stg, key=None):
    """Per-tile deadline check at STEP ENTRY (streaming jobs): a tile
    whose arrival-to-now age already exceeds ``tile_deadline_s`` — or
    that the ``tile_late`` chaos point forces late — is counted
    (``stream_tiles_late_total``) and, under ``late_policy="degrade"``,
    degraded to the last-good-Jones writeback instead of solved. A
    late tile NEVER stalls the stream. Returns ``(late, degrade)``.
    Degradation is unsupported under per-channel BFGS (its residual
    path re-solves; there is no staged last-good writeback), so that
    combination counts only."""
    t_arr = stg.get("_t_arrival")
    ddl = float(getattr(cfg, "tile_deadline_s", 0.0) or 0.0)
    late = faults.fires("tile_late", key=ti if key is None else key)
    if not late and ddl > 0.0 and t_arr is not None:
        late = (time.monotonic() - t_arr) > ddl
    if not late:
        return False, False
    obs.inc("stream_tiles_late_total")
    degrade = (getattr(cfg, "late_policy", "degrade") == "degrade"
               and not cfg.per_channel_bfgs)
    return True, degrade


class TileStepper:
    """One job's resumable per-tile execution unit (sequential driver).

    The serve scheduler's contract (serve/scheduler.py): ``stage(ti,
    tile)`` may run on a background reader thread; ``step(ti, tile,
    staged, io_wait)`` runs on the device-owner thread, strictly in
    tile order *within this job*; ``close()`` flushes the job's
    ordered writer and solution file. All mutable solve state (the
    warm-start Jones chain, divergence-reset bookkeeping, the donated
    staging ring, the per-job AsyncWriter) lives HERE, so interleaving
    tiles from many jobs through one device changes nothing about any
    single job's chain — per-job outputs are bit-identical to a solo
    ``FullBatchPipeline.run`` by construction (and by gate,
    tests/test_serve.py).
    """

    def __init__(self, pipe: "FullBatchPipeline", write_residuals=True,
                 solution_path=None, max_tiles=None, log=print,
                 depth: int = 0, trace_ctx=None,
                 on_diverge: str = "reset", open_ended: bool = False):
        if on_diverge not in ("reset", "quarantine"):
            raise ValueError(f"on_diverge {on_diverge!r}: "
                             "expected 'reset' or 'quarantine'")
        self.p = pipe
        self.log = log
        self.depth = int(depth)
        self.write_residuals = write_residuals
        self.on_diverge = on_diverge
        ms, sky = pipe.ms, pipe.sky
        meta = ms.meta
        self.n_tiles = ms.n_tiles
        if max_tiles:
            self.n_tiles = min(self.n_tiles, int(max_tiles))
        # open-ended (streaming) mode: the tile count is NOT known at
        # start — the transport's EndOfStream is the end, progress is
        # "tiles so far", and checkpoint/resume is disabled: a live
        # stream cannot deterministically re-read its past, so the
        # recovery story is the lateness policy, never a rewind
        # (MIGRATION.md "Streaming mode")
        self.open_ended = bool(open_ended)
        if self.open_ended:
            self.n_tiles = None
        # tile-boundary checkpoint/resume (MIGRATION.md "Fault
        # tolerance"): the sidecar lives next to the solutions file —
        # no solutions file, no checkpoint. The identity meta refuses
        # resuming against a different dataset/sky/solver shape.
        self._ckpt_meta = dict(
            n_tiles=-1 if self.n_tiles is None else int(self.n_tiles),
            n_stations=int(pipe.n),
            n_clusters=int(sky.n_clusters), kmax=int(pipe.kmax),
            tilesz=int(meta["tilesz"]))
        self.ckpt_path = (sol.checkpoint_path(solution_path)
                          if solution_path and not self.open_ended
                          else None)
        ck = None
        if getattr(pipe.cfg, "resume", False) and self.open_ended:
            log("resume: not applicable to a live stream; ignoring")
        elif getattr(pipe.cfg, "resume", False):
            if self.ckpt_path is None:
                log("resume: no solutions file -> no checkpoint; "
                    "starting fresh")
            else:
                ck = sol.load_checkpoint(self.ckpt_path,
                                         expect_meta=self._ckpt_meta)
                if ck is None:
                    log("resume: no checkpoint found; starting fresh")
        self.writer = None
        if solution_path:
            if ck is not None:
                # a kill can land between a solution write and its
                # checkpoint: truncate the file back to the byte
                # watermark of the last CHECKPOINTED interval, then
                # append — the final file is byte-identical to an
                # uninterrupted run's
                size = os.path.getsize(solution_path)
                if size < ck["sol_bytes"]:
                    raise ValueError(
                        f"resume: {solution_path!r} is shorter "
                        f"({size} B) than its checkpoint watermark "
                        f"({ck['sol_bytes']} B); refusing to resume "
                        "from inconsistent state")
                with open(solution_path, "r+") as f:
                    f.truncate(ck["sol_bytes"])
                self.writer = sol.SolutionWriter.open_resume(
                    solution_path, pipe.n)
            else:
                self.writer = sol.SolutionWriter(
                    solution_path, meta["freq0"], meta["fdelta"],
                    meta["tilesz"] * meta["tdelta"] / 60.0, pipe.n,
                    sky.n_clusters, sky.n_eff_clusters)
        self.pinit = pipe.initial_jones()
        self.J = self.pinit.copy()
        self.first = True
        self.res_prev = None
        self.start_tile = 0
        # warm-start prior seed (serve/priors.py): a banked same-key
        # solution replaces the cold identity start and enters the
        # chain as WARM state (first=False — the boosted cold solver
        # exists for identity starts, solvers/sage.py inflight_warm).
        # pinit stays the cold identity: a divergence reset still
        # recovers to the reference start + re-armed boost, so a bad
        # seed costs one reset, never the run. A checkpoint restore
        # (below) overrides the seed — the checkpointed chain IS the
        # job's own state. Under readwrite the post-solve chain is
        # accumulated per tile and banked at a clean close.
        self._prior_mode = getattr(pipe.cfg, "prior_cache", "off")
        self._prior_banked: list = []
        self._prior_res2 = 0.0          # sum |written residual|^2
        self._prior_res_tiles = 0       # over this many banked tiles
        if ck is None:
            Jp = pipe.prior_initial_jones(self.start_tile)
            if Jp is not None:
                self.J = Jp
                self.first = False
                log("prior-cache: J0 seeded from the solution prior "
                    "store (cold identity kept as the divergence-"
                    "reset target)")
        if ck is not None:
            # restore the EXACT chain state at the watermark: the
            # warm-start Jones (full precision — the text file is
            # lossy), the boost/reset flag, the divergence watermark,
            # and a sticky inflight downgrade
            self.start_tile = ck["tile"] + 1
            self.J = ck["J"]
            self.first = ck["first"]
            self.res_prev = ck["res_prev"]
            if ck["inflight"] < pipe.base_cfg.inflight:
                pipe._inflight_downgrade(log)
            log(f"resume: checkpoint at tile {ck['tile']}; skipping "
                f"{self.start_tile}/{self.n_tiles} completed tiles")
        self._last_tile = self.start_tile - 1
        self.history = []
        # donated-staging ring + ordered writer thread (sched): under
        # overlap the next tile reads + stages on a background thread
        # while this one solves, and residual/solution writes drain on
        # the writer thread — strictly in tile order, failures
        # re-raised at the next tile boundary (AsyncWriter.check in
        # step(); per-job, so one job's write failure never touches a
        # neighbour's writer)
        self.ring = sched.DonatedRing(self.depth + 2)
        # trace_ctx: zero-arg diag-scope factory so the writer thread's
        # emits route to the owning job's tracer (serve scheduler)
        self.aw = sched.AsyncWriter(enabled=self.depth > 0,
                                    context=trace_ctx)
        self.stage_xr = write_residuals and not pipe.cfg.per_channel_bfgs

    # -- reader-thread half -------------------------------------------------

    def stage(self, ti, tile):
        t_stage = time.perf_counter()
        with dtrace.phase("stage", tile=ti, bg=self.depth > 0):
            stg = self._stage_tile(ti, tile)
        obs.observe("tile_stage_seconds", time.perf_counter() - t_stage)
        return stg

    def _stage_tile(self, ti, tile):
        p = self.p
        cfg, meta = p.cfg, p.ms.meta
        pad = p.pad_rows
        u_np, v_np, w_np = tile.u, tile.v, tile.w
        sta1_np, sta2_np = tile.sta1, tile.sta2
        # shared staging decision (VisTile.solve_input): native
        # per-channel-flag packing when applicable, plain mean else;
        # stored uv-cut rows survive either way
        # "pack" is the host's arithmetic, "copy" the arrays handed to
        # the device, "dispatch" the eager device operations on them:
        # where the reader's seconds go is read by those three names
        with dtrace.phase("pack"):
            x8_np, rowflags, _good = tile.solve_input(
                uvtaper_m=cfg.uvtaper)
            if pad:
                # tile-bucket padding (serve/cache.py): geometry rows
                # repeat real rows (finite uvw, in-range stations), data
                # rows are zero, and the row flag 1 gives them ZERO
                # weight — they enter no reduction, exactly like the
                # sharded path's mesh padding
                u_np = pcache.pad_rows_repeat(u_np, pad)
                v_np = pcache.pad_rows_repeat(v_np, pad)
                w_np = pcache.pad_rows_repeat(w_np, pad)
                sta1_np = pcache.pad_rows_repeat(sta1_np, pad)
                sta2_np = pcache.pad_rows_repeat(sta2_np, pad)
                x8_np = pcache.pad_rows_zero(x8_np, pad)
                rowflags = np.concatenate(
                    [rowflags, np.ones(pad, np.asarray(rowflags).dtype)])
            xr_np = None
            if self.stage_xr:
                xr_np = utils.c2r(
                    tile.x if not pad else pcache.pad_rows_zero(tile.x,
                                                                pad))
        with dtrace.phase("copy"):
            u = jnp.asarray(u_np, p.rdt)
            v = jnp.asarray(v_np, p.rdt)
            w = jnp.asarray(w_np, p.rdt)
            # dtype-policy storage staging (see the batched driver)
            x8 = jnp.asarray(x8_np, p.sdt)
            rowflags_d = jnp.asarray(rowflags, jnp.int32)
            freqs_d = jnp.asarray(tile.freqs, p.rdt)
            sta1 = jnp.asarray(sta1_np)
            sta2 = jnp.asarray(sta2_np)
            x_r = None if xr_np is None else jnp.asarray(xr_np, p.sdt)
        with dtrace.phase("dispatch", prog="weights"):
            flags = rp.uvcut_flags(rowflags_d, u, v, freqs_d,
                                   cfg.uvmin, cfg.uvmax)
            if cfg.whiten:
                # -W: uv-density whitening of the solve input only
                # (fullbatch_mode.cpp applies whiten_data to the
                # averaged x)
                from sagecal_tpu.solvers import robust as rb
                x8 = rb.whiten_data(x8, u, v, meta["freq0"])
            wt = lm_mod.make_weights(flags, p.sdt)
        # beam_stage: the beam-table staging chaos seam; it fires
        # BEFORE the ring stages this tile's residual input below, so
        # the reader-thread retry can safely re-run the whole stage
        faults.inject("beam_stage", key=ti)
        stg = dict(u=u, v=v, w=w, x8=x8, flags=flags, wt=wt,
                   sta1=sta1, sta2=sta2, beam=p._tile_beam(tile, ti))
        if x_r is not None:
            # residual input staged ahead; DONATED to the residual
            # program (ring: no read-after-donate, no aliasing)
            self.ring.stage(ti, x_r)
        return stg

    # -- device-owner half --------------------------------------------------

    def step(self, ti, tile, stg, io_wait=0.0, degrade=False):
        # the root span of a tile's cycle on the device-owner thread:
        # with the consumer's "io" it covers the cycle (diag/trace.py)
        with dtrace.phase("step", tile=ti):
            return self._step(ti, tile, stg, io_wait, degrade)

    def _step(self, ti, tile, stg, io_wait, degrade):
        p = self.p
        cfg, ms, sky, meta = p.cfg, p.ms, p.sky, p.ms.meta
        log = self.log
        self.aw.check()  # async write failure -> fail at the boundary
        bubble = io_wait
        t0 = time.time()
        # streaming: the transport stamped this tile's arrival; the
        # SLO observation (arrival -> residual durably written) is
        # submitted to the ordered writer AFTER the residual write
        t_arr = stg.pop("_t_arrival", None)
        u, v, w = stg["u"], stg["v"], stg["w"]
        sta1, sta2 = stg["sta1"], stg["sta2"]
        x8, flags, wt = stg["x8"], stg["flags"], stg["wt"]
        tile_beam = stg["beam"]

        degraded = bool(degrade) and not cfg.per_channel_bfgs
        quarantined = False
        if degraded:
            # late-tile degradation (stream_tile_late): the tile
            # missed its per-tile deadline, so its solve is SKIPPED
            # and its solutions/residual come from the LAST-GOOD
            # Jones — the quarantine writeback, triggered by the
            # arrival clock instead of divergence. Bounded staleness
            # for bounded latency; the chain, divergence watermark
            # and boost state stay untouched, exactly as quarantine.
            res_0 = res_1 = mean_nu = float("nan")
            info = None
            log(f"tile {ti}: Late (deadline exceeded; writing "
                "last-good-Jones residual)")
            obs.inc("stream_tiles_degraded_total")
            dtrace.emit("degraded", tile=ti)
        else:
            solver = p._solve_first if self.first else p._solve_rest
            J_prev = self.J          # the last-good chain (quarantine)
            with dtrace.phase("carry"):
                J_r8 = jnp.asarray(utils.jones_c2r_np(self.J), p.rdt)
            t_solve = time.perf_counter()
            # the span ends in the read-backs the step needs anyway
            with dtrace.phase("solve", tile=ti):
                Jd_r8, info = solver(x8, u, v, w, sta1, sta2, wt, J_r8,
                                     tile_beam, tile_idx=ti)
                self.first = False
                with dtrace.phase("wait"):      # blocked on the device
                    res_0 = float(info["res_0"])
                    res_1 = float(info["res_1"])
                    mean_nu = float(info["mean_nu"])
                    Jd_r8 = np.asarray(Jd_r8)
                self.J = utils.jones_r2c_np(Jd_r8)
            obs.observe("tile_solve_seconds",
                        time.perf_counter() - t_solve)
        # solve_nan: the poisoned-tile chaos seam (a NaN/nonfinite
        # residual drives the divergence policy below)
        if not degraded and faults.active() \
                and faults.fires("solve_nan", key=ti):
            res_1 = float("nan")

        # divergence handling (fullbatch_mode.cpp:605-621): res_1 of
        # exactly 0.0 means fully flagged data and always takes the
        # reference reset; a genuinely divergent solve takes the
        # configured policy. A degraded tile never enters it — its
        # (skipped) solve produced nothing to judge.
        diverged = not degraded and (
                res_1 == 0.0 or not np.isfinite(res_1) or (
                    self.res_prev is not None
                    and res_1 > RES_RATIO * self.res_prev))
        if degraded:
            pass
        elif diverged and res_1 != 0.0 and self.on_diverge == "quarantine":
            # quarantine: the poisoned solve never enters the chain —
            # this tile's solutions/residuals come from the LAST-GOOD
            # Jones, the divergence watermark and boost state stay
            # untouched, and the tile is flagged in the diag trace
            # instead of writing poisoned residuals
            quarantined = True
            log(f"tile {ti}: Quarantined (divergent solve "
                f"res_1={res_1:.6g}; continuing from last-good "
                "solutions)")
            self.J = J_prev
            obs.inc("tiles_quarantined_total")
            dtrace.emit("quarantine", tile=ti, res_1=res_1)
        elif diverged:
            log(f"tile {ti}: Resetting Solution")
            if res_1 != 0.0:   # zero = flagged data, not divergence
                p._inflight_downgrade(log)
            self.J = self.pinit.copy()
            self.first = True
            self.res_prev = res_1 if np.isfinite(res_1) else None
        else:
            self.res_prev = (res_1 if self.res_prev is None
                             else min(self.res_prev, res_1))
        if ppriors.writes(self._prior_mode) and not degraded \
                and not quarantined and not diverged:
            # prior-store accumulation: only chain states that the
            # divergence policy accepted — a reset/quarantined tile's
            # J must never be banked as a seed for the next job
            self._prior_banked.append((ti, self.J.copy()))

        if cfg.per_channel_bfgs:
            bubble += self._step_per_channel(ti, tile, stg, info)
        else:
            if self.writer:
                bubble += self.aw.submit(_write_solutions, self.writer,
                                         ti, self.J, sky.nchunk)

            if self.write_residuals:
                with dtrace.phase("residual", tile=ti):
                    with dtrace.phase("carry"):
                        J_r8 = jnp.asarray(utils.jones_c2r_np(self.J),
                                           p.rdt)
                    x_r = self.ring.take(ti)
                    with dtrace.phase("dispatch", prog="residual"):
                        res_r = p._residual_fn(J_r8, x_r, u, v, w, sta1,
                                               sta2, tile_beam)
                if self.depth > 0:
                    # non-blocking d->h copy now; fetch + MS
                    # write on the ordered writer thread
                    sched.start_host_copy(res_r)
                # depth 0 runs the same job inline through submit —
                # one path, so the transient-retry layer covers both
                bubble += self.aw.submit(
                    p._write_residual_tile, ti, tile, res_r,
                    bg=self.depth > 0)
                if ppriors.writes(self._prior_mode) and not degraded \
                        and not quarantined and not diverged:
                    # banked-chain quality rides the same ordered
                    # queue: the UNWEIGHTED norm of the residual this
                    # job writes. The solver's robust res_1 is the
                    # wrong figure here — nu re-weighting IMPROVES it
                    # while the written residual drifts, which is
                    # exactly the degradation the store must refuse
                    bubble += self.aw.submit(
                        self._accum_prior_quality, res_r,
                        tile.x.shape[0])

        if t_arr is not None:
            # the streaming SLO: arrival -> residual durably written.
            # Submitted to the SAME ordered writer queue immediately
            # after this tile's writes, so the stamp is taken only
            # once they landed (depth 0 runs it inline right here)
            self.aw.submit(self._observe_stream_latency, ti, t_arr)

        if self.writer and self.ckpt_path:
            # checkpoint this tile boundary. Submitted to the SAME
            # ordered writer queue AFTER the tile's solution/residual
            # writes: the watermark can only ever name tiles whose
            # outputs durably landed (a failed write skips every later
            # job, checkpoint included — AsyncWriter fail-stop)
            bubble += self.aw.submit(
                self._save_checkpoint,
                dict(tile=ti, J=self.J.copy(), first=self.first,
                     res_prev=self.res_prev,
                     inflight=int(p.base_cfg.inflight)))

        self._last_tile = ti
        dt = (time.time() - t0) / 60.0
        with dtrace.phase("record"):
            if not degraded:
                log(f"Timeslot: {ti} Residual: initial={res_0:.6g}, "
                    f"final={res_1:.6g}, Time spent={dt:.3g} minutes, "
                    f"nu={mean_nu:.2f}")
            rec = {"tile": ti, "res_0": res_0, "res_1": res_1,
                   "mean_nu": mean_nu, "minutes": dt}
            if isinstance(info, dict) and "solver_iters" in info:
                # executed inner-solver trips — the sweeps-to-convergence
                # signal the serve layer aggregates per job (loadgen
                # replay rows) and benchmarks/ reads. The solve already
                # synced on res_0/res_1, so this fetch adds no wait.
                rec["solver_iters"] = int(
                    np.asarray(info["solver_iters"]).sum())
            if quarantined:
                rec["quarantined"] = True
            if degraded:
                rec["degraded"] = True
            self.history.append(rec)
            _emit_tile_record(ti, res_0, res_1, mean_nu, info, dt,
                              bubble_s=bubble, overlap=self.depth,
                              cmask=p.cmask, coh=p.coh_record)
        return rec

    def _observe_stream_latency(self, ti, t_arr):
        """Writer-queue job: the per-tile arrival-to-write latency
        observation (TILE_LAT_BUCKETS ladder — declared at stream
        open). Runs strictly after the tile's residual write by
        AsyncWriter ordering."""
        lat = time.monotonic() - t_arr
        obs.observe("stream_tile_latency_seconds", lat)

    def _accum_prior_quality(self, res_r, n_rows) -> None:
        """Writer-queue job: fold one banked tile's written-residual
        power into the prior-quality accumulator. Runs right after
        the tile's residual write on the same ordered queue, so the
        buffer is already host-side; bucket padding rows (never
        solved on) are sliced off like the MS write does."""
        r = np.asarray(res_r, np.float64)[:n_rows]
        self._prior_res2 += float(np.sum(np.square(r)))
        self._prior_res_tiles += 1

    def _bank_priors(self) -> None:
        """Writer-queue job: bank the completed chain in the solution
        prior store (close() submits it only on a clean completion).
        Best-effort — a store refusal logs and moves on; a finished
        job must never fail on its own write-back."""
        p = self.p
        try:
            tis = [t for t, _ in self._prior_banked]
            Js = np.stack([J for _, J in self._prior_banked])
            T, M, K, N = Js.shape[:4]
            times = np.concatenate(
                [p._interval_times(int(t)) for t in tis])
            # [T, M, K, N, 2, 2] -> [1 band, T*K intervals, M, N, 2, 2]
            Jt = np.transpose(Js, (0, 2, 1, 3, 4, 5)).reshape(
                1, T * K, M, N, 2, 2)
            # quality = mean written-residual power per banked tile
            # (accumulated by _accum_prior_quality on this same
            # ordered queue, so every tile has landed by now): the
            # store's refuse-to-degrade guard — a warm repeat whose
            # chain fits the data worse than the entry it seeded from
            # must not supersede it (generational drift). Runs that
            # write no residuals bank quality-less (always supersede).
            quality = (self._prior_res2 / self._prior_res_tiles
                       if self._prior_res_tiles else None)
            ppriors.PRIORS.bank(p.prior_key(), Jt, times,
                                [float(p.ms.meta["freq0"])],
                                quality=quality,
                                jones_mode=getattr(
                                    p.cfg, "jones_mode", "full"))
        except Exception as e:
            self.log(f"prior-cache: bank skipped ({e})")

    def _save_checkpoint(self, state: dict) -> None:
        """Writer-thread half of the checkpoint: runs strictly after
        this tile's writes, reads the solutions file's live byte
        position (accurate — ``_write_cols`` flushed), and lands the
        sidecar atomically."""
        sol.save_checkpoint(self.ckpt_path,
                            sol_bytes=self.writer.f.tell(),
                            meta=self._ckpt_meta, **state)

    def _step_per_channel(self, ti, tile, stg, info):
        # -b 1: per-channel LBFGS re-solve + per-channel residual
        # (fullbatch_mode.cpp:442-488). Channels are independent
        # (each warm-starts from the same joint solution), so the
        # whole channel axis runs as ONE vmapped solve + ONE
        # vmapped residual program instead of a sequential loop.
        # The last channel's solutions become the carried/written
        # solutions (fullbatch_mode.cpp:485 memcpy).
        p = self.p
        cfg, ms, sky, meta = p.cfg, p.ms, p.sky, p.ms.meta
        bubble = 0.0
        u, v, w = stg["u"], stg["v"], stg["w"]
        sta1, sta2 = stg["sta1"], stg["sta2"]
        wt, flags, tile_beam = stg["wt"], stg["flags"], stg["beam"]
        J0c_r8 = jnp.asarray(utils.jones_c2r_np(self.J), p.rdt)
        flags_np = np.asarray(flags)
        F = len(tile.freqs)
        Bn = tile.x.shape[0]
        x8C = np.zeros((F, Bn, 8))
        xC = np.zeros((F, Bn, 2, 2), np.complex128)
        badC = np.zeros((F, Bn), bool)
        for ci_ch in range(F):
            xc = np.array(tile.x[:, ci_ch])
            # per-channel flags (same data the joint pack path
            # zeroes) + row flags
            bad = flags_np == 1
            if tile.cflags is not None:
                bad = bad | (tile.cflags[:, ci_ch] != 0)
            xc[bad] = 0.0
            x8C[ci_ch] = utils.vis_to_x8(xc)
            xC[ci_ch] = xc
            badC[ci_ch] = bad
        x8C_d = jnp.asarray(x8C, p.rdt)
        if cfg.whiten:
            from sagecal_tpu.solvers import robust as rb
            x8C_d = jax.vmap(
                lambda x: rb.whiten_data(x, u, v, meta["freq0"])
            )(x8C_d)
        # channel-flagged rows carry zero weight in THEIR
        # channel's solve (zeroed data must not pull the fit)
        wtC = wt[None] * jnp.asarray(~badC, p.rdt)[:, :, None]
        freqsC = jnp.asarray(tile.freqs, p.rdt)
        # blocks of channels: one vmapped execution per block bounds
        # each execution for a wide band; the last block is padded
        # (zero weight) to keep one compiled program
        CB = min(F, 16)
        nblk = -(-F // CB)
        Fp = nblk * CB
        if Fp != F:
            padc = Fp - F
            x8C_d = jnp.concatenate(
                [x8C_d, jnp.zeros((padc,) + x8C_d.shape[1:],
                                  x8C_d.dtype)])
            wtC = jnp.concatenate(
                [wtC, jnp.zeros((padc,) + wtC.shape[1:],
                                wtC.dtype)])
            freqsC = jnp.concatenate(
                [freqsC, jnp.full((padc,), freqsC[-1],
                                  freqsC.dtype)])
        JC_blocks, res_blocks = [], []
        x_rC_full = None
        if self.write_residuals:
            # PR 6 known limit made EXPLICIT: the per-channel residual
            # assembly moves axes host-side with numpy, which has no
            # bf16/f16 — this branch stages and ships PIPELINE-dtype
            # bytes regardless of --dtype-policy. One-time warning +
            # diag record of the un-melted traffic, so a service job
            # running -b 1 under a reduced policy never reports byte
            # savings it didn't get.
            x_rC_full = jnp.asarray(utils.c2r(xC[:, :, None]), p.rdt)
            if p.dtype_policy != "f32" and not getattr(
                    p, "_warned_b1_dtype", False):
                p._warned_b1_dtype = True
                unmelted = int(x_rC_full.size) * (
                    np.dtype(p.rdt).itemsize - np.dtype(p.sdt).itemsize)
                self.log(
                    f"dtype-policy {p.dtype_policy}: the -b 1 "
                    "per-channel residual assembly is host-side numpy "
                    "(no bf16/f16) and stays at the pipeline dtype — "
                    f"~{unmelted / 1e6:.1f} MB/tile of residual "
                    "traffic is NOT melted by the storage policy")
            if Fp != F:
                x_rC_full = jnp.concatenate(
                    [x_rC_full,
                     jnp.zeros((Fp - F,) + x_rC_full.shape[1:],
                               x_rC_full.dtype)])
        for blk in range(nblk):
            sl = slice(blk * CB, (blk + 1) * CB)
            JC_b, _, _ = p._chan_solver(
                x8C_d[sl], wtC[sl], freqsC[sl], u, v, w, sta1,
                sta2, J0c_r8, tile_beam)
            JC_blocks.append(np.asarray(JC_b))
            if self.write_residuals:
                res_b = p._chan_residual_fn(
                    JC_b, x_rC_full[sl], u, v, w, sta1, sta2,
                    freqsC[sl], tile_beam)
                res_blocks.append(np.asarray(res_b))
        JC_r8 = np.concatenate(JC_blocks)[:F]
        if self.write_residuals:
            resC = np.concatenate(res_blocks)[:F]
            # [F, B, 1, 2, 2] complex -> [B, F, 2, 2]
            tile.x = np.moveaxis(
                utils.r2c(resC)[:, :, 0], 0, 1
            ).astype(np.complex128)
            bubble += self.aw.submit(p._put_tile, ti, tile)
        self.J = utils.jones_r2c_np(np.asarray(JC_r8[-1]))
        if self.writer:
            bubble += self.aw.submit(_write_solutions, self.writer, ti,
                                     self.J, sky.nchunk)
        return bubble

    def close(self, raise_pending: bool = True):
        """Flush + close the job's writer thread and solution file.
        Re-raises a pending async-write failure (unless told not to —
        the scheduler's failed-job teardown path, where the failure
        was already recorded and a second raise would mask cleanup).
        A COMPLETED run (every tile stepped, writes flushed clean)
        removes its checkpoint sidecar; a failed/killed run keeps it —
        that file IS the ``resume=true`` re-entry point."""
        if raise_pending and self._prior_banked and (
                self.open_ended
                or (self.n_tiles is not None
                    and self._last_tile >= self.n_tiles - 1)):
            # prior-store write-back rides the ORDERED writer thread:
            # submitted after every tile's writes and before the close
            # flush, so a banked prior can only ever name a chain
            # whose outputs durably landed. Open-ended (stream) jobs
            # bank whatever accumulated at their clean close — a live
            # stream has no "last tile", EndOfStream is the end.
            self.aw.submit(self._bank_priors)
        try:
            self.aw.close(raise_pending=raise_pending)
        finally:
            if self.writer:
                self.writer.close()
        if raise_pending and self.ckpt_path \
                and self.n_tiles is not None \
                and self._last_tile >= self.n_tiles - 1:
            try:
                os.remove(self.ckpt_path)
            except OSError:
                pass


def run(cfg: RunConfig, log=print):
    """Open dataset + sky model, dispatch fullbatch or simulation.

    The three run modes of the reference main.cpp:288-299 (fullbatch /
    stochastic / stochastic-consensus) dispatch here; stochastic modes live
    in sagecal_tpu.stochastic. ``stream_source`` set dispatches the
    live-ingest driver (sagecal_tpu.stream; MIGRATION.md "Streaming
    mode") — the transport owns dataset materialization.
    """
    strm = None
    if getattr(cfg, "stream_source", None):
        from sagecal_tpu import stream as tstream
        strm, ms = tstream.open_stream(cfg, log=log)
    else:
        ms = ds.open_dataset(cfg.ms, cfg.ms_list, tilesz=cfg.tile_size,
                             data_column=cfg.input_column,
                             out_column=cfg.output_column)
    meta = ms.meta
    sky = skymodel.read_sky_cluster(cfg.sky_model, cfg.cluster_file,
                                    meta["ra0"], meta["dec0"], meta["freq0"],
                                    cfg.format_3)
    pipe = FullBatchPipeline(cfg, ms, sky, log=log)
    if strm is not None:
        return pipe.run(solution_path=cfg.solutions_file, log=log,
                        stream=strm)
    if cfg.simulation != SimulationMode.OFF:
        return pipe.run_simulation(log=log)
    return pipe.run(solution_path=cfg.solutions_file,
                    max_tiles=cfg.max_timeslots or None, log=log)
