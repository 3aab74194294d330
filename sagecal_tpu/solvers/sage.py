"""SAGE expectation-maximization driver: the central calibration algorithm.

Capability parity with reference ``sagefit_visibilities`` (lmfit.c:778-1043):
per EM iteration, each direction cluster is updated in sequence against a
shared residual — add the cluster's current model back, solve that cluster
per hybrid time chunk, re-subtract. Iteration budget is re-weighted by each
cluster's cost reduction (lmfit.c:859-882: 80% evenly, 20% by share), robust
nu is averaged over clusters (lmfit.c:1002-1017), and a final joint LBFGS
refine polishes all 8*N*Mt parameters (lmfit.c:1019-1037).

Solver-mode dispatch follows lmfit.c:906-962 exactly: modes 1/2/3 run
ordered-subsets LM on every EM iteration except the last, which switches to
plain LM / OS-robust-LM / robust-LM respectively; modes 4/5 run (robust)
RTR throughout; mode 6 NSD. Cluster visiting order is randomly permuted per
EM iteration under ``randomize`` (random_permutation, lmfit.c:1085 — used
by the ADMM/CUDA drivers admm_solve.c:740, lmfit_cuda.c:734: random when
unweighted, sorted by cost reduction when weighted).

TPU re-architecture:
- the cluster loop is a ``lax.fori_loop`` over the padded [M, ...] axis
  (sequencing is algorithmic — SAGE needs it, SURVEY.md P2);
- within a cluster all hybrid chunks solve simultaneously (batched LM,
  lm.py) instead of the reference's sequential chunk loop;
- the joint refine's cost, gradient and restriction to a search line are
  written out on real planes with the rows on the minor axes
  (:func:`_refine_cost_fn`; the Student's-t or Gaussian objective of
  robust_lbfgs.c:94-155), all clusters in one elementwise expression.

Two drivers share the same per-cluster update:
- :func:`sagefit` — fully traced (one XLA program), used inside the mesh
  consensus-ADMM program and anywhere the whole solve must stay jittable;
- :func:`sagefit_host` — EM/cluster loops on the host, one bounded jit call
  per cluster solve: long solves are chunked into short device executions,
  which is also the natural streaming structure for very large M.

The dual-GPU pipeline machinery of lmfit_cuda.c (P5) is intentionally
absent: XLA's async dispatch over a sharded mesh replaces it.
"""

from __future__ import annotations

import functools
import logging
import threading
import time
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from sagecal_tpu import dtypes as dtp
from sagecal_tpu.config import SolverMode
from sagecal_tpu.diag import trace as dtrace
from sagecal_tpu.obs import metrics as obs
from sagecal_tpu.solvers import lbfgs as lbfgs_mod
from sagecal_tpu.solvers import lm as lm_mod
from sagecal_tpu.solvers import normal_eq as ne
from sagecal_tpu.solvers import robust as rb
from sagecal_tpu.solvers import rtr as rtr_mod

# sagefit_host sweep-fusion verdicts, per problem shape (see its
# docstring); process-lifetime cache, entries are tiny
_FUSION_CACHE: dict = {}
# device-program call log (read by tests only since PR 32; ROADMAP
# "harness hooks"): name -> [jitted_fn, (args, kwargs of the last
# call), n_calls]. A reader resets it, runs, then lowers or prices each
# program once from the stored skeleton and multiplies by the count.
_PROGRAM_CALLS: dict = {}


def program_stats_reset():
    _PROGRAM_CALLS.clear()


def program_stats():
    """{name: (jitted_fn, (args, kwargs), n_calls)} since the last reset."""
    return {k: (v[0], v[1], v[2]) for k, v in _PROGRAM_CALLS.items()}


def _spec_of(a):
    """Shape/dtype skeleton of one logged program argument: arrays
    become ShapeDtypeStructs (what ``jfn.lower`` needs), statics pass
    through. Live buffers must NOT be stored — several logged programs
    DONATE their carries, and pinning the raw args would retain (and
    later re-read) buffers XLA already reclaimed, besides holding
    tile-sized arrays alive for the log's lifetime."""
    if isinstance(a, (jax.Array, np.ndarray)):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)
    return a


# device executions this THREAD has issued through _call: a solve reads
# it on entry and on return (info["solve_dispatches"]), so solves on
# other threads (serve) are not counted into it
_DISPATCHED = threading.local()


def _dispatched() -> int:
    return getattr(_DISPATCHED, "n", 0)


def _call(name, jfn, *args, **kwargs):
    rec = _PROGRAM_CALLS.setdefault(name, [jfn, None, 0])
    rec[1] = (tuple(_spec_of(a) for a in args),
              {k: _spec_of(v) for k, v in kwargs.items()})
    rec[2] += 1
    _DISPATCHED.n = _dispatched() + 1
    # one device execution enqueued: the host's share of a solve that
    # is not a wait (the null context without --diag or --profile)
    with dtrace.phase("dispatch", prog=name):
        return jfn(*args, **kwargs)


#: what :func:`_plan_info` adds to a host-driven solve's info dict: host
#: values (a str, an int), not arrays
_PLAN_KEYS = ("plan", "solve_dispatches", "refine_rows", "assemble_rows",
              "sweep_rows")


def sweep_rows(config, B: int) -> str:
    """The row layout a solve's sweeps carry their running residual and
    evaluate a cluster's model on, which :class:`normal_eq.RowPlanes`
    decides from its input: ``"periodic"`` (``config.nbase`` dividing
    the ``B`` rows, whatever the clusters' chunk counts: ``[8, tilesz,
    nbase]`` planes, the Jones gathered for ``nbase`` rows a chunk) or
    ``"flat"`` (``[8, B]``, a Jones gathered a row)."""
    return "periodic" if ne.periodic_rows(config.nbase, B) else "flat"


def assemble_rows(config, B: int):
    """The row layout the per-cluster solves' dense Gauss-Newton matrix
    is assembled from, which ``normal_eq.normal_equations`` and
    ``rtr.make_hess`` decide from their input: ``"periodic"``
    (``config.nbase`` dividing the ``B`` rows, whatever the clusters'
    chunk counts: the assembly on ``[tilesz, nbase]`` planes,
    ``normal_eq.plane_equations``) or ``"generic"`` (the ``[B, 8]``
    scatter assembly). None where the solves assemble no such matrix or
    another code does (NSD, ``inner="cg"``, a constrained Jones mode, a
    reduced storage dtype)."""
    if (config.inner != "chol"
            or config.jones_mode != "full" or config.dtype_policy != "f32"
            or int(config.solver_mode) == int(SolverMode.NSD_RLBFGS)):
        return None
    return "periodic" if ne.periodic_rows(config.nbase, B) \
        else "generic"


def _plan_info(info: dict, plan: str, n0: int, config, x8) -> dict:
    """``info`` with what a host-driven solve ran: ``plan`` is what its
    LAST sweep executed ("promoted": the whole solve as one program,
    "fused": a program a sweep, "per_cluster": a program a cluster or
    group), ``solve_dispatches`` the device executions it issued through
    :func:`_call` since ``n0``, ``refine_rows`` (where a refine ran) the
    row layout its model passes worked on, which the mechanism decides
    from its input (``"periodic"``: ``[tilesz, nbase]`` planes, the
    Jones gathered for ``nbase`` rows a chunk; ``"flat"``: ``[B]``),
    ``sweep_rows`` (:func:`sweep_rows`, where a sweep ran) the same of
    the sweeps' running residual and cluster models, and
    ``assemble_rows`` (:func:`assemble_rows`, where it says one) of the
    sweeps' assembly, all here from ``config.nbase`` and the shape of
    ``x8 [(T,) B, 8]``. Host values: nothing is fetched."""
    out = {**info, "plan": plan, "solve_dispatches": _dispatched() - n0}
    B = x8.shape[-2]
    if config.max_lbfgs > 0:
        out["refine_rows"] = sweep_rows(config, B)
    if config.max_emiter > 0:
        out["sweep_rows"] = sweep_rows(config, B)
    rows = assemble_rows(config, B)
    if rows is not None:
        out["assemble_rows"] = rows
    return out


_LOG = logging.getLogger(__name__)

#: length of a sweep's i32 counter vector ``tk`` (:func:`_cluster_update`)
N_TK = 4


def _learned(kind: str, key, verdict) -> None:
    """Execution-plan verdicts are logged per shape so perf runs can be
    reproduced with the force knobs (SageConfig.fuse/promote)."""
    _LOG.info("sagefit_host %s verdict for shape %s: %s", kind,
              key[:4], verdict)


# sweep-fusion verdicts feed full-trace promotion: once the timed fused sweeps
# prove the WHOLE solve fits comfortably inside the per-execution budget
# below, subsequent calls run the fully traced sagefit — ~3 device
# round-trips per solve instead of ~max_emiter+4, which matters when
# per-execution dispatch overhead is large
_PROMOTE_CACHE: dict = {}
_PROMOTE_BUDGET_S = 35.0


@functools.partial(jax.jit,
                   static_argnames=("n_stations", "config", "os_nsub"))
def _jit_sagefit(x8, coh, sta1, sta2, chunk_idx, chunk_mask, J0,
                 n_stations, wt_base, nu0, config, os_ids, os_nsub, key):
    os_id = None if os_ids is None else (os_ids, os_nsub)
    return sagefit(x8, coh, sta1, sta2, chunk_idx, chunk_mask, J0,
                   n_stations, wt_base, nu0=nu0, config=config,
                   os_id=os_id, key=key)


class SageConfig(NamedTuple):
    max_emiter: int = 3
    max_iter: int = 10            # LM/RTR iterations per cluster solve (-g)
    max_lbfgs: int = 10           # joint refine iterations (-l)
    lbfgs_m: int = 7              # LBFGS memory (-m)
    solver_mode: int = int(SolverMode.RTR_OSRLM_RLBFGS)  # -j
    nulow: float = 2.0
    nuhigh: float = 30.0
    randomize: bool = True
    linsolv: int = 1
    # host-driver execution plan: "auto" learns from timed sweeps (the
    # wall-clock heuristics below), "on"/"off" force the verdict — perf
    # runs become reproducible whatever the dispatch latency
    # (--solve-fuse/--solve-promote; VERDICT r3 weak item 6)
    fuse: str = "auto"            # fuse an EM sweep into one execution
    promote: str = "auto"         # promote the whole solve to one program
    # clusters solved concurrently per SAGE sweep step (--inflight).
    # 1 = the reference's strict Gauss-Seidel sequencing. G>1 solves G
    # clusters per step against the SAME entering residual and applies
    # their updates jointly (block-Jacobi within the group) — the TPU
    # analogue of the reference GPU pipeline keeping 2 clusters in
    # flight per device (lmfit_cuda.c:450-516), batching the small
    # per-cluster systems G-wide on the MXU. The EM residual bookkeeping
    # stays exact (group updates sum model deltas against one base
    # residual), but simultaneous updates overcorrect when a large
    # fraction of clusters move at once (measured: G=M diverges on a
    # cold start). Three protections stack: the EFFECTIVE width is
    # clamped (_eff_inflight; the M >> G regime this exists for is
    # north-star M=100 with G=4..8); a COLD start restricts the first
    # EM sweep to width <= 2 (measured at M=32: G>=4 from identity
    # Jones diverges while G=2 tracks sequential); and every group step
    # is a DAMPED trial — omega in (1, 1/2, 1/4), first safe step wins,
    # else no-op (see _group_update; measured at M=64 warm: G=4 lands
    # within 4% of sequential over 3 sweeps with zero rejections, G=8
    # converges where undamped rejection stalls). Callers whose J0 is
    # already near a solution (pipeline warm tiles, ADMM iterations
    # > 0, a J0 seeded from the solution prior store —
    # serve/priors.py: TileStepper enters the chain with first=False
    # so the warm solver runs from tile 0) set inflight_warm=True to
    # skip the cold restriction.
    inflight: int = 1
    inflight_warm: bool = False
    # row baseline period of the [tilesz, nbase] visibility layout
    # (io.dataset / rime.predict build all rows this way — the same
    # invariant lm.os_subset_ids hard-codes), which also promises that
    # the hybrid chunk of a row is its timeslot's
    # (rime.predict.chunk_indices, which every caller in this package
    # builds its map with). The sweeps, the assembly, the refine and the
    # residual keep [tilesz, nbase] planes by it, whatever the chunk
    # counts; 0 = unknown, or another chunk map (flat rows and the
    # generic scatter assembly, the same result). normal_eq.RowPlanes
    # raises on a CONCRETE map that breaks the promise; a TRACED one (a
    # jit argument, as in every program here) cannot be looked at, and
    # a foreign map handed that way with nbase set is solved as if each
    # timeslot had its first row's chunk: silently another answer.
    nbase: int = 0
    # fold each cluster visit's residual re-subtract and the NEXT
    # visit's add-back into ONE pass over the running residual's planes
    # (the augmented residual rides the sweep carry), instead of a
    # write-back to xres and a fresh add-back per visit. Identical
    # math — the +/- association order is preserved, so the residual
    # stream is bit-identical (parity-gated in tests/test_sage.py).
    # Measured 2026-08-03 at the LOFAR smoke-test shape on the host CPU
    # (M=8, B=18910, -j3, interleaved warm sweeps): median 7.96 s/sweep
    # fused vs 8.01 unfused — a wall-clock wash on a latency-rich CPU —
    # while the fused program runs one traversal of the planes less per
    # cluster visit, so it defaults ON along the traffic axis the
    # roofline gates (PERF.md: the hot path is bandwidth-bound; the
    # TPU wall-clock verdict lands with the next healthy chip window).
    # G>1 group sweeps ignore the flag (their block-Jacobi update
    # needs the plain residual).
    fuse_residual: bool = True
    # inner linear solver for the per-cluster damped Gauss-Newton step
    # (lm.LMConfig.inner) AND the RTR tCG Hessian operator
    # (rtr.RTRConfig.inner): "chol" assembles the dense [K, 8N, 8N]
    # normal matrix (batched Cholesky / materialized matvec — the
    # bit-reference path), "cg" is matrix-free (Wirtinger-factor
    # matvecs under the station-block preconditioner; inexact Newton on
    # the LM path, exact-operator tCG on the RTR path). Default stays
    # "chol", decided from measurement 2026-08-03 (older chip record, in
    # git before PR 32; CPU): at the -j5 shape N=64, M=100 cg LOSES at
    # every B rung — 506 -> 8420 ms/cluster at full B (+1564%), still
    # +1383% at quarter B — because every PCG trip re-pays a full
    # [B]-row matvec pass, and on CPU's ridge the trip chain's row
    # traffic dwarfs the O((8N)^3) triangular work it deletes. The
    # structural goal IS met: under cg the sweep scales ~linearly in B
    # (full/quarter ratio 3.74 vs 4.0 in B; chol 3.33), i.e. the
    # B-independent factorization floor is melted — it is just
    # replaced by B-proportional matvec traffic that only pays off
    # where batched einsum passes are cheap relative to serial
    # triangular solves (the MXU/HBM regime this flag targets; the TPU
    # verdict lands with the next healthy chip window). Flip per run
    # with --inner cg.
    inner: str = "chol"
    cg_tol: float = 0.1           # inexact-Newton forcing eta (lm.py)
    cg_maxiter: int = 25          # static PCG trip cap per damping iter
    # storage dtype policy (--dtype-policy; sagecal_tpu.dtypes): "f32"
    # is the bit-frozen identity; "bf16"/"f16" store the visibility
    # data, running residual and Wirtinger factors in the reduced dtype
    # with f32 accumulation everywhere (Gram products, costs, residual
    # norms, IRLS statistics). Solutions J stay c64; trajectories are
    # gated by per-policy tolerance envelopes, not bit parity
    # (MIGRATION.md "Dtype policy"; PERF.md round 9 for the measured
    # Δbytes/Δwall/drift trade)
    dtype_policy: str = "f32"
    # constrained-Jones parameterization (--jones;
    # normal_eq.JONES_MODES): "full" is the bit-frozen default; "diag"
    # and "phase" solve every per-cluster system and the joint LBFGS
    # refine in the reduced parameter space (4/2 real params per
    # station), shrinking the per-baseline Gram blocks the assemblies
    # emit (8x8 -> 4x4 / 2x2 real). J0 is constrained at entry; ADMM
    # consensus requires "full" (the solvers refuse otherwise)
    jones_mode: str = "full"


_OS_MODES = (int(SolverMode.OSLM_LBFGS),
             int(SolverMode.OSLM_OSRLM_RLBFGS),
             int(SolverMode.RLM_RLBFGS))


def _is_robust(mode: int) -> bool:
    return mode in (int(SolverMode.OSLM_OSRLM_RLBFGS),
                    int(SolverMode.RLM_RLBFGS),
                    int(SolverMode.RTR_OSRLM_RLBFGS),
                    int(SolverMode.NSD_RLBFGS))


def _cluster_model(rows: ne.RowPlanes, J_m, out_dtype):
    """One cluster's corrupted model ``J_p C J_q^H`` as ``[8, *rows]``
    planes, from the cluster's rows (:meth:`normal_eq.RowPlanes.cluster`)
    and its Jones ``J_m [kmax, N, 2, 2]``: real elementwise arithmetic
    with the rows on the minor axes (:func:`normal_eq.row_model`), the
    same bilinear form ``rime.predict.model8`` evaluates as ``[B, 2, 2]``
    complex products.

    The storage-emission contract of ``rime.predict.model8``: the model
    is evaluated in the Jones' precision (f32 from c64) and quantizes
    to the running residual's storage dtype (``out_dtype``) at the
    point it joins the residual's planes, a no-op for f32/f64.

    The model's three inputs pass a barrier: what made them (the solve's
    ``r2c``, a slice of the program's planes or a cluster's own) stays
    out of the multiply-adds' fusion, so how those round (which of them
    contract, on the CPU) is the same in every program that holds a
    visit, and the per-cluster, the per-sweep and the promoted plan of
    :func:`sagefit_host` agree to the bit, as they did when the model
    was a library product (MIGRATION.md's bit-identity of ``--prefetch``,
    resume and ``serve`` rests on it: the learner may change the plan
    between two runs of one tile)."""
    jp8, jq8, c8 = jax.lax.optimization_barrier(
        (*rows.gather(ne.jones_c2r(J_m)), rows.c))
    v, _, _ = ne.row_model(jp8, jq8, c8)
    return dtp.to_storage(v, out_dtype)


def _joint_model(rows: ne.RowPlanes, P):
    """The joint model on planes: ``(V, A, Bm)`` with ``V = sum_m J_p,m
    C_m J_q,m^H`` as ``[8, *rows]`` and the clusters' Wirtinger factors
    ``[8, M, *rows]``, from the stations' Jones planes ``P [M * kmax, N,
    8]``. One elementwise expression with the clusters a leading axis
    (:func:`normal_eq.row_model`), reduced over them."""
    v, a, bm = ne.row_model(*rows.gather(P), rows.c)
    return jnp.sum(v, axis=1), a, bm


def _joint_planes(rows: ne.RowPlanes, J):
    """:func:`_joint_model`'s V ``[8, *rows]`` under the Jones
    ``J [M, kmax, N, 2, 2]``, in the model-eval dtype."""
    return _joint_model(
        rows, ne.jones_c2r(J).reshape(-1, rows.n_stations, 8))[0]


def full_model8(J, coh, sta1, sta2, chunk_idx, row_period: int = 0):
    """Sum of all clusters' corrupted models [B, 8] (minimize_viz_full_pth):
    the joint refine's model (:func:`_joint_model`), handed back as rows.

    The cluster sum ACCUMULATES in the model-eval dtype (f32 from c64)
    regardless of the storage policy — callers emit to storage at the
    residual subtraction (dtp.to_storage), not inside the sum."""
    rows = ne.RowPlanes(None, coh, None, sta1, sta2, chunk_idx,
                        J.shape[1], J.shape[2], row_period)
    return rows.to_rows(_joint_planes(rows, J))


def _wnorm(r, w):
    """||r w||_2 over all planes, in the accumulation dtype."""
    return jnp.linalg.norm(dtp.acc(r * w))


def _prelude(rows: ne.RowPlanes, J0):
    """(xres0, res_0): the data less the storage-quantized model under
    ``J0`` on the planes of ``rows`` (all clusters, with data and
    weights), which is what the sweeps carry, and its weighted norm
    over the 8 B reals."""
    xres0 = rows.x - dtp.to_storage(_joint_planes(rows, J0), rows.x.dtype)
    return xres0, _wnorm(xres0, rows.w) / rows.x.size


def _final_res(rows: ne.RowPlanes, J):
    """res_1: the weighted norm of the data less the model under ``J``
    (all clusters, summed in the model-eval dtype) on the planes of
    ``rows``, over the 8 B reals. ONE expression for every plan
    (:func:`sagefit`, :func:`_jit_refine`, :func:`_jit_res`), so a tile's
    res_1 does not depend on which of them ran it."""
    return _wnorm(rows.x - _joint_planes(rows, J), rows.w) / rows.x.size


#: the solver modes whose cluster solves take the sweep's planes as they
#: are (:func:`rtr.rtr_rows`); the others get rows (:func:`_cluster_solve`)
_RTR_MODES = (int(SolverMode.RTR_OSLM_LBFGS),
              int(SolverMode.RTR_OSRLM_RLBFGS))


def _cluster_solve(mode: int, rows: ne.RowPlanes, coh_m, cmask_m, wt_base,
                   J_m, n_stations: int, nu_cj, config: SageConfig,
                   itermax, itcap: int, admm_m, os_cfg, last):
    """One cluster's per-chunk solve by solver mode (lmfit.c:906-962).

    ``rows``: the cluster's row data on planes, ``rows.x`` the running
    residual with this cluster's model added and ``rows.w`` the
    sqrt-weights. The RTR family (modes 4 and 5) takes them as they are
    (:func:`rtr.rtr_rows`); the LM family and NSD take rows, so for them
    the planes of ``rows.x`` are laid out as ``[B, 8]`` here, beside the
    cluster's ``coh_m [B, 2, 2]`` (None for the RTR family, which reads
    neither it nor ``wt_base [B, 8]``).
    ``last`` (traced bool) is the is-last-EM-iteration switch; ``os_cfg``
    is an lm.OSConfig or None (static). Returns
    (Jn [K,N,2,2], nu_new scalar, init_cost [K], final_cost [K],
    iters i32 scalar — executed inner-solver iterations — and
    cg_iters i32 scalar — executed inner CG trips: LM's PCG trips under
    inner="cg" (0 on its chol path), RTR's truncated-CG bodies (its loop
    ends when every chunk has stopped, rtr._tcg), 0 for NSD, which has
    no inner CG — and row_passes i32 scalar — evaluations of the row
    model RTR's solve executed (rtr.rtr_solve; 0 for the solvers that do
    not count theirs); all for the telemetry's trip accounting).
    """
    lm_cfg = lm_mod.LMConfig(itmax=itcap, inner=config.inner,
                             cg_tol=config.cg_tol,
                             cg_maxiter=config.cg_maxiter,
                             dtype_policy=config.dtype_policy,
                             jones_mode=config.jones_mode)
    nbase = int(config.nbase)
    zero_i = jnp.zeros((), jnp.int32)
    sta1, sta2, cidx_m = rows.sta1, rows.sta2, rows.chunk_id
    xdummy = None if mode in _RTR_MODES else rows.to_rows(rows.x)

    def plain_lm(os=None):
        Jn, info = lm_mod.lm_solve(
            xdummy, coh_m, sta1, sta2, cidx_m, wt_base, J_m, n_stations,
            chunk_mask=cmask_m, config=lm_cfg, itmax_dynamic=itermax,
            admm=admm_m, os=os, row_period=nbase)
        return (Jn, nu_cj, info["init_cost"], info["final_cost"],
                info["iters"], info["cg_iters"], zero_i)

    def robust_lm(os=None):
        Jn, nu_new, info = rb.robust_lm_solve(
            xdummy, coh_m, sta1, sta2, cidx_m, wt_base, J_m, n_stations,
            nu0=nu_cj, nulow=config.nulow, nuhigh=config.nuhigh,
            chunk_mask=cmask_m, config=lm_cfg, wt_rounds=3,  # wt_itmax=3,
            itmax_dynamic=itermax, admm=admm_m, os=os,       # robustlm.c:103
            row_period=nbase)
        return (Jn, nu_new, info["init_cost"], info["final_cost"],
                info["iters"], info["cg_iters"], zero_i)

    if mode == int(SolverMode.RTR_OSLM_LBFGS):
        rtr_cfg = rtr_mod.RTRConfig(itmax=itcap, inner=config.inner,
                                    dtype_policy=config.dtype_policy,
                                    jones_mode=config.jones_mode)
        Jn, info = rtr_mod.rtr_rows(
            rows, J_m, n_stations, chunk_mask=cmask_m, config=rtr_cfg,
            itmax_dynamic=itermax, admm=admm_m, row_period=nbase)
        return (Jn, nu_cj, info["init_cost"], info["final_cost"],
                info["iters"], info["cg_iters"], info["row_passes"])

    if mode == int(SolverMode.RTR_OSRLM_RLBFGS):
        rtr_cfg = rtr_mod.RTRConfig(itmax=itcap, inner=config.inner,
                                    dtype_policy=config.dtype_policy,
                                    jones_mode=config.jones_mode)
        Jn, nu_new, info = rtr_mod.rtr_rows_robust(
            rows, J_m, n_stations,
            nu0=nu_cj, nulow=config.nulow, nuhigh=config.nuhigh,
            # 2 rounds/call: the reference robust RTR updates weights once
            # before and once after the TR loop (rtr_solve_robust.c:1625,
            # :1842), not the LM path's wt_itmax=3
            chunk_mask=cmask_m, config=rtr_cfg, wt_rounds=2,
            itmax_dynamic=itermax, admm=admm_m, row_period=nbase)
        return (Jn, nu_new, info["init_cost"], info["final_cost"],
                info["iters"], info["cg_iters"], info["row_passes"])

    if mode == int(SolverMode.NSD_RLBFGS):
        nsd_cfg = rtr_mod.NSDConfig(itmax=2 * itcap,
                                    jones_mode=config.jones_mode)
        Jn, nu_new, info = rtr_mod.nsd_solve_robust(
            xdummy, coh_m, sta1, sta2, cidx_m, wt_base, J_m, n_stations,
            nu0=nu_cj, nulow=config.nulow, nuhigh=config.nuhigh,
            chunk_mask=cmask_m, config=nsd_cfg, itmax_dynamic=2 * itermax,
            admm=admm_m)
        return (Jn, nu_new, info["init_cost"], info["final_cost"],
                info["iters"], zero_i, zero_i)

    if mode == int(SolverMode.LM_LBFGS) or os_cfg is None:
        # without OS machinery, the OS modes (0/3) degrade to
        # plain/robust LM and mode 2 to robust LM (the pre-OS behavior)
        if _is_robust(mode):
            return robust_lm()
        return plain_lm()

    # OS modes (lmfit.c:907-933): OS-LM on every EM iteration but the
    # last, which switches per mode
    if mode == int(SolverMode.OSLM_LBFGS):
        return jax.lax.cond(last, lambda: plain_lm(),
                            lambda: plain_lm(os_cfg))
    if mode == int(SolverMode.RLM_RLBFGS):
        return jax.lax.cond(last, lambda: robust_lm(),
                            lambda: plain_lm(os_cfg))
    # SM_OSLM_OSRLM_RLBFGS
    return jax.lax.cond(last, lambda: robust_lm(os_cfg),
                        lambda: plain_lm(os_cfg))


def _visit_solve(cj, rows: ne.RowPlanes, coh, cmask_m, J_m, nu_cj,
                 wt_base, n_stations: int,
                 config: SageConfig, nerr_prev, weighted, last, key, admm,
                 os_id, total_iter: int, iter_bar: int):
    """The solve half of one cluster visit (shared by the plain and the
    residual-fused sweeps): ``rows`` the cluster's rows with ``rows.x``
    = residual + this cluster's model, ``coh [M, B, 2, 2]`` and
    ``wt_base [B, 8]`` what the solvers that take rows read
    (:func:`_cluster_solve`). Returns (Jn, nu_new, dcost, its, cgs,
    rps)."""
    mode = int(config.solver_mode)
    coh_m = None if mode in _RTR_MODES else jnp.take(coh, cj, axis=0)
    itermax = jnp.where(
        weighted,
        (0.2 * jnp.take(nerr_prev, cj) * total_iter).astype(jnp.int32)
        + iter_bar,
        config.max_iter)
    admm_m = None
    if admm is not None:
        Y_all, BZ_all, rho_all = admm
        admm_m = (jnp.take(Y_all, cj, axis=0),
                  jnp.take(BZ_all, cj, axis=0),
                  jnp.take(rho_all, cj))
    os_cfg = None
    if os_id is not None and mode in _OS_MODES:
        ids, n_sub = os_id              # the (ids, count) pair from
        os_cfg = lm_mod.OSConfig(       # lm.os_subset_ids — count stays
            os_id=ids, n_subsets=int(n_sub),   # bound to the partition
            key=jax.random.fold_in(key, cj), randomize=config.randomize)

    itcap = int(config.max_iter) + iter_bar  # static while-loop cap
    Jn, nu_new, init_cost, final_cost, its, cgs, rps = _cluster_solve(
        mode, rows, coh_m, cmask_m, wt_base, J_m, n_stations, nu_cj,
        config, itermax, itcap, admm_m, os_cfg, last)
    init_res = jnp.sum(init_cost)
    final_res = jnp.sum(final_cost)
    dcost = jnp.where(init_res > 0,
                      jnp.maximum((init_res - final_res) / init_res, 0.0),
                      0.0)
    return Jn, nu_new, dcost, its, cgs, rps


def _sweep_planes(coh, wt_base, sta1, sta2, chunk_idx, kmax: int,
                  n_stations: int, config: SageConfig) -> ne.RowPlanes:
    """What a sweep reads of a program's row data, on planes, ONCE a
    program: the coherencies of all clusters (``c [8, M, *rows]``), the
    sqrt-weights (``w [8, *rows]``) and the station indices. The
    running residual (the sweep's carry, ``[8, *rows]`` in the storage
    dtype) has the same layout, which ``config.nbase`` decides
    (:func:`sweep_rows`)."""
    with jax.named_scope("update"):
        return ne.RowPlanes(None, coh, wt_base, sta1, sta2, chunk_idx,
                            kmax, n_stations, config.nbase)


def _visit_planes(cj, coh, wt_base, sta1, sta2, chunk_idx, kmax: int,
                  n_stations: int, config: SageConfig) -> ne.RowPlanes:
    """Cluster ``cj``'s slice of :func:`_sweep_planes`' for a program
    that visits that one cluster: only its coherencies are laid out."""
    with jax.named_scope("update"):
        return ne.RowPlanes(None, jnp.take(coh, cj, axis=0), wt_base,
                            sta1, sta2, jnp.take(chunk_idx, cj, axis=0),
                            kmax, n_stations, config.nbase)


def _cluster_update(cj, state, rows: ne.RowPlanes, coh, chunk_mask,
                    wt_base, n_stations: int, config: SageConfig,
                    nerr_prev, weighted, last, key, admm, os_id,
                    total_iter: int, iter_bar: int):
    """Visit one cluster: add model back to residual, solve, re-subtract
    (lmfit.c:890-981). ``rows``: cluster ``cj``'s rows with the weights
    (a slice of :func:`_sweep_planes`' or its own). ``state`` = (J, xres,
    nerr_acc, nuM, tk) with ``xres [8, *rows]`` the running residual on
    planes and ``tk`` an i32[N_TK] counter vector: [0] executed
    inner-solver iterations (the tile record's solver_iters), [1]
    rejected group steps (always 0 here — only :func:`_group_update` can
    reject), [2] executed inner CG trips (LM's PCG under
    SageConfig.inner="cg", RTR's truncated-CG bodies), [3] RTR's row
    passes (:func:`_cluster_solve`)."""
    J, xres, nerr_acc, nuM, tk = state
    cmask_m = jnp.take(chunk_mask, cj, axis=0)
    J_m = jnp.take(J, cj, axis=0)

    # second-level scopes (sage/sweep/update, /inner, and /assemble
    # from normal_eq.py): the model and running-residual update, the
    # inner solve with its loop control, the normal-equation assembly
    with jax.named_scope("update"):
        xdummy = xres + _cluster_model(rows, J_m, xres.dtype)
    with jax.named_scope("inner"):
        Jn, nu_new, dcost, its, cgs, rps = _visit_solve(
            cj, rows.with_x(xdummy), coh, cmask_m, J_m, jnp.take(nuM, cj),
            wt_base, n_stations, config, nerr_prev, weighted,
            last, key, admm, os_id, total_iter, iter_bar)
    with jax.named_scope("update"):
        nuM = nuM.at[cj].set(nu_new)
        nerr_acc = nerr_acc.at[cj].set(dcost)
        xres = xdummy - _cluster_model(rows, Jn, xres.dtype)
        J = J.at[cj].set(Jn)
    tk = tk.at[0].add(its).at[2].add(cgs).at[3].add(rps)
    return J, xres, nerr_acc, nuM, tk


def _sweep_g1(perm, state, rows: ne.RowPlanes, coh, chunk_mask,
              wt_base, n_stations: int, config: SageConfig, nerr_prev,
              weighted, last, key, admm, os_id, total_iter: int,
              iter_bar: int):
    """One EM sweep over all M clusters at group width 1, on the
    program's planes ``rows`` (:func:`_sweep_planes`).

    With ``config.fuse_residual`` the loop carries the AUGMENTED
    residual xd = xres + model(current cluster): each visit solves on
    xd, then one fused pass replaces it by
    (xd - model_new) + model(next cluster) — the re-subtract and the
    next add-back become a single read+write of the residual's planes
    instead of two (and the final visit's masked add costs nothing).
    The +/- association order matches the unfused path exactly, so the
    residual stream is bit-preserving; see SageConfig.fuse_residual for
    the measured defaults. ``perm`` may be None (natural order)."""
    J0_, xres, nerr_acc0, nuM0, tk0 = state
    M = chunk_mask.shape[0]

    if not config.fuse_residual:
        def cluster_step(cj, inner):
            cj_eff = cj if perm is None else jnp.take(perm, cj)
            return _cluster_update(
                cj_eff, inner, rows.cluster(cj_eff), coh, chunk_mask,
                wt_base, n_stations, config, nerr_prev, weighted, last,
                key, admm, os_id, total_iter, iter_bar)
        return jax.lax.fori_loop(0, M, cluster_step, state)

    def cl_of(j):
        jc = jnp.minimum(j, M - 1)
        return jc if perm is None else jnp.take(perm, jc)

    c0 = cl_of(0)
    with jax.named_scope("update"):
        xd = xres + _cluster_model(rows.cluster(c0),
                                   jnp.take(J0_, c0, axis=0), xres.dtype)

    def body(j, inner):
        J, xd, nerr_acc, nuM, tk = inner
        cj = cl_of(j)
        rows_m = rows.cluster(cj)
        J_m = jnp.take(J, cj, axis=0)
        with jax.named_scope("inner"):
            Jn, nu_new, dcost, its, cgs, rps = _visit_solve(
                cj, rows_m.with_x(xd), coh,
                jnp.take(chunk_mask, cj, axis=0), J_m, jnp.take(nuM, cj),
                wt_base, n_stations, config, nerr_prev,
                weighted, last, key, admm, os_id, total_iter, iter_bar)
        with jax.named_scope("update"):
            nuM = nuM.at[cj].set(nu_new)
            nerr_acc = nerr_acc.at[cj].set(dcost)
            J = J.at[cj].set(Jn)
            # next cluster's model from the UPDATED J (cl_of(j+1) != cj
            # for j < M-1, so the update never aliases; the clamped last
            # step's self-model is dropped by the where)
            cn = cl_of(j + 1)
            model_next = _cluster_model(rows.cluster(cn),
                                        jnp.take(J, cn, axis=0), xd.dtype)
            model_new = _cluster_model(rows_m, Jn, xd.dtype)
            xd = (xd - model_new) + jnp.where(j + 1 < M, model_next, 0.0)
        return (J, xd, nerr_acc, nuM,
                tk.at[0].add(its).at[2].add(cgs).at[3].add(rps))

    J, xd, nerr_acc, nuM, tk = jax.lax.fori_loop(
        0, M, body, (J0_, xd, nerr_acc0, nuM0, tk0))
    # after the last visit the masked add left xd == the plain residual
    return J, xd, nerr_acc, nuM, tk


def _omega_trial(w, Jo_g, Jn_g, rows: ne.RowPlanes, cjs, xres, valid,
                 model_old, res_old, anchor):
    """One damped block-Jacobi group step at relaxation ``w``: apply
    J(omega) = J_old + w (J_solved - J_old) jointly and test the
    weighted residual L2 against entry/anchor. Module-level so the
    omega-ladder cond branches in :func:`_group_update` stay priceable
    standalone — XLA cost analysis sums BOTH branches of a lax.cond,
    and inlining this body charged every group step for the omega=1/2
    and 1/4 model evaluations the common case never executes (jaxlint
    cond-cost; the PR 3 phantom-bytes class)."""
    Jr_g = Jo_g + w * (Jn_g - Jo_g)
    model_new = jax.vmap(
        lambda Jm, cj: _cluster_model(rows.cluster(cj), Jm, xres.dtype)
    )(Jr_g, cjs)
    # the live members' model deltas, summed over the group axis of
    # their planes in the accumulation dtype
    delta = jnp.where(valid.reshape((-1,) + (1,) * xres.ndim),
                      dtp.acc(model_old - model_new), 0.0)
    xnew = xres + dtp.to_storage(jnp.sum(delta, axis=0), xres.dtype)
    rn = jnp.sum(dtp.acc(xnew * rows.w) ** 2)
    ok = (rn <= res_old * (1.0 + 1e-9)) | (rn <= 1.05 * anchor)
    return ok, xnew, Jr_g


def _group_update(cjs, state, rows: ne.RowPlanes, coh, chunk_mask,
                  wt_base, n_stations: int, config: SageConfig,
                  nerr_prev, weighted, last, key, admm, os_id,
                  total_iter: int, iter_bar: int, res_anchor=None):
    """Visit a GROUP of clusters concurrently (config.inflight > 1).

    ``cjs`` [G] holds distinct cluster indices; padded slots carry the
    out-of-range index M — their scatter updates are dropped (JAX's
    default OOB-scatter semantics) and their residual contribution is
    masked. ``rows``: the program's planes (:func:`_sweep_planes`); the
    running residual in ``state`` and the members' models are
    ``[8, *rows]`` planes, the group a leading axis of them. Every
    member's solve sees the residual AS OF GROUP ENTRY
    (block-Jacobi); the group's model deltas then apply jointly:
    xres += sum_g (model(J_old_g) - model(J_new_g)).

    Group-step safeguard (damped block-Jacobi): the joint update is
    tried at step factors omega in (1, 1/2, 1/4) — J(omega) = J_old +
    omega (J_solved - J_old), the classic under-relaxation — and the
    FIRST factor whose joint weighted residual L2 is non-increasing (or
    within 5% of ``res_anchor``, the SWEEP-entry residual) is applied;
    if none passes the group is a no-op and tk[1] increments. The
    anchor keeps the slack from compounding (per-step relative slack
    alone would admit exponential growth at 1.05/step).

    Why: overlapping clusters make full joint updates overcorrect —
    measured warm G=8 at M=64 grows the residual 70x over one EM sweep
    while per-lane solves all report cost decreases (each lane's
    decrease is against the ENTRY residual; summed deltas
    double-subtract shared flux). Rejection alone STALLS there (7/8
    groups vetoed, and 0/8 by sweep 3); with the relaxed retry all
    groups accept (measured 3 at omega=1, 5 at omega=1/2) and the
    3-sweep residual reaches 0.0221 vs 0.0285 stalled. Each extra
    candidate costs G model evaluations + a norm — small next to the
    solves. The test metric is plain weighted L2 (cheap,
    mode-independent); robust/ADMM modes may legitimately trade a few
    percent of L2 for their own cost decrease, hence the anchored
    slack.
    """
    J, xres, nerr_acc, nuM, tk = state
    M = chunk_mask.shape[0]
    mode = int(config.solver_mode)
    valid = cjs < M

    def solve_one(cj):
        rows_m = rows.cluster(cj)               # OOB clamps; masked below
        coh_m = None if mode in _RTR_MODES else jnp.take(coh, cj, axis=0)
        cmask_m = jnp.take(chunk_mask, cj, axis=0)
        J_m = jnp.take(J, cj, axis=0)
        itermax = jnp.where(
            weighted,
            (0.2 * jnp.take(nerr_prev, cj, mode="clip") * total_iter)
            .astype(jnp.int32) + iter_bar,
            config.max_iter)
        admm_m = None
        if admm is not None:
            Y_all, BZ_all, rho_all = admm
            admm_m = (jnp.take(Y_all, cj, axis=0),
                      jnp.take(BZ_all, cj, axis=0),
                      jnp.take(rho_all, cj, mode="clip"))
        os_cfg = None
        if os_id is not None and mode in _OS_MODES:
            ids, n_sub = os_id
            os_cfg = lm_mod.OSConfig(
                os_id=ids, n_subsets=int(n_sub),
                key=jax.random.fold_in(key, cj),
                randomize=config.randomize)
        with jax.named_scope("update"):
            xdummy = xres + _cluster_model(rows_m, J_m, xres.dtype)
        itcap = int(config.max_iter) + iter_bar
        with jax.named_scope("inner"):
            (Jn, nu_new, init_cost, final_cost, its, cgs,
             rps) = _cluster_solve(
                mode, rows_m.with_x(xdummy), coh_m, cmask_m, wt_base,
                J_m, n_stations, jnp.take(nuM, cj, mode="clip"), config,
                itermax, itcap, admm_m, os_cfg, last)
        return Jn, nu_new, init_cost, final_cost, its, cgs, rps, xdummy

    (Jn_g, nu_g, ic_g, fc_g, its_g, cgs_g, rps_g,
     xd_g) = jax.vmap(solve_one)(cjs)
    # the joint update: the damped trials, then the scatters of whatever
    # was accepted
    with jax.named_scope("update"):
        Jo_g = jnp.take(J, cjs, axis=0)     # entering Jones (clipped)
        # entering models fall out of the solves' add-back (xdummy - xres):
        # no second RIME evaluation needed
        model_old = xd_g - xres[None]
        res_old = jnp.sum(dtp.acc(xres * rows.w) ** 2)
        anchor = res_old if res_anchor is None else res_anchor

        def try_omega(w):
            # forwards to the module-level body: the cond branches below
            # must not inline the model evaluations (priceability contract,
            # see _omega_trial)
            return _omega_trial(w, Jo_g, Jn_g, rows, cjs, xres, valid,
                                model_old, res_old, anchor)

        # first passing factor wins (largest safe step); the cond chain
        # skips the smaller-step model evaluations when omega=1 passes —
        # the common case (measured 3/8 at omega=1, 5/8 at 1/2)
        ok1, x1, Jr1 = try_omega(1.0)

        def fall1():
            ok2, x2, Jr2 = try_omega(0.5)

            def fall2():
                return try_omega(0.25)

            return jax.lax.cond(ok2, lambda: (ok2, x2, Jr2), fall2)

        accept, xres_sel, Jr_sel = jax.lax.cond(
            ok1, lambda: (ok1, x1, Jr1), fall1)

        init_res = jnp.sum(ic_g, axis=-1)
        final_res = jnp.sum(fc_g, axis=-1)
        # dcost from the full-step solve costs: at omega < 1 this OVERSTATES
        # the achieved reduction, but it only weights the next sweep's
        # iteration allocation — acceptable
        dcost = jnp.where(init_res > 0,
                          jnp.maximum((init_res - final_res)
                                      / jnp.maximum(init_res, 1e-30), 0.0),
                          0.0)
        # padded indices (cjs == M) are dropped by the scatters; a rejected
        # group keeps the entering state entirely
        nerr_acc = jnp.where(accept, nerr_acc.at[cjs].set(dcost), nerr_acc)
        nuM = jnp.where(accept, nuM.at[cjs].set(nu_g), nuM)
        J = jnp.where(accept, J.at[cjs].set(Jr_sel), J)
        xres = jnp.where(accept, xres_sel, xres)
        # tk[0]: useful-work iterations, summed over live lanes (a lower
        # bound on executed trips — the G-wide batched loop runs until its
        # slowest lane finishes; rejected groups still executed them).
        # tk[1]: fully-rejected group steps — the observability hook for
        # "groups are all vetoing" (info['rejected_groups']).
        # tk[2]: executed inner CG trips (LM PCG, RTR tCG), tk[3]: RTR's
        # row passes, the same live-lane sums.
        live = lambda c: jnp.sum(jnp.where(valid, c, 0)).astype(jnp.int32)
        tk = tk.at[0].add(live(its_g))
        tk = tk.at[1].add((~accept).astype(jnp.int32))
        tk = tk.at[2].add(live(cgs_g)).at[3].add(live(rps_g))
        return J, xres, nerr_acc, nuM, tk


_COLD_INFLIGHT = 2      # widest group proven safe from an identity start


def _eff_inflight(config: SageConfig, M: int) -> int:
    """Effective in-flight group width: the configured value clamped to
    M//4. With the damped group trials in :func:`_group_update` every
    width converges (measured, 3 warm sweeps, zero rejections: M=16
    G=4 within 5.5% of sequential, M=32 G=4 within 6.4%, G=8 within
    16%); M//4 caps the per-sweep convergence penalty while quartering
    the number of sequential group steps."""
    G = int(config.inflight)
    if G <= 1:
        return 1
    return max(1, min(G, M // 4))


def _inflight_widths(config: SageConfig, M: int) -> tuple[int, int]:
    """(first-sweep width, steady width): a cold start restricts the
    first EM sweep to _COLD_INFLIGHT (see SageConfig.inflight docs)."""
    G = _eff_inflight(config, M)
    G0 = G if config.inflight_warm else min(G, _COLD_INFLIGHT)
    return G0, G


def _pad_order(order, M: int, G: int):
    """Pad a cluster visiting order up to ceil(M/G)*G with the sentinel
    index M (dropped by the group scatters)."""
    n_groups = -(-M // G)
    pad = n_groups * G - M
    if pad == 0:
        return order, n_groups
    fill = jnp.full(order.shape[:-1] + (pad,), M, order.dtype)
    return jnp.concatenate([order, fill], axis=-1), n_groups


def _cluster_perm(ci, nerr_prev, weighted, key, M: int,
                  config: SageConfig):
    """Cluster visiting order for EM iteration ``ci`` (random_permutation,
    lmfit.c:1085 via admm_solve.c:740): random when unweighted, sorted by
    descending cost reduction when weighted."""
    if not config.randomize or M <= 1 or key is None:
        return None
    perm_rand = jax.random.permutation(jax.random.fold_in(key, 104729 + ci),
                                       M)
    perm_sort = jnp.argsort(-nerr_prev)
    return jnp.where(weighted, perm_sort, perm_rand).astype(jnp.int32)


def _refine_cost_fn(x8, coh, sta1, sta2, chunk_idx, wt_base, shape, kmax,
                    n_stations, robust: bool, mean_nu, mode: str = "full",
                    Jref=None, row_period: int = 0):
    """The joint refine's cost, its gradient and its restriction to a
    search line: ``(cost_fn, grad_fn, line_func)``, the three closures
    ``lbfgs.lbfgs_fit`` takes. All three evaluate the model of all
    clusters on real planes with the rows on the minor axes
    (:func:`_joint_model`; the planes are made here, once a refine), the
    gradient and the restriction written out from its Wirtinger factors:
    no autodiff passes over a row.

    ``line_func`` is None where the parameters do not enter the Jones
    matrices linearly (``phase``: J = Jref exp(i theta)). Where they do
    (``full``, ``diag``) the model ``sum_m J_p C_m J_q^H`` is a
    homogeneous quadratic in them, so along ``p = xk + a pk`` every
    row's weighted residual is exactly ``r0 - a V1 - a^2 V2`` and a trial
    step of the search is one elementwise pass over three plane arrays.
    """
    rows = ne.RowPlanes(x8, coh, wt_base, sta1, sta2, chunk_idx, kmax,
                        n_stations, row_period)
    # the model accumulates in the accumulation dtype (full_model8), and
    # so do the residual and its weighting here, whatever x8 and wt_base
    # are stored in
    x, w = dtp.acc(rows.x), dtp.acc(rows.w)

    # mode != "full": ``shape`` is the reduced (M*kmax, N, npar) layout
    # and Jref [M*kmax, N, 2, 2] carries the constrained reference
    # point (amplitudes for the phase retraction J = Jref * exp(i θ))
    def station_planes(p):
        """p [D] -> the stations' Jones as real planes [M*kmax, N, 8]."""
        if mode == "full":
            return p.reshape(shape)
        return ne.jones_c2r(ne.jones_from_params(p.reshape(shape), mode,
                                                 Jref))

    def cost_of(r):
        if robust:
            return jnp.sum(jnp.log1p(r * r / mean_nu))
        return jnp.sum(r * r)

    def cost_fn(p):
        return cost_of((x - _joint_model(rows, station_planes(p))[0]) * w)

    def grad_fn(p):
        v, a, bm = _joint_model(rows, station_planes(p))
        e = (x - v) * w
        # G = w f'(e), the complex form of -dc/dV; a row's shares of
        # dc/dJ_p and dc/dJ_q are -(G A^H) and -(G^H Bm)
        g = w * (2.0 * e / (mean_nu + e * e) if robust else 2.0 * e)
        gp, gq = ne.row_grad(g[:, None], a, bm)
        gP = -rows.station_sum(rows.time_sum(gp), rows.time_sum(gq))
        if mode == "full":
            return gP.reshape(-1)
        return jax.vjp(station_planes, p)[1](gP)[0]

    if mode == "phase":
        return cost_fn, grad_fn, None

    def line_func(xk, pk):
        with jax.named_scope("restrict"):
            v0, a, bm = _joint_model(rows, station_planes(xk))
            Pk = station_planes(pk)            # p -> J is linear
            # V1 is the derivative itself, dV = P_p A + Bm P_q^H: exact,
            # where model(xk + pk) - model(xk) - model(pk) cancels in
            # float32 when |pk| << |xk|
            dv = ne.row_tangent(*rows.gather(Pk), a, bm)
            r0 = (x - v0) * w
            v1 = jnp.sum(dv, axis=1) * w
            v2 = _joint_model(rows, Pk)[0] * w

        def on_line(a):
            r = r0 - a * (v1 + a * v2)
            # d/da of cost_of(r(a)), r' = -(V1 + 2 a V2)
            fr = r / (mean_nu + r * r) if robust else r
            return cost_of(r), -2.0 * jnp.sum(fr * (v1 + 2.0 * a * v2))
        return on_line

    return cost_fn, grad_fn, line_func


def sagefit(x8, coh, sta1, sta2, chunk_idx, chunk_mask, J0, n_stations: int,
            wt_base, nu0=None, config: SageConfig = SageConfig(),
            admm=None, os_id=None, key=None):
    """One solve interval of SAGE-EM calibration (fully traced).

    Args:
      x8: [B, 8] channel-averaged data (flagged rows zeroed).
      coh: [M, B, 2, 2] solve-path coherencies.
      sta1, sta2: [B] station indices.
      chunk_idx: [M, B] hybrid chunk ids; chunk_mask: [M, Kmax] live chunks.
      J0: [M, Kmax, N, 2, 2] initial Jones.
      wt_base: [B, 8] sqrt-weights (0 = excluded from solve).
      nu0: initial robust nu (defaults to config.nulow, lmfit.c:827).
      admm: optional (Y, BZ, rho) consensus augmentation with Y, BZ
        [M, Kmax, N, 8] real Jones and rho [M] per-cluster regularization.
        Each cluster solve then minimizes the augmented Lagrangian
        (sagefit_visibilities_admm, admm_solve.c:221: same EM loop with
        ADMM-regularized per-cluster solves; the joint LBFGS refine is
        disabled in this mode, matching the reference's max_lbfgs=0 call
        sites sagecal_slave.cpp:644-667).
      os_id: optional (ids [B], n_subsets) pair as returned by
        lm.os_subset_ids — enables the ordered-subsets path for solver
        modes 1/2/3 (P4 acceleration).
      key: PRNG key for OS subset draws + cluster-order permutation;
        a fixed default keeps runs reproducible.

    Returns (J, info) with res_0/res_1 = ||residual||_2 / n (lmfit.c:869,
    1043) and mean_nu.
    """
    M, B = coh.shape[0], coh.shape[1]
    kmax = J0.shape[1]
    # dtype policy: the [B]-data, weights and the running residual ride
    # the storage dtype (identity under "f32"); the EM state (nerr,
    # nuM, costs) lives in the accumulator dtype
    stq = dtp.storage_dtype(config.dtype_policy, x8.dtype)
    x8 = dtp.to_storage(x8, stq)
    wt_base = dtp.to_storage(wt_base, stq)
    dtype = dtp.acc_dtype(x8.dtype)
    robust = _is_robust(config.solver_mode)
    if config.jones_mode != "full":
        # constrained modes start (and stay) on the constraint surface;
        # the initial residual prices the same point the solvers see
        J0 = ne.jones_constrain(J0, config.jones_mode)
    if nu0 is None:
        nu0 = config.nulow
    if key is None:
        key = jax.random.PRNGKey(42)

    # the four first-level scopes below (and the same names on the
    # host-driven programs further down) are what a profiler trace of a
    # solve is split by: metadata only, the programs are unchanged
    with jax.named_scope("sage/prelude"):
        # the program's row data go to planes ONCE, here: the data, the
        # weights and all clusters' coherencies; the sweeps' running
        # residual stays on them to the end
        rows = ne.RowPlanes(x8, coh, wt_base, sta1, sta2, chunk_idx, kmax,
                            n_stations, config.nbase)
        xres0, res_0 = _prelude(rows, J0)

    total_iter = M * config.max_iter
    iter_bar = int(-(-0.8 * total_iter // M))  # ceil(0.8/M * total), host-side

    G0, G = _inflight_widths(config, M)

    @jax.named_scope("sage/sweep")
    def em_iter_width(ci, carry, Gi):
        J, xres, nerr, nuM, tk = carry
        weighted = (ci % 2 == 1) if config.randomize else jnp.asarray(False)
        last = ci == config.max_emiter - 1
        perm = _cluster_perm(ci, nerr, weighted, key, M, config)
        kci = jax.random.fold_in(key, ci)

        if Gi == 1:
            J, xres, nerr_new, nuM, tk = _sweep_g1(
                perm, (J, xres, jnp.zeros((M,), dtype), nuM, tk),
                rows, coh, chunk_mask, wt_base,
                n_stations, config, nerr, weighted, last, kci, admm,
                os_id, total_iter, iter_bar)
        else:
            base = (perm if perm is not None
                    else jnp.arange(M, dtype=jnp.int32))
            order_pad, n_groups = _pad_order(base, M, Gi)
            # sweep-entry anchor for the group-step safeguard
            anchor = jnp.sum(dtp.acc(xres * rows.w) ** 2)

            def group_step(g, inner):
                cjs = jax.lax.dynamic_slice(order_pad, (g * Gi,), (Gi,))
                return _group_update(
                    cjs, inner, rows, coh,
                    chunk_mask, wt_base, n_stations, config, nerr,
                    weighted, last, kci, admm, os_id, total_iter,
                    iter_bar, res_anchor=anchor)

            J, xres, nerr_new, nuM, tk = jax.lax.fori_loop(
                0, n_groups, group_step, (J, xres, jnp.zeros((M,), dtype),
                                          nuM, tk))
        total = jnp.sum(nerr_new)
        nerr = jnp.where(total > 0, nerr_new / total, nerr_new)
        return J, xres, nerr, nuM, tk

    nuM0 = jnp.full((M,), jnp.asarray(nu0, dtype))
    carry0 = (J0, xres0, jnp.zeros((M,), dtype), nuM0,
              jnp.zeros((N_TK,), jnp.int32))
    if G0 == G or config.max_emiter < 1:
        J, xres, nerr, nuM, tk = jax.lax.fori_loop(
            0, config.max_emiter, lambda ci, c: em_iter_width(ci, c, G),
            carry0)
    else:
        # cold start: first sweep at the restricted width, rest at G
        carry0 = em_iter_width(0, carry0, G0)
        J, xres, nerr, nuM, tk = jax.lax.fori_loop(
            1, config.max_emiter, lambda ci, c: em_iter_width(ci, c, G),
            carry0)

    mean_nu = jnp.clip(jnp.mean(nuM), config.nulow, config.nuhigh)

    # joint LBFGS refine over all parameters (lmfit.c:1019-1037);
    # skipped in ADMM mode (sagecal_slave.cpp passes max_lbfgs=0)
    lbfgs_k = passes = jnp.zeros((), jnp.int32)
    if config.max_lbfgs > 0 and admm is None:
        with jax.named_scope("sage/refine"):
            mode = config.jones_mode
            npar8 = ne.jones_npar(mode)
            shape = (M * kmax, n_stations, npar8)
            Jflat = J.reshape(M * kmax, n_stations, 2, 2)
            if mode == "full":
                Jref = None
                p0 = ne.jones_c2r(Jflat).reshape(-1).astype(dtype)
            else:
                Jref = ne.jones_constrain(Jflat, mode)
                p0 = ne.params_from_jones(Jref, mode).reshape(-1).astype(dtype)
            cost_fn, grad_fn, line_fn = _refine_cost_fn(
                x8, coh, sta1, sta2, chunk_idx, wt_base, shape, kmax,
                n_stations, robust, mean_nu, mode=mode, Jref=Jref,
                row_period=config.nbase)
            p1, lbfgs_k, passes = lbfgs_mod.lbfgs_fit(
                cost_fn, grad_fn, p0, itmax=config.max_lbfgs,
                M=config.lbfgs_m, return_iters=True, line_func=line_fn)
            if mode == "full":
                J = ne.jones_r2c(p1.reshape(shape)).reshape(
                    M, kmax, n_stations, 2, 2)
            else:
                J = ne.jones_from_params(p1.reshape(shape), mode,
                                         Jref).reshape(M, kmax, n_stations,
                                                       2, 2)

    with jax.named_scope("sage/final"):
        res_1 = _final_res(rows, J)
    return J, {"res_0": res_0, "res_1": res_1, "mean_nu": mean_nu,
               "nerr": nerr, "solver_iters": tk[0],
               "rejected_groups": tk[1], "cg_iters": tk[2],
               "row_passes": tk[3],
               "lbfgs_iters": lbfgs_k, "refine_passes": passes}


# ---------------------------------------------------------------------------
# host-driven variant: bounded per-cluster device executions
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("n_stations", "config", "total_iter",
                                    "iter_bar", "os_nsub"),
                   donate_argnums=(1, 2, 3, 4))
@jax.named_scope("sage/sweep")
def _jit_cluster_update(cj, J, xres, nerr_acc, nuM, coh, sta1, sta2,
                        chunk_idx, chunk_mask, wt_base, nerr_prev, weighted,
                        last, key, admm, os_ids, n_stations, config,
                        total_iter, iter_bar, os_nsub):
    """One cluster visit as a bounded execution; ``xres [8, *rows]`` the
    running residual on planes (:func:`_jit_prelude`'s), in and out."""
    os_id = None if os_ids is None else (os_ids, os_nsub)
    return _cluster_update(cj, (J, xres, nerr_acc, nuM,
                                jnp.zeros((N_TK,), jnp.int32)),
                           _visit_planes(cj, coh, wt_base, sta1, sta2,
                                         chunk_idx, J.shape[1], n_stations,
                                         config),
                           coh, chunk_mask, wt_base, n_stations,
                           config, nerr_prev, weighted, last, key, admm,
                           os_id, total_iter, iter_bar)


@functools.partial(jax.jit,
                   static_argnames=("n_stations", "config", "total_iter",
                                    "iter_bar", "os_nsub"),
                   donate_argnums=(1, 2, 3, 4))
@jax.named_scope("sage/sweep")
def _jit_group_update(cjs, J, xres, nerr_acc, nuM, coh, sta1, sta2,
                      chunk_idx, chunk_mask, wt_base, nerr_prev, weighted,
                      last, key, os_ids, n_stations, config, total_iter,
                      iter_bar, os_nsub, res_anchor):
    """One in-flight GROUP of cluster solves as a bounded execution
    (config.inflight > 1 on the unfused host path). ``res_anchor`` is
    the sweep-entry weighted residual L2 (host-computed) for the
    group-step safeguard."""
    os_id = None if os_ids is None else (os_ids, os_nsub)
    return _group_update(cjs, (J, xres, nerr_acc, nuM,
                               jnp.zeros((N_TK,), jnp.int32)),
                         _sweep_planes(coh, wt_base, sta1, sta2, chunk_idx,
                                       J.shape[1], n_stations, config),
                         coh, chunk_mask, wt_base, n_stations,
                         config, nerr_prev, weighted, last, key, None,
                         os_id, total_iter, iter_bar,
                         res_anchor=res_anchor)


@functools.partial(jax.jit,
                   static_argnames=("n_stations", "config", "total_iter",
                                    "iter_bar", "os_nsub"),
                   donate_argnums=(0, 1, 2))
@jax.named_scope("sage/sweep")
def _jit_em_sweep(J, xres, nuM, coh, sta1, sta2, chunk_idx, chunk_mask,
                  wt_base, nerr_prev, weighted, last, kci, perm, os_ids,
                  n_stations, config, total_iter, iter_bar, os_nsub):
    """One full EM sweep over all clusters as a single device execution
    (used by sagefit_host once a timed per-cluster sweep proves the fused
    program fits the runtime's per-execution wall-clock limit)."""
    os_id = None if os_ids is None else (os_ids, os_nsub)
    return _em_sweep(J, xres, nuM, coh, sta1, sta2, chunk_idx, chunk_mask,
                     wt_base, nerr_prev, weighted, last, kci, perm, os_id,
                     n_stations, config, total_iter, iter_bar)


def _em_sweep(J, xres, nuM, coh, sta1, sta2, chunk_idx, chunk_mask, wt_base,
              nerr_prev, weighted, last, kci, perm, os_id, n_stations,
              config, total_iter, iter_bar):
    """:func:`_jit_em_sweep`'s body (and, under ``vmap``, a tile's of
    :func:`_jit_em_sweep_tiles`): the program's planes made once, then
    the sweep at the effective group width, ``xres [8, *rows]`` in and
    out."""
    M = chunk_mask.shape[0]
    G = _eff_inflight(config, M)
    rows = _sweep_planes(coh, wt_base, sta1, sta2, chunk_idx, J.shape[1],
                         n_stations, config)
    state = (J, xres, jnp.zeros((M,), dtp.acc_dtype(xres.dtype)), nuM,
             jnp.zeros((N_TK,), jnp.int32))

    if G == 1:
        return _sweep_g1(
            perm, state, rows, coh, chunk_mask, wt_base,
            n_stations, config, nerr_prev, weighted, last, kci, None,
            os_id, total_iter, iter_bar)

    order_pad, n_groups = _pad_order(perm, M, G)
    anchor = jnp.sum(dtp.acc(xres * rows.w) ** 2)   # sweep-entry safeguard ref

    def group_step(g, inner):
        cjs = jax.lax.dynamic_slice(order_pad, (g * G,), (G,))
        return _group_update(cjs, inner, rows, coh,
                             chunk_mask, wt_base, n_stations, config,
                             nerr_prev, weighted, last, kci, None, os_id,
                             total_iter, iter_bar, res_anchor=anchor)

    return jax.lax.fori_loop(0, n_groups, group_step, state)


@functools.partial(jax.jit, static_argnames=("row_period",))
@jax.named_scope("sage/prelude")
def _jit_prelude(x8, coh, sta1, sta2, chunk_idx, J0, wt_base, row_period=0):
    """The sweeps' entering residual ON PLANES (``[8, *rows]``, the
    handle the host passes from one sweep program to the next) and
    res_0."""
    rows = ne.RowPlanes(x8, coh, wt_base, sta1, sta2, chunk_idx,
                        J0.shape[1], J0.shape[2], row_period)
    return _prelude(rows, J0)


@functools.partial(jax.jit, static_argnames=("n_stations", "config",
                                             "robust"),
                   donate_argnums=(5,))
def _jit_refine(x8, coh, sta1, sta2, chunk_idx, J, wt_base, mean_nu,
                n_stations, config, robust):
    with jax.named_scope("sage/refine"):
        M, kmax = J.shape[0], J.shape[1]
        dtype = dtp.acc_dtype(x8.dtype)
        mode = config.jones_mode
        shape = (M * kmax, n_stations, ne.jones_npar(mode))
        Jflat = J.reshape(M * kmax, n_stations, 2, 2)
        if mode == "full":
            Jref = None
            p0 = ne.jones_c2r(Jflat).reshape(-1).astype(dtype)
        else:
            Jref = ne.jones_constrain(Jflat, mode)
            p0 = ne.params_from_jones(Jref, mode).reshape(-1).astype(dtype)
        cost_fn, grad_fn, line_fn = _refine_cost_fn(
            x8, coh, sta1, sta2, chunk_idx, wt_base, shape, kmax,
            n_stations, robust, mean_nu, mode=mode, Jref=Jref,
            row_period=config.nbase)
        p1, k, passes = lbfgs_mod.lbfgs_fit(
            cost_fn, grad_fn, p0, itmax=config.max_lbfgs,
            M=config.lbfgs_m, return_iters=True, line_func=line_fn)
        if mode == "full":
            Jn = ne.jones_r2c(p1.reshape(shape)).reshape(M, kmax, n_stations,
                                                         2, 2)
        else:
            Jn = ne.jones_from_params(p1.reshape(shape), mode, Jref).reshape(
                M, kmax, n_stations, 2, 2)
    with jax.named_scope("sage/final"):
        res = _final_res(
            ne.RowPlanes(x8, coh, wt_base, sta1, sta2, chunk_idx, kmax,
                         n_stations, config.nbase), Jn)
    return Jn, res, k, passes


@functools.partial(jax.jit, static_argnames=("row_period",))
@jax.named_scope("sage/final")
def _jit_res(x8, coh, sta1, sta2, chunk_idx, J, wt_base, row_period=0):
    return _final_res(
        ne.RowPlanes(x8, coh, wt_base, sta1, sta2, chunk_idx, J.shape[1],
                     J.shape[2], row_period), J)


@jax.jit
def _jit_wres2(xres, wt_base):
    """Weighted residual L2^2 — the sweep-entry anchor the host group
    path feeds the group-step safeguard; ``xres [8, *rows]`` on planes,
    ``wt_base [B, 8]``."""
    w = jnp.moveaxis(wt_base, -1, 0).reshape(xres.shape)
    return jnp.sum(dtp.acc(xres * w) ** 2)


@jax.jit
def _jit_wres2_tiles(xres, wt_base):
    return jax.vmap(_jit_wres2.__wrapped__)(xres, wt_base)


def sagefit_host(x8, coh, sta1, sta2, chunk_idx, chunk_mask, J0,
                 n_stations: int, wt_base, nu0=None,
                 config: SageConfig = SageConfig(), os_id=None, key=None):
    """:func:`sagefit` with the EM/cluster loops on the host.

    Identical math; each device execution is one cluster solve (or the
    joint refine), which bounds every XLA program's execution time and
    scales to large cluster counts without giant compilations. ADMM mode is not offered here — the
    mesh ADMM program must stay fully traced (use :func:`sagefit`).
    """
    M = coh.shape[0]
    # dtype policy: quantize the staged data once on entry (identity
    # under "f32" / pre-quantized staging); host-side EM state in the
    # accumulator dtype. The storage dtype rides the fusion/promotion
    # cache keys below through str(x8.dtype).
    x8 = dtp.to_storage(x8, dtp.storage_dtype(config.dtype_policy,
                                              x8.dtype))
    wt_base = dtp.to_storage(wt_base, x8.dtype)
    dtype = dtp.acc_dtype(x8.dtype)
    robust = _is_robust(config.solver_mode)
    if config.jones_mode != "full":
        J0 = ne.jones_constrain(J0, config.jones_mode)
    if nu0 is None:
        nu0 = config.nulow
    if key is None:
        key = jax.random.PRNGKey(42)

    total_iter = M * config.max_iter
    iter_bar = int(-(-0.8 * total_iter // M))

    # max_emiter drives only THIS host loop; strip it (and the
    # host-only execution-plan knobs) from the static config handed to
    # the jitted programs so the first-tile EM boost (pipeline.py) and
    # runs differing only in force knobs reuse the compiled
    # per-cluster/sweep/refine programs instead of compiling a second
    # identical set.
    fuse_mode, promote_mode = config.fuse, config.promote
    dev_config = config._replace(max_emiter=0, fuse="auto", promote="auto",
                                 inflight_warm=False)
    # per-sweep group widths (cold-start restriction, see SageConfig)
    G0_w, Gs_w = _inflight_widths(config, M)

    os_ids, os_nsub = (None, 0) if os_id is None else \
        (jnp.asarray(os_id[0]), int(os_id[1]))
    chunk_idx = jnp.asarray(chunk_idx)
    chunk_mask = jnp.asarray(chunk_mask)

    # sweep-fusion and full-trace-promotion verdicts are remembered per
    # problem shape across calls — re-learning fusion every solve cost
    # ~M extra device round-trips per tile (the warm-path gap between
    # round-2 and round-3 config-1 numbers). The fusion key deliberately
    # excludes the iteration budget (dev_config strips max_emiter, and a
    # sweep's cost doesn't depend on how many sweeps run) so the
    # first-tile EM boost and the rest-tiles share one verdict; the
    # promotion key must include the budget — it bounds a WHOLE solve.
    # The force knobs ("on"/"off") bypass the caches entirely.
    fuse_key = (M, x8.shape, n_stations, chunk_mask.shape,
                str(x8.dtype), dev_config, os_id is None, os_nsub)
    promote_key = fuse_key + (config.max_emiter, config.max_lbfgs)
    promoted = promote_mode == "on" or (
        promote_mode == "auto" and _PROMOTE_CACHE.get(promote_key, False))
    n0 = _dispatched()
    if promoted:
        # whole solve proven to fit the per-execution budget: one
        # traced program, minimal device round-trips
        J, info = _call("sagefit", _jit_sagefit, x8, coh, sta1, sta2,
                        chunk_idx, chunk_mask, J0, n_stations, wt_base,
                        jnp.asarray(nu0, dtype),
                        config._replace(fuse="auto", promote="auto"),
                        os_ids if os_id is not None else None,
                        os_nsub, key)
        return J, _plan_info(info, "promoted", n0, config, x8)
    xres, res_0 = _call("prelude", _jit_prelude, x8, coh, sta1, sta2,
                        chunk_idx, J0, wt_base, row_period=config.nbase)
    # the per-sweep/per-cluster programs DONATE their state carries
    # (J, xres, nerr_acc, nuM) so XLA reuses the buffers in place
    # instead of allocating fresh HBM every dispatch; the first sweep
    # would otherwise consume the CALLER's J0 buffer, so hand it a copy
    # (one small transfer per solve vs ~max_emiter donated round trips)
    J = J0.copy() if isinstance(J0, jax.Array) else J0
    nerr = jnp.zeros((M,), dtype)
    nuM = jnp.full((M,), jnp.asarray(nu0, dtype))
    fused = (fuse_mode == "on" or
             (fuse_mode == "auto" and _FUSION_CACHE.get(fuse_key, False)))
    ran_fused = fused   # what a solve of no sweeps would have run
    sweep_times: list = []
    tk_total = jnp.zeros((N_TK,), jnp.int32)
    for ci in range(config.max_emiter):
        weighted = config.randomize and (ci % 2 == 1)
        last = ci == config.max_emiter - 1
        kci = jax.random.fold_in(key, ci)
        if config.randomize and M > 1:
            if weighted:
                order = np.argsort(-np.asarray(nerr))
            else:
                order = np.asarray(jax.random.permutation(
                    jax.random.fold_in(key, 104729 + ci), M))
        else:
            order = np.arange(M)
        # cold-start width restriction applies to the first sweep only;
        # the device programs see the EXACT width via config.inflight
        Gi = G0_w if ci == 0 else Gs_w
        cfg_i = dev_config._replace(inflight=Gi)
        ran_fused = fused   # the mode THIS sweep executes (the auto
        #                     verdict below may flip `fused` for the next)
        if fused:
            t_sweep = time.perf_counter()
            J, xres, nerr_acc, nuM, tk = _call("em_sweep", _jit_em_sweep,
                J, xres, nuM, coh, sta1, sta2, chunk_idx, chunk_mask,
                wt_base, nerr, jnp.asarray(weighted), jnp.asarray(last),
                kci, jnp.asarray(order, jnp.int32), os_ids,
                n_stations, cfg_i, total_iter, iter_bar, os_nsub)
            tk_total = tk_total + tk
            with dtrace.phase("wait"):
                # jaxlint: disable=host-sync -- deliberate ONE-per-sweep timing barrier: the auto fuse/promote plan learns from real sweep wall-clock (bounded-execution contract)
                jax.block_until_ready(J)
            sweep_times.append(time.perf_counter() - t_sweep)
        else:
            t_sweep = time.perf_counter()
            nerr_acc = jnp.zeros((M,), dtype)
            if Gi == 1:
                for cj in order:
                    J, xres, nerr_acc, nuM, tk = _call(
                        "cluster_update", _jit_cluster_update,
                        jnp.asarray(int(cj), jnp.int32), J, xres,
                        nerr_acc, nuM, coh, sta1, sta2, chunk_idx,
                        chunk_mask, wt_base, nerr, jnp.asarray(weighted),
                        jnp.asarray(last), kci, None, os_ids, n_stations,
                        cfg_i, total_iter, iter_bar, os_nsub)
                    tk_total = tk_total + tk
            else:
                opad = np.concatenate(
                    [np.asarray(order),
                     np.full((-(-M // Gi)) * Gi - M, M)]).astype(np.int32)
                anchor = _call("wres2", _jit_wres2, xres, wt_base)
                for g in range(len(opad) // Gi):
                    J, xres, nerr_acc, nuM, tk = _call(
                        "group_update", _jit_group_update,
                        jnp.asarray(opad[g * Gi:(g + 1) * Gi]), J, xres,
                        nerr_acc, nuM, coh, sta1, sta2, chunk_idx,
                        chunk_mask, wt_base, nerr, jnp.asarray(weighted),
                        jnp.asarray(last), kci, os_ids, n_stations,
                        cfg_i, total_iter, iter_bar, os_nsub, anchor)
                    tk_total = tk_total + tk
            with dtrace.phase("wait"):
                # jaxlint: disable=host-sync -- deliberate ONE-per-sweep timing barrier: the fuse=auto verdict needs the unfused sweep's real wall-clock
                jax.block_until_ready(J)
            # the fused program does the same work minus dispatch overhead,
            # so a 25 s per-cluster sweep bounds its single execution
            if fuse_mode == "auto":
                fused = time.perf_counter() - t_sweep < 25.0
                _FUSION_CACHE[fuse_key] = fused
                _learned("fuse", fuse_key, fused)
        total = jnp.sum(nerr_acc)
        if dtrace.active() or obs.active():
            # convergence record per EM sweep; the float()/int() syncs
            # are behind the active() gates so disabled runs pay nothing
            sweep_wall = time.perf_counter() - t_sweep
            trips = int(tk_total[0])
            err_red = float(total)
            dtrace.emit("em_sweep", sweep=ci, wall_s=sweep_wall,
                        fused=bool(ran_fused), groups=int(Gi),
                        err_reduction=err_red, solver_iters=trips)
            if obs.active():
                obs.inc("solver_sweeps_total")
                obs.observe("em_sweep_seconds", sweep_wall)
                obs.set_gauge("em_sweep_err_reduction", err_red)
                obs.set_gauge("em_sweep_solver_iters", trips)
        # normalization stays on device (the float(total) sync here was
        # a per-sweep dispatch stall — jaxlint host-sync); same guarded
        # formula as the tiles driver below
        nerr = jnp.where(total > 0, nerr_acc / jnp.maximum(total, 1e-30),
                         nerr_acc)

    # promote: non-first fused sweeps are warm device executions, so
    # max_emiter of them (+ refine margin) bounds the traced program's
    # execution time; promote only when comfortably under the budget.
    # A cold restricted first sweep (G0 < Gs) runs ~Gs/G0 times more
    # group dispatches than a steady sweep and the promoted program
    # includes it — charge that extra cost or the estimate undershoots
    # the budget.
    warm = sweep_times[1:] if len(sweep_times) > 1 else sweep_times
    cold_extra = (Gs_w / G0_w - 1.0) if G0_w != Gs_w else 0.0
    if (promote_mode == "auto" and warm
            and max(warm) * (config.max_emiter + 1 + cold_extra)
            < _PROMOTE_BUDGET_S):
        _PROMOTE_CACHE[promote_key] = True
        _learned("promote", promote_key, True)

    mean_nu = jnp.clip(jnp.mean(nuM), config.nulow, config.nuhigh)
    lbfgs_k = passes = jnp.zeros((), jnp.int32)
    if config.max_lbfgs > 0:
        J, res_1, lbfgs_k, passes = _call(
            "refine", _jit_refine, x8, coh, sta1, sta2, chunk_idx, J,
            wt_base, mean_nu, n_stations, dev_config, robust)
    else:
        res_1 = _call("res", _jit_res, x8, coh, sta1, sta2, chunk_idx, J,
                      wt_base, row_period=config.nbase)
    return J, _plan_info(
        {"res_0": res_0, "res_1": res_1, "mean_nu": mean_nu,
         "nerr": nerr, "solver_iters": tk_total[0],
         "rejected_groups": tk_total[1], "cg_iters": tk_total[2],
         "row_passes": tk_total[3],
         "lbfgs_iters": lbfgs_k, "refine_passes": passes},
        "fused" if ran_fused else "per_cluster", n0, config, x8)


# ---------------------------------------------------------------------------
# multi-tile batched variant: T independent solve intervals as one program
# ---------------------------------------------------------------------------
#
# SAGE's cluster loop is sequential (P2) and each per-cluster system is
# small (8N x 8N with a handful of hybrid chunks), so a single tile keeps
# the MXU nearly idle — round-3 measured well under 1% utilization. Solve
# intervals (tiles) are INDEPENDENT problems; vmapping the whole solve
# over a tile axis multiplies every batched operation (normal-equation
# einsums, Cholesky factors, tCG matvecs) by T with near-constant step
# latency — the TPU equivalent of lmfit_cuda.c:450-516 keeping multiple
# clusters in flight per GPU. The math per tile is EXACTLY sagefit's:
# per-tile iteration budgets, robust nu, and cluster permutations ride
# through vmap (the while-loop bodies freeze converged/budget-exhausted
# states, see lm.py/rtr.py/lbfgs.py).

_TILE_AXES = (0, 0, None, None, None, None, 0)   # x8, coh, sta1, sta2,
#                                                  cidx, cmask, J0


@functools.partial(jax.jit,
                   static_argnames=("n_stations", "config", "os_nsub"))
def _jit_sagefit_tiles(x8, coh, sta1, sta2, chunk_idx, chunk_mask, J0,
                       n_stations, wt_base, nu0, config, os_ids, os_nsub,
                       keys):
    def one(x8_t, coh_t, J0_t, wt_t, key_t):
        os_id = None if os_ids is None else (os_ids, os_nsub)
        return sagefit(x8_t, coh_t, sta1, sta2, chunk_idx, chunk_mask,
                       J0_t, n_stations, wt_t, nu0=nu0, config=config,
                       os_id=os_id, key=key_t)
    return jax.vmap(one)(x8, coh, J0, wt_base, keys)


@functools.partial(jax.jit,
                   static_argnames=("n_stations", "config", "total_iter",
                                    "iter_bar", "os_nsub"),
                   donate_argnums=(0, 1, 2))
@jax.named_scope("sage/sweep")
def _jit_em_sweep_tiles(J, xres, nuM, coh, sta1, sta2, chunk_idx,
                        chunk_mask, wt_base, nerr_prev, weighted, last,
                        keys, perm, os_ids, n_stations, config, total_iter,
                        iter_bar, os_nsub):
    """One EM sweep over all clusters for T tiles at once (vmapped
    :func:`_jit_em_sweep`; per-tile visiting order ``perm`` [T, M])."""
    os_id = None if os_ids is None else (os_ids, os_nsub)
    return jax.vmap(
        lambda J_t, xres_t, nuM_t, coh_t, wt_t, nerr_t, key_t, perm_t:
        _em_sweep(J_t, xres_t, nuM_t, coh_t, sta1, sta2, chunk_idx,
                  chunk_mask, wt_t, nerr_t, weighted, last, key_t, perm_t,
                  os_id, n_stations, config, total_iter, iter_bar)
    )(J, xres, nuM, coh, wt_base, nerr_prev, keys, perm)


@functools.partial(jax.jit, static_argnames=("row_period",))
def _jit_prelude_tiles(x8, coh, sta1, sta2, chunk_idx, J0, wt_base,
                       row_period=0):
    return jax.vmap(
        lambda x8_t, coh_t, J0_t, wt_t: _jit_prelude.__wrapped__(
            x8_t, coh_t, sta1, sta2, chunk_idx, J0_t, wt_t, row_period)
    )(x8, coh, J0, wt_base)


@functools.partial(jax.jit, static_argnames=("n_stations", "config",
                                             "robust"),
                   donate_argnums=(5,))
def _jit_refine_tiles(x8, coh, sta1, sta2, chunk_idx, J, wt_base, mean_nu,
                      n_stations, config, robust):
    return jax.vmap(
        lambda x8_t, coh_t, J_t, wt_t, mnu_t: _jit_refine.__wrapped__(
            x8_t, coh_t, sta1, sta2, chunk_idx, J_t, wt_t, mnu_t,
            n_stations, config, robust)
    )(x8, coh, J, wt_base, mean_nu)


@functools.partial(jax.jit, static_argnames=("row_period",))
def _jit_res_tiles(x8, coh, sta1, sta2, chunk_idx, J, wt_base, row_period=0):
    return jax.vmap(
        lambda x8_t, coh_t, J_t, wt_t: _jit_res.__wrapped__(
            x8_t, coh_t, sta1, sta2, chunk_idx, J_t, wt_t, row_period)
    )(x8, coh, J, wt_base)


def tile_keys(n_tiles: int, base=None):
    """Per-tile PRNG keys. Tile 0 keeps the single-tile default key so a
    batched solve makes the same PRNG draws (subset choices, cluster
    permutations) for tile 0 as the unbatched driver."""
    base = jax.random.PRNGKey(42) if base is None else base
    if n_tiles == 1:
        return base[None]
    rest = jax.vmap(lambda t: jax.random.fold_in(base, t))(
        jnp.arange(1, n_tiles) + 1000)
    return jnp.concatenate([base[None], rest])


def sagefit_host_tiles(x8, coh, sta1, sta2, chunk_idx, chunk_mask, J0,
                       n_stations: int, wt_base, nu0=None,
                       config: SageConfig = SageConfig(), os_id=None,
                       keys=None):
    """:func:`sagefit_host` over a leading tile axis T.

    Args are sagefit_host's with x8 [T, B, 8], coh [T, M, B, 2, 2],
    J0 [T, M, K, N, 2, 2], wt_base [T, B, 8] and per-tile ``keys``
    [T, key]; geometry (sta1/sta2/chunk arrays) is shared — tiles of one
    dataset have identical baseline ordering. Returns (J [T, ...], info)
    with per-tile res_0/res_1/mean_nu/nerr arrays.

    Shares the sweep-fusion and full-trace-promotion machinery (and its
    caches) with the single-tile driver; the timed verdicts are learned
    per (shape, T) so a wide batch never runs as one long execution
    unproven.
    """
    T, M = coh.shape[0], coh.shape[1]
    if keys is None:
        keys = tile_keys(T)
    if T == 1:
        # Measured on-chip (2026-07-31, older chip record, 16 clusters): the
        # vmapped UNIT tile axis alone costs ~40% (16.2 vs 11.5 s warm
        # step) — every latency-bound solver op carries a [1, ...]
        # leading dim that changes TPU layouts without adding work. A
        # single tile takes the axis-free driver; PRNG stream matches
        # (keys[0] is tile 0's stream either way).
        J1, info1 = sagefit_host(x8[0], coh[0], sta1, sta2, chunk_idx,
                                 chunk_mask, J0[0], n_stations,
                                 wt_base[0], nu0=nu0, config=config,
                                 os_id=os_id, key=keys[0])
        info = {k: v if k in _PLAN_KEYS
                else jnp.asarray(v)[None] for k, v in info1.items()}
        return J1[None], info
    x8 = dtp.to_storage(x8, dtp.storage_dtype(config.dtype_policy,
                                              x8.dtype))
    wt_base = dtp.to_storage(wt_base, x8.dtype)
    dtype = dtp.acc_dtype(x8.dtype)
    robust = _is_robust(config.solver_mode)
    if config.jones_mode != "full":
        J0 = ne.jones_constrain(J0, config.jones_mode)
    if nu0 is None:
        nu0 = config.nulow

    total_iter = M * config.max_iter
    iter_bar = int(-(-0.8 * total_iter // M))
    fuse_mode, promote_mode = config.fuse, config.promote
    dev_config = config._replace(max_emiter=0, fuse="auto", promote="auto",
                                 inflight_warm=False)
    G0_w, Gs_w = _inflight_widths(config, M)

    os_ids, os_nsub = (None, 0) if os_id is None else \
        (jnp.asarray(os_id[0]), int(os_id[1]))
    chunk_idx = jnp.asarray(chunk_idx)
    chunk_mask = jnp.asarray(chunk_mask)

    fuse_key = (M, x8.shape, n_stations, chunk_mask.shape,
                str(x8.dtype), dev_config, os_id is None, os_nsub,
                "tiles")
    promote_key = fuse_key + (config.max_emiter, config.max_lbfgs)
    promoted = promote_mode == "on" or (
        promote_mode == "auto" and _PROMOTE_CACHE.get(promote_key, False))
    n0 = _dispatched()
    if promoted:
        J, info = _call("sagefit_tiles", _jit_sagefit_tiles, x8, coh,
                        sta1, sta2, chunk_idx, chunk_mask, J0, n_stations,
                        wt_base, jnp.asarray(nu0, dtype),
                        config._replace(fuse="auto", promote="auto"),
                        os_ids if os_id is not None else None,
                        os_nsub, keys)
        return J, _plan_info(info, "promoted", n0, config, x8)
    xres, res_0 = _call("prelude_tiles", _jit_prelude_tiles, x8, coh,
                        sta1, sta2, chunk_idx, J0, wt_base,
                        row_period=config.nbase)
    # donation guard: see sagefit_host — the sweep programs consume
    # their state-carry buffers in place
    J = J0.copy() if isinstance(J0, jax.Array) else J0
    nerr = jnp.zeros((T, M), dtype)
    nuM = jnp.full((T, M), jnp.asarray(nu0, dtype))
    fused = (fuse_mode == "on" or
             (fuse_mode == "auto" and _FUSION_CACHE.get(fuse_key, False)))
    ran_fused = fused   # what a solve of no sweeps would have run
    sweep_times: list = []
    tk_total = jnp.zeros((T, N_TK), jnp.int32)
    for ci in range(config.max_emiter):
        weighted = config.randomize and (ci % 2 == 1)
        last = ci == config.max_emiter - 1
        kci = jax.vmap(lambda k: jax.random.fold_in(k, ci))(keys)
        if config.randomize and M > 1:
            if weighted:
                order = np.argsort(-np.asarray(nerr), axis=1)
            else:
                order = np.stack([
                    np.asarray(jax.random.permutation(
                        jax.random.fold_in(keys[t], 104729 + ci), M))
                    for t in range(T)])
        else:
            order = np.tile(np.arange(M), (T, 1))
        order = jnp.asarray(order, jnp.int32)
        t_sweep = time.perf_counter()
        Gi = G0_w if ci == 0 else Gs_w      # cold-start width restriction
        cfg_i = dev_config._replace(inflight=Gi)
        ran_fused = fused   # the mode THIS sweep executes (see sagefit_host)
        if fused:
            J, xres, nerr_acc, nuM, tk = _call(
                "em_sweep_tiles", _jit_em_sweep_tiles,
                J, xres, nuM, coh, sta1, sta2, chunk_idx, chunk_mask,
                wt_base, nerr, jnp.asarray(weighted), jnp.asarray(last),
                kci, order, os_ids, n_stations, cfg_i, total_iter,
                iter_bar, os_nsub)
            tk_total = tk_total + tk
            with dtrace.phase("wait"):
                # jaxlint: disable=host-sync -- deliberate ONE-per-sweep timing barrier: the auto fuse/promote plan learns from real sweep wall-clock (bounded-execution contract)
                jax.block_until_ready(J)
            sweep_times.append(time.perf_counter() - t_sweep)
        else:
            nerr_acc = jnp.zeros((T, M), dtype)
            if Gi == 1:
                for cj in range(M):
                    J, xres, nerr_acc, nuM, tk = _call(
                        "cluster_update_tiles", _jit_cluster_update_tiles,
                        order[:, cj], J, xres, nerr_acc, nuM, coh,
                        sta1, sta2, chunk_idx, chunk_mask, wt_base, nerr,
                        jnp.asarray(weighted), jnp.asarray(last), kci,
                        os_ids, n_stations, cfg_i, total_iter,
                        iter_bar, os_nsub)
                    tk_total = tk_total + tk
            else:
                pad = (-(-M // Gi)) * Gi - M
                opad = jnp.concatenate(
                    [order, jnp.full((T, pad), M, order.dtype)], axis=1)
                anchor = _call("wres2_tiles", _jit_wres2_tiles, xres,
                               wt_base)
                for g in range(opad.shape[1] // Gi):
                    J, xres, nerr_acc, nuM, tk = _call(
                        "group_update_tiles", _jit_group_update_tiles,
                        opad[:, g * Gi:(g + 1) * Gi], J, xres, nerr_acc,
                        nuM, coh, sta1, sta2, chunk_idx, chunk_mask,
                        wt_base, nerr, jnp.asarray(weighted),
                        jnp.asarray(last), kci, os_ids, n_stations,
                        cfg_i, total_iter, iter_bar, os_nsub, anchor)
                    tk_total = tk_total + tk
            with dtrace.phase("wait"):
                # jaxlint: disable=host-sync -- deliberate ONE-per-sweep timing barrier: the fuse=auto verdict needs the unfused sweep's real wall-clock
                jax.block_until_ready(J)
            if fuse_mode == "auto":
                fused = time.perf_counter() - t_sweep < 25.0
                _FUSION_CACHE[fuse_key] = fused
                _learned("fuse", fuse_key, fused)
        total = jnp.sum(nerr_acc, axis=1, keepdims=True)
        if dtrace.active() or obs.active():
            sweep_wall = time.perf_counter() - t_sweep
            trips = int(jnp.sum(tk_total[:, 0]))
            err_red = float(jnp.sum(total))
            dtrace.emit("em_sweep", sweep=ci, wall_s=sweep_wall,
                        fused=bool(ran_fused), groups=int(Gi), tiles=T,
                        err_reduction=err_red, solver_iters=trips)
            if obs.active():
                obs.inc("solver_sweeps_total")
                obs.observe("em_sweep_seconds", sweep_wall)
                obs.set_gauge("em_sweep_err_reduction", err_red)
                obs.set_gauge("em_sweep_solver_iters", trips)
        nerr = jnp.where(total > 0, nerr_acc / jnp.maximum(total, 1e-30),
                         nerr_acc)

    warm = sweep_times[1:] if len(sweep_times) > 1 else sweep_times
    # charge the cold restricted first sweep's extra dispatches (see
    # the sagefit_host promote comment)
    cold_extra = (Gs_w / G0_w - 1.0) if G0_w != Gs_w else 0.0
    if (promote_mode == "auto" and warm
            and max(warm) * (config.max_emiter + 1 + cold_extra)
            < _PROMOTE_BUDGET_S):
        _PROMOTE_CACHE[promote_key] = True
        _learned("promote", promote_key, True)

    mean_nu = jnp.clip(jnp.mean(nuM, axis=1), config.nulow, config.nuhigh)
    lbfgs_k = passes = jnp.zeros((T,), jnp.int32)
    if config.max_lbfgs > 0:
        J, res_1, lbfgs_k, passes = _call(
            "refine_tiles", _jit_refine_tiles, x8, coh, sta1, sta2,
            chunk_idx, J, wt_base, mean_nu, n_stations, dev_config, robust)
    else:
        res_1 = _call("res_tiles", _jit_res_tiles, x8, coh, sta1, sta2,
                      chunk_idx, J, wt_base, row_period=config.nbase)
    return J, _plan_info(
        {"res_0": res_0, "res_1": res_1, "mean_nu": mean_nu,
         "nerr": nerr, "solver_iters": tk_total[:, 0],
         "rejected_groups": tk_total[:, 1],
         "cg_iters": tk_total[:, 2],
         "row_passes": tk_total[:, 3],
         "lbfgs_iters": lbfgs_k, "refine_passes": passes},
        "fused" if ran_fused else "per_cluster", n0, config, x8)


@functools.partial(jax.jit,
                   static_argnames=("n_stations", "config", "total_iter",
                                    "iter_bar", "os_nsub"),
                   donate_argnums=(1, 2, 3, 4))
@jax.named_scope("sage/sweep")
def _jit_cluster_update_tiles(cj, J, xres, nerr_acc, nuM, coh, sta1,
                              sta2, chunk_idx, chunk_mask, wt_base,
                              nerr_prev, weighted, last, keys, os_ids,
                              n_stations, config, total_iter, iter_bar,
                              os_nsub):
    """Vmapped :func:`_jit_cluster_update`: one cluster visit (per-tile
    cluster index ``cj`` [T]) across all tiles in one execution."""
    def one(cj_t, J_t, xres_t, nerr_acc_t, nuM_t, coh_t, wt_t,
            nerr_t, key_t):
        os_id = None if os_ids is None else (os_ids, os_nsub)
        return _cluster_update(cj_t, (J_t, xres_t, nerr_acc_t, nuM_t,
                                      jnp.zeros((N_TK,), jnp.int32)),
                               _visit_planes(cj_t, coh_t, wt_t, sta1, sta2,
                                             chunk_idx, J_t.shape[1],
                                             n_stations, config),
                               coh_t, chunk_mask, wt_t, n_stations, config,
                               nerr_t, weighted, last, key_t, None, os_id,
                               total_iter, iter_bar)
    return jax.vmap(one)(cj, J, xres, nerr_acc, nuM, coh, wt_base,
                         nerr_prev, keys)


@functools.partial(jax.jit,
                   static_argnames=("n_stations", "config", "total_iter",
                                    "iter_bar", "os_nsub"),
                   donate_argnums=(1, 2, 3, 4))
@jax.named_scope("sage/sweep")
def _jit_group_update_tiles(cjs, J, xres, nerr_acc, nuM, coh, sta1,
                            sta2, chunk_idx, chunk_mask, wt_base,
                            nerr_prev, weighted, last, keys, os_ids,
                            n_stations, config, total_iter, iter_bar,
                            os_nsub, res_anchor):
    """Vmapped :func:`_jit_group_update`: one in-flight group visit
    (per-tile index rows ``cjs`` [T, G]) across all tiles;
    ``res_anchor`` [T] carries each tile's sweep-entry safeguard ref."""
    def one(cjs_t, J_t, xres_t, na_t, nuM_t, coh_t, wt_t, nerr_t,
            key_t, anch_t):
        os_id = None if os_ids is None else (os_ids, os_nsub)
        return _group_update(cjs_t, (J_t, xres_t, na_t, nuM_t,
                                     jnp.zeros((N_TK,), jnp.int32)),
                             _sweep_planes(coh_t, wt_t, sta1, sta2,
                                           chunk_idx, J_t.shape[1],
                                           n_stations, config),
                             coh_t, chunk_mask,
                             wt_t, n_stations, config, nerr_t, weighted,
                             last, key_t, None, os_id, total_iter,
                             iter_bar, res_anchor=anch_t)
    return jax.vmap(one)(cjs, J, xres, nerr_acc, nuM, coh, wt_base,
                         nerr_prev, keys, res_anchor)


def bfgsfit(x8, coh, sta1, sta2, chunk_idx, J0, n_stations: int,
            wt_base, config: SageConfig = SageConfig(), nu: float = 2.0):
    """LBFGS-only joint solve over all clusters (``bfgsfit_visibilities``,
    lmfit.c:1127) — the per-channel bandpass solver (-b 1,
    fullbatch_mode.cpp:442-488). Warm-started from ``J0``; robust
    Student's-t cost when the solver mode is robust. Residual figures
    use the same B*8 normalization as :func:`sagefit`.
    """
    x8 = dtp.to_storage(x8, dtp.storage_dtype(config.dtype_policy,
                                              x8.dtype))
    wt_base = dtp.to_storage(wt_base, x8.dtype)
    dtype = dtp.acc_dtype(x8.dtype)
    M, kmax = J0.shape[0], J0.shape[1]
    n = x8.shape[0] * 8
    robust = _is_robust(config.solver_mode)
    mode = config.jones_mode
    if mode != "full":
        J0 = ne.jones_constrain(J0, mode)
    shape = (M * kmax, n_stations, ne.jones_npar(mode))
    Jflat0 = J0.reshape(M * kmax, n_stations, 2, 2)
    if mode == "full":
        Jref = None
        p0 = ne.jones_c2r(Jflat0).reshape(-1).astype(dtype)
    else:
        Jref = Jflat0
        p0 = ne.params_from_jones(Jref, mode).reshape(-1).astype(dtype)

    cost_fn, grad_fn, line_fn = _refine_cost_fn(
        x8, coh, sta1, sta2, chunk_idx, wt_base, shape, kmax, n_stations,
        robust, nu, mode=mode, Jref=Jref, row_period=config.nbase)
    res_0 = jnp.linalg.norm(dtp.acc(
        (x8 - full_model8(J0, coh, sta1, sta2, chunk_idx, config.nbase))
        * wt_base)) / n
    p1, k, passes = lbfgs_mod.lbfgs_fit(
        cost_fn, grad_fn, p0, itmax=config.max_lbfgs,
        M=config.lbfgs_m, return_iters=True, line_func=line_fn)
    if mode == "full":
        J = ne.jones_r2c(p1.reshape(shape)).reshape(M, kmax, n_stations,
                                                    2, 2)
    else:
        J = ne.jones_from_params(p1.reshape(shape), mode, Jref).reshape(
            M, kmax, n_stations, 2, 2)
    res_1 = jnp.linalg.norm(dtp.acc(
        (x8 - full_model8(J, coh, sta1, sta2, chunk_idx, config.nbase))
        * wt_base)) / n
    return J, {"res_0": res_0, "res_1": res_1, "lbfgs_iters": k,
               "refine_passes": passes}
