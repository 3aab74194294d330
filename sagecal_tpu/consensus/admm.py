"""Distributed consensus-ADMM across frequency subbands on a device mesh.

Capability parity with the reference's MPI master/slave per-timeslot loop
(``src/MPI/sagecal_master.cpp:621-890`` + ``sagecal_slave.cpp:488-930``,
SURVEY.md section 3.3), re-architected as ONE SPMD program over a
``jax.sharding.Mesh`` with a "freq" axis (SURVEY.md P9/P10/C1):

- the hub-and-spoke MPI tag protocol disappears: J/Y updates run
  shard-local per subband; the master's gather(Y) + Z-solve + broadcast(BZ)
  becomes ``psum`` over the subband axis + a replicated small solve;
- ADMM iteration 0: plain SAGE solve, dual seed Y = rho*J, then manifold
  averaging of Y across frequency (master :739-751) — here a psum-based
  Procrustes averaging (consensus/manifold.py);
- iterations k>0: augmented-Lagrangian SAGE solve (admm_solve.c:221
  semantics via solvers.sage with the admm term), Y += rho*J, z-sum via
  psum, Z = Bii z, Y -= rho*BZ (slave :686-770);
- optional Barzilai-Borwein adaptive rho per (subband, cluster)
  (slave :782-786, consensus_poly.c:923);
- rho is scaled by each subband's unflagged-data fraction
  (master :646-650).

Data multiplexing (Scurrent rotation, master :883-889) is unnecessary when
every subband owns a shard; when F exceeds the mesh size, multiple subbands
ride one shard via the local leading axis — same effect, no rotation.

When F does not divide the mesh size, the caller pads the subband axis up
to ``Fl * ndev`` (replicating a real subband's data so padded solves stay
numerically tame) and passes the REAL count as ``nf_total``: rows with
global index >= nf_total get zero basis rows in the padded ``B_poly``,
zero rho, and are masked out of the manifold mean and every dual/Y
quantity — so 7 subbands use 8 devices instead of shrinking the mesh to a
divisor (the reference's analogue is idle slaves, sagecal_master.cpp:155).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sagecal_tpu.consensus import manifold as mf
from sagecal_tpu.consensus import poly as cpoly
from sagecal_tpu.diag import trace as dtrace
from sagecal_tpu.obs import metrics as obs
from sagecal_tpu.solvers import normal_eq as ne
from sagecal_tpu.solvers import sage


class ADMMConfig(NamedTuple):
    n_admm: int = 10
    npoly: int = 2
    poly_type: int = 2
    # scalar, or [M] per-cluster array: an explicit -G rho file, or a
    # banked schedule seeded by --prior-cache read (serve/priors.py —
    # the previous run's converged per-cluster rho; -G wins over it)
    rho: float = 5.0
    adaptive_rho: bool = False
    manifold_iters: int = 20     # master :740 Niter
    sage: sage.SageConfig = sage.SageConfig()
    # -X l2,l1,order,fista_iters,cadence (README.md:160-166); None = off
    spatialreg: tuple | None = None
    federated_alpha: float = 0.0  # -u : alpha of the spatial/federated prior


#: device scope (``jax.named_scope``, metadata only) of what consensus
#: adds to a J update: the z-sum ``psum``, the ``Bii`` solve, the dual
#: and rho updates. Under ``sage/`` beside the solver's own scopes
#: (``sage/prelude|sweep|refine|final``) and ``sage/manifold``, where a
#: profiler trace's reader looks for them.
CONSENSUS_SCOPE = "sage/consensus"


def pad_subbands(arrays, B_poly, nf: int, ndev: int):
    """THE padding contract for uneven F over the mesh, in one place.

    arrays: sequence of host arrays with a leading real-subband axis
    [nf, ...]. Returns (padded_arrays, padded_B, fpad): each array's
    leading axis padded to ``fpad = ceil(nf/ndev)*ndev`` (ndev may exceed
    nf: fpad then equals ndev) by replicating the first subband — padded
    solves stay numerically tame — and B_poly gains zero rows so padded
    slots contribute nothing to any collective. Pass the REAL count nf as
    ``nf_total`` to :func:`make_admm_runner`; slice every per-subband
    output back to [:nf] on the host.
    """
    ndev = max(int(ndev), 1)
    fpad = -(-max(nf, ndev) // ndev) * ndev
    if fpad == nf:
        return list(arrays), np.asarray(B_poly), fpad
    out = []
    for a in arrays:
        a = np.asarray(a)
        out.append(np.concatenate(
            [a, np.broadcast_to(a[:1], (fpad - nf,) + a.shape[1:])]))
    B = np.asarray(B_poly)
    B = np.vstack([B, np.zeros((fpad - nf, B.shape[1]), B.dtype)])
    return out, B, fpad


def lockstep(trips, nf: int, group: int):
    """What a fold costs in loop bodies, from a runner's ``trips``
    [n_admm, Fpad, 2] (host array): ``(useful, pct)``.

    ``useful``: trust-region plus inner-CG iterations the ``nf`` real
    subbands' J updates needed, summed over subbands and ADMM
    iterations. ``pct``: of the loop bodies a device executed, the share
    spent on a slot that had already ended (or is padding). Subbands
    that share one execution -- ``group`` consecutive slots: ``Fl`` of a
    device of the mesh, ``--block-f`` of the blocked plan -- run their J
    update under ``jax.vmap``, where every ``lax.while_loop`` makes the
    trips of the slowest for all, the finished ones frozen by masks: per
    ADMM iteration and group ``1 - sum_f t_f / (group x max_f t_f)``
    with ``t_f`` subband f's two counts added, the mean over groups and
    iterations, in percent. The loops nest (truncated CG inside the
    trust region), so the slowest subband of a trip is not always the
    same one and this is a lower bound. Exactly 0 at ``group`` 1."""
    t = np.asarray(trips, np.int64).sum(axis=-1)        # [n_admm, Fpad]
    real = np.arange(t.shape[1]) < nf
    useful = int(t[:, real].sum())
    if group <= 1 or t.size == 0:
        return useful, 0.0
    short = -t.shape[1] % group         # a ragged last block ran padded
    t, real = np.pad(t, ((0, 0), (0, short))), np.pad(real, (0, short))
    tg = np.where(real, t, 0).reshape(t.shape[0], -1, group)
    top = t.reshape(tg.shape).max(axis=-1)
    share = np.where(top > 0, tg.sum(axis=-1) / (group * np.maximum(top, 1)),
                     1.0)
    return useful, float(100.0 * (1.0 - share.mean()))


def _blocks(J_r8):
    """[.., M, K, N, 8] real Jones -> [.., M*K, 2N, 2] complex blocks."""
    J = ne.jones_r2c(J_r8)
    shp = J.shape
    J = J.reshape(shp[:-5] + (shp[-5] * shp[-4], shp[-3], 2, 2))
    return mf.jones_to_blocks(J)


def _unblocks(X, m, k, n):
    J = mf.blocks_to_jones(X)
    J = J.reshape(J.shape[:-4] + (m, k, n, 2, 2))
    return ne.jones_c2r(J)


@jax.named_scope("sage/manifold")    # beside sage/consensus in a trace
def manifold_average_mesh(Y_r8, axis_name, nf_total: int, m: int,
                          k: int, n: int, niter: int = 20):
    """Mesh version of calculate_manifold_average over the freq axis.

    Y_r8: [Fl, M, K, N, 8] local shard (Fl subbands per device). Each
    (m, k) block is rotated by ONE unitary toward the cross-frequency
    average; the reference block is the globally-first subband.
    ``axis_name=None`` means all subbands are local (single-device
    blocked path): psums become local sums.
    """
    psum = ((lambda x: x) if axis_name is None
            else (lambda x: jax.lax.psum(x, axis_name)))
    X0 = _blocks(Y_r8)                      # [Fl, MK, 2N, 2] complex
    # broadcast only the globally-first subband's block as the reference
    # (cheaper than all_gathering the whole array to read one element)
    if axis_name is None:
        ref = X0[0]
    else:
        is_first = (jax.lax.axis_index(axis_name) == 0)
        ref = psum(jnp.where(is_first, X0[0], jnp.zeros_like(X0[0])))

    Xp = jax.vmap(lambda Xf: mf.procrustes_project(ref, Xf))(X0)

    def body(Xp, _):
        mean = psum(jnp.sum(Xp, axis=0)) / nf_total
        Xp = jax.vmap(lambda Xf: mf.procrustes_project(mean, Xf))(Xp)
        return Xp, None

    Xp, _ = jax.lax.scan(body, Xp, None, length=niter)
    mean = psum(jnp.sum(Xp, axis=0)) / nf_total
    Xout = jax.vmap(lambda Xf: mf.procrustes_project(mean, Xf))(X0)
    return _unblocks(Xout, m, k, n)


def _emit_deferred(pend, interval):
    """Emit the host loop's collected per-iteration admm_iter records
    in ONE batched device->host fetch AFTER the loop (overlap-
    preserving: tracing never serializes the ADMM dispatch chain
    behind per-iteration float() syncs). ``pend``: (iter, r1_mean,
    dual|None, rho_mean) device scalars, copies started async.
    Feeds BOTH telemetry sinks — the diag trace (a no-op without a
    tracer) and the obs registry (consensus-residual gauges + the
    iteration counter); ``pend`` is only collected when one of the two
    is active, so the disabled path stays sync-free."""
    if not pend:
        return
    from sagecal_tpu import sched as _sched
    scalars = [x for rec in pend for x in rec[1:] if x is not None]
    _sched.start_host_copy(*scalars)
    # the last iteration's scalars end with the solve: what blocks here
    # is the device, not the host
    _sched.wait_device(*scalars)
    for it, r1m, dual, rhom in pend:
        r1 = float(np.asarray(r1m))
        du = 0.0 if dual is None else float(np.asarray(dual))
        rho = float(np.asarray(rhom))
        dtrace.emit("admm_iter", interval=interval, iter=it,
                    r1_mean=r1, dual=du, rho_mean=rho, deferred=True)
        if obs.active():
            obs.inc("admm_iterations_total")
            obs.set_gauge("admm_primal_residual", r1)
            obs.set_gauge("admm_dual_residual", du)
            obs.set_gauge("admm_rho_mean", rho)


def make_admm_runner(dsky, sta1, sta2, cidx, cmask, n_stations: int,
                     fdelta: float, B_poly: np.ndarray, cfg: ADMMConfig,
                     mesh: Mesh, nf_total: int, with_shapelets: bool = False,
                     spatial_coords=None, host_loop: bool = False,
                     dobeam: int = 0, nbase: int | None = None,
                     donate: bool = True, timer: list | None = None,
                     _return_parts: bool = False):
    """Build the jitted per-timeslot consensus-ADMM program.

    Returns ``run(x8F, uF, vF, wF, freqF, wtF, fratioF, J0F_r8)`` operating
    on [F, ...] arrays sharded over the mesh "freq" axis; gives back
    (JF_r8, Z, rhoF, res0, res1, r1_per_admm, dual_per_admm, Y0F_r8,
    trips) where Y0F is the manifold-projected rho*J of iteration 0 (the
    MDL input, master :815-822) and trips [n_admm, F, 2] i32 holds, per
    ADMM iteration and subband, the ``solver_iters`` and ``cg_iters`` of
    that subband's own J update (:func:`lockstep` reads them).

    B_poly: [Fpad, P] polynomial basis (host numpy, replicated); when the
    staged subband axis Fpad exceeds the real count ``nf_total`` (uneven
    F over the mesh), rows >= nf_total must be zero and the caller
    replicates some real subband's data into the padded slots — they are
    masked out of every collective.
    spatial_coords: ([Mt] r, [Mt] theta) per-effective-cluster polar
    centroids (spatial.cluster_polar_coords) — required when
    cfg.spatialreg is set.
    host_loop: run the ADMM iteration loop on the host, one bounded
    jitted execution per iteration (identical math; bounds each
    execution's wall-clock, and is cheaper to compile: the scan body
    becomes a reusable program).
    donate: host-loop only — donate the ADMM carry buffers to each body
    execution (in-place reuse; bit-identical results, gated by
    tests/test_donation.py). False keeps every input buffer alive, for
    embedders that hold references across iterations.
    timer: host-loop only — optional list receiving
    ("iter0"|"body[k]", seconds) per device execution, the same
    telemetry contract as make_admm_runner_blocked. The returned
    runner also exposes ``run.consensus_program`` — the per-iteration
    consensus half (Z psum + duals + BB rho) as its OWN mesh program,
    so a caller can time the collective overhead apart from the J-update
    solves (tests/test_krylov.py does; ROADMAP "harness hooks").

    Dtype policy (MIGRATION.md "Dtype policy"): ``x8F``/``wtF`` may
    arrive in the reduced storage dtype (cli_mpi stages them per
    ``--dtype-policy``; ``cfg.sage.dtype_policy`` rides into every
    sagefit call, which owns the storage/accumulate split). The
    consensus state itself — Y, Z, BZ, rho, and the polynomial basis —
    NEVER quantizes: it derives from the f32 Jones state (``JF.dtype``
    below), so the ADMM convergence analysis is untouched by the
    policy and the Z psum collectives move f32.
    """
    from sagecal_tpu.consensus import spatial as sp
    from sagecal_tpu.rime import predict as rp

    M = int(np.asarray(cmask).shape[0])
    K = int(np.asarray(cmask).shape[1])
    N = n_stations
    Ppoly = B_poly.shape[1]
    Bfull = jnp.asarray(B_poly)            # [F, P] replicated

    # --- spatial regularization setup (master :294-397), host-side once.
    # Phi blocks live on the padded (m, k) grid: padded chunk slots get
    # zero blocks so they never contribute to Phikk or the Z update.
    spat = None
    if cfg.spatialreg is not None:
        sh_l2, sh_mu, sh_n0, fista_iters, cadence = cfg.spatialreg
        rr_c, tt_c = spatial_coords
        G = int(sh_n0) * int(sh_n0)
        Phi, Phikk = sp.phi_padded(cmask, rr_c, tt_c, sh_n0, sh_l2)
        # stage complex as re/im pairs (no complex host<->device transfer)
        spat = dict(
            Phi_ri=jnp.asarray(np.stack([Phi.real, Phi.imag], -1)),
            Phikk_ri=jnp.asarray(np.stack([Phikk.real, Phikk.imag], -1)),
            mu=float(sh_mu), iters=int(fista_iters), cadence=int(cadence),
            G=G)

    cidx_j = jnp.asarray(cidx)
    cmask_j = jnp.asarray(cmask)
    sta1_j = jnp.asarray(sta1)
    sta2_j = jnp.asarray(sta2)

    from sagecal_tpu.io import dataset as _dsmod
    # sta1 is per ROW ([nbase*tilesz]); the caller supplies the true
    # baseline count for the row->timeslot map the beam indexes with
    tslot_j = None
    if dobeam:
        if nbase is None:
            raise ValueError("dobeam needs nbase (the per-timeslot "
                             "baseline count) for the row->tslot map")
        tslot_j = jnp.asarray(
            _dsmod.row_tslot(len(np.asarray(sta1)), nbase))

    def coh_for(u, v, w, freq, beam=None):
        # with -B: per-subband beam tables folded into the source sum
        # (precalculate_coherencies_multifreq_withbeam, the slaves'
        # predict path predict_withbeam.c:690). Fluxes at THIS subband's
        # frequency along each source's spectral index, as the residual
        # program takes them and as an upstream slave reads its sky at
        # its own MS's frequency: ``dsky`` holds one scaling, at the mean
        # of all subbands, and a J solved against that absorbs the flux
        # ratio, which the residual then applies a second time
        return rp.coherencies(dsky, u, v, w, freq[None], fdelta,
                              per_channel_flux=True,
                              with_shapelets=with_shapelets,
                              beam=beam, dobeam=dobeam, tslot=tslot_j,
                              sta1=sta1_j, sta2=sta2_j)[:, :, 0]

    def coh_subbands(uF, vF, wF, freqF, beamF=None):
        """``[Fl, M, B, 2, 2]``: the local subbands' coherencies, made
        ONCE an interval (they do not change over its ADMM iterations)
        and a subband at a time, BEFORE the vmap of the solves that take
        them: each subband's source sum then compiles as the residual
        program's does, rows x sources on full register tiles. Under
        that vmap the sum's contraction is a batched ``dot`` the TPU
        compiler lowers as a dilated ``convolution`` (``admm-f8-fold``:
        ``rime/phasor`` 26.9 ms an interval against 5.2; PERF.md section
        6, PR 49)."""
        if uF.shape[0] == 1:
            return coh_for(*jax.tree.map(
                lambda x: x[0], (uF, vF, wF, freqF, beamF)))[None]
        return jax.lax.map(lambda a: coh_for(*a),
                           (uF, vF, wF, freqF, beamF))

    # rows are [tilesz, nbase] per subband: forward the baseline period
    # to the solvers' normal-equation assembly (normal_eq row_period)
    sage_cfg = (cfg.sage if not nbase
                else cfg.sage._replace(nbase=int(nbase)))

    def _trips(info):
        # [2] i32: the trust-region (or LM) iterations and the inner CG
        # iterations THIS subband's solve needed. Under the vmap of a
        # fold a finished subband's loop carry is frozen while the
        # slowest one's trips run, so these stay its own (lockstep())
        return jnp.stack([info["solver_iters"],
                          info["cg_iters"]]).astype(jnp.int32)

    def local_solve_plain(x8, coh, wt, J_r8):
        J, info = sage.sagefit(x8, coh, sta1_j, sta2_j, cidx_j, cmask_j,
                               ne.jones_r2c(J_r8), N, wt, config=sage_cfg)
        return ne.jones_c2r(J), info["res_0"], info["res_1"], _trips(info)

    def local_solve_admm(x8, coh, wt, J_r8, Y_r8, BZ_r8, rho_m):
        # ADMM iterations k>0 always warm-start from the previous
        # iterate, so cluster groups (inflight>1) skip the cold-start
        # width restriction; iteration 0 (local_solve_plain, sage_cfg
        # unmodified) keeps it
        scfg = sage_cfg._replace(max_lbfgs=0, inflight_warm=True)
        J, info = sage.sagefit(x8, coh, sta1_j, sta2_j, cidx_j, cmask_j,
                               ne.jones_r2c(J_r8), N, wt, config=scfg,
                               admm=(Y_r8, BZ_r8, rho_m))
        return ne.jones_c2r(J), info["res_0"], info["res_1"], _trips(info)

    axis = "freq"

    def _brow(Fl, ax=axis):
        # per-subband basis rows: gather local rows from the replicated
        # Bfull via the global subband index of each local row. ax=None:
        # everything is local (single-device blocked path).
        dev_idx = 0 if ax is None else jax.lax.axis_index(ax)
        local_ids = dev_idx * Fl + jnp.arange(Fl, dtype=jnp.int32)
        return Bfull[local_ids]                  # [Fl, P]

    def _fmask(Fl, dtype, ax=axis):
        """[Fl, 1] 1.0 for real subbands, 0.0 for padded slots (global
        index >= nf_total when the caller padded F up to the mesh)."""
        dev_idx = 0 if ax is None else jax.lax.axis_index(ax)
        local_ids = dev_idx * Fl + jnp.arange(Fl, dtype=jnp.int32)
        return (local_ids < nf_total).astype(dtype)[:, None]

    # rho for ALL subbands (for Bii): [M, F]
    def all_rho(rhoF, ax=axis):
        if ax is None:
            return rhoF.T
        g = jax.lax.all_gather(rhoF, ax)         # [ndev, Fl, M]
        return g.reshape(-1, M).T                # [M, F]

    def _alpha_vec(rho_m, dtype):
        if spat is None:
            return None
        # per-cluster alpha scaled by initial rho, =alpha at max rho
        # (sagecal_master.cpp:577-579; matters with a -G rho file)
        return (cfg.federated_alpha * rho_m
                / jnp.maximum(jnp.max(rho_m), 1e-30)).astype(dtype)

    def z_update(Brow, YF, rhoF, alpha_vec, Zbar=None, Xd=None, ax=axis):
        """z = sum_f B_f Y_f where YF already holds Y + rho J as sent
        to the master (slave :686-700); Z = Bii z (master :755-779).
        With spatial reg the prior pulls in: z += alpha Zbar - X and
        Bii gains the federated +alpha I (master :668-673,:768-775)."""
        zsum_local = jnp.einsum("fp,fmknr->mpknr", Brow, YF)
        zsum = (zsum_local if ax is None
                else jax.lax.psum(zsum_local, ax))
        if Zbar is not None:
            # alphak[cm] Zbar - X (master :768-775)
            zsum = zsum + alpha_vec[:, None, None, None, None] * Zbar - Xd
        Bii = cpoly.find_prod_inverse(
            Bfull, all_rho(rhoF, ax).astype(YF.dtype), alpha=alpha_vec)
        return cpoly.z_from_contributions(zsum, Bii)

    def spatial_step(Z, Zbar, Xd, dtype):
        """FISTA prox + Zbar/X refresh (master :789-814):
        Zbar <- Zspat Phi from the FISTA solve on Z; X += alpha(Z-Zbar).
        All replicated ops."""
        from sagecal_tpu.consensus import spatial as sp
        Phi = jax.lax.complex(spat["Phi_ri"][..., 0],
                              spat["Phi_ri"][..., 1])
        Phikk = jax.lax.complex(spat["Phikk_ri"][..., 0],
                                spat["Phikk_ri"][..., 1])
        cdt = jnp.complex64 if dtype == jnp.float32 else jnp.complex128
        Zb = sp.z_r8_to_blocks(Z).astype(cdt)       # [MK, 2PN, 2]
        Zspat = sp.fista_spatialreg(Zb, Phikk.astype(cdt),
                                    Phi.astype(cdt), spat["mu"],
                                    spat["iters"])
        Zbar_new = sp.blocks_to_z_r8(
            sp.spatial_predict(Zspat, Phi.astype(cdt)),
            M, Ppoly, K, N).astype(Z.dtype)
        Xd_new = Xd + cfg.federated_alpha * (Z - Zbar_new)
        return Zbar_new, Xd_new

    def iter0_post(JF, res0, res1, fratioF, ax=axis):
        """Everything after iteration 0's solves: dual seed + manifold
        average + first Z/dual update. Shared by the mesh path (ax =
        mesh axis, JF local) and the blocked path (ax=None, JF full)."""
        Fl = JF.shape[0]
        dtype = JF.dtype
        Brow = _brow(Fl, ax)
        fm = _fmask(Fl, dtype, ax)               # [Fl, 1] padded-slot mask
        fm5 = fm[:, :, None, None, None]         # [Fl, 1, 1, 1, 1]
        # per-(subband, cluster) rho scaled by unflagged fraction; cfg.rho
        # may be a scalar or an [M] per-cluster array (readsky.c:780 -G)
        rho_m = jnp.broadcast_to(jnp.asarray(cfg.rho, dtype), (M,))
        rhoF = rho_m[None, :] * fratioF[:, None] * fm * jnp.ones(
            (Fl, M), dtype)
        alpha_vec = _alpha_vec(rho_m, dtype)

        # padded slots contribute exact zeros to every collective (the
        # where also stops a non-finite padded J from poisoning 0*J)
        YF = jnp.where(fm5 > 0,
                       rhoF[..., None, None, None]
                       * JF.reshape(Fl, M, K, N, 8), 0.0)
        YF = manifold_average_mesh(YF, ax, nf_total, M, K, N,
                                   cfg.manifold_iters)
        YF = jnp.where(fm5 > 0, YF, 0.0)
        Y0F = YF     # manifold-projected rho*J: the MDL input (:815-822)

        # spatial-reg state (replicated); zeros when disabled
        Zbar = jnp.zeros((M, Ppoly, K, N, 8), dtype)
        Xd = jnp.zeros_like(Zbar)

        # iteration 0 Z update: Y currently = rho*J (manifold-aligned)
        with jax.named_scope(CONSENSUS_SCOPE):
            Z = z_update(Brow, YF, rhoF, alpha_vec, ax=ax)
            if spat is not None:
                # admm==0 matches !(admm % cadence) (master :789)
                Zbar, Xd = spatial_step(Z, Zbar, Xd, dtype)
            BZ = jnp.einsum("fp,mpknr->fmknr", Brow, Z)
            YF = YF - rhoF[..., None, None, None] * BZ  # dual (slave :750)

        carry = (JF, YF, Z, rhoF, YF, JF.reshape(Fl, M, K, N, 8),
                 Zbar, Xd, rhoF)
        return carry, res0, res1, Y0F

    def _per_subband(fn):
        """vmap over the local subband axis — except at width 1, where
        the axis-free call avoids the measured 25-40% unit-vmap layout
        penalty on the latency-bound solver ops (see
        sage.sagefit_host_tiles' T=1 fast path; same physics). Width is
        a trace-time constant, so this is free."""
        def call(*args):
            lead = args[0].shape[0]
            if lead != 1:
                return jax.vmap(fn)(*args)
            out = fn(*jax.tree.map(lambda x: x[0], args))
            return jax.tree.map(lambda x: x[None], out)
        return call

    def iter0_local(x8F, cohF, wtF, fratioF, J0F):
        """ADMM iteration 0 on the LOCAL shard: plain solve + post."""
        JF, res0, res1, tk = _per_subband(local_solve_plain)(
            x8F, cohF, wtF, J0F)
        return iter0_post(JF, res0, res1, fratioF) + (tk,)

    @jax.named_scope(CONSENSUS_SCOPE)
    def body_post(Jr, r0, r1, carry, it, ax=axis):
        """Everything after iteration k>0's solves (slave :686-770)."""
        JF, YF, Z, rhoF, Yhat_prev, Jprev, Zbar, Xd, rho_upper = carry
        Fl = Jr.shape[0]
        dtype = Jr.dtype
        Brow = _brow(Fl, ax)
        fm = _fmask(Fl, dtype, ax)
        fm5 = fm[:, :, None, None, None]
        rho_m = jnp.broadcast_to(jnp.asarray(cfg.rho, dtype), (M,))
        alpha_vec = _alpha_vec(rho_m, dtype)

        J5 = Jr.reshape(Fl, M, K, N, 8)
        YF = jnp.where(fm5 > 0,
                       YF + rhoF[..., None, None, None] * J5, 0.0)
        Zold = Z
        if spat is None:
            Z = z_update(Brow, YF, rhoF, alpha_vec, ax=ax)
        else:
            Z = z_update(Brow, YF, rhoF, alpha_vec, Zbar, Xd, ax=ax)
            Zbar, Xd = jax.lax.cond(
                it % spat["cadence"] == 0,
                lambda z, zb, xd: spatial_step(z, zb, xd, dtype),
                lambda z, zb, xd: (zb, xd),
                Z, Zbar, Xd)
        BZn = jnp.einsum("fp,mpknr->fmknr", Brow, Z)
        # Yhat for BB rho uses BZ_old (slave :724-732, TAG_CONSENSUS_OLD)
        Yhat = jnp.where(fm5 > 0,
                         YF - rhoF[..., None, None, None] * jnp.einsum(
                             "fp,mpknr->fmknr", Brow, Zold), 0.0)
        YF = jnp.where(fm5 > 0,
                       YF - rhoF[..., None, None, None] * BZn, 0.0)

        if cfg.adaptive_rho:
            rhoF = jax.vmap(
                lambda r, ru, dy, dj: cpoly.update_rho_bb(
                    r, ru, dy, dj, axes=(1, 2, 3))
            )(rhoF, rho_upper, Yhat - Yhat_prev, J5 - Jprev)
            rhoF = jnp.where(fm > 0, rhoF, 0.0)  # BB on padded: 0/0 guard

        dual = jnp.linalg.norm(Z - Zold) / np.sqrt(Z.size)
        return (Jr, YF, Z, rhoF, Yhat, J5, Zbar, Xd, rho_upper), \
            (r0, r1, dual)

    def body_local(x8F, cohF, wtF, carry, it):
        """One ADMM iteration k>0 on the LOCAL shard (slave :686-770)."""
        Fl = x8F.shape[0]
        with jax.named_scope(CONSENSUS_SCOPE):
            BZ = jnp.einsum("fp,mpknr->fmknr", _brow(Fl), carry[2])
        Jr, r0, r1, tk = _per_subband(local_solve_admm)(
            x8F, cohF, wtF, carry[0], carry[1], BZ, carry[3])
        carry, per_iter = body_post(Jr, r0, r1, carry, it)
        return carry, per_iter + (tk,)

    if _return_parts:
        # building blocks for make_admm_runner_blocked (same math,
        # different execution granularity)
        return dict(coh_subbands=coh_subbands,
                    local_solve_plain=local_solve_plain,
                    local_solve_admm=local_solve_admm,
                    iter0_post=iter0_post, body_post=body_post,
                    _brow=_brow, _per_subband=_per_subband,
                    Bfull=Bfull)

    def admm_program(x8F, uF, vF, wF, freqF, wtF, fratioF, J0F,
                     *beam_rest):
        # shapes here are the LOCAL shard: [Fl, ...]
        cohF = coh_subbands(uF, vF, wF, freqF, *beam_rest)
        carry, res0, res1, Y0F, tk0 = iter0_local(
            x8F, cohF, wtF, fratioF, J0F)

        def body(carry, it):
            return body_local(x8F, cohF, wtF, carry, it)

        carry, (r0s, r1s, duals, tks) = jax.lax.scan(
            body, carry, jnp.arange(1, max(cfg.n_admm, 1),
                                    dtype=jnp.int32))
        JF, YF, Z, rhoF = carry[0], carry[1], carry[2], carry[3]
        trips = jnp.concatenate([tk0[None], tks])    # [n_admm, Fl, 2]
        return JF, Z, rhoF, res0, res1, r1s, duals, Y0F, trips

    from jax import shard_map
    spec_f = P(axis)
    spec_r = P()
    nin = 8 + (1 if dobeam else 0)     # beam pytree rides a prefix spec
    if not host_loop:
        prog = shard_map(
            admm_program, mesh=mesh,
            in_specs=(spec_f,) * nin,
            out_specs=(spec_f, spec_r, spec_f, spec_f, spec_f,
                       P(None, axis), spec_r, spec_f, P(None, axis)),
            check_vma=False)
        return jax.jit(prog)

    # --- host-driven ADMM loop: one bounded device execution per ADMM
    # iteration (a fully traced n_admm-iteration program over folded
    # subbands is one long execution; this is also the natural structure
    # for streaming telemetry per iteration, like the master's per-iter
    # prints).
    carry_specs = (spec_f, spec_f, spec_r, spec_f, spec_f, spec_f,
                   spec_r, spec_r, spec_f)

    def iter0_flat(x8F, uF, vF, wF, freqF, wtF, fratioF, J0F,
                   *beam_rest):
        carry, res0, res1, Y0F, tk = iter0_local(
            x8F, coh_subbands(uF, vF, wF, freqF, *beam_rest), wtF,
            fratioF, J0F)
        return carry + (res0, res1, Y0F, tk)

    def body_flat(x8F, uF, vF, wF, freqF, wtF, JF, YF, Z, rhoF, Yhat,
                  Jprev, Zbar, Xd, rho_upper, it, *beam_rest):
        carry = (JF, YF, Z, rhoF, Yhat, Jprev, Zbar, Xd, rho_upper)
        carry, (r0, r1, dual, tk) = body_local(
            x8F, coh_subbands(uF, vF, wF, freqF, *beam_rest), wtF,
            carry, it)
        return carry + (r0, r1, dual, tk)

    beam_specs = (spec_f,) if dobeam else ()
    prog0 = jax.jit(shard_map(
        iter0_flat, mesh=mesh, in_specs=(spec_f,) * 8 + beam_specs,
        out_specs=carry_specs + (spec_f, spec_f, spec_f, spec_f),
        check_vma=False))
    # the ADMM carry (J/Y/Z/rho accumulators + BB state) is DONATED to
    # each body execution: every iteration rebinds the carry from the
    # program's outputs, so XLA reuses the buffers in place instead of
    # allocating a fresh accumulator set per ADMM iteration
    progb = jax.jit(shard_map(
        body_flat, mesh=mesh,
        in_specs=(spec_f,) * 6 + carry_specs + (spec_r,) + beam_specs,
        out_specs=carry_specs + (spec_f, spec_f, spec_r, spec_f),
        check_vma=False),
        donate_argnums=tuple(range(6, 15)) if donate else ())

    # consensus-only program: everything one ADMM body iteration does
    # AFTER the J-update solves (z-sum psum, Bii solve, duals, BB rho),
    # as its own mesh execution — the measured collective-overhead
    # probe. Never donated: the caller times it repeatedly on one carry.
    def cons_flat(Jr, r0, r1, JF, YF, Z, rhoF, Yhat, Jprev, Zbar, Xd,
                  rho_upper, it):
        carry = (JF, YF, Z, rhoF, Yhat, Jprev, Zbar, Xd, rho_upper)
        carry, (r0o, r1o, dual) = body_post(Jr, r0, r1, carry, it)
        return carry + (r0o, r1o, dual)

    prog_cons = jax.jit(shard_map(
        cons_flat, mesh=mesh,
        in_specs=(spec_f, spec_f, spec_f) + carry_specs + (spec_r,),
        out_specs=carry_specs + (spec_f, spec_f, spec_r),
        check_vma=False))

    n_runs = [0]    # runner invocation ordinal = interval, for traces

    import time as _time

    def _t(label, t0, out):
        if timer is not None:
            jax.block_until_ready(out)
            timer.append((label, _time.perf_counter() - t0))
        return out

    def run(x8F, uF, vF, wF, freqF, wtF, fratioF, J0F, *beam_rest):
        interval = n_runs[0]
        n_runs[0] += 1
        t0 = _time.perf_counter()
        out = prog0(x8F, uF, vF, wF, freqF, wtF, fratioF, J0F,
                    *beam_rest)
        _t("iter0", t0, out[0])
        carry, (res0, res1, Y0F, tk0) = out[:9], out[9:]
        # per-iteration convergence records are DEFERRED: the means are
        # dispatched on device here (gated, cheap) and fetched in ONE
        # batched transfer after the loop, so tracing never inserts a
        # per-iteration host sync into the ADMM chain
        pend = []
        if dtrace.active() or obs.active():
            pend.append((0, jnp.mean(res1), None, jnp.mean(carry[3])))
        r1s, duals, tks = [], [], [tk0]
        for it in range(1, max(cfg.n_admm, 1)):
            t0 = _time.perf_counter()
            out = progb(x8F, uF, vF, wF, freqF, wtF, *carry,
                        jnp.asarray(it, jnp.int32), *beam_rest)
            _t(f"body[{it}]", t0, out[0])
            carry, (_, r1, dual, tk) = out[:9], out[9:]
            r1s.append(r1)
            duals.append(dual)
            tks.append(tk)
            if dtrace.active() or obs.active():
                pend.append((it, jnp.mean(r1), dual,
                             jnp.mean(carry[3])))
        _emit_deferred(pend, interval)
        JF, Z, rhoF = carry[0], carry[2], carry[3]
        F = x8F.shape[0]
        r1s_a = (jnp.stack(r1s) if r1s
                 else jnp.zeros((0, F), x8F.dtype))
        duals_a = (jnp.stack(duals) if duals
                   else jnp.zeros((0,), x8F.dtype))
        return (JF, Z, rhoF, res0, res1, r1s_a, duals_a, Y0F,
                jnp.stack(tks))

    run.consensus_program = prog_cons
    return run


def pad_time(arrays, nt: int, ndev_t: int, axis: int = 1):
    """THE padding contract for the time axis of the 2-D mesh, mirror
    of :func:`pad_subbands`: pad ``axis`` (the solution-interval axis)
    of every host array up to ``tpad = ceil(nt/ndev_t)*ndev_t`` by
    replicating the LAST interval — padded intervals solve numerically
    tame copies whose outputs the caller drops ([:nt] on the time
    axis). Unlike padded subbands they need no collective mask: the
    time axis carries no collective, every interval's consensus is its
    own freq-psum."""
    ndev_t = max(int(ndev_t), 1)
    tpad = -(-max(nt, ndev_t) // ndev_t) * ndev_t
    if tpad == nt:
        return list(arrays), tpad
    out = []
    for a in arrays:
        a = np.asarray(a)
        last = np.take(a, [-1], axis=axis)
        reps = np.concatenate([last] * (tpad - nt), axis=axis)
        out.append(np.concatenate([a, reps], axis=axis))
    return out, tpad


def divergence_reset(JF, J0F, res0, res_fin, ratio: float = 5.0):
    """The per-subband warm-start divergence rule (slave :680-683, the
    cli_mpi host-loop rule) as a traced op: a subband whose final ADMM
    residual is non-finite, exactly zero (all-flagged) or blew past
    ``ratio`` x its initial residual restarts the next interval from
    ``J0F`` instead of carrying its diverged Jones forward."""
    bad = (~jnp.isfinite(res_fin)) | (res_fin == 0.0) \
        | (res_fin > ratio * res0)
    return jnp.where(bad[:, None, None, None, None], J0F, JF)


def make_admm_runner_2d(dsky, sta1, sta2, cidx, cmask, n_stations: int,
                        fdelta: float, B_poly: np.ndarray,
                        cfg: ADMMConfig, mesh: Mesh, nf_total: int,
                        nt_total: int, with_shapelets: bool = False,
                        nbase: int | None = None,
                        host_loop: bool = False,
                        timer: list | None = None):
    """Consensus ADMM over a 2-D ``('freq', 'time')`` mesh: subbands
    shard on the freq axis exactly as :func:`make_admm_runner`, and
    the solution intervals shard on the time axis with the PR 2
    ``[tilesz, nbase]`` ``row_period`` tile as the shard unit — an
    F-subband x T-interval pod slice solves the whole observation as
    ONE SPMD program.

    Structure (MIGRATION.md "2-D mesh"):

    - per-interval SAGE/LM J-updates are independent along time: every
      (subband, interval) cell solves shard-local;
    - the polynomial-in-frequency consensus update (z-sum psum + Bii
      solve + duals) is a **freq-axis collective**: each interval owns
      its own Z, so time shards run the identical iteration schedule
      with no cross-time communication at all;
    - the warm-start J chain becomes a **time-axis scan seam**: each
      time shard scans its local contiguous block of intervals in
      order (interval t+1 warm-starts from t's Jones, with the
      divergence-reset rule in-program), and the FIRST interval of
      each block cold-starts from ``J0F`` — the one deliberate
      numerical deviation from the sequential chain, gated by the
      residual-parity envelope of tests/test_mesh2d.py.

    Dtype policy: identical contract to the 1-D mesh runner — ``x8``
    and ``wt`` may arrive in the reduced storage dtype and
    ``cfg.sage.dtype_policy`` rides into every sagefit; the consensus
    state never quantizes. There is no f32 fallback on this path.

    ``mesh`` must carry exactly the axes ``("freq", "time")``. Interval
    mapping: time-device d owns the contiguous block
    ``[d*Tl, (d+1)*Tl)`` where ``Tl = Tpad // ndev_time``.

    ``run(x8FT, uFT, vFT, wFT, freqF, wtFT, fratioFT, J0F)`` takes
    HOST arrays (it owns its staging, unlike the 1-D runner):
    ``[Fpad, Tpad, ...]`` per-cell data, ``freqF [Fpad]``, ``J0F
    [Fpad, M, K, N, 8]``; subband padding via :func:`pad_subbands`,
    time padding via :func:`pad_time`. Returns
    ``(JT, ZT, rhoT, res0T, res1T, r1sT, dualsT, Y0T)`` with a leading
    GLOBAL time axis: ``JT [Tpad, Fpad, M, K, N, 8]``, ``ZT [Tpad, M,
    P, K, N, 8]``, ``res* [Tpad, Fpad]``, ``r1sT [Tpad, n_admm-1,
    Fpad]``, ``dualsT [Tpad, n_admm-1]``.

    ``host_loop=True`` executes one bounded mesh program per time
    WAVEFRONT (wavefront w = interval ``d*Tl + w`` on every time
    device d, the warm-start carry rebound on the host between
    executions) — identical math to the fully traced scan, per-
    execution ``timer`` telemetry like the 1-D host loop. The runner
    exposes ``run.consensus_program`` (the per-iteration consensus
    half on the 2-D mesh) for the collective-overhead probe either
    way.

    Not offered here (use the 1-D runner): ``-X`` spatial
    regularization and ``-B`` beam tables (per-interval beam staging
    across the time mesh is future work; cli_mpi refuses the combo).
    """
    if cfg.spatialreg is not None:
        raise ValueError("2-D mesh runner does not support -X spatial "
                         "regularization; use make_admm_runner")
    if tuple(mesh.axis_names) != ("freq", "time"):
        raise ValueError(f"make_admm_runner_2d needs a ('freq', 'time') "
                         f"mesh, got axes {mesh.axis_names}")
    ndev_f, ndev_t = mesh.devices.shape
    parts = make_admm_runner(
        dsky, sta1, sta2, cidx, cmask, n_stations, fdelta, B_poly, cfg,
        mesh, nf_total, with_shapelets=with_shapelets, nbase=nbase,
        _return_parts=True)
    lsp = parts["local_solve_plain"]
    lsa = parts["local_solve_admm"]
    iter0_post = parts["iter0_post"]
    body_post = parts["body_post"]
    _brow = parts["_brow"]
    _per_subband = parts["_per_subband"]
    coh_subbands = parts["coh_subbands"]

    def one_interval(Jc, x8t, ut, vt, wt_, wtt, frt, freqF, J0F):
        """One solution interval's FULL ADMM chain on the local freq
        shard ([Fl, ...] arrays): iteration 0 + n_admm-1 body
        iterations, every consensus step a freq-axis collective.
        Returns (Jnext, outputs) — Jnext is the warm-start carry for
        the next interval in this time shard's block."""
        cohF = coh_subbands(ut, vt, wt_, freqF)
        JF, res0, res1, _ = _per_subband(lsp)(x8t, cohF, wtt, Jc)
        carry, res0, res1, Y0F = iter0_post(JF, res0, res1, frt)
        Fl = x8t.shape[0]

        def body(carry, it):
            Brow = _brow(Fl)
            BZ = jnp.einsum("fp,mpknr->fmknr", Brow, carry[2])
            Jr, r0, r1, _ = _per_subband(lsa)(
                x8t, cohF, wtt, carry[0], carry[1], BZ, carry[3])
            return body_post(Jr, r0, r1, carry, it)

        carry, (r0s, r1s, duals) = jax.lax.scan(
            body, carry, jnp.arange(1, max(cfg.n_admm, 1),
                                    dtype=jnp.int32))
        JF, Z, rhoF = carry[0], carry[2], carry[3]
        res_fin = r1s[-1] if cfg.n_admm > 1 else res1
        Jnext = divergence_reset(JF, J0F, res0, res_fin)
        return Jnext, (JF, Z, rhoF, res0, res1, r1s, duals, Y0F)

    def scan_program(x8, u, v, w, freqF, wtf, fratio, J0F):
        # local shard: [Fl, Tl, ...]; scan the time block in order so
        # the warm-start chain is sequential WITHIN the shard
        xs = tuple(jnp.moveaxis(a, 1, 0)
                   for a in (x8, u, v, w, wtf, fratio))

        def step(Jc, per_t):
            x8t, ut, vt, wt_, wtt, frt = per_t
            return one_interval(Jc, x8t, ut, vt, wt_, wtt, frt, freqF,
                                J0F)

        _, outs = jax.lax.scan(step, J0F, xs)
        return outs

    def wave_program(x8, u, v, w, freqF, wtf, fratio, J0F, Jc):
        # local shard: [Fl, 1, ...] (one interval per time device per
        # wavefront); squeeze the unit time axis, run the interval,
        # re-emit with it so the out specs shard back over "time"
        sq = [a[:, 0] for a in (x8, u, v, w, wtf)]
        Jnext, outs = one_interval(Jc[:, 0], sq[0], sq[1], sq[2], sq[3],
                                   sq[4], fratio[:, 0], freqF, J0F)
        outs = tuple(o[None] for o in outs)     # leading local-time 1
        return (Jnext[:, None],) + outs

    from jax import shard_map
    Pft = P("freq", "time")
    Pf = P("freq")
    # outputs stack a leading local-time axis: [Tl, ...]
    out_specs = (P("time", "freq"),            # JF
                 P("time"),                    # Z
                 P("time", "freq"),            # rhoF
                 P("time", "freq"),            # res0
                 P("time", "freq"),            # res1
                 P("time", None, "freq"),      # r1s
                 P("time"),                    # duals
                 P("time", "freq"))            # Y0F
    in_specs = (Pft, Pft, Pft, Pft, Pf, Pft, Pft, Pf)

    prog_scan = jax.jit(shard_map(
        scan_program, mesh=mesh, in_specs=in_specs,
        out_specs=out_specs, check_vma=False))
    prog_wave = jax.jit(shard_map(
        wave_program, mesh=mesh, in_specs=in_specs + (Pft,),
        out_specs=(Pft,) + out_specs, check_vma=False))

    # the consensus half of one body iteration as its OWN 2-D mesh
    # program (the measured collective-overhead probe, multichip
    # precedent): every time shard runs its interval's freq-psum
    # consensus concurrently — exactly the per-iteration communication
    # pattern of the 2-D program. Carries are [Fpad, ...] arrays
    # replicated along "time".
    carry_specs = (Pf, Pf, P(), Pf, Pf, Pf, P(), P(), Pf)

    def cons_flat(Jr, r0, r1, JF, YF, Z, rhoF, Yhat, Jprev, Zbar, Xd,
                  rho_upper, it):
        carry = (JF, YF, Z, rhoF, Yhat, Jprev, Zbar, Xd, rho_upper)
        carry, (r0o, r1o, dual) = body_post(Jr, r0, r1, carry, it)
        return carry + (r0o, r1o, dual)

    prog_cons = jax.jit(shard_map(
        cons_flat, mesh=mesh,
        in_specs=(Pf, Pf, Pf) + carry_specs + (P(),),
        out_specs=carry_specs + (Pf, Pf, P()),
        check_vma=False))

    sh_ft = NamedSharding(mesh, Pft)
    sh_f = NamedSharding(mesh, Pf)

    import time as _time

    def _t(label, t0, out):
        if timer is not None:
            jax.block_until_ready(out)
            timer.append((label, _time.perf_counter() - t0))
        return out

    def run(x8FT, uFT, vFT, wFT, freqF, wtFT, fratioFT, J0F):
        x8FT, uFT, vFT, wFT, wtFT, fratioFT = [
            np.asarray(a) for a in (x8FT, uFT, vFT, wFT, wtFT,
                                    fratioFT)]
        Fpad, Tpad = x8FT.shape[:2]
        if Fpad % ndev_f or Tpad % ndev_t:
            raise ValueError(
                f"staged axes [F={Fpad}, T={Tpad}] must divide the "
                f"mesh {ndev_f}x{ndev_t} (pad_subbands / pad_time)")
        if Tpad < -(-nt_total // ndev_t) * ndev_t:
            raise ValueError(
                f"staged time axis {Tpad} cannot hold the declared "
                f"{nt_total} intervals over {ndev_t} time devices "
                f"(pad_time)")
        freq_d = jax.device_put(np.asarray(freqF), sh_f)
        J0_d = jax.device_put(np.asarray(J0F), sh_f)
        if not host_loop:
            t0 = _time.perf_counter()
            args_d = [jax.device_put(a, sh_ft)
                      for a in (x8FT, uFT, vFT, wFT)]
            wt_d = jax.device_put(wtFT, sh_ft)
            fr_d = jax.device_put(fratioFT, sh_ft)
            out = prog_scan(args_d[0], args_d[1], args_d[2], args_d[3],
                            freq_d, wt_d, fr_d, J0_d)
            _t("scan", t0, out[0])
            return out

        # wavefront host loop: one bounded execution per local
        # interval index w; time-device d solves interval d*Tl + w
        Tl = Tpad // ndev_t
        outs_host = [None] * 8

        def _place(buf, w, a, t_lead):
            # a: wavefront output with time axis leading (t_lead) or
            # second; scatter device d's cell to interval d*Tl + w
            a = np.asarray(a)
            at = a if t_lead else np.moveaxis(a, 1, 0)
            if buf is None:
                buf = np.zeros((Tpad,) + at.shape[1:], at.dtype)
            buf[w::Tl] = at
            return buf

        Jc = np.broadcast_to(
            np.asarray(J0F)[:, None],
            (Fpad, ndev_t) + np.asarray(J0F).shape[1:])
        Jc_d = jax.device_put(np.ascontiguousarray(Jc), sh_ft)
        for w in range(Tl):
            t0 = _time.perf_counter()
            sl = [jax.device_put(np.ascontiguousarray(a[:, w::Tl]),
                                 sh_ft)
                  for a in (x8FT, uFT, vFT, wFT, wtFT, fratioFT)]
            out = prog_wave(sl[0], sl[1], sl[2], sl[3], freq_d, sl[4],
                            sl[5], J0_d, Jc_d)
            _t(f"wave[{w}]", t0, out[0])
            Jc_d = out[0]
            # wavefront outputs: JF/rho/res/r1s/Y0 lead with the local
            # time axis (size 1 per device -> global [ndev_t, ...])
            for i, o in enumerate(out[1:]):
                outs_host[i] = _place(outs_host[i], w, o, t_lead=True)
        return tuple(jnp.asarray(b) for b in outs_host)

    run.consensus_program = prog_cons
    run.mesh_shape = (ndev_f, ndev_t)
    return run


def make_admm_runner_stale(dsky, sta1, sta2, cidx, cmask,
                           n_stations: int, fdelta: float,
                           B_poly: np.ndarray, cfg: ADMMConfig,
                           nf_total: int, staleness: int = 0,
                           with_shapelets: bool = False,
                           nbase: int | None = None, device=None,
                           timer: list | None = None):
    """Bounded-staleness consensus ADMM (opt-in): a straggling subband
    may SKIP its J-update for a round while every other subband keeps
    iterating against its last-sent dual contribution — consumed up to
    ``staleness`` iterations stale — instead of the whole pod pacing
    on the slowest subband (arXiv:1605.09219's stale-tolerant rho
    schedules; arXiv:1410.2101's ADI analysis of reordered updates).

    Composition with the PR 9 fault harness makes the straggler a
    MEASURED experiment rather than a hang: per round, each subband
    asks ``faults.fires("admm_subband_slow", key=f)`` whether it is
    slow — but only when skipping would keep its staleness within the
    bound (``staleness=0`` never even asks: the synchronous chain).
    A subband whose bound is exhausted is forced to update — the
    simulation analogue of the synchronous runner blocking on it, so
    the chain NEVER deadlocks on a slow subband, and a ``kind:
    "fatal"`` rule marks the subband DEAD: it is masked out of every
    later consensus like a padded mesh slot (zero rho, zero sent
    dual) and its last residual is carried forward.

    Semantics per round (vs the synchronous body_post):

    - updated subbands: ``Ysent_f = Y_f + rho_f J_f(new)`` then the
      dual step against the fresh Z, exactly the synchronous math;
    - sleeping subbands: ``Ysent_f`` (their last-sent contribution)
      enters the z-sum unchanged — the "stale dual" — and their
      ``Y_f``/``J_f``/residual are untouched;
    - the Z solve itself stays exact over the mixed-freshness table.

    With ``staleness=0`` — or any bound but no fault plan — every
    subband updates every round and the chain is BIT-IDENTICAL to
    ``make_admm_runner_blocked(block_f=1)`` (gated,
    tests/test_mesh2d.py). ``adaptive_rho`` is refused: BB steps over
    mixed-staleness increments have no convergence story.

    Single-device host-driven execution (block_f=1 per-subband
    executions — the granularity that lets a real deployment actually
    skip a straggler's solve). Same run signature/outputs as
    :func:`make_admm_runner_blocked`; additionally ``run.schedule``
    holds, per interval, the list of per-round update masks and
    ``run.dead`` the dead-subband set — the harness's telemetry.
    """
    import time as _time

    from sagecal_tpu import faults

    if cfg.spatialreg is not None:
        raise ValueError("bounded-staleness runner does not support -X "
                         "spatial regularization")
    if cfg.adaptive_rho:
        raise ValueError("bounded-staleness consensus requires "
                         "adaptive_rho=False (BB rho over stale "
                         "increments is undefined)")
    S = int(staleness)
    if S < 0:
        raise ValueError(f"staleness {S}: must be >= 0")

    devs = [device] if device is not None else jax.devices()[:1]
    mesh = Mesh(np.array(devs), ("freq",))
    parts = make_admm_runner(
        dsky, sta1, sta2, cidx, cmask, n_stations, fdelta, B_poly, cfg,
        mesh, nf_total, with_shapelets=with_shapelets, nbase=nbase,
        _return_parts=True)
    local_solve_plain = parts["local_solve_plain"]
    local_solve_admm = parts["local_solve_admm"]
    iter0_post = parts["iter0_post"]
    body_post = parts["body_post"]
    _brow = parts["_brow"]
    _per_subband = parts["_per_subband"]

    M = int(np.asarray(cmask).shape[0])
    K = int(np.asarray(cmask).shape[1])
    N = n_stations

    coh_prog = jax.jit(parts["coh_subbands"])
    solve0 = jax.jit(_per_subband(local_solve_plain))
    solveb = jax.jit(_per_subband(local_solve_admm))
    cons0 = jax.jit(lambda JF, res0, res1, fratioF: iter0_post(
        JF, res0, res1, fratioF, ax=None), donate_argnums=(0,))
    bz_prog = jax.jit(
        lambda Z, Brow: jnp.einsum("fp,mpknr->fmknr", Brow, Z))

    def stale_post(Jr, r1_new, upd, alive, JF, YF, Z, rhoF, Ysent,
                   r1_prev, it):
        """The consensus half of one stale round. ``upd``/``alive``:
        [F] {0,1} masks. With upd == alive == 1 everywhere this
        computes bit-for-bit the synchronous ``body_post`` values
        (the where() wrappers select the identical branch
        expressions), which is the S=0 parity gate's contract."""
        F = Jr.shape[0]
        dtype = Jr.dtype
        Brow = _brow(F, None)
        J5 = Jr.reshape(F, M, K, N, 8)
        upd5 = upd[:, None, None, None, None]
        alive5 = alive[:, None, None, None, None]
        rho_eff = jnp.where(alive[:, None] > 0, rhoF, 0.0)
        Ysent = jnp.where(upd5 > 0,
                          YF + rho_eff[..., None, None, None] * J5,
                          Ysent)
        Ysent = jnp.where(alive5 > 0, Ysent, 0.0)
        Zold = Z
        zsum = jnp.einsum("fp,fmknr->mpknr", Brow, Ysent)
        Bii = cpoly.find_prod_inverse(
            parts["Bfull"], rho_eff.T.astype(Ysent.dtype))
        Z = cpoly.z_from_contributions(zsum, Bii)
        BZn = jnp.einsum("fp,mpknr->fmknr", Brow, Z)
        YF = jnp.where(upd5 > 0,
                       Ysent - rho_eff[..., None, None, None] * BZn, YF)
        JF = jnp.where(upd5 > 0, J5.reshape(JF.shape), JF)
        r1 = jnp.where(upd > 0, r1_new, r1_prev)
        dual = jnp.linalg.norm(Z - Zold) / np.sqrt(Z.size)
        return JF, YF, Z, rho_eff, Ysent, r1, dual

    stale_cons = jax.jit(stale_post)

    def _t(label, t0, out):
        if timer is not None:
            jax.block_until_ready(out)
            timer.append((label, _time.perf_counter() - t0))
        return out

    n_runs = [0]
    schedule: list = []
    dead_log: list = []

    def run(x8F, uF, vF, wF, freqF, wtF, fratioF, J0F, *beam_rest):
        if beam_rest:
            raise ValueError("bounded-staleness runner does not "
                             "support -B beam tables")
        interval = n_runs[0]
        n_runs[0] += 1
        F = x8F.shape[0]
        Brow_full = _brow(F, None)

        def take(a, f):
            return jax.tree.map(lambda x: x[f:f + 1], a)

        # each subband's coherencies ONCE an interval, not a round
        cohs = [coh_prog(take(uF, f), take(vF, f), take(wF, f),
                         take(freqF, f)) for f in range(F)]

        def sub_solve0(f):
            t0 = _time.perf_counter()
            Jb, r0b, r1b, tkb = solve0(
                take(x8F, f), cohs[f], take(wtF, f), take(J0F, f))
            _t(f"solve0[{f}]", t0, Jb)
            return Jb, r0b, r1b, tkb

        def sub_solveb(f, JF, YF, BZ, rhoF):
            t0 = _time.perf_counter()
            Jb, r0b, r1b, tkb = solveb(
                take(x8F, f), cohs[f], take(wtF, f), take(JF, f),
                take(YF, f), take(BZ, f), take(rhoF, f))
            _t(f"solve[{f}]", t0, Jb)
            return Jb, r0b, r1b, tkb

        # --- iteration 0: synchronous for every subband (the dual
        # seed + manifold averaging need the full subband set)
        Js, r0s, r1s_l, tk_l = zip(*[sub_solve0(f) for f in range(F)])
        tks = [jnp.concatenate(tk_l)]
        JF = jnp.concatenate(Js)
        res0 = jnp.concatenate(r0s)
        res1 = jnp.concatenate(r1s_l)
        t0 = _time.perf_counter()
        carry, res0, res1, Y0F = cons0(JF, res0, res1, fratioF)
        _t("cons0", t0, carry[2])
        JF, YF, Z, rhoF = carry[0], carry[1], carry[2], carry[3]
        # last-sent table: iteration 0's sent contribution is the
        # manifold-projected rho*J — exactly Y0F
        Ysent = Y0F
        r1_cur = res1

        alive_np = np.ones(F, np.float64)
        alive_np[nf_total:] = 0.0          # padded mesh slots
        upd_base = alive_np.copy()
        last_update = np.zeros(F, np.int64)
        dead: set = set()
        sched_rounds: list = []
        r1h, dualh, pend = [], [], []
        for it in range(1, max(cfg.n_admm, 1)):
            upd_np = upd_base.copy()
            for f in range(min(nf_total, F)):
                if f in dead:
                    upd_np[f] = 0.0
                    continue
                # may f be lazy this round? only asked when the bound
                # permits the resulting staleness
                if S > 0 and (it - last_update[f]) <= S:
                    kind = faults.draw("admm_subband_slow", key=f)
                    if kind == "fatal":
                        dead.add(f)
                        alive_np[f] = 0.0
                        upd_base[f] = 0.0
                        upd_np[f] = 0.0
                        dead_log.append((interval, it, f))
                        continue
                    if kind is not None:
                        upd_np[f] = 0.0
                        continue
                last_update[f] = it
            sched_rounds.append(upd_np.copy())

            BZ = bz_prog(Z, Brow_full)
            Jr = JF
            r1_new = r1_cur
            tk_l = [jnp.zeros_like(tk_l[0])] * F    # skipped: no trips
            for f in range(F):
                if upd_np[f] == 0.0:
                    continue
                Jb, _r0b, r1b, tk_l[f] = sub_solveb(f, JF, YF, BZ, rhoF)
                # in-place-style scatter: one dispatch per subband,
                # no full-[F] copies (the values land verbatim, so
                # the S=0 bit-identity gate is untouched)
                Jr = Jr.at[f:f + 1].set(Jb)
                r1_new = r1_new.at[f:f + 1].set(r1b)
            upd_d = jnp.asarray(upd_np, JF.dtype)
            alive_d = jnp.asarray(alive_np, JF.dtype)
            t0 = _time.perf_counter()
            JF, YF, Z, rhoF, Ysent, r1_cur, dual = stale_cons(
                Jr, r1_new, upd_d, alive_d, JF, YF, Z, rhoF, Ysent,
                r1_cur, jnp.asarray(it, jnp.int32))
            _t(f"cons[{it}]", t0, Z)
            r1h.append(r1_cur)
            dualh.append(dual)
            tks.append(jnp.concatenate(tk_l))
            if dtrace.active() or obs.active():
                pend.append((it, jnp.mean(r1_cur), dual,
                             jnp.mean(rhoF)))
                skipped = [f for f in range(nf_total)
                           if upd_np[f] == 0.0]
                if skipped:
                    dtrace.emit("admm_stale", interval=interval,
                                iter=it, skipped=skipped,
                                dead=sorted(dead))
        _emit_deferred(pend, interval)
        schedule.append(sched_rounds)
        r1s_a = (jnp.stack(r1h) if r1h
                 else jnp.zeros((0, F), x8F.dtype))
        duals_a = (jnp.stack(dualh) if dualh
                   else jnp.zeros((0,), x8F.dtype))
        return (JF, Z, rhoF, res0, res1, r1s_a, duals_a, Y0F,
                jnp.stack(tks))

    run.schedule = schedule
    run.dead = dead_log
    return run


def make_admm_runner_blocked(dsky, sta1, sta2, cidx, cmask,
                             n_stations: int, fdelta: float,
                             B_poly: np.ndarray, cfg: ADMMConfig,
                             nf_total: int, block_f: int,
                             with_shapelets: bool = False,
                             dobeam: int = 0, nbase: int | None = None,
                             device=None, timer=None):
    """Single-device consensus ADMM with the J-update split into subband
    BLOCKS of ``block_f`` — one bounded device execution per block, tiny
    consensus executions in between. Identical math to
    :func:`make_admm_runner` (it reuses the same iter0_post/body_post
    consensus code with ax=None), built for shapes where one folded
    J-update over all subbands is too long (or too large) for a single
    execution: the north-star 64-station x 100-direction x 32-subband
    problem.

    Spatial regularization is not offered here (use the mesh runner).
    ``timer``: optional list that receives (label, seconds) tuples for
    per-execution telemetry.
    """
    import time as _time

    if cfg.spatialreg is not None:
        raise ValueError("blocked runner does not support -X spatial "
                         "regularization; use make_admm_runner")
    # borrow the full closure set from make_admm_runner on a 1-device
    # mesh; we only use its ax=None entry points, never its shard_map
    # programs
    devs = [device] if device is not None else jax.devices()[:1]
    mesh = Mesh(np.array(devs), ("freq",))
    parts = make_admm_runner(
        dsky, sta1, sta2, cidx, cmask, n_stations, fdelta, B_poly, cfg,
        mesh, nf_total, with_shapelets=with_shapelets,
        dobeam=dobeam, nbase=nbase,
        _return_parts=True)
    local_solve_plain = parts["local_solve_plain"]
    local_solve_admm = parts["local_solve_admm"]
    iter0_post = parts["iter0_post"]
    body_post = parts["body_post"]
    _brow = parts["_brow"]

    # the shared unit-width wrapper: block_f == 1 (the north-star's
    # best plan) takes the axis-free call, avoiding the unit-vmap
    # layout penalty
    _per_subband = parts["_per_subband"]
    coh_prog = jax.jit(parts["coh_subbands"])
    solve0 = jax.jit(_per_subband(local_solve_plain))
    solveb = jax.jit(_per_subband(local_solve_admm))
    # donate the block-solved Jones and the ADMM carry into the
    # consensus steps (same in-place reuse as make_admm_runner's
    # host-loop donation; callers rebind both from the outputs)
    cons0 = jax.jit(lambda JF, res0, res1, fratioF: iter0_post(
        JF, res0, res1, fratioF, ax=None), donate_argnums=(0,))
    consb = jax.jit(lambda Jr, r0, r1, carry, it: body_post(
        Jr, r0, r1, carry, it, ax=None), donate_argnums=(0, 3))
    bz_prog = jax.jit(
        lambda Z, Brow: jnp.einsum("fp,mpknr->fmknr", Brow, Z))

    def _t(label, t0, out):
        if timer is not None:
            jax.block_until_ready(out)
            timer.append((label, _time.perf_counter() - t0))
        return out

    n_runs = [0]    # runner invocation ordinal = interval, for traces

    def run(x8F, uF, vF, wF, freqF, wtF, fratioF, J0F, *beam_rest):
        interval = n_runs[0]
        n_runs[0] += 1
        beamF = beam_rest[0] if beam_rest else None
        F = x8F.shape[0]
        Brow_full = _brow(F, None)          # eager: Bfull[:F]
        blocks = [slice(b, min(b + block_f, F))
                  for b in range(0, F, block_f)]

        def take(a, sl):
            """Block slice, padded to block_f by repeating the first
            row so every block compiles to ONE program shape (a ragged
            tail block would otherwise double the solve compiles)."""
            ab = a[sl]
            short = block_f - ab.shape[0]
            if short:
                ab = jnp.concatenate(
                    [ab, jnp.broadcast_to(ab[:1],
                                          (short,) + ab.shape[1:])])
            return ab

        # constant per-tile inputs: slice/pad each block ONCE, and make
        # its coherencies ONCE, not per ADMM iteration
        const_blocks = []           # per block: (x8, coh, wt)
        for sl in blocks:
            bb = (() if beamF is None
                  else (jax.tree.map(lambda a: take(a, sl), beamF),))
            const_blocks.append((
                take(x8F, sl),
                coh_prog(*(take(a, sl) for a in (uF, vF, wF, freqF)),
                         *bb),
                take(wtF, sl)))

        def blockwise(fn, *per_iter):
            """fn(x8, coh, wt, *per-iteration block args)."""
            outs = []           # per block: (J, res0, res1, trips)
            for i, sl in enumerate(blocks):
                t0 = _time.perf_counter()
                out = fn(*const_blocks[i],
                         *[take(a, sl) for a in per_iter])
                _t(f"solve[{i}]", t0, out[0])
                outs.append([o[:sl.stop - sl.start] for o in out])
            return tuple(jnp.concatenate(o) for o in zip(*outs))

        JF, res0, res1, tk = blockwise(solve0, J0F)
        t0 = _time.perf_counter()
        carry, res0, res1, Y0F = cons0(JF, res0, res1, fratioF)
        _t("cons0", t0, carry[2])
        r1h, dualh, tks = [], [], [tk]
        pend = []       # deferred admm_iter records (no per-iter sync)
        for it in range(1, max(cfg.n_admm, 1)):
            BZ = bz_prog(carry[2], Brow_full)
            Jr, r0, r1, tk = blockwise(solveb, carry[0], carry[1], BZ,
                                       carry[3])
            tks.append(tk)
            t0 = _time.perf_counter()
            carry, (r0, r1, dual) = consb(Jr, r0, r1, carry,
                                          jnp.asarray(it, jnp.int32))
            _t(f"cons[{it}]", t0, carry[2])
            r1h.append(r1)
            dualh.append(dual)
            if dtrace.active() or obs.active():
                pend.append((it, jnp.mean(r1), dual,
                             jnp.mean(carry[3])))
        _emit_deferred(pend, interval)
        JF, Z, rhoF = carry[0], carry[2], carry[3]
        r1s_a = (jnp.stack(r1h) if r1h
                 else jnp.zeros((0, F), x8F.dtype))
        duals_a = (jnp.stack(dualh) if dualh
                   else jnp.zeros((0,), x8F.dtype))
        return (JF, Z, rhoF, res0, res1, r1s_a, duals_a, Y0F,
                jnp.stack(tks))

    return run
