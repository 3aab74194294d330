"""Distributed stochastic calibration with federated averaging.

Capability parity with the reference's stochastic sagecal-mpi mode
(``sagecal-mpi -N > 0``; ``src/MPI/sagecal_stochastic_master.cpp`` +
``sagecal_stochastic_slave.cpp``): every "slave" (here: one subband
dataset; reference: one MPI rank with its MS list) runs minibatch
consensus calibration over its own frequency mini-bands with a LOCAL
polynomial consensus Z, and the slaves are coupled by FEDERATED
AVERAGING of their Z's:

- local Z update (slave :780-825): z = sum_b B_b Y_b (+ alpha Zavg - X
  after the first outer iteration), Z = Bii_fed z where Bii_fed is the
  inverse of (sum_b rho_b B_b B_b^T + alpha I)
  (``find_prod_inverse_full_fed``, consensus_poly.c);
- global Zavg = mean over slaves (stochastic master :329-351) — ONE
  shard_map program over a "slave" mesh axis: every slave's
  epochs x minibatches x bands J/Y/Z updates run shard-local and the
  federated average is a psum (``lax.pmean`` semantics, SURVEY.md P11);
- federated dual X += alpha (Z - Zavg) per cluster (slave :867-875);
- per-(slave, band) J updates are the stochastic consensus LBFGS solver
  (``bfgsfit_minibatch_consensus``), with diverged bands flagged out of
  the Z update exactly as the single-node mode does.

The mesh runner executes one outer (federated) iteration per device
program — the host keeps only the n_admm loop and tile I/O. A
host-sequential implementation (:func:`run_federated_sequential`) is
retained as the oracle for the sharding-invariance test. Slaves that
don't divide the mesh fold onto the local leading axis; a slave count
below the device count pads with masked replicas (admm.pad_subbands
pattern).
"""

from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from sagecal_tpu import skymodel, utils
from sagecal_tpu.config import RunConfig
from sagecal_tpu.consensus import poly as cpoly
from sagecal_tpu.io import dataset as ds
from sagecal_tpu.solvers import lbfgs as lbfgs_mod
from sagecal_tpu import stochastic as st

RES_RATIO = st.RES_RATIO


def make_fed_outer(rn0, cfg: RunConfig, mesh, nslaves: int, alpha,
                   n_epochs: int):
    """Build the jitted one-outer-iteration federated program.

    Input arrays carry a leading slave axis [Spad, ...] sharded over the
    mesh's "slave" axis (Spad = Fl*ndev; padded slave slots replicate
    slave 0 and are masked out of the federated average):

    data:  x8 [S, nmb, W, B, Fp, 8], wt same, freqs [S, W, Fp],
           u/v/w [S, nmb, B], tslot [nmb, B] (shared), Bb [S, W, P],
           Bii [S, M, P, P], rhok [S, W, M], beam (stacked pytree | None)
    state: p [S, W, M, K, N, 8], mem (stacked LBFGSMemory), Y [S, W, M,
           K, N, 8], Z [S, M, P, K, N, 8], X like Z, Zavg [M, P, K, N,
           8] replicated, it (scalar outer index)

    Returns (p, mem, Y, Z, X, Zavg', resband [S, W], r0h [S, E*nmb, W],
    r1h, feda) — feda is the federated dual residual
    sum_s ||Z_s - Zavg||^2 over real slaves (stochastic master :329-351).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    axis = "slave"
    raw = st.make_band_solver(
        rn0.dsky, rn0.n, rn0.cidx, rn0.cmask, rn0.fdelta_chan,
        nu=cfg.robust_nulow, max_lbfgs=cfg.max_lbfgs, consensus=True,
        dobeam=rn0.dobeam, loss=cfg.stochastic_loss).__wrapped__
    minibatches = rn0.minibatches
    alpha_np = np.asarray(alpha)

    def per_slave(x8, wt, freqs, u, v, w, tslot, sta1, sta2, Bb, Bii,
                  rhok, beam, p, mem, Y, Z, X, Zavg, it):
        a5 = jnp.asarray(alpha_np, x8.dtype)[:, None, None, None, None]
        steps = jnp.arange(n_epochs * minibatches) % minibatches

        def body(carry, mb):
            p, mem, Y, Z, resband = carry
            BZ = jnp.einsum("wp,mpkns->wmkns", Bb, Z)
            out = jax.vmap(
                lambda x8b, wtb, fqb, pb, memb, Yb, BZb, rhob: raw(
                    x8b, u[mb], v[mb], w[mb], sta1, sta2, wtb, fqb,
                    tslot[mb], pb, memb, Y=Yb, BZ=BZb, rho=rhob,
                    beam=beam)
            )(x8[mb], wt[mb], freqs, p, mem, Y, BZ, rhok)
            p, mem = out.p, out.mem
            r0s, r1s = out.res_0, out.res_1
            resband = jnp.where((r0s > 0) & (r1s > 0), r1s, jnp.inf)
            rmean = jnp.mean(r1s)
            good = (resband <= RES_RATIO * rmean).astype(p.dtype)
            g5 = good[:, None, None, None, None]
            r4 = rhok[..., None, None, None]
            # local ADMM update (slave :780-825)
            Y = Y + g5 * r4 * p
            zsum = jnp.einsum("w,wp,wmkns->mpkns", good, Bb, Y)
            zsum = zsum + jnp.where(it > 0, a5 * Zavg - X, 0.0)
            Z = jnp.einsum("mpq,mqkns->mpkns", Bii, zsum)
            BZn = jnp.einsum("wp,mpkns->wmkns", Bb, Z)
            Y = Y - g5 * r4 * BZn
            return (p, mem, Y, Z, resband), (r0s, r1s)

        resband0 = jnp.zeros(x8.shape[1], x8.dtype)   # [W] bands
        (p, mem, Y, Z, resband), (r0h, r1h) = jax.lax.scan(
            body, (p, mem, Y, Z, resband0), steps)
        return p, mem, Y, Z, resband, r0h, r1h

    beam_ax = None if rn0.tile_beam is None else 0

    def outer_local(x8, wt, freqs, u, v, w, tslot, sta1, sta2, Bb, Bii,
                    rhok, beam, p, mem, Y, Z, X, Zavg, it):
        Sl = x8.shape[0]
        dev_idx = jax.lax.axis_index(axis)
        smask = ((dev_idx * Sl + jnp.arange(Sl))
                 < nslaves).astype(x8.dtype)
        p, mem, Y, Z, resband, r0h, r1h = jax.vmap(
            per_slave,
            in_axes=(0, 0, 0, 0, 0, 0, None, None, None, 0, 0, 0,
                     beam_ax, 0, 0, 0, 0, 0, None, None),
        )(x8, wt, freqs, u, v, w, tslot, sta1, sta2, Bb, Bii, rhok,
          beam, p, mem, Y, Z, X, Zavg, it)
        s6 = smask[:, None, None, None, None, None]
        # federated averaging = pmean over REAL slaves (P11)
        Zavg_new = jax.lax.psum(jnp.sum(jnp.where(s6 > 0, Z, 0.0),
                                        axis=0), axis) / nslaves
        d = Z - Zavg_new[None]
        X = X + jnp.asarray(alpha_np,
                            X.dtype)[None, :, None, None, None, None] * d
        X = jnp.where(s6 > 0, X, 0.0)
        feda = jax.lax.psum(
            jnp.sum(smask * jnp.sum(d * d, axis=(1, 2, 3, 4, 5))), axis)
        return p, mem, Y, Z, X, Zavg_new, resband, r0h, r1h, feda

    ps, pr = P(axis), P()
    in_specs = ((ps,) * 6 + (pr, pr, pr) + (ps,) * 3
                + ((pr,) if beam_ax is None else (ps,))
                + (ps,) * 5 + (pr, pr))
    out_specs = (ps, ps, ps, ps, ps, pr, ps, ps, ps, pr)
    return jax.jit(shard_map(outer_local, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False))


def _fed_setup(cfg: RunConfig, paths: list):
    """Shared slave/basis/state setup for both federated implementations
    — the identical-math premise of the sharding-invariance oracle rests
    on both paths consuming exactly this."""
    # each slave path may be a SimMS directory or a real CASA table
    mss = [ds.open_part(p, tilesz=cfg.tile_size,
                        data_column=cfg.input_column,
                        out_column=cfg.output_column) for p in paths]
    meta0 = mss[0].meta
    sky = skymodel.read_sky_cluster(
        cfg.sky_model, cfg.cluster_file, meta0["ra0"], meta0["dec0"],
        float(np.mean([m.meta["freq0"] for m in mss])), cfg.format_3)
    runners = [st._StochasticRunner(cfg, m, sky, log=(lambda *a: None))
               for m in mss]
    rn0 = runners[0]
    M = rn0.M
    ref_f = float(np.mean([m.meta["freq0"] for m in mss]))
    alpha = np.full(M, cfg.federated_alpha)
    arho = np.full(M, cfg.admm_rho)
    if cfg.rho_file:
        arho = skymodel.read_cluster_rho(cfg.rho_file, sky.cluster_ids,
                                         cfg.admm_rho)
    Bs, Biis, rhoks = [], [], []
    for rn in runners:
        fcen = np.array([rn.freqs[c0:c0 + nc].mean()
                         for c0, nc in zip(rn.chanstart, rn.nchan)])
        B = cpoly.setup_polynomials(fcen, ref_f, cfg.n_poly,
                                    cfg.poly_type)
        rhok = np.tile(arho[None, :], (rn.nsolbw, 1))       # [nb, M]
        # federated inverse: +alpha I (find_prod_inverse_full_fed)
        Bii = np.asarray(cpoly.find_prod_inverse(
            jnp.asarray(B), jnp.asarray(rhok.T), alpha=jnp.asarray(alpha)))
        Bs.append(B)
        Biis.append(Bii)
        rhoks.append(rhok)
    states = []
    for rn in runners:
        pinit, pfreq = rn.initial_p()
        mems = [lbfgs_mod.lbfgs_memory_init(rn.nparam, cfg.lbfgs_m,
                                            rn.rdt)
                for _ in range(rn.nsolbw)]
        states.append({"pfreq": pfreq, "mems": mems, "pinit": pinit,
                       "res_prev": None})
    return mss, sky, runners, alpha, Bs, Biis, rhoks, states


def run_federated(cfg: RunConfig, paths: list, log=print, mesh=None):
    """Mesh-parallel federated stochastic calibration: slaves ride a
    "slave" mesh axis, one device program per outer iteration, Zavg via
    psum (P11). ``mesh=None`` builds one over all available devices."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mss, sky, runners, alpha, Bs, Biis, rhoks, states = _fed_setup(
        cfg, paths)
    nslaves = len(mss)
    rn0 = runners[0]
    if mesh is None:
        ndev = min(len(jax.devices()), nslaves)
        mesh = Mesh(np.array(jax.devices()[:ndev]), ("slave",))
    ndev = mesh.devices.size
    spad = -(-max(nslaves, ndev) // ndev) * ndev
    log(f"Federated stochastic calibration: {nslaves} slave datasets "
        f"over {ndev} device(s)"
        + (f" (padded to {spad})" if spad != nslaves else "")
        + f", {cfg.n_epochs} epochs x {rn0.minibatches} minibatches, "
        f"{rn0.nsolbw} mini-bands each, {cfg.n_admm} outer iterations")

    M, kmax, n, Pn = rn0.M, rn0.kmax, rn0.n, cfg.n_poly

    outer = make_fed_outer(rn0, cfg, mesh, nslaves, alpha, cfg.n_epochs)
    sh = NamedSharding(mesh, P("slave"))
    shr = NamedSharding(mesh, P())
    rdt = rn0.rdt

    def pad_s(a):
        a = np.asarray(a)
        if spad == nslaves:
            return a
        return np.concatenate(
            [a, np.broadcast_to(a[:1], (spad - nslaves,) + a.shape[1:])])

    def stage_s(a):
        return jax.device_put(jnp.asarray(pad_s(a), rdt), sh)

    pshape = (M, kmax, n, 8)
    BbS = stage_s(np.stack(Bs))
    BiiS = stage_s(np.stack(Biis))
    rhokS = stage_s(np.stack(rhoks))

    writer = rn0.solution_writer()
    n_tiles = min(m.n_tiles for m in mss)
    start = cfg.skip_timeslots
    stop = n_tiles if not cfg.max_timeslots else min(
        n_tiles, start + cfg.max_timeslots)
    history = []
    for ti in range(start, stop):
        t0 = time.time()
        tiles = [m.read_tile(ti) for m in mss]
        for rn, tile in zip(runners, tiles):
            rn.prepare_tile(tile)

        # stage the tile's data: [S, nmb, ...] stacks of band_inputs_all.
        # sta1/sta2/tslot are staged ONCE and replicated: the mesh
        # program assumes homogeneous row/baseline ordering across
        # slaves and minibatches, so verify it instead of trusting it
        x8_s, wt_s, fq_s, u_s, v_s, w_s = [], [], [], [], [], []
        tslot = sta1 = sta2 = None
        for rn in runners:
            per_mb = [rn.band_inputs_all(nmb)
                      for nmb in range(rn.minibatches)]
            x8_s.append(np.stack([np.asarray(a[0]) for a in per_mb]))
            u_s.append(np.stack([np.asarray(a[1]) for a in per_mb]))
            v_s.append(np.stack([np.asarray(a[2]) for a in per_mb]))
            w_s.append(np.stack([np.asarray(a[3]) for a in per_mb]))
            wt_s.append(np.stack([np.asarray(a[6]) for a in per_mb]))
            fq_s.append(np.asarray(per_mb[0][7]))
            ts = np.stack([np.asarray(a[8]) for a in per_mb])
            s1, s2 = np.asarray(per_mb[0][4]), np.asarray(per_mb[0][5])
            for a in per_mb[1:]:
                if not (np.array_equal(np.asarray(a[4]), s1)
                        and np.array_equal(np.asarray(a[5]), s2)):
                    raise ValueError(
                        f"{rn.ms.path}: baseline ordering differs "
                        f"between minibatches — unsupported by the mesh "
                        f"federated program")
            if sta1 is not None and not (
                    np.array_equal(s1, sta1) and np.array_equal(s2, sta2)
                    and np.array_equal(ts, tslot)):
                raise ValueError(
                    f"{rn.ms.path}: baseline/timeslot layout differs "
                    f"from the first slave dataset — unsupported by the "
                    f"mesh federated program (use "
                    f"run_federated_sequential)")
            sta1, sta2, tslot = s1, s2, ts
        beam_s = None
        if rn0.tile_beam is not None:
            beam_s = jax.tree.map(
                lambda *xs: jax.device_put(
                    jnp.asarray(pad_s(np.stack([np.asarray(x)
                                                for x in xs]))), sh),
                *[rn.tile_beam for rn in runners])

        pS = stage_s(np.stack([np.stack(s["pfreq"]) for s in states]))
        memS = jax.tree.map(
            lambda *xs: jax.device_put(jnp.stack(list(xs)
                                                 + [xs[0]] * (spad - nslaves)),
                                       sh),
            *[jax.tree.map(lambda *bs: jnp.stack(bs), *s["mems"])
              for s in states])
        YS = stage_s(np.zeros((nslaves, rn0.nsolbw) + pshape))
        ZS = stage_s(np.zeros((nslaves, M, Pn, kmax, n, 8)))
        XS = stage_s(np.zeros((nslaves, M, Pn, kmax, n, 8)))
        Zavg = jax.device_put(jnp.zeros((M, Pn, kmax, n, 8), rdt), shr)

        data_dev = (stage_s(np.stack(x8_s)), stage_s(np.stack(wt_s)),
                    stage_s(np.stack(fq_s)), stage_s(np.stack(u_s)),
                    stage_s(np.stack(v_s)), stage_s(np.stack(w_s)),
                    jax.device_put(jnp.asarray(tslot), shr),
                    jax.device_put(jnp.asarray(sta1), shr),
                    jax.device_put(jnp.asarray(sta2), shr),
                    BbS, BiiS, rhokS, beam_s)

        res_0 = res_1 = 0.0
        r0h = r1h = None
        for nadmm in range(cfg.n_admm):
            out = outer(*data_dev, pS, memS, YS, ZS, XS, Zavg,
                        jnp.asarray(nadmm, jnp.int32))
            pS, memS, YS, ZS, XS, Zavg, resbandS, r0h, r1h, feda = out
            if cfg.verbose:
                log(f"FEDA: {nadmm} dual residual="
                    f"{float(np.sqrt(np.asarray(feda) / max(Zavg.size * nslaves, 1))):.6f}")
        r0h = np.asarray(r0h)[:nslaves]
        r1h = np.asarray(r1h)[:nslaves]
        res_0, res_1 = float(r0h.mean()), float(r1h.mean())
        resband_np = np.asarray(resbandS)[:nslaves]
        Z_np = np.asarray(ZS)[:nslaves]
        p_np = np.asarray(pS)[:nslaves]
        mem_host = jax.tree.map(np.asarray, memS)

        for s, rn in enumerate(runners):
            pfreq, mems = states[s]["pfreq"], states[s]["mems"]
            for b in range(rn.nsolbw):
                pfreq[b] = p_np[s, b]
                mems[b] = jax.tree.map(lambda a: jnp.asarray(a[s, b]),
                                       mem_host)
            if cfg.use_global_solution:
                for b in range(rn.nsolbw):
                    pfreq[b] = np.einsum("p,mpkns->mkns", Bs[s][b],
                                         Z_np[s]).astype(pfreq[b].dtype)
            rn.end_of_tile(tiles[s], ti, states[s], resband_np[s], res_0,
                           res_1, t0, writer if s == 0 else None,
                           history if s == 0 else [])
    if writer:
        writer.close()
    return history


def run_federated_sequential(cfg: RunConfig, paths: list, log=print):
    """Host-sequential federated implementation: identical math, one
    slave at a time (the sharding-invariance oracle)."""
    mss, sky, runners, alpha, Bs, Biis, rhoks, states = _fed_setup(
        cfg, paths)
    nslaves = len(mss)
    rn0 = runners[0]
    log(f"Federated stochastic calibration: {nslaves} slave datasets, "
        f"{cfg.n_epochs} epochs x {rn0.minibatches} minibatches, "
        f"{rn0.nsolbw} mini-bands each, {cfg.n_admm} outer iterations")

    solver = st.make_band_solver(
        rn0.dsky, rn0.n, rn0.cidx, rn0.cmask, rn0.fdelta_chan,
        nu=cfg.robust_nulow, max_lbfgs=cfg.max_lbfgs, consensus=True,
        dobeam=rn0.dobeam, loss=cfg.stochastic_loss)

    P = cfg.n_poly
    M, kmax, n = rn0.M, rn0.kmax, rn0.n
    pshape = (M, kmax, n, 8)
    writer = rn0.solution_writer()
    n_tiles = min(m.n_tiles for m in mss)
    start = cfg.skip_timeslots           # -K (CTRL_SKIP, master :623-634)
    stop = n_tiles if not cfg.max_timeslots else min(
        n_tiles, start + cfg.max_timeslots)
    history = []
    for ti in range(start, stop):
        t0 = time.time()
        tiles = [m.read_tile(ti) for m in mss]
        for rn, tile in zip(runners, tiles):
            rn.prepare_tile(tile)
        Zavg = np.zeros((M, P, kmax, n, 8))
        Zs = [np.zeros_like(Zavg) for _ in range(nslaves)]
        Xs = [np.zeros_like(Zavg) for _ in range(nslaves)]
        Ys = [np.zeros((rn.nsolbw,) + pshape) for rn in runners]
        resband = [np.zeros(rn.nsolbw) for rn in runners]
        res_0 = res_1 = 0.0
        for nadmm in range(cfg.n_admm):
            r0all, r1all = [], []
            for s, rn in enumerate(runners):
                B, Bii, rhok = Bs[s], Biis[s], rhoks[s]
                Y, Z, X = Ys[s], Zs[s], Xs[s]
                pfreq, mems = states[s]["pfreq"], states[s]["mems"]
                for nepch in range(cfg.n_epochs):
                    for nmb in range(rn.minibatches):
                        r0s, r1s = [], []
                        for b in range(rn.nsolbw):
                            BZ = np.einsum("p,mpkns->mkns", B[b], Z)
                            args = rn.band_inputs(nmb, b)
                            out = solver(
                                *args, jnp.asarray(pfreq[b], rn.rdt),
                                mems[b], Y=jnp.asarray(Y[b], rn.rdt),
                                BZ=jnp.asarray(BZ, rn.rdt),
                                rho=jnp.asarray(rhok[b], rn.rdt),
                                beam=rn.tile_beam)
                            pfreq[b] = np.asarray(out.p)
                            mems[b] = out.mem
                            r00, r01 = float(out.res_0), float(out.res_1)
                            resband[s][b] = r01 if (r00 > 0 and r01 > 0) \
                                else np.inf
                            r0s.append(r00)
                            r1s.append(r01)
                        rmean = float(np.mean(r1s))
                        fband = resband[s] > RES_RATIO * rmean
                        good = ~fband
                        # local ADMM update (slave :780-825)
                        for b in np.where(good)[0]:
                            Y[b] += (rhok[b][:, None, None, None]
                                     * pfreq[b])
                        zsum = np.einsum("b,bp,bmkns->mpkns",
                                         good.astype(float), B, Y)
                        if nadmm > 0:
                            zsum += (alpha[:, None, None, None, None]
                                     * Zavg - X)
                        Z = np.einsum("mpq,mqkns->mpkns", Bii, zsum)
                        for b in np.where(good)[0]:
                            BZb = np.einsum("p,mpkns->mkns", B[b], Z)
                            Y[b] -= rhok[b][:, None, None, None] * BZb
                        r0all.extend(r0s)
                        r1all.extend(r1s)
                Zs[s] = Z
            # federated averaging (stochastic master :329-351; pmean on a
            # mesh) + dual update X += alpha (Z - Zavg) (slave :867-875)
            Zavg = np.mean(Zs, axis=0)
            feda = 0.0
            for s in range(nslaves):
                d = Zs[s] - Zavg
                Xs[s] += alpha[:, None, None, None, None] * d
                feda += float(np.linalg.norm(d)) ** 2
            if cfg.verbose:
                log(f"FEDA: {nadmm} dual residual="
                    f"{np.sqrt(feda / max(Zavg.size * nslaves, 1)):.6f}")
            res_0 = float(np.mean(r0all))
            res_1 = float(np.mean(r1all))

        for s, rn in enumerate(runners):
            pfreq = states[s]["pfreq"]
            if cfg.use_global_solution:
                for b in range(rn.nsolbw):
                    pfreq[b] = np.einsum("p,mpkns->mkns", Bs[s][b],
                                         Zs[s]).astype(pfreq[b].dtype)
            rn.end_of_tile(tiles[s], ti, states[s], resband[s], res_0,
                           res_1, t0, writer if s == 0 else None,
                           history if s == 0 else [])
    if writer:
        writer.close()
    return history
