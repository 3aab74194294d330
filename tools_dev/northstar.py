#!/usr/bin/env python
"""North-star scale evidence (BASELINE.md): 64 stations x 100 directions
x 32 subbands x hybrid chunks through the distributed CLI, recording
ADMM wall-clock per iteration.

Generates the synthetic multi-subband observation (the Change_freq.py
analogue at the dosage-mpi.sh north-star shape), then invokes
``sagecal_tpu.cli_mpi`` with the robust-RTR solver (-j 5) and the
single-device blocked execution plan (--block-f) that bounds every device
program's execution time. Two tiles are
calibrated so the second tile's per-iteration wall-clock is compile-free;
that number goes to NORTHSTAR.json and a row is appended to
BENCH_TABLE.md.

Usage: python tools_dev/northstar.py [--cpu] [--block-f 2] [--admm 3]
       [--stations 64] [--dirs 100] [--subbands 32] [--keep DIR]
"""

import argparse
import json
import os
import re
import subprocess
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# repo root on the path up front: generate() imports sagecal_tpu before
# main()'s bench import — an uninstalled fresh session must still work
sys.path.insert(0, HERE)


def generate(workdir, n_sta, n_dir, n_sub, tilesz, n_tiles, seed=5):
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    from sagecal_tpu import skymodel
    from sagecal_tpu.io import dataset as ds
    from sagecal_tpu.rime import predict as rp

    rng = np.random.default_rng(seed)
    ra0, dec0 = 1.2, 0.7
    # 100 directions x 2 sources, hybrid chunks 1/2 alternating
    sky_lines, clus_lines = [], []
    for m in range(n_dir):
        names = []
        for s in range(2):
            # 'P' prefix: POINT (readsky.c name-prefix source typing —
            # G/D/R/S select gaussian/disk/ring/shapelet)
            nm = f"P{m:03d}_{s}"
            ra = ra0 + rng.normal(0, 0.03)
            dec = dec0 + rng.normal(0, 0.03)
            h = (ra % (2 * np.pi)) * 12 / np.pi
            rah, rm_ = int(h), int((h - int(h)) * 60)
            rs = ((h - rah) * 60 - rm_) * 60
            dd = np.degrees(dec)
            deg, dm = int(dd), int((dd - int(dd)) * 60)
            dsec = ((dd - deg) * 60 - dm) * 60
            flux = float(np.exp(rng.normal(0.5, 0.8)))
            sky_lines.append(
                f"{nm} {rah} {rm_} {rs:.4f} {deg} {dm} {dsec:.4f} "
                f"{flux:.4f} 0 0 0 -0.7 0 0 0 0 150e6")
            names.append(nm)
        clus_lines.append(f"{m} {1 + m % 2} " + " ".join(names))
    skyp = os.path.join(workdir, "northstar.sky.txt")
    clup = os.path.join(workdir, "northstar.sky.txt.cluster")
    with open(skyp, "w") as f:
        f.write("\n".join(sky_lines) + "\n")
    with open(clup, "w") as f:
        f.write("\n".join(clus_lines) + "\n")

    sky = skymodel.read_sky_cluster(skyp, clup, ra0, dec0, 150e6)
    dsky = rp.sky_to_device(sky, jnp.float32)
    Jbase = ds.random_jones(sky.n_clusters, sky.nchunk, n_sta, seed=6,
                            scale=0.15)
    slope = (ds.random_jones(sky.n_clusters, sky.nchunk, n_sta, seed=7,
                             scale=0.04) - np.eye(2))
    paths = []
    for f_i in range(n_sub):
        fr = 120e6 * (1 + 0.004 * f_i)
        Jf = Jbase + slope * (fr - 120e6) / 120e6
        tiles = [ds.simulate_dataset(
            dsky, n_stations=n_sta, tilesz=tilesz, freqs=[fr], ra0=ra0,
            dec0=dec0, jones=Jf, nchunk=sky.nchunk, noise_sigma=0.02,
            seed=20 + t) for t in range(n_tiles)]
        p = os.path.join(workdir, f"sb{f_i:02d}.ms")
        ds.SimMS.create(p, tiles)
        paths.append(p)
        print(f"  subband {f_i + 1}/{n_sub} written", flush=True)
    lst = os.path.join(workdir, "mslist.txt")
    with open(lst, "w") as f:
        f.write("\n".join(paths) + "\n")
    return skyp, clup, lst


def _northstar_sky(n_sta, n_dir, seed=5):
    """The in-process north-star sky (100 directions x 2 sources,
    hybrid chunks 1/2 alternating) shared by --b-scaling and
    --multichip."""
    from sagecal_tpu import skymodel
    rng = np.random.default_rng(seed)
    srcs, clusters = {}, []
    for m in range(n_dir):
        names = []
        for s in range(2):
            nm = f"P{m:03d}_{s}"
            ll, mm = rng.normal(0, 0.03, 2)
            nn = np.sqrt(max(1 - ll * ll - mm * mm, 0.0))
            flux = float(np.exp(rng.normal(0.5, 0.8)))
            srcs[nm] = skymodel.Source(
                name=nm, ra=0, dec=0, ll=ll, mm=mm, nn=nn - 1, sI=flux,
                sQ=0.0, sU=0.0, sV=0.0, sI0=flux, sQ0=0, sU0=0, sV0=0,
                spec_idx=-0.7, spec_idx1=0.0, spec_idx2=0.0, f0=150e6)
            names.append(nm)
        clusters.append((m, 1 + m % 2, names))    # hybrid chunks 1/2
    return skymodel.build_cluster_sky(srcs, clusters)


def b_scaling(args):
    """The round-5 VERDICT's missing experiment: the north-star
    per-cluster sweep cost at B, B/2, B/4 data rows (tilesz 4/2/1 at
    N=64, M=100, robust-RTR -g 3 — the exact shape whose 31 ms/cluster
    plateaus the single-chip target). If ms/cluster scales ~linearly
    with B the sweep is data-traffic-bound (fusion/dtype wins ride on
    it); if it barely moves, the floor is per-cluster dispatch/latency
    overhead and more traffic shrinking cannot cut it. Runs in-process
    (one subband, one EM sweep per shape, warm-timed).

    ``--inner chol|cg`` selects the inner linear solver; ``--inner
    both`` runs the ladder under each and writes the round-7 comparison
    record BSCALING_r07.json (chol vs cg per B rung + the delta on the
    B-independent floor) instead of BSCALING.json — the PR-3 tentpole's
    banked verdict.

    ``--kernel xla|pallas|both`` additionally selects the row-pass
    kernel (SageConfig.kernel; ops/sweep_pallas.py). With more than one
    (inner, kernel) combination the run writes the banked comparison
    record BSCALING_r17.json (round 11 introduced the series; round 17
    adds the fused-chol/K-major cells plus explicit full-B and
    small-rung headline fields) — kernel on/off x inner chol/cg per B
    rung, with EXECUTED trip counts (solver/cg) per cell so the floor
    melt and the cg trip price are compared at equal work, measured
    deltas in JSON rather than prose. The SAGECAL_BENCH_KERNEL env var
    is honored as the default when --kernel is not given (bench.py
    parity)."""
    import jax
    from sagecal_tpu import utils
    utils.setup_backend("cpu" if args.cpu else None)
    import jax.numpy as jnp
    from sagecal_tpu.io import dataset as ds
    from sagecal_tpu.rime import predict as rp
    from sagecal_tpu.solvers import sage

    n_sta, n_dir = args.stations, args.dirs
    sky = _northstar_sky(n_sta, n_dir)
    dsky = rp.sky_to_device(sky, jnp.float32)
    kmax = int(sky.nchunk.max())
    cmask = jnp.asarray(
        np.arange(kmax)[None, :] < sky.nchunk[:, None])
    Jtrue = ds.random_jones(n_dir, sky.nchunk, n_sta, seed=6, scale=0.15)
    M = n_dir
    inners = (("chol", "cg") if args.inner == "both" else (args.inner,))
    kernels = (("xla", "pallas") if args.kernel == "both"
               else (args.kernel,))
    combos = [(i, k) for i in inners for k in kernels]
    ladders = {c: [] for c in combos}
    for tilesz in (args.tilesz, args.tilesz // 2, args.tilesz // 4):
        if tilesz < 1:
            continue
        tile = ds.simulate_dataset(dsky, n_stations=n_sta, tilesz=tilesz,
                                   freqs=[150e6], ra0=1.2, dec0=0.7,
                                   jones=Jtrue, nchunk=sky.nchunk,
                                   noise_sigma=0.02, seed=23)
        B = tile.nrows
        cidx = jnp.asarray(rp.chunk_indices(tilesz, tile.nbase,
                                            sky.nchunk))
        u = jnp.asarray(tile.u, jnp.float32)
        v = jnp.asarray(tile.v, jnp.float32)
        w = jnp.asarray(tile.w, jnp.float32)
        coh = rp.coherencies(dsky, u, v, w,
                             jnp.asarray([150e6], jnp.float32),
                             tile.fdelta)[:, :, 0]
        xa = np.asarray(tile.averaged())
        x8 = jnp.asarray(np.stack([xa.reshape(-1, 4).real,
                                   xa.reshape(-1, 4).imag],
                                  -1).reshape(-1, 8), jnp.float32)
        wt = jnp.asarray((np.asarray(tile.flags) == 0)[:, None]
                         * np.ones((1, 8)), jnp.float32)
        s1 = jnp.asarray(tile.sta1, jnp.int32)
        s2 = jnp.asarray(tile.sta2, jnp.int32)
        J0 = jnp.asarray(np.tile(np.eye(2, dtype=np.complex64),
                                 (M, kmax, n_sta, 1, 1)))
        total_iter = M * 3
        iter_bar = int(-(-0.8 * total_iter // M))
        key = jax.random.fold_in(jax.random.PRNGKey(42), 0)
        perm = jnp.arange(M, dtype=jnp.int32)
        xres = x8 - sage.full_model8(J0, coh, s1, s2, cidx)
        nuM = jnp.full((M,), 2.0, jnp.float32)

        for inner, kern in combos:
            cfg = sage.SageConfig(max_iter=3, max_lbfgs=0,
                                  solver_mode=args.solver,
                                  nbase=tile.nbase, inner=inner,
                                  kernel=kern,
                                  jones_mode=getattr(args, "jones",
                                                     "full"))

            def sweep():
                # fresh state per call: the sweep program donates its
                # carries
                return sage._jit_em_sweep(
                    J0.copy(), xres.copy(), nuM.copy(), x8, coh, s1, s2,
                    cidx, cmask, wt, jnp.zeros((M,), jnp.float32),
                    jnp.asarray(False), jnp.asarray(False), key, perm,
                    None, n_stations=n_sta,
                    config=cfg._replace(max_emiter=0),
                    total_iter=total_iter, iter_bar=iter_bar, os_nsub=0)

            out = sweep()
            jax.block_until_ready(out[0])          # compile
            times = []
            for _ in range(args.reps):
                t0 = time.time()
                out = sweep()
                jax.block_until_ready(out[0])
                times.append(time.time() - t0)
            med = float(np.median(times))
            # executed-trip counters (sweep carry tk: [solver iters,
            # rejected groups, cg trips]) — the "equal trip counts"
            # evidence next to each timing cell
            tk = np.asarray(out[4])
            ladders[(inner, kern)].append(
                {"tilesz": tilesz, "B": int(B), "sweep_s": round(med, 3),
                 "ms_per_cluster": round(1e3 * med / M, 2),
                 "solver_trips": int(tk[0]), "cg_trips": int(tk[2])})
            print(f"inner={inner} kernel={kern} tilesz={tilesz} B={B}: "
                  f"sweep {med:.3f} s -> {1e3 * med / M:.2f} ms/cluster"
                  f" trips={int(tk[0])}/{int(tk[2])} "
                  f"(runs {[f'{t:.2f}' for t in times]})", flush=True)

    def ladder_fields(rows):
        full, quarter = rows[0], rows[-1]
        ratio = full["ms_per_cluster"] / max(quarter["ms_per_cluster"],
                                             1e-9)
        bratio = full["B"] / quarter["B"]
        # linear-in-B would give ratio ~= bratio; flat gives ~1
        verdict = ("bandwidth" if ratio > 0.5 * bratio + 0.5
                   else "overhead")
        return {"rows": rows,
                "ms_per_cluster_ratio_full_vs_quarter": round(ratio, 2),
                "B_ratio_full_vs_quarter": round(bratio, 2),
                "verdict": verdict}

    import jax as _jax
    shape = f"N={n_sta} M={M} -j{args.solver} -g 3 hybrid-chunks"
    platform = _jax.devices()[0].platform
    if len(combos) == 1:
        inner, kern = combos[0]
        rec = {"metric": "north-star sweep B-scaling", "shape": shape,
               "platform": platform,
               "inner": inner, "kernel": kern,
               **ladder_fields(ladders[combos[0]])}
        out_path = os.path.join(getattr(args, "bank_dir", None) or HERE,
                                "BSCALING.json")
    elif len(kernels) == 1 and kernels[0] == "xla":
        per = {i: ladder_fields(ladders[(i, "xla")]) for i in inners}
        # the PR-3 headline: how much of the B-independent floor does
        # the matrix-free inner melt, per B rung and at the floor (the
        # quarter-B rung, where the PR-2 record showed wall-clock stops
        # following B)
        deltas = [
            {"tilesz": c["tilesz"], "B": c["B"],
             "chol_ms_per_cluster": c["ms_per_cluster"],
             "cg_ms_per_cluster": g["ms_per_cluster"],
             "cg_vs_chol_pct": round(
                 100.0 * (g["ms_per_cluster"] - c["ms_per_cluster"])
                 / c["ms_per_cluster"], 1)}
            for c, g in zip(per["chol"]["rows"], per["cg"]["rows"])]
        rec = {"metric": "north-star sweep B-scaling, chol vs cg inner",
               "shape": shape,
               "platform": platform,
               "chol": per["chol"], "cg": per["cg"],
               "cg_vs_chol": deltas,
               "floor_cg_vs_chol_pct": deltas[-1]["cg_vs_chol_pct"]}
        out_path = os.path.join(getattr(args, "bank_dir", None) or HERE,
                                "BSCALING_r07.json")
    else:
        # round-11 record: kernel on/off x inner chol/cg — the fused-
        # sweep melt as measured deltas. Per (inner, kernel) ladders
        # carry executed trip counters; the kernel deltas compare each
        # inner's pallas rung against its xla rung (same trajectory
        # class, trips recorded next to each cell), and the cg-vs-chol
        # gap is re-stated under each kernel so the "--inner cg pays
        # for its trips" claim is a number
        per = {f"{i}-{k}": ladder_fields(ladders[(i, k)])
               for (i, k) in combos}
        kernel_deltas = []
        for i in inners:
            if "xla" not in kernels or "pallas" not in kernels:
                break
            for cx, cp in zip(per[f"{i}-xla"]["rows"],
                              per[f"{i}-pallas"]["rows"]):
                kernel_deltas.append(
                    {"inner": i, "tilesz": cx["tilesz"], "B": cx["B"],
                     "xla_ms_per_cluster": cx["ms_per_cluster"],
                     "pallas_ms_per_cluster": cp["ms_per_cluster"],
                     "pallas_vs_xla_pct": round(
                         100.0 * (cp["ms_per_cluster"]
                                  - cx["ms_per_cluster"])
                         / cx["ms_per_cluster"], 1),
                     "xla_trips": [cx["solver_trips"], cx["cg_trips"]],
                     "pallas_trips": [cp["solver_trips"],
                                      cp["cg_trips"]]})
        rec = {"metric": "north-star sweep B-scaling, "
                         "kernel on/off x inner chol/cg",
               "shape": shape, "platform": platform,
               "interpret_mode": platform != "tpu",
               "ladders": per, "pallas_vs_xla": kernel_deltas}
        # bank hygiene: only the FULL kernel-pair x inner-pair grid may
        # claim the banked round-11 comparison record — a partial combo
        # set (e.g. SAGECAL_BENCH_KERNEL=pallas leaking in as the
        # --kernel default under --inner both, or --kernel both at the
        # default chol-only inner) lacks ladders the committed record's
        # headline fields cite and must not clobber it
        banked_pair = (set(kernels) >= {"xla", "pallas"}
                       and set(inners) >= {"chol", "cg"})
        if kernel_deltas:
            # headline: the per-cluster floor melt at the quarter-B
            # rung (B-independent regime) per inner, and the cg-vs-chol
            # gap under each kernel at full B
            for i in inners:
                rows = [d for d in kernel_deltas if d["inner"] == i]
                rec[f"floor_pallas_vs_xla_pct_{i}"] = \
                    rows[-1]["pallas_vs_xla_pct"]
                # round-17 headline: the FULL-B rung per inner (the
                # fused-chol melt acceptance cell), plus every sub-full
                # rung stated as its own field so a small-B regression
                # is PRICED in the banked record rather than buried in
                # the ladder rows
                rec[f"full_pallas_vs_xla_pct_{i}"] = \
                    rows[0]["pallas_vs_xla_pct"]
                rec[f"small_rung_pallas_vs_xla_pct_{i}"] = [
                    d["pallas_vs_xla_pct"] for d in rows[1:]]
            if set(inners) >= {"chol", "cg"}:
                for k in kernels:
                    c = per[f"chol-{k}"]["rows"][0]["ms_per_cluster"]
                    g = per[f"cg-{k}"]["rows"][0]["ms_per_cluster"]
                    rec[f"cg_vs_chol_pct_{k}"] = round(
                        100.0 * (g - c) / c, 1)
        bank_dir = getattr(args, "bank_dir", None) or HERE
        if banked_pair:
            out_path = os.path.join(bank_dir, "BSCALING_r17.json")
        else:
            out_path = os.path.join(bank_dir, "BSCALING_EXPLORE.json")
            print(f"# partial (inner, kernel) combo set {combos}: "
                  f"writing {os.path.basename(out_path)}, not the "
                  f"banked BSCALING_r17.json")
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec))
    return 0


def multichip(args):
    """Measured (not projected) multi-device evidence at the north-star
    ADMM shape: the full consensus-ADMM program on a VIRTUAL 8-device
    CPU mesh (``--xla_force_host_platform_device_count``), one subband
    per device, host-looped so every ADMM iteration is a bounded timed
    execution. Banks MULTICHIP_rNN.json with (a) per-iteration
    wall-clock, (b) the consensus half (z-sum psum + Bii solve + dual
    updates + manifold collectives) timed as its OWN mesh program —
    the per-iteration collective overhead, measured on the real
    communication pattern rather than projected from op counts — and
    (c) per-subband residuals, which must still FALL under the
    matrix-free inner solver (--inner cg) for the record to count
    (VERDICT weak-multichip follow-up)."""
    import jax
    from sagecal_tpu import utils
    utils.setup_backend("cpu", args.devices)
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from sagecal_tpu.consensus import admm as cadmm
    from sagecal_tpu.consensus import poly as cpoly
    from sagecal_tpu.io import dataset as ds
    from sagecal_tpu.rime import predict as rp
    from sagecal_tpu.solvers import lm as lm_mod, sage

    ndev = args.devices
    assert len(jax.devices()) >= ndev, jax.devices()
    n_sta, n_dir, F = args.stations, args.dirs, args.subbands
    sky = _northstar_sky(n_sta, n_dir)
    dsky = rp.sky_to_device(sky, jnp.float32)
    kmax = int(sky.nchunk.max())
    Jbase = ds.random_jones(n_dir, sky.nchunk, n_sta, seed=6, scale=0.15)
    slope = (ds.random_jones(n_dir, sky.nchunk, n_sta, seed=7,
                             scale=0.04) - np.eye(2))
    freqs = 120e6 * (1 + 0.004 * np.arange(F))
    tiles = []
    for f_i in range(F):
        Jf = Jbase + slope * (freqs[f_i] - 120e6) / 120e6
        tiles.append(ds.simulate_dataset(
            dsky, n_stations=n_sta, tilesz=args.tilesz, freqs=[freqs[f_i]],
            ra0=1.2, dec0=0.7, jones=Jf, nchunk=sky.nchunk,
            noise_sigma=0.02, seed=20 + f_i))
    tile = tiles[0]
    B = tile.nrows
    cidx = rp.chunk_indices(args.tilesz, tile.nbase, sky.nchunk)
    cmask = np.arange(kmax)[None, :] < sky.nchunk[:, None]
    Bpoly = cpoly.setup_polynomials(freqs, float(freqs.mean()), 2, 2)
    mesh = Mesh(np.array(jax.devices()[:ndev]), axis_names=("freq",))

    timer: list = []
    cfg = cadmm.ADMMConfig(
        n_admm=args.admm, npoly=2, rho=5.0, manifold_iters=5,
        sage=sage.SageConfig(max_emiter=1, max_iter=3, max_lbfgs=0,
                             solver_mode=args.solver, nbase=tile.nbase,
                             inner=args.inner,
                             kernel=args.kernel))
    runner = cadmm.make_admm_runner(
        dsky, tile.sta1, tile.sta2, cidx, cmask, n_sta, tile.fdelta,
        Bpoly, cfg, mesh, F, host_loop=True, nbase=tile.nbase,
        timer=timer)

    def x8_of(t):
        xa = np.asarray(t.averaged())
        return np.stack([xa.reshape(-1, 4).real, xa.reshape(-1, 4).imag],
                        -1).reshape(-1, 8)

    x8F = np.stack([x8_of(t) for t in tiles])
    uF = np.stack([t.u for t in tiles])
    vF = np.stack([t.v for t in tiles])
    wF = np.stack([t.w for t in tiles])
    wtF = np.stack([np.asarray(lm_mod.make_weights(
        jnp.asarray(t.flags, jnp.int32), jnp.float32)) for t in tiles])
    J0 = np.tile(np.eye(2, dtype=np.complex64),
                 (F, n_dir, kmax, n_sta, 1, 1))
    sh = NamedSharding(mesh, P("freq"))
    argsd = [jax.device_put(jnp.asarray(a, jnp.float32), sh) for a in
             (x8F, uF, vF, wF, freqs, wtF, np.ones(F),
              utils.jones_c2r_np(J0))]

    print(f"multichip: {ndev} virtual CPU devices, N={n_sta} M={n_dir} "
          f"F={F} B={B} tilesz={args.tilesz} -j{args.solver} "
          f"inner={args.inner} x{args.admm} ADMM iters", flush=True)
    t0 = time.time()
    out = runner(*argsd)           # compile + first (cold) run
    compile_s = time.time() - t0
    cold = list(timer)
    timer.clear()
    t0 = time.time()
    out = runner(*argsd)           # warm run: the banked numbers
    warm_total = time.time() - t0
    JF, Z, rhoF, res0, res1, r1s, duals = out[:7]
    res0 = np.asarray(res0)
    res1 = np.asarray(res1)
    r1s = np.asarray(r1s)          # [n_admm-1, F]
    body_walls = [s for lbl, s in timer if lbl.startswith("body")]

    # consensus-only: the collective half of one body iteration as its
    # own mesh execution, warm-timed on correctly-shaped carries — the
    # measured per-iteration collective overhead
    Ppoly = Bpoly.shape[1]
    f32 = jnp.float32
    mk = (F, n_dir, kmax, n_sta, 8)
    shr = NamedSharding(mesh, P())
    carry_shapes = [
        (mk, sh), (mk, sh), ((n_dir, Ppoly, kmax, n_sta, 8), shr),
        ((F, n_dir), sh), (mk, sh), (mk, sh),
        ((n_dir, Ppoly, kmax, n_sta, 8), shr),
        ((n_dir, Ppoly, kmax, n_sta, 8), shr), ((F, n_dir), sh)]
    carry0 = [jax.device_put(jnp.full(shp, 0.01, f32), s)
              for shp, s in carry_shapes]
    carry0[3] = jax.device_put(jnp.full((F, n_dir), 5.0, f32), sh)  # rhoF
    carry0[8] = carry0[3]                                    # rho_upper
    Jr = jax.device_put(jnp.full(mk, 0.01, f32), sh)
    r0d = jax.device_put(jnp.zeros((F,), f32), sh)
    cons = runner.consensus_program
    it1 = jnp.asarray(1, jnp.int32)
    o = cons(Jr, r0d, r0d, *carry0, it1)
    jax.block_until_ready(o[0])    # compile
    cons_times = []
    for _ in range(max(args.reps, 2)):
        t0 = time.time()
        o = cons(Jr, r0d, r0d, *carry0, it1)
        jax.block_until_ready(o[0])
        cons_times.append(time.time() - t0)
    cons_s = float(np.median(cons_times))

    body_med = float(np.median(body_walls)) if body_walls else float("nan")
    # residual trajectory per subband: iteration-0 final, then each
    # ADMM body iteration's final — all must fall vs the initial
    falling = bool(np.all(res1 < res0)) and (
        r1s.shape[0] == 0 or bool(np.all(r1s[-1] < res0)))
    import glob as _glob
    import re as _re
    rounds = [int(m.group(1)) for p in
              _glob.glob(os.path.join(HERE, "MULTICHIP_r*.json"))
              if (m := _re.search(r"_r(\d+)\.json$", p))]
    out_path = os.path.join(
        HERE, f"MULTICHIP_r{max(rounds, default=0) + 1:02d}.json")
    rec = {
        "metric": "north-star ADMM on virtual multi-device CPU mesh",
        "n_devices": ndev, "measured": True,
        "shape": f"N={n_sta} M={n_dir} F={F} B={B} tilesz={args.tilesz} "
                 f"-j{args.solver} -g 3 inner={args.inner} "
                 f"x{args.admm}it host-loop",
        "platform": "cpu-virtual-mesh",
        "compile_s": round(compile_s, 1),
        "cold_iter_s": [round(s, 3) for _, s in cold],
        "warm_iter0_s": round(dict(timer).get("iter0", float("nan")), 3),
        "warm_body_iter_s": [round(s, 3) for s in body_walls],
        "warm_body_iter_median_s": round(body_med, 3),
        "consensus_only_s": round(cons_s, 4),
        "consensus_share_pct": round(100.0 * cons_s / body_med, 2)
        if body_med == body_med else None,
        "warm_total_s": round(warm_total, 1),
        "res0": res0.round(5).tolist(), "res1": res1.round(5).tolist(),
        "r1_per_admm": r1s.round(5).tolist(),
        "residuals_falling_all_subbands": falling,
    }
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec))
    if not falling:
        print("WARNING: residuals not falling on all subbands")
        return 1
    return 0


def mesh2d(args):
    """ISSUE 14 tentpole evidence: the north-star ADMM shape on a
    VIRTUAL 2-D ``(freq, time)`` CPU mesh — subbands shard on the freq
    axis, solution intervals on the time axis, the whole observation
    ONE SPMD program (admm.make_admm_runner_2d). Banks a round-stamped
    ``MESH2D_rNN.json`` (bench.stamp_family; judged by the sentinel's
    MESH_TOLERANCES) holding, all measured:

    - per-ADMM-iteration wall on the warm mesh leg + the consensus
      half timed as its OWN 2-D mesh program (the collective-overhead
      fraction — MULTICHIP precedent, now with a time axis);
    - residual PARITY vs the sequential warm-start chain at the same
      shape/policy, gated AT BANK TIME: the time-shard-0 prefix must
      match tightly (same solve programs, no seam), the cold-seam
      intervals must stay within a stated ratio and keep falling — a
      failed gate refuses to write the record and exits non-zero;
    - the dtype policy ACTIVE on the sharded path (default bf16 —
      storage-dtype [B]-traffic through the mesh programs, no
      f32-fallback anywhere), with the bf16-vs-f32 residual drift of
      a matched mesh pair inside bench.DTYPE_DRIFT_ENVELOPE;
    - a bounded-staleness leg (admm.make_admm_runner_stale composed
      with the faults harness): one injected slow subband under
      ``--staleness`` S, banked NEXT TO its synchronous baseline with
      the per-subband convergence delta as numbers in the record.

    CPU wall-clock honesty: virtual devices share one host core, so
    the walls measure program structure + collective overhead, not
    compute scaling — the compute verdict awaits a TPU window (the
    full 64x100x32 defaults are wired for it; the CPU-banked shape is
    stated in the record, MULTICHIP r06 precedent)."""
    ndev = args.devices_f * args.devices_t
    import bench as _bench
    import jax
    from sagecal_tpu import faults, utils
    utils.setup_backend("cpu", ndev)
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from sagecal_tpu.consensus import admm as cadmm
    from sagecal_tpu.consensus import poly as cpoly
    from sagecal_tpu.io import dataset as ds
    from sagecal_tpu.rime import predict as rp
    from sagecal_tpu.solvers import lm as lm_mod, sage

    assert len(jax.devices()) >= ndev, jax.devices()
    n_sta, n_dir = args.stations, args.dirs
    F, T = args.subbands, args.intervals
    ndev_f, ndev_t = args.devices_f, args.devices_t
    if F % ndev_f or T % ndev_t:
        raise SystemExit(f"F={F} and T={T} must divide the "
                         f"{ndev_f}x{ndev_t} mesh")
    policy = args.dtype_policy
    sky = _northstar_sky(n_sta, n_dir)
    dsky = rp.sky_to_device(sky, jnp.float32)
    kmax = int(sky.nchunk.max())
    cmask = np.arange(kmax)[None, :] < sky.nchunk[:, None]
    Jbase = ds.random_jones(n_dir, sky.nchunk, n_sta, seed=6, scale=0.15)
    slope = (ds.random_jones(n_dir, sky.nchunk, n_sta, seed=7,
                             scale=0.04) - np.eye(2))
    freqs = 120e6 * (1 + 0.004 * np.arange(F))
    print(f"mesh2d: generating {F} subbands x {T} intervals "
          f"(N={n_sta} M={n_dir} tilesz={args.tilesz})", flush=True)
    tiles = []
    for f_i in range(F):
        Jf = Jbase + slope * (freqs[f_i] - 120e6) / 120e6
        tiles.append([ds.simulate_dataset(
            dsky, n_stations=n_sta, tilesz=args.tilesz,
            freqs=[freqs[f_i]], ra0=1.2, dec0=0.7, jones=Jf,
            nchunk=sky.nchunk, noise_sigma=0.02, seed=20 + f_i + 97 * t)
            for t in range(T)])
        if (f_i + 1) % 8 == 0:
            print(f"  subband {f_i + 1}/{F} generated", flush=True)
    tile = tiles[0][0]
    B = tile.nrows
    cidx = rp.chunk_indices(args.tilesz, tile.nbase, sky.nchunk)
    Bpoly_full = cpoly.setup_polynomials(freqs, float(freqs.mean()), 2, 2)

    def x8_of(t):
        xa = np.asarray(t.averaged())
        return np.stack([xa.reshape(-1, 4).real,
                         xa.reshape(-1, 4).imag], -1).reshape(-1, 8)

    def sd_np(pol):
        from sagecal_tpu import dtypes as dtp
        return dtp.storage_np(pol, np.float32)

    def inputs_ft(F_use, pol):
        """[F_use, T, ...] host inputs with the [B]-traffic staged in
        the policy storage dtype (the active-under-sharding melt)."""
        sd = sd_np(pol)
        x8 = np.stack([np.stack([x8_of(tiles[f][t]) for t in range(T)])
                       for f in range(F_use)]).astype(sd)
        u = np.stack([np.stack([tiles[f][t].u for t in range(T)])
                      for f in range(F_use)]).astype(np.float32)
        v = np.stack([np.stack([tiles[f][t].v for t in range(T)])
                      for f in range(F_use)]).astype(np.float32)
        w = np.stack([np.stack([tiles[f][t].w for t in range(T)])
                      for f in range(F_use)]).astype(np.float32)
        wt = np.stack([np.stack([np.asarray(lm_mod.make_weights(
            jnp.asarray(tiles[f][t].flags, jnp.int32), jnp.float32))
            for t in range(T)]) for f in range(F_use)]).astype(sd)
        fr = np.ones((F_use, T), np.float32)
        J0 = np.zeros((F_use, n_dir, kmax, n_sta, 8), np.float32)
        J0[..., 0] = 1.0
        J0[..., 6] = 1.0
        return x8, u, v, w, wt, fr, J0

    def cfg_for(pol, n_admm):
        return cadmm.ADMMConfig(
            n_admm=n_admm, npoly=2, rho=5.0, manifold_iters=5,
            sage=sage.SageConfig(
                max_emiter=1, max_iter=args.maxit, max_lbfgs=0,
                solver_mode=args.solver, nbase=tile.nbase,
                inner="chol" if args.inner == "both" else args.inner,
                kernel="xla" if args.kernel == "both" else args.kernel,
                dtype_policy=pol))

    partial = {}

    def checkpoint(tag, data):
        partial[tag] = data
        with open("/tmp/mesh2d_partial.json", "w") as f:
            json.dump(partial, f, indent=1, default=float)
        print(f"mesh2d: leg {tag} done", flush=True)

    def res_fin_of(out, n_admm):
        r1sT = np.asarray(out[5])               # [T, n_admm-1, F]
        return (r1sT[:, -1, :] if n_admm > 1
                else np.asarray(out[4]))        # [T, F]

    def mesh_leg(F_use, nf_f, pol, tag, warm: bool):
        mesh = Mesh(np.array(jax.devices()[:nf_f * ndev_t]).reshape(
            nf_f, ndev_t), ("freq", "time"))
        timer = []
        Bp = cpoly.setup_polynomials(freqs[:F_use],
                                     float(freqs[:F_use].mean()), 2, 2)
        runner = cadmm.make_admm_runner_2d(
            dsky, tile.sta1, tile.sta2, cidx, cmask, n_sta, tile.fdelta,
            Bp, cfg_for(pol, args.admm), mesh, F_use, T,
            nbase=tile.nbase, host_loop=True, timer=timer)
        ins = inputs_ft(F_use, pol)
        x8, u, v, w, wt, fr, J0 = ins
        t0 = time.time()
        out = runner(x8, u, v, w, freqs[:F_use], wt, fr, J0)
        cold_s = time.time() - t0
        cold_waves = [s for _, s in timer]
        print(f"mesh2d: leg {tag} cold run {cold_s:.1f}s "
              f"(waves {[round(s, 1) for s in cold_waves]})",
              flush=True)
        warm_waves = None
        if warm:
            timer.clear()
            t0 = time.time()
            out = runner(x8, u, v, w, freqs[:F_use], wt, fr, J0)
            warm_waves = [s for _, s in timer]
            print(f"mesh2d: leg {tag} warm run {time.time() - t0:.1f}s",
                  flush=True)
        rfin = res_fin_of(out, args.admm)
        res0 = np.asarray(out[3])
        falling = bool(np.all(np.isfinite(rfin))
                       and np.all(rfin < res0))
        leg = {"mesh": [nf_f, ndev_t], "policy": pol,
               "cold_total_s": round(cold_s, 1),
               "cold_wave_s": [round(s, 2) for s in cold_waves],
               "warm_wave_s": ([round(s, 2) for s in warm_waves]
                               if warm_waves else None),
               "res0": res0.round(6).tolist(),
               "res_fin": rfin.round(6).tolist(),
               "residuals_falling": falling}
        checkpoint(tag, leg)
        return runner, out, leg

    # ---- leg A: the headline 2-D mesh run, warm-timed, policy active
    runner_a, out_a, leg_a = mesh_leg(F, ndev_f, policy, "mesh", True)
    n_it = max(args.admm, 1)
    warm_wave = float(np.median(leg_a["warm_wave_s"]))
    wall_per_iter = warm_wave / n_it

    # ---- consensus-overhead probe: body_post as its own 2-D mesh
    # program on dummy carries (multichip precedent)
    Ppoly = Bpoly_full.shape[1]
    f32 = jnp.float32
    mesh_a = Mesh(np.array(jax.devices()[:ndev_f * ndev_t]).reshape(
        ndev_f, ndev_t), ("freq", "time"))
    sh_f = NamedSharding(mesh_a, P("freq"))
    sh_r = NamedSharding(mesh_a, P())
    mk = (F, n_dir, kmax, n_sta, 8)
    zshape = (n_dir, Ppoly, kmax, n_sta, 8)
    carry_shapes = [(mk, sh_f), (mk, sh_f), (zshape, sh_r),
                    ((F, n_dir), sh_f), (mk, sh_f), (mk, sh_f),
                    (zshape, sh_r), (zshape, sh_r), ((F, n_dir), sh_f)]
    carry0 = [jax.device_put(jnp.full(shp, 0.01, f32), s)
              for shp, s in carry_shapes]
    carry0[3] = jax.device_put(jnp.full((F, n_dir), 5.0, f32), sh_f)
    carry0[8] = carry0[3]
    Jr = jax.device_put(jnp.full(mk, 0.01, f32), sh_f)
    r0d = jax.device_put(jnp.zeros((F,), f32), sh_f)
    cons = runner_a.consensus_program
    it1 = jnp.asarray(1, jnp.int32)
    o = cons(Jr, r0d, r0d, *carry0, it1)
    jax.block_until_ready(o[0])
    cons_times = []
    for _ in range(max(args.reps, 2)):
        t0 = time.time()
        o = cons(Jr, r0d, r0d, *carry0, it1)
        jax.block_until_ready(o[0])
        cons_times.append(time.time() - t0)
    cons_s = float(np.median(cons_times))
    checkpoint("consensus", {"consensus_only_s": cons_s})

    # ---- leg B: the sequential warm-start chain at the SAME shape,
    # policy and per-device subband width (the parity reference)
    mesh_seq = Mesh(np.array(jax.devices()[:ndev_f]), ("freq",))
    runner_s = cadmm.make_admm_runner(
        dsky, tile.sta1, tile.sta2, cidx, cmask, n_sta, tile.fdelta,
        Bpoly_full, cfg_for(policy, args.admm), mesh_seq, F,
        host_loop=True, nbase=tile.nbase)
    x8, u, v, w, wt, fr, J0 = inputs_ft(F, policy)
    sh_seq = NamedSharding(mesh_seq, P("freq"))
    Jc = J0.copy()
    seq_fin = np.zeros((T, F))
    for t in range(T):
        argsd = [jax.device_put(jnp.asarray(a), sh_seq) for a in
                 (x8[:, t], u[:, t], v[:, t], w[:, t],
                  freqs.astype(np.float32), wt[:, t], fr[:, t], Jc)]
        o = runner_s(*argsd)
        Jf, r0, r1 = (np.asarray(o[0]), np.asarray(o[3]),
                      np.asarray(o[4]))
        r1s = np.asarray(o[5])
        rfin = r1s[-1] if args.admm > 1 else r1
        seq_fin[t] = rfin
        bad = (~np.isfinite(rfin)) | (rfin == 0) | (rfin > 5 * r0)
        Jc = np.where(bad[:, None, None, None, None], J0, Jf).astype(
            np.float32)
    checkpoint("seq", {"res_fin": seq_fin.round(6).tolist()})

    # ---- parity gate (AT BANK TIME). Two claims, separately gated:
    # (a) PREFIX parity — time-shard 0's interval block has no seam
    #     (identical warm chain), so the 2-D program must reproduce
    #     the sequential chain tightly there: same math, different
    #     execution plan;
    # (b) SEAM parity — the first interval of every later time shard
    #     is a COLD start by construction, so its converged residual
    #     is compared to the chain's own cold interval (interval 0),
    #     which is its like-for-like reference: a seam interval
    #     landing well off the cold level means the seam broke the
    #     solve, not just forwent the warm start. The warm-start
    #     advantage the seam gives up is REPORTED as its own number
    #     (seam_vs_warm_ratio), not gated — it is the measured price
    #     of time-parallelism at this iteration budget.
    mesh_fin = np.asarray(leg_a["res_fin"])     # [T, F]
    Tl = T // ndev_t
    prefix = slice(0, Tl)                       # time-shard 0 == chain
    prefix_rel = float(np.max(
        np.abs(mesh_fin[prefix] - seq_fin[prefix])
        / np.maximum(seq_fin[prefix], 1e-12)))
    # the cold seam is the FIRST interval of each later time shard
    # (intervals Tl, 2*Tl, ...); later intervals of those shards are
    # warm again within their block and are not gated
    seam = slice(Tl, None, Tl)
    seam_vs_warm = float(np.mean(
        mesh_fin[seam] / np.maximum(seq_fin[seam], 1e-12)))
    cold_ref = np.mean(seq_fin[0])              # the chain's own cold
    seam_vs_cold = float(np.mean(mesh_fin[seam]) / max(cold_ref,
                                                       1e-12))
    band = args.parity_seam_ratio
    parity_ok = (prefix_rel < args.parity_prefix_rel
                 and 1.0 / band <= seam_vs_cold <= band
                 and leg_a["residuals_falling"])
    checkpoint("parity", {"prefix_max_rel": prefix_rel,
                          "seam_vs_cold_ratio": seam_vs_cold,
                          "seam_vs_warm_ratio": seam_vs_warm,
                          "parity_ok": parity_ok})

    # ---- dtype drift: a matched mesh pair (bf16 vs f32) at a reduced
    # subband count — same program structure, only the storage dtype
    # differs; must sit inside the banked envelope
    drift = None
    if policy != "f32":
        Fd = min(F, args.drift_subbands)
        nf_d = max(1, min(ndev_f, Fd))
        while Fd % nf_d:
            nf_d -= 1
        _, out_f32, leg_f32 = mesh_leg(Fd, nf_d, "f32", "drift-f32",
                                       False)
        _, out_red, leg_red = mesh_leg(Fd, nf_d, policy,
                                       f"drift-{policy}", False)
        rf = np.asarray(leg_f32["res_fin"])
        rr_ = np.asarray(leg_red["res_fin"])
        envelope = _bench.DTYPE_DRIFT_ENVELOPE.get(policy, 0.25)
        drift = {"subbands": Fd, "policy": policy,
                 "rel_mean": float(np.mean(np.abs(rr_ - rf)
                                           / np.maximum(rf, 1e-12))),
                 "rel_max": float(np.max(np.abs(rr_ - rf)
                                         / np.maximum(rf, 1e-12))),
                 "envelope": envelope}
        drift["inside_envelope"] = bool(
            drift["rel_mean"] <= envelope)
        checkpoint("drift", drift)

    # ---- bounded-staleness experiment: sync baseline vs one injected
    # slow subband, SAME runner/programs, convergence delta in numbers
    Fs = min(F, args.stale_subbands)
    Bst = cpoly.setup_polynomials(freqs[:Fs],
                                  float(freqs[:Fs].mean()), 2, 2)
    cfg_st = cfg_for(policy, args.stale_admm)
    x8a, ua, va, wa, wta, fra, J0a = inputs_ft(Fs, policy)
    st_args = tuple(jnp.asarray(a) for a in
                    (x8a[:, 0], ua[:, 0], va[:, 0], wa[:, 0],
                     freqs[:Fs].astype(np.float32), wta[:, 0],
                     fra[:, 0], J0a))

    def stale_leg(plan):
        if plan:
            faults.enable(plan)
        try:
            run = cadmm.make_admm_runner_stale(
                dsky, tile.sta1, tile.sta2, cidx, cmask, n_sta,
                tile.fdelta, Bst, cfg_st, Fs,
                staleness=args.staleness, nbase=tile.nbase)
            t0 = time.time()
            out = run(*st_args)
            wall = time.time() - t0
            rfin = (np.asarray(out[5])[-1] if args.stale_admm > 1
                    else np.asarray(out[4]))
            return (rfin, np.asarray(out[3]), wall,
                    [m.tolist() for m in run.schedule[0]])
        finally:
            if plan:
                faults.disable()

    sync_fin, sync_r0, sync_wall, _ = stale_leg(None)
    slow_plan = [{"point": "admm_subband_slow",
                  "at": [args.slow_subband],
                  "times": args.slow_rounds}]
    stale_fin, stale_r0, stale_wall, sched = stale_leg(slow_plan)
    skipped = int(sum(1 - np.asarray(m)[args.slow_subband]
                      for m in sched))
    st_delta = np.abs(stale_fin - sync_fin) / np.maximum(sync_fin,
                                                         1e-12)
    stale_rec = {
        "shape": f"N={n_sta} M={n_dir} F={Fs} tilesz={args.tilesz} "
                 f"x{args.stale_admm}it interval0 {policy}",
        "staleness_S": args.staleness,
        "slow_subband": args.slow_subband,
        "slow_rounds_injected": args.slow_rounds,
        "skipped_solves": skipped,
        "schedule": sched,
        "sync_final_res": sync_fin.round(6).tolist(),
        "stale_final_res": stale_fin.round(6).tolist(),
        "convergence_delta_rel": st_delta.round(4).tolist(),
        "convergence_delta_rel_mean": float(st_delta.mean()),
        "convergence_delta_rel_slow_subband":
            float(st_delta[args.slow_subband]),
        "stale_still_falling": bool(
            np.all(np.isfinite(stale_fin))
            and np.all(stale_fin < stale_r0)),
        "sync_wall_s": round(sync_wall, 1),
        "stale_wall_s": round(stale_wall, 1),
    }
    checkpoint("staleness", stale_rec)

    rec = {
        "metric": "north-star ADMM on virtual 2-D (freq x time) mesh",
        "measured": True,
        "shape": f"N={n_sta} M={n_dir} F={F} T={T} B={B} "
                 f"tilesz={args.tilesz} mesh={ndev_f}x{ndev_t} "
                 f"-j{args.solver} -g {args.maxit} x{args.admm}it "
                 f"{policy} wavefront",
        "platform_detail": "cpu-virtual-mesh (one host core: walls "
                           "measure program structure + collective "
                           "overhead, not compute scaling; TPU "
                           "verdict awaits a chip window)",
        "n_devices": ndev_f * ndev_t,
        "mesh_devices": [ndev_f, ndev_t],
        "dtype_policy": policy,
        "f32_fallback": False,
        "compile_plus_cold_total_s": leg_a["cold_total_s"],
        "cold_wave_s": leg_a["cold_wave_s"],
        "warm_wave_s": leg_a["warm_wave_s"],
        "wall_per_admm_iter_s": round(wall_per_iter, 3),
        "consensus_only_s": round(cons_s, 4),
        "collective_overhead_frac": round(cons_s / wall_per_iter, 6),
        "res0": leg_a["res0"],
        "res_fin": leg_a["res_fin"],
        "residuals_falling_all_subbands": leg_a["residuals_falling"],
        "seq_res_fin": seq_fin.round(6).tolist(),
        "parity": {"vs": "sequential warm-start chain, same "
                         "shape/policy/subband-width",
                   "prefix_intervals": Tl,
                   "prefix_max_rel": round(prefix_rel, 6),
                   "prefix_gate": args.parity_prefix_rel,
                   "seam_vs_cold_ratio": round(seam_vs_cold, 4),
                   "seam_gate_band": args.parity_seam_ratio,
                   "seam_vs_warm_ratio": round(seam_vs_warm, 4)},
        "parity_ok": 1 if parity_ok else 0,
        "dtype_drift": drift,
        "staleness": stale_rec,
    }
    if not parity_ok:
        print("mesh2d: PARITY GATE FAILED — record NOT banked:\n"
              + json.dumps(rec["parity"], indent=1), file=sys.stderr)
        with open("/tmp/mesh2d_FAILED.json", "w") as f:
            json.dump(rec, f, indent=1, default=float)
        return 1
    path = _bench.stamp_family(rec, "cpu", "MESH2D",
                               "10-mesh2d-northstar", first_round=13,
                               bank_dir=getattr(args, "bank_dir", None))
    print(f"mesh2d: banked {os.path.basename(path)}")
    print(json.dumps(rec))
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--block-f", type=int, default=1,
                    help="subbands per solve execution (measured best: "
                         "1 — PERF.md north-star landscape)")
    ap.add_argument("--admm", type=int, default=3)
    ap.add_argument("--stations", type=int, default=64)
    ap.add_argument("--dirs", type=int, default=100)
    ap.add_argument("--subbands", type=int, default=32)
    ap.add_argument("--tilesz", type=int, default=4)
    ap.add_argument("--tiles", type=int, default=2)
    ap.add_argument("--solver", type=int, default=5)
    ap.add_argument("--inflight", type=int, default=1,
                    help="clusters in flight per SAGE sweep step")
    ap.add_argument("--keep", default=None,
                    help="reuse/keep the dataset directory")
    ap.add_argument("--b-scaling", action="store_true",
                    help="run the B/B2/B4 sweep-cost ladder instead of "
                         "the full ADMM run (writes BSCALING.json, or "
                         "BSCALING_r07.json with --inner both)")
    ap.add_argument("--inner", choices=("chol", "cg", "both"),
                    default="chol",
                    help="inner linear solver (sage.SageConfig.inner); "
                         "'both' runs the --b-scaling ladder under each "
                         "and banks the comparison")
    ap.add_argument("--kernel", choices=("xla", "pallas", "both"),
                    default=os.environ.get("SAGECAL_BENCH_KERNEL",
                                           "xla"),
                    help="row-pass kernel (sage.SageConfig.kernel; "
                         "ops/sweep_pallas.py fused sweep); 'both' "
                         "runs the --b-scaling ladder kernel-on/off "
                         "and banks BSCALING_r17.json; defaults to "
                         "SAGECAL_BENCH_KERNEL when set")
    ap.add_argument("--jones", choices=("full", "diag", "phase"),
                    default="full",
                    help="Jones parameterization for the --b-scaling "
                         "ladder (sage.SageConfig.jones_mode; round "
                         "20): constrained modes solve/factor reduced "
                         "Gram blocks (diag 4x4, phase 2x2 vs full "
                         "8x8 real)")
    ap.add_argument("--multichip", action="store_true",
                    help="run the ADMM shape on a virtual multi-device "
                         "CPU mesh and bank a measured per-iteration + "
                         "collective-overhead record (MULTICHIP_rNN)")
    ap.add_argument("--devices", type=int, default=8,
                    help="virtual device count for --multichip")
    ap.add_argument("--mesh2d", action="store_true",
                    help="run the ADMM shape on a virtual 2-D "
                         "(freq x time) mesh: warm-timed wavefronts, "
                         "consensus-overhead probe, sequential-chain "
                         "parity gate, dtype-drift pair and the "
                         "bounded-staleness experiment; banks "
                         "MESH2D_rNN.json (ISSUE 14)")
    ap.add_argument("--devices-f", type=int, default=8,
                    help="freq-axis device count for --mesh2d")
    ap.add_argument("--devices-t", type=int, default=2,
                    help="time-axis device count for --mesh2d")
    ap.add_argument("--intervals", type=int, default=2,
                    help="solution intervals (time-axis extent) for "
                         "--mesh2d")
    ap.add_argument("--maxit", type=int, default=2,
                    help="solver max_iter (-g) for --mesh2d")
    ap.add_argument("--dtype-policy", choices=("f32", "bf16", "f16"),
                    default="bf16",
                    help="--mesh2d storage dtype policy (bf16 default: "
                         "the melt must be ACTIVE under sharding)")
    ap.add_argument("--drift-subbands", type=int, default=8,
                    help="subband count of the --mesh2d bf16-vs-f32 "
                         "drift pair")
    ap.add_argument("--parity-prefix-rel", type=float, default=2e-2,
                    help="--mesh2d bank gate: max rel final-residual "
                         "diff vs the sequential chain on the "
                         "time-shard-0 prefix (no seam there)")
    ap.add_argument("--parity-seam-ratio", type=float, default=1.5,
                    help="--mesh2d bank gate: band (ratio and its "
                         "inverse) the cold-seam intervals' mean "
                         "residual must sit in vs the chain's own "
                         "COLD interval level (like-for-like); the "
                         "forgone warm-start advantage is reported, "
                         "not gated")
    ap.add_argument("--staleness", type=int, default=2,
                    help="--mesh2d bounded-staleness S")
    ap.add_argument("--stale-subbands", type=int, default=8,
                    help="subband count of the --mesh2d staleness legs")
    ap.add_argument("--stale-admm", type=int, default=4,
                    help="ADMM iterations of the staleness legs")
    ap.add_argument("--slow-subband", type=int, default=1,
                    help="subband the admm_subband_slow fault targets")
    ap.add_argument("--slow-rounds", type=int, default=2,
                    help="rounds the injected slow subband straggles")
    ap.add_argument("--reps", type=int, default=3,
                    help="warm sweep timings per shape (--b-scaling)")
    ap.add_argument("--bank-dir", default=None,
                    help="write banked records (BSCALING*/MESH2D_rNN) "
                         "here instead of tools_dev/ — the burn-down "
                         "--dry-run's scratch-bank mode; committed "
                         "records are never touched when set")
    args = ap.parse_args()
    if args.inner == "both" and not args.b_scaling:
        # "both" is the --b-scaling comparison mode only; silently
        # coercing it to chol would bank a record indistinguishable
        # from an intentional chol run
        ap.error("--inner both requires --b-scaling "
                 "(--multichip and the full ADMM run take chol|cg)")
    if args.kernel not in ("xla", "pallas", "both"):
        # the default may come from SAGECAL_BENCH_KERNEL, which
        # argparse choices do not validate
        ap.error(f"--kernel {args.kernel}: pick xla|pallas|both")
    if args.kernel == "both" and not args.b_scaling:
        ap.error("--kernel both requires --b-scaling (the full runs "
                 "take xla|pallas)")
    if args.b_scaling:
        return b_scaling(args)
    if args.multichip:
        return multichip(args)
    if args.mesh2d:
        return mesh2d(args)

    # this process only synthesizes data: it stays on the CPU platform,
    # because the chip belongs to the one cli_mpi child started below
    from sagecal_tpu import utils
    utils.setup_backend("cpu")
    workdir = args.keep or tempfile.mkdtemp(prefix="northstar_")
    os.makedirs(workdir, exist_ok=True)
    if os.path.exists(os.path.join(workdir, "mslist.txt")):
        skyp = os.path.join(workdir, "northstar.sky.txt")
        clup = skyp + ".cluster"
        lst = os.path.join(workdir, "mslist.txt")
        print(f"reusing datasets in {workdir}")
    else:
        print(f"generating {args.subbands} subbands in {workdir} ...")
        skyp, clup, lst = generate(workdir, args.stations, args.dirs,
                                   args.subbands, args.tilesz, args.tiles)

    cmd = [sys.executable, "-m", "sagecal_tpu.cli_mpi",
           "-f", lst, "-s", skyp, "-c", clup,
           "-A", str(args.admm), "-P", "2", "-Q", "2", "-r", "5",
           "-j", str(args.solver), "-e", "1", "-g", "3", "-l", "0",
           "-t", str(args.tilesz), "-V",
           "--block-f", str(args.block_f),
           "--inflight", str(args.inflight),
           "--inner", args.inner, "--kernel", args.kernel]
    # the child picks its own backend and persistent compile cache
    # (utils.setup_backend): re-runs, and the second tile's programs,
    # skip the big solve compiles
    if args.cpu:
        cmd += ["--platform", "cpu", "--cpu-devices", "1"]
    print("running:", " ".join(cmd), flush=True)
    t0 = time.time()
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    per_tile_iters = []
    residuals = []          # (initial, final) mean residual per tile —
    # the G=1 vs --inflight parity evidence (VERDICT r5 item 2)
    platform = "cpu" if args.cpu else "unknown"
    for line in proc.stdout:
        print(line, end="", flush=True)
        pm = re.match(r"Platform: (\w+)", line)
        if pm:
            platform = pm.group(1)   # provenance from the actual backend
        m = re.match(r"ADMM wall-clock/iter: (.*) \(blocks", line)
        if m:
            per_tile_iters.append(
                [float(x[:-1]) for x in m.group(1).split()])
        rm = re.match(r"Timeslot:\d+ ADMM:\d+ residual "
                      r"initial=(\S+) final=(\S+)", line)
        if rm:
            # float() handles nan/inf too — divergence is exactly the
            # evidence the parity record must not drop
            residuals.append([float(rm.group(1)), float(rm.group(2))])
    rc = proc.wait()
    wall = time.time() - t0
    if rc != 0:
        print(f"FAILED rc={rc} after {wall:.0f}s")
        return rc

    # warm numbers: the LAST tile's iterations exclude compilation
    warm = per_tile_iters[-1] if per_tile_iters else []
    # within the tile, iteration 0 (plain solve + manifold) and the
    # body iterations are distinct programs; report the body median
    body = warm[1:] if len(warm) > 1 else warm
    per_iter = float(np.median(body)) if body else float("nan")
    itag = "" if args.inner in ("chol", "both") else f" inner={args.inner}"
    shape = (f"N={args.stations} M={args.dirs} F={args.subbands} "
             f"hybrid-chunks tilesz={args.tilesz} -j{args.solver} "
             f"block_f={args.block_f} G={args.inflight}{itag}")
    rec = {"metric": "ADMM wall-clock/iter (north-star shape)",
           "value": round(per_iter, 3), "unit": "s/ADMM-iter",
           "shape": shape, "per_tile_iters": per_tile_iters,
           "residuals": residuals, "inflight": args.inflight,
           "total_wall_s": round(wall, 1), "platform": platform}
    with open(os.path.join(HERE, "NORTHSTAR.json"), "w") as f:
        json.dump(rec, f, indent=1)
    # ONE row formatter: bench.write_table re-emits the northstar row
    # from NORTHSTAR.json; regenerate the table through it so the two
    # writers can never drift
    try:
        sys.path.insert(0, HERE)
        import bench
        with open(os.path.join(HERE, "bench_results.json")) as f:
            br = json.load(f)
        bench.write_table(br["results"], br["platform"],
                          date=br.get("date"))
    except Exception as e:
        print(f"table regeneration skipped ({e}); NORTHSTAR.json written")
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
