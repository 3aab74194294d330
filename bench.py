#!/usr/bin/env python
"""Benchmark: the five BASELINE.json configs on one chip.

Prints ONE JSON line on stdout:

  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

The headline value is config 1 (the ``test/Calibration`` smoke shape:
fullbatch SAGE calibration, vis/s/chip). All five configs are timed and the
full table is written to ``BENCH_TABLE.md`` + ``bench_results.json`` next to
this file; per-config details also go to stderr so a failing config never
corrupts the stdout contract.

A run is on the chip, or on the CPU because ``--cpu`` asked for it: with no
chip and no ``--cpu`` the bench exits non-zero before any config runs, and a
config that fails on the chip is recorded as failed, never re-run on the
CPU. This process stays off JAX — the chip belongs to one process at a
time — and every config runs in a child of its own, one after another.

``vs_baseline``: if ``ref_baseline.json`` exists (reference libdirac CPU
timing, see tools/ref_bench/), the ratio is config 1 over that reference;
otherwise it is reported as 1.
"""

import atexit
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 17
PLATFORM_SRC = "import jax; print('PLATFORM=' + jax.devices()[0].platform)"


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def device_platform(timeout_s: int = 120) -> str | None:
    """The platform JAX picks here, asked in a child that exits before
    any config starts; None when the child fails or does not answer."""
    try:
        r = subprocess.run([sys.executable, "-c", PLATFORM_SRC],
                           capture_output=True, text=True,
                           timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return None
    m = [ln for ln in (r.stdout or "").splitlines()
         if ln.startswith("PLATFORM=")]
    return m[-1][len("PLATFORM="):] if r.returncode == 0 and m else None


# ---------------------------------------------------------------------------
# problem builders
# ---------------------------------------------------------------------------

def _point(name, ll, mm, flux, f0=150e6, si=0.0, si1=0.0, si2=0.0):
    from sagecal_tpu import skymodel
    nn = np.sqrt(max(1 - ll * ll - mm * mm, 0.0))
    return skymodel.Source(
        name=name, ra=0, dec=0, ll=ll, mm=mm, nn=nn - 1, sI=flux,
        sQ=0.0, sU=0.0, sV=0.0, sI0=flux, sQ0=0, sU0=0, sV0=0,
        spec_idx=si, spec_idx1=si1, spec_idx2=si2, f0=f0)


def make_sky(n_clusters, srcs_per_cluster=3, seed=SEED, extended=False,
             spectra3=False):
    """Build an in-memory ClusterSky; optionally with Gaussian + shapelet
    extended sources and 3rd-order spectra (BASELINE config 4)."""
    from sagecal_tpu import skymodel
    rng = np.random.default_rng(seed)
    srcs, clusters = {}, []
    for m in range(n_clusters):
        names = []
        for s in range(srcs_per_cluster):
            nm = f"P{m}_{s}"
            ll, mm = rng.normal(0, 0.03, 2)
            flux = float(1 + 2 * rng.random())
            si = si1 = si2 = 0.0
            if spectra3:
                si = float(rng.normal(-0.7, 0.1))
                si1 = float(rng.normal(0, 0.05))
                si2 = float(rng.normal(0, 0.02))
            src = _point(nm, ll, mm, flux, si=si, si1=si1, si2=si2)
            if extended and s == 0:
                # Gaussian component (readsky.c:405-413 semantics)
                src.stype = skymodel.STYPE_GAUSSIAN
                src.eX = 2 * 0.002
                src.eY = 2 * 0.001
                src.eP = float(rng.random())
            if extended and s == 1:
                # shapelet with a 3x3 synthetic mode set
                src.stype = skymodel.STYPE_SHAPELET
                src.eX = src.eY = 1.0
                src.sh_n0 = 3
                src.sh_beta = 0.01
                src.sh_modes = rng.normal(0, 0.4, 9)
                src.sh_modes[0] = 1.0
            names.append(nm)
            srcs[nm] = src
        clusters.append((m, 1, names))
    return skymodel.build_cluster_sky(srcs, clusters)


def build_fullbatch(dtype, n_stations, n_clusters, tilesz, extended=False,
                    spectra3=False, nchan=1, seed=SEED, n_tiles=1):
    """Returns (sky, dsky, tiles): ``n_tiles`` independent solve intervals
    of the same observation (tile 0 is the historical single-tile shape,
    so residual figures stay comparable across rounds)."""
    import jax.numpy as jnp
    from sagecal_tpu.io import dataset as ds
    from sagecal_tpu.rime import predict as rp

    sky = make_sky(n_clusters, extended=extended, spectra3=spectra3,
                   seed=seed)
    dsky = rp.sky_to_device(sky, dtype)
    Jtrue = ds.random_jones(n_clusters, sky.nchunk, n_stations,
                            seed=seed + 1, scale=0.2)
    f0 = 150e6
    freqs = f0 + 0.2e6 * np.arange(nchan)
    tiles = [ds.simulate_dataset(dsky, n_stations=n_stations, tilesz=tilesz,
                                 freqs=freqs, ra0=0.1, dec0=0.9,
                                 jones=Jtrue, nchunk=sky.nchunk,
                                 noise_sigma=0.01, seed=seed + 2 + 1000 * t)
             for t in range(n_tiles)]
    return sky, dsky, tiles


def _sage_inputs(sky, tiles, dtype, device):
    """Device inputs for a batched multi-tile solve; arrays that differ
    per tile carry a leading [T] axis, shared geometry does not."""
    import jax
    import jax.numpy as jnp
    from sagecal_tpu import utils
    from sagecal_tpu.rime import predict as rp
    from sagecal_tpu.solvers import lm as lm_mod

    tile = tiles[0]
    T = len(tiles)
    kmax = int(sky.nchunk.max())
    n = tile.n_stations
    cidx = rp.chunk_indices(tile.tilesz, tile.nbase, sky.nchunk)
    cmask = np.arange(kmax)[None, :] < sky.nchunk[:, None]

    def x8_of(t):
        xa = t.averaged()
        return np.stack([xa.reshape(-1, 4).real, xa.reshape(-1, 4).imag],
                        -1).reshape(-1, 8)
    x8 = np.stack([x8_of(t) for t in tiles])
    J0 = np.tile(np.eye(2, dtype=complex),
                 (T, sky.n_clusters, kmax, n, 1, 1))
    put = lambda a, dt: jax.device_put(jnp.asarray(a, dt), device)
    wt = jnp.stack([lm_mod.make_weights(put(t.flags, jnp.int32), dtype)
                    for t in tiles])
    return dict(
        x8=put(x8, dtype),
        u=put(np.stack([t.u for t in tiles]), dtype),
        v=put(np.stack([t.v for t in tiles]), dtype),
        w=put(np.stack([t.w for t in tiles]), dtype),
        s1=put(tile.sta1, jnp.int32),
        s2=put(tile.sta2, jnp.int32), wt=wt,
        # Jones cross the boundary as [.., 8] reals (only real arrays
        # cross host<->device, sagecal_tpu/utils.py)
        J0=put(utils.jones_c2r_np(J0), dtype),
        cidx=put(cidx, jnp.int32), cmask=put(cmask, bool),
        freq=put([tile.freq0], dtype), kmax=kmax)


# device peak tables live in sagecal_tpu.diag.roofline (bf16 FLOP/s +
# HBM bytes/s per device kind); imported lazily so the parent bench
# driver process stays jax-free (only --config children touch jax)


def _rl():
    from sagecal_tpu.diag import roofline
    return roofline


def peak_flops(device):
    return _rl().peak_flops(device)


def _cost(jfn, args, kwargs):
    """{"flops", "bytes_accessed"} of one compiled program via XLA cost
    analysis (diag.roofline). Loop bodies are counted ONCE (measured: a
    10-trip fori_loop prices like a single trip), so per-program figures
    are lower bounds; the dynamic-trip correction happens in
    :func:`time_sage` via the solvers' executed-iteration counters
    (info["solver_iters"] / info["lbfgs_iters"]) x
    :func:`solver_trip_cost`."""
    return _rl().program_cost(jfn, args, kwargs)


def _lower_cost(fn, *specs):
    """Price ``fn`` at abstract shapes (jax.ShapeDtypeStruct) — lowering
    + cost analysis only, nothing executes."""
    return _rl().lower_cost(fn, *specs)


# -------------------------------------------------------------------------
# MFU trip accounting (VERDICT r4 item 3)
#
# XLA cost analysis prices while_loop bodies once regardless of trip
# count, so summing program costs undercounts solver FLOPs by orders of
# magnitude (solvers spend hundreds of damping/tCG/linesearch iterations
# inside loops). The fix has two halves:
#   1. the solvers return their EXECUTED iteration counts
#      (lm.py/rtr.py "iters" -> sage info["solver_iters"], and the
#      joint-refine LBFGS count in info["lbfgs_iters"]);
#   2. ONE iteration of each solver family is priced here by lowering
#      the actual component functions (damped-Cholesky solve, normal-eq
#      assembly, cost/grad, tCG Hessian-vector product) at the solve
#      shapes, and total_flops += trips x per_trip.
# Known slack, all documented lower-bound-leaning: line-search cost
# evaluations beyond 1/iteration are uncounted, robust E-step weight
# updates are priced once per program (not per IRLS round), and the one
# body trip already inside each program cost is not subtracted (<1% at
# realistic trip counts).
# -------------------------------------------------------------------------

_TRIP_CACHE: dict = {}


def solver_trip_flops(solver_mode, kmax, n_stations, B, dtype):
    """FLOPs of ONE inner solver iteration (back-compat scalar wrapper
    around :func:`solver_trip_cost`)."""
    c = solver_trip_cost(solver_mode, kmax, n_stations, B, dtype)
    return None if c is None else c["flops"]


def _bytes_baseline(platform: str):
    """Per-config ``bytes_accessed`` from the newest round-stamped bench
    record of this ``platform`` committed next to this file (the bank
    the tentpole's traffic claims measure against); {} when no banked
    record carries the roofline fields yet.

    ``bench_results.json`` is consulted ONLY when no round-stamped
    record exists (first-round bootstrap): every live run overwrites
    it, so treating it as the newest bank would let a discarded
    mis-measured run shadow the committed record and poison the next
    run's Δbytes column (observed round 7: a rejected trial run left
    its inflated figures there)."""
    import glob
    import re as _re
    best, best_r = {}, -1
    pat = os.path.join(HERE, f"BENCH_{platform.upper()}_r*.json")
    stamped = sorted(glob.glob(pat))
    for p in stamped or [os.path.join(HERE, "bench_results.json")]:
        try:
            with open(p) as f:
                d = json.load(f)
        except Exception:
            continue
        res = d.get("results", {})
        if d.get("platform") != platform:
            continue
        per = {k: v.get("bytes_accessed") for k, v in res.items()
               if isinstance(v, dict) and v.get("bytes_accessed")}
        if not per:
            continue
        m = _re.search(r"_r(\d+)\.json$", p)
        rnd = int(m.group(1)) if m else 10**6   # live file: newest
        if rnd > best_r:
            best, best_r = per, rnd
    return best


def refine_trip_flops(M, kmax, n_stations, B, robust, dtype):
    """FLOPs of ONE joint-refine LBFGS iteration (back-compat scalar
    wrapper around :func:`refine_trip_cost`)."""
    c = refine_trip_cost(M, kmax, n_stations, B, robust, dtype)
    return None if c is None else c["flops"]


def solver_trip_cost(solver_mode, kmax, n_stations, B, dtype, nbase=0,
                     inner="chol", kernel="xla", jones="full"):
    """FLOPs + bytes accessed of ONE inner solver iteration at the
    per-cluster solve shape.

    LM families (modes 0-3): one damped Gauss-Newton trip = batched
    Cholesky solve of (JTJ + mu I) dp = JTe over [K, 8N, 8N] plus ONE
    normal-equation + acceptance-cost pass at the trial point — the
    restructured lm.py body's single row traversal (rounds <= PR 1
    additionally priced a separate full-data cost evaluation, which the
    body no longer performs). Under ``inner="cg"`` the damping trip's
    fixed part is priced instead: the matrix-free gn_factors pass +
    station-block preconditioner factorization + initial apply — the
    PCG loop body itself is priced per EXECUTED trip by
    :func:`cg_trip_cost` x info["cg_iters"] (roofline.trip_correct).
    RTR families (modes 4-5): one outer TR trip = Gauss-Newton assembly
    + cost + projected gradient, plus tcg_iters Hessian-vector products
    ([K,8N,8N]@[K,8N] matvec + tangent projection each, rtr.py _tcg;
    under inner="cg" the product is the matrix-free gn_matvec and the
    assembly is gn_factors). NOTE: tcg_iters is the CAP of _tcg's loop,
    not the count it executes — the loop ends when every chunk has
    stopped (a tenth of the cap at the benchmark's shapes) and the
    executed bodies are info["cg_iters"]; this price still charges the
    cap a trip and so overstates an RTR solve's work.
    NSD (mode 6): one Nesterov step = projected gradient + the static
    ls_tries backtracking cost evaluations (rtr.py nsd_solve_robust) —
    no Cholesky/assembly, which the LM price would wrongly charge.
    ``nbase``: the rows' baseline period, forwarded to the assembly so
    the priced program IS the solvers' (normal_eq row_period path).
    ``kernel``: "pallas" prices the fused-sweep bodies the solvers
    execute under SageConfig.kernel="pallas" (ops/sweep_pallas.py) —
    assembly via the fused kernel and, under inner="cg", tCG/PCG
    products on the B-independent per-baseline blocks. A
    Mosaic-compiled pallas_call is invisible to XLA cost analysis, so
    roofline.program_cost folds in the kernel's own cost_estimate
    (roofline.pallas_cost); interpret-mode (CPU) lowerings price
    through cost_analysis directly.
    ``jones``: the Jones parameterization (SageConfig.jones_mode,
    round 20) — constrained modes price the REDUCED bodies the solvers
    execute (mdim-wide Gram blocks, [K, npar N, npar N] damped solves,
    npar = 4 diag / 2 phase vs 8 full), so equal-executed-trip
    comparisons measure the true per-trip byte melt. ``jones="full"``
    prices the exact pre-mode bodies (byte-frozen).
    """
    key = (int(solver_mode), kmax, n_stations, B, str(dtype), int(nbase),
           str(inner), str(kernel), str(jones))
    if key in _TRIP_CACHE:
        return _TRIP_CACHE[key]
    import jax
    import jax.numpy as jnp
    from sagecal_tpu import dtypes as dtp
    from sagecal_tpu.config import SolverMode
    from sagecal_tpu.solvers import lm as lm_mod
    from sagecal_tpu.solvers import normal_eq as ne
    from sagecal_tpu.solvers import rtr as rtr_mod
    K, N = kmax, n_stations
    jm = str(jones)
    md = ne.jones_mdim(jm)
    P = 2 * md * N
    # ``dtype`` may be a reduced STORAGE dtype (SAGECAL_BENCH_DTYPE /
    # config 7): data specs carry it, solver-state specs carry the
    # accumulator dtype, and the priced bodies are the reduced ones
    # (normal_equations dispatches on the spec dtype; the damped solve
    # routes through the LU body the reduced lm path executes)
    f = dtype
    fa = dtp.acc_dtype(dtype)
    reduced = dtp.is_reduced(dtype)
    c = jnp.complex64 if fa == jnp.float32 else jnp.complex128
    i = jnp.int32
    S = jax.ShapeDtypeStruct
    x8, coh = S((B, 8), f), S((B, 2, 2), c)
    s1, s2, cid = S((B,), i), S((B,), i), S((B,), i)
    wt, p = S((B, 8), f), S((K, P), fa)
    # amplitude/reference Jones the constrained modes retract against
    # (jones_from_params Jref); unused for jm == "full"
    Jrf = S((K, N, 2, 2), c)
    use_pk = False
    if kernel == "pallas":
        from sagecal_tpu.ops import sweep_pallas as swp
        use_pk = swp.supported(K, int(nbase), B)
    nb_ = int(nbase)
    try:
        if int(solver_mode) in (int(SolverMode.RTR_OSLM_LBFGS),
                                int(SolverMode.RTR_OSRLM_RLBFGS)):
            # mode 4 runs the Gaussian objective (rtr_solve robust_nu
            # =None); only mode 5 pays the Student's-t log1p per element
            rnu = (2.0 if int(solver_mode)
                   == int(SolverMode.RTR_OSRLM_RLBFGS) else None)

            if inner == "cg" and use_pk and jm != "full":
                # reduced fused-sweep assembly + mdim blocks products
                def outer(p, Jr, x8, coh, s1, s2, cid, wt):
                    J = ne.jones_from_params(
                        p.reshape(K, N, 2 * md), jm, Jr)
                    cfn = rtr_mod.make_cost(x8, coh, s1, s2, cid, wt,
                                            K, N, robust_nu=rnu,
                                            mode=jm, Jref=Jr)
                    g = jax.grad(lambda q: jnp.sum(cfn(q)))(p)
                    g = rtr_mod.project_tangent_mode(p, g, K, N, jm)
                    fac, _, _ = swp.gn_blocks(x8, J, coh, s1, s2, cid,
                                              wt, N, K, nb_, jones=jm)
                    return g, fac, cfn(p)

                def hv(p, pp, qq, pq, D, v, s1, s2):
                    fac = swp.GNBlocks(pp=pp, qq=qq, pq=pq, D=D)
                    Hv = 2.0 * swp.gn_matvec_blocks(fac, v, s1, s2, N)
                    return rtr_mod.project_tangent_mode(p, Hv, K, N, jm)

                trip = _rl().combine(
                    _lower_cost(outer, p, Jrf, x8, coh, s1, s2, cid, wt),
                    _rl().scale(
                        _lower_cost(hv, p, S((K, nb_, 2, md, md), fa),
                                    S((K, nb_, 2, md, md), fa),
                                    S((K, nb_, 2, 2, md, md), fa),
                                    S((K, N, 2, md, md), fa), p, s1, s2),
                        rtr_mod.RTRConfig().tcg_iters))
            elif inner == "cg" and use_pk:
                # fused-sweep assembly + B-independent blocks products
                # (the bodies rtr.make_hess executes at kernel="pallas")
                def outer(p, x8, coh, s1, s2, cid, wt):
                    J = ne.jones_r2c(p.reshape(K, N, 8))
                    cfn = rtr_mod.make_cost(x8, coh, s1, s2, cid, wt,
                                            K, N, robust_nu=rnu)
                    g = jax.grad(lambda q: jnp.sum(cfn(q)))(p)
                    g = rtr_mod.project_tangent(p, g, K, N)
                    fac, _, _ = swp.gn_blocks(x8, J, coh, s1, s2, cid,
                                              wt, N, K, nb_)
                    return g, fac, cfn(p)

                def hv(p, pp, qq, pq, D, v, s1, s2):
                    fac = swp.GNBlocks(pp=pp, qq=qq, pq=pq, D=D)
                    Hv = 2.0 * swp.gn_matvec_blocks(fac, v, s1, s2, N)
                    return rtr_mod.project_tangent(p, Hv, K, N)

                trip = _rl().combine(
                    _lower_cost(outer, p, x8, coh, s1, s2, cid, wt),
                    _rl().scale(
                        _lower_cost(hv, p, S((K, nb_, 2, 4, 4), fa),
                                    S((K, nb_, 2, 4, 4), fa),
                                    S((K, nb_, 2, 2, 4, 4), fa),
                                    S((K, N, 2, 4, 4), fa), p, s1, s2),
                        rtr_mod.RTRConfig().tcg_iters))
            elif inner == "cg" and jm != "full":
                # matrix-free trip on the reduced mode factors
                def outer(p, Jr, x8, coh, s1, s2, cid, wt):
                    J = ne.jones_from_params(
                        p.reshape(K, N, 2 * md), jm, Jr)
                    cfn = rtr_mod.make_cost(x8, coh, s1, s2, cid, wt,
                                            K, N, robust_nu=rnu,
                                            mode=jm, Jref=Jr)
                    g = jax.grad(lambda q: jnp.sum(cfn(q)))(p)
                    g = rtr_mod.project_tangent_mode(p, g, K, N, jm)
                    fac, _, _ = ne.gn_factors_mode(x8, J, coh, s1, s2,
                                                   cid, wt, N, K,
                                                   mode=jm,
                                                   row_period=int(nbase))
                    return g, fac, cfn(p)

                def hv(p, FA, FB, w2, D, v, s1, s2, cid):
                    fac = ne.GNFactorsMode(FA=FA, FB=FB, w2=w2, D=D)
                    Hv = 2.0 * ne.gn_matvec_mode(fac, v, s1, s2, cid,
                                                 K, N)
                    return rtr_mod.project_tangent_mode(p, Hv, K, N, jm)

                trip = _rl().combine(
                    _lower_cost(outer, p, Jrf, x8, coh, s1, s2, cid, wt),
                    _rl().scale(
                        _lower_cost(hv, p, S((B, 2, 2, 2, md), f),
                                    S((B, 2, 2, 2, md), f),
                                    S((B, 2, 2, 2), f),
                                    S((K, N, 2, md, md), fa), p,
                                    s1, s2, cid),
                        rtr_mod.RTRConfig().tcg_iters))
            elif inner == "cg":
                def outer(p, x8, coh, s1, s2, cid, wt):
                    J = ne.jones_r2c(p.reshape(K, N, 8))
                    cfn = rtr_mod.make_cost(x8, coh, s1, s2, cid, wt,
                                            K, N, robust_nu=rnu)
                    g = jax.grad(lambda q: jnp.sum(cfn(q)))(p)
                    g = rtr_mod.project_tangent(p, g, K, N)
                    fac, _, _ = ne.gn_factors(x8, J, coh, s1, s2, cid,
                                              wt, N, K,
                                              row_period=int(nbase))
                    return g, fac, cfn(p)

                def hv(p, MA, MB, w2, D, v, s1, s2, cid):
                    fac = ne.GNFactors(MA=MA, MB=MB, w2=w2, D=D)
                    Hv = 2.0 * ne.gn_matvec(fac, v, s1, s2, cid, K,
                                            N, row_period=int(nbase))
                    return rtr_mod.project_tangent(p, Hv, K, N)

                trip = _rl().combine(
                    _lower_cost(outer, p, x8, coh, s1, s2, cid, wt),
                    _rl().scale(
                        _lower_cost(hv, p, S((B, 2, 2, 4), f),
                                    S((B, 2, 2, 4), f),
                                    S((B, 2, 2, 2), f),
                                    S((K, N, 2, 4, 4), fa), p,
                                    s1, s2, cid),
                        rtr_mod.RTRConfig().tcg_iters))
            elif jm != "full":
                # dense reduced assembly ([K, npar N, npar N]): the
                # fused kernel (use_pk) and xla bodies price through
                # the same mode entry points the solvers execute
                def outer(p, Jr, x8, coh, s1, s2, cid, wt):
                    J = ne.jones_from_params(
                        p.reshape(K, N, 2 * md), jm, Jr)
                    cfn = rtr_mod.make_cost(x8, coh, s1, s2, cid, wt,
                                            K, N, robust_nu=rnu,
                                            mode=jm, Jref=Jr)
                    g = jax.grad(lambda q: jnp.sum(cfn(q)))(p)
                    g = rtr_mod.project_tangent_mode(p, g, K, N, jm)
                    if use_pk:
                        JTJ, _, _ = swp.normal_equations_fused(
                            x8, J, coh, s1, s2, cid, wt, N, K, nb_,
                            jones=jm)
                    else:
                        JTJ, _, _ = ne.normal_equations_mode(
                            x8, J, coh, s1, s2, cid, wt, N, K, mode=jm,
                            row_period=int(nbase))
                    return g, JTJ, cfn(p)

                def hv(p, JTJ, v):
                    Hv = 2.0 * jnp.einsum("kij,kj->ki", JTJ, v)
                    return rtr_mod.project_tangent_mode(p, Hv, K, N, jm)

                trip = _rl().combine(
                    _lower_cost(outer, p, Jrf, x8, coh, s1, s2, cid, wt),
                    _rl().scale(_lower_cost(hv, p, S((K, P, P), fa), p),
                                rtr_mod.RTRConfig().tcg_iters))
            elif use_pk:
                def outer(p, x8, coh, s1, s2, cid, wt):
                    J = ne.jones_r2c(p.reshape(K, N, 8))
                    cfn = rtr_mod.make_cost(x8, coh, s1, s2, cid, wt,
                                            K, N, robust_nu=rnu)
                    g = jax.grad(lambda q: jnp.sum(cfn(q)))(p)
                    g = rtr_mod.project_tangent(p, g, K, N)
                    JTJ, _, _ = swp.normal_equations_fused(
                        x8, J, coh, s1, s2, cid, wt, N, K, nb_)
                    return g, JTJ, cfn(p)

                def hv(p, JTJ, v):
                    Hv = 2.0 * jnp.einsum("kij,kj->ki", JTJ, v)
                    return rtr_mod.project_tangent(p, Hv, K, N)

                trip = _rl().combine(
                    _lower_cost(outer, p, x8, coh, s1, s2, cid, wt),
                    _rl().scale(_lower_cost(hv, p, S((K, P, P), fa), p),
                                rtr_mod.RTRConfig().tcg_iters))
            else:
                def outer(p, x8, coh, s1, s2, cid, wt):
                    J = ne.jones_r2c(p.reshape(K, N, 8))
                    cfn = rtr_mod.make_cost(x8, coh, s1, s2, cid, wt,
                                            K, N, robust_nu=rnu)
                    g = jax.grad(lambda q: jnp.sum(cfn(q)))(p)
                    g = rtr_mod.project_tangent(p, g, K, N)
                    JTJ, _, _ = ne.normal_equations(x8, J, coh, s1, s2,
                                                    cid, wt, N, K,
                                                    row_period=int(nbase))
                    return g, JTJ, cfn(p)

                def hv(p, JTJ, v):
                    Hv = 2.0 * jnp.einsum("kij,kj->ki", JTJ, v)
                    return rtr_mod.project_tangent(p, Hv, K, N)

                trip = _rl().combine(
                    _lower_cost(outer, p, x8, coh, s1, s2, cid, wt),
                    _rl().scale(_lower_cost(hv, p, S((K, P, P), fa), p),
                                rtr_mod.RTRConfig().tcg_iters))
        elif (int(solver_mode) == int(SolverMode.NSD_RLBFGS)
              and jm != "full"):
            def nsd_outer(p, Jr, x8, coh, s1, s2, cid, wt):
                cfn = rtr_mod.make_cost(x8, coh, s1, s2, cid, wt, K, N,
                                        robust_nu=2.0, mode=jm, Jref=Jr)
                g = jax.grad(lambda q: jnp.sum(cfn(q)))(p)
                return rtr_mod.project_tangent_mode(p, g, K, N, jm)

            def nsd_cost(p, Jr, x8, coh, s1, s2, cid, wt):
                return rtr_mod.make_cost(x8, coh, s1, s2, cid, wt, K, N,
                                         robust_nu=2.0, mode=jm,
                                         Jref=Jr)(p)

            trip = _rl().combine(
                _lower_cost(nsd_outer, p, Jrf, x8, coh, s1, s2, cid, wt),
                _rl().scale(_lower_cost(nsd_cost, p, Jrf, x8, coh, s1,
                                        s2, cid, wt),
                            rtr_mod.NSDConfig().ls_tries))
        elif int(solver_mode) == int(SolverMode.NSD_RLBFGS):
            def nsd_outer(p, x8, coh, s1, s2, cid, wt):
                cfn = rtr_mod.make_cost(x8, coh, s1, s2, cid, wt, K, N,
                                        robust_nu=2.0)
                g = jax.grad(lambda q: jnp.sum(cfn(q)))(p)
                return rtr_mod.project_tangent(p, g, K, N)

            def nsd_cost(p, x8, coh, s1, s2, cid, wt):
                return rtr_mod.make_cost(x8, coh, s1, s2, cid, wt, K, N,
                                         robust_nu=2.0)(p)

            trip = _rl().combine(
                _lower_cost(nsd_outer, p, x8, coh, s1, s2, cid, wt),
                _rl().scale(_lower_cost(nsd_cost, p, x8, coh, s1, s2,
                                        cid, wt),
                            rtr_mod.NSDConfig().ls_tries))
        elif inner == "cg":
            # matrix-free damping trip, FIXED part only: gn_factors
            # assembly at the trial point + station-block preconditioner
            # factorization + the initial apply. The PCG loop body
            # (matvec + apply) is priced per EXECUTED trip by
            # cg_trip_cost — lm.py counts them in info["cg_iters"].
            if jm != "full":
                def lm_trip(JTe0, mu, p, Jr, x8, coh, s1, s2, cid, wt):
                    Jn = ne.jones_from_params(
                        p.reshape(K, N, 2 * md), jm, Jr)
                    if use_pk:
                        fac, JTe, cost = swp.gn_blocks(
                            x8, Jn, coh, s1, s2, cid, wt, N, K, nb_,
                            jones=jm)
                    else:
                        fac, JTe, cost = ne.gn_factors_mode(
                            x8, Jn, coh, s1, s2, cid, wt, N, K, mode=jm,
                            row_period=int(nbase))
                    Lfac = ne.gn_precond_factor(fac.D, mu + 1e-9)
                    z0 = ne.gn_precond_apply(Lfac, JTe, K, N)
                    return fac, JTe, cost, z0

                trip = _lower_cost(lm_trip, p, S((K,), fa), p, Jrf, x8,
                                   coh, s1, s2, cid, wt)
            else:
                def lm_trip(JTe0, mu, p, x8, coh, s1, s2, cid, wt):
                    Jn = ne.jones_r2c(p.reshape(K, N, 8))
                    if use_pk:
                        fac, JTe, cost = swp.gn_blocks(
                            x8, Jn, coh, s1, s2, cid, wt, N, K, nb_)
                    else:
                        fac, JTe, cost = ne.gn_factors(
                            x8, Jn, coh, s1, s2, cid, wt, N, K,
                            row_period=int(nbase))
                    Lfac = ne.gn_precond_factor(fac.D, mu + 1e-9)
                    z0 = ne.gn_precond_apply(Lfac, JTe, K, N)
                    return fac, JTe, cost, z0

                trip = _lower_cost(lm_trip, p, S((K,), fa), p, x8, coh,
                                   s1, s2, cid, wt)
        elif (reduced and K == 1 and int(nbase) > 0
              and B % int(nbase) == 0
              and int(solver_mode)
              == int(SolverMode.OSLM_OSRLM_RLBFGS)):
            # reduced-policy ORDERED-SUBSETS trip (mode 3: every EM
            # iteration's LM body runs under OS): lm.py slices the
            # subset's contiguous rows (ne.os_subset_equations — exact,
            # and ~1/n_subsets of the assembly traffic) plus one
            # full-[B] residual pass for the acceptance cost, solved by
            # the LU body. Pricing the masked full assembly here would
            # overstate the reduced path's bytes by ~3x. Modes 0/2 mix
            # OS and non-OS EM iterations, so they keep the full-
            # assembly price (an over-, never under-count).
            tilesz = B // int(nbase)
            # derive ntper from the SAME partition lm.py executes
            # (os_subset_ids), not a re-statement of its law: the block
            # size is subset 0's timeslot count
            os_ids_np, _ns = lm_mod.os_subset_ids(tilesz, int(nbase))
            import numpy as _np
            ntper = int(_np.sum(_np.asarray(os_ids_np)[::int(nbase)] == 0))

            if jm != "full":
                def lm_trip(JTJ, JTe, mu, p, Jr, x8, coh, s1, s2, wt,
                            osids, l):
                    dp, _ = lm_mod._lu_solve_shift(JTJ, JTe, mu + 1e-9)
                    Jn = ne.jones_from_params(
                        (p + dp).reshape(K, N, 2 * md), jm, Jr)
                    return ne.os_subset_equations_mode(
                        x8, Jn, coh, s1, s2, wt, osids, l, ntper,
                        int(nbase), N, wt, mode=jm)

                trip = _lower_cost(lm_trip, S((K, P, P), fa), p,
                                   S((K,), fa), p, Jrf, x8, coh, s1, s2,
                                   wt, S((B,), i), S((), i))
            else:
                def lm_trip(JTJ, JTe, mu, p, x8, coh, s1, s2, wt,
                            osids, l):
                    dp, _ = lm_mod._lu_solve_shift(JTJ, JTe, mu + 1e-9)
                    Jn = ne.jones_r2c((p + dp).reshape(K, N, 8))
                    return ne.os_subset_equations(x8, Jn, coh, s1, s2,
                                                  wt, osids, l, ntper,
                                                  int(nbase), N, wt)

                trip = _lower_cost(lm_trip, S((K, P, P), fa), p,
                                   S((K,), fa), p, x8, coh, s1, s2, wt,
                                   S((B,), i), S((), i))
        elif use_pk:
            # fused block-Cholesky damping trip (kernel="pallas",
            # inner="chol"): lm.py carries the B-independent per-
            # baseline blocks and executes sweep_pallas.
            # chol_solve_blocks_shift (assemble + factor WITHOUT the
            # symmetrize pass + solve) followed by one fused-sweep
            # row pass at the trial point. Pricing the dense
            # _chol_solve_shift here would price a body the pallas
            # path no longer executes (the PR 3 phantom-bytes class);
            # the retry lax.cond is excluded for the same reason.
            if jm != "full":
                def lm_trip(pp, qq, pq, Db, JTe, mu, p, Jr, x8, coh,
                            s1, s2, cid, wt):
                    fac = swp.GNBlocks(pp=pp, qq=qq, pq=pq, D=Db)
                    dp, _ = swp.chol_solve_blocks_shift(
                        fac, JTe, mu + 1e-9, s1, s2, N, reduced=reduced)
                    Jn = ne.jones_from_params(
                        (p + dp).reshape(K, N, 2 * md), jm, Jr)
                    return swp.gn_blocks(x8, Jn, coh, s1, s2, cid, wt,
                                         N, K, nb_, jones=jm)

                trip = _lower_cost(
                    lm_trip, S((K, nb_, 2, md, md), fa),
                    S((K, nb_, 2, md, md), fa),
                    S((K, nb_, 2, 2, md, md), fa),
                    S((K, N, 2, md, md), fa), p, S((K,), fa), p, Jrf,
                    x8, coh, s1, s2, cid, wt)
            else:
                def lm_trip(pp, qq, pq, Db, JTe, mu, p, x8, coh, s1, s2,
                            cid, wt):
                    fac = swp.GNBlocks(pp=pp, qq=qq, pq=pq, D=Db)
                    dp, _ = swp.chol_solve_blocks_shift(
                        fac, JTe, mu + 1e-9, s1, s2, N, reduced=reduced)
                    Jn = ne.jones_r2c((p + dp).reshape(K, N, 8))
                    # blocks AND acceptance cost from the body's single
                    # fused row pass (lm.py); no separate cost
                    # evaluation
                    return swp.gn_blocks(x8, Jn, coh, s1, s2, cid, wt,
                                         N, K, nb_)

                trip = _lower_cost(
                    lm_trip, S((K, nb_, 2, 4, 4), fa),
                    S((K, nb_, 2, 4, 4), fa),
                    S((K, nb_, 2, 2, 4, 4), fa),
                    S((K, N, 2, 4, 4), fa), p, S((K,), fa), p, x8, coh,
                    s1, s2, cid, wt)
        elif jm != "full":
            # reduced dense damping trip: [K, npar N, npar N] damped
            # solve + one mode-assembly row pass (the body lm.py
            # executes under --jones diag/phase, kernel="xla")
            def lm_trip(JTJ, JTe, mu, p, Jr, x8, coh, s1, s2, cid, wt):
                if reduced:
                    dp, _ = lm_mod._lu_solve_shift(JTJ, JTe, mu + 1e-9)
                else:
                    dp, _ = lm_mod._chol_solve_shift(JTJ, JTe, mu + 1e-9)
                Jn = ne.jones_from_params(
                    (p + dp).reshape(K, N, 2 * md), jm, Jr)
                return ne.normal_equations_mode(
                    x8, Jn, coh, s1, s2, cid, wt, N, K, mode=jm,
                    row_period=int(nbase))

            trip = _lower_cost(lm_trip, S((K, P, P), fa), p, S((K,), fa),
                               p, Jrf, x8, coh, s1, s2, cid, wt)
        else:
            def lm_trip(JTJ, JTe, mu, p, x8, coh, s1, s2, cid, wt):
                # price the executed all-ok solve body, NOT
                # _solve_damped: cost analysis sums both lax.cond
                # branches, so the wrapper would charge every trip for
                # the never-taken jitter-retry factorization (+31%
                # bytes on config 1 when this priced the wrapper).
                # Reduced policies price the LU body lm.py executes.
                if reduced:
                    dp, _ = lm_mod._lu_solve_shift(JTJ, JTe, mu + 1e-9)
                else:
                    dp, _ = lm_mod._chol_solve_shift(JTJ, JTe, mu + 1e-9)
                Jn = ne.jones_r2c((p + dp).reshape(K, N, 8))
                # normal equations AND acceptance cost from the body's
                # single row pass (lm.py); no separate cost evaluation
                return ne.normal_equations(x8, Jn, coh, s1, s2, cid, wt,
                                           N, K, row_period=int(nbase))

            trip = _lower_cost(lm_trip, S((K, P, P), fa), p, S((K,), fa),
                               p, x8, coh, s1, s2, cid, wt)
        _TRIP_CACHE[key] = trip
        return trip
    except Exception as e:          # pragma: no cover - version-dependent
        log(f"# trip pricing unavailable: {type(e).__name__}: {e}")
        _TRIP_CACHE[key] = None
        return None


def cg_trip_cost(kmax, n_stations, B, dtype, nbase=0, kernel="xla",
                 jones="full"):
    """FLOPs + bytes of ONE executed PCG inner trip (lm.py
    _solve_damped_cg body under inner="cg"): one matrix-free gn_matvec
    over the Wirtinger factors + one station-block preconditioner apply
    + the axpy/dot chain. Multiplied by info["cg_iters"] via
    roofline.trip_correct — without this the matrix-free path's actual
    Krylov traffic would vanish from the roofline (the while_loop body
    prices once). The tiny [K,N,2] 4x4 factorization is charged per
    damping trip (solver_trip_cost), not here. ``kernel="pallas"``
    prices the B-independent blocks matvec
    (sweep_pallas.gn_matvec_blocks) instead of the [B]-row factor
    pass — the melt the fused-sweep kernel buys the cg path.
    ``jones``: constrained modes price the mdim-wide matvec bodies
    (gn_matvec_mode / reduced blocks) at npar N vector width."""
    key = ("cgtrip", kmax, n_stations, B, str(dtype), int(nbase),
           str(kernel), str(jones))
    if key in _TRIP_CACHE:
        return _TRIP_CACHE[key]
    import jax
    import jax.numpy as jnp
    from sagecal_tpu import dtypes as dtp
    from sagecal_tpu.solvers import normal_eq as ne
    K, N = kmax, n_stations
    jm = str(jones)
    md = ne.jones_mdim(jm)
    f = dtype
    fa = dtp.acc_dtype(dtype)
    i = jnp.int32
    S = jax.ShapeDtypeStruct
    use_pk = False
    if kernel == "pallas":
        from sagecal_tpu.ops import sweep_pallas as swp
        use_pk = swp.supported(K, int(nbase), B)
    nb_ = int(nbase)
    try:
        if use_pk:
            def body(pp, qq, pq, Larr, v, r, shift, s1, s2):
                fac = swp.GNBlocks(pp=pp, qq=qq, pq=pq, D=Larr)
                Ap = swp.gn_matvec_blocks(fac, v, s1, s2, N,
                                          shift=shift)
                alpha = jnp.sum(r * r, axis=-1) \
                    / jnp.maximum(jnp.sum(v * Ap, axis=-1), 1e-30)
                rn = r - alpha[:, None] * Ap
                z = ne.gn_precond_apply((Larr, True), rn, K, N)
                return rn, z, jnp.sum(rn * z, axis=-1)

            trip = _lower_cost(
                body, S((K, nb_, 2, md, md), fa),
                S((K, nb_, 2, md, md), fa),
                S((K, nb_, 2, 2, md, md), fa), S((K, N, 2, md, md), fa),
                S((K, 2 * md * N), fa), S((K, 2 * md * N), fa),
                S((K,), fa), S((B,), i), S((B,), i))
            _TRIP_CACHE[key] = trip
            return trip

        if jm != "full":
            def body(FA, FB, w2, Larr, v, r, shift, s1, s2, cid):
                fac = ne.GNFactorsMode(FA=FA, FB=FB, w2=w2, D=Larr)
                Ap = ne.gn_matvec_mode(fac, v, s1, s2, cid, K, N,
                                       shift=shift)
                alpha = jnp.sum(r * r, axis=-1) \
                    / jnp.maximum(jnp.sum(v * Ap, axis=-1), 1e-30)
                rn = r - alpha[:, None] * Ap
                z = ne.gn_precond_apply((Larr, True), rn, K, N)
                return rn, z, jnp.sum(rn * z, axis=-1)

            trip = _lower_cost(
                body, S((B, 2, 2, 2, md), f), S((B, 2, 2, 2, md), f),
                S((B, 2, 2, 2), f), S((K, N, 2, md, md), fa),
                S((K, 2 * md * N), fa), S((K, 2 * md * N), fa),
                S((K,), fa), S((B,), i), S((B,), i), S((B,), i))
            _TRIP_CACHE[key] = trip
            return trip

        def body(MA, MB, w2, Larr, v, r, shift, s1, s2, cid):
            fac = ne.GNFactors(MA=MA, MB=MB, w2=w2, D=Larr)
            Ap = ne.gn_matvec(fac, v, s1, s2, cid, K, N, shift=shift,
                              row_period=int(nbase))
            alpha = jnp.sum(r * r, axis=-1) \
                / jnp.maximum(jnp.sum(v * Ap, axis=-1), 1e-30)
            rn = r - alpha[:, None] * Ap
            z = ne.gn_precond_apply((Larr, True), rn, K, N)
            return rn, z, jnp.sum(rn * z, axis=-1)

        trip = _lower_cost(
            body, S((B, 2, 2, 4), f), S((B, 2, 2, 4), f),
            S((B, 2, 2, 2), f), S((K, N, 2, 4, 4), fa),
            S((K, 8 * N), fa), S((K, 8 * N), fa), S((K,), fa),
            S((B,), i), S((B,), i), S((B,), i))
        _TRIP_CACHE[key] = trip
        return trip
    except Exception as e:          # pragma: no cover - version-dependent
        log(f"# cg trip pricing unavailable: {type(e).__name__}: {e}")
        _TRIP_CACHE[key] = None
        return None


def refine_trip_cost(M, kmax, n_stations, B, robust, dtype):
    """FLOPs + bytes of ONE joint-refine LBFGS iteration: cost + gradient
    of the all-cluster objective (sage._refine_cost_fn). The line search
    is not counted: its restriction (a jvp and a model evaluation, about
    one more gradient's worth) in the linear Jones modes, its trials
    through the whole model in ``phase`` mode."""
    key = ("refine", M, kmax, n_stations, B, bool(robust), str(dtype))
    if key in _TRIP_CACHE:
        return _TRIP_CACHE[key]
    import jax
    import jax.numpy as jnp
    from sagecal_tpu import dtypes as dtp
    from sagecal_tpu.solvers import sage as sage_mod
    f = dtype
    fa = dtp.acc_dtype(dtype)
    c = jnp.complex64 if fa == jnp.float32 else jnp.complex128
    i = jnp.int32
    S = jax.ShapeDtypeStruct
    shape = (M * kmax, n_stations, 8)
    try:
        def cg(p, x8, coh, s1, s2, cidx, wt):
            cost_fn, _line_fn = sage_mod._refine_cost_fn(
                x8, coh, s1, s2, cidx, wt, shape, M, kmax, n_stations,
                robust, 5.0)
            return jax.value_and_grad(cost_fn)(p)

        out = _lower_cost(
            cg, S((M * kmax * n_stations * 8,), fa), S((B, 8), f),
            S((M, B, 2, 2), c), S((B,), i), S((B,), i), S((M, B), i),
            S((B, 8), f))
        _TRIP_CACHE[key] = out
        return out
    except Exception as e:          # pragma: no cover - version-dependent
        log(f"# refine trip pricing unavailable: {type(e).__name__}: {e}")
        _TRIP_CACHE[key] = None
        return None


def cost_of_stats(stats, extra=()):
    """Sum cost-analysis FLOPs + bytes x call count over the solver's
    program log (sage.program_stats) plus ``extra`` (jfn, args, kwargs,
    n) entries. Returns None when any program refuses to lower (older
    jax, etc.)."""
    rl = _rl()
    total = rl.zero_cost()
    try:
        for name, (jfn, argkw, n) in stats.items():
            if argkw is None or n == 0:
                continue
            total = rl.combine(total,
                               rl.scale(_cost(jfn, argkw[0], argkw[1]), n))
        for jfn, args, kwargs, n in extra:
            total = rl.combine(total, rl.scale(_cost(jfn, args, kwargs), n))
    except Exception as e:          # pragma: no cover - version-dependent
        log(f"# cost accounting unavailable: {type(e).__name__}: {e}")
        return None
    return total


def pallas_ok(device, dtype, sky) -> bool:
    """Host-side gate for the Pallas coherency kernel: the choice
    FullBatchPipeline makes, from what is known before running (TPU,
    f32, a kernel-supported source type). No probe — a kernel that
    fails to compile or run fails the config. Mixed models count as
    supported — time_sage then runs the hybrid split path."""
    import jax.numpy as jnp
    from sagecal_tpu.ops import coh_pallas
    return (device.platform == "tpu" and dtype == jnp.float32
            and coh_pallas.any_supported(sky))


def time_sage(device, dtype, sky, dsky, tiles, solver_mode, reps=2,
              max_emiter=3, max_iter=10, max_lbfgs=10, use_pallas=False,
              inflight=1, inner="chol", dtype_policy="f32",
              kernel="xla"):
    """Compile + time one batched SAGE solve over ``tiles`` independent
    solve intervals; returns (vis/s, r0, r1, dt, compile_s, cost_step)
    where cost_step is {"flops", "bytes_accessed"} per timed step (or
    None when cost analysis is unavailable).

    Uses the host-driven EM loop over a tile batch
    (sage.sagefit_host_tiles): T tiles run as ONE vmapped program per
    bounded device execution — the tile axis is what keeps the MXU fed
    (VERDICT r3 item 1); per-execution wall-clock stays bounded via
    the same fusion/promotion machinery.
    Residual figures are tile 0's. With ``inflight`` == 1 tile 0 solves
    identically to the historical single-tile bench (sage.tile_keys
    keeps its PRNG stream); with groups active (the round-5 TPU default
    G=2) the EM sweep semantics change (block-Jacobi groups), so
    res_0/res_1 are NOT bit-comparable with the BENCH_r02..r04 records
    — the shape string's G tag marks which regime a record is from.

    ``cost_step``: achieved FLOPs + bytes accessed of one timed step =
    XLA cost analysis over every device program the step executed
    (sage.program_stats) PLUS the dynamic-trip correction (executed
    solver/refine iteration counts x per-trip price — see the MFU
    trip-accounting block above). Without the correction the numbers
    undercount by orders of magnitude because XLA prices loop bodies
    once regardless of trip count (VERDICT r4 weak 2).
    """
    import jax
    import jax.numpy as jnp
    from sagecal_tpu import dtypes as dtp
    from sagecal_tpu.rime import predict as rp
    from sagecal_tpu.solvers import lm as lm_mod, normal_eq as ne, sage

    tile = tiles[0]
    T = len(tiles)
    inp = _sage_inputs(sky, tiles, dtype, device)
    # dtype-policy storage staging: the bench ships/solves the same
    # sdt bytes the pipeline would (identity at "f32")
    sdt = dtp.storage_dtype(dtype_policy, dtype)
    inp["x8"] = inp["x8"].astype(sdt)
    inp["wt"] = inp["wt"].astype(sdt)
    dsky_d = jax.device_put(dsky, device)
    os_ids, ns = lm_mod.os_subset_ids(tile.tilesz, tile.nbase)
    cfg = sage.SageConfig(max_emiter=max_emiter, max_iter=max_iter,
                          max_lbfgs=max_lbfgs, solver_mode=int(solver_mode),
                          inflight=inflight, nbase=tile.nbase, inner=inner,
                          dtype_policy=dtype_policy, kernel=kernel)
    if T > 1:
        # tile-batch trials route through the per-sweep host-tiles
        # driver (VERDICT r5 weak #3): force-fuse each EM sweep into
        # ONE bounded execution and never promote to the whole-solve
        # program — the round-5 T=8 trial never finished: the promoted
        # fused-8-tile program was one multi-minute compile plus one
        # very long execution. With fuse=on/promote=off the
        # largest execution is one sweep, so a T>1 record is a bounded
        # number instead of "never finishes".
        cfg = cfg._replace(fuse="on", promote="off")
    n = tile.n_stations
    cidx_d, cmask_d, freq = inp["cidx"], inp["cmask"], inp["freq"]
    os_d = (jax.device_put(jnp_i32(os_ids), device), ns)
    keys = jax.device_put(sage.tile_keys(T), device)

    if use_pallas:
        from sagecal_tpu import skymodel as sm
        sky_pg, sky_rest = sm.split_for_pallas(sky)
        pg_d = jax.device_put(rp.sky_to_device(sky_pg, dtype), device)
        rest_d = (None if sky_rest is None else
                  jax.device_put(rp.sky_to_device(sky_rest, dtype), device))

        def coh_one(u1, v1, w1):
            return rp.coherencies_split(pg_d, rest_d, u1, v1, w1, freq,
                                        tile.fdelta)[:, :, 0]
    else:
        def coh_one(u1, v1, w1):
            return rp.coherencies(dsky_d, u1, v1, w1, freq,
                                  tile.fdelta)[:, :, 0]
    # all tiles' coherencies in ONE program (T unrolled predicts: the
    # Pallas kernel needs no batching rule this way); complex stacking
    # and the real<->complex Jones conversions run jitted (complex
    # stays on-device)
    coh_fn = jax.jit(lambda u, v, w: jnp.stack(
        [coh_one(u[t], v[t], w[t]) for t in range(T)]))
    r2c = jax.jit(ne.jones_r2c)
    c2r = jax.jit(ne.jones_c2r)

    def step(x8, u, v, w, s1, s2, wt, J0):
        coh = coh_fn(u, v, w)
        J, info = sage.sagefit_host_tiles(
            x8, coh, s1, s2, cidx_d, cmask_d, r2c(J0), n, wt, config=cfg,
            os_id=os_d, keys=keys)
        return (J, info["res_0"], info["res_1"],
                info["solver_iters"], info["lbfgs_iters"],
                info["cg_iters"])

    args = (inp["x8"], inp["u"], inp["v"], inp["w"], inp["s1"], inp["s2"],
            inp["wt"], inp["J0"])
    tc0 = time.perf_counter()
    J, r0, r1, si, lk, ci = step(*args)
    jax.block_until_ready(J)
    compile_s = time.perf_counter() - tc0
    # untimed settling calls: sagefit_host_tiles may PROMOTE this shape
    # to the fully traced program a call in (it qualifies during the
    # warmup call for max_emiter >= 2 — every bench config), and that
    # compile must not land inside the timed reps. Two settle calls
    # bound the cost: call 1 absorbs the promoted compile, call 2
    # confirms steady state.
    t_prev = None
    settle_s = 0.0
    n_settle = 0
    for _ in range(2):
        tp0 = time.perf_counter()
        J, r0, r1, si, lk, ci = step(*args)
        jax.block_until_ready(J)
        t_call = time.perf_counter() - tp0
        settle_s += t_call
        n_settle += 1
        if t_prev is not None and abs(t_call - t_prev) < 0.25 * t_prev:
            break
        t_prev = t_call
    sage.program_stats_reset()
    t0 = time.perf_counter()
    for _ in range(reps):
        J, r0, r1, si, lk, ci = step(*args)
    jax.block_until_ready(J)
    dt = (time.perf_counter() - t0) / reps
    compile_s += max(settle_s - n_settle * dt, 0.0)
    rl = _rl()
    total = cost_of_stats(
        sage.program_stats(),
        extra=[(coh_fn, (inp["u"], inp["v"], inp["w"]), {}, reps)])
    cost_step = None if total is None else rl.scale(total, 1.0 / reps)
    # dynamic-trip correction: executed solver/refine iterations (summed
    # over tiles — the step is identical every rep) x per-trip price.
    # See the MFU trip-accounting block above for the method + slack.
    if cost_step is not None:
        kmax = int(cmask_d.shape[1])
        trips = float(np.asarray(si).sum())
        refine_trips = float(np.asarray(lk).sum())
        cg_trips = float(np.asarray(ci).sum())
        tf = solver_trip_cost(solver_mode, kmax, n, tile.nrows, sdt,
                              nbase=tile.nbase, inner=inner,
                              kernel=kernel)
        rf = refine_trip_cost(sky.n_clusters, kmax, n, tile.nrows,
                              sage._is_robust(int(solver_mode)), sdt)
        # composition detail so config 7 can re-price at EQUAL trip
        # counts across policies (merged into cost_step after the trip
        # corrections below — trip_correct returns a fresh dict)
        detail = {
            "base_bytes": cost_step["bytes_accessed"],
            "solver_trips": trips, "refine_trips": refine_trips,
            "cg_trips": cg_trips,
            "solver_trip_bytes": 0.0 if tf is None
            else tf["bytes_accessed"],
            "refine_trip_bytes": 0.0 if rf is None
            else rf["bytes_accessed"]}
        # each term applies independently: dropping BOTH because one
        # price failed would silently revert to the orders-of-magnitude
        # undercount this correction exists to fix
        base_gf = cost_step["flops"] / 1e9
        cost_step = rl.trip_correct(cost_step, tf, trips)
        cost_step = rl.trip_correct(cost_step, rf, refine_trips)
        cf = None
        from sagecal_tpu.config import SolverMode
        # an RTR mode's info["cg_iters"] are tCG bodies, which
        # solver_trip_cost already prices inside a trip
        is_rtr = int(solver_mode) in (int(SolverMode.RTR_OSLM_LBFGS),
                                      int(SolverMode.RTR_OSRLM_RLBFGS))
        if inner == "cg" and cg_trips and not is_rtr:
            # the matrix-free path's Krylov traffic: executed PCG trips
            # (info["cg_iters"]) x one matvec + preconditioner apply
            cf = cg_trip_cost(kmax, n, tile.nrows, sdt,
                              nbase=tile.nbase, kernel=kernel)
            cost_step = rl.trip_correct(cost_step, cf, cg_trips)
        cost_step.update(detail)
        log(f"# flops: {trips:.0f} solver trips x "
            f"{(tf['flops'] if tf else 0) / 1e9:.4f} GF + "
            f"{refine_trips:.0f} refine trips x "
            f"{(rf['flops'] if rf else 0) / 1e9:.4f} GF + "
            f"{cg_trips:.0f} cg trips x "
            f"{(cf['flops'] if cf else 0) / 1e9:.4f} GF "
            f"+ base {base_gf:.2f} GF; "
            f"bytes {cost_step['bytes_accessed'] / 1e9:.3f} GB")
    nvis = T * tile.nrows * len(tile.freqs)
    r0_0 = float(np.asarray(r0).reshape(-1)[0])
    r1_0 = float(np.asarray(r1).reshape(-1)[0])
    return nvis / dt, r0_0, r1_0, dt, compile_s, cost_step


def jnp_i32(a):
    import jax.numpy as jnp
    return jnp.asarray(a, jnp.int32)


# ---------------------------------------------------------------------------
# configs
# ---------------------------------------------------------------------------

def _env_or_tpu_default(env_name: str, device, default: int) -> int:
    """Env-int override, else ``default`` on TPU and 1 on the
    (single-core) CPU fallback, where batching just multiplies
    wall-clock."""
    envv = int(os.environ.get(env_name, 0) or 0)
    if envv:
        return envv
    return default if device.platform == "tpu" else 1


def _tiles_for(device, default: int = 1) -> int:
    """Tile-batch width (SAGECAL_BENCH_TILES override).

    Default 1 everywhere, measured 2026-07-31 on the real chip:
    T=8 on config-1 never finished inside 400 s (one fused 8-tile
    program pays a multi-minute XLA compile and its single execution
    runs for about a minute), while T=1 completes the whole
    config in ~100 s cold.  Per-execution time at T=1 is ~6.6 s, so
    dispatch latency — the overhead tile-batching amortizes — is <1%
    of the step; there is nothing for the lever to win here.  It stays
    an env/CLI opt-in for pod-scale runs where executions are short."""
    return _env_or_tpu_default("SAGECAL_BENCH_TILES", device, default)


def _inflight_for(device, M: int, default: int = 1) -> tuple[int, int]:
    """(requested, effective) --inflight group width for the SAGE
    configs (SAGECAL_BENCH_INFLIGHT override).  Default 1, measured
    2026-07-31 on the real chip: G(eff)=2 on config-1 is 0.68x the
    G=1 throughput (1,961 vs 2,879 vis/s) and the north-star at G=4
    is 0.69x (166.3 vs 114.0 s/ADMM-iter) — the group step's damped
    retries add model evaluations and the vmapped G-lane solve runs
    every lane to the slowest lane's trip count, which costs more
    than the halved sweep length saves.  The EFFECTIVE width after
    the solver's clamp is what the record must say: attributing
    clamped-G numbers to the requested G would make wider groups look
    free."""
    from sagecal_tpu.solvers import sage
    G = _env_or_tpu_default("SAGECAL_BENCH_INFLIGHT", device, default)
    return G, sage._eff_inflight(sage.SageConfig(inflight=G), M)


def _dtype_policy_for() -> str:
    """Storage dtype policy for the SAGE configs (SAGECAL_BENCH_DTYPE
    override: f32 | bf16 | f16, default f32). Non-f32 runs tag their
    records with ``dtype_policy`` and are NEVER round-stamped as the
    standard configs (the bank must stay the f32 reference the Δbytes
    column measures against) — config ``7-dtype-melt`` is the banked
    vehicle for the per-policy numbers."""
    v = os.environ.get("SAGECAL_BENCH_DTYPE", "f32")
    if v not in ("f32", "bf16", "f16"):
        raise SystemExit(f"SAGECAL_BENCH_DTYPE={v}: pick f32|bf16|f16")
    return v


def _kernel_for() -> str:
    """Row-pass kernel for the SAGE configs (SAGECAL_BENCH_KERNEL
    override: "xla" | "pallas"). Default xla — the bit-frozen reference
    the banked rounds price. "pallas" routes the per-cluster assembly
    and the inner="cg" matvec through the fused-sweep kernel
    (ops/sweep_pallas.py; interpret-mode on CPU). Non-default runs tag
    their records with ``kernel`` and are NEVER round-stamped as the
    standard configs (mirror of the SAGECAL_BENCH_DTYPE exploration
    rule); tools_dev/northstar.py --b-scaling --kernel both is the
    banked vehicle for the kernel-on/off deltas (BSCALING_r11.json)."""
    v = os.environ.get("SAGECAL_BENCH_KERNEL", "xla")
    if v not in ("xla", "pallas"):
        raise SystemExit(f"SAGECAL_BENCH_KERNEL={v}: pick xla|pallas")
    return v


def _inner_for() -> str:
    """Inner linear solver for the SAGE configs (SAGECAL_BENCH_INNER
    override: "chol" | "cg"). Default chol — the measured verdict
    everywhere on CPU: the north-star ladder has cg 13.6-16.6x slower
    at every B rung (BSCALING_r07.json — each PCG trip re-pays a full
    [B]-row matvec pass), and the config-1 cg trial loses the same way
    at the small bench shape; see SageConfig.inner's rationale. The
    banked BENCH_CPU_r07 rows therefore price the chol path; flip the
    env var for a cg round on a TPU window."""
    v = os.environ.get("SAGECAL_BENCH_INNER", "chol")
    return v if v in ("chol", "cg") else "chol"


def _roofline_fields(out, device, cost_step, dt):
    """Merge the roofline record (flops, bytes_accessed, achieved_gbps,
    bound, ... — diag.roofline) into a bench record, plus the legacy MFU
    keys (flops_step/flops_per_s/mfu_pct) for cross-round comparability."""
    if cost_step and cost_step.get("flops"):
        out.update(_rl().roofline_fields(cost_step, dt, device))
        out["flops_step"] = cost_step["flops"]
        out["flops_per_s"] = cost_step["flops"] / dt
        pk = peak_flops(device)
        if pk:
            out["mfu_pct"] = 100.0 * cost_step["flops"] / dt / pk
    return out


# back-compat alias (round<=5 callers/tools referenced _mfu_fields)
_mfu_fields = _roofline_fields


def config1_fullbatch_lm(device, dtype):
    """BASELINE config 1: point sources, LM-family solver (smoke shape
    scaled to LOFAR station count), one solve interval per execution
    (T/G opt-in via SAGECAL_BENCH_TILES/_INFLIGHT). On
    TPU the Pallas coherency kernel is measured against the XLA path
    (kernel-on/off throughput both recorded)."""
    from sagecal_tpu.config import SolverMode
    T = _tiles_for(device)
    G, Ge = _inflight_for(device, 8)
    inr = _inner_for()
    kern = _kernel_for()
    pol = _dtype_policy_for()
    sky, dsky, tiles = build_fullbatch(dtype, n_stations=62, n_clusters=8,
                                       tilesz=10, n_tiles=T)
    pal = pallas_ok(device, dtype, sky)
    vps, r0, r1, dt, comp, fl = time_sage(device, dtype, sky, dsky, tiles,
                                          SolverMode.OSLM_OSRLM_RLBFGS,
                                          use_pallas=pal, inflight=G,
                                          inner=inr, dtype_policy=pol,
                                          kernel=kern)
    itag = ("" if inr == "chol" else f" inner={inr}") \
        + ("" if kern == "xla" else f" kernel={kern}")
    ptag = "" if pol == "f32" else f" {pol}"
    out = dict(value=vps, unit="vis/s", res_0=r0, res_1=r1,
               step_s=dt, compile_s=comp, pallas=pal, tiles=T,
               inflight=G, inflight_eff=Ge, inner=inr, kernel=kern,
               shape=f"N=62 M=8 tilesz=10 point -j3 T{T} G{Ge}{itag}{ptag}")
    if pol != "f32":
        out["dtype_policy"] = pol
    _roofline_fields(out, device, fl, dt)
    if pal:
        vps0, _, _, _, _, _ = time_sage(device, dtype, sky, dsky, tiles,
                                        SolverMode.OSLM_OSRLM_RLBFGS,
                                        use_pallas=False, inflight=G,
                                        inner=inr, dtype_policy=pol,
                                          kernel=kern)
        out["value_xla"] = vps0
        out["pallas_speedup"] = vps / vps0
    return out


def config2_stochastic(device, dtype):
    """BASELINE config 2: stochastic-LBFGS bandpass (-N 1), multi-channel."""
    import jax
    import jax.numpy as jnp
    from sagecal_tpu.io import dataset as ds
    from sagecal_tpu.rime import predict as rp
    from sagecal_tpu.solvers import lbfgs as lbfgs_mod
    from sagecal_tpu import stochastic as st

    n_stations, n_clusters, tilesz, nchan = 32, 4, 8, 8
    sky, dsky, tiles = build_fullbatch(dtype, n_stations, n_clusters,
                                       tilesz, nchan=nchan)
    tile = tiles[0]
    dsky = jax.device_put(dsky, device)
    kmax = int(sky.nchunk.max())
    cmask = np.arange(kmax)[None, :] < sky.nchunk[:, None]
    nmb = 2  # minibatches per epoch
    row0, nts, tpm = st.minibatch_rows(tilesz, tile.nbase, nmb)
    cidx = rp.chunk_indices(tpm, tile.nbase, sky.nchunk)
    fdelta_chan = tile.fdelta / nchan
    nu_band = 2.0   # shared with the per-iteration price in band_cg below
    solver = st.make_band_solver(dsky, n_stations, cidx, cmask, fdelta_chan,
                                 nu=nu_band, max_lbfgs=10, consensus=False)

    # one band spanning all channels; [B, F, 8]-real data layout
    x = tile.x
    x8F = np.stack([x.reshape(x.shape[0], nchan, 4).real,
                    x.reshape(x.shape[0], nchan, 4).imag],
                   -1).reshape(x.shape[0], nchan, 8)
    wtrow = (tile.flags == 0).astype(np.float64)
    wtF = np.broadcast_to(wtrow[:, None, None],
                          (len(wtrow), nchan, 8)).copy()
    put = lambda a, dt: jax.device_put(jnp.asarray(a, dt), device)
    freqsF = put(tile.freqs, dtype)
    nparam = n_clusters * kmax * n_stations * 8
    mem = lbfgs_mod.lbfgs_memory_init(nparam, 7)
    mem = jax.device_put(mem, device)
    p0 = np.zeros((n_clusters, kmax, n_stations, 8))
    p0[..., 0] = p0[..., 6] = 1.0

    bmb = tpm * tile.nbase
    tslot = ds.row_tslot(bmb, tile.nbase)

    last_args = {}

    def run_minibatch(nb, p, mem):
        lo = row0[nb]
        sl = slice(lo, lo + bmb)
        args = (put(x8F[sl], dtype), put(tile.u[sl], dtype),
                put(tile.v[sl], dtype), put(tile.w[sl], dtype),
                put(tile.sta1[sl], jnp.int32),
                put(tile.sta2[sl], jnp.int32),
                put(wtF[sl], dtype), freqsF,
                put(tslot, jnp.int32), put(p, dtype), mem)
        last_args["a"] = args
        return solver(*args)

    # warmup/compile on minibatch 0
    tc0 = time.perf_counter()
    out = run_minibatch(0, p0, mem)
    jax.block_until_ready(out.p)
    comp = time.perf_counter() - tc0
    r0 = float(out.res_0)
    t0 = time.perf_counter()
    nsteps = 0
    iters_acc = []
    p, m = p0, mem
    for _ in range(2):           # epochs
        for nb in range(nmb):
            out = run_minibatch(nb, p, m)
            p, m = out.p, out.mem
            iters_acc.append(out.iters)
            nsteps += 1
    jax.block_until_ready(out.p)
    dt = (time.perf_counter() - t0) / nsteps
    r1 = float(out.res_1)
    nvis = bmb * nchan

    # P7 band-axis scaling: W=nchan mini-bands (1 channel each), one
    # batched device program vs a sequential per-band host loop
    # (minibatch_consensus_mode's band structure; VERDICT r2 item 5)
    W = nchan
    solver_b = st.make_band_solver_batched(
        dsky, n_stations, cidx, cmask, fdelta_chan, nu=nu_band,
        max_lbfgs=10, consensus=False)
    sl = slice(row0[0], row0[0] + bmb)
    x8W = put(np.transpose(x8F[sl].reshape(bmb, W, 1, 8), (1, 0, 2, 3)),
              dtype)
    wtW = put(np.transpose(wtF[sl].reshape(bmb, W, 1, 8), (1, 0, 2, 3)),
              dtype)
    fqW = put(np.asarray(tile.freqs).reshape(W, 1), dtype)
    pW = put(np.broadcast_to(p0, (W,) + p0.shape).copy(), dtype)
    memW = jax.device_put(
        jax.tree.map(lambda a: jnp.stack([a] * W),
                     lbfgs_mod.lbfgs_memory_init(nparam, 7)), device)
    geo = (put(tile.u[sl], dtype), put(tile.v[sl], dtype),
           put(tile.w[sl], dtype), put(tile.sta1[sl], jnp.int32),
           put(tile.sta2[sl], jnp.int32))
    tsl = put(tslot, jnp.int32)

    outb = solver_b(x8W, *geo[:3], geo[3], geo[4], wtW, fqW, tsl, pW,
                    memW, None, None, None, None)
    jax.block_until_ready(outb.p)                 # compile
    t0 = time.perf_counter()
    outb = solver_b(x8W, *geo[:3], geo[3], geo[4], wtW, fqW, tsl, pW,
                    memW, None, None, None, None)
    jax.block_until_ready(outb.p)
    dt_batched = time.perf_counter() - t0

    solver_1 = st.make_band_solver(dsky, n_stations, cidx, cmask,
                                   fdelta_chan, nu=nu_band, max_lbfgs=10,
                                   consensus=False)
    out1 = solver_1(x8W[0], *geo[:3], geo[3], geo[4], wtW[0], fqW[0],
                    tsl, pW[0], jax.tree.map(lambda a: a[0], memW))
    jax.block_until_ready(out1.p)                 # compile
    t0 = time.perf_counter()
    for b in range(W):
        out1 = solver_1(x8W[b], *geo[:3], geo[3], geo[4], wtW[b], fqW[b],
                        tsl, pW[b], jax.tree.map(lambda a: a[b], memW))
    jax.block_until_ready(out1.p)
    dt_seq = time.perf_counter() - t0

    out2 = dict(value=nvis / dt, unit="vis/s", res_0=r0, res_1=r1,
                step_s=dt, compile_s=comp,
                bands=W, bands_batched_s=dt_batched, bands_seq_s=dt_seq,
                band_speedup=dt_seq / dt_batched,
                shape=f"N=32 M=4 F={nchan}ch minibatch -N2")
    try:
        fl = _cost(solver, last_args["a"], {})
        # dynamic-trip correction: LBFGS iterations run inside a
        # while_loop the program price counts once. Per-iteration price =
        # cost + grad of the robust band objective (line-search extras
        # uncounted; see the MFU trip-accounting block).
        mean_iters = float(np.mean([np.asarray(k) for k in iters_acc]))
        # the priced objective IS the solver's (same builder — no copy
        # that could drift if the solver cost changes)
        cost_of = st.make_band_cost(cidx, cmask, n_stations, nu_band,
                                    consensus=False)
        s1b = jnp.asarray(tile.sta1[:bmb], jnp.int32)
        s2b = jnp.asarray(tile.sta2[:bmb], jnp.int32)

        def band_cg(pflat, coh, x8b, wtb):
            return jax.value_and_grad(
                cost_of(x8b, coh, wtb, s1b, s2b))(pflat)

        S = jax.ShapeDtypeStruct
        cdt = jnp.complex64 if dtype == jnp.float32 else jnp.complex128
        fiter = _lower_cost(
            band_cg, S((nparam,), dtype),
            S((n_clusters, bmb, nchan, 2, 2), cdt),
            S((bmb, nchan, 8), dtype), S((bmb, nchan, 8), dtype))
        fl = _rl().combine(fl, _rl().scale(fiter, mean_iters))
        log(f"# flops: {mean_iters:.1f} lbfgs iters x "
            f"{fiter['flops'] / 1e9:.4f} GF/iter")
    except Exception as e:          # pragma: no cover - version-dependent
        log(f"# flop accounting unavailable: {type(e).__name__}: {e}")
        fl = None
    return _roofline_fields(out2, device, fl, dt)


def config3_rtr16(device, dtype):
    """BASELINE config 3: robust Student's-t + RTR (-j 5), 16 clusters,
    one solve interval per execution (T/G opt-in via env)."""
    from sagecal_tpu.config import SolverMode
    # 2 EM iterations: a 3-EM robust-RTR step at 16 clusters is ~150 s
    # on-chip and the subprocess must fit warmup + 1 timed rep in 570 s.
    # CPU fallback drops to 1 EM iteration: the 2-EM run alone ate 440 s
    # of the round-4 1700 s budget and starved config 5 (VERDICT weak 1)
    on_tpu = device.platform == "tpu"
    emi = 2 if on_tpu else 1
    T = _tiles_for(device)
    G, Ge = _inflight_for(device, 16)
    inr = _inner_for()
    kern = _kernel_for()
    pol = _dtype_policy_for()
    sky, dsky, tiles = build_fullbatch(dtype, n_stations=62, n_clusters=16,
                                       tilesz=10, seed=SEED + 10,
                                       n_tiles=T)
    vps, r0, r1, dt, comp, fl = time_sage(device, dtype, sky, dsky, tiles,
                                          SolverMode.RTR_OSRLM_RLBFGS,
                                          reps=1, max_emiter=emi,
                                          inflight=G, inner=inr,
                                          kernel=kern,
                                          dtype_policy=pol)
    small = "" if on_tpu else " (cpu-small E1)"
    itag = ("" if inr == "chol" else f" inner={inr}") \
        + ("" if kern == "xla" else f" kernel={kern}")
    ptag = "" if pol == "f32" else f" {pol}"
    out = dict(value=vps, unit="vis/s", res_0=r0, res_1=r1,
               step_s=dt, compile_s=comp, tiles=T, inflight=G,
               inflight_eff=Ge, inner=inr, kernel=kern,
               shape=f"N=62 M=16 tilesz=10 point -j5 T{T} G{Ge}"
                     f"{small}{itag}{ptag}")
    if pol != "f32":
        out["dtype_policy"] = pol
    return _roofline_fields(out, device, fl, dt)


def config4_extended(device, dtype):
    """BASELINE config 4: shapelet + Gaussian sources, 3rd-order spectra,
    64 stations, one solve interval per execution (T/G opt-in via env).
    On TPU the hybrid
    Pallas split (kernel for point+gaussian, XLA for shapelets) is
    measured against pure XLA."""
    from sagecal_tpu.config import SolverMode
    on_tpu = device.platform == "tpu"
    emi = 2 if on_tpu else 1      # CPU fallback: budget, see config 3
    T = _tiles_for(device)
    G, Ge = _inflight_for(device, 8)
    sky, dsky, tiles = build_fullbatch(dtype, n_stations=64, n_clusters=8,
                                       tilesz=10, extended=True,
                                       spectra3=True, seed=SEED + 20,
                                       n_tiles=T)
    pal = pallas_ok(device, dtype, sky)
    inr = _inner_for()
    kern = _kernel_for()
    pol = _dtype_policy_for()
    vps, r0, r1, dt, comp, fl = time_sage(device, dtype, sky, dsky, tiles,
                                          SolverMode.RTR_OSRLM_RLBFGS,
                                          reps=1, max_emiter=emi,
                                          use_pallas=pal, inflight=G,
                                          inner=inr, dtype_policy=pol,
                                          kernel=kern)
    small = "" if on_tpu else " (cpu-small E1)"
    itag = ("" if inr == "chol" else f" inner={inr}") \
        + ("" if kern == "xla" else f" kernel={kern}")
    ptag = "" if pol == "f32" else f" {pol}"
    out = dict(value=vps, unit="vis/s", res_0=r0, res_1=r1,
               step_s=dt, compile_s=comp, pallas=pal, tiles=T,
               inflight=G, inflight_eff=Ge, inner=inr, kernel=kern,
               shape=f"N=64 M=8 shapelet+gauss -F1 -j5 T{T} G{Ge}"
                     f"{small}{itag}{ptag}")
    if pol != "f32":
        out["dtype_policy"] = pol
    _roofline_fields(out, device, fl, dt)
    if pal:
        vps0, _, _, _, _, _ = time_sage(device, dtype, sky, dsky, tiles,
                                        SolverMode.RTR_OSRLM_RLBFGS,
                                        reps=1, max_emiter=emi,
                                        use_pallas=False, inflight=G,
                                        inner=inr, dtype_policy=pol,
                                          kernel=kern)
        out["value_xla"] = vps0
        out["pallas_speedup"] = vps / vps0
    return out


def config5_admm32(device, dtype):
    """BASELINE config 5: consensus-ADMM over 32 subbands x many
    directions, folded onto the available chip(s). Metric: ADMM
    wall-clock per iteration.

    On the (1-core) CPU fallback the full F=32 x 5-iteration run is what
    starved this config out of the round-4 record (4/5, VERDICT weak 1):
    the fallback runs a reduced F=8 x 3-iteration shape instead — the
    s/ADMM-iter metric stays well-defined, the shape string records the
    reduction, and a 5/5 record beats a 4/5 record with one big number.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from sagecal_tpu import utils
    from sagecal_tpu.config import SolverMode
    from sagecal_tpu.consensus import admm as cadmm
    from sagecal_tpu.consensus import poly as cpoly
    from sagecal_tpu.rime import predict as rp
    from sagecal_tpu.solvers import lm as lm_mod, sage

    on_tpu = device.platform == "tpu"
    F = 32 if on_tpu else 8
    n_stations, n_clusters, tilesz = 32, 16, 4
    n_admm = 5 if on_tpu else 3
    sky, dsky, tiles = build_fullbatch(dtype, n_stations, n_clusters,
                                       tilesz, seed=SEED + 30)
    tile = tiles[0]
    dsky = jax.device_put(dsky, device)
    n = tile.n_stations
    kmax = int(sky.nchunk.max())
    cidx = rp.chunk_indices(tilesz, tile.nbase, sky.nchunk)
    cmask = np.arange(kmax)[None, :] < sky.nchunk[:, None]
    freqs = 150e6 * (1.0 + 0.005 * np.arange(F))
    Bpoly = cpoly.setup_polynomials(freqs, float(freqs.mean()), 2, 2)
    mesh = Mesh(np.array([device]), axis_names=("freq",))

    inr = _inner_for()
    kern = _kernel_for()
    cfg = cadmm.ADMMConfig(
        n_admm=n_admm, npoly=2, rho=2.0, manifold_iters=5,
        sage=sage.SageConfig(max_emiter=1, max_iter=3, max_lbfgs=3,
                             solver_mode=int(SolverMode.LM_LBFGS),
                             nbase=tile.nbase, inner=inr,
                             kernel=kern))
    # host_loop: one bounded execution per ADMM iteration (F=32 folded
    # onto one device is otherwise one very long execution) and much
    # cheaper to compile
    runner = cadmm.make_admm_runner(
        dsky, tile.sta1, tile.sta2, cidx, cmask, n, tile.fdelta,
        Bpoly, cfg, mesh, F, host_loop=True, nbase=tile.nbase)

    B = tile.nrows
    xa = tile.averaged()
    x8 = np.stack([xa.reshape(-1, 4).real, xa.reshape(-1, 4).imag],
                  -1).reshape(-1, 8)
    x8F = np.broadcast_to(x8, (F, B, 8)).copy()
    uF = np.broadcast_to(tile.u, (F, B)).copy()
    vF = np.broadcast_to(tile.v, (F, B)).copy()
    wF = np.broadcast_to(tile.w, (F, B)).copy()
    wt = np.asarray(lm_mod.make_weights(
        jnp.asarray(tile.flags, jnp.int32), dtype))
    wtF = np.broadcast_to(wt, (F,) + wt.shape).copy()
    J0 = np.tile(np.eye(2, dtype=np.complex64),
                 (F, sky.n_clusters, kmax, n, 1, 1))
    fratioF = np.ones(F)
    sh = NamedSharding(mesh, P("freq"))
    args = [jax.device_put(jnp.asarray(a, dtype), sh) for a in
            (x8F, uF, vF, wF, freqs, wtF, fratioF,
             utils.jones_c2r_np(J0))]

    tc0 = time.perf_counter()
    out = runner(*args)
    jax.block_until_ready(out[0])
    comp = time.perf_counter() - tc0
    reps = 2 if on_tpu else 1
    t0 = time.perf_counter()
    for _ in range(reps):
        out = runner(*args)
    jax.block_until_ready(out[0])
    per_iter = (time.perf_counter() - t0) / reps / n_admm
    res0, res1 = np.asarray(out[3]), np.asarray(out[4])
    small = "" if on_tpu else " (cpu-small)"
    itag = ("" if inr == "chol" else f" inner={inr}") \
        + ("" if kern == "xla" else f" kernel={kern}")
    rec = dict(value=per_iter, unit="s/ADMM-iter", compile_s=comp,
               res_0=float(res0.mean()), res_1=float(res1.mean()),
               inner=inr, kernel=kern,
               shape=f"F={F} N={n_stations} M={n_clusters} "
                     f"folded-1-chip x{n_admm}it{small}{itag}")
    # roofline: the ADMM J-update trip count is static here — the LM stop
    # thresholds (eps 1e-15) never fire at these residual levels, so
    # every cluster solve runs exactly sage.max_iter damping trips.
    # Per-iteration cost = F subbands x M clusters x max_iter x the
    # priced LM trip (consensus Z-update flops are small and uncounted).
    # Under inner="cg" the dominant cost is the DYNAMIC PCG trip chain
    # inside each damping trip, and the traced ADMM program does not
    # surface info["cg_iters"] to the host — pricing only the fixed
    # part would bank the exact orders-of-magnitude undercount the trip
    # correction exists to prevent, so this config refuses to price the
    # cg path until the runner exports the executed-trip counter.
    if inr == "cg":
        log("# config5 roofline skipped under inner=cg: the ADMM "
            "program does not surface cg_iters; a fixed-part-only "
            "price would undercount the Krylov traffic")
        return rec
    tf = solver_trip_cost(int(SolverMode.LM_LBFGS), kmax, n_stations,
                          B, dtype, nbase=tile.nbase, inner=inr,
                          kernel=kern)
    if tf:
        fl = _rl().scale(tf, F * n_clusters * cfg.sage.max_iter)
        _roofline_fields(rec, device, fl, per_iter)
    return rec


def config6_overlap(device, dtype):
    """Round-8 config: END-TO-END overlapped execution (ISSUE 5) —
    tiles/sec and device-busy fraction over a >=4-tile config-1-shaped
    pipeline run, deliberately distinct from configs 1-5's per-step
    pricing: this one times the WHOLE host loop (io + stage + solve +
    residual + write) twice at equal trip counts, ``--prefetch 0``
    (synchronous reference) vs ``--prefetch 1`` (double-buffered tile
    prefetch + async residual writeback), and refuses to bank unless
    solutions AND written residuals are bit-identical between the two.

    The Δwall column is ``dwall_pct`` (async vs sync, negative =
    overlap won); bubble accounting comes from the diag trace
    (trace.overlap_stats). NO ``bytes_accessed`` here on purpose:
    ``_bytes_baseline`` must keep reading configs 1-5's traffic from
    the newest record that prices it.
    """
    import tempfile
    import jax
    from sagecal_tpu import pipeline as pl
    from sagecal_tpu.config import RunConfig, SolverMode
    from sagecal_tpu.diag import trace as dtrace
    from sagecal_tpu.io import dataset as ds_mod

    # shape choice (measured 2026-08-03 on this host): the overlap can
    # only win what the host loop stalls on, so the e2e metric runs a
    # STREAMING-shaped problem — many short solve intervals over a
    # wide band (12 tiles x tilesz 4 x 16 channels), where the
    # io+stage+residual-fetch+write share is ~10% of wall. At config
    # 1's exact shape (4 big tiles, deep solves) the bubble is ~0.6%
    # and the comparison is pure noise.
    n_tiles, n_stations, n_clusters, tilesz, nchan = 12, 20, 3, 4, 16
    sky, dsky, tiles = build_fullbatch(dtype, n_stations, n_clusters,
                                       tilesz, nchan=nchan,
                                       n_tiles=n_tiles, seed=SEED + 60)
    tmpd = tempfile.mkdtemp(prefix="sagecal_overlap_")
    msdir = os.path.join(tmpd, "sim.ms")
    ds_mod.SimMS.create(msdir, tiles)
    cfg = RunConfig(ms=msdir, tile_size=tilesz, max_em_iter=1,
                    max_iter=4, max_lbfgs=2,
                    solver_mode=SolverMode.OSLM_LBFGS)
    ms = ds_mod.SimMS(msdir)
    noop = (lambda *a: None)
    pipe = pl.FullBatchPipeline(cfg, ms, sky, log=noop)

    def run(depth, tag, traced=False):
        tr = os.path.join(tmpd, f"{tag}.jsonl")
        if traced:
            dtrace.enable(tr, entry="bench-overlap", prefetch=depth)
        try:
            t0 = time.perf_counter()
            hist = pipe.run(solution_path=os.path.join(
                tmpd, f"{tag}.solutions"), prefetch=depth, log=noop)
            wall = time.perf_counter() - t0
        finally:
            if traced:
                dtrace.disable()
        out = ds_mod.SimMS(msdir, data_column="CORRECTED_DATA")
        res = [out.read_tile(i).x.copy() for i in range(n_tiles)]
        return wall, hist, res, tr

    # TWO settling runs: run 1 learns the fuse/promote execution plan,
    # run 2 compiles the promoted program (the same settle contract as
    # time_sage) — a single warm run leaves a multi-second compile
    # inside the first "timed" rep and fabricates a 2.5x overlap win
    t_w0 = time.perf_counter()
    run(0, "warm0")
    run(1, "warm1")
    comp_wall = time.perf_counter() - t_w0
    # alternating timed reps, min per mode: wall noise on a shared
    # 2-core host is ~10%, an order larger than the io+stage+write
    # bubble the overlap can hide — min-of-3 at EQUAL trip counts is
    # the comparison the Δwall column banks
    walls = {0: [], 1: []}
    outs = {}
    for rep in range(3):
        for depth in (0, 1):
            tag = f"{'sync' if depth == 0 else 'async'}{rep}"
            wall, hist, res, tr = run(depth, tag, traced=True)
            walls[depth].append(wall)
            outs[depth] = (hist, res, tr, tag)
    (h0, res_sync, tr_sync, tag0) = outs[0]
    (h1, res_async, tr_async, tag1) = outs[1]

    same = all(np.array_equal(a, b)
               for a, b in zip(res_sync, res_async))
    with open(os.path.join(tmpd, f"{tag0}.solutions")) as f0, \
            open(os.path.join(tmpd, f"{tag1}.solutions")) as f1:
        same = same and (f0.read() == f1.read())
    if not same:
        return {"error": "prefetch=1 outputs NOT bit-identical to the "
                         "sync reference — overlap contract broken"}
    st_sync = dtrace.overlap_stats(dtrace.read(tr_sync))
    st_async = dtrace.overlap_stats(dtrace.read(tr_async))
    wall_sync = min(walls[0])
    wall_async = min(walls[1])
    rec = dict(
        value=n_tiles / wall_async, unit="tiles/s",
        res_0=h1[0]["res_0"], res_1=h1[0]["res_1"],
        step_s=wall_async / n_tiles,
        compile_s=max(comp_wall - wall_sync - wall_async, 0.0),
        wall_sync_s=wall_sync, wall_async_s=wall_async,
        walls_sync=[round(w, 3) for w in walls[0]],
        walls_async=[round(w, 3) for w in walls[1]],
        dwall_pct=100.0 * (wall_async - wall_sync) / wall_sync,
        busy_frac_sync=st_sync["busy_frac"],
        busy_frac_async=st_async["busy_frac"],
        bubble_s_sync=st_sync["bubble_s"],
        bubble_s_async=st_async["bubble_s"],
        bit_identical=True,
        shape=f"N={n_stations} M={n_clusters} tilesz={tilesz} "
              f"F={nchan} x{n_tiles}tiles -j0 e1g4l2 pf1-vs-pf0")
    return rec


# per-policy residual-drift envelopes for the dtype-melt config: a
# record whose |res_1/res_1_f32 - 1| exceeds its policy's envelope is
# REFUSED from the bank (the byte win would be riding a broken solve).
# bf16 (8-bit mantissa) is allowed more drift than f16 (11-bit);
# envelopes sized 4x above the measured config-1 drift so noise never
# flaps the gate while a real breakage (O(1) drift) always trips it.
DTYPE_DRIFT_ENVELOPE = {"bf16": 0.25, "f16": 0.10}


def config7_dtype(device, dtype):
    """Round-9 config: the mixed-precision traffic melt (ISSUE 6).

    Runs the config-1 problem shape (N=62, M=8, tilesz=10, -j3) under
    each dtype policy at a reduced iteration budget (the per-trip price
    is shape-determined, and the comparison below normalizes trip
    counts anyway), then reports per policy, ALL AT THE f32 RUN'S
    EXECUTED TRIP COUNTS:

      bytes_eq = base_bytes(policy) + solver_trips_f32 x trip(policy)
                 + refine_trips_f32 x refine(policy)

    so ``bytes_vs_f32_pct`` is a pure price delta — trajectory-length
    differences between policies cannot masquerade as traffic savings.
    ``res_drift`` is |res_1/res_1_f32 - 1|; policies beyond their
    DTYPE_DRIFT_ENVELOPE are dropped from the banked record (refusal
    logged). The top-level bytes_accessed/res fields are the f32
    reference's, so the round-stamped bank stays f32-comparable for
    future Δbytes columns.
    """
    from sagecal_tpu.config import SolverMode
    sky, dsky, tiles = build_fullbatch(dtype, n_stations=62, n_clusters=8,
                                       tilesz=10, n_tiles=1)
    runs = {}
    for policy in ("f32", "bf16", "f16"):
        vps, r0, r1, dt, comp, fl = time_sage(
            device, dtype, sky, dsky, tiles,
            SolverMode.OSLM_OSRLM_RLBFGS, reps=1, max_emiter=1,
            max_iter=8, max_lbfgs=4, dtype_policy=policy)
        runs[policy] = dict(value=vps, res_0=r0, res_1=r1, step_s=dt,
                            compile_s=comp, cost=fl)
    f32r = runs["f32"]
    fc = f32r["cost"]
    if (fc is None or not fc.get("solver_trips")
            or not fc.get("solver_trip_bytes")):
        # solver_trip_cost fails version-dependently (its own
        # try/except leaves trip bytes at 0.0 while the trip COUNTER
        # stays nonzero) — a zero price would divide by zero below or
        # bank phantom savings
        out = dict(error="cost analysis unavailable; dtype melt needs "
                         "the priced composition",
                   shape="N=62 M=8 tilesz=10 point -j3 dtype-melt")
        return out

    def bytes_eq(c):
        # equal-trip pricing: THIS policy's prices, the f32 run's trips
        return (c["base_bytes"]
                + fc["solver_trips"] * c["solver_trip_bytes"]
                + fc["refine_trips"] * c["refine_trip_bytes"])

    ref_bytes = bytes_eq(fc)
    out = dict(value=f32r["value"], unit="vis/s", res_0=f32r["res_0"],
               res_1=f32r["res_1"], step_s=f32r["step_s"],
               compile_s=f32r["compile_s"],
               solver_trips=fc["solver_trips"],
               refine_trips=fc["refine_trips"],
               shape="N=62 M=8 tilesz=10 point -j3 dtype-melt")
    _roofline_fields(out, device, {"flops": fc["flops"],
                                   "bytes_accessed": ref_bytes},
                     f32r["step_s"])
    policies = {}
    for policy in ("bf16", "f16"):
        r = runs[policy]
        c = r["cost"]
        if c is None or not c.get("solver_trip_bytes"):
            # a failed reduced-trip price would read as a phantom
            # ~-100% byte saving — refuse instead of banking it
            log(f"# dtype policy {policy}: trip pricing unavailable; "
                "dropping from the record")
            continue
        drift = abs(r["res_1"] / f32r["res_1"] - 1.0) \
            if f32r["res_1"] else float("inf")
        rec = dict(bytes_eq=bytes_eq(c),
                   bytes_vs_f32_pct=round(
                       100.0 * (bytes_eq(c) / ref_bytes - 1.0), 2),
                   trip_bytes=c["solver_trip_bytes"],
                   trip_vs_f32_pct=round(
                       100.0 * (c["solver_trip_bytes"]
                                / fc["solver_trip_bytes"] - 1.0), 2),
                   wall_s=r["step_s"],
                   wall_vs_f32_pct=round(
                       100.0 * (r["step_s"] / f32r["step_s"] - 1.0), 2),
                   res_1=r["res_1"], res_drift=drift)
        env = DTYPE_DRIFT_ENVELOPE[policy]
        if drift > env:
            log(f"# REFUSING to bank dtype policy {policy}: residual "
                f"drift {drift:.3g} exceeds its tolerance envelope "
                f"{env} — the byte win would ride a broken solve")
            rec["refused"] = f"drift {drift:.3g} > envelope {env}"
        policies[policy] = rec
    out["dtype_policies"] = policies
    return out


_SERVE_SKY = """\
P0A 0 40 0 40 0 0 3.0 0 0 0 0 0 0 0 0 150e6
P1A 1 20 0 38 0 0 2.5 0 0 0 0 0 0 0 0 150e6
"""
_SERVE_CLUSTER = "0 1 P0A\n1 2 P1A\n"


def config8_serve(device, dtype):
    """Round-10 config: calibration-as-a-service throughput (ISSUE 8).

    FOUR synthetic jobs in TWO shape buckets (2x tilesz 4, 2x tilesz
    6 — two program-cache keys, sharing within each bucket) run (a)
    serially through the batch pipeline (the 4-solo-CLI-runs
    reference, same process so both legs enjoy the same warm compile
    cache — the comparison isolates the SCHEDULING win, interleaving
    one job's ready tiles into another's host stalls, from the
    compile-sharing win the cache hit rate reports separately) and
    (b) concurrently through the live serve daemon (socket protocol
    and all). Banks jobs/hour, the device-busy fraction and the
    compile-cache hit rate, REFUSES to bank unless every daemon job's
    written residuals and solutions are bit-identical to its serial
    run. Settle-then-alternate timing, min-of-2 per leg (config 6
    contract: compiles never land in a timed rep)."""
    import math as _math
    import shutil
    import tempfile
    import jax.numpy as jnp
    from sagecal_tpu import pipeline as pl
    from sagecal_tpu import skymodel
    from sagecal_tpu.io import dataset as ds_mod
    from sagecal_tpu.rime import predict as rp_mod
    from sagecal_tpu.serve import cache as pcache
    from sagecal_tpu.serve.api import Client, Server, config_from_dict

    tmpd = tempfile.mkdtemp(prefix="sagecal_serve_")
    skyf = os.path.join(tmpd, "sky.txt")
    clusf = skyf + ".cluster"
    with open(skyf, "w") as f:
        f.write(_SERVE_SKY)
    with open(clusf, "w") as f:
        f.write(_SERVE_CLUSTER)
    ra0 = (41 / 60) * _math.pi / 12
    dec0 = 40 * _math.pi / 180
    srcs = skymodel.parse_sky_model(skyf, ra0, dec0, 150e6)
    sky = skymodel.build_cluster_sky(
        srcs, skymodel.parse_cluster_file(clusf))
    dsky = rp_mod.sky_to_device(sky, jnp.float32)
    # streaming-shaped jobs (the config-6 lesson): many short solve
    # intervals over a wide band, where io+stage+residual-fetch+write
    # is a real share of wall — the share the daemon can fill with a
    # neighbour's ready tile. Tiny 0.5 s jobs measure only the
    # daemon's fixed per-job costs
    n_stations, n_tiles, nchan = 16, 8, 24
    Jt = ds_mod.random_jones(sky.n_clusters, sky.nchunk, n_stations,
                             seed=5, scale=0.15)
    freqs = np.linspace(149e6, 151e6, nchan)
    jobs = []          # (name, tilesz, serial msdir, daemon msdir)
    for jn, tilesz in enumerate((4, 4, 6, 6)):
        tiles = [ds_mod.simulate_dataset(
            dsky, n_stations=n_stations, tilesz=tilesz, freqs=freqs,
            ra0=ra0, dec0=dec0, jones=Jt, nchunk=sky.nchunk,
            noise_sigma=0.02, seed=SEED + 80 + 10 * jn + t)
            for t in range(n_tiles)]
        ms_s = os.path.join(tmpd, f"job{jn}_serial.ms")
        ds_mod.SimMS.create(ms_s, tiles)
        ms_d = os.path.join(tmpd, f"job{jn}_daemon.ms")
        shutil.copytree(ms_s, ms_d)
        jobs.append((f"job{jn}", tilesz, ms_s, ms_d))
    noop = (lambda *a: None)

    def job_cfg(tilesz, msdir, sol):
        # prefetch 2 on BOTH legs (bit-identical by the overlap
        # contract): the scheduler's sticky bound is depth + 1, so a
        # deeper per-job prefetch trades a little staging memory for
        # fewer compiled-program alternations between shape buckets
        return dict(ms=msdir, sky_model=skyf, cluster_file=clusf,
                    solver_mode=0, max_em_iter=1, max_iter=4,
                    max_lbfgs=2, tile_size=tilesz, solutions_file=sol,
                    prefetch=2)

    def run_serial():
        t0 = time.perf_counter()
        for name, tilesz, ms_s, _ in jobs:
            cfg = config_from_dict(job_cfg(
                tilesz, ms_s, os.path.join(tmpd, f"{name}_serial.sol")))
            pl.run(cfg, log=noop)
        return time.perf_counter() - t0

    def run_serial_cli():
        # the ISSUE's reference leg and the production UX the service
        # replaces: each job is its OWN CLI process with a cold jax
        # import and compile cache — the loop-turnaround price
        # (CubiCal arXiv:1805.03410 / SKA-GPU arXiv:1910.13908) that
        # the daemon's warm process amortizes across tenants. Measured
        # once: the compile wall dominates and dwarfs rep noise.
        t0 = time.perf_counter()
        for name, tilesz, ms_s, _ in jobs:
            argv = [sys.executable, "-m", "sagecal_tpu.cli",
                    "-d", ms_s, "-s", skyf, "-c", clusf,
                    "-j", "0", "-e", "1", "-g", "4", "-l", "2",
                    "-t", str(tilesz), "--prefetch", "2",
                    "-p", os.path.join(tmpd, f"{name}_serial.sol")]
            if device.platform == "cpu":
                argv += ["--platform", "cpu"]
            r = subprocess.run(argv, capture_output=True, text=True)
            if r.returncode:
                raise RuntimeError(
                    f"serial CLI {name} rc={r.returncode}: "
                    f"{(r.stderr or '')[-200:]}")
        return time.perf_counter() - t0

    def run_daemon():
        # the server is PERSISTENT by definition — its thread/socket
        # startup is amortized over a process lifetime, so the timed
        # wall is steady-state submit -> all-done
        srv = Server(port=0, max_inflight=4)
        srv.start()
        try:
            with Client(port=srv.port) as c:
                c.request(op="ping")
                # the DAEMON LEG's own compile-cache traffic: the
                # ProgramCache is a process singleton also warmed by
                # the serial control legs, so the banked hit rate must
                # be the delta across this leg, not the process total
                cs0 = pcache.PROGRAMS.stats()
                t0 = time.perf_counter()
                ids = [c.submit(job_cfg(
                    tilesz, ms_d,
                    os.path.join(tmpd, f"{name}_daemon.sol")))
                    for name, tilesz, _, ms_d in jobs]
                # drain(wait) blocks server-side until every accepted
                # job finished — the completion signal, with NO status
                # polling stealing host cycles from the solve
                c.drain(wait=True)
                wall = time.perf_counter() - t0
                m = c.metrics()
                cs1 = pcache.PROGRAMS.stats()
                dh = cs1["hits"] - cs0["hits"]
                dm = cs1["misses"] - cs0["misses"]
                m["hit_rate"] = dh / (dh + dm) if dh + dm else 1.0
                m["hits"], m["misses"] = dh, dm
                for jid in ids:
                    snap = c.status(jid)
                    if snap["state"] != "done":
                        raise RuntimeError(
                            f"daemon job {jid}: {snap['state']} "
                            f"({snap.get('error')})")
        finally:
            srv.stop()
        return wall, m

    # settle: both legs once, untimed — both shape buckets compile
    # here, never inside a timed rep
    t_w0 = time.perf_counter()
    run_serial()
    run_daemon()
    comp_wall = time.perf_counter() - t_w0
    walls_s, walls_d, metrics_d = [], [], None
    for _rep in range(3):
        walls_s.append(run_serial())
        wall, m = run_daemon()
        walls_d.append(wall)
        metrics_d = m
    wall_serial = min(walls_s)
    wall_conc = min(walls_d)
    # the headline serial leg LAST: it rewrites the *_serial outputs
    # (same bits — identical configs/data), so the bit-identity gate
    # below compares the daemon against actual CLI-process output
    wall_cli = run_serial_cli()

    # bit-identity gate: every daemon job vs its serial (solo) run
    for name, _tilesz, ms_s, ms_d in jobs:
        out_s = ds_mod.SimMS(ms_s, data_column="CORRECTED_DATA")
        out_d = ds_mod.SimMS(ms_d, data_column="CORRECTED_DATA")
        for i in range(n_tiles):
            if not np.array_equal(out_s.read_tile(i).x,
                                  out_d.read_tile(i).x):
                return {"error": f"{name}: daemon residuals NOT "
                                 "bit-identical to the serial run"}
        with open(os.path.join(tmpd, f"{name}_serial.sol")) as f0, \
                open(os.path.join(tmpd, f"{name}_daemon.sol")) as f1:
            if f0.read() != f1.read():
                return {"error": f"{name}: daemon solutions NOT "
                                 "bit-identical to the serial run"}

    rec = dict(
        value=len(jobs) / wall_conc * 3600.0, unit="jobs/h",
        step_s=wall_conc / len(jobs),
        compile_s=max(comp_wall - wall_serial - wall_conc, 0.0),
        n_jobs=len(jobs), shape_buckets=2,
        # the acceptance comparison (ISSUE 8): the same 4 jobs run
        # serially via the CLI — 4 cold processes, the production UX
        wall_serial_cli_s=wall_cli,
        dwall_pct=100.0 * (wall_conc - wall_cli) / wall_cli,
        # the equal-warmth scheduling-only comparison (in-process
        # serial sharing the same warm cache): on a host whose
        # "device" shares cores with the reader threads this is
        # parity within noise — recorded, not hidden
        wall_serial_warm_s=wall_serial,
        dwall_warm_pct=100.0 * (wall_conc - wall_serial) / wall_serial,
        wall_concurrent_s=wall_conc,
        walls_serial_warm=[round(w, 3) for w in walls_s],
        walls_concurrent=[round(w, 3) for w in walls_d],
        device_busy_frac=metrics_d["device_busy_frac"],
        cache_hit_rate=metrics_d["hit_rate"],
        cache_hits=metrics_d["hits"], cache_misses=metrics_d["misses"],
        tiles_total=metrics_d["tiles_done"],
        bit_identical=True,
        shape=f"4 jobs x {n_tiles}tiles N={n_stations} M=2 F={nchan} "
              f"tilesz 4,4,6,6 -j0 e1g4l2 daemon-vs-cli-serial")
    prog = pcache.PROGRAMS.stats()
    rec["program_cache"] = prog
    return rec


def stamp_family(rec: dict, platform: str, family: str,
                 config_name: str, first_round: int,
                 bank_dir: str | None = None) -> str:
    """Round-stamp one record of a standalone record family
    (``<FAMILY>_rNN.json`` — the BSCALING/MULTICHIP precedent: its own
    filename series, judged by the sentinel's family tolerances
    instead of the BENCH table columns). NN = 1 + the newest committed
    round of the family, starting at ``first_round`` (the PR round
    that introduced it). Never overwrites an existing round; the
    sentinel's loaders read the ``{"platform", "results": {name:
    rec}}`` envelope written here.

    Family names are EXACT-MATCH: ``[A-Z][A-Z0-9]*`` only (an
    underscore would make ``<FAMILY>_rNN`` unparseable), and a name
    that is a prefix of — or prefixed by — a family already banked in
    ``bank_dir`` is REFUSED: the PR 14 round landed a stray
    ``MESH_r13.json`` next to ``MESH2D_r13.json``, and two families
    whose names nest are one typo away from cross-reading each
    other's rounds (regression-gated in tests/test_router.py)."""
    import glob as _glob
    import re as _re
    # SAGECAL_BANK_DIR: the burn-down --dry-run's scratch-bank
    # redirect — bench configs stamp their family records there
    # instead of the repo root, so a CI rehearsal never touches the
    # committed rounds (tools_dev/burndown.py)
    bank_dir = (bank_dir or os.environ.get("SAGECAL_BANK_DIR")
                or HERE)
    if not _re.fullmatch(r"[A-Z][A-Z0-9]*", family):
        raise ValueError(
            f"stamp_family: family {family!r} must match "
            "[A-Z][A-Z0-9]* (no underscores — '_rNN' is the round "
            "separator)")
    on_disk = set()
    for p in _glob.glob(os.path.join(bank_dir, "*_r[0-9]*.json")):
        m = _re.fullmatch(r"([A-Z][A-Z0-9]*)_r(\d+)\.json",
                          os.path.basename(p))
        if m:
            on_disk.add(m.group(1))
    for other in sorted(on_disk):
        if other != family and (other.startswith(family)
                                or family.startswith(other)):
            raise ValueError(
                f"stamp_family: family {family!r} prefix-collides "
                f"with banked family {other!r}; pick a name neither "
                "prefixes")
    rounds = [int(m.group(2)) for p in
              _glob.glob(os.path.join(bank_dir, f"{family}_r*.json"))
              if (m := _re.fullmatch(
                  r"([A-Z][A-Z0-9]*)_r(\d+)\.json",
                  os.path.basename(p))) and m.group(1) == family]
    nn = max(rounds, default=first_round - 1) + 1
    path = os.path.join(bank_dir, f"{family}_r{nn:02d}.json")
    with open(path, "w") as f:
        json.dump({"platform": platform,
                   "date": time.strftime("%Y-%m-%d %H:%M:%S"),
                   "results": {config_name: rec}},
                  f, indent=1, default=float)
    return path


def _stamp_fleet(rec: dict, platform: str) -> str:
    """Round-stamp the fleet record (FLEET_rNN.json; first round is
    12 — the ISSUE 12 PR)."""
    return stamp_family(rec, platform, "FLEET", "9-fleet-throughput",
                        first_round=12)


def config9_fleet(device, dtype):
    """Round-12 config: fleet-scale serving throughput (ISSUE 12).

    The SAME seeded traffic replay (serve/loadgen.py: 8 jobs, 2 shape
    buckets, burst arrival, streaming-ingest pacing) drives the
    daemon twice — one device, then a 2-virtual-device fleet — and
    banks aggregate throughput scaling, p99 queue wait, per-device
    cache hit rate, and (from a dedicated leg) the measured cost of a
    tile-boundary migration. REFUSES to bank unless every replay
    job's residuals + solutions are bit-identical to a solo run of
    its template, and unless the migrated job re-ran ZERO tiles.

    Measurement regime, stated honestly: with ingest pacing each
    tenant's tile stream is rate-limited (the quasi-real-time
    LOFAR/SKA arrival model, arXiv:1410.2101), so per-device
    throughput is bounded by per-device ADMISSION (a device-memory
    budget) times the stream rate, not by solve FLOPs — the regime
    where a fleet scales linearly and where this host (virtual CPU
    devices sharing one core) can measure the scheduling/placement
    machinery without pretending the core count doubled. The
    per-device busy fractions ride the record so the regime is
    visible; on real multi-chip hardware the same config measures
    compute-bound scaling."""
    import shutil
    import tempfile
    import jax
    from sagecal_tpu import pipeline as pl
    from sagecal_tpu.io import dataset as ds
    from sagecal_tpu.serve import cache as pcache
    from sagecal_tpu.serve import loadgen
    from sagecal_tpu.serve.api import Client, Server, config_from_dict

    if len(jax.devices()) < 2:
        return {"error": "fleet bench needs >= 2 (virtual) devices"}
    noop = (lambda *a: None)
    tmpd = tempfile.mkdtemp(prefix="sagecal_fleet_")
    PACE = 0.5          # s/tile ingest pacing; per-tile solve is
    #                     ~0.1 s at these shapes (config 8), so even
    #                     the 4-concurrent-job fleet leg keeps the
    #                     single-core host unsaturated — the scaling
    #                     measured is admission/ingest, not luck
    N_TILES = 6
    spec = {
        "seed": 12, "n_jobs": 8,
        "arrival": {"process": "burst"},
        "templates": [
            {"name": "bucket4", "weight": 1, "n_stations": 16,
             "tilesz": 4, "n_tiles": N_TILES, "nchan": 24,
             "config": {"tile_arrival_s": PACE}},
            {"name": "bucket6", "weight": 1, "n_stations": 16,
             "tilesz": 6, "n_tiles": N_TILES, "nchan": 24,
             "config": {"tile_arrival_s": PACE}}]}
    fixtures = loadgen.build_fixtures(spec, tmpd)

    def leg(n_devices, tag):
        work = os.path.join(tmpd, f"leg_{tag}")
        os.makedirs(work, exist_ok=True)
        srv = Server(port=0, max_inflight=2, devices=n_devices)
        # work stealing OFF in the throughput legs: placement is the
        # subject here; migration is priced by its own leg below
        srv.scheduler.MIGRATE_MIN_REMAINING_TILES = 10 ** 6
        srv.start()
        cs0 = pcache.PROGRAMS.stats_by_device()
        try:
            with Client(port=srv.port) as c:
                rec = loadgen.replay(c, spec, fixtures, work, log=noop)
                m = c.metrics()
        finally:
            srv.stop()
        cs1 = pcache.PROGRAMS.stats_by_device()
        # per-device cache traffic DELTA across this leg only (the
        # process cache is shared with the other legs)
        cache = {}
        for dev in sorted(cs1):
            h = cs1[dev]["hits"] - cs0.get(dev, {}).get("hits", 0)
            mi = cs1[dev]["misses"] - cs0.get(dev, {}).get("misses", 0)
            if h or mi:
                cache[dev] = {"hits": h, "misses": mi,
                              "hit_rate": h / (h + mi) if h + mi
                              else 1.0}
        rec["cache_by_device"] = cache
        rec["device_busy_frac"] = m["device_busy_frac"]
        rec["devices"] = [
            {k: d[k] for k in ("device", "busy_frac", "tiles_done",
                               "jobs_done")}
            for d in m["devices"]]
        if rec["states"] != {"done": rec["n_jobs"]}:
            raise RuntimeError(f"leg {tag}: jobs not all done: "
                               f"{rec['states']}")
        return rec

    # solo references (one per template — every replay job is a byte
    # copy of its template, so one solo run is THE reference for all)
    solo = {}
    for name, f in fixtures.items():
        msdir = os.path.join(tmpd, f"solo_{name}.ms")
        shutil.copytree(f["ms"], msdir)
        solp = os.path.join(tmpd, f"solo_{name}.sol")
        cfg = loadgen.job_config(spec, name, msdir, solp)
        cfg.update(sky_model=f["sky"], cluster_file=f["cluster"])
        pl.run(config_from_dict(cfg), log=noop)
        out = ds.SimMS(msdir, data_column="CORRECTED_DATA")
        solo[name] = ([out.read_tile(i).x.copy()
                       for i in range(out.n_tiles)],
                      open(solp).read())

    def assert_bit_identical(rec, tag):
        for row in rec["jobs"]:
            res, sol_text = solo[row["template"]]
            out = ds.SimMS(row["ms"], data_column="CORRECTED_DATA")
            for i in range(out.n_tiles):
                if not np.array_equal(out.read_tile(i).x, res[i]):
                    return (f"{tag}/{row['job_id']}: residuals NOT "
                            "bit-identical to the solo run")
            if open(row["solutions"]).read() != sol_text:
                return (f"{tag}/{row['job_id']}: solutions NOT "
                        "bit-identical to the solo run")
        return None

    # settle both arms: every (bucket, device) program pair compiles
    # here, never inside a timed rep (the config 6/8 contract)
    t_w0 = time.perf_counter()
    leg(1, "settle1")
    leg(2, "settle2")
    comp_wall = time.perf_counter() - t_w0
    # timed: min-of-2 per arm, alternating
    legs1, legs2 = [], []
    for rep in range(2):
        legs1.append(leg(1, f"d1_{rep}"))
        legs2.append(leg(2, f"d2_{rep}"))
    for tag, rec in (("1dev0", legs1[0]), ("1dev1", legs1[1]),
                     ("2dev0", legs2[0]), ("2dev1", legs2[1])):
        err = assert_bit_identical(rec, tag)
        if err:
            return {"error": err}
    r1 = min(legs1, key=lambda r: r["wall_s"])
    r2 = min(legs2, key=lambda r: r["wall_s"])

    # migration leg: one paced job on the 2-device fleet, migrated at
    # a tile boundary via the api op — wall + tiles re-run measured
    mig_ms = os.path.join(tmpd, "mig.ms")
    shutil.copytree(fixtures["bucket4"]["ms"], mig_ms)
    mig_sol = os.path.join(tmpd, "mig.sol")
    mig_cfg = loadgen.job_config(spec, "bucket4", mig_ms, mig_sol)
    mig_cfg.update(sky_model=fixtures["bucket4"]["sky"],
                   cluster_file=fixtures["bucket4"]["cluster"])
    srv = Server(port=0, max_inflight=2, devices=2)
    srv.scheduler.MIGRATE_MIN_REMAINING_TILES = 2
    srv.start()
    try:
        with Client(port=srv.port) as c:
            jid = c.submit(mig_cfg)
            t_dead = time.monotonic() + 60
            while True:
                snap = c.status(jid)
                if snap["state"] == "running" \
                        and 1 <= snap["tiles_done"] <= 3:
                    break
                if time.monotonic() > t_dead or snap["state"] not in \
                        ("queued", "running"):
                    return {"error": f"migration leg: job stuck in "
                                     f"{snap['state']}"}
                time.sleep(0.02)
            c.migrate(jid, 1)
            snap = c.wait(jid, timeout_s=120)
            if snap["state"] != "done" or not snap["migrations"]:
                return {"error": "migration leg: job did not migrate "
                                 f"and finish ({snap['state']})"}
            mig = snap["migrations"][0]
    finally:
        srv.stop()
    if mig["tiles_rerun"] != 0:
        return {"error": f"migration re-ran {mig['tiles_rerun']} "
                         "tiles; refusing to bank"}
    out = ds.SimMS(mig_ms, data_column="CORRECTED_DATA")
    res, sol_text = solo["bucket4"]
    for i in range(out.n_tiles):
        if not np.array_equal(out.read_tile(i).x, res[i]):
            return {"error": "migrated job NOT bit-identical to the "
                             "solo run; refusing to bank"}
    if open(mig_sol).read() != sol_text:
        return {"error": "migrated job solutions NOT bit-identical; "
                         "refusing to bank"}

    thr1 = r1["throughput_jobs_per_s"]
    thr2 = r2["throughput_jobs_per_s"]
    cache2 = r2["cache_by_device"]
    rec = dict(
        value=thr2 / thr1, unit="x-thr 1->2dev",
        step_s=r2["wall_s"] / r2["n_jobs"],
        compile_s=max(comp_wall - r1["wall_s"] - r2["wall_s"], 0.0),
        n_jobs=spec["n_jobs"], shape_buckets=2, n_tiles=N_TILES,
        scaling_1to2=thr2 / thr1,
        throughput_1dev_jobs_h=thr1 * 3600.0,
        throughput_2dev_jobs_h=thr2 * 3600.0,
        throughput_per_device_1dev_jobs_h=thr1 * 3600.0,
        throughput_per_device_2dev_jobs_h=thr2 * 3600.0 / 2,
        wall_1dev_s=r1["wall_s"], wall_2dev_s=r2["wall_s"],
        walls_1dev=[r["wall_s"] for r in legs1],
        walls_2dev=[r["wall_s"] for r in legs2],
        p50_queue_wait_1dev_s=r1["queue_wait_p50_s"],
        p99_queue_wait_1dev_s=r1["queue_wait_p99_s"],
        p50_queue_wait_2dev_s=r2["queue_wait_p50_s"],
        p99_queue_wait_2dev_s=r2["queue_wait_p99_s"],
        e2e_p99_1dev_s=r1["e2e_p99_s"], e2e_p99_2dev_s=r2["e2e_p99_s"],
        device_busy_frac_1dev=r1["device_busy_frac"],
        device_busy_frac_2dev=r2["device_busy_frac"],
        cache_by_device_2dev={str(k): v for k, v in cache2.items()},
        cache_hit_rate_min_2dev=min(
            (v["hit_rate"] for v in cache2.values()), default=1.0),
        migration=dict(wall_s=mig["wall_s"], yield_s=mig["yield_s"],
                       tile=mig["tile"], tiles_rerun=mig["tiles_rerun"],
                       src=mig["src"], dst=mig["dst_actual"],
                       bit_identical=True),
        ingest=dict(
            tile_arrival_s=PACE, arrival="burst",
            # the floor an ideal scheduler cannot beat: waves of
            # admitted jobs, each paced to n_tiles * PACE (job tile 0
            # arrives unpaced, so measured walls sit slightly under)
            floor_1dev_s=-(-spec["n_jobs"] // 2) * N_TILES * PACE,
            floor_2dev_s=-(-spec["n_jobs"] // 4) * N_TILES * PACE,
            regime="ingest/admission-limited: per-tenant streaming "
                   "pacing bounds per-job rate, so throughput = "
                   "admission slots x stream rate and both legs' "
                   "walls sit on their ingest floors — the regime "
                   "where a fleet scales linearly, measured on the "
                   "scheduling/placement machinery. NOT a CPU "
                   "compute-scaling claim: the virtual devices share "
                   "one host core, and the 2dev busy fractions are "
                   "inflated by cross-thread timeslicing (each "
                   "step's wall includes preemption by the other "
                   "owner loop); the compute-bound TPU verdict "
                   "awaits a healthy chip window"),
        bit_identical=True,
        shape=f"8 jobs x {N_TILES}tiles N=16 M=2 F=24 tilesz 4,6 "
              f"pace{PACE} burst 1dev-vs-2dev e1g4l2")
    rec["program_cache"] = pcache.PROGRAMS.stats()
    try:
        rec["fleet_record"] = _stamp_fleet(
            rec, jax.devices()[0].platform)
    except Exception as e:        # the bench result still stands
        log(f"# fleet record stamping failed: {e}")
    return rec


def _stamp_scaleout(rec: dict, platform: str) -> str:
    """Round-stamp the cross-process scale-out record
    (SCALEOUT_rNN.json; first round is 15 — the ISSUE 15 PR)."""
    return stamp_family(rec, platform, "SCALEOUT", "10-scaleout",
                        first_round=15)


def config10_scaleout(device, dtype):
    """Round-15 config: cross-process fleet scale-out (ISSUE 15).

    The SAME seeded traffic replay as config 9 drives a ROUTER
    (serve/router.py) fronting W = 1, 2, 4 real WORKER PROCESSES
    (``python -m sagecal_tpu.serve --worker --router ...``), plus two
    dedicated legs: a cross-process tile-boundary migration (the api
    ``migrate`` op, cancel-at-boundary + shared-filesystem checkpoint
    resume) and a worker-LOSS recovery (the ``worker_crash`` fault
    point kills a worker mid-job; the router's lease eviction
    re-queues its job onto the survivor as a resume). REFUSES to bank
    unless every replay job's residuals + solutions are bit-identical
    to a solo run of its template, and unless BOTH the migrated and
    the recovered job re-ran ZERO completed tiles.

    Measurement regime, stated honestly (the config 9 discipline one
    level up): with per-tenant ingest pacing, throughput is bounded by
    fleet-wide admission slots x stream rate, not solve FLOPs — the
    regime where worker processes scale linearly and which a host with
    few cores can measure without pretending its core count grew. The
    host's real core count rides the record; on a genuinely multi-core
    host the same config (pacing off) measures compute-bound process
    scaling, and per-worker busy walls are recorded either way."""
    import shutil
    import subprocess
    import sys as _sys
    import tempfile
    import jax
    from sagecal_tpu import pipeline as pl
    from sagecal_tpu.io import dataset as ds
    from sagecal_tpu.serve import loadgen
    from sagecal_tpu.serve.api import Client, config_from_dict
    from sagecal_tpu.serve.router import Router

    noop = (lambda *a: None)
    tmpd = tempfile.mkdtemp(prefix="sagecal_scaleout_")
    PACE = 0.5
    N_TILES = 6
    LEASE_S = 2.0
    spec = {
        "seed": 12, "n_jobs": 8,
        "arrival": {"process": "burst"},
        "templates": [
            {"name": "bucket4", "weight": 1, "n_stations": 16,
             "tilesz": 4, "n_tiles": N_TILES, "nchan": 24,
             "config": {"tile_arrival_s": PACE, "prefetch": 0}},
            {"name": "bucket6", "weight": 1, "n_stations": 16,
             "tilesz": 6, "n_tiles": N_TILES, "nchan": 24,
             "config": {"tile_arrival_s": PACE, "prefetch": 0}}]}
    fixtures = loadgen.build_fixtures(spec, tmpd)
    worker_env = dict(os.environ, JAX_PLATFORMS="cpu")

    def spawn_worker(rport, name, faults=None):
        args = [_sys.executable, "-m", "sagecal_tpu.serve",
                "--worker", "--router", f"127.0.0.1:{rport}",
                "--port", "0", "--max-inflight", "2",
                "--worker-id", name]
        if faults:
            args += ["--faults", faults]
        logf = open(os.path.join(tmpd, f"{name}.log"), "w")
        return subprocess.Popen(args, stdout=logf,
                                stderr=subprocess.STDOUT,
                                env=worker_env, cwd=HERE)

    def wait_alive(r, n, timeout=240):
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout:
            if r.metrics()["n_alive"] >= n:
                return
            time.sleep(0.1)
        raise RuntimeError(f"fleet never reached {n} alive workers")

    def stop_all(r, procs):
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
        r.stop()

    def run_topology(W):
        """Router + W fresh worker processes; one settle replay (every
        worker's programs compile OUTSIDE the timed legs), two timed
        replays (min wall wins), per-worker cache-hit DELTAS across
        the timed legs only. Returns (best, legs, cache, pipelining)."""
        r = Router(port=0, lease_s=LEASE_S, heartbeat_s=0.4, log=noop)
        r.start()
        procs = [spawn_worker(r.port, f"w{W}_{i}") for i in range(W)]
        legs = []
        try:
            wait_alive(r, W)
            with Client(port=r.port) as c:
                work = os.path.join(tmpd, f"settle_w{W}")
                loadgen.replay(c, spec, fixtures, work, log=noop,
                               drain=False, tag=f"s{W}")
                m0 = c.metrics()
                for rep in range(2):
                    work = os.path.join(tmpd, f"leg_w{W}_{rep}")
                    rec = loadgen.replay(c, spec, fixtures, work,
                                         log=noop, drain=False,
                                         tag=f"t{W}{rep}")
                    if rec["states"] != {"done": rec["n_jobs"]}:
                        raise RuntimeError(
                            f"W={W} rep{rep}: jobs not all done: "
                            f"{rec['states']}")
                    legs.append(rec)
                m1 = c.metrics()
                pipelining = None
                if W == 2:
                    # the Client-pipelining satellite, measured where
                    # it matters: status polls against the router
                    # (which proxies each to the owning worker)
                    jid = legs[-1]["jobs"][0]["job_id"]
                    NOPS = 100
                    t0 = time.perf_counter()
                    for _ in range(NOPS):
                        c.status(jid)
                    seq_s = time.perf_counter() - t0
                    t0 = time.perf_counter()
                    c.status_many([jid] * NOPS)
                    pipe_s = time.perf_counter() - t0
                    pipelining = dict(
                        n_ops=NOPS, sequential_s=round(seq_s, 4),
                        pipelined_s=round(pipe_s, 4),
                        sequential_per_op_ms=round(seq_s / NOPS * 1e3,
                                                   4),
                        pipelined_per_op_ms=round(pipe_s / NOPS * 1e3,
                                                  4),
                        saving_pct=round(
                            100.0 * (1 - pipe_s / seq_s), 1))
        finally:
            stop_all(r, procs)
        cache = {}
        w0 = {w["worker_id"]: w["cache"] for w in m0["workers"]}
        for w in m1["workers"]:
            c0 = w0.get(w["worker_id"], {})
            h = w["cache"].get("hits", 0) - c0.get("hits", 0)
            mi = w["cache"].get("misses", 0) - c0.get("misses", 0)
            cache[w["worker_id"]] = {
                "hits": h, "misses": mi,
                "hit_rate": (h / (h + mi)) if h + mi else 1.0}
        best = min(legs, key=lambda rec: rec["wall_s"])
        return best, legs, cache, pipelining

    # solo references (one per template — every replay job is a byte
    # copy of its template; the bench process and the workers share
    # the same default-config CPU backend, so in-process solo runs are
    # THE bit-identity reference, the config 9 discipline)
    solo = {}
    for name, f in fixtures.items():
        msdir = os.path.join(tmpd, f"solo_{name}.ms")
        shutil.copytree(f["ms"], msdir)
        solp = os.path.join(tmpd, f"solo_{name}.sol")
        cfg = loadgen.job_config(spec, name, msdir, solp)
        cfg.update(sky_model=f["sky"], cluster_file=f["cluster"])
        pl.run(config_from_dict(cfg), log=noop)
        out = ds.SimMS(msdir, data_column="CORRECTED_DATA")
        solo[name] = ([out.read_tile(i).x.copy()
                       for i in range(out.n_tiles)],
                      open(solp).read())

    def assert_bit_identical(rec, tag):
        for row in rec["jobs"]:
            res, sol_text = solo[row["template"]]
            out = ds.SimMS(row["ms"], data_column="CORRECTED_DATA")
            for i in range(out.n_tiles):
                if not np.array_equal(out.read_tile(i).x, res[i]):
                    return (f"{tag}/{row['job_id']}: residuals NOT "
                            "bit-identical to the solo run")
            if open(row["solutions"]).read() != sol_text:
                return (f"{tag}/{row['job_id']}: solutions NOT "
                        "bit-identical to the solo run")
        return None

    t_w0 = time.perf_counter()
    topo = {}
    for W in (1, 2, 4):
        topo[W] = run_topology(W)
    comp_wall = time.perf_counter() - t_w0
    for W, (best, legs, _c, _p) in topo.items():
        for i, rec in enumerate(legs):
            err = assert_bit_identical(rec, f"w{W}_rep{i}")
            if err:
                return {"error": err}

    # -- cross-process migration leg ----------------------------------------
    def paced_job_cfg(name, msdir, solp):
        cfg = loadgen.job_config(spec, name, msdir, solp)
        cfg.update(sky_model=fixtures[name]["sky"],
                   cluster_file=fixtures[name]["cluster"])
        return cfg

    r = Router(port=0, lease_s=LEASE_S, heartbeat_s=0.2, log=noop)
    r.start()
    procs = [spawn_worker(r.port, "mig_a"), spawn_worker(r.port, "mig_b")]
    try:
        wait_alive(r, 2)
        mig_ms = os.path.join(tmpd, "mig.ms")
        shutil.copytree(fixtures["bucket4"]["ms"], mig_ms)
        mig_sol = os.path.join(tmpd, "mig.sol")
        with Client(port=r.port) as c:
            jid = c.submit(paced_job_cfg("bucket4", mig_ms, mig_sol))
            t_dead = time.monotonic() + 180
            while True:
                snap = c.status(jid)
                if snap["state"] == "running" \
                        and 1 <= snap["tiles_done"] <= 3:
                    break
                if time.monotonic() > t_dead or snap["state"] not in \
                        ("queued", "dispatched", "running"):
                    return {"error": "migration leg: job stuck in "
                                     f"{snap['state']}"}
                time.sleep(0.02)
            src = snap["worker"]
            dst = "mig_b" if src == "mig_a" else "mig_a"
            c.request(op="migrate", job_id=jid, worker=dst)
            snap = c.wait(jid, timeout_s=300)
            if snap["state"] != "done" or not snap["hops"]:
                return {"error": "migration leg: job did not migrate "
                                 f"and finish ({snap['state']})"}
            mig = snap["hops"][0]
    finally:
        stop_all(r, procs)
    if mig.get("tiles_rerun") != 0:
        return {"error": f"cross-process migration re-ran "
                         f"{mig.get('tiles_rerun')} tiles; refusing "
                         "to bank"}
    out = ds.SimMS(mig_ms, data_column="CORRECTED_DATA")
    res, sol_text = solo["bucket4"]
    for i in range(out.n_tiles):
        if not np.array_equal(out.read_tile(i).x, res[i]):
            return {"error": "migrated job NOT bit-identical to the "
                             "solo run; refusing to bank"}
    if open(mig_sol).read() != sol_text:
        return {"error": "migrated job solutions NOT bit-identical; "
                         "refusing to bank"}

    # -- worker-loss recovery leg -------------------------------------------
    CRASH_TILE = 3
    import json as _json
    plan = _json.dumps({"rules": [{"point": "worker_crash",
                                   "at": [f"crash-r15:{CRASH_TILE}"]}]})
    r = Router(port=0, lease_s=LEASE_S, heartbeat_s=0.2, log=noop)
    r.start()
    procs = [spawn_worker(r.port, "crash_w1", faults=plan)]
    try:
        wait_alive(r, 1)
        with Client(port=r.port) as c:
            # warm crash_w1's bucket4 programs so the crash job's tile
            # cadence is the PACE (heartbeats must observe every
            # boundary before the crash)
            wm_ms = os.path.join(tmpd, "warm.ms")
            shutil.copytree(fixtures["bucket4"]["ms"], wm_ms)
            wcfg = paced_job_cfg("bucket4", wm_ms,
                                 os.path.join(tmpd, "warm.sol"))
            wcfg["tile_arrival_s"] = 0.0
            wid = c.submit(wcfg)
            if c.wait(wid, timeout_s=300)["state"] != "done":
                return {"error": "recovery leg: warm-up job failed"}
            crash_ms = os.path.join(tmpd, "crash.ms")
            shutil.copytree(fixtures["bucket4"]["ms"], crash_ms)
            crash_sol = os.path.join(tmpd, "crash.sol")
            jid = c.submit(paced_job_cfg("bucket4", crash_ms,
                                         crash_sol),
                           job_id="crash-r15")
            # the survivor registers while the doomed worker solves
            procs.append(spawn_worker(r.port, "crash_w2"))
            wait_alive(r, 2)
            snap = c.wait(jid, timeout_s=300)
            if snap["state"] != "done" or not snap["hops"]:
                return {"error": "recovery leg: job did not recover "
                                 f"({snap['state']}: {snap.get('error')})"}
            rec_hop = snap["hops"][0]
            m_rec = c.metrics()
    finally:
        stop_all(r, procs)
    if rec_hop.get("reason") != "worker_lost" \
            or rec_hop.get("tiles_rerun") != 0 \
            or rec_hop.get("resume_tile") != CRASH_TILE:
        return {"error": f"recovery hop not clean: {rec_hop}; "
                         "refusing to bank"}
    out = ds.SimMS(crash_ms, data_column="CORRECTED_DATA")
    res, sol_text = solo["bucket4"]
    for i in range(out.n_tiles):
        if not np.array_equal(out.read_tile(i).x, res[i]):
            return {"error": "recovered job NOT bit-identical to the "
                             "solo run; refusing to bank"}
    if open(crash_sol).read() != sol_text:
        return {"error": "recovered job solutions NOT bit-identical; "
                         "refusing to bank"}

    r1, legs1, cache1, _ = topo[1]
    r2, legs2, cache2, pipelining = topo[2]
    r4, legs4, cache4, _ = topo[4]
    thr1 = r1["throughput_jobs_per_s"]
    thr2 = r2["throughput_jobs_per_s"]
    thr4 = r4["throughput_jobs_per_s"]
    recovery_wall = round((rec_hop.get("detect_s") or 0.0)
                          + rec_hop["wall_s"], 3)
    floors = {W: -(-spec["n_jobs"] // (2 * W)) * N_TILES * PACE
              for W in (1, 2, 4)}
    # a leg well above its ingest floor left the paced regime: its
    # concurrent solves saturated the host cores (recorded so the
    # scaling numbers cannot be read past the host's core count)
    over_floor = [f"{W}w" for W, (best, _l, _c, _p) in topo.items()
                  if best["wall_s"] > 1.5 * floors[W]]
    rec = dict(
        workers="cpu",      # worker_env above pins them, whatever this
        #                     child runs on
        value=thr2 / thr1, unit="x-thr 1->2proc",
        step_s=r2["wall_s"] / r2["n_jobs"],
        compile_s=max(comp_wall - r1["wall_s"] - r2["wall_s"]
                      - r4["wall_s"], 0.0),
        n_jobs=spec["n_jobs"], shape_buckets=2, n_tiles=N_TILES,
        host_cores=os.cpu_count(),
        scaling_1to2=thr2 / thr1,
        scaling_1to4=thr4 / thr1,
        throughput_1w_jobs_h=thr1 * 3600.0,
        throughput_2w_jobs_h=thr2 * 3600.0,
        throughput_4w_jobs_h=thr4 * 3600.0,
        wall_1w_s=r1["wall_s"], wall_2w_s=r2["wall_s"],
        wall_4w_s=r4["wall_s"],
        walls_1w=[x["wall_s"] for x in legs1],
        walls_2w=[x["wall_s"] for x in legs2],
        walls_4w=[x["wall_s"] for x in legs4],
        p50_queue_wait_1w_s=r1["queue_wait_p50_s"],
        p99_queue_wait_1w_s=r1["queue_wait_p99_s"],
        p50_queue_wait_2w_s=r2["queue_wait_p50_s"],
        p99_queue_wait_2w_s=r2["queue_wait_p99_s"],
        p99_queue_wait_4w_s=r4["queue_wait_p99_s"],
        e2e_p99_1w_s=r1["e2e_p99_s"], e2e_p99_2w_s=r2["e2e_p99_s"],
        cache_by_worker_2w=cache2,
        cache_hit_rate_min_2w=min(
            (v["hit_rate"] for v in cache2.values()), default=1.0),
        migration=dict(wall_s=mig["wall_s"],
                       tiles_at_yield=mig["tiles_at_yield"],
                       resume_tile=mig["resume_tile"],
                       tiles_rerun=mig["tiles_rerun"],
                       src=mig["src"], dst=mig["dst"],
                       bit_identical=True),
        recovery=dict(detect_s=rec_hop.get("detect_s"),
                      resume_wall_s=rec_hop["wall_s"],
                      total_wall_s=recovery_wall,
                      crash_tile=CRASH_TILE,
                      tiles_at_yield=rec_hop["tiles_at_yield"],
                      resume_tile=rec_hop["resume_tile"],
                      tiles_rerun=rec_hop["tiles_rerun"],
                      lease_s=LEASE_S,
                      lease_evictions=m_rec["lease_evictions"],
                      bit_identical=True),
        recovery_wall_s=recovery_wall,
        recovery_tiles_rerun=rec_hop["tiles_rerun"],
        client_pipelining=pipelining,
        ingest=dict(
            tile_arrival_s=PACE, arrival="burst",
            floor_1w_s=floors[1], floor_2w_s=floors[2],
            floor_4w_s=floors[4],
            legs_over_floor=over_floor,
            regime="ingest/admission-limited across PROCESSES: "
                   "per-tenant streaming pacing bounds per-job rate, "
                   "so aggregate throughput = fleet-wide admission "
                   "slots x stream rate while a leg's wall sits on "
                   "its ingest floor — the regime where worker "
                   "processes scale linearly and which this host "
                   f"({os.cpu_count()} core(s)) can measure honestly. "
                   "Legs listed in legs_over_floor EXCEEDED their "
                   "floor: their concurrent solves saturated the "
                   "host cores, so their scaling numbers document "
                   "the HOST ceiling, not the fleet's. NOT a "
                   "compute-scaling claim: the workers timeshare the "
                   "host cores, so the in-regime scaling measured is "
                   "the router/registry/placement/recovery machinery "
                   "end to end; the compute-bound multi-core/"
                   "TPU-host verdict takes the same config with "
                   "pacing off on real parallel hardware"),
        bit_identical=True,
        shape=f"8 jobs x {N_TILES}tiles N=16 M=2 F=24 tilesz 4,6 "
              f"pace{PACE} burst router 1w-vs-2w-vs-4w procs e1g4l2")
    try:
        rec["scaleout_record"] = _stamp_scaleout(
            rec, jax.devices()[0].platform)
    except Exception as e:        # the bench result still stands
        log(f"# scaleout record stamping failed: {e}")
    return rec


def _stamp_stream(rec: dict, platform: str) -> str:
    """Round-stamp the streaming-latency record (STREAM_rNN.json;
    first round is 16 — the ISSUE 16 PR)."""
    return stamp_family(rec, platform, "STREAM", "11-stream-latency",
                        first_round=16)


def config11_stream_latency(device, dtype):
    """Round-16 config: streaming calibration latency (ISSUE 16).

    The SLO under measurement is PER-TILE: latency from a solution
    interval's ARRIVAL (the stream transport's clock) to its residual
    DURABLY WRITTEN — not job makespan. One device, admission capacity
    1: a batch job (the config 9 loadgen shape, paced ingest) is
    running when a stream job (generator transport, one tile per
    INTERVAL_S) is submitted at the stream default priority; the
    scheduler must PREEMPT the batch job at a tile boundary, serve the
    stream within budget, then resume the batch job from its
    checkpoint. Banks p50/p99 arrival-to-write latency against the
    STATED budget.

    REFUSES to bank unless (a) the streamed outputs are bit-identical
    to the same tiles run as a batch job, (b) the preempted batch
    job's outputs are bit-identical to its solo run with ZERO
    completed tiles re-run across every preemption, (c) no stream
    tile was late/degraded, and (d) p99 is under budget.

    Measurement regime, stated honestly: at this shape a tile solves
    in ~0.1-0.3 s on one host core, so the budget prices scheduler
    wait + solve + ordered write-back, not FLOPs; the batch job's
    pacing keeps the host unsaturated the way the config 9/10 ingest
    regime does. On real hardware the same config measures the
    device-bound tail."""
    import shutil
    import tempfile
    import jax
    from sagecal_tpu import pipeline as pl
    from sagecal_tpu.io import dataset as ds
    from sagecal_tpu.serve import loadgen
    from sagecal_tpu.serve.api import Client, Server, config_from_dict

    noop = (lambda *a: None)
    tmpd = tempfile.mkdtemp(prefix="sagecal_stream_")
    PACE = 0.5          # batch tenant's ingest pacing (config 9)
    INTERVAL_S = 0.5    # stream arrival interval
    BUDGET_S = 1.0      # the stated p99 arrival-to-write budget
    N_TILES = 8         # per job
    spec = {
        "seed": 16, "n_jobs": 2,
        "arrival": {"process": "burst"},
        "templates": [
            {"name": "bucket4", "weight": 1, "n_stations": 16,
             "tilesz": 4, "n_tiles": N_TILES, "nchan": 24,
             "config": {"tile_arrival_s": PACE}}]}
    fixtures = loadgen.build_fixtures(spec, tmpd)
    proto = fixtures["bucket4"]

    def job_cfg(msdir, sol, **extra):
        cfg = loadgen.job_config(spec, "bucket4", msdir, sol)
        cfg.update(sky_model=proto["sky"], cluster_file=proto["cluster"],
                   **extra)
        return cfg

    # solo reference: every job below is a byte copy of the prototype,
    # so ONE batch run is THE reference for stream and batch alike
    solo_ms = os.path.join(tmpd, "solo.ms")
    shutil.copytree(proto["ms"], solo_ms)
    solo_sol = os.path.join(tmpd, "solo.sol")
    pl.run(config_from_dict(job_cfg(solo_ms, solo_sol)), log=noop)
    out = ds.SimMS(solo_ms, data_column="CORRECTED_DATA")
    solo_res = [out.read_tile(i).x.copy() for i in range(out.n_tiles)]
    solo_txt = open(solo_sol).read()

    def check_outputs(msdir, sol, tag):
        got = ds.SimMS(msdir, data_column="CORRECTED_DATA")
        for i in range(got.n_tiles):
            if not np.array_equal(got.read_tile(i).x, solo_res[i]):
                return f"{tag}: residuals NOT bit-identical (tile {i})"
        if open(sol).read() != solo_txt:
            return f"{tag}: solutions NOT bit-identical"
        return None

    def leg(tag):
        """One contention leg: batch running, stream submitted mid-run;
        returns (err, measurements)."""
        bms = os.path.join(tmpd, f"{tag}_b.ms")
        sms = os.path.join(tmpd, f"{tag}_s.ms")
        shutil.copytree(proto["ms"], bms)
        shutil.copytree(proto["ms"], sms)
        bsol = os.path.join(tmpd, f"{tag}_b.sol")
        ssol = os.path.join(tmpd, f"{tag}_s.sol")
        srv = Server(port=0, max_inflight=1)
        srv.start()
        try:
            with Client(port=srv.port) as c:
                jb = c.submit(job_cfg(bms, bsol))
                t_dead = time.monotonic() + 120
                while True:
                    snap = c.status(jb)
                    if snap["state"] == "running" \
                            and snap["tiles_done"] >= 1:
                        break
                    if time.monotonic() > t_dead or snap["state"] \
                            not in ("queued", "running"):
                        return (f"{tag}: batch stuck in "
                                f"{snap['state']}", None)
                    time.sleep(0.02)
                js = c.submit(job_cfg(
                    sms, ssol, stream_source=f"gen:{INTERVAL_S}",
                    tile_deadline_s=5 * BUDGET_S))
                snap_s = c.wait(js, timeout_s=300)
                snap_b = c.wait(jb, timeout_s=300)
                full = c.metrics_full()
        finally:
            srv.stop()
        if snap_s["state"] != "done" or snap_b["state"] != "done":
            return (f"{tag}: jobs not done (stream {snap_s['state']}, "
                    f"batch {snap_b['state']})", None)
        if not snap_b["migrations"]:
            return (f"{tag}: the stream job never preempted the "
                    "batch job", None)
        err = check_outputs(sms, ssol, f"{tag}/stream") \
            or check_outputs(bms, bsol, f"{tag}/batch")
        if err:
            return err, None
        lat = full["registry"].get(
            "stream_tile_latency_seconds", {}).get(
            "series", {}).get(f"job={js}")
        if not lat or lat["count"] != N_TILES:
            return (f"{tag}: stream latency histogram incomplete "
                    f"({lat})", None)
        rerun = sum(m["tiles_rerun"] for m in snap_b["migrations"])
        return None, dict(
            p50=lat["p50"], p99=lat["p99"],
            late=snap_s["tiles_late"], degraded=snap_s["tiles_degraded"],
            preemptions=len(snap_b["migrations"]),
            preempt_yield_s=[round(m["yield_s"], 4)
                             for m in snap_b["migrations"]],
            batch_tiles_rerun=rerun)

    # settle: compile every (shape, role) program outside the timed
    # leg — the config 6/8/9 contract
    err, _ = leg("settle")
    if err:
        return {"error": err}
    err, m = leg("timed")
    if err:
        return {"error": err}

    # refuse-to-bank gates beyond bit-identity (checked in leg)
    if m["batch_tiles_rerun"] != 0:
        return {"error": f"preempted batch job re-ran "
                         f"{m['batch_tiles_rerun']} tiles; refusing "
                         "to bank"}
    if m["late"] or m["degraded"]:
        return {"error": f"stream tiles late={m['late']} "
                         f"degraded={m['degraded']}; refusing to bank"}
    if m["p99"] is None or m["p99"] > BUDGET_S:
        return {"error": f"p99 arrival-to-write {m['p99']}s over the "
                         f"{BUDGET_S}s budget; refusing to bank"}

    rec = dict(
        value=m["p99"], unit="s p99 arr->write",
        p50_latency_s=m["p50"], p99_latency_s=m["p99"],
        budget_s=BUDGET_S, interval_s=INTERVAL_S,
        n_tiles_stream=N_TILES, n_tiles_batch=N_TILES,
        late_frac=m["late"] / N_TILES,
        degraded_tiles=m["degraded"],
        preemptions=m["preemptions"],
        preempt_yield_s=m["preempt_yield_s"],
        batch_tiles_rerun=m["batch_tiles_rerun"],
        batch_pace_s=PACE,
        transport="gen",
        bit_identical=True,
        regime="one device, admission capacity 1: the stream job "
               "preempts the batch tenant at a tile boundary and its "
               "p99 prices scheduler wait + solve + ordered "
               "write-back at a ~0.1-0.3 s/tile shape; latency is "
               "read from the job-scoped stream_tile_latency_seconds "
               "histogram (TILE_LAT_BUCKETS resolution)",
        shape=f"stream {N_TILES}x{INTERVAL_S}s + batch {N_TILES}t "
              f"pace{PACE} N=16 M=2 F=24 tilesz4 e1g4l2 1dev cap1")
    try:
        rec["stream_record"] = _stamp_stream(
            rec, jax.devices()[0].platform)
    except Exception as e:        # the bench result still stands
        log(f"# stream record stamping failed: {e}")
    return rec


def _stamp_warm(rec: dict, platform: str) -> str:
    """Round-stamp the warm-start prior-cache record (WARM_rNN.json;
    first round is 18 — the ISSUE 18 PR)."""
    return stamp_family(rec, platform, "WARM", "12-warm-start",
                        first_round=18)


def config12_warm_start(device, dtype):
    """Round-18 config: warm-start solution prior cache (ISSUE 18).

    Repeat-field traffic (ONE field re-observed n_jobs times, the
    loadgen ``repeat`` regime) replayed twice against an in-process
    daemon: a COLD control with ``prior_cache=off`` (the bit-frozen
    default — every job byte-identical to a solo run, and the prior
    store must end the leg untouched) and a WARM leg with
    ``prior_cache=readwrite`` where job 0 banks its final Jones chain
    and every later job seeds J0 from it, skipping the first-tile
    cold-start EM boost. Banks the sweeps-to-convergence reduction
    and wall-per-job warm vs cold over the seeded jobs, the prior-
    store hit rate, and — from a third leg, a router fronting two
    worker processes fed the same repeat field sequentially — the
    router's prior-affinity placement hit rate.

    REFUSES to bank unless (a) the off control is bit-identical to
    the solo run with ZERO prior-store traffic, (b) seeding reduced
    sweeps (the whole point), (c) warm final residuals stay within
    RES_ENVELOPE of the cold control (tolerance-work, not bit-work:
    warm must converge AS WELL, just cheaper), and (d) the seeded
    jobs actually hit the store.

    Measurement regime, stated honestly: at this shape the saved work
    is the 4x first-tile EM boost (pipeline.first_tile_boost), so the
    sweeps axis is deterministic while the wall axis prices host
    scheduling too; on real hardware the same config measures the
    device-bound saving."""
    import shutil
    import subprocess
    import sys as _sys
    import tempfile
    import jax
    from sagecal_tpu import pipeline as pl
    from sagecal_tpu.io import dataset as ds
    from sagecal_tpu.serve import loadgen
    from sagecal_tpu.serve import priors as ppriors
    from sagecal_tpu.serve.api import Client, Server, config_from_dict
    from sagecal_tpu.serve.router import Router

    noop = (lambda *a: None)
    tmpd = tempfile.mkdtemp(prefix="sagecal_warm_")
    N_TILES = 6
    N_JOBS = 5
    RES_ENVELOPE = 0.05   # warm/cold final-residual ratio slack
    spec = {
        "seed": 18, "n_jobs": N_JOBS,
        "arrival": {"process": "burst"},
        "templates": [
            {"name": "fieldA", "weight": 1, "repeat": 4.0,
             "n_stations": 16, "tilesz": 4, "n_tiles": N_TILES,
             "nchan": 24, "config": {"prefetch": 0}}]}
    fixtures = loadgen.build_fixtures(spec, tmpd)
    proto = fixtures["fieldA"]

    def job_cfg(msdir, sol, **extra):
        cfg = loadgen.job_config(spec, "fieldA", msdir, sol)
        cfg.update(sky_model=proto["sky"],
                   cluster_file=proto["cluster"], **extra)
        return cfg

    # solo reference (prior_cache defaults off): THE byte reference
    # for every cold-leg job and the residual-norm baseline
    solo_ms = os.path.join(tmpd, "solo.ms")
    shutil.copytree(proto["ms"], solo_ms)
    solo_sol = os.path.join(tmpd, "solo.sol")
    pl.run(config_from_dict(job_cfg(solo_ms, solo_sol)), log=noop)
    out = ds.SimMS(solo_ms, data_column="CORRECTED_DATA")
    solo_res = [out.read_tile(i).x.copy() for i in range(out.n_tiles)]
    solo_txt = open(solo_sol).read()

    def res_norm(msdir) -> float:
        got = ds.SimMS(msdir, data_column="CORRECTED_DATA")
        return float(np.sqrt(sum(
            np.sum(np.abs(got.read_tile(i).x) ** 2)
            for i in range(got.n_tiles))))

    solo_norm = res_norm(solo_ms)

    def leg(tag, mode):
        """One serialized replay of the repeat-field spec with
        ``prior_cache=mode``; returns (replay_rec, prior_stats)."""
        ppriors.PRIORS.clear()
        spec_m = json.loads(json.dumps(spec))
        spec_m["templates"][0]["config"]["prior_cache"] = mode
        srv = Server(port=0, max_inflight=1, log=noop)
        srv.start()
        try:
            with Client(port=srv.port) as c:
                work = os.path.join(tmpd, f"leg_{tag}")
                rec = loadgen.replay(c, spec_m, fixtures, work,
                                     log=noop, tag=tag)
        finally:
            srv.stop()
        if rec["states"] != {"done": rec["n_jobs"]}:
            raise RuntimeError(f"{tag}: jobs not all done: "
                               f"{rec['states']}")
        return rec, ppriors.PRIORS.stats()

    cold, cold_stats = leg("cold", "off")
    # gate (a): off is bit-frozen — byte-identical outputs AND zero
    # prior-store traffic
    for row in cold["jobs"]:
        got = ds.SimMS(row["ms"], data_column="CORRECTED_DATA")
        for i in range(got.n_tiles):
            if not np.array_equal(got.read_tile(i).x, solo_res[i]):
                return {"error": f"cold/{row['job_id']}: residuals "
                                 f"NOT bit-identical (tile {i}) with "
                                 "prior_cache=off; refusing to bank"}
        if open(row["solutions"]).read() != solo_txt:
            return {"error": f"cold/{row['job_id']}: solutions NOT "
                             "bit-identical with prior_cache=off; "
                             "refusing to bank"}
    if cold_stats["hits"] or cold_stats["misses"] or \
            cold_stats["banked"]:
        return {"error": f"prior_cache=off touched the prior store "
                         f"({cold_stats}); refusing to bank"}

    warm, warm_stats = leg("warm", "readwrite")
    # seeded jobs = every job after the first (job 0 banks the prior)
    cold_rows, warm_rows = cold["jobs"][1:], warm["jobs"][1:]
    sweeps_cold = float(np.mean([r["solver_iters"]
                                 for r in cold_rows]))
    sweeps_warm = float(np.mean([r["solver_iters"]
                                 for r in warm_rows]))
    wall_cold = float(np.mean([r["e2e_s"] for r in cold_rows]))
    wall_warm = float(np.mean([r["e2e_s"] for r in warm_rows]))
    reduction = (1.0 - sweeps_warm / sweeps_cold) if sweeps_cold \
        else 0.0
    # gate (d): the seeded jobs actually hit the store
    if warm_stats["hits"] < len(warm_rows):
        return {"error": f"warm leg: {warm_stats['hits']} prior hits "
                         f"for {len(warm_rows)} seeded jobs "
                         f"({warm_stats}); refusing to bank"}
    # gate (b): seeding reduced sweeps
    if reduction <= 0.0:
        return {"error": f"warm start saved no sweeps (cold "
                         f"{sweeps_cold}, warm {sweeps_warm}); "
                         "refusing to bank"}
    # gate (c): warm converges as well as cold (tolerance, not bits)
    ratios = [res_norm(r["ms"]) / solo_norm for r in warm_rows]
    res_ratio = float(max(ratios))
    if res_ratio > 1.0 + RES_ENVELOPE:
        return {"error": f"warm final residual {res_ratio:.4f}x the "
                         f"cold control (> {1 + RES_ENVELOPE}); "
                         "refusing to bank"}

    # router leg: prior-affinity placement across TWO worker
    # processes. The repeat field is fed sequentially (submit, wait,
    # one heartbeat) so each placement decision sees the fleet's
    # published prior inventory — the affinity signal under test,
    # not a race against the first heartbeat.
    HB_S = 0.4
    r = Router(port=0, lease_s=2.0, heartbeat_s=HB_S, log=noop)
    r.start()
    worker_env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = []

    def spawn_worker(name):
        args = [_sys.executable, "-m", "sagecal_tpu.serve",
                "--worker", "--router", f"127.0.0.1:{r.port}",
                "--port", "0", "--max-inflight", "2",
                "--worker-id", name]
        logf = open(os.path.join(tmpd, f"{name}.log"), "w")
        return subprocess.Popen(args, stdout=logf,
                                stderr=subprocess.STDOUT,
                                env=worker_env, cwd=HERE)

    try:
        procs = [spawn_worker(f"wp{i}") for i in range(2)]
        t_dead = time.monotonic() + 240
        while r.metrics()["n_alive"] < 2:
            if time.monotonic() > t_dead:
                raise RuntimeError("fleet never reached 2 workers")
            time.sleep(0.1)
        with Client(port=r.port) as c:
            for i in range(N_JOBS):
                rms = os.path.join(tmpd, f"rt_{i}.ms")
                shutil.copytree(proto["ms"], rms)
                rsol = os.path.join(tmpd, f"rt_{i}.sol")
                jid = c.submit(job_cfg(rms, rsol,
                                       prior_cache="readwrite"),
                               job_id=f"rt-{i}")
                snap = c.wait(jid, timeout_s=300)
                if snap["state"] != "done":
                    raise RuntimeError(
                        f"router job rt-{i}: {snap['state']}")
                time.sleep(2.5 * HB_S)   # inventory rides a heartbeat
            rm = r.metrics()
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
        r.stop()
    aff = rm.get("prior_affinity") or {}
    if not aff.get("hits"):
        return {"error": f"router prior affinity never placed a job "
                         f"({aff}); refusing to bank"}

    rec = dict(
        workers="cpu",      # the router leg's worker_env pins them
        value=round(reduction, 4), unit="sweeps saved warm/cold",
        sweeps_reduction_frac=round(reduction, 4),
        sweeps_cold=round(sweeps_cold, 3),
        sweeps_warm=round(sweeps_warm, 3),
        wall_per_job_cold_s=round(wall_cold, 4),
        wall_per_job_warm_s=round(wall_warm, 4),
        residual_ratio_warm_vs_cold=round(res_ratio, 6),
        res_envelope=RES_ENVELOPE,
        prior_hit_rate=round(warm_stats["hit_rate"], 4),
        prior_hits=warm_stats["hits"],
        prior_banked=warm_stats["banked"],
        prior_kept=warm_stats["kept"],
        prior_refused=warm_stats["refused"],
        router_prior_affinity_hit_rate=round(aff.get("hit_rate", 0.0),
                                             4),
        router_prior_affinity_hits=aff.get("hits", 0),
        router_prior_affinity_total=aff.get("total", 0),
        n_jobs=N_JOBS, n_seeded=len(warm_rows),
        off_bit_identical=True,
        sweeps_by_template_cold=cold.get("sweeps_by_template"),
        sweeps_by_template_warm=warm.get("sweeps_by_template"),
        regime="repeat-field replay, one in-process device, "
               "admission capacity 1: the saved work is the 4x "
               "first-tile EM boost a seeded J0 skips; the router "
               "leg feeds the same field sequentially to 2 worker "
               "processes so placement sees the heartbeat-published "
               "prior inventory",
        shape=f"{N_JOBS}x(N=16 M=2 F=24 tilesz4 {N_TILES}t "
              f"e1g4l2) repeat-field")
    try:
        rec["warm_record"] = _stamp_warm(rec,
                                         jax.devices()[0].platform)
    except Exception as e:        # the bench result still stands
        log(f"# warm record stamping failed: {e}")
    return rec


def _stamp_jones(rec: dict, platform: str) -> str:
    """Round-stamp the constrained-Jones record (JONES_rNN.json; first
    round is 20 — the ISSUE 20 PR)."""
    return stamp_family(rec, platform, "JONES", "13-jones-melt",
                        first_round=20)


def config13_jones_melt(device, dtype):
    """Round-20 config: constrained-Jones traffic melt (ISSUE 20).

    One per-cluster solve shape (K=1 baseline-major, the fused-kernel
    regime) with a PHASE-CONSTRAINED truth — unit-amplitude diagonal
    Jones, representable by every jones_mode — solved under
    jones in {full, diag, phase} x kernel in {xla, pallas} at a fixed
    trip budget. Banks, per leg and mode: the priced bytes/trip and
    flops/trip of the damping trip (solver_trip_cost — the reduced
    [K, npar N, npar N] bodies the solvers execute), measured
    wall/step, EXECUTED trips, and the final residual norm relative
    to the full-Jones solve.

    REFUSES to bank unless (a) every mode executed the SAME trip
    count (the equal-executed-trips comparison frame), (b) phase-mode
    bytes/trip <= PHASE_GATE x full-mode on BOTH kernel legs (the
    8x8 -> 2x2 Gram melt, ROADMAP item 2), (c) the constrained-truth
    residual envelope holds — diag and phase final residual norms
    within RES_ENVELOPE of full's (a constraint that MATCHES the
    data's structure must not cost solution quality), and (d) the
    mode entry points delegate bit-exactly at jones="full" (the
    default path stays byte-frozen).

    Measurement regime, stated honestly: kernel="pallas" on CPU runs
    interpret-mode, so wall/step is meaningful only within a leg;
    bytes/trip comes from the lowered-program pricing either way and
    is the banked headline. The compiled-Mosaic verdict rides the
    burn-down queue (tools_dev/burndown.py 13-jones-melt)."""
    import functools
    import jax
    import jax.numpy as jnp
    from sagecal_tpu.solvers import lm as lm_mod
    from sagecal_tpu.solvers import normal_eq as ne
    from sagecal_tpu.ops import sweep_pallas as swp

    N, T, K = 40, 2, 1
    nb = N * (N - 1) // 2
    B = nb * T
    ITMAX = 12
    REP = 3
    PHASE_GATE = 0.35
    RES_ENVELOPE = 0.05
    if not swp.supported(K, nb, B):
        return {"error": f"shape K={K} nbase={nb} B={B} not "
                         "fused-kernel eligible; refusing to bank"}

    rng = np.random.default_rng(20)
    i1, i2 = np.triu_indices(N, 1)
    s1 = jnp.asarray(np.tile(i1, T).astype(np.int32))
    s2 = jnp.asarray(np.tile(i2, T).astype(np.int32))
    coh_np = (rng.normal(size=(B, 2, 2))
              + 1j * rng.normal(size=(B, 2, 2))).astype(np.complex64)
    # dominant diagonal + off-diagonal leakage: polarized enough that
    # a diag/phase MIS-fit of full-Jones data would shows up, while
    # the constrained truth keeps all three modes comparable
    coh_np = coh_np + 2.0 * np.eye(2, dtype=np.complex64)
    th = rng.uniform(-0.7, 0.7, size=(K, N, 2)).astype(np.float32)
    d = np.exp(1j * th)
    Jt = np.zeros((K, N, 2, 2), np.complex64)
    Jt[..., 0, 0] = d[..., 0]
    Jt[..., 1, 1] = d[..., 1]
    V = np.einsum("bij,bjk,blk->bil", Jt[0][np.tile(i1, T)], coh_np,
                  Jt[0][np.tile(i2, T)].conj())
    V = V + 0.02 * (rng.normal(size=(B, 2, 2))
                    + 1j * rng.normal(size=(B, 2, 2)))
    vf = V.reshape(-1, 4)
    x8 = jnp.asarray(np.stack([vf.real, vf.imag], -1).reshape(-1, 8),
                     jnp.float32)
    coh = jnp.asarray(coh_np)
    wt = jnp.ones((B, 8), jnp.float32)
    chunk = jnp.zeros((B,), jnp.int32)
    J0 = jnp.asarray(np.tile(np.eye(2, dtype=np.complex64),
                             (K, N, 1, 1)))

    # gate (d): the jones="full" entry points delegate bit-exactly —
    # the byte-frozen default path (r18 parity) is untouched by the
    # mode layer
    ref = ne.normal_equations(x8, jnp.asarray(Jt), coh, s1, s2, chunk,
                              wt, N, K, row_period=nb)
    via = ne.normal_equations_mode(x8, jnp.asarray(Jt), coh, s1, s2,
                                   chunk, wt, N, K, mode="full",
                                   row_period=nb)
    for a, b in zip(ref, via):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            return {"error": "jones='full' normal_equations_mode NOT "
                             "bit-identical to normal_equations; "
                             "refusing to bank"}

    legs = {}
    for kern in ("xla", "pallas"):
        per = {}
        for jm in ("full", "diag", "phase"):
            cfg = lm_mod.LMConfig(itmax=ITMAX, kernel=kern,
                                  jones_mode=jm)
            f = jax.jit(functools.partial(
                lm_mod.lm_solve, n_stations=N, config=cfg,
                row_period=nb))
            J, info = f(x8, coh, s1, s2, chunk, wt, J0)
            jax.block_until_ready(J)
            t0 = time.perf_counter()
            for _ in range(REP):
                J, info = f(x8, coh, s1, s2, chunk, wt, J0)
                jax.block_until_ready(J)
            wall = (time.perf_counter() - t0) / REP
            trips = int(np.asarray(info["iters"]).sum())
            tc = solver_trip_cost(0, K, N, B, jnp.float32, nbase=nb,
                                  inner="chol", kernel=kern, jones=jm)
            per[jm] = dict(
                executed_trips=trips,
                final_cost=float(np.asarray(info["final_cost"]).sum()),
                wall_per_step_s=round(wall / max(trips, 1), 6),
                bytes_per_trip=None if tc is None
                else tc["bytes_accessed"],
                flops_per_trip=None if tc is None else tc["flops"])
            if jm == "full":
                # the default-config solve IS the jones="full" solve
                # (LMConfig.jones_mode defaults to "full"): bit parity
                # documents the frozen default
                f0 = jax.jit(functools.partial(
                    lm_mod.lm_solve, n_stations=N,
                    config=lm_mod.LMConfig(itmax=ITMAX, kernel=kern),
                    row_period=nb))
                Jd, _ = f0(x8, coh, s1, s2, chunk, wt, J0)
                if not np.array_equal(np.asarray(J), np.asarray(Jd)):
                    return {"error": f"{kern}: --jones full solve NOT "
                                     "bit-identical to the default "
                                     "config; refusing to bank"}
        # gate (a): equal executed trips across modes
        tset = {m: per[m]["executed_trips"] for m in per}
        if len(set(tset.values())) != 1:
            return {"error": f"{kern}: unequal executed trips across "
                             f"modes ({tset}); refusing to bank"}
        if any(per[m]["bytes_per_trip"] is None for m in per):
            return {"error": f"{kern}: trip pricing unavailable; "
                             "refusing to bank"}
        bf = per["full"]["bytes_per_trip"]
        ratios = {m: per[m]["bytes_per_trip"] / bf for m in per}
        # gate (b): the phase melt gate
        if ratios["phase"] > PHASE_GATE:
            return {"error": f"{kern}: phase bytes/trip "
                             f"{ratios['phase']:.3f}x full "
                             f"(> {PHASE_GATE}); refusing to bank"}
        # gate (c): constrained-truth residual envelope (residual
        # NORM ratio — sqrt of the summed squared cost)
        cf = per["full"]["final_cost"]
        res = {m: float(np.sqrt(per[m]["final_cost"] / cf))
               for m in per}
        for m in ("diag", "phase"):
            if res[m] > 1.0 + RES_ENVELOPE:
                return {"error": f"{kern}: {m} residual {res[m]:.4f}x "
                                 f"full (> {1 + RES_ENVELOPE}); "
                                 "refusing to bank"}
        legs[kern] = dict(
            modes=per,
            bytes_per_trip_vs_full={m: round(r, 4)
                                    for m, r in ratios.items()},
            residual_norm_vs_full={m: round(r, 6)
                                   for m, r in res.items()},
            executed_trips=tset["full"])

    rec = dict(
        value=round(legs["xla"]["bytes_per_trip_vs_full"]["phase"], 4),
        unit="phase/full bytes per trip (xla)",
        phase_bytes_ratio_xla=legs["xla"][
            "bytes_per_trip_vs_full"]["phase"],
        phase_bytes_ratio_pallas=legs["pallas"][
            "bytes_per_trip_vs_full"]["phase"],
        diag_bytes_ratio_xla=legs["xla"][
            "bytes_per_trip_vs_full"]["diag"],
        diag_bytes_ratio_pallas=legs["pallas"][
            "bytes_per_trip_vs_full"]["diag"],
        phase_gate=PHASE_GATE, res_envelope=RES_ENVELOPE,
        residual_envelope_met=True, full_mode_bit_identical=True,
        legs=legs,
        regime="phase-constrained truth (unit-amplitude diagonal "
               "Jones), cold identity start, fixed trip budget; "
               "pallas leg is interpret-mode on CPU so its wall axis "
               "is within-leg only; bytes/trip is the lowered-program "
               "price either way",
        shape=f"N={N} K={K} B={B} nbase={nb} itmax={ITMAX} f32")
    try:
        rec["jones_record"] = _stamp_jones(rec,
                                           jax.devices()[0].platform)
    except Exception as e:        # the bench result still stands
        log(f"# jones record stamping failed: {e}")
    return rec


CONFIGS = [
    ("1-fullbatch-lm", config1_fullbatch_lm),
    ("2-stochastic-lbfgs", config2_stochastic),
    ("3-rtr-16cluster", config3_rtr16),
    ("4-extended-64sta", config4_extended),
    ("5-admm-32subband", config5_admm32),
    ("6-overlap-e2e", config6_overlap),
    ("7-dtype-melt", config7_dtype),
    ("8-serve-throughput", config8_serve),
    ("9-fleet-throughput", config9_fleet),
    ("10-scaleout", config10_scaleout),
    ("11-stream-latency", config11_stream_latency),
    ("12-warm-start", config12_warm_start),
    ("13-jones-melt", config13_jones_melt),
]

#: configs that need a virtual multi-device fleet: run_one_config
#: requests the CPU device count BEFORE the backend initializes
#: (utils.setup_backend; a real TPU host uses its visible chips)
MULTI_DEVICE_CONFIGS = {"9-fleet-throughput": 2}



def _fmt_pct(v):
    """Percentage with 2 significant digits: tiny utilizations on a
    ~400 TFLOP/s chip must not round to an information-free 0.00%."""
    if v is None or v != v:
        return "—"
    if v == 0 or v >= 0.1:
        return f"{v:.2f}%"
    from math import floor, log10
    return f"{v:.{max(0, 1 - floor(log10(abs(v))))}f}%"

def _fmt_s(r, key, fmt):
    v = r.get(key)
    return ("—" if v is None or (isinstance(v, float) and v != v)
            else format(v, fmt) + "s")


_ROUND_STAMP: dict = {}     # platform -> BENCH_<PLAT>_rNN.json path
_LIVE_GUARD: dict = {}      # pre-run bench_results.json platform


def _stamp_path(platform: str) -> str:
    """Round-stamped record path for this process: NN = 1 + the newest
    committed BENCH_<PLAT>_rNN.json (SAGECAL_BENCH_ROUND overrides);
    chosen once per process so the per-config flushes keep appending to
    ONE record."""
    if platform in _ROUND_STAMP:
        return _ROUND_STAMP[platform]
    import glob
    import re as _re
    env = os.environ.get("SAGECAL_BENCH_ROUND")
    if env:
        nn = int(env)
    else:
        rounds = [int(m.group(1)) for p in
                  glob.glob(os.path.join(
                      HERE, f"BENCH_{platform.upper()}_r*.json"))
                  if (m := _re.search(r"_r(\d+)\.json$", p))]
        nn = max(rounds, default=5) + 1
    path = os.path.join(HERE, f"BENCH_{platform.upper()}_r{nn:02d}.json")
    _ROUND_STAMP[platform] = path
    return path


def write_table(results, platform, date=None, stamp=False):
    """``date``: measurement timestamp; None stamps now. Regenerators
    (tools_dev/northstar.py) pass the stored stamp so stale results are
    never re-dated as fresh.

    Bank-vs-live hygiene (VERDICT r5 weak #7): a live bench run
    (``stamp=True``) always writes its round-stamped
    ``BENCH_<PLATFORM>_rNN.json`` record, and REFUSES to overwrite a
    committed ``BENCH_TABLE.md``/``bench_results.json`` that came from a
    DIFFERENT backend (e.g. a CPU-fallback run while the banked record
    is TPU) unless SAGECAL_BENCH_OVERWRITE=1 — the round-5 handoff left
    a CPU table shadowing the banked TPU record on disk."""
    date = date or time.strftime("%Y-%m-%d %H:%M:%S")
    lines = [
        "# BENCH table (auto-generated by bench.py)",
        "",
        f"Device platform: **{platform}**  |  dtype f32  |  "
        f"date {date}",
        "",
        "Roofline axes (sagecal_tpu.diag.roofline): FLOPs AND bytes "
        "accessed come from XLA cost analysis of every device program a "
        "timed step executed PLUS the dynamic-trip correction: the "
        "solvers report executed iteration counts and one iteration of "
        "each solver family is priced by lowering its component "
        "functions at the solve shapes (see bench.py's MFU "
        "trip-accounting block). GB/s = bytes accessed / wall-clock; "
        "bound = compute|bandwidth, the side of the device ridge point "
        "(peak FLOP/s ÷ peak HBM bytes/s) the step's operational "
        "intensity falls on. MFU≥ (achieved FLOP/s vs bf16 peak) is "
        "retained for cross-round comparability only — the bound "
        "column is the axis that explains plateaus. Remaining slack is "
        "lower-bound-leaning: line-search evaluations beyond 1/iter "
        "and per-IRLS-round E-steps are uncounted.",
        "",
        "| config | value | unit | res_0 -> res_1 | step | compile | "
        "GFLOP/s | GB/s | Δbytes | bound | MFU≥ | shape |",
        "|---|---|---|---|---|---|---|---|---|---|---|---|",
    ]
    # the sentinel reads its toleranced metrics out of the banked
    # records this table renders; assert the column mapping here so
    # a renamed/dropped column can never silently orphan a tolerance
    # (tests/test_obs.py pins the mapping itself)
    from sagecal_tpu.obs import sentinel as _sentinel
    _sentinel.assert_table_contract(lines[-2])
    for name, r in results.items():
        if "error" in r:
            lines.append(f"| {name} | FAILED | — | — | — | — | — | — | — "
                         f"| — | — | {r['error'][:80]} |")
            continue
        res = (f"{r.get('res_0', float('nan')):.4g} -> "
               f"{r.get('res_1', float('nan')):.4g}")
        shape = r.get("shape", "")
        if r.get("pallas"):
            sp = r.get("pallas_speedup")
            shape += (f" [pallas x{sp:.2f}]" if sp else " [pallas]")
        gfs = r.get("flops_per_s")
        gfs_s = "—" if not gfs else f"{gfs / 1e9:.1f}"
        gbs = r.get("achieved_gbps")
        gbs_s = "—" if gbs is None else f"{gbs:.2f}"
        dby = r.get("bytes_vs_bank_pct")
        dby_s = "—" if dby is None else f"{dby:+.1f}%"
        bound_s = r.get("bound", "—")
        mfu = r.get("mfu_pct")
        mfu_s = _fmt_pct(mfu)
        lines.append(
            f"| {name} | {r['value']:.1f} | {r['unit']} | {res} | "
            f"{_fmt_s(r, 'step_s', '.3f')} | {_fmt_s(r, 'compile_s', '.1f')}"
            f" | {gfs_s} | {gbs_s} | {dby_s} | {bound_s} | {mfu_s} "
            f"| {shape} |")
    # the north-star scale row (tools_dev/northstar.py) is measured by a
    # separate scripted run; re-emit it from its record so regenerating
    # this table never drops it
    ns_path = os.path.join(HERE, "NORTHSTAR.json")
    if os.path.exists(ns_path):
        try:
            with open(ns_path) as f:
                ns = json.load(f)
            gfs = ns.get("flops_per_s")
            gfs_s = "—" if not gfs else f"{gfs / 1e9:.1f}"
            gbs = ns.get("achieved_gbps")
            gbs_s = "—" if gbs is None else f"{gbs:.2f}"
            mfu = ns.get("mfu_pct")
            mfu_s = _fmt_pct(mfu)
            lines.append(
                f"| northstar | {ns['value']:.2f} | {ns['unit']} | — | — "
                f"| — | {gfs_s} | {gbs_s} | — | {ns.get('bound', '—')} "
                f"| {mfu_s} | {ns.get('shape', '')} "
                f"[{ns.get('platform', '?')}] |")
        except Exception as e:
            log(f"# NORTHSTAR.json unreadable: {e}")
    payload = {"platform": platform, "date": date, "results": results}
    if stamp:
        # bank hygiene: a standard config measured under a non-f32
        # SAGECAL_BENCH_DTYPE exploration run must never become the
        # round-stamped reference — the Δbytes column measures reduced
        # policies AGAINST the f32 bank (config 7 banks the per-policy
        # numbers; a refused-drift policy is already dropped there)
        off_policy = {k for k, v in results.items()
                      if isinstance(v, dict)
                      and v.get("dtype_policy", "f32") != "f32"}
        # same rule for SAGECAL_BENCH_KERNEL exploration runs: the
        # banked reference stays the bit-frozen xla path (northstar
        # --b-scaling --kernel both is the banked kernel comparison)
        off_policy |= {k for k, v in results.items()
                       if isinstance(v, dict)
                       and v.get("kernel", "xla") != "xla"}
        if off_policy:
            log(f"# refusing to round-stamp off-policy records "
                f"{sorted(off_policy)}; rerun without "
                f"SAGECAL_BENCH_DTYPE/SAGECAL_BENCH_KERNEL to bank")
            payload = {"platform": platform, "date": date,
                       "results": {k: v for k, v in results.items()
                                   if k not in off_policy}}
        with open(_stamp_path(platform), "w") as f:
            json.dump(payload, f, indent=1, default=float)
        payload = {"platform": platform, "date": date, "results": results}
    live = os.path.join(HERE, "bench_results.json")
    if stamp and not os.environ.get("SAGECAL_BENCH_OVERWRITE"):
        # snapshot the PRE-RUN record's backend once per process: the
        # guard protects the bank from this run, not this run's own
        # earlier per-config flushes after a mid-run platform drift
        if "platform" not in _LIVE_GUARD:
            try:
                with open(live) as f:
                    _LIVE_GUARD["platform"] = json.load(f).get("platform")
            except Exception:
                _LIVE_GUARD["platform"] = None
        if platform == "cpu" and _LIVE_GUARD["platform"] == "tpu":
            log("# refusing to overwrite the banked tpu "
                "BENCH_TABLE.md/bench_results.json with a cpu run; "
                f"this run's record is {_stamp_path(platform)} "
                "(set SAGECAL_BENCH_OVERWRITE=1 to force)")
            return
    with open(os.path.join(HERE, "BENCH_TABLE.md"), "w") as f:
        f.write("\n".join(lines) + "\n")
    with open(live, "w") as f:
        json.dump(payload, f, indent=1, default=float)


def run_one_config(name: str):
    """Child-process entry: run ONE config, print its result JSON."""
    import jax
    from sagecal_tpu import utils
    # BEFORE the first device use: platform, the virtual-CPU device
    # count (a no-op on a TPU host, whose real chips are already
    # visible) and the persistent compile cache — each config runs in
    # a fresh process, so without the cache every run re-pays its
    # compiles
    utils.setup_backend(
        "cpu" if os.environ.get("SAGECAL_BENCH_CPU") else None,
        MULTI_DEVICE_CONFIGS.get(name))
    dev = jax.devices()[0]
    # platform assertion: a config expected on TPU must never silently
    # produce a CPU number under a TPU label (round-3 weak item 4)
    expect = os.environ.get("SAGECAL_BENCH_EXPECT")
    if expect and dev.platform != expect:
        print("BENCHRESULT " + json.dumps(
            {"error": f"platform assertion: expected {expect}, "
                      f"got {dev.platform}", "platform": dev.platform}))
        return
    import jax.numpy as jnp
    fn = dict(CONFIGS)[name]
    r = fn(dev, jnp.float32)
    r["platform"] = dev.platform
    print("BENCHRESULT " + json.dumps(r, default=float))


_CURRENT_CHILD = [None]    # live --config subprocess, killed on SIGTERM


def run_config_subprocess(name: str, timeout_s: int = 570, cpu=False):
    """Run one config isolated in a subprocess: a TPU kernel fault (seen
    with round-2 config 3) poisons the whole process's device client, so
    each config gets a fresh one."""
    env = dict(os.environ)
    if cpu:
        env["SAGECAL_BENCH_CPU"] = "1"
        env.pop("SAGECAL_BENCH_EXPECT", None)
    else:
        env["SAGECAL_BENCH_EXPECT"] = "tpu"
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--config", name],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env)
    _CURRENT_CHILD[0] = proc
    try:
        out, err = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        return {"error": f"timeout after {timeout_s}s"}
    finally:
        _CURRENT_CHILD[0] = None
    sys.stderr.write(err or "")
    for line in (out or "").splitlines():
        if line.startswith("BENCHRESULT "):
            return json.loads(line[len("BENCHRESULT "):])
    tail = ((err or "").strip().splitlines() or ["no output"])[-1]
    return {"error": f"rc={proc.returncode}: {tail[:200]}"}


def _flag(name, default):
    if name in sys.argv:
        return int(sys.argv[sys.argv.index(name) + 1])
    return default


class _Emitter:
    """Guarantees the stdout JSON contract fires exactly once — on normal
    completion, on SIGTERM/SIGINT (the driver's `timeout` sends TERM
    first), or at interpreter exit. Round-2 failure mode: one runaway
    config hit the outer rc=124 and zeroed the whole perf record."""

    def __init__(self, platform: str):
        self.results = {}
        self.platform = platform
        self.vs = None
        self.done = False
        self.total = len(CONFIGS)    # planned, not attempted: a partial
        # emit must still show how many configs the round OWED
        atexit.register(self.emit)
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                signal.signal(sig, self._on_signal)
            except ValueError:
                pass

    def _on_signal(self, signum, frame):
        log(f"# signal {signum}: emitting partial bench record")
        child = _CURRENT_CHILD[0]
        if child is not None:
            # don't orphan a child holding the chip
            try:
                child.kill()
            except OSError:
                pass
        self.emit()
        os._exit(124)

    def emit(self):
        if self.done:
            return
        self.done = True
        head = self.results.get("1-fullbatch-lm", {})
        value = head.get("value", 0.0)
        vs = self.vs if self.vs is not None else 1.0
        # the headline device is the platform the headline config
        # ACTUALLY ran on
        device = head.get("platform", self.platform)
        print(json.dumps({
            "metric": "visibilities calibrated/sec/chip",
            "value": round(float(value), 1),
            "unit": "vis/s",
            "vs_baseline": round(float(vs), 3),
            "device": device,
            "configs_ok": sum(1 for r in self.results.values()
                              if "error" not in r),
            "configs_total": self.total,
        }), flush=True)


def main():
    if "--config" in sys.argv:
        run_one_config(sys.argv[sys.argv.index("--config") + 1])
        return

    quick = "--quick" in sys.argv
    cpu = "--cpu" in sys.argv
    timeout_s = _flag("--timeout", int(os.environ.get(
        "SAGECAL_BENCH_TIMEOUT", 570)))
    budget_s = _flag("--budget", int(os.environ.get(
        "SAGECAL_BENCH_BUDGET", 1700)))
    t_start = time.perf_counter()

    if not cpu:
        found = device_platform()
        if found != "tpu":
            log(f"# bench: JAX finds no TPU here (platform: {found}) and "
                "--cpu was not given; nothing was run")
            sys.exit(2)
    em = _Emitter("cpu" if cpu else "tpu")
    if quick:
        em.total = 1
    # snapshot the banked per-config bytes_accessed BEFORE this run's
    # first table flush: every result is annotated with its traffic
    # delta vs the bank, so the tentpole's fewer-bytes claim is asserted
    # by the bench record itself rather than by prose
    bytes_bank = {p: _bytes_baseline(p) for p in ("cpu", "tpu")}
    # the sentinel's fuller bank snapshot (wall/bytes/busy/cache per
    # config): every fresh result is compared as it lands and the
    # violations ride the stamped record — the post-run half of the
    # obs/sentinel.py contract (CI runs the --fast half). Importing it
    # initialises no JAX backend, so this process stays off the chip.
    from sagecal_tpu.obs import sentinel as _sentinel
    sent_bank = {p: _sentinel.newest_bank_results(p)
                 for p in ("cpu", "tpu")}
    log(f"# bench platform: {em.platform} (timeout {timeout_s}s/config, "
        f"budget {budget_s}s)")

    def run_and_record(name):
        t0 = time.perf_counter()
        remaining = budget_s - (time.perf_counter() - t_start) - 30
        r = run_config_subprocess(name, timeout_s=int(
            min(timeout_s, remaining)), cpu=cpu)
        if "error" not in r:
            r["total_s"] = round(time.perf_counter() - t0, 1)
            base = bytes_bank.get(r.get("platform", ""), {}).get(name)
            if base and r.get("bytes_accessed"):
                r["bytes_bank"] = base
                r["bytes_vs_bank_pct"] = round(
                    100.0 * (r["bytes_accessed"] - base) / base, 2)
                log(f"# {name}: bytes {r['bytes_accessed']:.3e} vs bank "
                    f"{base:.3e} ({r['bytes_vs_bank_pct']:+.1f}%)")
            log(f"# {name}: {r['value']:.1f} {r['unit']} "
                f"(res {r.get('res_0', 0):.4g}->{r.get('res_1', 0):.4g}, "
                f"total {r['total_s']}s)")
            viol = _sentinel.compare(
                {name: r}, sent_bank.get(r.get("platform", ""), {}))
            if viol:
                # recorded, not fatal: a bench round must never zero
                # itself — the regression is named in the stamped JSON
                # and the CI sentinel lane judges the committed bank
                r["sentinel"] = [v["msg"] for v in viol]
                for v in viol:
                    log(f"# SENTINEL REGRESSION: {v['msg']}")
        else:
            log(f"# {name}: FAILED {r['error']}")
        em.results[name] = r
        # flush after EVERY config: a later timeout/fault can no longer
        # zero the round's perf record
        write_table(em.results, em.platform, stamp=True)
        return r

    for name, fn in CONFIGS:
        if quick and not name.startswith("1"):
            continue
        remaining = budget_s - (time.perf_counter() - t_start) - 30
        if remaining < 60:
            em.results[name] = {"error": "skipped: bench budget exhausted"}
            log(f"# {name}: skipped (budget)")
            write_table(em.results, em.platform, stamp=True)
            continue
        run_and_record(name)

    head = em.results.get("1-fullbatch-lm", {})
    value = head.get("value", 0.0)

    # vs_baseline: against the measured reference-CPU number
    ref_path = os.path.join(HERE, "ref_baseline.json")
    if os.path.exists(ref_path) and value:
        try:
            with open(ref_path) as f:
                ref = json.load(f)
            rv = ref.get("config1_vis_per_sec")
            if rv:
                em.vs = value / rv
                # label with the platform config 1 ACTUALLY ran on —
                # round 3's record said "TPU 374" about a CPU run
                dev = head.get("platform", em.platform)
                log(f"# vs_baseline = {dev} {value:.0f} / reference-CPU "
                    f"{rv:.0f} vis/s ({ref.get('note', '')})")
        except Exception as e:
            log(f"# ref_baseline.json unreadable: {e}")
    em.emit()


if __name__ == "__main__":
    main()
