"""The sweep's running residual on planes (ISSUE 41).

From the prelude to the sweep's end the SAGE sweep carries its running
residual as ``[8, *rows]`` real planes and evaluates a cluster's model
on them by written-out multiply-adds (``sage._cluster_model``); the RTR
family takes a visit's planes as they are (``rtr.rtr_rows_robust``).
These cases hold all of it to the plain construction it replaced, kept
here as the reference: ``rime.predict.model8`` (gathers of ``[B, 2, 2]``
complex Jones, two batched complex products) added to and subtracted
from a ``[B, 8]`` carry around the solvers' ``[B, 8]`` entries. And they
lower the solve at ``cal-m8x3``'s shapes and look inside the EM loop for
what that construction would bring back.
"""

import functools
import itertools
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from sagecal_tpu import dtypes as dtp
from sagecal_tpu.config import SolverMode
from sagecal_tpu.rime import predict as rp
from sagecal_tpu.solvers import lm as lm_mod, normal_eq as ne, rtr, sage

from test_refine_planes import B62, M8, N62, NB62, _walk

N, M, TSZ = 5, 3, 4
PAIRS = [(i, j) for i in range(N) for j in range(i + 1, N)]
NBASE = len(PAIRS)
B = NBASE * TSZ
RTR, LM = int(SolverMode.RTR_OSRLM_RLBFGS), int(SolverMode.LM_LBFGS)
#: id -> (kmax, nbase handed to the solve, the layout it must decide on)
LAYOUTS = {"k1-periodic": (1, NBASE, "periodic"), "k1-flat": (1, 0, "flat"),
           "k2": (2, NBASE, "periodic"), "k2-flat": (2, 0, "flat")}
DTYPES = {"f64": jnp.float64, "f32": jnp.float32}
#: relative to the largest entry: rounding at f64, a trajectory of a
#: dozen trust-region steps at f32, storage quantization at bf16
TOL = {"f64": 1e-9, "f32": 2e-3, "bf16": 0.1}


@functools.lru_cache(maxsize=None)
def _problem(kmax, dt="f64", seed=3, nbase=0):
    """A tiny observation: M clusters, ``kmax`` hybrid chunks, a tenth of
    the rows flagged to zero weight. Under a row period (``nbase``) the
    chunk map is ``rime.predict.chunk_indices``' of ``kmax``, ``kmax``
    and one chunk, the last cluster's other slots masked; on flat rows a
    mixed ``chunk_idx`` that also varies inside a timeslot."""
    rdt = DTYPES[dt]
    cdt = jnp.complex128 if dt == "f64" else jnp.complex64
    rng = np.random.default_rng(seed)
    sta1 = jnp.asarray(np.tile([p[0] for p in PAIRS], TSZ), jnp.int32)
    sta2 = jnp.asarray(np.tile([p[1] for p in PAIRS], TSZ), jnp.int32)
    coh = jnp.asarray(rng.normal(size=(M, B, 2, 2))
                      + 1j * rng.normal(size=(M, B, 2, 2)), cdt)
    cidx = np.zeros((M, B), np.int32)
    cmask = np.ones((M, kmax), bool)
    if kmax > 1 and nbase:
        nchunk = np.array([kmax, kmax, 1])
        cidx = rp.chunk_indices(TSZ, NBASE, nchunk)
        cmask = np.arange(kmax) < nchunk[:, None]
    elif kmax > 1:
        cidx[0] = (np.arange(B) * kmax) // B
        cidx[1] = np.arange(B) % kmax
        cidx[2] = (np.arange(B) // NBASE) % kmax
    J0 = np.tile(np.eye(2), (M, kmax, N, 1, 1)).astype(complex)
    Jt = J0 + 0.1 * (rng.normal(size=J0.shape)
                     + 1j * rng.normal(size=J0.shape))
    x8 = sum(rp.model8(coh[m], jnp.asarray(Jt[m], cdt), sta1, sta2,
                       jnp.asarray(cidx[m])) for m in range(M)) \
        + jnp.asarray(0.05 * rng.normal(size=(B, 8)), rdt)
    wt = np.ones((B, 8))
    wt[rng.random(B) < 0.1] = 0.0
    return dict(x8=x8.astype(rdt), coh=coh, sta1=sta1, sta2=sta2,
                cidx=jnp.asarray(cidx), cmask=jnp.asarray(cmask),
                J0=jnp.asarray(J0, cdt), Jt=jnp.asarray(Jt, cdt),
                wt=jnp.asarray(wt, rdt), kmax=kmax)


def _cfg(nbase, mode=RTR, **kw):
    return sage.SageConfig(max_emiter=2, max_iter=3, max_lbfgs=0,
                           solver_mode=mode, randomize=False, nbase=nbase,
                           **kw)


def _close(got, want, tol, what):
    want = np.asarray(want, np.float64 if np.isrealobj(want) else complex)
    got = np.asarray(got, want.dtype)
    assert got.shape == want.shape, what
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=tol * np.abs(want).max(), err_msg=what)


def _same_solution(pb, J, Jw, dt):
    """The clusters' models under ``J`` and under ``Jw`` agree; at
    float64 so do the Jones themselves. (A cluster's model does not
    change under a unitary on its Jones, and in float32 a dozen
    trust-region steps wander along that direction by a few percent.)"""
    for m in range(M):
        _close(_plain_model(pb, J, m), _plain_model(pb, Jw, m), TOL[dt],
               f"model {m}")
    if dt == "f64":
        _close(J, Jw, TOL[dt], "J")


# -- the plain construction ---------------------------------------------------

def _plain_model(pb, J, m, out_dtype=None):
    return rp.model8(pb["coh"][m], J[m], pb["sta1"], pb["sta2"],
                     pb["cidx"][m], out_dtype=out_dtype)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _plain_visit(cfg, xres, coh_m, sta1, sta2, cidx_m, wt, cmask_m, J_m, nu,
                 admm_m):
    """One cluster visit the plain way: ``model8`` added to a ``[B, 8]``
    carry, the solve through the solvers' ``[B, 8]`` entries with the
    budget ``sage._visit_solve`` gives an unweighted sweep, ``model8``
    subtracted. One program a configuration, whatever the cluster."""
    iter_bar = int(-(-0.8 * M * cfg.max_iter // M))
    itcap = cfg.max_iter + iter_bar
    xd = xres + rp.model8(coh_m, J_m, sta1, sta2, cidx_m,
                          out_dtype=xres.dtype)
    args = (xd, coh_m, sta1, sta2, cidx_m, wt, J_m, N)
    if cfg.solver_mode == LM:
        Jn, _info = lm_mod.lm_solve(
            *args, chunk_mask=cmask_m,
            config=lm_mod.LMConfig(itmax=itcap,
                                   dtype_policy=cfg.dtype_policy),
            itmax_dynamic=cfg.max_iter, admm=admm_m, row_period=cfg.nbase)
    else:
        Jn, nu, _info = rtr.rtr_solve_robust(
            *args, nu0=nu, nulow=cfg.nulow, nuhigh=cfg.nuhigh,
            chunk_mask=cmask_m,
            config=rtr.RTRConfig(itmax=itcap,
                                 dtype_policy=cfg.dtype_policy),
            wt_rounds=2, itmax_dynamic=cfg.max_iter, admm=admm_m,
            row_period=cfg.nbase)
    return Jn, nu, xd - rp.model8(coh_m, Jn, sta1, sta2, cidx_m,
                                  out_dtype=xres.dtype)


def _plain_sweep(pb, cfg, order, J, xres, nuM, admm=None):
    """One EM sweep the plain way, a ``[B, 8]`` carry through the
    clusters in ``order``."""
    J, nuM = list(J), list(nuM)
    wt = pb["wt"].astype(xres.dtype)
    cfg = cfg._replace(fuse_residual=True)      # one program for both
    for m in order:
        admm_m = None if admm is None else tuple(a[m] for a in admm)
        J[m], nuM[m], xres = _plain_visit(
            cfg, xres, pb["coh"][m], pb["sta1"], pb["sta2"], pb["cidx"][m],
            wt, pb["cmask"][m], J[m], nuM[m], admm_m)
    return jnp.stack(J), xres, jnp.stack(nuM)


def _plain_entry(pb, cfg):
    """(x8, xres0, nuM0) in the storage dtype, the prelude's way."""
    stq = dtp.storage_dtype(cfg.dtype_policy, pb["x8"].dtype)
    x8 = pb["x8"].astype(stq)
    v = sum(_plain_model(pb, pb["J0"], m) for m in range(M))
    nu0 = jnp.asarray(cfg.nulow, dtp.acc_dtype(stq))
    return x8, x8 - v.astype(stq), jnp.full((M,), nu0)


@functools.lru_cache(maxsize=None)
def _plain_solve(layout, dt, sweeps, with_admm=False):
    """(J, res_0, res_1) of ``sweeps`` plain sweeps in the natural
    order."""
    kmax, nbase, _ = LAYOUTS[layout]
    pb = _problem(kmax, dt, nbase=nbase)
    cfg = _cfg(nbase)
    x8, xres, nuM = _plain_entry(pb, cfg)
    res_0 = jnp.linalg.norm(xres * pb["wt"]) / (8 * B)
    J = pb["J0"]
    for _ci in range(sweeps):
        J, xres, nuM = _plain_sweep(pb, cfg, range(M), J, xres, nuM,
                                    _admm(pb) if with_admm else None)
    v = sum(_plain_model(pb, J, m) for m in range(M))
    res_1 = jnp.linalg.norm((x8 - v) * pb["wt"]) / (8 * B)
    return J, float(res_0), float(res_1)


def _admm(pb):
    """(Y, BZ, rho): a consensus pull toward the true Jones."""
    rdt = pb["wt"].dtype
    BZ = ne.jones_c2r(pb["Jt"]).astype(rdt)
    Y = 0.01 * jnp.ones_like(BZ)
    return Y, BZ, jnp.asarray([0.5, 1.0, 2.0], rdt)


def _planes(pb, cfg):
    """(rows over all clusters with data and weights, xres0 on planes):
    what a program's prelude makes."""
    stq = dtp.storage_dtype(cfg.dtype_policy, pb["x8"].dtype)
    rows = ne.RowPlanes(pb["x8"].astype(stq), pb["coh"],
                        pb["wt"].astype(stq), pb["sta1"], pb["sta2"],
                        pb["cidx"], pb["kmax"], N, cfg.nbase)
    return rows, sage._prelude(rows, pb["J0"])[0]


# -- (a) a cluster's model ----------------------------------------------------

@pytest.mark.parametrize("layout, dt", [
    *itertools.product(LAYOUTS, DTYPES), ("k1-periodic", "bf16")])
def test_cluster_model_is_model8(layout, dt):
    """``_cluster_model`` on a cluster's slice of the program's planes
    against ``rime.predict.model8``, every cluster (float64: 1e-12)."""
    kmax, nbase, want = LAYOUTS[layout]
    store = jnp.bfloat16 if dt == "bf16" else None
    pb = _problem(kmax, "f32" if store else dt, nbase=nbase)
    rows = ne.RowPlanes(None, pb["coh"], None, pb["sta1"], pb["sta2"],
                        pb["cidx"], kmax, N, nbase)
    assert ("periodic" if rows.periodic else "flat") == want
    assert rows.rows == ((TSZ, NBASE) if rows.periodic else (B,))
    for m in range(M):
        got = sage._cluster_model(rows.cluster(m), pb["Jt"][m],
                                  store or pb["x8"].dtype)
        assert got.shape == (8,) + rows.rows
        assert got.dtype == (store or pb["x8"].dtype)
        _close(rows.to_rows(got), _plain_model(pb, pb["Jt"], m, store),
               {"f64": 1e-12, "f32": 1e-5, "bf16": 1e-2}[dt], m)


def test_a_map_that_varies_inside_a_timeslot_is_refused_a_period():
    """The mixed chunk map of the flat layouts, handed with ``nbase``
    set, is refused where it is concrete: by ``RowPlanes`` itself,
    through the one check it makes (``planes.check_chunk_rows``)."""
    from sagecal_tpu.rime import planes
    pb = _problem(2)
    args = (None, pb["coh"], None, pb["sta1"], pb["sta2"], pb["cidx"], 2, N)
    with pytest.raises(ValueError, match="inside a timeslot"):
        ne.RowPlanes(*args, NBASE)
    with pytest.raises(ValueError, match="inside a timeslot"):
        planes.check_chunk_rows(np.asarray(pb["cidx"]), NBASE)
    assert not ne.RowPlanes(*args, 0).periodic
    planes.check_chunk_rows(np.asarray(pb["cidx"]), 0)
    planes.check_chunk_rows(
        np.asarray(_problem(2, nbase=NBASE)["cidx"]), NBASE)


# -- (b) one EM sweep ---------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _sweep_program(layout, dt, mode, fused):
    """``sage._em_sweep`` as one program of (J, xres planes, nuM, wt
    rows, perm), and what it starts from."""
    kmax, nbase, _ = LAYOUTS[layout]
    pb = _problem(kmax, "f32" if dt == "bf16" else dt, nbase=nbase)
    cfg = _cfg(nbase, mode, fuse_residual=fused,
               dtype_policy="bf16" if dt == "bf16" else "f32")
    iter_bar = int(-(-0.8 * M * cfg.max_iter // M))

    @jax.jit
    def program(J, xres, nuM, wt, perm):
        return sage._em_sweep(
            J, xres, nuM, pb["coh"], pb["sta1"], pb["sta2"], pb["cidx"],
            pb["cmask"], wt, jnp.zeros((M,), nuM.dtype), jnp.asarray(False),
            jnp.asarray(False), jax.random.PRNGKey(0), perm, None, N, cfg,
            M * cfg.max_iter, iter_bar)
    return pb, cfg, program


@pytest.mark.parametrize("layout, dt, mode, fused, order", [
    *[(lay, "f64", RTR, f, o) for lay, f, o in itertools.product(
        LAYOUTS, (True, False), ("natural", "permuted"))],
    ("k1-periodic", "f64", LM, True, "permuted"),
    ("k2", "f64", LM, True, "permuted"),
    ("k2-flat", "f64", LM, True, "permuted"),
    ("k1-periodic", "f32", RTR, True, "permuted"),
    ("k1-flat", "f32", RTR, True, "permuted"),
    ("k1-periodic", "bf16", RTR, True, "natural")],
    ids=lambda v: {RTR: "rtr", LM: "lm", True: "fused",
                   False: "unfused"}.get(v, v))
def test_one_sweep_is_the_plain_sweep(layout, dt, mode, fused, order):
    """``_em_sweep`` on planes, fused and unfused, in the natural and in
    a permuted order, against ``model8`` around the ``[B, 8]`` solver
    entries on a ``[B, 8]`` carry: the clusters' models under the ``J``
    it ends on (``J`` itself at float64), nu and the residual."""
    pb, cfg, program = _sweep_program(layout, dt, mode, fused)
    perm = [0, 1, 2] if order == "natural" else [2, 0, 1]
    _x8, xres0, nuM0 = _plain_entry(pb, cfg)
    rows, xres_p = _planes(pb, cfg)
    _close(rows.to_rows(xres_p), xres0, TOL[dt] * 1e-2, "prelude")
    Jw, xw, nuw = _plain_sweep(pb, cfg, perm, pb["J0"], xres0, nuM0)
    J, xres, _nerr, nuM, tk = program(
        pb["J0"], xres_p, nuM0, rows.to_rows(rows.w),
        jnp.asarray(perm, jnp.int32))
    assert xres.shape == (8,) + rows.rows and xres.dtype == xres0.dtype
    assert int(tk[0]) > 0 and (int(tk[3]) > 0) == (mode == RTR)
    _same_solution(pb, J, Jw, dt)
    _close(nuM, nuw, TOL[dt], "nu")
    _close(rows.to_rows(xres), xw, TOL[dt], "xres")


# -- (c) the drivers ----------------------------------------------------------

def _drive(driver, pb, cfg):
    args = (pb["x8"], pb["coh"], pb["sta1"], pb["sta2"], pb["cidx"],
            pb["cmask"], pb["J0"], N, pb["wt"])
    if driver == "sagefit":
        return jax.jit(lambda: sage.sagefit(*args, config=cfg))()
    if driver == "admm":
        return jax.jit(lambda: sage.sagefit(*args, config=cfg,
                                            admm=_admm(pb)))()
    plan = driver.split("-")[1]
    return sage.sagefit_host(*args, config=cfg._replace(
        promote="on" if plan == "promoted" else "off",
        fuse="on" if plan == "fused" else "off"))


@pytest.mark.parametrize("driver, layout, dt", [
    *itertools.product(("sagefit", "sagefit_host-per_cluster",
                        "sagefit_host-fused", "admm"), LAYOUTS, ("f64",)),
    ("sagefit", "k1-periodic", "f32"), ("admm", "k1-periodic", "f32")])
def test_solve_is_the_plain_solve(driver, layout, dt):
    """``sagefit``, ``sagefit_host`` (a program a cluster and a program
    a sweep) and the ADMM J update (``admm=`` given) against the plain
    construction's sweeps: the solution, res_0 and res_1. Two sweeps at
    float64; one at float32, where a second sweep's trust region takes
    another branch on one rounding or the other."""
    kmax, nbase, want = LAYOUTS[layout]
    pb = _problem(kmax, dt, nbase=nbase)
    sweeps = 2 if dt == "f64" else 1
    Jw, r0, r1 = _plain_solve(layout, dt, sweeps,
                              with_admm=driver == "admm")
    J, info = _drive(driver, pb, _cfg(nbase)._replace(max_emiter=sweeps))
    _same_solution(pb, J, Jw, dt)
    assert float(info["res_0"]) == pytest.approx(r0, rel=TOL[dt])
    assert float(info["res_1"]) == pytest.approx(r1, rel=TOL[dt])
    assert r1 < r0
    if driver.startswith("sagefit_host"):
        assert info["plan"] == driver.split("-")[1]
        assert info["sweep_rows"] == want


@pytest.mark.parametrize("lbfgs", [0, 2], ids=["res", "refine"])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_residuals_do_not_depend_on_the_plan(layout, lbfgs):
    """A tile's res_0 and res_1 are one expression on planes whichever
    plan ran it (``_jit_prelude`` / ``_jit_res`` / ``_jit_refine`` or
    ``_jit_sagefit``'s own): bit for bit, which ``tests/test_overlap.py``
    counts on when the learner promotes between its two runs."""
    kmax, nbase, _ = LAYOUTS[layout]
    pb = _problem(kmax, "f32", nbase=nbase)
    out = {}
    for plan in ("per_cluster", "fused", "promoted"):
        J, info = _drive(f"sagefit_host-{plan}", pb,
                         _cfg(nbase)._replace(max_lbfgs=lbfgs))
        assert info["plan"] == plan
        out[plan] = (np.asarray(J), float(info["res_0"]),
                     float(info["res_1"]))
    for plan in ("fused", "promoted"):
        np.testing.assert_array_equal(out[plan][0], out["per_cluster"][0])
        assert out[plan][1:] == out["per_cluster"][1:], plan


# -- (d) the counter that says which layout ran -------------------------------

@pytest.mark.parametrize("driver", ["sagefit_host", "sagefit_host_tiles"])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_info_names_the_sweep_layout(driver, layout):
    """``sweep_rows``: what the mechanism decided from ``nbase`` and
    ``B``, whatever ``kmax``, as a host value beside ``plan``; absent
    from a solve of no sweeps."""
    kmax, nbase, want = LAYOUTS[layout]
    pb = _problem(kmax, nbase=nbase)
    args = [pb["x8"], pb["coh"], pb["sta1"], pb["sta2"], pb["cidx"],
            pb["cmask"], pb["J0"], N, pb["wt"]]
    if driver == "sagefit_host_tiles":
        for i in (0, 1, 6, 8):
            args[i] = jnp.stack([args[i], args[i]])
    cfg = _cfg(nbase)._replace(max_emiter=1, promote="off", fuse="on")
    J, info = getattr(sage, driver)(*args, config=cfg)
    assert info["sweep_rows"] == want == sage.sweep_rows(cfg, B)
    assert "sweep_rows" in sage._PLAN_KEYS
    if driver == "sagefit_host_tiles":
        # the planes under a leading tile axis: the one-tile solve twice
        J1, _info = sage.sagefit_host(
            pb["x8"], pb["coh"], pb["sta1"], pb["sta2"], pb["cidx"],
            pb["cmask"], pb["J0"], N, pb["wt"], config=cfg)
        _close(J[0], J1, 1e-9, "tile 0")
        _close(J[1], J1, 1e-9, "tile 1")
    _J, info = getattr(sage, driver)(*args,
                                     config=cfg._replace(max_emiter=0))
    assert "sweep_rows" not in info


def test_tile_record_carries_sweep_rows(tmp_path):
    from sagecal_tpu import pipeline
    from sagecal_tpu.diag import trace as dtrace
    path = str(tmp_path / "diag.jsonl")
    dtrace.enable(path, entry="test", argv=[])
    try:
        pipeline._emit_tile_record(
            0, 1.0, 0.5, 2.0, {"plan": "promoted", "solve_dispatches": 1,
                               "sweep_rows": "periodic"}, 0.1)
        pipeline._emit_tile_record(1, 1.0, 0.5, 2.0, {"solver_iters": 7},
                                   0.1)
    finally:
        dtrace.disable()
    first, second = [r for r in dtrace.read(path) if r.get("ev") == "tile"]
    assert first["sweep_rows"] == "periodic"
    assert "sweep_rows" not in second


def test_consensus_tile_record_carries_sweep_rows(tmp_path):
    """``cli_mpi``'s interval records name the layout its J updates
    carried their residual on, beside ``assemble_rows``."""
    from sagecal_tpu import cli_mpi
    from sagecal_tpu.diag import trace as dtrace
    import test_consensus_stepper as tcs

    root = str(tmp_path / "obs")
    tcs.make_observation(root)
    diag = tmp_path / "diag.jsonl"
    assert cli_mpi.main(tcs.argv(root) + ["--diag", str(diag)]) == 0
    recs = [r for r in dtrace.read(str(diag)) if r.get("ev") == "tile"]
    assert recs and all(r["sweep_rows"] == "periodic" for r in recs)


# -- what the sweep lowers to at cal-m8x3's shapes ----------------------------

def _loop_eqns(jaxpr, inside=False):
    """Every equation inside a ``while`` (or ``scan``) of a jaxpr, with
    the loops' own equations (whose operands are the carries)."""
    for eqn in jaxpr.eqns:
        loop = eqn.primitive.name in ("while", "scan")
        if inside or loop:
            yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _loop_eqns(inner, inside or loop)


def _row_sized(shape) -> bool:
    """An array over the rows: ``B`` elements or more along an axis of
    ``B`` or of ``nbase`` (the station-sized ``[K, 8N, 8N]`` matrix of
    the trust region's products is 246 016 elements and none of it)."""
    shape = tuple(int(d) for d in shape)
    return (int(np.prod(shape or (1,))) >= B62
            and bool(set(shape) & {B62, NB62}))


def _sweep_specs():
    f, i, c = jnp.float32, jnp.int32, jnp.complex64
    sd = jax.ShapeDtypeStruct
    return dict(x8=sd((B62, 8), f), coh=sd((M8, B62, 2, 2), c),
                sta=sd((B62,), i), cidx=sd((M8, B62), i),
                cmask=sd((M8, 1), jnp.bool_),
                J=sd((M8, 1, N62, 2, 2), c), nuM=sd((M8,), f),
                s=sd((), f), b=sd((), jnp.bool_), perm=sd((M8,), i),
                key=sd((2,), jnp.uint32))


@pytest.mark.parametrize("program", ["sagefit", "em_sweep"])
@pytest.mark.parametrize("nbase", [NB62, 0], ids=["periodic", "flat"])
def test_sweep_lowers_without_row_sized_products(program, nbase):
    """``_jit_sagefit`` and ``_jit_em_sweep`` at M 8, B 18 910, N 62,
    ``-j 5`` (nothing runs): inside the loops no ``dot_general`` and no
    ``convolution`` has an operand of ``B`` elements or more, no loop
    carries an array with a trailing ``2, 2`` of ``B`` rows, and the
    running residual they do carry is ``[8, *rows]``."""
    sp = _sweep_specs()
    cfg = sage.SageConfig(max_emiter=4, max_iter=2, max_lbfgs=10,
                          solver_mode=RTR, nbase=nbase)
    rows = (B62 // NB62, NB62) if nbase else (B62,)
    with jax.enable_x64(False):
        if program == "sagefit":
            traced = sage._jit_sagefit.trace(
                sp["x8"], sp["coh"], sp["sta"], sp["sta"], sp["cidx"],
                sp["cmask"], sp["J"], N62, sp["x8"], sp["s"], cfg, None,
                0, sp["key"])
        else:
            xres = jax.ShapeDtypeStruct((8,) + rows, jnp.float32)
            traced = sage._jit_em_sweep.trace(
                sp["J"], xres, sp["nuM"], sp["coh"], sp["sta"],
                sp["sta"], sp["cidx"], sp["cmask"], sp["x8"], sp["nuM"],
                sp["b"], sp["b"], sp["key"], sp["perm"], None, N62,
                cfg._replace(max_emiter=0), M8 * cfg.max_iter, 2, 0)
    eqns = list(_loop_eqns(traced.jaxpr.jaxpr))
    names = {e.primitive.name for e in eqns}
    assert "while" in names
    # without a period the Gauss-Newton matrix comes from the generic
    # assembly of ``[B, 2, 2, 4]`` factors (scope ``assemble``, which
    # lays its rows out itself): the guard there is on the rest
    generic = nbase == 0

    def product(e):
        return (e.primitive.name in ("dot_general", "conv_general_dilated")
                and any(_row_sized(v.aval.shape) for v in e.invars)
                and not (generic and "assemble" in str(
                    e.source_info.name_stack)))
    assert not [e for e in eqns if product(e)]
    carried = [v.aval for e in eqns if e.primitive.name in ("while", "scan")
               for v in e.invars if hasattr(v.aval, "shape")]
    if not generic:
        assert not [a for a in carried
                    if a.shape[-2:] == (2, 2) and a.size >= 4 * B62], carried
    assert not [a for a in carried if a.shape == (B62, 8)] or generic
    assert [a for a in carried if a.shape == (8,) + rows]
    # the whole program: a row-sized product outside the loops would be
    # a ``[.., 2, 2]`` model of the prelude or the final
    assert not [e for e in _walk(traced.jaxpr.jaxpr) if product(e)]
    if generic:
        return
    # and the same of the module the compiler is handed
    for line in traced.lower().as_text().splitlines():
        if re.search(r"stablehlo\.(dot_general|convolution)\b", line):
            assert not any(
                _row_sized([d for d in dims.split("x") if d])
                for dims in re.findall(r"tensor<((?:\d+x)*)[a-z]", line)
            ), line
