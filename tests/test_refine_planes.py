"""The joint refine on planes (ISSUE 36).

``sage._refine_cost_fn`` evaluates the model of all clusters, its cost, its
gradient and its restriction to a search line as real elementwise
arithmetic on planes with the rows on the minor axes, written out from
the row model's Wirtinger factors. These cases hold all of it to the
plain construction it replaced, kept here as the reference: a sum over
the clusters of ``rime.predict.model8`` (gathers of ``[B, 2, 2]`` complex
Jones, two batched complex products a cluster) under ``jax.grad`` and
``jax.jvp``. And they lower the refine at ``cal-m8x3``'s shapes and look
for what that construction would bring back.
"""

import itertools
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from sagecal_tpu.rime import predict as rp
from sagecal_tpu.solvers import lbfgs as lb, normal_eq as ne, sage

from test_line_restrict import M, N, PAIRS, TSZ, _cfg, _problem

NBASE = len(PAIRS)
NU = 2.5
ROWS = {"periodic": NBASE, "flat": 0}


def _rows_problem(kmax, rows, *args):
    """The tiny observation for a layout: a row period promises a chunk
    map by timeslot; flat rows keep the map that alternates row by
    row."""
    return _problem(kmax, *args, by_timeslot=rows == "periodic")


def _reference(pb, robust, mode, nu=NU):
    """(model, cost_fn, line_func, shape, Jref) the plain way."""
    kmax = pb["kmax"]
    shape = (M * kmax, N, ne.jones_npar(mode))
    Jref = ne.jones_constrain(pb["J0"].reshape(M * kmax, N, 2, 2), mode)
    x8, wt = pb["x8"], pb["wt"]

    def model(p):
        Jr = ne.jones_from_params(p.reshape(shape), mode, Jref).reshape(
            M, kmax, N, 2, 2)
        return sum(rp.model8(pb["coh"][m], Jr[m], pb["sta1"], pb["sta2"],
                             pb["cidx"][m]) for m in range(M))

    def cost_of(r):
        return jnp.sum(jnp.log1p(r * r / nu)) if robust else jnp.sum(r * r)

    def cost_fn(p):
        return cost_of((x8 - model(p)) * wt)

    def line_func(xk, pk):
        m0, dm = jax.jvp(model, (xk,), (pk,))
        r0, v1, v2 = (x8 - m0) * wt, dm * wt, model(pk) * wt

        def on_line(a):
            r = r0 - a * (v1 + a * v2)
            fr = r / (nu + r * r) if robust else r
            return cost_of(r), -2.0 * jnp.sum(fr * (v1 + 2.0 * a * v2))
        return on_line

    return model, cost_fn, line_func, shape, Jref


def _planes(pb, robust, mode, rows, nu=NU):
    """(cost_fn, grad_fn, line_func) as the refine builds them."""
    _model, _cost, _line, shape, Jref = _reference(pb, robust, mode)
    return sage._refine_cost_fn(
        pb["x8"], pb["coh"], pb["sta1"], pb["sta2"], pb["cidx"], pb["wt"],
        shape, pb["kmax"], N, robust, nu, mode=mode,
        Jref=None if mode == "full" else Jref, row_period=ROWS[rows])


def _point(pb, mode, scale=0.05):
    Jref = ne.jones_constrain(
        pb["J0"].reshape(M * pb["kmax"], N, 2, 2), mode)
    p0 = ne.params_from_jones(Jref, mode).reshape(-1)
    return p0 + scale * jnp.asarray(pb["rng"].normal(size=p0.shape))


@pytest.mark.parametrize("robust, mode, kmax, rows", [
    pytest.param(r, m, k, w, id=f"{'robust' if r else 'plain'}-{m}-k{k}-{w}")
    for r, m, k, w in itertools.product(
        (True, False), ("full", "diag", "phase"), (1, 2), sorted(ROWS))])
def test_joint_pass_is_the_plain_construction(robust, mode, kmax, rows):
    """Cost, gradient and restriction (float64, rtol 1e-10) against the
    sum of ``model8`` under ``jax.grad`` and ``jax.jvp``."""
    pb = _rows_problem(kmax, rows)
    _model, cost_ref, line_ref, _shape, _Jref = _reference(pb, robust, mode)
    cost, grad, line = _planes(pb, robust, mode, rows)
    p = _point(pb, mode)
    assert float(cost(p)) == pytest.approx(float(cost_ref(p)), rel=1e-10)
    g_ref = np.asarray(jax.grad(cost_ref)(p))
    np.testing.assert_allclose(np.asarray(grad(p)), g_ref, rtol=1e-10,
                               atol=1e-10 * np.abs(g_ref).max())
    if mode == "phase":
        assert line is None
        return
    pk = 0.05 * jnp.asarray(pb["rng"].normal(size=p.shape))
    steps = jnp.asarray([0.0, 0.1, 1.0, 10.0])
    got = np.asarray(jax.vmap(line(p, pk))(steps))
    want = np.asarray(jax.vmap(line_ref(p, pk))(steps))
    np.testing.assert_allclose(got[0], want[0], rtol=1e-10)
    np.testing.assert_allclose(got[1], want[1],
                               atol=1e-10 * np.abs(want[1]).max())


@pytest.mark.parametrize("kmax, rows, layout", [
    (1, "periodic", "periodic"), (1, "flat", "flat"),
    (2, "periodic", "periodic"), (2, "flat", "flat")])
def test_full_model8_is_the_joint_model(kmax, rows, layout):
    """One definition of the sum of all clusters' corrupted models: the
    rows the callers of ``full_model8`` get are the refine's planes
    transposed, in the layout the input decides."""
    pb = _rows_problem(kmax, rows)
    model, _cost, _line, shape, _Jref = _reference(pb, False, "full")
    p = _point(pb, "full")
    J = ne.jones_r2c(p.reshape(shape)).reshape(M, kmax, N, 2, 2)
    got = sage.full_model8(J, pb["coh"], pb["sta1"], pb["sta2"], pb["cidx"],
                           ROWS[rows])
    assert got.shape == pb["x8"].shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(model(p)),
                               rtol=1e-10, atol=1e-12)
    planes = ne.RowPlanes(pb["x8"], pb["coh"], pb["wt"], pb["sta1"],
                          pb["sta2"], pb["cidx"], kmax, N, ROWS[rows])
    assert planes.periodic == (layout == "periodic")
    assert planes.rows == ((TSZ, NBASE) if planes.periodic
                           else (TSZ * NBASE,))
    assert planes.c.shape == (8, M) + planes.rows
    np.testing.assert_array_equal(np.asarray(planes.to_rows(planes.x)),
                                  np.asarray(pb["x8"]))


@pytest.mark.parametrize("rows", sorted(ROWS))
def test_sagefit_refines_as_the_plain_construction_does(rows):
    """float32: the sweeps are one program either way, so a solve with
    the refine off hands the plain refine its starting point, and the
    solve with the refine on must end where that ends: ``res_1`` to the
    rounding of another summation order (tests/test_sage.py: 1e-5), the
    Jones to the search's (tests/test_line_restrict.py: 1e-4)."""
    pb = _problem(1, jnp.float32)
    cfg = _cfg(nbase=ROWS[rows])
    args = (pb["x8"], pb["coh"], pb["sta1"], pb["sta2"], pb["cidx"],
            pb["cmask"], pb["J0"], N, pb["wt"])
    J_sw, info_sw = sage.sagefit(*args, config=cfg._replace(max_lbfgs=0))
    J, info = sage.sagefit(*args, config=cfg)
    assert int(info["lbfgs_iters"]) == cfg.max_lbfgs

    pb_sw = dict(pb, J0=J_sw)
    model, cost_ref, line_ref, shape, _Jref = _reference(
        pb_sw, True, "full", nu=info_sw["mean_nu"])
    p0 = ne.jones_c2r(J_sw).reshape(-1)
    p1, k, _n = lb.lbfgs_fit(cost_ref, jax.grad(cost_ref), p0,
                             itmax=cfg.max_lbfgs, M=cfg.lbfgs_m,
                             return_iters=True, line_func=line_ref)
    assert int(k) == cfg.max_lbfgs
    res_ref = jnp.linalg.norm((pb["x8"] - model(p1)) * pb["wt"]) \
        / pb["x8"].size
    assert float(info["res_1"]) == pytest.approx(float(res_ref), rel=1e-5)
    assert float(info["res_1"]) < float(info_sw["res_1"])
    np.testing.assert_allclose(
        np.asarray(ne.jones_c2r(J)).reshape(-1), np.asarray(p1), atol=1e-4)


@pytest.mark.parametrize("driver", ["sagefit_host", "sagefit_host_tiles"])
@pytest.mark.parametrize("kmax, rows", [(1, "periodic"), (1, "flat"),
                                        (2, "periodic")])
def test_info_names_the_row_layout(driver, kmax, rows):
    """``refine_rows``: what the mechanism decided from ``nbase`` and
    ``B``, whatever ``kmax``, as a host value beside ``plan``."""
    pb = _rows_problem(kmax, rows)
    cfg = _cfg(nbase=ROWS[rows])
    args = [pb["x8"], pb["coh"], pb["sta1"], pb["sta2"], pb["cidx"],
            pb["cmask"], pb["J0"], N, pb["wt"]]
    if driver == "sagefit_host_tiles":
        for i in (0, 1, 6, 8):
            args[i] = jnp.stack([args[i], args[i]])
    _J, info = getattr(sage, driver)(*args, config=cfg)
    assert info["refine_rows"] == rows
    _J, info = getattr(sage, driver)(*args,
                                     config=cfg._replace(max_lbfgs=0))
    assert "refine_rows" not in info


def test_a_map_that_varies_inside_a_timeslot_is_refused_a_period():
    """The map that alternates row by row, handed with ``nbase`` set, is
    refused where it is concrete; at ``row_period=0`` it refines on flat
    rows (``test_joint_pass_is_the_plain_construction``'s k2-flat)."""
    pb = _problem(2)
    with pytest.raises(ValueError, match="inside a timeslot"):
        _planes(pb, True, "full", "periodic")
    assert _planes(pb, True, "full", "flat")


def test_tile_record_carries_refine_rows(tmp_path):
    from sagecal_tpu import pipeline
    from sagecal_tpu.diag import trace as dtrace
    path = str(tmp_path / "diag.jsonl")
    dtrace.enable(path, entry="test", argv=[])
    try:
        pipeline._emit_tile_record(
            0, 1.0, 0.5, 2.0, {"refine_passes": jnp.asarray(31),
                               "plan": "promoted", "solve_dispatches": 1,
                               "refine_rows": "periodic"}, 0.1)
        pipeline._emit_tile_record(1, 1.0, 0.5, 2.0, {"solver_iters": 7},
                                   0.1)
    finally:
        dtrace.disable()
    first, second = [r for r in dtrace.read(path) if r.get("ev") == "tile"]
    assert (first["refine_passes"], first["refine_rows"]) == (31, "periodic")
    assert "refine_rows" not in second


# -- what the refine lowers to at cal-m8x3's shapes ---------------------------

N62, M8, T10 = 62, 8, 10
NB62 = N62 * (N62 - 1) // 2
B62 = NB62 * T10


def _walk(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _walk(inner)


@pytest.mark.parametrize("nbase", [NB62, 0], ids=["periodic", "flat"])
def test_refine_lowers_without_row_sized_contractions(nbase):
    """``_jit_refine`` at M 8, B 18 910, N 62 (nothing runs): no
    ``dot_general`` and no ``convolution`` has an operand of ``B``
    elements or more, where the plain construction had a batched 2 x 2
    product a cluster and its transposes, and nothing scans over the
    clusters."""
    f, i, c = jnp.float32, jnp.int32, jnp.complex64
    sd = jax.ShapeDtypeStruct
    spec = (sd((B62, 8), f), sd((M8, B62, 2, 2), c), sd((B62,), i),
            sd((B62,), i), sd((M8, B62), i), sd((M8, 1, N62, 2, 2), c),
            sd((B62, 8), f), sd((), f))
    cfg = sage.SageConfig(nbase=nbase, max_lbfgs=10, lbfgs_m=7)
    with jax.enable_x64(False):
        traced = sage._jit_refine.trace(*spec, N62, cfg, True)
        text = traced.lower().as_text()
    eqns = list(_walk(traced.jaxpr.jaxpr))
    names = {e.primitive.name for e in eqns}
    assert "while" in names            # the walk reaches the LBFGS loop
    assert not names & {"scan", "conv_general_dilated"}
    big = [e for e in eqns if e.primitive.name == "dot_general"
           and max(v.aval.size for v in e.invars) >= B62]
    assert not big, big
    assert max(v.aval.size for e in eqns for v in e.outvars) >= 8 * B62
    # and the same of the module the compiler is handed
    for line in text.splitlines():
        if re.search(r"stablehlo\.(dot_general|convolution)\b", line):
            sizes = [np.prod([int(d) for d in dims.split("x") if d] or [1])
                     for dims in re.findall(r"tensor<((?:\d+x)*)[a-z]", line)]
            assert max(sizes) < B62, line
