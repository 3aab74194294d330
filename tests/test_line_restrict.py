"""The joint refine's cost restricted to a search line (ISSUE 27).

Where the Jones matrices are linear in the parameters (``full``,
``diag``) the model is a homogeneous quadratic in them, so along
``xk + a pk`` the weighted residual is exactly ``r0 - a V1 - a^2 V2``
and ``sage._refine_cost_fn`` hands ``lbfgs_fit`` that restriction beside
the cost. These cases hold the restriction to the cost it restricts, the
search on it to the search through the whole model, and the pass counter
to what the loop really ran.
"""

import functools
import itertools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from sagecal_tpu.config import SolverMode
from sagecal_tpu.rime import predict as rp
from sagecal_tpu.solvers import lbfgs as lb, normal_eq as ne, sage

N, M, TSZ = 5, 2, 4
PAIRS = [(i, j) for i in range(N) for j in range(i + 1, N)]
B = len(PAIRS) * TSZ
STEPS = (0.0, 0.1, 1.0, 10.0)


def _problem(kmax, rdt=jnp.float64, seed=3, by_timeslot=False):
    """A tiny observation: M clusters, ``kmax`` hybrid chunks with a
    mixed ``chunk_idx`` (cluster 0 changes chunk mid-tile, cluster 1
    alternates row by row: a map for flat rows only) or, ``by_timeslot``,
    the map ``rime.predict.chunk_indices`` makes of ``kmax`` and one
    chunk (what a row period promises); a tenth of the rows flagged to
    zero weight."""
    cdt = jnp.complex128 if rdt == jnp.float64 else jnp.complex64
    rng = np.random.default_rng(seed)
    sta1 = jnp.asarray(np.tile([p[0] for p in PAIRS], TSZ), jnp.int32)
    sta2 = jnp.asarray(np.tile([p[1] for p in PAIRS], TSZ), jnp.int32)
    coh = jnp.asarray(rng.normal(size=(M, B, 2, 2))
                      + 1j * rng.normal(size=(M, B, 2, 2)), cdt)
    cidx = np.zeros((M, B), np.int32)
    if by_timeslot:
        cidx = rp.chunk_indices(TSZ, len(PAIRS), np.array([kmax, 1]))
    elif kmax > 1:
        cidx[0] = (np.arange(B) * kmax) // B
        cidx[1] = np.arange(B) % kmax
    cidx = jnp.asarray(cidx)
    J0 = np.tile(np.eye(2), (M, kmax, N, 1, 1)).astype(complex)
    Jt = J0 + 0.1 * (rng.normal(size=J0.shape)
                     + 1j * rng.normal(size=J0.shape))
    x8 = (sage.full_model8(jnp.asarray(Jt, cdt), coh, sta1, sta2, cidx)
          + jnp.asarray(0.05 * rng.normal(size=(B, 8)), rdt))
    wt = np.ones((B, 8))
    wt[rng.random(B) < 0.1] = 0.0
    return dict(x8=x8.astype(rdt), coh=coh, sta1=sta1, sta2=sta2, cidx=cidx,
                cmask=jnp.ones((M, kmax), bool), J0=jnp.asarray(J0, cdt),
                wt=jnp.asarray(wt, rdt), kmax=kmax, rng=rng)


def _closures(pb, robust, mode):
    """(cost_fn, line_func, p0) as the refine builds them."""
    kmax = pb["kmax"]
    shape = (M * kmax, N, ne.jones_npar(mode))
    Jref = ne.jones_constrain(pb["J0"].reshape(M * kmax, N, 2, 2), mode)
    cost, _grad, line = sage._refine_cost_fn(
        pb["x8"], pb["coh"], pb["sta1"], pb["sta2"], pb["cidx"], pb["wt"],
        shape, kmax, N, robust, 2.5, mode=mode,
        Jref=None if mode == "full" else Jref)
    p0 = ne.params_from_jones(Jref, mode).reshape(-1).astype(pb["x8"].dtype)
    return cost, line, p0


def _both_ways(cost, line, xk, pk, steps):
    """[(phi, dphi) restricted, (phi, dphi) through the model] per step."""
    steps = jnp.asarray(steps, xk.dtype)
    on_line = jax.jit(lambda: jax.vmap(line(xk, pk))(steps))()
    direct = jax.jit(jax.vmap(
        lambda a: (cost(xk + a * pk),
                   jnp.dot(jax.grad(cost)(xk + a * pk), pk))))(steps)
    return np.asarray(on_line), np.asarray(direct)


@functools.lru_cache(maxsize=None)
def _curve(robust, mode, kmax):
    pb = _problem(kmax)
    cost, line, xk = _closures(pb, robust, mode)
    pk = jnp.asarray(0.05 * pb["rng"].normal(size=xk.shape))
    return _both_ways(cost, line, xk, pk, STEPS)


@pytest.mark.parametrize("robust, mode, kmax, i", [
    pytest.param(r, m, k, i, id=f"{'robust' if r else 'plain'}-{m}-k{k}"
                                f"-a{STEPS[i]:g}")
    for r, m, k, i in itertools.product((True, False), ("full", "diag"),
                                        (1, 2), range(len(STEPS)))])
def test_restriction_is_the_cost_on_the_line(robust, mode, kmax, i):
    on_line, direct = _curve(robust, mode, kmax)
    assert on_line[0][i] == pytest.approx(direct[0][i], rel=1e-11)
    slope = np.abs(direct[1]).max()
    assert abs(on_line[1][i] - direct[1][i]) <= 1e-10 * slope


@pytest.mark.parametrize("rdt, rel", [(jnp.float32, 1e-5),
                                      (jnp.float64, 1e-12)],
                         ids=["f32", "f64"])
def test_restriction_does_not_cancel_on_a_short_step(rdt, rel):
    """``|pk| = 1e-3 |xk|``: V1 is the model's derivative, not a
    difference of three models, so nothing cancels."""
    pb = _problem(2, rdt)
    cost, line, xk = _closures(pb, True, "full")
    assert xk.dtype == rdt
    pk = jnp.asarray(pb["rng"].normal(size=xk.shape), rdt)
    pk = pk * (1e-3 * jnp.linalg.norm(xk) / jnp.linalg.norm(pk))
    on_line, direct = _both_ways(cost, line, xk, pk, STEPS)
    assert on_line.dtype == np.dtype(rdt)
    np.testing.assert_allclose(on_line[0], direct[0], rtol=rel)
    np.testing.assert_allclose(on_line[1], direct[1],
                               atol=rel * np.abs(direct[1]).max())


def test_restriction_lives_in_the_accumulation_dtype():
    """``--dtype-policy bf16`` stores x8 and the weights in bf16; the
    restriction, like the cost, is f32."""
    pb = _problem(1, jnp.float32)
    pb["x8"] = pb["x8"].astype(jnp.bfloat16)
    pb["wt"] = pb["wt"].astype(jnp.bfloat16)
    cost, line, xk = _closures(pb, True, "full")
    xk = xk.astype(jnp.float32)
    pk = jnp.asarray(0.05 * pb["rng"].normal(size=xk.shape), jnp.float32)
    on_line, direct = _both_ways(cost, line, xk, pk, STEPS)
    assert on_line.dtype == np.float32
    np.testing.assert_allclose(on_line[0], direct[0], rtol=1e-5)


def test_phase_mode_has_no_restriction():
    """J = Jref exp(i theta) is no polynomial in the step."""
    _cost, line, _p0 = _closures(_problem(1), True, "phase")
    assert line is None


# -- the search on the restriction against the search through the cost --------

def _quartic():
    """tests/test_lm.py's valley: a quartic, whose restriction to a line
    is written out by hand."""
    rng = np.random.default_rng(12)
    A = jnp.asarray(rng.normal(size=(30, 12)))
    b = jnp.asarray(rng.normal(size=30))

    def cost(p):
        r = A @ p - b
        return jnp.sum(r * r) + 0.1 * jnp.sum(p ** 4)

    def line(xk, pk):
        r0, v = A @ xk - b, A @ pk

        def on_line(a):
            r, p = r0 + a * v, xk + a * pk
            return (jnp.sum(r * r) + 0.1 * jnp.sum(p ** 4),
                    2.0 * jnp.dot(r, v) + 0.4 * jnp.dot(p ** 3, pk))
        return on_line
    return cost, line, jnp.asarray(rng.normal(size=12)), 12


def _flat():
    """tests/test_lm.py's degenerate slope."""
    return ((lambda p: jnp.sum(p * 0.0)),
            (lambda xk, pk: lambda a: (0.0 * a, 0.0 * a)), jnp.ones(4), 3)


def _calibration():
    cost, line, p0 = _closures(_problem(2), True, "full")
    return cost, line, p0, 6


@pytest.mark.parametrize("make", [_quartic, _flat, _calibration])
def test_search_on_the_line_ends_where_the_full_search_does(make):
    cost, line, p0, itmax = make()
    g = jax.grad(cost)
    x_a, k_a, n_a = lb.lbfgs_fit(cost, g, p0, itmax=itmax,
                                 return_iters=True)
    x_b, k_b, n_b = lb.lbfgs_fit(cost, g, p0, itmax=itmax,
                                 return_iters=True, line_func=line)
    assert int(k_a) == int(k_b)
    np.testing.assert_allclose(np.asarray(x_b), np.asarray(x_a), atol=1e-4)
    assert np.all(np.isfinite(np.asarray(x_b)))
    # the loop's own gradients, and a restriction an iteration
    assert int(n_b) == 1 + (1 + lb.LINE_FUNC_PASSES) * int(k_b)
    assert int(n_a) >= int(n_b) - lb.LINE_FUNC_PASSES * int(k_b)


# -- the counter ---------------------------------------------------------------

def test_generic_count_is_what_ran():
    """Without a restriction ``passes`` is the number of times the loop
    and its search went through ``cost_func`` or ``grad_func``."""
    cost, _line, p0, itmax = _quartic()
    calls = []

    def counted(f):
        def g(p):
            jax.debug.callback(lambda: calls.append(1))
            return f(p)
        return g

    _x, k, n = lb.lbfgs_fit(counted(cost), counted(jax.grad(cost)), p0,
                            itmax=itmax, return_iters=True)
    jax.effects_barrier()
    assert int(k) == itmax
    assert int(n) == len(calls) > 3 * itmax + 1


def _refine_args(pb):
    # a copy of J0: the refine programs take their Jones donated
    return (pb["x8"], pb["coh"], pb["sta1"], pb["sta2"], pb["cidx"],
            jnp.array(pb["J0"]), pb["wt"], jnp.asarray(2.5, pb["x8"].dtype))


def _cfg(mode="full", **kw):
    return sage.SageConfig(max_emiter=1, max_iter=2, max_lbfgs=3,
                           solver_mode=int(SolverMode.RTR_OSRLM_RLBFGS),
                           jones_mode=mode, **kw)


def test_phase_mode_counts_the_generic_search():
    pb = _problem(1)
    cfg = _cfg("phase")
    _J, _res, k, n = sage._jit_refine(*_refine_args(pb), N, cfg, True)
    cost, line, p0 = _closures(pb, True, "phase")
    assert line is None
    _x, k_g, n_g = lb.lbfgs_fit(cost, jax.grad(cost), p0,
                                itmax=cfg.max_lbfgs, M=cfg.lbfgs_m,
                                return_iters=True)
    assert (int(k), int(n)) == (int(k_g), int(n_g))
    assert int(n) > 3 * int(k) + 1


@pytest.mark.parametrize("driver", ["sagefit", "sagefit_host", "bfgsfit"])
def test_refine_passes_with_the_restriction(driver):
    """k iterations: a jvp and a model evaluation each (2 k), a gradient
    each (k), and g0."""
    pb = _problem(2)
    args = (pb["x8"], pb["coh"], pb["sta1"], pb["sta2"], pb["cidx"])
    if driver == "bfgsfit":
        _J, info = sage.bfgsfit(*args, pb["J0"], N, pb["wt"], config=_cfg())
    else:
        _J, info = getattr(sage, driver)(*args, pb["cmask"], pb["J0"], N,
                                         pb["wt"], config=_cfg())
    k = int(info["lbfgs_iters"])
    assert 0 < k <= 3
    assert int(info["refine_passes"]) == 2 * k + k + 1
    assert float(info["res_1"]) < float(info["res_0"])


def test_refine_tiles_equals_refine_on_each():
    """``--tile-batch``: the restriction's arrays get a tile axis under
    ``vmap`` and every tile searches as it does alone."""
    pbs = [_problem(2, seed=s) for s in (3, 4)]
    cfg = _cfg()
    one = [sage._jit_refine(*_refine_args(pb), N, cfg, True) for pb in pbs]
    sta1, sta2, cidx = (pbs[0][k] for k in ("sta1", "sta2", "cidx"))
    stack = lambda i: jnp.stack([_refine_args(pb)[i] for pb in pbs])
    J, res, k, n = sage._jit_refine_tiles(
        stack(0), stack(1), sta1, sta2, cidx, stack(5), stack(6), stack(7),
        N, cfg, True)
    for t, (J_t, res_t, k_t, n_t) in enumerate(one):
        np.testing.assert_allclose(np.asarray(J[t]), np.asarray(J_t),
                                   atol=1e-9)
        assert float(res[t]) == pytest.approx(float(res_t), rel=1e-9)
        assert (int(k[t]), int(n[t])) == (int(k_t), int(n_t))


def test_tile_record_carries_refine_passes(tmp_path):
    from sagecal_tpu import pipeline
    from sagecal_tpu.diag import trace as dtrace
    path = str(tmp_path / "diag.jsonl")
    dtrace.enable(path, entry="test", argv=[])
    try:
        pipeline._emit_tile_record(
            0, 1.0, 0.5, 2.0, {"solver_iters": 7, "lbfgs_iters": 10,
                               "refine_passes": jnp.asarray(31)}, 0.1)
    finally:
        dtrace.disable()
    tile, = [r for r in dtrace.read(path) if r.get("ev") == "tile"]
    assert (tile["lbfgs_iters"], tile["refine_passes"]) == (10, 31)
