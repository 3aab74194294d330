"""Executed-iteration counters of the solvers.

XLA cost analysis prices loop bodies once, so the solvers report how
many iterations actually ran (info["solver_iters"] / "lbfgs_iters" /
"cg_iters" / "refine_passes"); the pipeline writes them into each
``tile`` record, where benchmarks/ reads ``solver_trips``,
``tcg_trips`` and ``refine_passes``. These tests pin the counter
contract: present, positive, and identical between the fully traced
and host-driven drivers (same math -> same trip counts).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from sagecal_tpu.config import SolverMode
from sagecal_tpu.solvers import rtr as rtr_mod
from sagecal_tpu.solvers import sage

TCG_CAP = rtr_mod.RTRConfig().tcg_iters


@pytest.fixture(scope="module")
def problem():
    rng = np.random.default_rng(0)
    N, M, K = 6, 3, 2
    pairs = [(i, j) for i in range(N) for j in range(i + 1, N)]
    tsz = 6
    B = len(pairs) * tsz
    sta1 = np.tile(np.array([p[0] for p in pairs]), tsz).astype(np.int32)
    sta2 = np.tile(np.array([p[1] for p in pairs]), tsz).astype(np.int32)
    coh = (rng.normal(size=(M, B, 2, 2))
           + 1j * rng.normal(size=(M, B, 2, 2))).astype(np.complex128)
    cidx = (np.arange(B) // (B // K)).clip(0, K - 1)[None, :] \
        .repeat(M, 0).astype(np.int32)
    cmask = np.ones((M, K), bool)
    J0 = np.tile(np.eye(2, dtype=np.complex128), (M, K, N, 1, 1))
    Jt = J0 + 0.1 * (rng.normal(size=J0.shape)
                     + 1j * rng.normal(size=J0.shape))
    x8 = sage.full_model8(jnp.asarray(Jt), jnp.asarray(coh),
                          jnp.asarray(sta1), jnp.asarray(sta2),
                          jnp.asarray(cidx))
    wt = np.ones((B, 8), np.float64)
    return (jnp.asarray(x8, jnp.float64), jnp.asarray(coh),
            jnp.asarray(sta1), jnp.asarray(sta2), jnp.asarray(cidx),
            jnp.asarray(cmask), jnp.asarray(J0), N, jnp.asarray(wt))


@pytest.mark.slow
def test_iters_traced_vs_host(problem):
    cfg = sage.SageConfig(max_emiter=2, max_iter=5, max_lbfgs=4,
                          solver_mode=int(SolverMode.OSLM_OSRLM_RLBFGS))
    _, info_t = sage.sagefit(*problem, config=cfg)
    _, info_h = sage.sagefit_host(*problem, config=cfg)
    for info in (info_t, info_h):
        assert int(info["solver_iters"]) > 0
        assert 0 < int(info["lbfgs_iters"]) <= cfg.max_lbfgs
    assert int(info_t["solver_iters"]) == int(info_h["solver_iters"])
    assert int(info_t["lbfgs_iters"]) == int(info_h["lbfgs_iters"])


def test_iters_rtr_bounded(problem):
    cfg = sage.SageConfig(max_emiter=1, max_iter=4, max_lbfgs=0,
                          solver_mode=int(SolverMode.RTR_OSRLM_RLBFGS))
    _, info = sage.sagefit(*problem, config=cfg)
    M = problem[1].shape[0]
    iter_bar = -(-int(0.8 * M * cfg.max_iter) // M)
    # 2 IRLS rounds per cluster solve, each <= max_iter + iter_bar trips
    cap = M * cfg.max_emiter * 2 * (cfg.max_iter + iter_bar)
    assert 0 < int(info["solver_iters"]) <= cap
    assert int(info["lbfgs_iters"]) == 0
    # executed tCG bodies: at least one an outer trip, and the cap
    # (RTRConfig.tcg_iters a trip) is not what a solve runs
    its = int(info["solver_iters"])
    assert its <= int(info["cg_iters"]) < its * TCG_CAP


# name: (driver, solver mode, inner) -> what ``cg_iters`` has to hold
_CG_CASES = {
    "rtr": ("sagefit", SolverMode.RTR_OSLM_LBFGS, "chol"),
    "rtr-robust-host": ("sagefit_host", SolverMode.RTR_OSRLM_RLBFGS,
                        "chol"),
    "lm-chol": ("sagefit", SolverMode.LM_LBFGS, "chol"),
    "lm-cg": ("sagefit", SolverMode.LM_LBFGS, "cg"),
    "nsd": ("sagefit", SolverMode.NSD_RLBFGS, "chol"),
}


@pytest.mark.parametrize("case", sorted(_CG_CASES))
def test_cg_iters_reach_the_tile_record(problem, case, tmp_path):
    """``cg_iters`` = executed inner CG trips: RTR's truncated-CG bodies
    (the traced and the host-driven plan alike), LM's PCG trips under
    ``inner="cg"``, 0 where there is no inner CG; the ``tile`` record
    carries the key beside ``solver_iters``."""
    from sagecal_tpu import pipeline
    from sagecal_tpu.diag import trace as dtrace
    driver, mode, inner = _CG_CASES[case]
    cfg = sage.SageConfig(max_emiter=1, max_iter=3, max_lbfgs=0,
                          solver_mode=int(mode), inner=inner)
    _, info = getattr(sage, driver)(*problem, config=cfg)
    its, cgs = int(info["solver_iters"]), int(info["cg_iters"])
    assert its > 0
    if case.startswith("rtr"):
        assert its <= cgs <= its * TCG_CAP
    elif case == "lm-cg":
        assert cgs > 0
    else:
        assert cgs == 0
    path = str(tmp_path / "diag.jsonl")
    dtrace.enable(path, entry="test", argv=[])
    try:
        pipeline._emit_tile_record(0, 1.0, 0.5, 2.0, info, 0.1)
    finally:
        dtrace.disable()
    tile, = [r for r in dtrace.read(path) if r.get("ev") == "tile"]
    assert (tile["solver_iters"], tile["cg_iters"]) == (its, cgs)


@pytest.mark.slow
def test_iters_tiles_per_tile(problem):
    cfg = sage.SageConfig(max_emiter=1, max_iter=3, max_lbfgs=2,
                          solver_mode=int(SolverMode.LM_LBFGS))
    T = 2
    x8, coh, s1, s2, cidx, cmask, J0, N, wt = problem
    targs = (jnp.stack([x8] * T), jnp.stack([coh] * T), s1, s2, cidx,
             cmask, jnp.stack([J0] * T), N, jnp.stack([wt] * T))
    _, info = sage.sagefit_host_tiles(*targs, config=cfg)
    si = np.asarray(info["solver_iters"])
    assert si.shape == (T,) and (si > 0).all()
    # identical tiles solve identically under per-tile PRNG key 0 vs 1?
    # keys differ, but LM trips at eps=1e-15 are budget-capped: equal
    assert si[0] == si[1]


def test_band_solver_reports_iters():
    """BandSolverOutputs.iters: executed LBFGS iterations (config2)."""
    from sagecal_tpu.solvers import lbfgs as lbfgs_mod

    def cost(p):
        return jnp.sum((p - 2.0) ** 2)

    p0 = jnp.zeros(5, jnp.float32)
    mem = lbfgs_mod.lbfgs_memory_init(5, 3)
    p1, mem1, k = lbfgs_mod.lbfgs_fit_minibatch(cost, jax.grad(cost), p0,
                                                mem, itmax=6)
    assert 0 < int(k) <= 6
    assert np.allclose(np.asarray(p1), 2.0, atol=1e-3)
