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


# plan -> (forced knobs, device executions of a solve of E sweeps over M
# clusters with a refine: the prelude, the sweeps' programs, the refine)
_PLANS = {
    "promoted": (dict(promote="on"), lambda M, E: 1),
    "fused": (dict(fuse="on", promote="off"), lambda M, E: 1 + E + 1),
    "per_cluster": (dict(fuse="off", promote="off"),
                    lambda M, E: 1 + E * M + 1),
}


@pytest.mark.parametrize("tiles", [0, 1], ids=["solo", "tiles-1"])
@pytest.mark.parametrize("plan", sorted(_PLANS))
def test_plan_and_dispatches_reach_the_tile_record(problem, plan, tiles,
                                                   tmp_path):
    """``sagefit_host`` says which plan its last sweep ran and how many
    device executions the solve issued through ``sage._call``, under the
    forced knobs; ``sagefit_host_tiles`` hands a lone tile's through as
    host values; the ``tile`` record and the obs counter carry both."""
    from sagecal_tpu import pipeline
    from sagecal_tpu.diag import trace as dtrace
    from sagecal_tpu.obs import metrics as obs
    knobs, count = _PLANS[plan]
    cfg = sage.SageConfig(max_emiter=2, max_iter=2, max_lbfgs=2,
                          solver_mode=int(SolverMode.RTR_OSRLM_RLBFGS),
                          **knobs)
    M = problem[1].shape[0]
    if tiles:
        x8, coh, s1, s2, cidx, cmask, J0, N, wt = problem
        _, info = sage.sagefit_host_tiles(
            x8[None], coh[None], s1, s2, cidx, cmask, J0[None], N, wt[None],
            config=cfg)
        assert info["res_1"].shape == (1,)
    else:
        sage.program_stats_reset()
        _, info = sage.sagefit_host(*problem, config=cfg)
        # the count is of _call's executions, program by program
        assert info["solve_dispatches"] == sum(
            n for _, _, n in sage.program_stats().values())
    assert info["plan"] == plan
    assert info["solve_dispatches"] == count(M, cfg.max_emiter)
    assert type(info["solve_dispatches"]) is int     # nothing to fetch
    path = str(tmp_path / "diag.jsonl")
    was_on = obs.active()
    reg = obs.enable()
    before = reg.get("solver_dispatches_total")
    before = before.value() if before is not None else 0
    dtrace.enable(path, entry="test", argv=[])
    try:
        pipeline._emit_tile_record(0, 1.0, 0.5, 2.0, info, 0.1)
        after = reg.get("solver_dispatches_total").value()
    finally:
        dtrace.disable()
        if not was_on:
            obs.disable()
    tile, = [r for r in dtrace.read(path) if r.get("ev") == "tile"]
    assert (tile["plan"], tile["solve_dispatches"]) == (
        plan, count(M, cfg.max_emiter))
    assert after - before == count(M, cfg.max_emiter)


@pytest.mark.parametrize("info", [
    None,                                   # the mesh program's record
    {"solver_iters": 7, "lbfgs_iters": 3},  # a solver without a plan
], ids=["no-info", "no-plan"])
def test_tile_record_has_a_plan_only_where_the_solver_said_one(info,
                                                               tmp_path):
    from sagecal_tpu import pipeline
    from sagecal_tpu.diag import trace as dtrace
    path = str(tmp_path / "diag.jsonl")
    dtrace.enable(path, entry="test", argv=[])
    try:
        pipeline._emit_tile_record(0, 1.0, 0.5, 2.0, info, 0.1)
    finally:
        dtrace.disable()
    tile, = [r for r in dtrace.read(path) if r.get("ev") == "tile"]
    assert "plan" not in tile and "solve_dispatches" not in tile
    assert set(tile) >= {"tile", "res_0", "res_1", "mean_nu", "minutes"}
