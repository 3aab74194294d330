"""Buffer-donation gates for the solver hot paths.

The per-sweep/per-cluster SAGE programs, the joint refine, and the ADMM
host-loop body DONATE their state carries (donate_argnums) so XLA
reuses the output buffers in place instead of round-tripping fresh HBM
allocations every dispatch. Donation must be invisible to the math:

- donated and non-donated executions of the SAME program produce
  bit-identical results (LM, RTR and SAGE-sweep carries; ADMM carry);
- a donated-then-reused buffer RAISES instead of silently serving
  stale/corrupt data.

MIGRATION.md "Buffer donation" documents the embedder-facing contract.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sagecal_tpu.config import SolverMode
from sagecal_tpu.io import dataset as ds
from sagecal_tpu.rime import predict as rp
from sagecal_tpu.solvers import normal_eq as ne
from sagecal_tpu.solvers import sage

from problems import build_fullbatch, make_sky


N_STA, M, TILESZ = 8, 3, 4


@pytest.fixture(scope="module")
def problem():
    sky, dsky, tiles = build_fullbatch(jnp.float32, n_stations=N_STA,
                                       n_clusters=M, tilesz=TILESZ,
                                       n_tiles=1)
    tile = tiles[0]
    coh = rp.coherencies(dsky, jnp.asarray(tile.u, jnp.float32),
                         jnp.asarray(tile.v, jnp.float32),
                         jnp.asarray(tile.w, jnp.float32),
                         jnp.asarray([150e6], jnp.float32),
                         tile.fdelta)[:, :, 0]
    kmax = int(sky.nchunk.max())
    cidx = jnp.asarray(rp.chunk_indices(TILESZ, tile.nbase, sky.nchunk))
    cmask = jnp.asarray(np.arange(kmax)[None, :] < sky.nchunk[:, None])
    xa = np.asarray(tile.averaged())
    x8 = jnp.asarray(np.stack([xa.reshape(-1, 4).real,
                               xa.reshape(-1, 4).imag],
                              -1).reshape(-1, 8), jnp.float32)
    wt = jnp.asarray((np.asarray(tile.flags) == 0)[:, None]
                     * np.ones((1, 8)), jnp.float32)
    J0 = jnp.asarray(np.tile(np.eye(2, dtype=np.complex64),
                             (M, kmax, N_STA, 1, 1)))
    return dict(tile=tile, coh=coh, cidx=cidx, cmask=cmask, x8=x8,
                wt=wt, J0=J0, kmax=kmax,
                s1=jnp.asarray(tile.sta1, jnp.int32),
                s2=jnp.asarray(tile.sta2, jnp.int32))


def _sweep_args(pb, solver_mode):
    cfg = sage.SageConfig(max_iter=4, solver_mode=int(solver_mode),
                          nbase=pb["tile"].nbase)
    total_iter = M * cfg.max_iter
    iter_bar = int(-(-0.8 * total_iter // M))
    key = jax.random.fold_in(jax.random.PRNGKey(42), 0)
    perm = jnp.arange(M, dtype=jnp.int32)
    # the carry the host hands from program to program: the prelude's
    # residual on planes
    xres, _ = sage._jit_prelude(pb["x8"], pb["coh"], pb["s1"], pb["s2"],
                                pb["cidx"], pb["J0"], pb["wt"],
                                row_period=cfg.nbase)
    nuM = jnp.full((M,), 2.0, jnp.float32)
    args = (pb["J0"], xres, nuM, pb["coh"], pb["s1"], pb["s2"],
            pb["cidx"], pb["cmask"], pb["wt"],
            jnp.zeros((M,), jnp.float32), jnp.asarray(False),
            jnp.asarray(False), key, perm, None)
    kw = dict(n_stations=N_STA, config=cfg._replace(max_emiter=0),
              total_iter=total_iter, iter_bar=iter_bar, os_nsub=0)
    return args, kw


# the same program WITHOUT donation, for the bit-parity gates
_undonated_sweep = jax.jit(
    sage._jit_em_sweep.__wrapped__,
    static_argnames=("n_stations", "config", "total_iter", "iter_bar",
                     "os_nsub"))


@pytest.mark.parametrize("mode", [int(SolverMode.OSLM_LBFGS),
                                  int(SolverMode.RTR_OSRLM_RLBFGS)],
                         ids=["lm", "rtr"])
def test_donated_sweep_bit_identical(problem, mode):
    """Donated EM sweep (LM and RTR solver-state carries) == the same
    program without donation, bit for bit."""
    args, kw = _sweep_args(problem, mode)
    ref = _undonated_sweep(*args, **kw)
    don = sage._jit_em_sweep(
        *(a.copy() if isinstance(a, jax.Array) else a for a in args), **kw)
    for name, a, b in zip(("J", "xres", "nerr", "nuM", "tk"), ref, don):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name


def test_donated_then_reused_raises(problem):
    """A buffer consumed by a donating program must raise on reuse, not
    silently serve stale data."""
    args, kw = _sweep_args(problem, int(SolverMode.OSLM_LBFGS))
    J = args[0].copy()
    xres = args[1].copy()
    out = sage._jit_em_sweep(J, xres, *(a.copy() if isinstance(a, jax.Array)
                                        else a for a in args[2:]), **kw)
    jax.block_until_ready(out[0])
    if not (J.is_deleted() and xres.is_deleted()):
        pytest.skip("backend does not implement buffer donation")
    with pytest.raises(RuntimeError):
        np.asarray(J)
    with pytest.raises(RuntimeError):
        np.asarray(xres)


def test_donated_cluster_update_bit_identical(problem):
    """Per-cluster dispatch path: donated state carry == undonated."""
    pb = problem
    cfg = sage.SageConfig(max_iter=4, solver_mode=0,
                          nbase=pb["tile"].nbase)
    total_iter = M * cfg.max_iter
    iter_bar = int(-(-0.8 * total_iter // M))
    key = jax.random.fold_in(jax.random.PRNGKey(42), 0)
    xres, _ = sage._jit_prelude(pb["x8"], pb["coh"], pb["s1"], pb["s2"],
                                pb["cidx"], pb["J0"], pb["wt"],
                                row_period=cfg.nbase)
    und = jax.jit(sage._jit_cluster_update.__wrapped__,
                  static_argnames=("n_stations", "config", "total_iter",
                                   "iter_bar", "os_nsub"))
    common = (pb["coh"], pb["s1"], pb["s2"], pb["cidx"],
              pb["cmask"], pb["wt"], jnp.zeros((M,), jnp.float32),
              jnp.asarray(False), jnp.asarray(False), key, None, None)
    kw = dict(n_stations=N_STA, config=cfg._replace(max_emiter=0),
              total_iter=total_iter, iter_bar=iter_bar, os_nsub=0)
    cj = jnp.asarray(1, jnp.int32)
    nerr = jnp.zeros((M,), jnp.float32)
    nuM = jnp.full((M,), 2.0, jnp.float32)
    ref = und(cj, pb["J0"], xres, nerr, nuM, *common, **kw)
    don = sage._jit_cluster_update(cj, pb["J0"].copy(), xres.copy(),
                                   nerr.copy(), nuM.copy(), *common, **kw)
    for name, a, b in zip(("J", "xres", "nerr", "nuM", "tk"), ref, don):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name


def _admm_inputs(pb, F):
    from sagecal_tpu.consensus import poly as cpoly
    tile = pb["tile"]
    B = tile.nrows
    xa = np.asarray(pb["x8"])
    freqs = 150e6 * (1.0 + 0.005 * np.arange(F))
    Bpoly = cpoly.setup_polynomials(freqs, float(freqs.mean()), 2, 2)
    x8F = np.broadcast_to(xa, (F,) + xa.shape).copy()
    uF = np.broadcast_to(tile.u, (F, B)).copy()
    vF = np.broadcast_to(tile.v, (F, B)).copy()
    wF = np.broadcast_to(tile.w, (F, B)).copy()
    wtF = np.broadcast_to(np.asarray(pb["wt"]),
                          (F,) + pb["wt"].shape).copy()
    J0 = np.asarray(pb["J0"])[None].repeat(F, axis=0)
    from sagecal_tpu import utils
    J0r = utils.jones_c2r_np(J0)
    fr = np.ones(F)
    return Bpoly, [jnp.asarray(a, jnp.float32) for a in
                   (x8F, uF, vF, wF, freqs, wtF, fr, J0r)]


@pytest.mark.slow  # ~27 s (round-17 tier-1 rebalance); still a CI
# fail-fast gate — ci.yml runs it by -k without the 'not slow' filter
def test_admm_host_loop_donation_bit_identical(problem):
    """The donated ADMM host-loop carry == the identical runner built
    with donate=False, bit for bit."""
    from jax.sharding import Mesh
    from sagecal_tpu.consensus import admm as cadmm
    pb = problem
    tile = pb["tile"]
    F = 2
    Bpoly, args = _admm_inputs(pb, F)
    mesh = Mesh(np.array(jax.devices()[:1]), ("freq",))
    cfg = cadmm.ADMMConfig(
        n_admm=2, npoly=2, rho=2.0, manifold_iters=2,
        sage=sage.SageConfig(max_emiter=1, max_iter=2, max_lbfgs=0,
                             solver_mode=0))
    outs = []
    for donate in (True, False):
        runner = cadmm.make_admm_runner(
            rp.sky_to_device(  # fresh dsky is cheap at this shape
                make_sky(M, seed=17), jnp.float32),
            tile.sta1, tile.sta2, np.asarray(pb["cidx"]),
            np.asarray(pb["cmask"]), N_STA, tile.fdelta, Bpoly, cfg,
            mesh, F, host_loop=True, nbase=tile.nbase, donate=donate)
        out = runner(*[a.copy() for a in args])
        jax.block_until_ready(out[0])
        outs.append([np.asarray(o) for o in out])
    for name, a, b in zip(("J", "Z", "rho", "res0", "res1", "r1s",
                           "duals", "Y0"), outs[0], outs[1]):
        assert np.array_equal(a, b), name


def test_donated_ring_never_reads_a_donated_slot():
    """ISSUE 5 two-slot buffer ring (sched.DonatedRing): under
    overlapped execution the next tile's residual input is staged
    while the previous one is in flight; the ring must (a) refuse to
    overwrite a live (un-donated) slot, (b) hand each buffer out
    exactly once, and (c) refuse any read after the donating take —
    so pipeline code can never touch memory XLA reclaimed."""
    from sagecal_tpu import sched

    donating = jax.jit(lambda x: x + 1.0, donate_argnums=(0,))
    ring = sched.DonatedRing(2)
    a0 = jnp.full((256,), 3.0, jnp.float32)
    ring.stage(0, a0)
    ring.stage(1, jnp.full((256,), 4.0, jnp.float32))
    # overwrite of a live slot (tag 2 -> slot 0, never taken) refused
    with pytest.raises(RuntimeError, match="never taken"):
        ring.stage(2, jnp.zeros((256,), jnp.float32))
    buf = ring.take(0)
    out = donating(buf)
    jax.block_until_ready(out)
    # the slot cannot serve the donated buffer again
    with pytest.raises(RuntimeError, match="donation"):
        ring.take(0)
    # consumed slot re-arms for the tile after next
    ring.stage(2, jnp.zeros((256,), jnp.float32))
    assert np.asarray(ring.take(2)).sum() == 0.0
    if buf.is_deleted():    # backend implements donation: the buffer
        with pytest.raises(RuntimeError):   # is really gone
            np.asarray(buf)


def test_program_log_keeps_no_live_buffers(problem):
    """jaxlint use-after-donate regression (found by the lint, PR 4): the
    sage program log stored the raw args of every logged program;
    several of those programs DONATE their carries, so the log pinned —
    and a later cost accounting re-read — buffers XLA had
    already reclaimed. The log must keep shape/dtype skeletons only,
    and those skeletons must still lower and price from the stored
    record (what tests/test_mfu_iters.py does with them)."""
    args, kw = _sweep_args(problem, int(SolverMode.OSLM_LBFGS))
    sage.program_stats_reset()
    try:
        out = sage._call("em_sweep_probe", sage._jit_em_sweep,
                         *(a.copy() if isinstance(a, jax.Array) else a
                           for a in args), **kw)
        jax.block_until_ready(out[0])
        jfn, (largs, lkw), n = sage.program_stats()["em_sweep_probe"]
        assert n == 1
        for leaf in tuple(largs) + tuple(lkw.values()):
            assert not isinstance(leaf, (jax.Array, np.ndarray)), (
                f"live buffer retained in the program log: {leaf!r}")
        ca = jfn.lower(*largs, **lkw).compile().cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        assert float(ca.get("flops", 0.0)) > 0
    finally:
        sage.program_stats_reset()


def _aliased_params(compiled) -> set:
    """Parameter indices the compiled executable's
    ``input_output_alias`` attribute names as donated-and-aliased,
    parsed from the HLO text by a balanced-brace scan of the attribute
    (entries look like ``{ {}: (1, {}, may-alias) }``: output-index
    tree, then (param, param-index-tree, kind))."""
    txt = compiled.as_text()
    key = "input_output_alias={"
    start = txt.find(key)
    if start < 0:
        return set()
    i = start + len(key) - 1
    depth, j = 0, i
    while j < len(txt):
        if txt[j] == "{":
            depth += 1
        elif txt[j] == "}":
            depth -= 1
            if depth == 0:
                break
        j += 1
    return {int(m.group(1))
            for m in re.finditer(r"\(\s*(\d+)\s*,", txt[i:j + 1])}


def test_donated_visibilities_are_aliased_in_the_executable():
    """Donation ground truth (ISSUE 19): the jaxlint use-after-donate
    checker and the DonatedRing both PROMISE that ``donate_argnums``
    aliases the donated input into the output, but only the compiled
    program knows whether XLA honoured it. A residual-shaped program
    (Jones consulted, visibilities rewritten in place: parameter 1,
    donated in pipeline.py's ``_residuals`` jit) compiled twice: the
    donated twin aliases parameter 1, the plain twin aliases nothing."""
    rng = np.random.default_rng(0)
    B = 64
    J = jnp.asarray(rng.normal(size=(B, 2, 2))
                    + 1j * rng.normal(size=(B, 2, 2)), jnp.complex64)
    V = jnp.asarray(rng.normal(size=(B, 2, 2))
                    + 1j * rng.normal(size=(B, 2, 2)), jnp.complex64)

    def residuals(J, V):
        return V - J @ V @ jnp.conj(jnp.swapaxes(J, -1, -2))

    donated = jax.jit(residuals, donate_argnums=(1,)).lower(J, V).compile()
    plain = jax.jit(residuals).lower(J, V).compile()
    assert 1 in _aliased_params(donated)
    assert _aliased_params(plain) == set()


def test_alias_parse_is_not_vacuous():
    """The reader's own control: the first of two parameters donated
    reads as {0}, the same program undonated as the empty set, so an
    empty reading means missing aliasing and not a parse that matches
    nothing."""
    x = jnp.ones((8,), jnp.float32)

    def f(a, b):
        return a + b

    donated = jax.jit(f, donate_argnums=(0,)).lower(x, x).compile()
    plain = jax.jit(f).lower(x, x).compile()
    assert _aliased_params(donated) == {0}
    assert _aliased_params(plain) == set()
