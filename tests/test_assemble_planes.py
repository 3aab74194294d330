"""The Gauss-Newton matrix from planes (``normal_eq.plane_equations``)
against the materialized-Jacobian reference
``normal_eq._normal_equations_dense``, and the callers that have to run
the one implementation: ``normal_equations(row_period=nb)``,
``normal_equations_mode(mode="full")``, ``rtr.make_hess``."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from sagecal_tpu import pipeline
from sagecal_tpu.diag import trace as dtrace
from sagecal_tpu.solvers import normal_eq as ne
from sagecal_tpu.solvers import rtr
from sagecal_tpu.solvers import sage

N = 6
NB = N * (N - 1) // 2


def _problem(T=5, seed=3, dtype=np.float64):
    """Rows [T, NB] of one chunk with per-component weights, a tenth of
    the rows flagged (weight 0), and a second weight set for the cost."""
    rng = np.random.default_rng(seed)
    p, q = np.triu_indices(N, k=1)
    B = NB * T
    cdt = np.complex128 if dtype == np.float64 else np.complex64
    pb = dict(
        x8=rng.normal(size=(B, 8)).astype(dtype),
        coh=(rng.normal(size=(B, 2, 2))
             + 1j * rng.normal(size=(B, 2, 2))).astype(cdt),
        sta1=np.tile(p, T).astype(np.int32),
        sta2=np.tile(q, T).astype(np.int32),
        cid=np.zeros(B, np.int32),
        wt=(rng.random((B, 8)) * (rng.random((B, 1)) > 0.1)).astype(dtype),
        cwt=rng.random((B, 8)).astype(dtype),
        P=rng.normal(size=(1, N, 8)).astype(dtype))
    return {k: jnp.asarray(v) for k, v in pb.items()}


def _dense(pb, wt):
    """The reference in float64, whatever the problem's dtype."""
    f64 = lambda a: a.astype(jnp.float64)
    return ne._normal_equations_dense(
        f64(pb["x8"]), ne.jones_r2c(f64(pb["P"])),
        pb["coh"].astype(jnp.complex128), pb["sta1"], pb["sta2"],
        pb["cid"], f64(wt), N, 1)


def _planes(pb, cost=False):
    rows = ne.RowPlanes(pb["x8"], pb["coh"], pb["wt"], pb["sta1"],
                        pb["sta2"], pb["cid"], 1, N, NB)
    return ne.plane_equations(
        rows, pb["P"], cost_w8=rows.planes(pb["cwt"]) if cost else None)


def _close(got, want, tol):
    for name, g, w in zip(("JTJ", "JTe", "cost"), got, want):
        scale = float(np.abs(np.asarray(w)).max())
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=tol * scale, rtol=0, err_msg=name)


def dense_reference(_mp, T=5):
    pb = _problem(T)
    _close(_planes(pb), _dense(pb, pb["wt"]), 1e-12)


def dense_reference_one_timeslot(_mp):
    dense_reference(_mp, T=1)


def cost_weights(_mp):
    """JTJ and JTe keep ``wt``, the cost takes the second set."""
    pb = _problem()
    want = _dense(pb, pb["wt"])[:2] + (_dense(pb, pb["cwt"])[2],)
    _close(_planes(pb, cost=True), want, 1e-12)


def one_implementation(_mp):
    """``normal_equations(row_period=nb)`` and the mode-aware entry in
    full mode give the planes entry's answer to the last digit; the
    generic branch the same to 5e-9."""
    pb = _problem()
    J = ne.jones_r2c(pb["P"])
    args = (pb["x8"], J, pb["coh"], pb["sta1"], pb["sta2"], pb["cid"],
            pb["wt"], N, 1)
    want = _planes(pb, cost=True)
    for got in (ne.normal_equations(*args, cost_wt=pb["cwt"],
                                    row_period=NB),
                ne.normal_equations_mode(*args, mode="full",
                                         cost_wt=pb["cwt"],
                                         row_period=NB)):
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    _close(ne.normal_equations(*args, cost_wt=pb["cwt"]), want, 5e-9)


def symmetric(_mp):
    for T in (1, 5):
        JTJ = np.asarray(_planes(_problem(T))[0])
        np.testing.assert_array_equal(JTJ, JTJ.transpose(0, 2, 1))


def float32(_mp):
    pb = _problem(dtype=np.float32)
    got = _planes(pb, cost=True)
    assert all(g.dtype == jnp.float32 for g in got)
    want = _dense(pb, pb["wt"])[:2] + (_dense(pb, pb["cwt"])[2],)
    _close(got, want, 2e-5)


def other_rows_are_refused(_mp):
    pb = _problem()
    flat = ne.RowPlanes(pb["x8"], pb["coh"], pb["wt"], pb["sta1"],
                        pb["sta2"], pb["cid"], 1, N, 0)
    with pytest.raises(ValueError, match="periodic"):
        ne.plane_equations(flat, pb["P"])


def make_hess(monkeypatch):
    """``make_hess``'s product under ``robust_nu`` equals the one built
    from ``to_rows`` curvature weights through the dense reference: the
    Hessian operator a solve's first trust-region iteration hands to
    tCG, caught on its way in."""
    pb = _problem(T=4, seed=11)
    J0 = ne.jones_r2c(pb["P"])
    rows = ne.RowPlanes(pb["x8"], pb["coh"], pb["wt"], pb["sta1"],
                        pb["sta2"], pb["cid"], 1, N, NB)
    nu = 3.0
    seen = {}

    def spy(hess, g, delta, cfg):
        seen["hv"] = hess
        return jnp.zeros_like(g), jnp.zeros(g.shape[:1], g.dtype), \
            jnp.zeros((), jnp.int32)

    monkeypatch.setattr(rtr, "_tcg", spy)
    monkeypatch.setattr(jax.lax, "while_loop",
                        lambda cond, body, init: body(init))
    rtr._rtr_rows(rows, J0, N, None, rtr.RTRConfig(itmax=1), None, None,
                  nu, NB)
    p0 = pb["P"].reshape(1, -1)
    e = ne.residual8(pb["x8"], J0, pb["coh"], pb["sta1"], pb["sta2"],
                     pb["cid"]) * pb["wt"]
    JTJ = _dense(pb, pb["wt"] * jnp.sqrt(nu) / (nu + e * e))[0]
    v = jnp.asarray(np.random.default_rng(5).normal(size=p0.shape))
    want = rtr.project_tangent(
        p0, 2.0 * jnp.einsum("kij,kj->ki", JTJ, v), 1, N)
    np.testing.assert_allclose(
        np.asarray(seen["hv"](v)), np.asarray(want), rtol=0,
        atol=1e-10 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("case", [
    dense_reference, dense_reference_one_timeslot, cost_weights,
    one_implementation, symmetric, float32, other_rows_are_refused,
    make_hess], ids=lambda f: f.__name__)
def test_assembly_from_planes(case, monkeypatch):
    case(monkeypatch)


# -- the counter that says which assembly ran ---------------------------------

@pytest.mark.parametrize("kmax, nbase, want", [(1, NB, "periodic"),
                                               (2, NB, "generic"),
                                               (1, 0, "generic")])
def test_assemble_rows_follows_the_input(kmax, nbase, want):
    cfg = sage.SageConfig(nbase=nbase)
    assert sage.assemble_rows(cfg, kmax, NB * 4) == want
    for other in (dict(inner="cg"), dict(kernel="pallas"),
                  dict(jones_mode="diag"), dict(dtype_policy="bf16")):
        assert sage.assemble_rows(cfg._replace(**other), kmax,
                                  NB * 4) is None


@pytest.mark.parametrize("hybrid, want", [(1, "periodic"), (2, "generic")])
def test_calibration_says_which_assembly_ran(tmp_path, hybrid, want):
    """A calibration through ``cli`` on a sky whose one cluster has
    ``hybrid`` chunks: every ``tile`` record names the assembly its
    cluster solves ran, beside ``plan``."""
    import math

    from sagecal_tpu import cli, skymodel
    from sagecal_tpu.io import dataset as ds
    from sagecal_tpu.rime import predict as rp

    sky_file = tmp_path / "sky.txt"
    sky_file.write_text("P0A 0 40 0 40 0 0 3.0 0 0 0 0 0 0 0 0 150e6\n")
    cluster_file = tmp_path / "sky.txt.cluster"
    cluster_file.write_text(f"0 {hybrid} P0A\n")
    ra0, dec0 = (41 / 60) * math.pi / 12, 40 * math.pi / 180
    sky = skymodel.build_cluster_sky(
        skymodel.parse_sky_model(str(sky_file), ra0, dec0, 150e6),
        skymodel.parse_cluster_file(str(cluster_file)))
    Jt = ds.random_jones(1, sky.nchunk, 6, seed=5, scale=0.1)
    tiles = [ds.simulate_dataset(
        rp.sky_to_device(sky, jnp.float64), n_stations=6, tilesz=4,
        freqs=np.array([150e6]), ra0=ra0, dec0=dec0, jones=Jt,
        nchunk=sky.nchunk, noise_sigma=0.01, seed=11 + t)
        for t in range(2)]
    msdir = tmp_path / "sim.ms"
    ds.SimMS.create(str(msdir), tiles)
    diag = tmp_path / "diag.jsonl"
    assert cli.main(["-d", str(msdir), "-s", str(sky_file),
                     "-c", str(cluster_file), "-e", "1", "-g", "2",
                     "-l", "0", "-j", "5", "-B", "0",
                     "--diag", str(diag)]) == 0
    recs = [r for r in dtrace.read(str(diag)) if r.get("ev") == "tile"]
    assert len(recs) == 2
    assert [r["assemble_rows"] for r in recs] == [want, want]
    assert all("plan" in r and "refine_rows" not in r for r in recs)


def test_tile_record_carries_assemble_rows(tmp_path):
    path = str(tmp_path / "diag.jsonl")
    dtrace.enable(path, entry="test", argv=[])
    try:
        pipeline._emit_tile_record(
            0, 1.0, 0.5, 2.0, {"plan": "promoted", "solve_dispatches": 1,
                               "assemble_rows": "periodic"}, 0.1)
        pipeline._emit_tile_record(1, 1.0, 0.5, 2.0, {"solver_iters": 7},
                                   0.1)
    finally:
        dtrace.disable()
    first, second = [r for r in dtrace.read(path) if r.get("ev") == "tile"]
    assert first["assemble_rows"] == "periodic"
    assert "assemble_rows" not in second
