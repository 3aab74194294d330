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
from sagecal_tpu.rime import predict as rp
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


# -- several chunks: a chunk is a run of whole timeslots (ISSUE 45) -----------

T10 = 10
#: chunk counts of the all-cluster instance, padded to ``max`` slots
NCHUNK = np.array([5, 3, 1, 2])


def _chunk_problem(nchunk, dtype=np.float64, seed=7, tmap=None):
    """Rows [T10, NB] of ``len(nchunk)`` clusters padded to ``max(nchunk)``
    chunk slots, the chunk map ``chunk_indices``' (3 does not divide
    ten timeslots: chunks of 4, 4, 2) or the per-timeslot ``tmap``."""
    rng = np.random.default_rng(seed)
    nchunk = np.asarray(nchunk)
    M, K, B = len(nchunk), int(nchunk.max()), NB * T10
    p, q = np.triu_indices(N, k=1)
    cdt = np.complex128 if dtype == np.float64 else np.complex64
    cid = rp.chunk_indices(T10, NB, nchunk) if tmap is None \
        else np.repeat(np.asarray(tmap, np.int32), NB, axis=-1)
    pb = dict(
        x8=rng.normal(size=(B, 8)).astype(dtype),
        coh=(rng.normal(size=(M, B, 2, 2))
             + 1j * rng.normal(size=(M, B, 2, 2))).astype(cdt),
        sta1=np.tile(p, T10).astype(np.int32),
        sta2=np.tile(q, T10).astype(np.int32), cid=cid,
        wt=(rng.random((B, 8)) * (rng.random((B, 1)) > 0.1)).astype(dtype),
        cwt=rng.random((B, 8)).astype(dtype),
        P=rng.normal(size=(M, K, N, 8)).astype(dtype))
    return {k: jnp.asarray(v) for k, v in pb.items()}


def _cluster_dense(pb, m, wt):
    f64 = lambda a: a.astype(jnp.float64)
    K = pb["P"].shape[1]
    return ne._normal_equations_dense(
        f64(pb["x8"]), ne.jones_r2c(f64(pb["P"][m])),
        pb["coh"][m].astype(jnp.complex128), pb["sta1"], pb["sta2"],
        pb["cid"][m], f64(wt), N, K)


def _rows(pb, m=None, row_period=NB):
    """One cluster's instance, or (m None) the one over all clusters."""
    K = pb["P"].shape[1]
    pick = (lambda a: a) if m is None else (lambda a: a[m])
    return ne.RowPlanes(pb["x8"], pick(pb["coh"]), pb["wt"], pb["sta1"],
                        pb["sta2"], pick(pb["cid"]), K, N, row_period)


@pytest.mark.parametrize("kmax", [2, 3, 5])
def test_chunks_against_the_dense_reference(kmax):
    """``plane_equations`` of one cluster with ``kmax`` chunks over ten
    timeslots against the materialized Jacobians (float64, 1e-12), with
    a second weight set for the cost, and symmetric to the last digit."""
    pb = _chunk_problem([kmax])
    rows = _rows(pb, 0)
    assert rows.periodic and rows.rows == (T10, NB)
    assert rows.i1.shape == (kmax, NB)
    got = ne.plane_equations(rows, pb["P"][0],
                             cost_w8=rows.planes(pb["cwt"]))
    want = _cluster_dense(pb, 0, pb["wt"])[:2] \
        + (_cluster_dense(pb, 0, pb["cwt"])[2],)
    assert got[0].shape == (kmax, 8 * N, 8 * N)
    _close(got, want, 1e-12)
    JTJ = np.asarray(got[0])
    np.testing.assert_array_equal(JTJ, JTJ.transpose(0, 2, 1))


@pytest.mark.parametrize("kmax", [2, 3, 5])
def test_chunks_against_the_generic_branch(kmax):
    """float32: ``normal_equations`` with the period (the planes) and
    without (``row_period=0``: the scatter assembly of ``[B, 2, 2, 4]``
    factors) on the same hybrid rows, both beside the float64 dense
    reference."""
    pb = _chunk_problem([kmax], np.float32)
    args = (pb["x8"], ne.jones_r2c(pb["P"][0]), pb["coh"][0], pb["sta1"],
            pb["sta2"], pb["cid"][0], pb["wt"], N, kmax)
    got = ne.normal_equations(*args, row_period=NB)
    assert all(g.dtype == jnp.float32 for g in got)
    want = _cluster_dense(pb, 0, pb["wt"])
    _close(got, want, 2e-5)
    _close(ne.normal_equations(*args, row_period=0), want, 2e-5)
    _close(got, ne.normal_equations(*args, row_period=0), 2e-5)


@pytest.mark.parametrize("m", range(len(NCHUNK)))
def test_a_cluster_of_the_all_cluster_instance_assembles(m):
    """The refine's instance over clusters of DIFFERENT chunk counts
    (5, 3, 1, 2 in five slots, the others masked: no row names them):
    cluster ``m``'s slice assembles what the dense reference does, and a
    masked slot's block is exactly zero."""
    pb = _chunk_problem(NCHUNK)
    rows = _rows(pb).cluster(m)
    assert (rows.kmax, rows.chunks) == (5, 5)
    got = ne.plane_equations(rows, pb["P"][m])
    _close(got, _cluster_dense(pb, m, pb["wt"]), 1e-12)
    for g in got:
        assert not np.asarray(g)[NCHUNK[m]:].any()


def test_chunks_need_not_be_runs():
    """What ``row_period`` promises is that the chunk of a row is its
    timeslot's, not that a chunk's timeslots are adjacent."""
    tmap = [[0, 1, 0, 2, 1, 0, 2, 2, 1, 0]]
    pb = _chunk_problem([3], tmap=tmap)
    _close(ne.plane_equations(_rows(pb, 0), pb["P"][0]),
           _cluster_dense(pb, 0, pb["wt"]), 1e-12)


def gather(pb, per, flat):
    P = pb["P"].reshape(-1, N, 8)
    for a, b in zip(per.gather(P), flat.gather(P)):
        assert a.shape == (8,) + per.c.shape[1:]
        np.testing.assert_array_equal(
            np.asarray(a).reshape(b.shape), np.asarray(b))


def station_sum_of_time_sum(pb, per, flat):
    rng = np.random.default_rng(1)
    g = rng.normal(size=(2,) + per.c.shape)
    gp, gq = (per.time_sum(jnp.asarray(a)) for a in g)
    assert gp.shape == (8,) + per.i1.shape
    want = flat.station_sum(*(flat.time_sum(jnp.asarray(
        a.reshape(flat.c.shape))) for a in g))
    np.testing.assert_allclose(np.asarray(per.station_sum(gp, gq)),
                               np.asarray(want), rtol=0, atol=1e-12)


def chunk_sum(pb, per, flat):
    a = np.random.default_rng(2).normal(size=per.c.shape)
    np.testing.assert_allclose(
        np.asarray(per.chunk_sum(jnp.asarray(a))),
        np.asarray(flat.chunk_sum(jnp.asarray(a.reshape(flat.c.shape)))),
        rtol=0, atol=1e-12)


def select(pb, per, flat):
    rng = np.random.default_rng(3)
    new, old = rng.normal(size=(2,) + per.c.shape)
    take = jnp.asarray(rng.random(per.kmax) > 0.5)
    got = per.select(take, jnp.asarray(new), jnp.asarray(old))
    want = flat.select(take, jnp.asarray(new.reshape(flat.c.shape)),
                       jnp.asarray(old.reshape(flat.c.shape)))
    np.testing.assert_array_equal(np.asarray(got).reshape(want.shape),
                                  np.asarray(want))


@pytest.mark.parametrize("method, m", [
    (gather, 1), (station_sum_of_time_sum, 1), (chunk_sum, 1), (select, 1),
    (gather, None), (station_sum_of_time_sum, None)],
    ids=lambda v: getattr(v, "__name__", {1: "one", None: "all"}.get(v)))
def test_a_method_is_its_flat_twin(method, m):
    """Each method of ``RowPlanes`` on ``[tilesz, nbase]`` planes of
    several chunks against the same instance on flat rows
    (``row_period=0``: a Jones gathered a row, a segment sum a row):
    one cluster (3 chunks in 5 slots) and, for the methods the joint
    refine uses, the instance over all clusters."""
    pb = _chunk_problem(NCHUNK)
    per, flat = _rows(pb, m), _rows(pb, m, 0)
    assert per.periodic and not flat.periodic
    method(pb, per, flat)


def test_predict_model_is_the_sum_of_model8():
    """The residual program's model of a hybrid tile: ``predict_model``
    on ``[tilesz, nbase]`` planes with five chunk slots and a kept
    (masked) cluster against ``model8`` summed, and against itself on
    flat rows."""
    pb = _chunk_problem(NCHUNK)
    M, F = len(NCHUNK), 2
    rng = np.random.default_rng(4)
    coh = jnp.asarray(rng.normal(size=(M, NB * T10, F, 2, 2))
                      + 1j * rng.normal(size=(M, NB * T10, F, 2, 2)))
    c8 = jnp.moveaxis(ne.jones_c2r(jnp.moveaxis(coh, 2, 1)), -1, 0)
    mask = np.array([True, False, True, True])
    J = ne.jones_r2c(pb["P"])
    want = jnp.stack([sum(
        rp.model8(coh[m, :, f], J[m], pb["sta1"], pb["sta2"], pb["cid"][m])
        for m in range(M) if mask[m]) for f in range(F)])
    for row_period in (NB, 0):
        got = rp.predict_model(c8, pb["P"], pb["sta1"], pb["sta2"],
                               pb["cid"], cluster_mask=mask,
                               row_period=row_period)
        assert got.shape == (8, F, NB * T10)
        np.testing.assert_allclose(
            np.asarray(jnp.moveaxis(got, 0, -1)), np.asarray(want),
            rtol=0, atol=1e-12)


def test_a_map_that_varies_inside_a_timeslot_is_refused_a_period():
    """A concrete chunk map that is not constant along a timeslot, with
    ``nbase`` set, raises where it is handed over; at ``row_period=0`` it
    assembles on flat rows as before."""
    pb = _chunk_problem([2])
    cid = jnp.asarray(np.arange(NB * T10, dtype=np.int32) % 2)
    args = (pb["x8"], ne.jones_r2c(pb["P"][0]), pb["coh"][0], pb["sta1"],
            pb["sta2"], cid, pb["wt"], N, 2)
    with pytest.raises(ValueError, match="inside a timeslot"):
        ne.normal_equations(*args, row_period=NB)
    want = ne._normal_equations_dense(*args)
    _close(ne.normal_equations(*args, row_period=0), want, 1e-9)


# -- the counter that says which assembly ran ---------------------------------

@pytest.mark.parametrize("rows, nbase, want", [(NB * 4, NB, "periodic"),
                                               (NB * 4 + 1, NB, "generic"),
                                               (NB * 4, 0, "generic")])
def test_assemble_rows_follows_the_input(rows, nbase, want):
    """The period and the row count decide, whatever the chunk counts."""
    cfg = sage.SageConfig(nbase=nbase)
    assert sage.assemble_rows(cfg, rows) == want
    for other in (dict(inner="cg"), dict(jones_mode="diag"),
                  dict(dtype_policy="bf16")):
        assert sage.assemble_rows(cfg._replace(**other), rows) is None


@pytest.mark.parametrize("hybrid, want", [(1, "periodic"), (2, "periodic")])
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
