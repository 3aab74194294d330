"""Tests for extract_phases, phase-only correction, residual
interpolation, and the per-channel bandpass mode (-b 1)."""

import math

import numpy as np
import jax
import jax.numpy as jnp

from sagecal_tpu import cli, pipeline, skymodel
from sagecal_tpu.consensus import manifold as mf
from sagecal_tpu.io import dataset as ds, solutions as sol
from sagecal_tpu.rime import predict as rp
from sagecal_tpu.rime import residual as rr
import pytest


def test_extract_phases_recovers_diag_phases():
    """J = diag(a0 e^{i t0}, a1 e^{i t1}) per station: the joint
    diagonalization must return exactly the unit-modulus phases."""
    rng = np.random.default_rng(0)
    N = 6
    t0 = rng.uniform(-np.pi, np.pi, N)
    t1 = rng.uniform(-np.pi, np.pi, N)
    a0 = rng.uniform(0.5, 2.0, N)
    a1 = rng.uniform(0.5, 2.0, N)
    J = np.zeros((N, 2, 2), complex)
    J[:, 0, 0] = a0 * np.exp(1j * t0)
    J[:, 1, 1] = a1 * np.exp(1j * t1)
    P = np.asarray(mf.extract_phases(jnp.asarray(J)))
    np.testing.assert_allclose(np.abs(P[:, 0, 0]), 1.0, atol=1e-8)
    np.testing.assert_allclose(np.abs(P[:, 1, 1]), 1.0, atol=1e-8)
    np.testing.assert_allclose(P[:, 0, 1], 0.0, atol=1e-12)
    np.testing.assert_allclose(np.angle(P[:, 0, 0]), t0, atol=1e-6)
    np.testing.assert_allclose(np.angle(P[:, 1, 1]), t1, atol=1e-6)


def test_extract_phases_handles_offdiag():
    """With small off-diagonal leakage the result stays a unit-modulus
    diagonal and approximates the underlying phases."""
    rng = np.random.default_rng(1)
    N = 8
    t0 = rng.uniform(-1, 1, N)
    J = np.zeros((N, 2, 2), complex)
    J[:, 0, 0] = 1.3 * np.exp(1j * t0)
    J[:, 1, 1] = 0.8 * np.exp(-1j * t0)
    J += 0.05 * (rng.normal(size=(N, 2, 2))
                 + 1j * rng.normal(size=(N, 2, 2)))
    P = np.asarray(mf.extract_phases(jnp.asarray(J)))
    np.testing.assert_allclose(np.abs(P[:, 0, 0]), 1.0, atol=1e-8)
    assert np.abs(np.angle(P[:, 0, 0]) - t0).max() < 0.2


def _tiny_problem(tmp_path, freqs, n_sta=8, tilesz=2):
    (tmp_path / "sky.txt").write_text(
        "P0A 0 40 0 40 0 0 3.0 0 0 0 0 0 0 0 0 150e6\n"
        "P1A 1 20 0 38 0 0 2.0 0 0 0 0 0 0 0 0 150e6\n")
    (tmp_path / "sky.txt.cluster").write_text("0 1 P0A\n1 1 P1A\n")
    ra0 = (41 / 60) * math.pi / 12
    dec0 = 40 * math.pi / 180
    srcs = skymodel.parse_sky_model(str(tmp_path / "sky.txt"),
                                    ra0, dec0, 150e6)
    sky = skymodel.build_cluster_sky(
        srcs, skymodel.parse_cluster_file(str(tmp_path / "sky.txt.cluster")))
    dsky = rp.sky_to_device(sky, jnp.float64)
    Jtrue = ds.random_jones(2, sky.nchunk, n_sta, seed=2, scale=0.2)
    tile = ds.simulate_dataset(dsky, n_stations=n_sta, tilesz=tilesz,
                               freqs=freqs, ra0=ra0, dec0=dec0,
                               jones=Jtrue, nchunk=sky.nchunk,
                               noise_sigma=0.01, seed=3)
    msdir = tmp_path / "sim.ms"
    ds.SimMS.create(str(msdir), [tile])
    return msdir, sky, dsky, tile, Jtrue


def test_residual_interp_matches_plain(tmp_path):
    """J_old == J_new -> interp residuals == plain residuals."""
    _, sky, dsky, tile, Jtrue = _tiny_problem(tmp_path, [149e6, 151e6])
    cidx = jnp.asarray(rp.chunk_indices(tile.tilesz, tile.nbase,
                                        sky.nchunk))
    args = (jnp.asarray(tile.x), jnp.asarray(tile.u),
            jnp.asarray(tile.v), jnp.asarray(tile.w),
            jnp.asarray(tile.freqs), tile.fdelta / 2,
            jnp.asarray(tile.sta1), jnp.asarray(tile.sta2), cidx,
            jnp.asarray(sky.subtract_mask()))
    J = jnp.asarray(Jtrue)
    plain = rr.calculate_residuals_multifreq(dsky, J, *args,
                                             correct_idx=0)
    interp = rr.calculate_residuals_interp(dsky, J, J, *args,
                                           correct_idx=0)
    np.testing.assert_allclose(np.asarray(plain), np.asarray(interp),
                               atol=1e-12)


def test_phase_only_correction_runs(tmp_path):
    """-k with -J: phase-only correction produces finite, different
    output from amplitude+phase correction."""
    _, sky, dsky, tile, Jtrue = _tiny_problem(tmp_path, [149e6, 151e6])
    cidx = jnp.asarray(rp.chunk_indices(tile.tilesz, tile.nbase,
                                        sky.nchunk))
    args = (jnp.asarray(tile.x), jnp.asarray(tile.u),
            jnp.asarray(tile.v), jnp.asarray(tile.w),
            jnp.asarray(tile.freqs), tile.fdelta / 2,
            jnp.asarray(tile.sta1), jnp.asarray(tile.sta2), cidx,
            jnp.asarray(sky.subtract_mask()))
    J = jnp.asarray(Jtrue)
    full = np.asarray(rr.calculate_residuals_multifreq(
        dsky, J, *args, correct_idx=0))
    ph = np.asarray(rr.calculate_residuals_multifreq(
        dsky, J, *args, correct_idx=0, phase_only=True))
    assert np.all(np.isfinite(ph))
    assert np.abs(full - ph).max() > 1e-6


def _pairs_problem(tmp_path, kmax3=False):
    """A tiny f32 tile as the jit boundaries see it: pairs ``x_r``,
    solutions as planes ``J_r8``, and the pieces of the plain reference
    (complex coherencies and Jones in double precision)."""
    from sagecal_tpu.rime import planes as pl
    _, sky, _, tile, Jtrue = _tiny_problem(tmp_path, [149e6, 151e6],
                                           n_sta=6, tilesz=3)
    dsky = rp.sky_to_device(sky, jnp.float32)
    nchunk = np.array([1, 3]) if kmax3 else sky.nchunk
    rng = np.random.default_rng(11)
    J = (ds.random_jones(2, nchunk, 6, seed=4, scale=0.2) if kmax3
         else Jtrue).astype(np.complex64)
    cidx = rp.chunk_indices(tile.tilesz, tile.nbase, nchunk)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    geo = (f32(tile.u), f32(tile.v), f32(tile.w), f32(tile.freqs),
           tile.fdelta / 2, jnp.asarray(tile.sta1), jnp.asarray(tile.sta2))
    coh = np.asarray(rp.coherencies(dsky, *geo[:5], per_channel_flux=True),
                     complex)
    x = (tile.x + 0.1 * rng.normal(size=tile.x.shape)).astype(np.complex64)
    x_r = jnp.stack([jnp.asarray(x.real), jnp.asarray(x.imag)], -1)
    return dict(dsky=dsky, geo=geo, cidx=cidx, coh=coh, x=x.astype(complex),
                x_r=x_r, J=J.astype(complex), J_r8=pl.jones_c2r(jnp.asarray(J)),
                sta1=tile.sta1, sta2=tile.sta2, nbase=tile.nbase)


def _reference_model(pb, mask):
    """sum_m mask_m J_p C_m J_q^H as the complex ``einsum`` of
    [B, F, 2, 2] operands, one cluster after another: the form the
    programs had before their planes, kept here as the plain
    reference."""
    out = np.zeros(pb["coh"].shape[1:], complex)
    for m in np.flatnonzero(mask):
        Jp = pb["J"][m][pb["cidx"][m], pb["sta1"]]
        Jq = pb["J"][m][pb["cidx"][m], pb["sta2"]]
        out += np.einsum("bij,bfjk,blk->bfil", Jp, pb["coh"][m], Jq.conj())
    return out


def _reference_correction(pb, res, m, phase_only):
    Jm = jnp.asarray(pb["J"][m])
    if phase_only:
        Jm = jax.vmap(mf.extract_phases)(Jm)
    G = np.asarray(rr.mmse_inverse(Jm, 1e-9))
    Gp = G[pb["cidx"][m], pb["sta1"]]
    Gq = G[pb["cidx"][m], pb["sta2"]]
    return np.einsum("bij,bfjk,blk->bfil", Gp, res, Gq.conj())


def _pairs(c):
    return np.stack([c.real, c.imag], -1)


#: the residual program's cases: (hybrid chunks, -k cluster, -J, storage)
_RESIDUAL_CASES = {
    "plain": (False, None, False, None),
    "hybrid": (True, None, False, None),
    "correct": (False, 0, False, None),
    "correct-hybrid": (True, 1, False, None),
    "correct-phase-only": (False, 0, True, None),
    "bf16": (False, None, False, jnp.bfloat16),
}


@pytest.mark.parametrize("case", list(_RESIDUAL_CASES))
def test_residual_pairs_match_complex_reference(tmp_path, case):
    """``calculate_residuals_pairs`` (pairs in, solutions as planes, the
    tile's period given) against x - sum_m J_p C_m J_q^H by complex
    ``einsum`` in double precision, to f32 rounding; under ``-k`` (with
    and without ``-J``) the residual corrected the same way; under bf16
    storage the same number rounded once to bf16."""
    kmax3, k, phase_only, sdt = _RESIDUAL_CASES[case]
    pb = _pairs_problem(tmp_path, kmax3)
    mask = np.array([True, False])
    x_r = pb["x_r"] if sdt is None else pb["x_r"].astype(sdt)
    got = rr.calculate_residuals_pairs(
        pb["dsky"], pb["J_r8"], x_r, *pb["geo"], jnp.asarray(pb["cidx"]),
        jnp.asarray(mask), out_dtype=sdt, correct_idx=k,
        phase_only=phase_only, row_period=pb["nbase"])
    assert got.shape == x_r.shape and got.dtype == x_r.dtype
    x = np.asarray(x_r.astype(jnp.float32), float)
    expect = (x[..., 0] + 1j * x[..., 1]) - _reference_model(pb, mask)
    if k is not None:
        expect = _reference_correction(pb, expect, k, phase_only)
    scale = np.abs(expect).max()
    # f32: a few ulp of the largest term; bf16: half an ulp of its 8 bits
    tol = 2e-6 if sdt is None else 2.0 ** -8
    assert np.abs(np.asarray(got, float) - _pairs(expect)).max() \
        < tol * scale
    if k is None and sdt is None:
        # the complex entry point is the same model, read as complex
        cplx = rr.calculate_residuals_multifreq(
            pb["dsky"], jnp.asarray(pb["J"], jnp.complex64),
            jnp.asarray(pb["x"], jnp.complex64), *pb["geo"],
            jnp.asarray(pb["cidx"]), jnp.asarray(mask))
        np.testing.assert_array_equal(_pairs(np.asarray(cplx)),
                                      np.asarray(got))


@pytest.mark.parametrize("solutions", [True, False],
                         ids=["with-p", "without-p"])
@pytest.mark.parametrize("mode", [1, 2, 3])
def test_simulate_pairs_match_complex_reference(tmp_path, mode, solutions):
    """``simulate_pairs`` modes 1, 2, 3 (replace, add, subtract), with
    one cluster on the ignore list, against the complex ``einsum``
    reference; without solutions the model is the clusters' plain
    sum."""
    pb = _pairs_problem(tmp_path)
    keep = np.array([False, True])
    got = rr.simulate_pairs(
        pb["dsky"], pb["x_r"], *pb["geo"], mode=mode,
        J=pb["J_r8"] if solutions else None,
        chunk_idx=jnp.asarray(pb["cidx"]), ignore_mask=keep,
        row_period=pb["nbase"])
    model = _reference_model(pb, keep) if solutions \
        else pb["coh"][keep].sum(0)
    expect = {1: model, 2: pb["x"] + model, 3: pb["x"] - model}[mode]
    assert got.shape == pb["x_r"].shape and got.dtype == jnp.float32
    assert np.abs(np.asarray(got, float) - _pairs(expect)).max() \
        < 2e-6 * np.abs(expect).max()


@pytest.mark.slow
def test_per_channel_bandpass_mode(tmp_path):
    """-b 1 CLI end-to-end: per-channel solve converges and writes
    solutions + residuals."""
    msdir, sky, dsky, tile, Jtrue = _tiny_problem(
        tmp_path, [148e6, 150e6, 152e6])
    solpath = str(tmp_path / "sols.txt")
    args = cli.build_parser().parse_args([
        "-d", str(msdir), "-s", str(tmp_path / "sky.txt"),
        "-c", str(tmp_path / "sky.txt.cluster"), "-p", solpath,
        "-j", "0", "-e", "2", "-g", "8", "-l", "6", "-b", "1"])
    cfg = cli.config_from_args(args)
    assert cfg.per_channel_bfgs
    history = pipeline.run(cfg, log=lambda *a: None)
    h = history[0]
    assert np.isfinite(h["res_1"]) and h["res_1"] < h["res_0"]
    hdr, blocks = sol.read_solutions(solpath, sky.nchunk)
    assert len(blocks) == 1
    # residuals written back shrank the data
    back = ds.SimMS(str(msdir),
                    data_column="CORRECTED_DATA").read_tile(0)
    assert np.abs(back.x).mean() < 0.3 * np.abs(tile.x).mean()
