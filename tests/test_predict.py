"""RIME prediction tests against hand-computed oracles.

The oracle mirrors the reference math (predict.c:270-415): phase
2*pi*(ul+vm+wn)*f, |sinc| channel smearing, Stokes->correlation mapping,
envelope formulas — computed here independently with numpy/scipy-free code.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from sagecal_tpu import skymodel
from sagecal_tpu.rime import predict as rp
from sagecal_tpu.rime import envelopes as env
from sagecal_tpu.io import dataset as ds


def make_sky(sources, clusters):
    return skymodel.build_cluster_sky(sources, clusters)


def point_source(name, ll, mm, sI=1.0, sQ=0.0, sU=0.0, sV=0.0,
                 si=0.0, f0=150e6):
    nn = np.sqrt(1 - ll * ll - mm * mm)
    return skymodel.Source(
        name=name, ra=0, dec=0, ll=ll, mm=mm, nn=nn - 1.0,
        sI=sI, sQ=sQ, sU=sU, sV=sV, sI0=sI, sQ0=sQ, sU0=sU, sV0=sV,
        spec_idx=si, spec_idx1=0.0, spec_idx2=0.0, f0=f0)


def test_point_source_coherency_oracle():
    s1 = point_source("P1", 0.01, -0.02, sI=2.0, sQ=0.5, sU=0.25, sV=-0.1)
    s2 = point_source("P2", -0.004, 0.003, sI=1.5)
    sky = make_sky({"P1": s1, "P2": s2}, [(0, 1, ["P1"]), (1, 1, ["P2"])])
    dsky = rp.sky_to_device(sky, jnp.float64)

    u = np.array([100.0, -50.0, 3.0]) / ds.C_M_S * 1000
    v = np.array([20.0, 7.0, -2.0]) / ds.C_M_S * 1000
    w = np.array([1.0, 2.0, 0.5]) / ds.C_M_S * 1000
    freqs = np.array([140e6, 150e6])
    fdelta = 1e6

    coh = np.asarray(rp.coherencies(
        dsky, jnp.asarray(u), jnp.asarray(v), jnp.asarray(w),
        jnp.asarray(freqs), fdelta))
    assert coh.shape == (2, 3, 2, 2, 2)

    # oracle for cluster 0 (P1), baseline 1, channel 0
    b, f = 1, 0
    G = 2 * np.pi * (u[b] * s1.ll + v[b] * s1.mm + w[b] * s1.nn)
    ph = np.exp(1j * G * freqs[f])
    sm = abs(np.sin(G * fdelta / 2) / (G * fdelta / 2))
    P = ph * sm
    expect = np.array([[P * (s1.sI + s1.sQ), P * (s1.sU + 1j * s1.sV)],
                       [P * (s1.sU - 1j * s1.sV), P * (s1.sI - s1.sQ)]])
    np.testing.assert_allclose(coh[0, b, f], expect, rtol=1e-10)


def test_phase_center_source_is_real():
    s = point_source("P1", 0.0, 0.0, sI=3.0)
    sky = make_sky({"P1": s}, [(0, 1, ["P1"])])
    dsky = rp.sky_to_device(sky, jnp.float64)
    u = np.random.default_rng(0).normal(size=8) * 1e-5
    coh = np.asarray(rp.coherencies(
        dsky, jnp.asarray(u), jnp.asarray(u), jnp.asarray(u),
        jnp.asarray([150e6]), 180e3))
    # source at phase center: no fringe, XX=YY=I exactly
    np.testing.assert_allclose(coh[0, :, 0, 0, 0], 3.0, rtol=1e-12)
    np.testing.assert_allclose(coh[0, :, 0, 1, 1], 3.0, rtol=1e-12)
    np.testing.assert_allclose(coh[0, :, 0, 0, 1], 0.0, atol=1e-12)


def test_per_channel_spectral_flux():
    s = point_source("P1", 0.001, 0.0, sI=2.0, si=-0.7, f0=140e6)
    sky = make_sky({"P1": s}, [(0, 1, ["P1"])])
    # parse-time scaling to data freq0=150MHz affects sI only
    dsky = rp.sky_to_device(sky, jnp.float64)
    u = jnp.asarray([1e-6])
    coh = np.asarray(rp.coherencies(dsky, u, u, u, jnp.asarray([160e6]), 1.0,
                                    per_channel_flux=True))
    amp = np.abs(coh[0, 0, 0, 0, 0])
    expect = np.exp(np.log(2.0) - 0.7 * np.log(160e6 / 140e6))
    np.testing.assert_allclose(amp, expect, rtol=1e-9)


def test_gaussian_envelope_matches_formula():
    x = np.array([3000.0, 150.0])  # wavelengths
    y = np.array([-2000.0, 80.0])
    z = np.zeros(2)
    eX, eY, eP = 2 * 0.001, 2 * 0.0005, 0.3
    got = np.asarray(env.gaussian(
        jnp.asarray(x), jnp.asarray(y), jnp.asarray(z),
        eX, eY, eP, 1.0, 0.0, 1.0, 0.0, jnp.asarray(False)))
    ut = eX * (np.cos(eP) * x - np.sin(eP) * y)
    vt = eY * (np.sin(eP) * x + np.cos(eP) * y)
    np.testing.assert_allclose(got, np.pi / 2 * np.exp(-(ut**2 + vt**2)),
                               rtol=1e-6)


def test_bessel_approximations():
    try:
        from scipy.special import j0, j1
    except ImportError:
        pytest.skip("scipy unavailable")
    x = np.linspace(-30, 30, 301)
    np.testing.assert_allclose(np.asarray(env._bessel_j0(jnp.asarray(x))),
                               j0(x), atol=2e-7)
    np.testing.assert_allclose(np.asarray(env._bessel_j1(jnp.asarray(x))),
                               j1(x), atol=2e-7)


def test_shapelet_envelope_n0_1():
    # single-mode shapelet (n0=1): envelope = 2*pi*modes[0]*B0(-ut)B0(vt)*a*b
    beta, mode0 = 0.5, 0.8
    eX = eY = 1.0
    u = np.array([0.3])
    vv = np.array([-0.2])
    w = np.zeros(1)
    got = np.asarray(env.shapelet(
        jnp.asarray(u), jnp.asarray(vv), jnp.asarray(w),
        eX, eY, 0.0, beta, jnp.asarray([[mode0]]),
        1.0, 0.0, 1.0, 0.0, jnp.asarray(False)))
    def b0(x):
        return np.exp(-0.5 * x * x) / np.sqrt(2.0)
    expect = 2 * np.pi * mode0 * b0(-u[0] * beta) * b0(vv[0] * beta)
    np.testing.assert_allclose(got.real, expect, rtol=1e-6)
    np.testing.assert_allclose(got.imag, 0.0, atol=1e-9)


def _sandwich_reference(coh, J, sta1, sta2, cidx, mask):
    """The plain reference of ``predict_model``: per cluster the
    ``[B, F, 2, 2]`` complex ``einsum`` J_p C J_q^H, summed over the
    clusters the mask keeps (the form the program itself had before its
    planes)."""
    out = np.zeros(coh.shape[1:], complex)
    for m in np.flatnonzero(mask):
        Jp, Jq = J[m][cidx[m], sta1], J[m][cidx[m], sta2]
        out += np.einsum("bij,bfjk,blk->bfil", Jp, coh[m], Jq.conj())
    return out


def _sandwich_problem(layout, F, seed=5):
    """(coh [M,B,F,2,2], J [M,K,N,2,2], sta1, sta2, cidx [M,B], nbase):
    ``periodic`` rows [tilesz, nbase] with one chunk, ``ragged`` the same
    less its last two rows (B no multiple of the period), ``hybrid``
    three chunks in one of the clusters."""
    rng = np.random.default_rng(seed)
    N, T, M = 5, 6, 3
    p, q = np.triu_indices(N, 1)
    nbase = len(p)
    B = T * nbase - (2 if layout == "ragged" else 0)
    sta1, sta2 = np.tile(p, T)[:B], np.tile(q, T)[:B]
    nchunk = np.array([1, 3, 1] if layout == "hybrid" else [1, 1, 1])
    K = int(nchunk.max())
    cidx = rp.chunk_indices(T, nbase, nchunk)[:, :B]
    coh = rng.normal(size=(M, B, F, 2, 2)) \
        + 1j * rng.normal(size=(M, B, F, 2, 2))
    J = rng.normal(size=(M, K, N, 2, 2)) + 1j * rng.normal(size=(M, K, N, 2, 2))
    return coh, J, sta1.astype(np.int32), sta2.astype(np.int32), cidx, nbase


@pytest.mark.parametrize("masked", [False, True], ids=["all", "one-out"])
@pytest.mark.parametrize("F", [1, 4])
@pytest.mark.parametrize("layout", ["periodic", "ragged", "hybrid"])
def test_predict_model_matches_complex_sandwich(layout, F, masked):
    """``predict_model``'s planes against the complex ``einsum`` form
    and, for one channel, against ``model8``, to f32 rounding: the same
    sixteen multiply-adds a row, in another order. With the tile's
    period given or not (0) the layout differs and the numbers do not."""
    from sagecal_tpu.rime import planes as pl
    coh, J, sta1, sta2, cidx, nbase = _sandwich_problem(layout, F)
    coh, J = coh.astype(np.complex64), J.astype(np.complex64)
    mask = np.array([True, not masked, True])
    expect = _sandwich_reference(coh.astype(complex), J.astype(complex),
                                 sta1, sta2, cidx, mask)
    c8 = jnp.transpose(pl.jones_c2r(jnp.asarray(coh)), (3, 0, 2, 1))
    P = pl.jones_c2r(jnp.asarray(J))
    assert c8.dtype == P.dtype == jnp.float32
    scale = np.abs(expect).max()
    got = {}
    for period in (nbase, 0):
        v8 = rp.predict_model(c8, P, jnp.asarray(sta1), jnp.asarray(sta2),
                              jnp.asarray(cidx),
                              cluster_mask=jnp.asarray(mask) if masked
                              else None, row_period=period)
        assert v8.shape == (8, F, len(sta1)) and v8.dtype == jnp.float32
        got[period] = np.asarray(pl.jones_r2c(jnp.transpose(v8, (2, 1, 0))))
        assert np.abs(got[period] - expect).max() < 5e-7 * scale
    np.testing.assert_array_equal(got[nbase], got[0])
    if F == 1:
        m8 = sum(np.asarray(rp.model8(
            jnp.asarray(coh[m, :, 0]), jnp.asarray(J[m]), jnp.asarray(sta1),
            jnp.asarray(sta2), jnp.asarray(cidx[m])))
            for m in np.flatnonzero(mask))
        assert np.abs(np.asarray(pl.jones_c2r(jnp.asarray(got[0][:, 0])))
                      - m8).max() < 5e-7 * scale


def test_coherency_planes_are_the_coherencies():
    """``coherencies(planes=True)`` hands on the source sum's four
    correlations as eight real planes [8, M, F, B]: the numbers of the
    ``[M, B, F, 2, 2]`` complex form, bit for bit."""
    from sagecal_tpu.rime import planes as pl
    srcs = {"A": point_source("A", 0.01, 0.005, sI=2.0, sQ=0.3, sU=-0.2,
                              sV=0.1),
            "B": point_source("B", -0.02, 0.01, sI=1.0)}
    sky = make_sky(srcs, [(0, 1, ["A"]), (1, 1, ["B"])])
    dsky = rp.sky_to_device(sky, jnp.float32)
    rng = np.random.default_rng(2)
    u, v, w = (jnp.asarray(rng.normal(size=7) * 1e-6, jnp.float32)
               for _ in range(3))
    args = (dsky, u, v, w, jnp.asarray([149e6, 150e6, 151e6], jnp.float32),
            1e5)
    coh = rp.coherencies(*args, per_channel_flux=True)
    c8 = rp.coherencies(*args, per_channel_flux=True, planes=True)
    assert c8.shape == (8, 2, 3, 7) and c8.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(c8), np.asarray(jnp.transpose(pl.jones_c2r(coh),
                                                 (3, 0, 2, 1))))


@pytest.mark.parametrize("planes", [True, False], ids=["planes", "complex"])
@pytest.mark.parametrize("F", [1, 3])
@pytest.mark.parametrize("S", [3, 128])
def test_source_sum_against_float64_sum(S, F, planes):
    """The sum over a cluster's sources is a contraction on real planes
    (PR 49).  Against a float64 sum written out here (fringe phase,
    ``|sinc|`` smearing, spectral scaling, the Stokes weights of the four
    correlations) from the SAME f32 inputs: S sources of four non-zero
    Stokes in one cluster and S - 1 in the other (so one slot of the
    padded sky is masked), and one live source masked by hand."""
    rng = np.random.default_rng(100 * S + F)
    srcs, names = {}, []
    for i in range(S):
        ll, mm = rng.normal(0, 0.01, 2)
        I = 1 + rng.random()
        srcs[f"s{i}"] = point_source(
            f"s{i}", ll, mm, sI=I, sQ=0.3 * I * rng.normal(),
            sU=0.2 * I * rng.normal(), sV=0.1 * I * rng.normal(),
            si=-0.7 * rng.random() - 0.1)
        names.append(f"s{i}")
    sky = make_sky(srcs, [(0, 1, names), (1, 1, names[:-1])])
    dsky = rp.sky_to_device(sky, jnp.float32)
    assert not bool(dsky.smask[1, S - 1])
    dsky = dsky._replace(smask=dsky.smask.at[0, 1].set(False))
    B, fdelta = 11, 2e5
    u, v, w = (jnp.asarray(rng.normal(size=B) * 2e-6, jnp.float32)
               for _ in range(3))
    freqs = jnp.asarray(np.linspace(149e6, 151e6, F), jnp.float32)
    got = np.asarray(rp.coherencies(dsky, u, v, w, freqs, fdelta,
                                    per_channel_flux=True, planes=planes))

    f8 = lambda a: np.asarray(a, np.float64)
    want = np.zeros((2, B, F, 2, 2), complex)
    for m in range(2):
        G = 2 * np.pi * (np.outer(f8(u), f8(dsky.ll[m]))
                         + np.outer(f8(v), f8(dsky.mm[m]))
                         + np.outer(f8(w), f8(dsky.nn[m])))      # [B, S]
        x = np.where(G == 0, 1.0, G * fdelta / 2)   # a padded slot: G = 0
        smear = np.where(G == 0, 1.0, np.abs(np.sin(x) / x))
        live = np.asarray(dsky.smask[m])
        for f, freq in enumerate(f8(freqs)):
            scale = np.exp(f8(dsky.spec_idx[m])
                           * np.log(freq / f8(dsky.f0[m])))
            I, Q, U, V = (f8(a[m]) * scale for a in
                          (dsky.sI0, dsky.sQ0, dsky.sU0, dsky.sV0))
            P = np.exp(1j * G * freq) * smear * live
            want[m, :, f] = np.stack(
                [np.stack([P @ (I + Q), P @ (U + 1j * V)], -1),
                 np.stack([P @ (U - 1j * V), P @ (I - Q)], -1)], -2)
    if planes:
        from sagecal_tpu.rime import planes as pl
        assert got.shape == (8, 2, F, B) and got.dtype == np.float32
        want = np.asarray(pl.jones_c2r(jnp.asarray(want))).transpose(
            3, 0, 2, 1)
    else:
        assert got.shape == want.shape and got.dtype == np.complex64
    # f32 rounding: phases of a few radians to 1e-7 of themselves, then
    # S terms of order one added up
    assert np.abs(got - want).max() < 4e-6 * np.abs(want).max()


def test_chunk_indices():
    ci = rp.chunk_indices(tilesz=10, nbase=3, nchunk=np.array([1, 3]))
    assert ci.shape == (2, 30)
    assert set(ci[0]) == {0}
    # ceil(10/3)=4 -> timeslots 0-3 chunk0, 4-7 chunk1, 8-9 chunk2
    assert ci[1][0] == 0 and ci[1][3 * 4] == 1 and ci[1][3 * 8] == 2


def test_uvcut():
    flags = jnp.zeros(3, jnp.int32)
    u = jnp.asarray([1e-7, 1e-4, 1e-2])
    v = jnp.zeros(3)
    out = np.asarray(rp.uvcut_flags(flags, u, v, jnp.asarray([150e6]),
                                    uvmin=50.0, uvmax=100e3))
    assert list(out) == [2, 0, 2]


def _device_uvcut(rowflags, tile, uvmin, uvmax):
    """``apply_uvcut`` as it was while it ran on a device (until PR 50):
    the form ``pipeline.py`` still computes its flags in."""
    return np.asarray(rp.uvcut_flags(
        jnp.asarray(np.asarray(rowflags), jnp.int32),
        jnp.asarray(np.asarray(tile.u, np.float64)),
        jnp.asarray(np.asarray(tile.v, np.float64)),
        jnp.asarray(np.asarray(tile.freqs, np.float64)),
        uvmin, uvmax), np.int8)


def _cell_tile(seed=50, n_sta=62, tilesz=10, freqs=(150e6,)):
    """u, v (seconds) of a tile of the consensus cells' shape: 1891
    baselines x 10 slots, a dense core inside a few kilometres, a
    tenth of the rows flagged 1 or 2 beforehand."""
    import types
    rng = np.random.default_rng(seed)
    r = 400.0 * np.exp(rng.normal(0.0, 1.3, n_sta))
    th = rng.uniform(0, 2 * np.pi, n_sta)
    p, q = np.triu_indices(n_sta, 1)
    rot = 2 * np.pi * np.arange(tilesz)[:, None] * 10.0 / 86164.0
    dx, dy = (r * np.cos(th))[p] - (r * np.cos(th))[q], \
        (r * np.sin(th))[p] - (r * np.sin(th))[q]
    u = (dx * np.cos(rot) - dy * np.sin(rot)).ravel() / ds.C_M_S
    v = 0.8 * (dx * np.sin(rot) + dy * np.cos(rot)).ravel() / ds.C_M_S
    flags = rng.choice(np.array([0, 1, 2], np.int8), u.size,
                       p=[0.9, 0.05, 0.05])
    return types.SimpleNamespace(u=u, v=v, freqs=np.asarray(freqs, float),
                                 flags=flags)


def _edge_rows(edge, freq0, dt, steps=4):
    """u (v = 0) whose uv distance in ``dt`` walks ``steps`` steps of
    the dtype either side of ``edge`` wavelengths at ``freq0``."""
    u0 = dt.type(dt.type(edge) / dt.type(freq0))
    us = [u0]
    for _ in range(steps):
        us.insert(0, np.nextafter(us[0], dt.type(0)))
        us.append(np.nextafter(us[-1], dt.type(np.inf)))
    return np.asarray(us, np.float64)       # exact: every dt is a double


@pytest.mark.parametrize("x64", [True, False], ids=["x64", "x32"])
@pytest.mark.parametrize("case", ["cell", "edges", "edges2ch", "full"])
def test_host_uvcut_is_the_device_rule_flag_for_flag(case, x64):
    """``apply_uvcut`` (numpy, the consensus reader's thread) against
    ``uvcut_flags`` (jnp, what ``pipeline.py`` keeps on the device): one
    rule in two forms, in the dtype ``jnp.asarray`` gives the tile's
    float64 geometry. On a tile of the cells' shape under ``-x 30``
    with rows already flagged 1 and 2; on rows built to lie within four
    steps of the computing dtype either side of both edges; on a full
    window, which hands the input back unchanged."""
    import jax
    import types
    with jax.enable_x64(x64):
        dt = np.dtype(jax.dtypes.canonicalize_dtype(np.float64))
        assert dt == (np.float64 if x64 else np.float32)
        if case == "full":
            tile = _cell_tile()
            out = rp.apply_uvcut(tile.flags, tile, 0.0, 1e9)
            assert out is tile.flags
            return
        if case == "cell":
            tile, uvmin, uvmax = _cell_tile(), 30.0, 1e9
        else:
            freqs = (150e6,) if case == "edges" else (149e6, 151e6)
            uvmin, uvmax = 30.1, 2500.3     # neither is a float32
            # the upper test is uvdist * f_last > uvmax * f_0
            u = np.concatenate([
                _edge_rows(uvmin, freqs[0], dt),
                _edge_rows(uvmax * freqs[0] / freqs[-1], freqs[0], dt)])
            tile = types.SimpleNamespace(
                u=np.concatenate([u, 0 * u]), v=np.concatenate([0 * u, -u]),
                freqs=np.asarray(freqs), flags=np.zeros(2 * u.size, np.int8))
        got = rp.apply_uvcut(tile.flags, tile, uvmin, uvmax)
        want = _device_uvcut(tile.flags, tile, uvmin, uvmax)
    assert got.dtype == want.dtype == np.int8 and got.shape == want.shape
    assert got.tobytes() == want.tobytes()
    assert got is not tile.flags and got.flags.writeable
    # the cut cuts, and leaves what was flagged as it was
    was = tile.flags != 0
    assert (got[was] == tile.flags[was]).all()
    assert set(got[~was]) == {0, 2}
    if case == "cell":
        assert 0.002 < (got[~was] == 2).mean() < 0.2
    else:
        # each edge is straddled: both sides of it among its nine rows
        for rows in np.split(got, 4):
            assert set(rows) == {0, 2}, rows


def test_host_uvcut_window_beyond_the_dtype_cuts_nothing():
    """``-y`` past float32's range: the product overflows to infinity in
    both forms and no row is outside it."""
    import jax
    with jax.enable_x64(False):
        tile = _cell_tile()
        got = rp.apply_uvcut(tile.flags, tile, 0.0, 1e35)
        want = _device_uvcut(tile.flags, tile, 0.0, 1e35)
    assert got.tobytes() == want.tobytes() == tile.flags.tobytes()


@pytest.mark.parametrize("dtype", [jnp.float64, jnp.float32, jnp.bfloat16],
                         ids=["f64", "f32", "bf16"])
def test_host_weights_are_the_device_weights(dtype):
    """``lm.make_weights_np`` is ``np.asarray(lm.make_weights(...))``:
    dtype, shape, bytes, and an array of its own."""
    from sagecal_tpu.solvers import lm
    flags = _cell_tile().flags
    want = np.asarray(lm.make_weights(jnp.asarray(flags, jnp.int32), dtype))
    got = lm.make_weights_np(flags, np.dtype(dtype))
    assert got.dtype == want.dtype == np.dtype(dtype)
    assert got.shape == want.shape == (flags.size, 8)
    assert got.tobytes() == want.tobytes()
    assert got.flags.c_contiguous and got.flags.writeable
    assert float(got.astype(np.float64).sum()) == 8 * (flags == 0).sum()


def test_simulate_roundtrip_consistency():
    s = point_source("P1", 0.01, 0.005, sI=1.0)
    sky = make_sky({"P1": s}, [(0, 1, ["P1"])])
    dsky = rp.sky_to_device(sky, jnp.float64)
    tile = ds.simulate_dataset(dsky, n_stations=5, tilesz=4,
                               freqs=[149e6, 151e6], ra0=0.0, dec0=0.7)
    assert tile.nrows == 10 * 4
    assert tile.x.shape == (40, 2, 2, 2)
    # identity Jones: data equals summed model coherencies
    coh = np.asarray(rp.coherencies(
        dsky, jnp.asarray(tile.u), jnp.asarray(tile.v), jnp.asarray(tile.w),
        jnp.asarray(tile.freqs), tile.fdelta / 2, per_channel_flux=True))
    np.testing.assert_allclose(tile.x, coh.sum(0), rtol=1e-9)


def test_simms_roundtrip(tmp_path):
    s = point_source("P1", 0.01, 0.005)
    sky = make_sky({"P1": s}, [(0, 1, ["P1"])])
    dsky = rp.sky_to_device(sky, jnp.float64)
    tile = ds.simulate_dataset(dsky, n_stations=4, tilesz=2,
                               freqs=[150e6], ra0=0.0, dec0=0.7)
    ms = ds.SimMS.create(str(tmp_path / "sim.ms"), [tile])
    i, t2 = next(ms.tiles())
    np.testing.assert_allclose(t2.x, tile.x)
    np.testing.assert_allclose(t2.u, tile.u)
    assert t2.n_stations == 4
