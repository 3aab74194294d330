"""The compact pack of a model's shapelet sources (``rime/predict.
ShapeletPack``, PR 52): the source sum that evaluates the basis for ``[M,
S_sh]`` packed slots and places its envelope into the cluster's phasors,
against the DENSE evaluation written out here in numpy float64
(predict.c:142's formula on every slot of the model, selected by the
slot's kind).

Clusters of six slots with 0, 1, 2 and 3 shapelets at the first, the
last and adjacent slots, ``n0`` 1, 4 and 7 padded to the model's
``n0max``, sources on both sides of ``PROJ_CUT``, a shapelet slot that is
dead (masked), two channels under the per-channel flux law of ``-F 1``,
float32 and float64, complex and planes out, and the sky once closed over
and once an ARGUMENT of the jitted program: ``S_sh`` is read from a shape,
so both lower to the pack, and neither makes an array of rows x sources x
modes.
"""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.polynomial.hermite import hermval

from sagecal_tpu import pipeline, skymodel
from sagecal_tpu.rime import planes as pl, predict as rp

B, S, F = 13, 6, 2
FREQS = np.array([148e6, 152e6])
FDELTA = 90e3
#: per cluster, the slots that hold a shapelet; a negative slot is a
#: shapelet that is dead (its ``smask`` False): not packed, not summed
LAYOUTS = {
    "none": [[], []],
    "one-first": [[0], []],
    "two-adjacent-last": [[4, 5], [5, -2]],
    "three-first-adjacent": [[0, 2, 3], [-3, 5], []],
}
#: the orders handed out in turn: a model of three or more is padded to 7
ORDERS = (4, 1, 7)
TOL = {jnp.float32: 2e-4, jnp.float64: 1e-10}


def _source(name, rng, n0=0, far=False):
    """A point, or with ``n0`` a shapelet of that order, as
    ``skymodel.parse_sky_model`` makes one: ``far`` puts it beyond
    ``PROJ_CUT`` of the phase centre, where its uv are projected."""
    r, th = (0.08 if far else 0.02) * (1 + 0.2 * rng.random()), \
        2 * math.pi * rng.random()
    ll, mm = r * math.cos(th), r * math.sin(th)
    nn = math.sqrt(1 - ll * ll - mm * mm)
    flux = [1 + rng.random(), *(0.2 * rng.normal(size=3))]
    si = [-0.7 * rng.random() - 0.1, 0.1 * rng.normal(), 0.05 * rng.normal()]
    s = skymodel.Source(
        name=name, ra=0.0, dec=0.0, ll=ll, mm=mm, nn=nn - 1.0,
        sI=flux[0], sQ=flux[1], sU=flux[2], sV=flux[3],
        sI0=flux[0], sQ0=flux[1], sU0=flux[2], sV0=flux[3],
        spec_idx=si[0], spec_idx1=si[1], spec_idx2=si[2], f0=130e6)
    if n0:
        phi, xi = math.acos(nn), math.atan2(-ll, mm)
        s.stype = skymodel.STYPE_SHAPELET
        s.cxi, s.sxi = math.cos(xi), math.sin(-xi)
        s.cphi, s.sphi = math.cos(phi), math.sin(-phi)
        s.use_projection = nn < skymodel.PROJ_CUT
        assert s.use_projection == far
        s.eX, s.eY, s.eP = 0.8 + 0.4 * rng.random(), 1.1, rng.random()
        s.sh_n0, s.sh_beta = n0, 2e-3 * (1 + rng.random())
        s.sh_modes = rng.normal(size=n0 * n0) / (1 + np.arange(n0 * n0))
    return s


def _sky(layout):
    rng = np.random.default_rng(52)
    srcs, clusters, dead, turn = {}, [], [], 0
    for m, slots in enumerate(LAYOUTS[layout]):
        at = {s % S for s in slots}
        names = []
        for s in range(S):
            names.append(f"{'S' if s in at else 'P'}{m}_{s}")
            if s in at:
                srcs[names[-1]] = _source(names[-1], rng, ORDERS[turn % 3],
                                          far=turn % 2 == 1)
                turn += 1
            else:
                srcs[names[-1]] = _source(names[-1], rng, far=s % 2 == 1)
        clusters.append((m, 1, names))
        dead += [(m, s % S) for s in slots if s < 0]
    sky = skymodel.build_cluster_sky(srcs, clusters)
    for m, s in dead:
        sky.smask[m, s] = False
    return sky


def _basis(n, x):
    """B_n(x) = H_n(x) e^{-x^2/2} / sqrt(2^(n+1) n!) (predict.c:86-92)."""
    return (hermval(x, [0.0] * n + [1.0]) * np.exp(-0.5 * x * x)
            / math.sqrt(2.0 ** (n + 1) * math.factorial(n)))


def _dense(sky, dtype, u, v, w, shapelets=True):
    """[M, B, F, 2, 2] in float64 from the inputs as ``dtype`` holds them:
    every slot's fringe, smearing and flux law, and predict.c:142 on every
    slot, kept where the slot is a shapelet."""
    f8 = lambda a: np.asarray(np.asarray(a, dtype), np.float64)
    n0max = int(round(math.sqrt(sky.sh_modes.shape[-1])))
    u, v, w = f8(u)[:, None], f8(v)[:, None], f8(w)[:, None]
    out = np.zeros((len(sky.smask), B, F, 2, 2), complex)
    for m in range(len(sky.smask)):
        g = lambda name: f8(getattr(sky, name)[m])[None, :]
        G = 2 * np.pi * (u * g("ll") + v * g("mm") + w * g("nn"))
        x = G * FDELTA / 2
        smear = np.where(G == 0, 1.0, np.abs(np.sin(x) / np.where(
            x == 0, 1.0, x)))
        for fi, freq in enumerate(f8(FREQS)):
            ul, vl, wl = u * freq, v * freq, w * freq
            up = -(ul * g("cxi") - vl * g("cphi") * g("sxi")
                   + wl * g("sphi") * g("sxi"))
            vp = -(ul * g("sxi") + vl * g("cphi") * g("cxi")
                   - wl * g("sphi") * g("cxi"))
            proj = sky.use_projection[m][None, :]
            up, vp = np.where(proj, up, ul), np.where(proj, vp, vl)
            a = 1 / np.where(g("eX") != 0, g("eX"), 1.0)
            b = 1 / np.where(g("eY") != 0, g("eY"), 1.0)
            ut = a * (np.cos(g("eP")) * up - np.sin(g("eP")) * vp)
            vt = b * (np.sin(g("eP")) * up + np.cos(g("eP")) * vp)
            beta = g("sh_beta")
            c = f8(sky.sh_modes[m]).reshape(S, n0max, n0max)   # [S, n2, n1]
            env = np.zeros((B, S), complex)
            for n2 in range(n0max):
                for n1 in range(n0max):
                    env += (1j ** (n1 + n2) * c[None, :, n2, n1]
                            * _basis(n1, -ut * beta) * _basis(n2, vt * beta))
            env *= 2 * np.pi * a * b
            is_sh = (sky.stype[m] == skymodel.STYPE_SHAPELET)[None, :]
            env = np.where(is_sh & shapelets, env, 1.0)
            fr = np.log(freq / g("f0"))
            law = np.exp(g("spec_idx") * fr + g("spec_idx1") * fr ** 2
                         + g("spec_idx2") * fr ** 3)
            I, Q, U, V = (g(k) * law for k in ("sI0", "sQ0", "sU0", "sV0"))
            ph = np.exp(1j * G * freq) * smear * env * sky.smask[m][None, :]
            out[m, :, fi] = np.stack(
                [np.stack([(ph * (I + Q)).sum(1), (ph * (U + 1j * V)).sum(1)],
                          -1),
                 np.stack([(ph * (U - 1j * V)).sum(1), (ph * (I - Q)).sum(1)],
                          -1)], -2)
    return out


def _uvw(dtype):
    rng = np.random.default_rng(7)
    return tuple(jnp.asarray(rng.normal(size=B) * s, dtype)
                 for s in (2e-6, 2e-6, 2e-7))


def _tensor_dims(text):
    return [tuple(int(d) for d in dims.split("x") if d)
            for dims in re.findall(r"tensor<((?:\d+x)+)[a-z]", text)]


@pytest.mark.parametrize("planes", [False, True], ids=["complex", "planes"])
@pytest.mark.parametrize("sky_is", ["closed-over", "argument"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64],
                         ids=["f32", "f64"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_packed_source_sum_is_the_dense_sum(layout, dtype, sky_is, planes):
    sky = _sky(layout)
    live = [[s for s in slots if s >= 0] for slots in LAYOUTS[layout]]
    s_sh = max(len(slots) for slots in live)
    n0max = int(round(math.sqrt(sky.sh_modes.shape[-1])))
    dsky = rp.sky_to_device(sky, dtype)
    pack = dsky.shapelets
    assert pack.slot.shape == (len(live), s_sh)
    assert pack.modes.shape == (len(live), n0max, n0max, s_sh)
    for m, slots in enumerate(live):
        assert list(np.asarray(pack.slot[m])) == slots + [-1] * (
            s_sh - len(slots))
    if layout == "three-first-adjacent":
        assert n0max == 7
        assert set(sky.sh_n0[sky.smask & (sky.sh_n0 > 0)]) == {1, 4, 7}
    if s_sh > 1:
        assert {bool(p) for p, s in zip(np.asarray(pack.use_projection).flat,
                                        np.asarray(pack.slot).flat)
                if s >= 0} == {False, True}
    u, v, w = _uvw(dtype)
    freqs = jnp.asarray(FREQS, dtype)

    def coh(d, u, v, w):
        return rp.coherencies(d, u, v, w, freqs, FDELTA,
                              per_channel_flux=True, planes=planes)
    if sky_is == "argument":
        fn, args = jax.jit(coh), (dsky, u, v, w)
    else:
        fn, args = jax.jit(lambda *a: coh(dsky, *a)), (u, v, w)
    low = fn.lower(*args)
    dims = _tensor_dims(low.as_text())
    # rows x sources and rows alone are there; rows x sources x modes not
    assert any(B in d and S in d for d in dims)
    if s_sh:
        assert not [d for d in dims if B in d and S in d and (
            n0max in d or n0max ** 2 in d)]
        assert [d for d in dims if d[-3:] == (n0max, s_sh, B)]
    else:
        assert n0max == 1

    got = np.asarray(fn(*args))
    if planes:
        got = np.asarray(pl.jones_r2c(np.moveaxis(got, 0, -1)))  # [M,F,B,2,2]
        got = np.moveaxis(got, 1, 2)
    want = _dense(sky, dtype, u, v, w)
    rms = np.sqrt(np.mean(np.abs(want) ** 2))
    assert np.abs(got - want).max() < TOL[dtype] * rms
    if s_sh:
        # the shapelets are in the sum: as points it is another sum
        other = _dense(sky, dtype, u, v, w, shapelets=False)
        assert np.abs(other - want).max() > 0.05 * rms


def test_with_shapelets_false_elides_the_basis():
    """The callers' explicit ``with_shapelets=False`` (``parallel.py``,
    ``consensus/admm.py``) keeps its meaning: the pack is there and the
    basis is not traced; a shapelet slot then has no envelope."""
    sky = _sky("three-first-adjacent")
    dsky = rp.sky_to_device(sky, jnp.float64)
    u, v, w = _uvw(jnp.float64)
    fn = jax.jit(lambda d, *a: rp.coherencies(
        d, *a, jnp.asarray(FREQS), FDELTA, per_channel_flux=True,
        with_shapelets=False))
    assert not [d for d in _tensor_dims(fn.lower(dsky, u, v, w).as_text())
                if B in d and 7 in d]
    want = _dense(sky, jnp.float64, u, v, w, shapelets=False)
    got = np.asarray(fn(dsky, u, v, w))
    assert np.abs(got - want).max() < 1e-10 * np.abs(want).max()


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_shapelet_slots_counts_what_the_program_evaluates(layout):
    """``pipeline.source_kinds``' ``shapelet_slots`` (the ``tile``
    records' field, ``shapelet_slots.ext`` in the benchmark) is the
    pack's size, ``M x S_sh``: dead slots and clusters without a shapelet
    count as what the widest cluster makes of them."""
    sky = _sky(layout)
    live = [[s for s in slots if s >= 0] for slots in LAYOUTS[layout]]
    kinds = pipeline.source_kinds(sky)
    assert kinds["shapelet_slots"] == len(live) * max(map(len, live))
    assert kinds["shapelet_slots"] == rp.sky_to_device(
        sky).shapelets.slot.size
    assert kinds["sources_shapelet"] == sum(map(len, live))
