"""Extended-source visibility envelopes, vectorized for TPU.

Capability parity with reference ``src/lib/Radio/predict.c``
(``gaussian_contrib``:193, ``ring_contrib``:222, ``disk_contrib``:237,
``shapelet_contrib``:142 with Hermite recursion ``H_e``:31) — re-designed as
masked array ops over a [..., S] source grid instead of per-source function
pointers, so one fused XLA computation evaluates every morphology; the
shapelet basis alone is evaluated on a compact pack of the model's shapelet
sources, [S_sh, B], and placed into the grid.

All inputs are in wavelengths (u·f/c etc. — callers pass u_sec * freq).
Padded sources must carry eX=eY=0; every division here is guarded so padded
lanes produce finite garbage that gets masked by zero flux downstream.
"""

from __future__ import annotations

import jax
import numpy as np
import jax.numpy as jnp

from sagecal_tpu.skymodel import STYPE_DISK, STYPE_GAUSSIAN, STYPE_RING


def _project_uv(u, v, w, cxi, sxi, cphi, sphi, use_projection, negate):
    """Rotate (u,v,w) into the source-local tangent frame.

    Reference predict.c:168-180 (gaussian) / :152-158 (shapelet, negated
    variant). Disk/ring always project (predict.c:224-245); gaussian and
    shapelet only when the source sits far from the phase center
    (use_projection flag, readsky.c:420-424).
    """
    up = u * cxi - v * cphi * sxi + w * sphi * sxi
    vp = u * sxi + v * cphi * cxi - w * sphi * cxi
    if negate:
        # shapelet variant negates the projected frame only (predict.c:152-158);
        # the unprojected branch stays (u, v)
        up, vp = -up, -vp
    up = jnp.where(use_projection, up, u)
    vp = jnp.where(use_projection, vp, v)
    return up, vp


def gaussian(u, v, w, eX, eY, eP, cxi, sxi, cphi, sphi, use_projection):
    """predict.c:193 — pi/2 * exp(-(ut^2+vt^2)), axes pre-doubled at parse."""
    up, vp = _project_uv(u, v, w, cxi, sxi, cphi, sphi, use_projection,
                         negate=False)
    sinph, cosph = jnp.sin(eP), jnp.cos(eP)
    ut = eX * (cosph * up - sinph * vp)
    vt = eY * (sinph * up + cosph * vp)
    return (jnp.pi / 2.0) * jnp.exp(-(ut * ut + vt * vt))


def _bessel_j0(x):
    """Abramowitz & Stegun 9.4.1/9.4.3 rational approximations (|err|<1e-7)."""
    ax = jnp.abs(x)
    # small |x|
    y = x * x
    p_small = (57568490574.0 + y * (-13362590354.0 + y * (651619640.7
               + y * (-11214424.18 + y * (77392.33017 + y * (-184.9052456))))))
    q_small = (57568490411.0 + y * (1029532985.0 + y * (9494680.718
               + y * (59272.64853 + y * (267.8532712 + y)))))
    small = p_small / q_small
    # large |x|
    z = 8.0 / jnp.maximum(ax, 1e-30)
    y2 = z * z
    xx = ax - 0.785398164
    p1 = (1.0 + y2 * (-0.1098628627e-2 + y2 * (0.2734510407e-4
          + y2 * (-0.2073370639e-5 + y2 * 0.2093887211e-6))))
    p2 = (-0.1562499995e-1 + y2 * (0.1430488765e-3 + y2 * (-0.6911147651e-5
          + y2 * (0.7621095161e-6 + y2 * (-0.934935152e-7)))))
    large = jnp.sqrt(0.636619772 / jnp.maximum(ax, 1e-30)) * (
        jnp.cos(xx) * p1 - z * jnp.sin(xx) * p2)
    return jnp.where(ax < 8.0, small, large)


def _bessel_j1(x):
    """Abramowitz & Stegun 9.4.4/9.4.6 rational approximations."""
    ax = jnp.abs(x)
    y = x * x
    p_small = x * (72362614232.0 + y * (-7895059235.0 + y * (242396853.1
              + y * (-2972611.439 + y * (15704.48260 + y * (-30.16036606))))))
    q_small = (144725228442.0 + y * (2300535178.0 + y * (18583304.74
              + y * (99447.43394 + y * (376.9991397 + y)))))
    small = p_small / q_small
    z = 8.0 / jnp.maximum(ax, 1e-30)
    y2 = z * z
    xx = ax - 2.356194491
    p1 = (1.0 + y2 * (0.183105e-2 + y2 * (-0.3516396496e-4
          + y2 * (0.2457520174e-5 + y2 * (-0.240337019e-6)))))
    p2 = (0.04687499995 + y2 * (-0.2002690873e-3 + y2 * (0.8449199096e-5
          + y2 * (-0.88228987e-6 + y2 * 0.105787412e-6))))
    large = jnp.sqrt(0.636619772 / jnp.maximum(ax, 1e-30)) * (
        jnp.cos(xx) * p1 - z * jnp.sin(xx) * p2) * jnp.sign(x)
    return jnp.where(ax < 8.0, small, large)


def ring(u, v, w, eX, cxi, sxi, cphi, sphi):
    """predict.c:222 — J0(2*pi*|uv_projected|*eX); always projected."""
    up = u * cxi - v * cphi * sxi + w * sphi * sxi
    vp = u * sxi + v * cphi * cxi - w * sphi * cxi
    b = jnp.sqrt(up * up + vp * vp) * eX * 2.0 * jnp.pi
    return _bessel_j0(b)


def disk(u, v, w, eX, cxi, sxi, cphi, sphi):
    """predict.c:237 — J1(2*pi*|uv_projected|*eX); always projected."""
    up = u * cxi - v * cphi * sxi + w * sphi * sxi
    vp = u * sxi + v * cphi * cxi - w * sphi * cxi
    b = jnp.sqrt(up * up + vp * vp) * eX * 2.0 * jnp.pi
    return _bessel_j1(b)


def _hermite_basis(x, n0max: int):
    """Shapelet 1-D basis B_n(x) = H_n(x) exp(-x^2/2)/sqrt(2^(n+1) n!).

    Same normalization as predict.c:86-92 (note its sqrt(2<<n * n!) =
    sqrt(2^(n+1) n!)). Returns [n0max, *x.shape], the mode index on the
    LEADING axis, so that ``x``'s own minor axis (the rows) stays the
    minor axis of every array made here. Physicists' Hermite recursion
    unrolled at trace time (n0max is static).
    """
    hs = [jnp.ones_like(x)]
    if n0max > 1:
        hs.append(2.0 * x)
    for n in range(2, n0max):
        hs.append(2.0 * x * hs[n - 1] - 2.0 * (n - 1) * hs[n - 2])
    fact = 1.0
    norms = []
    for n in range(n0max):
        if n > 0:
            fact *= n
        norms.append(1.0 / np.sqrt(float(2 ** (n + 1)) * fact))
    expv = jnp.exp(-0.5 * x * x)
    return jnp.stack([h * (expv * nrm) for h, nrm in zip(hs, norms)], axis=0)


def shapelet_sign_tables(n0max: int):
    """(sign, is_imag) [n0max, n0max] numpy tables for mode (n1, n2).

    Mode parity: i^(n1+n2) folded into a real/imag split with sign
    (predict.c:110-121).
    """
    n1 = np.arange(n0max)[:, None]
    n2 = np.arange(n0max)[None, :]
    tot = n1 + n2
    is_imag = (tot % 2).astype(np.float64)
    sign = np.where(is_imag == 0,
                    np.where(((tot // 2) % 2) == 0, 1.0, -1.0),
                    np.where((((tot - 1) // 2) % 2) == 0, 1.0, -1.0))
    return sign, is_imag


# a scope of its own below ``rime/phasor`` (PERF.md section 3; metadata
# only): the trace reads the basis apart as ``rime/phasor/shapelet``.  The
# other envelopes fuse into the source sum.
@jax.named_scope("shapelet")
def shapelet(u, v, w, eX, eY, eP, beta, modes, cxi, sxi, cphi, sphi,
             use_projection):
    """predict.c:142 — complex envelope 2*pi*(Re + i*Im)*a*b.

    ``modes`` is [n0max, n0max, ...]: a source's coefficients ``c[n2,
    n1]`` on two LEADING axes, zero beyond its own ``n0`` (so no explicit
    mask is needed), the rest broadcasting like the source's other
    parameters.  The caller lays the sources on a leading axis and the
    rows on the minor one (u, v, w [1, B], a source's parameters [S_sh,
    1]): the result is [S_sh, B], and no array made here has the modes or
    the sources on the minor axis with the rows above them.
    Evaluates the Fourier-domain Hermite basis at (-ut, vt) as the reference
    does (it decomposes f(-l, m)).
    """
    n0max = modes.shape[0]
    up, vp = _project_uv(u, v, w, cxi, sxi, cphi, sphi, use_projection,
                         negate=True)
    a = 1.0 / jnp.where(eX != 0, eX, 1.0)
    b = 1.0 / jnp.where(eY != 0, eY, 1.0)
    sinph, cosph = jnp.sin(eP), jnp.cos(eP)
    ut = a * (cosph * up - sinph * vp)
    vt = b * (sinph * up + cosph * vp)

    bu = _hermite_basis(-ut * beta, n0max)          # [n0max, ...] (n1 axis)
    bv = _hermite_basis(vt * beta, n0max)           # [n0max, ...] (n2 axis)
    sign, is_imag = shapelet_sign_tables(n0max)
    # sum_n2 bv[n2] * sum_n1 (sign * modes)[n2, n1] * bu[n1], the real and
    # the imaginary parity of n1 + n2 apart: multiply-adds on [S_sh, B]
    sums = [jnp.zeros_like(ut), jnp.zeros_like(ut)]
    for n2 in range(n0max):
        inner = [0.0, 0.0]
        for n1 in range(n0max):
            part = int(is_imag[n1, n2])
            inner[part] = inner[part] + (float(sign[n1, n2])
                                         * modes[n2, n1]) * bu[n1]
        sums = [s + bv[n2] * i for s, i in zip(sums, inner)]
    return 2.0 * jnp.pi * (sums[0] + 1j * sums[1]) * a * b


def apply_envelopes(phasor, stype, u, v, w, eX, eY, eP, cxi, sxi, cphi, sphi,
                    use_projection, shapelets=None):
    """Multiply a per-source phasor by its morphology envelope.

    ``phasor`` [B, S]; the source params [1, S]; u, v, w [B, 1] in
    wavelengths.  ``shapelets`` is the cluster's row of the model's
    compact pack of shapelet sources (``rime/predict.ShapeletPack``:
    [S_sh] parameters, [n0max, n0max, S_sh] modes, ``slot`` the source
    slot of each, -1 where the pack is padding), or None where the model
    has none or the caller elides them: the basis is evaluated for the
    pack alone, rows on the minor axis, and its envelope placed into the
    phasors of its slots.
    """
    env = jnp.ones_like(phasor)
    env = jnp.where(stype == STYPE_GAUSSIAN,
                    gaussian(u, v, w, eX, eY, eP, cxi, sxi, cphi, sphi,
                             use_projection).astype(env.dtype), env)
    env = jnp.where(stype == STYPE_RING,
                    ring(u, v, w, eX, cxi, sxi, cphi, sphi).astype(env.dtype),
                    env)
    env = jnp.where(stype == STYPE_DISK,
                    disk(u, v, w, eX, cxi, sxi, cphi, sphi).astype(env.dtype),
                    env)
    if shapelets is not None:
        p = shapelets
        col = lambda a: a[:, None]
        sh = shapelet(u.T, v.T, w.T, col(p.eX), col(p.eY), col(p.eP),
                      col(p.beta), p.modes[..., None], col(p.cxi),
                      col(p.sxi), col(p.cphi), col(p.sphi),
                      col(p.use_projection)).astype(env.dtype)   # [S_sh, B]
        slots = jnp.arange(phasor.shape[-1])
        for k in range(p.slot.shape[0]):     # a padding's slot is -1: nowhere
            env = jnp.where(slots == p.slot[k], sh[k][:, None], env)
    return phasor * env
