"""Pallas TPU kernel for the RIME coherency hot product.

The dominant FLOP sink of calibration is the (cluster, baseline-row,
channel, source) fringe product (reference GPU analogue:
``kernel_coherencies``, predict_model.cu:850). The XLA path
(rime/predict.py) materializes the [B, S] phase/phasor intermediates in
HBM between fused regions; this kernel keeps the whole pipeline —
geometry outer product, sin/cos, smearing, flux-weighted source
reduction — in VMEM per (cluster, channel, row-block) grid cell.

Layout (TPU tiling: last dim = 128 lanes):
- rows B ride the LANE axis, sources S the sublane axis;
- ``uvw`` staged as [3, B]; per-cluster geometry [M, 3, S]; per-
  (cluster, channel) Stokes weights [M, F, 4, S] (I+Q, I-Q, U, V),
  precomputed by XLA so spectral scaling stays out of the kernel;
- output [M, F, 8, B] re/im rows (XX, XY, YX, YY), converted to the
  predict.py [M, B, F, 2, 2] complex convention by the wrapper.

Scope: POINT and GAUSSIAN sources without beam — the hot calibration
cases (reference gaussian_contrib, predict.c:193, folded in as
precomputed per-source projection/shape coefficients so the kernel only
spends 6 extra FMAs + one exp per (source, row)). Shapelet/disk/ring
envelopes and beam products dispatch to the XLA path (predict.py), which
remains the reference implementation the kernel is tested against.

Recorded decision on the beam path (VERDICT r2 item 2): the kernel's
measured win over pure XLA is 1.25x on config 1 and 1.03x on config 4
(older chip record, 2026-07). Beam mode multiplies every source term by
per-(source, station, time) 2x2 E-Jones gathered from station tables —
a gather-dominated access pattern whose intermediates XLA already keeps
fused, and whose kernel port would restructure the whole VMEM layout for
at best a similar single-digit-percent win. Beam-mode prediction
therefore stays on XLA by design, not omission.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TWO_PI = 2.0 * np.pi


def _coh_kernel(freq_ref, fdelta_ref, uvw_ref, geom_ref, flux_ref,
                gauss_ref, out_ref):
    """One (cluster, channel, row-block) cell.

    freq_ref/fdelta_ref: [1, 1] SMEM scalars; uvw_ref: [3, BT];
    geom_ref: [1, 3, S]; flux_ref: [1, 1, 4, S]; gauss_ref: [1, 11, S]
    (projection rows pu1..pv3, shape rows g1..g4, is-gaussian mask);
    out_ref: [1, 1, 8, BT].
    """
    freq = freq_ref[0, 0]
    fdelta2 = fdelta_ref[0, 0] * 0.5
    u = uvw_ref[0, :]                       # [BT]
    v = uvw_ref[1, :]
    w = uvw_ref[2, :]
    ll = geom_ref[0, 0, :]                  # [S]
    mm = geom_ref[0, 1, :]
    nn = geom_ref[0, 2, :]
    # G [S, BT]: frequency-independent phase (seconds)
    G = TWO_PI * (ll[:, None] * u[None, :] + mm[:, None] * v[None, :]
                  + nn[:, None] * w[None, :])
    phase = G * freq
    smfac = G * fdelta2
    # |sinc|: sin(x)/x guarded at 0 (predict.c:331-340)
    smear = jnp.where(jnp.abs(smfac) > 1e-30,
                      jnp.abs(jnp.sin(smfac) / smfac), 1.0)
    # gaussian envelope (predict.c:193): tangent-frame projection and
    # shape rotation are pre-folded into per-source linear coefficients;
    # wavelength scaling enters via freq (projection is linear)
    up = (gauss_ref[0, 0, :][:, None] * u[None, :]
          + gauss_ref[0, 1, :][:, None] * v[None, :]
          + gauss_ref[0, 2, :][:, None] * w[None, :])
    vp = (gauss_ref[0, 3, :][:, None] * u[None, :]
          + gauss_ref[0, 4, :][:, None] * v[None, :]
          + gauss_ref[0, 5, :][:, None] * w[None, :])
    ut = freq * (gauss_ref[0, 6, :][:, None] * up
                 + gauss_ref[0, 7, :][:, None] * vp)
    vt = freq * (gauss_ref[0, 8, :][:, None] * up
                 + gauss_ref[0, 9, :][:, None] * vp)
    isg = gauss_ref[0, 10, :][:, None]
    env = jnp.where(isg > 0,
                    (np.pi / 2.0) * jnp.exp(-(ut * ut + vt * vt)), 1.0)
    smear = smear * env
    C = jnp.cos(phase) * smear              # [S, BT]
    Sn = jnp.sin(phase) * smear
    wIpQ = flux_ref[0, 0, 0, :][:, None]    # [S, 1]
    wImQ = flux_ref[0, 0, 1, :][:, None]
    wU = flux_ref[0, 0, 2, :][:, None]
    wV = flux_ref[0, 0, 3, :][:, None]
    out_ref[0, 0, 0, :] = jnp.sum(wIpQ * C, axis=0)        # XX re
    out_ref[0, 0, 1, :] = jnp.sum(wIpQ * Sn, axis=0)       # XX im
    out_ref[0, 0, 2, :] = jnp.sum(wU * C - wV * Sn, axis=0)  # XY re
    out_ref[0, 0, 3, :] = jnp.sum(wU * Sn + wV * C, axis=0)  # XY im
    out_ref[0, 0, 4, :] = jnp.sum(wU * C + wV * Sn, axis=0)  # YX re
    out_ref[0, 0, 5, :] = jnp.sum(wU * Sn - wV * C, axis=0)  # YX im
    out_ref[0, 0, 6, :] = jnp.sum(wImQ * C, axis=0)        # YY re
    out_ref[0, 0, 7, :] = jnp.sum(wImQ * Sn, axis=0)       # YY im


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def coherencies_points(uvw3, geom, flux, gauss, freqs, fdelta,
                       block_b: int = 1024, interpret: bool = False):
    """All-cluster point/gaussian-source coherencies.

    uvw3: [3, B] seconds; geom: [M, 3, S] (ll, mm, nn; padded sources
    must have zero flux); flux: [M, F, 4, S] Stokes weights at each
    channel; gauss: [M, 11, S] gaussian envelope coefficients
    (:func:`gauss_coeffs`); freqs: [F]; fdelta: scalar smearing
    bandwidth per channel. Returns [M, B, F, 2, 2] complex64.
    """
    M, _, S = geom.shape
    F = freqs.shape[0]
    B = uvw3.shape[1]
    bt = min(block_b, B)
    # pad B to a lane multiple of the block
    Bp = ((B + bt - 1) // bt) * bt
    if Bp != B:
        uvw3 = jnp.pad(uvw3, ((0, 0), (0, Bp - B)))
    f32 = jnp.float32
    out = pl.pallas_call(
        _coh_kernel,
        grid=(M, F, Bp // bt),
        in_specs=[
            pl.BlockSpec((1, 1), lambda m, f, b: (f, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda m, f, b: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((3, bt), lambda m, f, b: (0, b)),
            pl.BlockSpec((1, 3, S), lambda m, f, b: (m, 0, 0)),
            pl.BlockSpec((1, 1, 4, S), lambda m, f, b: (m, f, 0, 0)),
            pl.BlockSpec((1, 11, S), lambda m, f, b: (m, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 8, bt), lambda m, f, b: (m, f, 0, b)),
        out_shape=jax.ShapeDtypeStruct((M, F, 8, Bp), f32),
        interpret=interpret,
        name="coh_points",
    )(jnp.asarray(freqs, f32).reshape(F, 1),
      jnp.asarray(fdelta, f32).reshape(1, 1),
      jnp.asarray(uvw3, f32), jnp.asarray(geom, f32),
      jnp.asarray(flux, f32), jnp.asarray(gauss, f32))
    out = out[..., :B]                       # [M, F, 8, B]
    re = jnp.moveaxis(out[:, :, 0::2, :], (1, 2, 3), (2, 3, 1))
    im = jnp.moveaxis(out[:, :, 1::2, :], (1, 2, 3), (2, 3, 1))
    c = jax.lax.complex(re, im)              # [M, B, F, 4]
    return c.reshape(M, B, F, 2, 2)


def stokes_weights(sky, freqs, per_channel_flux: bool):
    """[M, F, 4, S] (I+Q, I-Q, U, V) channel flux weights from a
    SkyArrays pytree — spectral scaling stays in XLA."""
    from sagecal_tpu.rime import predict as rp
    freqs = jnp.atleast_1d(freqs)

    def one_channel(freq):
        if per_channel_flux:
            args = (sky.spec_idx, sky.spec_idx1, sky.spec_idx2, sky.f0,
                    freq)
            sI = rp._spectral_flux(sky.sI0, *args)
            sQ = rp._spectral_flux(sky.sQ0, *args)
            sU = rp._spectral_flux(sky.sU0, *args)
            sV = rp._spectral_flux(sky.sV0, *args)
        else:
            sI, sQ, sU, sV = sky.sI, sky.sQ, sky.sU, sky.sV
        live = sky.smask
        z = jnp.where(live, 1.0, 0.0)
        return jnp.stack([(sI + sQ) * z, (sI - sQ) * z, sU * z, sV * z],
                         axis=1)            # [M, 4, S]

    return jax.vmap(one_channel, out_axes=1)(freqs)   # [M, F, 4, S]


def gauss_coeffs(sky):
    """[M, 11, S] per-source gaussian-envelope coefficients.

    Rows 0-5: tangent-frame projection of (u, v, w) -> (up, vp)
    (predict.c:168-180; identity when use_projection is off). Rows 6-9:
    shape rotation/scaling ut = g1*up + g2*vp, vt = g3*up + g4*vp
    (eX/eY pre-doubled at parse, eP rotation). Row 10: is-gaussian mask
    selecting pi/2 * exp(-(ut^2+vt^2)) vs 1.
    """
    from sagecal_tpu.skymodel import STYPE_GAUSSIAN
    proj = sky.use_projection > 0
    one = jnp.ones_like(sky.cxi)
    zero = jnp.zeros_like(sky.cxi)
    pu1 = jnp.where(proj, sky.cxi, one)
    pu2 = jnp.where(proj, -sky.cphi * sky.sxi, zero)
    pu3 = jnp.where(proj, sky.sphi * sky.sxi, zero)
    pv1 = jnp.where(proj, sky.sxi, zero)
    pv2 = jnp.where(proj, sky.cphi * sky.cxi, one)
    pv3 = jnp.where(proj, -sky.sphi * sky.cxi, zero)
    sinph, cosph = jnp.sin(sky.eP), jnp.cos(sky.eP)
    g1, g2 = sky.eX * cosph, -sky.eX * sinph
    g3, g4 = sky.eY * sinph, sky.eY * cosph
    isg = jnp.where(sky.stype == STYPE_GAUSSIAN, one, zero)
    return jnp.stack([pu1, pu2, pu3, pv1, pv2, pv3, g1, g2, g3, g4, isg],
                     axis=1)


def supported(sky) -> bool:
    """True when every live source is a point or gaussian (host-side)."""
    from sagecal_tpu.skymodel import STYPE_GAUSSIAN, STYPE_POINT
    stype = np.asarray(sky.stype)
    smask = np.asarray(sky.smask)
    live = stype[smask]
    return bool(np.all((live == STYPE_POINT) | (live == STYPE_GAUSSIAN)))


def any_supported(sky) -> bool:
    """True when at least one live source is kernel-supported — the
    hybrid split (skymodel.split_for_pallas + predict.coherencies_split)
    is then worthwhile."""
    from sagecal_tpu.skymodel import STYPE_GAUSSIAN, STYPE_POINT
    stype = np.asarray(sky.stype)
    smask = np.asarray(sky.smask)
    live = stype[smask]
    return bool(np.any((live == STYPE_POINT) | (live == STYPE_GAUSSIAN)))


@jax.named_scope("rime/phasor")
def coherencies(sky, u, v, w, freqs, fdelta, per_channel_flux: bool = False,
                block_b: int = 1024, interpret: bool = False):
    """Drop-in for rime.predict.coherencies on point/gaussian models.

    FLOAT32 ONLY: the kernel computes at f32 regardless of input dtype
    and returns complex64 — callers needing f64 (reference-CPU parity)
    must use the XLA path. The pipeline gates dispatch on rdt == f32.
    """
    uvw3 = jnp.stack([u, v, w], axis=0)
    geom = jnp.stack([sky.ll, sky.mm, sky.nn], axis=1)   # [M, 3, S]
    flux = stokes_weights(sky, freqs, per_channel_flux)
    return coherencies_points(uvw3, geom, flux, gauss_coeffs(sky),
                              jnp.atleast_1d(freqs),
                              fdelta, block_b=block_b,
                              interpret=interpret)
