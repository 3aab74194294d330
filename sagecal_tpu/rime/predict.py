"""Visibility prediction (the RIME) in JAX.

Capability parity with reference ``src/lib/Radio``:
- ``precalculate_coherencies`` predict.c:653 / ``_multifreq`` predict.c:890
- ``predict_visibilities`` predict.c:417
- model prediction with solutions + residual subtraction residual.c:930,1242
- GPU variant predict_model.cu:850 (``kernel_coherencies``)

Re-architected TPU-first: instead of a pthread pool over baseline ranges
calling per-source scalar functions, the whole (cluster, baseline, channel,
source) product is one vectorized masked computation. Clusters are mapped
with ``lax.map`` (peak memory [S, B] per cluster) and everything inside
fuses into a handful of XLA kernels on the VPU; the sum over a cluster's
sources is ONE contraction of eight real weight rows with the phasors'
real and imaginary planes. The Jones sandwich of
the model that leaves the solver (:func:`predict_model`) is real
elementwise arithmetic on planes (``rime/planes.py``), the source sum's
four correlations handed to it as eight real planes a cluster.

Conventions (identical to reference):
- u,v,w in SECONDS (meters/c); multiply by frequency for wavelengths.
- fringe phase 2*pi*(u l + v m + w n) * f with n carrying the -1.
- channel smearing |sinc(G * fdelta/2)|; time smearing exists in the
  reference only as dead code (residual.c:429) and is likewise omitted.
- coherencies (solve path) use fluxes pre-scaled to the data reference
  frequency; the per-channel model (residual path) rescales from catalog
  values per channel (residual.c:453-478).
- Stokes -> correlations: [[I+Q, U+iV], [U-iV, I-Q]] (predict.c:385-390).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from sagecal_tpu.rime import envelopes, planes as pl
from sagecal_tpu.skymodel import ClusterSky, STYPE_SHAPELET


class ShapeletPack(NamedTuple):
    """The model's shapelet sources alone, packed compactly: [M, S_sh]
    arrays, ``S_sh`` the most live shapelet sources (``sh_n0`` > 0) any
    cluster holds, 0 where the model has none.  ``slot`` is the source
    slot a packed source came from, -1 where a cluster holds fewer than
    ``S_sh`` (its other values are then harmless padding); ``modes`` is
    [M, n0max, n0max, S_sh], a source's ``c[n2, n1]`` zero beyond its own
    order.  What ``envelopes.shapelet`` needs and no more: the basis is
    evaluated for these slots only, and ``S_sh`` is read from a shape, so
    a sky that enters a program as an argument says it too."""

    slot: jax.Array
    eX: jax.Array
    eY: jax.Array
    eP: jax.Array
    cxi: jax.Array
    sxi: jax.Array
    cphi: jax.Array
    sphi: jax.Array
    use_projection: jax.Array
    beta: jax.Array
    modes: jax.Array


class SkyArrays(NamedTuple):
    """Device-resident padded sky model (pytree of [M, Smax] arrays, and
    the compact pack of its shapelet sources)."""

    ll: jax.Array
    mm: jax.Array
    nn: jax.Array
    ra: jax.Array
    dec: jax.Array
    sI: jax.Array
    sQ: jax.Array
    sU: jax.Array
    sV: jax.Array
    sI0: jax.Array
    sQ0: jax.Array
    sU0: jax.Array
    sV0: jax.Array
    spec_idx: jax.Array
    spec_idx1: jax.Array
    spec_idx2: jax.Array
    f0: jax.Array
    stype: jax.Array
    eX: jax.Array
    eY: jax.Array
    eP: jax.Array
    cxi: jax.Array
    sxi: jax.Array
    cphi: jax.Array
    sphi: jax.Array
    use_projection: jax.Array
    shapelets: ShapeletPack
    smask: jax.Array


def shapelet_slots(sky: ClusterSky) -> np.ndarray:
    """[M, S_sh] int32: per cluster the slots of its live shapelet sources
    in their order, -1 where it has fewer than the cluster with the most.
    The slots the source sum evaluates the shapelet basis for."""
    is_sh = (np.asarray(sky.smask, bool)
             & (np.asarray(sky.stype) == STYPE_SHAPELET)
             & (np.asarray(sky.sh_n0) > 0))
    slot = np.full((len(is_sh), int(is_sh.sum(axis=1).max(initial=0))), -1,
                   np.int32)
    for m, row in enumerate(is_sh):
        idx = np.flatnonzero(row)
        slot[m, :len(idx)] = idx
    return slot


def _pack_shapelets(sky: ClusterSky, f) -> ShapeletPack:
    slot = shapelet_slots(sky)
    live, at = slot >= 0, np.maximum(slot, 0)
    take = lambda a, fill: np.where(
        live, np.take_along_axis(np.asarray(a), at, 1), fill)
    n0max = int(np.sqrt(sky.sh_modes.shape[-1]).round())
    modes = np.where(live[:, :, None],
                     np.take_along_axis(sky.sh_modes, at[:, :, None], 1), 0.0)
    return ShapeletPack(
        slot=jnp.asarray(slot),
        eX=f(take(sky.eX, 1.0)), eY=f(take(sky.eY, 1.0)),
        eP=f(take(sky.eP, 0.0)),
        cxi=f(take(sky.cxi, 1.0)), sxi=f(take(sky.sxi, 0.0)),
        cphi=f(take(sky.cphi, 1.0)), sphi=f(take(sky.sphi, 0.0)),
        use_projection=jnp.asarray(take(sky.use_projection, False), bool),
        beta=f(take(sky.sh_beta, 1.0)),
        # [M, S_sh, n2 * n1] -> [M, n2, n1, S_sh]
        modes=f(np.moveaxis(
            modes.reshape(slot.shape + (n0max, n0max)), 1, -1)))


def sky_to_device(sky: ClusterSky, real_dtype=jnp.float32) -> SkyArrays:
    f = lambda a: jnp.asarray(a, real_dtype)
    return SkyArrays(
        ll=f(sky.ll), mm=f(sky.mm), nn=f(sky.nn),
        ra=f(sky.ra), dec=f(sky.dec),
        sI=f(sky.sI), sQ=f(sky.sQ), sU=f(sky.sU), sV=f(sky.sV),
        sI0=f(sky.sI0), sQ0=f(sky.sQ0), sU0=f(sky.sU0), sV0=f(sky.sV0),
        spec_idx=f(sky.spec_idx), spec_idx1=f(sky.spec_idx1),
        spec_idx2=f(sky.spec_idx2), f0=f(sky.f0),
        stype=jnp.asarray(sky.stype, jnp.int32),
        eX=f(sky.eX), eY=f(sky.eY), eP=f(sky.eP),
        cxi=f(sky.cxi), sxi=f(sky.sxi), cphi=f(sky.cphi), sphi=f(sky.sphi),
        use_projection=jnp.asarray(sky.use_projection, bool),
        shapelets=_pack_shapelets(sky, f),
        smask=jnp.asarray(sky.smask, bool),
    )


def _spectral_flux(s0, spec_idx, spec_idx1, spec_idx2, f0, freq):
    """Catalog flux -> flux at ``freq`` (residual.c:453-478 semantics:
    scaling applies only where spec_idx != 0; sign passes through)."""
    fr = jnp.log(freq / f0)
    tempfr = spec_idx * fr + spec_idx1 * fr * fr + spec_idx2 * fr ** 3
    mag = jnp.exp(jnp.log(jnp.maximum(jnp.abs(s0), 1e-300)) + tempfr)
    scaled = jnp.where(s0 == 0.0, 0.0, jnp.sign(s0) * mag)
    return jnp.where(spec_idx != 0.0, scaled, s0)


# Names the profiler trace can find (PERF.md section 3; metadata only,
# the compiled programs are the same): ``rime/phasor`` is the source
# sum of one cluster (fringe phase, cos/sin, smearing, envelopes,
# flux), ``rime/corrupt`` the Jones sandwich J_p C J_q^H.
@jax.named_scope("rime/phasor")
def _cluster_coherency(csky, u, v, w, freqs, fdelta, per_channel_flux: bool,
                       with_shapelets: bool,
                       af=None, E=None, tslot=None, sta1=None, sta2=None,
                       planes: bool = False):
    """Coherencies of ONE cluster: [B, F, 2, 2] complex, or with
    ``planes`` the same numbers as eight real planes [8, F, B]
    (``rime/planes.py``: the source sum's four correlations, real and
    imaginary parts, as they come out of the sum; the complex form is
    made from them).

    ``csky`` is a SkyArrays row (arrays [S]); u,v,w [B] seconds; freqs [F].
    Beam (predict_withbeam.c:139-187): ``af`` [F, S, T, N] array-factor
    gains multiply each source's amplitude by af_p*af_q; ``E`` [S, T, N,
    2, 2] element E-Jones sandwich each source's brightness E_p B E_q^H.
    ``tslot``/``sta1``/``sta2`` [B] map data rows to (time, antennas).
    """
    cdtype = jnp.complex64 if u.dtype == jnp.float32 else jnp.complex128
    # G [B, S]: frequency-independent phase term (seconds)
    G = 2.0 * jnp.pi * (u[:, None] * csky.ll[None, :]
                        + v[:, None] * csky.mm[None, :]
                        + w[:, None] * csky.nn[None, :])
    if E is not None:
        Et = jnp.moveaxis(E, (0, 1, 2), (2, 0, 1))      # [T, N, S, 2, 2]
        E1 = Et[tslot, sta1]                            # [B, S, 2, 2]
        E2 = Et[tslot, sta2]

    def one_channel(freq, af_f=None):
        # f32 fringe phases match the reference's float GPU predict path
        # (predict_model.cu); pass f64 u,v,w for reference-CPU precision.
        phase = G * freq
        phasor = jax.lax.complex(jnp.cos(phase), jnp.sin(phase)).astype(cdtype)
        smfac = G * (fdelta * 0.5)
        smear = jnp.where(jnp.abs(G) > 0,
                          jnp.abs(jnp.sinc(smfac / jnp.pi)), 1.0)
        phasor = phasor * smear.astype(cdtype)
        # wavelengths for envelopes
        ul, vl, wl = u[:, None] * freq, v[:, None] * freq, w[:, None] * freq
        phasor = envelopes.apply_envelopes(
            phasor, csky.stype[None, :], ul, vl, wl,
            csky.eX[None, :], csky.eY[None, :], csky.eP[None, :],
            csky.cxi[None, :], csky.sxi[None, :], csky.cphi[None, :],
            csky.sphi[None, :], csky.use_projection[None, :],
            csky.shapelets if with_shapelets else None)
        if af_f is not None:
            aft = jnp.moveaxis(af_f, 0, -1)             # [T, N, S]
            phasor = phasor * (aft[tslot, sta1]
                               * aft[tslot, sta2]).astype(cdtype)
        if per_channel_flux:
            sI = _spectral_flux(csky.sI0, csky.spec_idx, csky.spec_idx1,
                                csky.spec_idx2, csky.f0, freq)
            sQ = _spectral_flux(csky.sQ0, csky.spec_idx, csky.spec_idx1,
                                csky.spec_idx2, csky.f0, freq)
            sU = _spectral_flux(csky.sU0, csky.spec_idx, csky.spec_idx1,
                                csky.spec_idx2, csky.f0, freq)
            sV = _spectral_flux(csky.sV0, csky.spec_idx, csky.spec_idx1,
                                csky.spec_idx2, csky.f0, freq)
        else:
            sI, sQ, sU, sV = csky.sI, csky.sQ, csky.sU, csky.sV
        live = csky.smask
        phasor = jnp.where(live[None, :], phasor, 0.0)
        b00 = (sI + sQ).astype(cdtype)
        b01 = (sU + 1j * sV).astype(cdtype)
        b10 = (sU - 1j * sV).astype(cdtype)
        b11 = (sI - sQ).astype(cdtype)
        if E is None:
            # the sum over the sources is a contraction, and is handed to
            # the compiler as ONE: eight real weight rows against (Re, Im)
            # of the phasors.  The TPU compiler then makes cos, sin and
            # sinc once a pair on register tiles of rows x sources; four
            # ``jnp.sum(phasor * b, axis=1)`` it compiles with ONE row to
            # a register tile, an eighth of the vector unit at work
            # (tests/test_chip_compile.py holds it; PERF.md section 6).
            b = jnp.stack([b00, b01, b10, b11])              # [4, S]
            wts = jnp.stack([b.real, -b.imag, b.imag, b.real], 1)
            p8 = jnp.einsum(
                "kcs,cbs->kb", wts.reshape(8, 2, -1),        # [8, (Re,Im), S]
                jnp.stack([phasor.real, phasor.imag]),
                precision="highest")                         # [8, B]
            return p8 if planes else pl.jones_r2c(p8.T)      # [B, 2, 2]
        # element beam: per-source 2x2 sandwich, then sum over sources
        Bm = jnp.stack([jnp.stack([b00, b01], -1),
                        jnp.stack([b10, b11], -1)], -2)      # [S, 2, 2]
        Bm = phasor[..., None, None] * Bm[None]              # [B, S, 2, 2]
        out = jnp.einsum("bsij,bsjk,bslk->bil", E1, Bm, jnp.conj(E2))
        return pl.jones_c2r(out).T if planes else out

    if af is None:
        out = jax.vmap(lambda f: one_channel(f), out_axes=1)(freqs)
    else:
        out = jax.vmap(one_channel, out_axes=1)(freqs, af)
    return out  # [B, F, 2, 2], or the planes [8, F, B]


def coherencies(sky: SkyArrays, u, v, w, freqs, fdelta,
                per_channel_flux: bool = False,
                with_shapelets: bool | None = None,
                beam=None, dobeam: int = 0,
                tslot=None, sta1=None, sta2=None, planes: bool = False):
    """All-cluster coherencies [M, B, F, 2, 2] (no Jones applied), or
    with ``planes`` the same numbers as real planes [8, M, F, B], the
    form :func:`predict_model` takes (``rime/planes.py``).

    Equivalent of precalculate_coherencies[_multifreq] (predict.c:653/:890);
    with ``beam`` (a :class:`sagecal_tpu.rime.beam.BeamArrays`) and
    ``dobeam`` != 0 this is precalculate_coherencies[_multifreq]_withbeam
    (predict_withbeam.c:522/:690) — the array-factor tables of all
    clusters are made first (scope ``rime/beam``), the element beam's per
    cluster, and both are folded into the source sum.
    ``fdelta`` is the smearing bandwidth PER CHANNEL (callers pass total
    bandwidth for channel-averaged single-freq solves, total/Nchan for
    multifreq, matching predict.c:943).
    The shapelet basis is traced where the model's pack holds a source
    (``S_sh`` > 0, static: a shape); ``with_shapelets`` False elides it.
    """
    with_shapelets = (with_shapelets is not False
                      and sky.shapelets.slot.shape[-1] > 0)
    af_all = None
    with_beam = beam is not None and bool(dobeam)
    if with_beam:
        from sagecal_tpu.rime import beam as beam_mod
        if dobeam in (beam_mod.DOBEAM_ARRAY, beam_mod.DOBEAM_FULL):
            # every cluster's array-factor table, made BEFORE the map
            # over clusters and under a scope of its own: a scope nested
            # inside ``rime/phasor`` could not be read apart (the
            # profile's readers take the first of these names in an
            # operation's path).  The element beam's [S, T, N, 2, 2]
            # stays a cluster's own, inside the map.
            with jax.named_scope("rime/beam"):
                af_all = jax.lax.map(
                    lambda csky: beam_mod.cluster_beam(
                        beam, csky.ra, csky.dec, jnp.atleast_1d(freqs),
                        beam_mod.DOBEAM_ARRAY)[0],
                    sky)        # [M, F, S, T, N]

    def per_cluster(xs):
        csky, af = xs
        E = None
        if with_beam and dobeam != beam_mod.DOBEAM_ARRAY:
            E = beam_mod.cluster_beam(beam, csky.ra, csky.dec,
                                      jnp.atleast_1d(freqs),
                                      beam_mod.DOBEAM_ELEMENT)[1]
        return _cluster_coherency(csky, u, v, w, freqs, fdelta,
                                  per_channel_flux, with_shapelets,
                                  af=af, E=E, tslot=tslot, sta1=sta1,
                                  sta2=sta2, planes=planes)

    with jax.named_scope("rime/phasor"):    # the map's stacking too
        out = jax.lax.map(per_cluster, (sky, af_all))
        return jnp.moveaxis(out, 1, 0) if planes else out


def coherencies_split(sky_pg, sky_rest, u, v, w, freqs, fdelta,
                      per_channel_flux: bool = False):
    """Hybrid coherencies: Pallas kernel on the point/gaussian half,
    XLA on the compact repacked rest (skymodel.split_for_pallas).

    ``sky_rest`` None means the model is fully kernel-supported. The two
    halves preserve cluster order, so outputs add elementwise.
    """
    from sagecal_tpu.ops import coh_pallas
    out = coh_pallas.coherencies(sky_pg, u, v, w, freqs, fdelta,
                                 per_channel_flux=per_channel_flux)
    if sky_rest is not None:
        out = out + coherencies(sky_rest, u, v, w, freqs, fdelta,
                                per_channel_flux=per_channel_flux)
    return out


def uvcut_flags(flags, u, v, freqs, uvmin, uvmax):
    """Mark baselines outside the uv range with flag=2: still subtracted,
    excluded from the solve (predict.c:876-882, multifreq rule)."""
    freqs = jnp.atleast_1d(freqs)
    uvdist = jnp.sqrt(u * u + v * v) * freqs[0]
    out = (uvdist < uvmin) | (uvdist * freqs[-1] > uvmax * freqs[0])
    return jnp.where((flags == 0) & out, 2, flags)


def apply_uvcut(rowflags, tile, uvmin: float, uvmax: float):
    """Host-side uv-window on a COPY of a tile's row flags (the shared
    gate for every mode: full window -> unchanged input). Returns int8
    [nrows]; callers must never write the result back into the tile
    (the cut is solve-scoped, Data::loadData semantics).

    :func:`uvcut_flags`'s rule, the same operations in the same order,
    in numpy: no device execution and no read-back, so a reader thread
    that stages the next tile never queues behind the program that is
    solving this one. The arithmetic is in the dtype the device form
    computes in, what ``jnp.asarray`` makes of the tile's float64
    geometry (float32 unless ``jax_enable_x64``). Upstream's
    predict.c:876 computes in double; this follows the device form
    instead so that a row at the cut's edge falls on the side it falls
    on in ``pipeline.py``, which keeps the flags on the device
    (tests/test_predict.py holds the two forms together)."""
    rowflags = np.asarray(rowflags)
    if not (uvmin > 0.0 or uvmax < 1e9):
        return rowflags
    dt = np.dtype(jax.dtypes.canonicalize_dtype(np.float64))
    u, v, freqs = (np.asarray(a, np.float64).astype(dt, copy=False)
                   for a in (tile.u, tile.v, np.atleast_1d(tile.freqs)))
    uvdist = np.sqrt(u * u + v * v) * freqs[0]
    with np.errstate(over="ignore"):    # -y beyond the dtype: no row is out
        out = ((uvdist < dt.type(uvmin))
               | (uvdist * freqs[-1] > dt.type(uvmax) * freqs[0]))
    return np.where((rowflags == 0) & out, 2, rowflags).astype(np.int8)


def chunk_indices(tilesz: int, nbase: int, nchunk: np.ndarray) -> np.ndarray:
    """[M, B] map from data row to hybrid time-chunk per cluster.

    Rows are ordered [tilesz, nbase] flattened; chunk ck covers timeslots
    [ck*ceil(tilesz/nchunk), ...) (lmfit.c:893-899).
    """
    t = np.arange(tilesz * nbase) // nbase
    out = np.zeros((len(nchunk), tilesz * nbase), np.int32)
    for m, K in enumerate(np.asarray(nchunk)):
        tilechunk = (tilesz + K - 1) // K
        out[m] = np.minimum(t // tilechunk, K - 1)
    return out


@jax.named_scope("rime/corrupt")
def model8(coh_m, J_m, sta1, sta2, chunk_idx_m, out_dtype=None):
    """One cluster's corrupted model as [B, 8] reals (solve-path data
    order: (Re, Im) of XX, XY, YX, YY — Dirac.h:1541-1546).

    ``out_dtype`` is the dtype-policy storage emission contract
    (sagecal_tpu.dtypes): the model EVALUATION is complex (c64 — J and
    the coherencies never quantize) and the emitted real stream casts
    to the storage dtype exactly where it joins the [B]-residual
    traffic; a no-op for f32/f64. The solvers and
    :func:`predict_model` evaluate the same bilinear form on real planes
    under the same contract (planes.row_model, normal_eq.residual8, the
    sweep of solvers/sage.py) — this is the rime-layer entry point for
    embedders that build their own residual streams, and the plain
    reference the tests hold the planes against.
    """
    from sagecal_tpu import dtypes as dtp
    Jp = J_m[chunk_idx_m, sta1]
    Jq = J_m[chunk_idx_m, sta2]
    V = Jp @ coh_m @ jnp.conj(jnp.swapaxes(Jq, -1, -2))
    vf = V.reshape(-1, 4)
    out = jnp.stack([vf.real, vf.imag], -1).reshape(-1, 8)
    return out if out_dtype is None else dtp.to_storage(out, out_dtype)


@jax.named_scope("rime/corrupt")
def predict_model(c8, P, sta1, sta2, chunk_idx, cluster_mask=None,
                  row_period: int = 0):
    """Sum of corrupted cluster models, sum_m mask_m J_p,m C_m J_q,m^H,
    on real planes: [8, F, B] (``rime/planes.py``'s order, the model of
    the residual and of the simulated column).

    c8: the coherency planes [8, M, F, B] (:func:`coherencies` with
    ``planes``); P: the stations' Jones planes [M, Kmax, N, 8]
    (``planes.jones_c2r`` of [M, Kmax, N, 2, 2]); chunk_idx: [M, B];
    cluster_mask: [M] bool (e.g. subtract mask / ignore list).

    Real multiply-adds with the rows on the minor axes, the clusters and
    the channels leading axes that the Jones broadcast over, reduced over
    the clusters (what ``solvers/sage._joint_model`` is to the refine).
    The layout follows what the input shows: rows ``[tilesz,
    row_period]`` (``planes.periodic_rows``; ``row_period`` is the
    tile's ``nbase``, 0 where the caller knows of none or the chunk of a
    row is not its timeslot's) have the Jones gathered for
    ``row_period`` rows a chunk and broadcast over the chunk's
    timeslots (``planes.gather_period``); any other rows are gathered
    row by row.
    """
    M, kmax, N = P.shape[:3]
    F, B = c8.shape[2:]
    if cluster_mask is not None:
        P = jnp.where(jnp.asarray(cluster_mask)[:, None, None, None], P, 0.0)
    R = row_period if pl.periodic_rows(row_period, B) else B
    rows = (B // R, R) if R < B else (B,)
    off = kmax * jnp.arange(M)[:, None]
    if kmax > 1 and R < B:
        # per (chunk, baseline) [M, 1 (channels), kmax, R], a timeslot
        # then picks its chunk's: [8, M, 1, T, R]
        slot = ((off + jnp.arange(kmax)) * N)[:, None, :, None]
        jp, jq = (pl.gather_period(P, slot + sta[:R],
                                   chunk_idx[:, None, ::R])
                  for sta in (sta1, sta2))
    else:
        slot = (chunk_idx[:, :R] + off) * N
        # [8, M, 1 (channels), (1 (time),) R] against c8 [8, M, F, *rows]
        jp, jq = (pl.take(P, slot + sta[:R]).reshape(
            (8, M) + (1,) * len(rows) + (R,)) for sta in (sta1, sta2))
    v = pl.mm(jp, pl.mm(c8.reshape((8, M, F) + rows), jq, adj_b=True))
    return jnp.sum(v, axis=1).reshape(8, F, B)


def predict_visibilities(sky: SkyArrays, u, v, w, freqs, fdelta,
                         per_channel_flux: bool = True,
                         cluster_mask=None, beam=None, dobeam: int = 0,
                         tslot=None, sta1=None, sta2=None):
    """Uncorrupted model visibilities summed over clusters [B, F, 2, 2]
    (predict.c:417 / residual.c:1242 simulation path; with beam:
    predict_visibilities_multifreq_withbeam, predict_withbeam.c:1155)."""
    coh = coherencies(sky, u, v, w, freqs, fdelta,
                      per_channel_flux=per_channel_flux,
                      beam=beam, dobeam=dobeam,
                      tslot=tslot, sta1=sta1, sta2=sta2)
    if cluster_mask is not None:
        coh = jnp.where(cluster_mask[:, None, None, None, None], coh, 0.0)
    return jnp.sum(coh, axis=0)
