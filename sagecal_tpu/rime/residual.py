"""Residual computation, subtraction and correction.

Capability parity with reference ``src/lib/Radio/residual.c``:
- ``calculate_residuals_multifreq`` (:930): per-channel model with catalog
  spectra, subtract J_p C J_q^H for subtractable clusters, optionally
  correct the residual by the inverse solution of one cluster (``-k``)
  with an MMSE-regularized 2x2 inverse (``mat_invert`` :163);
- ``predict_visibilities_multifreq[_withsol]`` (:1242/:1601): simulation
  modes (replace/add/subtract, ignore lists, optional correction).

Negative cluster ids are solved for but never subtracted (README.md:50);
that policy arrives here as ``subtract_mask``.

Every function here takes the solutions ``J`` as ``[M, Kmax, N, 2, 2]``
complex or as their real planes ``[M, Kmax, N, 8]`` (``planes.jones_c2r``,
the form the jit boundaries carry them in), and ``row_period``, the
tile's ``nbase`` where its rows lie ``[tilesz, nbase]`` and the chunk
of a row is its timeslot's (0: no period known, or another chunk map),
which :func:`predict.predict_model` lays its planes out by.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from sagecal_tpu.rime import planes as pl, predict as rp


def residual_writeback(res, out_dtype=None):
    """[..., 2, 2] complex residual -> stacked real pairs [..., 2] in
    the dtype-policy storage dtype.

    The writeback emission point of the residual pipeline: under a
    reduced policy the device->host readback (and the DonatedRing slot
    that carried the staged input) ships half the bytes, while the
    residual subtraction itself stays c64. ``out_dtype`` None or
    f32/f64 is the identity path (the pre-policy utils.c2r layout).
    """
    from sagecal_tpu import dtypes as dtp
    out = jnp.stack([res.real, res.imag], axis=-1)
    return out if out_dtype is None else dtp.to_storage(out, out_dtype)


def mmse_inverse(J, rho):
    """Regularized 2x2 inverse: inv(J + rho I), det nudged by rho when
    nearly singular (residual.c:163 ``mat_invert``)."""
    a = J + rho * jnp.eye(2, dtype=J.dtype)
    det = a[..., 0, 0] * a[..., 1, 1] - a[..., 0, 1] * a[..., 1, 0]
    det = jnp.where(jnp.sqrt(jnp.abs(det)) <= rho, det + rho, det)
    inv = jnp.stack([
        jnp.stack([a[..., 1, 1], -a[..., 0, 1]], -1),
        jnp.stack([-a[..., 1, 0], a[..., 0, 0]], -1),
    ], -2)
    return inv / det[..., None, None]


def _model_pairs(v8):
    """Model planes [8, F, B] -> stacked real pairs [B, F, 2, 2, 2]."""
    F, B = v8.shape[1:]
    return jnp.transpose(v8, (2, 1, 0)).reshape(B, F, 2, 2, 2)


def _model_complex(v8):
    """Model planes [8, F, B] -> [B, F, 2, 2] complex."""
    v = _model_pairs(v8)
    return jax.lax.complex(v[..., 0], v[..., 1])


def correct_by_cluster(res, J_m, sta1, sta2, chunk_idx_m, rho,
                       phase_only: bool = False, row_period: int = 0):
    """Apply inv(J_p) res inv(J_q)^H using cluster ``m``'s solutions
    (residual.c:945-1030 correction path). With ``phase_only`` (-J flag)
    each chunk's solutions are first reduced to unit-modulus diagonal
    phases by joint diagonalization (residual.c:965-980 +
    extract_phases). res: [B, F, 2, 2].

    The same sandwich as the model's, with the inverted Jones for the
    one cluster's solutions and the residual for its coherency: it runs
    on planes through :func:`predict.predict_model`."""
    if not jnp.iscomplexobj(J_m):
        J_m = pl.jones_r2c(J_m)
    if phase_only:
        from sagecal_tpu.consensus import manifold as mf
        J_m = jax.vmap(mf.extract_phases)(J_m)        # per chunk [K,N,2,2]
    Jinv = mmse_inverse(J_m, jnp.asarray(rho, J_m.real.dtype))  # [K,N,2,2]
    r8 = jnp.transpose(pl.jones_c2r(res), (2, 1, 0))            # [8, F, B]
    return _model_complex(rp.predict_model(
        r8[:, None], pl.jones_c2r(Jinv)[None], sta1, sta2,
        chunk_idx_m[None], row_period=row_period))


def _model_multifreq(sky, J, u, v, w, freqs, fdelta_chan, sta1, sta2,
                     chunk_idx, subtract_mask, beam, dobeam, tslot,
                     row_period):
    """sum_m J_p C_m(f) J_q^H over subtractable clusters, as planes
    [8, F, B]."""
    c8 = rp.coherencies(sky, u, v, w, freqs, fdelta_chan,
                        per_channel_flux=True, beam=beam, dobeam=dobeam,
                        tslot=tslot, sta1=sta1, sta2=sta2, planes=True)
    P = pl.jones_c2r(J) if jnp.iscomplexobj(J) else J
    return rp.predict_model(c8, P, sta1, sta2, chunk_idx,
                            cluster_mask=subtract_mask,
                            row_period=row_period)


def calculate_residuals_multifreq(sky: rp.SkyArrays, J, x, u, v, w, freqs,
                                  fdelta_chan, sta1, sta2, chunk_idx,
                                  subtract_mask, correct_idx: int | None = None,
                                  rho: float = 1e-9,
                                  beam=None, dobeam: int = 0, tslot=None,
                                  phase_only: bool = False,
                                  row_period: int = 0):
    """Residual x - sum_m J_p C_m(f) J_q^H over subtractable clusters.

    x: [B, F, 2, 2]; J: [M, Kmax, N, 2, 2] (or planes); chunk_idx: [M, B];
    subtract_mask: [M] bool; ``correct_idx`` is the PADDED-ARRAY index of
    the cluster whose solutions correct the residual (host code resolves
    the user-facing ``-k`` cluster id to an index).

    With ``beam``/``dobeam`` this is calculate_residuals_multifreq_withbeam
    (predict_withbeam.c:1895). Returns [B, F, 2, 2] residuals.
    """
    res = x - _model_complex(_model_multifreq(
        sky, J, u, v, w, freqs, fdelta_chan, sta1, sta2, chunk_idx,
        subtract_mask, beam, dobeam, tslot, row_period))
    if correct_idx is not None:
        res = correct_by_cluster(res, J[correct_idx], sta1, sta2,
                                 chunk_idx[correct_idx], rho,
                                 phase_only=phase_only,
                                 row_period=row_period)
    return res


def calculate_residuals_pairs(sky: rp.SkyArrays, J, x_r, u, v, w, freqs,
                              fdelta_chan, sta1, sta2, chunk_idx,
                              subtract_mask, out_dtype=None,
                              correct_idx: int | None = None,
                              rho: float = 1e-9,
                              beam=None, dobeam: int = 0, tslot=None,
                              phase_only: bool = False,
                              row_period: int = 0):
    """:func:`calculate_residuals_multifreq` in the form the jit
    boundaries use: visibilities in and residuals out as stacked real
    pairs [B, F, 2, 2, 2] (``x_r`` in the storage dtype, the result
    through the :func:`residual_writeback` ``out_dtype`` emission).

    Without a correction no complex number is made: the model's planes
    are laid out as pairs once and the subtraction runs on the real
    pairs themselves — bit-identical to the complex one, which subtracts
    the two parts separately too. Forming a complex ``x`` from slices of
    the minor axis only to restack ``res.real``/``res.imag`` on that
    same axis is rewritten by XLA:TPU into an unaligned in-place update
    of ``x_r``, and its fusion emitter then aborts the process (libtpu
    0.0.34, ``Check failed: fusion_util::IsFusibleUnalignedDUS``; found
    on the v5e, PERF.md "Bring-up on v5e")."""
    from sagecal_tpu import dtypes as dtp, utils
    if correct_idx is not None:
        return residual_writeback(calculate_residuals_multifreq(
            sky, J, utils.r2c(x_r), u, v, w, freqs,
            fdelta_chan, sta1, sta2, chunk_idx, subtract_mask,
            correct_idx=correct_idx, rho=rho, beam=beam, dobeam=dobeam,
            tslot=tslot, phase_only=phase_only, row_period=row_period),
            out_dtype)
    model = _model_multifreq(sky, J, u, v, w, freqs, fdelta_chan, sta1,
                             sta2, chunk_idx, subtract_mask, beam, dobeam,
                             tslot, row_period)
    with jax.named_scope("rime/residual"):   # subtraction + write-back
        out = dtp.acc(x_r) - _model_pairs(model)
        return out if out_dtype is None else dtp.to_storage(out, out_dtype)


def calculate_residuals_interp(sky: rp.SkyArrays, J_old, J_new, x, u, v, w,
                               freqs, fdelta_chan, sta1, sta2, chunk_idx,
                               subtract_mask, correct_idx: int | None = None,
                               rho: float = 1e-9, row_period: int = 0):
    """Residuals with OLD-solution correction (``calculate_residuals_interp``,
    residual.c:201): subtract the model corrupted by the NEW solutions,
    correct the residual with the inverse of the OLD solutions' cluster
    ``correct_idx``. (The reference's time interpolation between the two
    is disabled upstream — residual.c:288 'interpolation is disabled for
    the moment' — so this matches its actual behavior.)
    """
    res = x - _model_complex(_model_multifreq(
        sky, J_new, u, v, w, freqs, fdelta_chan, sta1, sta2, chunk_idx,
        subtract_mask, None, 0, None, row_period))
    if correct_idx is not None:
        res = correct_by_cluster(res, J_old[correct_idx], sta1, sta2,
                                 chunk_idx[correct_idx], rho,
                                 row_period=row_period)
    return res


def simulate_visibilities(sky: rp.SkyArrays, x, u, v, w, freqs, fdelta_chan,
                          sta1, sta2, mode: int, J=None, chunk_idx=None,
                          ignore_mask=None, correct_idx: int | None = None,
                          rho: float = 1e-9,
                          beam=None, dobeam: int = 0, tslot=None,
                          row_period: int = 0):
    """Simulation modes (-a 1/2/3): replace/add/subtract the model
    (residual.c:1242 predict_visibilities_multifreq, :1601 _withsol;
    with beam: predict_visibilities_multifreq_with[sol_with]beam_gpu
    semantics, Radio.h:400-446).

    ``J`` (optional) corrupts the model with solutions; ``ignore_mask`` [M]
    True = keep cluster in the simulated model (reference ignorelist holds
    clusters to skip).  ``x`` [B, F, 2, 2] complex is not looked at in
    mode 1 (it may be None): that call is the model itself, which
    :func:`simulate_pairs` forms through here.
    """
    if J is not None:
        if chunk_idx is None:
            chunk_idx = jnp.zeros((sky.ll.shape[0], u.shape[0]), jnp.int32)
        model = _model_complex(_model_multifreq(
            sky, J, u, v, w, freqs, fdelta_chan, sta1, sta2, chunk_idx,
            ignore_mask, beam, dobeam, tslot, row_period))
    else:
        model = rp.predict_visibilities(
            sky, u, v, w, freqs, fdelta_chan, cluster_mask=ignore_mask,
            beam=beam, dobeam=dobeam, tslot=tslot, sta1=sta1, sta2=sta2)
    with jax.named_scope("rime/residual"):
        if mode == 2:       # SIMUL_ADD
            out = x + model
        elif mode == 3:     # SIMUL_SUB
            out = x - model
        else:               # SIMUL_ONLY
            out = model
        if correct_idx is not None and J is not None:
            out = correct_by_cluster(out, J[correct_idx], sta1, sta2,
                                     chunk_idx[correct_idx], rho,
                                     row_period=row_period)
    return out


def simulate_pairs(sky: rp.SkyArrays, x_r, u, v, w, freqs, fdelta_chan,
                   sta1, sta2, mode: int, J=None, chunk_idx=None,
                   ignore_mask=None, correct_idx: int | None = None,
                   rho: float = 1e-9,
                   beam=None, dobeam: int = 0, tslot=None,
                   row_period: int = 0):
    """:func:`simulate_visibilities` in the form the jit boundary uses:
    the input column in and the simulated one out as stacked real pairs
    [B, F, 2, 2, 2].

    The model comes from :func:`simulate_visibilities` (mode 1: its
    planes read as complex) and its two parts are stacked once; the add
    and the subtract then run on the pairs themselves, which is what the
    complex ones do part by part.  No complex ``x`` is made from slices
    of ``x_r``'s minor axis: with the restack of the result on that same
    axis XLA:TPU turns that into an unaligned in-place update of ``x_r``
    and aborts the process in modes 2 and 3
    (:func:`calculate_residuals_pairs` says more).  A correction by a
    cluster needs the complex residual and keeps that path."""
    from sagecal_tpu import utils
    kw = dict(J=J, chunk_idx=chunk_idx, ignore_mask=ignore_mask, beam=beam,
              dobeam=dobeam, tslot=tslot, row_period=row_period)
    if correct_idx is not None and J is not None:
        return utils.c2r(simulate_visibilities(
            sky, utils.r2c(x_r), u, v, w, freqs, fdelta_chan, sta1, sta2,
            mode, correct_idx=correct_idx, rho=rho, **kw))
    model = simulate_visibilities(sky, None, u, v, w, freqs, fdelta_chan,
                                  sta1, sta2, 1, **kw)
    with jax.named_scope("rime/residual"):
        m_r = jnp.stack([model.real, model.imag], axis=-1)
        if mode == 2:       # SIMUL_ADD
            return x_r + m_r
        if mode == 3:       # SIMUL_SUB
            return x_r - m_r
        return m_r          # SIMUL_ONLY
