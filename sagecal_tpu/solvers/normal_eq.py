"""Analytic Jacobians and normal equations for the per-direction solve.

The measurement model per baseline b=(p,q) is V_b = J_p C_b J_q^H with one
2x2 complex Jones per station. The reference evaluates derivative kernels
per 8-parameter station blocks (mderiv.cu:30 ``kernel_deriv``; CPU
``mylm_jac_single_pth`` lmfit.c); here the same closed forms are assembled
into block-sparse normal equations, no per-parameter loops. The row
MODEL, V and its two Wirtinger factors, is sixteen complex
multiply-adds a row and is written out as real elementwise arithmetic
on planes with the rows on the minor axis (``rime/planes.py``:
:func:`row_model`; here :class:`RowPlanes`): a 2 x 2 product fed to a
128 x 128 systolic array at f32 ``highest`` costs a hundred times its
arithmetic. So is the Gauss-Newton matrix of rows with a period
(:func:`plane_equations`: a baseline's Gram blocks are 4 x 4, as
small); the generic assembly of :func:`normal_equations`,
:func:`gn_factors` and the constrained modes' are batched einsums +
scatter-adds over ``[B, 2, 2, 4]`` factors.

Derivatives (Wirtinger):
  with A = C_b J_q^H:  dV/d(J_p)_{cd}       = e_c e_d^T A   (complex-linear)
  with B = J_p C_b:    dV/d(conj J_q)_{cd}  = B e_d e_c^T   (conj-linear)

Real parametrization per station: 8 reals, pairs (Re, Im) of J in row-major
order (00, 01, 10, 11). Residual 8-vector per baseline likewise (Re, Im) of
(V00, V01, V10, V11) — matching the reference's XX,XY,YX,YY (re, im) data
layout (Dirac.h:1541-1546).
"""

from __future__ import annotations

import copy
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from sagecal_tpu import dtypes as dtp
from sagecal_tpu.rime import planes as pl
# the plane algebra has ONE home, shared with the programs that leave
# the solver (rime/predict.predict_model); these are its names here
from sagecal_tpu.rime.planes import (  # noqa: F401
    jones_c2r, jones_r2c, periodic_rows, row_grad, row_model, row_tangent)

_EYE2 = jnp.eye(2)


class RowPlanes:
    """Row data as real planes, the rows on the minor axes, with the
    gather from and the segment sum to the stations: one cluster's
    (``coh [B, 2, 2]``, ``chunk_id [B]``: :func:`rtr.make_row_pass`) or
    all clusters' at once (``coh [M, B, 2, 2]``, ``chunk_id [M, B]``: the
    joint refine of ``solvers/sage.py``), the clusters then a leading
    axis of every plane array but ``x`` and ``w`` and cluster ``m``'s
    chunks the Jones slots ``m * kmax ..`` of ``M * kmax``.

    ``x``, ``w``: data and sqrt-weights ``[8, *rows]`` in their (storage)
    dtype (None where none were given); ``c``: the coherency planes
    ``[8, (M,) *rows]``. With a ``row_period`` that divides the rows
    (:func:`periodic_rows`: rows laid out ``[tilesz, nbase]``, stations
    repeating every ``nbase``, the chunk of a row its timeslot's)
    ``rows`` is ``(tilesz, nbase)``: the Jones are gathered for ``nbase``
    rows a chunk and broadcast over the chunk's timeslots, and a per-row
    gradient is summed over a chunk's timeslots before ``kmax x nbase``
    rows are scattered; otherwise ``rows`` is ``(B,)``. A chunk map
    that is not a tracer is held to the promise
    (``planes.check_chunk_rows``)."""

    def __init__(self, x8, coh, wt, sta1, sta2, chunk_id, kmax: int,
                 n_stations: int, row_period: int = 0):
        B, lead = coh.shape[-3], coh.shape[:-3]
        self.periodic = periodic_rows(row_period, B)
        R = row_period if self.periodic else B
        self.rows = (B // R, R) if self.periodic else (B,)
        #: chunks a cluster; ``kmax`` counts all clusters' slots
        self.chunks = kmax
        #: per timeslot, its chunk within the cluster [(M,) tilesz], and
        #: whether timeslot t is of chunk k [(M,) chunks, tilesz, 1]: set
        #: where planes of several chunks need them
        self.tchunk = self.of_chunk = None
        if self.periodic and kmax > 1:
            if not isinstance(chunk_id, jax.core.Tracer):
                pl.check_chunk_rows(chunk_id, R)
            self.tchunk = chunk_id[..., ::R]
            self.of_chunk = (self.tchunk[..., None, :] == jnp.arange(
                kmax, dtype=chunk_id.dtype)[:, None])[..., None]
        if lead:
            chunk_id = chunk_id + kmax * jnp.arange(
                lead[0], dtype=chunk_id.dtype)[:, None]
            kmax *= lead[0]
        self.kmax, self.n_stations, self.chunk_id = kmax, n_stations, chunk_id
        self.sta1, self.sta2 = sta1, sta2
        if self.tchunk is None:
            self.i1 = (chunk_id * n_stations + sta1)[..., :R]
            self.i2 = (chunk_id * n_stations + sta2)[..., :R]
        else:
            # per (chunk, baseline): [(M,) chunks, R]
            slot = jnp.arange(kmax, dtype=chunk_id.dtype).reshape(
                lead + (self.chunks, 1)) * n_stations
            self.i1, self.i2 = slot + sta1[:R], slot + sta2[:R]
        self.x, self.w = (None if a is None else self.planes(a)
                          for a in (x8, wt))
        self.c = self.planes(jones_c2r(coh))

    def planes(self, a):
        """[(M,) B, 8] -> [8, (M,) *rows]."""
        return jnp.moveaxis(a, -1, 0).reshape(
            (8,) + a.shape[:-2] + self.rows)

    def to_rows(self, a):
        """[8, *rows] -> [B, 8]."""
        return jnp.moveaxis(a.reshape(8, -1), 0, -1)

    def cluster(self, m):
        """Cluster ``m``'s rows of an instance over all clusters, as an
        instance over one: what a cluster solve takes
        (:func:`rtr.rtr_rows`) and a cluster's model is evaluated on
        (the sweep of ``solvers/sage.py``); the sqrt-weights stay, the
        data are the caller's to give (:meth:`with_x`). Slices of what
        this instance holds: nothing is laid out again."""
        out = copy.copy(self)
        out.kmax = self.chunks
        off = m * out.kmax
        out.chunk_id = self.chunk_id[m] - off
        out.i1 = self.i1[m] - off * self.n_stations
        out.i2 = self.i2[m] - off * self.n_stations
        if self.tchunk is not None:
            out.tchunk, out.of_chunk = self.tchunk[m], self.of_chunk[m]
        out.c = self.c[:, m]
        return out

    def with_x(self, x):
        """The same rows with ``x [8, *rows]`` for their data."""
        out = copy.copy(self)
        out.x = x
        return out

    def flat(self):
        """One cluster's row data back as rows, ``(x8 [B, 8], coh
        [B, 2, 2], wt [B, 8])``, for the assemblies that take those
        (:func:`normal_equations`, :func:`gn_factors`, the constrained
        modes')."""
        return (self.to_rows(self.x), jones_r2c(self.to_rows(self.c)),
                self.to_rows(self.w))

    def gather(self, P):
        """Station planes P [K, N, 8] -> (jp8, jq8) for :func:`row_model`."""
        if self.periodic:
            return (pl.gather_period(P, self.i1, self.tchunk),
                    pl.gather_period(P, self.i2, self.tchunk))
        return pl.take(P, self.i1), pl.take(P, self.i2)

    def time_sum(self, a):
        """The part of :meth:`station_sum` that is elementwise with the
        rows: [W, (M,) *rows] -> [W, (M,) R], the sum over time, with one
        chunk a cluster; [W, (M,) chunks, R], the sums over each chunk's
        timeslots (a masked sum along the time axis: elementwise and a
        reduction, no contraction), with several."""
        if not self.periodic:
            return a
        if self.tchunk is None:
            return jnp.sum(a, axis=-2)
        return jnp.sum(jnp.where(self.of_chunk, a[..., None, :, :], 0),
                       axis=-2)

    def station_sum(self, gp, gq):
        """Per-row shares [W, (M,) (chunks,) R] of the first and of the
        second station (after :meth:`time_sum`) -> [K, N, W] (W = 8 for
        a gradient's planes)."""
        W = gp.shape[0]
        out = jnp.zeros((self.kmax * self.n_stations, W), gp.dtype)
        out = (out.at[self.i1].add(jnp.moveaxis(gp, 0, -1))
               .at[self.i2].add(jnp.moveaxis(gq, 0, -1)))
        return out.reshape(self.kmax, self.n_stations, W)

    def chunk_sum(self, a):
        """One cluster's [8, *rows] -> per-chunk sums [K]."""
        if self.kmax == 1:
            return jnp.sum(a).reshape(1)
        if self.tchunk is None:
            return jax.ops.segment_sum(jnp.sum(a, axis=0), self.chunk_id,
                                       num_segments=self.kmax)
        # the baselines first, then the timeslots of a chunk
        return jnp.sum(jnp.where(self.of_chunk[..., 0],
                                 jnp.sum(a, axis=(0, -1)), 0), axis=-1)

    def select(self, take, new, old):
        """One cluster's rows of the chunks where ``take`` [K] holds from
        ``new``, the others from ``old`` (both [8, *rows])."""
        if self.kmax == 1:
            return jnp.where(take[0], new, old)
        if self.tchunk is None:
            return jnp.where(take[self.chunk_id], new, old)
        return jnp.where(take[self.tchunk][:, None], new, old)


def residual8(x8, J, coh, sta1, sta2, chunk_id):
    """Real residual r = x - vec(J_p C J_q^H): [B, 8].

    x8: [B, 8]; J: [K, N, 2, 2] complex; coh: [B, 2, 2]; chunk_id: [B].
    """
    P, N = jones_c2r(J), J.shape[-3]
    v8, _, _ = row_model(pl.take(P, chunk_id * N + sta1),
                         pl.take(P, chunk_id * N + sta2),
                         jones_c2r(coh).T)
    # dtype-policy storage/accumulate contract: the model EMITS the
    # data's storage dtype (a no-op for f32/f64 data), so the residual
    # stream stays storage-sized; reductions over it upcast (dtp.acc)
    return x8 - dtp.to_storage(v8.T, x8.dtype)


def _real_jac(D, conj_param: bool):
    """Complex derivative tensor [B, 2, 2, 2, 2] -> real Jacobian [B, 8, 8].

    D[b, a, o, c, d] = dV_{ao}/dtheta_{cd} where theta is the complex param
    (or its conjugate when ``conj_param``). Rows are (Re,Im) of V (row-major
    a,o); columns (Re,Im) of theta (row-major c,d).
    """
    B = D.shape[0]
    Dr, Di = D.real, D.imag
    # columns: ci=0 is the Re-part parameter, ci=1 the Im-part.
    # linear:  dV/dRe = D, dV/dIm = iD  -> (Re,Im) rows (Dr,-Di) / (Di,Dr)
    # conj:    dV/dRe = D, dV/dIm = -iD -> (Re,Im) rows (Dr, Di) / (Di,-Dr)
    J = jnp.stack([
        jnp.stack([Dr, -Di if not conj_param else Di], axis=-1),   # ri=Re
        jnp.stack([Di, Dr if not conj_param else -Dr], axis=-1),   # ri=Im
    ], axis=3)  # [B, a, o, ri, c, d, ci]
    return J.reshape(B, 8, 8)


def baseline_jacobians(J, coh, sta1, sta2, chunk_id):
    """Per-baseline real Jacobian blocks (dV/dtheta_p, dV/dtheta_q): [B,8,8] x2."""
    Jp = J[chunk_id, sta1]                      # [B,2,2]
    Jq = J[chunk_id, sta2]
    A = coh @ jnp.conj(jnp.swapaxes(Jq, -1, -2))   # [B,2,2]
    Bm = Jp @ coh
    # Dp[b,a,o,c,d] = I[a,c] A[b,d,o]
    Dp = jnp.einsum("ac,bdo->baocd", _EYE2.astype(A.dtype), A)
    # Dq[b,a,o,c,d] = I[o,c] B[b,a,d]   (deriv wrt conj(Jq))
    Dq = jnp.einsum("oc,bad->baocd", _EYE2.astype(A.dtype), Bm)
    return _real_jac(Dp, conj_param=False), _real_jac(Dq, conj_param=True)


def _normal_equations_dense(x8, J, coh, sta1, sta2, chunk_id, wt,
                            n_stations: int, kmax: int):
    """Reference assembly via materialized [B, 8, 8] Jacobian blocks.

    Kept as the ground truth the traffic-lean :func:`normal_equations`
    is equivalence-tested against (tests/test_lm.py); not used on any
    hot path — it moves ~3x the bytes of the structured assembly.
    """
    N = n_stations
    r = residual8(x8, J, coh, sta1, sta2, chunk_id)
    Gp, Gq = baseline_jacobians(J, coh, sta1, sta2, chunk_id)
    rw = r * wt
    Gp = Gp * wt[:, :, None]
    Gq = Gq * wt[:, :, None]

    pp = jnp.einsum("bri,brj->bij", Gp, Gp)
    qq = jnp.einsum("bri,brj->bij", Gq, Gq)
    pq = jnp.einsum("bri,brj->bij", Gp, Gq)
    jtep = jnp.einsum("bri,br->bi", Gp, rw)
    jteq = jnp.einsum("bri,br->bi", Gq, rw)

    JTJ = jnp.zeros((kmax, N, N, 8, 8), Gp.dtype)
    JTJ = JTJ.at[chunk_id, sta1, sta1].add(pp)
    JTJ = JTJ.at[chunk_id, sta2, sta2].add(qq)
    JTJ = JTJ.at[chunk_id, sta1, sta2].add(pq)
    JTJ = JTJ.at[chunk_id, sta2, sta1].add(jnp.swapaxes(pq, -1, -2))
    JTJ = JTJ.transpose(0, 1, 3, 2, 4).reshape(kmax, 8 * N, 8 * N)

    JTe = jnp.zeros((kmax, N, 8), Gp.dtype)
    JTe = JTe.at[chunk_id, sta1].add(jtep)
    JTe = JTe.at[chunk_id, sta2].add(jteq)
    JTe = JTe.reshape(kmax, 8 * N)

    cost = jnp.zeros((kmax,), Gp.dtype).at[chunk_id].add(
        jnp.sum(rw * rw, axis=1))
    return JTJ, JTe, cost


def _ma_factor(A):
    """[B, 2, 2] complex A (dV_ao/d(J_p)_ad = A_do) -> MA [B, 2, 2, 4]
    real with MA[b, o, ri, (d, ci)] = Gp[b, (a, o, ri), (a, d, ci)]:
    the 4x4 block every station-p Jacobian row block repeats (Gp is
    block-diagonal over a == c)."""
    Ar = jnp.swapaxes(A.real, -1, -2)              # [B, o, d]
    Ai = jnp.swapaxes(A.imag, -1, -2)
    # ci columns: (Re, Im) params; ri=Re row (Ar, -Ai), ri=Im row (Ai, Ar)
    MA = jnp.stack([jnp.stack([Ar, -Ai], -1),      # ri = Re
                    jnp.stack([Ai, Ar], -1)], 2)   # ri = Im
    return MA.reshape(A.shape[0], 2, 2, 4)         # [B, o, ri, (d, ci)]


def _mb_factor(Bm):
    """[B, 2, 2] complex Bm (dV_ao/d(conj J_q)_od = Bm_ad) -> MB
    [B, 2, 2, 4] real with MB[b, a, ri, (d, ci)] =
    Gq[b, (a, o, ri), (o, d, ci)] (Gq is block-diagonal over o == c;
    conjugate-linear, so the Im-param column flips sign)."""
    Br, Bi = Bm.real, Bm.imag                      # [B, a, d]
    MB = jnp.stack([jnp.stack([Br, Bi], -1),       # ri = Re
                    jnp.stack([Bi, -Br], -1)], 2)  # ri = Im
    return MB.reshape(Bm.shape[0], 2, 2, 4)        # [B, a, ri, (d, ci)]


def _reduced_gram_baseline_major(wt, MA, MB, rw, T: int, nb: int, N: int,
                                 sta1, sta2, acc):
    """The reduced path's baseline-major Gram/gradient assembly from
    storage-dtype factors: f32 dot operands materialized directly in
    merged-contraction layouts (each dot reads its operands once on the
    CPU cost model), cross blocks scattered straight into the final
    [1, N, 8, N, 8] station-major matrix. Returns (JTJ [1, 8N, 8N],
    JTe [1, 8N]). Shared by :func:`_normal_equations_reduced` and the
    OS-subset assembly :func:`os_subset_equations`."""
    wvr = wt.reshape(T, nb, 2, 2, 2)           # [t, n, a, o, r]
    MAr = MA.reshape(T, nb, 2, 2, 4)           # [t, n, o, r, i]
    MBr = MB.reshape(T, nb, 2, 2, 4)           # [t, n, a, r, j]
    rwr = rw.reshape(T, nb, 2, 2, 2)
    wv_a = jnp.transpose(wvr, (1, 2, 3, 0, 4))          # [n,a,o,t,r]
    MA_a = jnp.transpose(MAr, (1, 2, 0, 3, 4))[:, None]  # [n,1,o,t,r,i]
    rw_a = jnp.transpose(rwr, (1, 2, 3, 0, 4))
    wv_b = jnp.transpose(wvr, (1, 3, 2, 0, 4))          # [n,o,a,t,r]
    MB_b = jnp.transpose(MBr, (1, 2, 0, 3, 4))[:, None]  # [n,1,a,t,r,j]
    rw_b = jnp.transpose(rwr, (1, 3, 2, 0, 4))
    MB_a = jnp.transpose(MBr, (1, 2, 0, 3, 4))[:, :, None]  # [n,a,1,..]
    Xa = (wv_a[..., None].astype(acc)
          * MA_a.astype(acc)).reshape(nb, 2, 2 * T * 2, 4)
    Xb = (wv_b[..., None].astype(acc)
          * MB_b.astype(acc)).reshape(nb, 2, 2 * T * 2, 4)
    Xab = (wv_a[..., None].astype(acc)
           * MB_a.astype(acc)).reshape(nb, 2, 2, T * 2, 4)
    Ra = rw_a.astype(acc).reshape(nb, 2, 2 * T * 2)
    Rb = rw_b.astype(acc).reshape(nb, 2, 2 * T * 2)
    pp = jnp.einsum("naki,nakj->naij", Xa, Xa)
    qq = jnp.einsum("noki,nokj->noij", Xb, Xb)
    # cross block: native dot emission [n,a,o,i,j], then the two
    # scatter layouts ([(a i), (o j)] block and its transpose) as
    # output permutes — cheaper than forcing the dot to emit the
    # interleaved order (the pq lhs is a bitcast view of Xa:
    # [n,a,(o t r),i] -> [n,a,o,(t r),i])
    pq4 = jnp.einsum("naoki,naokj->naoij",
                     Xa.reshape(nb, 2, 2, T * 2, 4), Xab)
    pq = jnp.transpose(pq4, (0, 1, 3, 2, 4)).reshape(nb, 8, 8)
    pqT = jnp.transpose(pq4, (0, 2, 4, 1, 3)).reshape(nb, 8, 8)
    jtep = jnp.einsum("naki,nak->nai", Xa, Ra)
    jteq = jnp.einsum("noki,nok->noi", Xb, Rb)
    s1b, s2b = sta1[:nb], sta2[:nb]
    D = jnp.zeros((1, N, 2, 4, 4), acc)
    D = D.at[0, s1b].add(pp).at[0, s2b].add(qq)
    JTe = jnp.zeros((1, N, 2, 4), acc)
    JTe = JTe.at[0, s1b].add(jtep).at[0, s2b].add(jteq)
    eye2 = jnp.eye(2, dtype=acc)
    Dfull = jnp.einsum("knaij,ab->knaibj", D, eye2).reshape(1, N, 8, 8)
    idx = jnp.arange(N)
    JTJ = jnp.zeros((1, N, 8, N, 8), acc)
    JTJ = JTJ.at[0, s1b, :, s2b, :].add(pq)
    JTJ = JTJ.at[0, s2b, :, s1b, :].add(pqT)
    JTJ = JTJ.at[0, idx, :, idx, :].add(Dfull[0])
    return JTJ.reshape(1, 8 * N, 8 * N), JTe.reshape(1, 8 * N)


@jax.named_scope("assemble")     # sage/sweep/assemble in a solve
def os_subset_equations(x8, J, coh, sta1, sta2, wt, os_id, subset,
                        ntper: int, row_period: int, n_stations: int,
                        cost_wt):
    """Ordered-subsets normal equations from the SUBSET's rows only
    (reduced dtype policy, single-chunk baseline-major layout).

    The OS body's equations come from one contiguous time block of
    ``ntper`` timeslots; the f32 path realizes that as a FULL [B]-pass
    with subset-masked weights (bit-reference), which pays the whole
    row traffic for ~1/n_subsets of the information. Zero-weight rows
    contribute exactly nothing to JTJ/JTe, so slicing the assembly to
    the block is numerically exact up to summation order — freedom the
    reduced path's trajectory-tolerance contract grants and the
    bit-frozen default does not have. The FULL-data acceptance cost
    (``cost_wt``, clmfit.c:1404 semantics) still takes one whole-[B]
    model/residual pass — that pass also feeds the sliced residual, so
    the model is evaluated once.

    ``subset`` is the traced subset index; the slice start clamps for
    the short tail block and the sliced ``os_id`` re-masks the weights,
    so misaligned tail rows drop out exactly like the masked full pass.
    Returns (JTJ [1, 8N], JTe, cost [1]) like normal_equations at
    kmax == 1.
    """
    N = n_stations
    B = x8.shape[0]
    st = x8.dtype
    acc = dtp.acc_dtype(st)
    nb = row_period
    os_id = jnp.asarray(os_id)
    bs = ntper * nb                            # static subset row count
    start = jnp.minimum(subset * bs, B - bs)   # clamped short-tail start
    # ONE full-[B] model/residual pass: the acceptance cost needs it,
    # and the subset's residual rows slice out of it for free
    Jp = J[0][sta1]                            # kmax == 1
    Jq = J[0][sta2]
    Bm = Jp @ coh
    V = Bm @ jnp.conj(jnp.swapaxes(Jq, -1, -2))
    vf = V.reshape(-1, 4)
    r = x8 - jnp.stack([vf.real, vf.imag], -1).reshape(-1, 8).astype(st)
    rca = (r * cost_wt).astype(acc)
    cost = jnp.sum(rca * rca).reshape(1)
    # subset slices (all static-size dynamic slices over the row axis)
    sl = lambda a: jax.lax.dynamic_slice_in_dim(a, start, bs, 0)
    wts = sl(wt) * (sl(os_id) == subset).astype(st)[:, None]
    rs = sl(r)
    cohs = sl(coh)
    Jqs = sl(Jq)
    As = cohs @ jnp.conj(jnp.swapaxes(Jqs, -1, -2))
    Bms = sl(Bm)
    MA = _ma_factor(As).astype(st)
    MB = _mb_factor(Bms).astype(st)
    rws = rs * wts
    JTJ, JTe = _reduced_gram_baseline_major(
        wts, MA, MB, rws, ntper, nb, N, sl(sta1), sl(sta2), acc)
    return JTJ, JTe, cost


def _normal_equations_reduced(x8, J, coh, sta1, sta2, chunk_id, wt,
                              n_stations: int, kmax: int, cost_wt=None,
                              row_period: int = 0):
    """Reduced-storage (bf16/f16) assembly with f32 accumulation.

    Same weighted Gauss-Newton linearization as :func:`normal_equations`
    (which dispatches here when ``x8`` carries a reduced storage dtype),
    re-laid for the storage/accumulate split:

    - the [B]-data arrays (x8, wt, residual stream) and the Wirtinger
      factors MA/MB stay in the storage dtype;
    - every contraction names an f32 accumulator, and — because XLA CPU
      upconverts dot operands (a bf16 dot is priced and executed as an
      f32 dot plus converts) — the weighted Gram operands are
      materialized DIRECTLY in f32, in a baseline-major batch layout
      whose dots read each operand exactly once. That re-lay is free to
      differ from the f32 path's summation order: the reduced policy
      is trajectory-tolerance-gated (MIGRATION.md "Dtype policy"), not
      bit-gated;
    - the JTe gradient rides the Gram as a 5th column (one dot yields
      pp AND jtep), and the station-pair cross blocks scatter straight
      into the FINAL [K, N, 8, N, 8] layout (symmetrized by a second
      scatter of the transposed updates), skipping the dense-expansion
      transpose passes of the f32 path.

    Complex coherencies stay c64 (XLA has no sub-f32 complex dtype);
    their share of one priced LM trip is ~1%. The generic
    (multi-chunk / no-row-period) branch keeps the scatter structure of
    the f32 path with storage-dtype elementwise arrays and
    ``preferred_element_type`` accumulators — its dots dominate its CPU
    byte count either way (PERF.md round 9).
    """
    N = n_stations
    B = x8.shape[0]
    st = x8.dtype
    acc = dtp.acc_dtype(st)
    pet = dtp.pet(st)
    Jp = J[chunk_id, sta1]                         # [B, 2, 2]
    Jq = J[chunk_id, sta2]
    A = coh @ jnp.conj(jnp.swapaxes(Jq, -1, -2))
    Bm = Jp @ coh
    V = Jp @ A
    vf = V.reshape(-1, 4)
    r = x8 - jnp.stack([vf.real, vf.imag], -1).reshape(-1, 8).astype(st)
    rw = r * wt
    MA = _ma_factor(A).astype(st)                  # [B, o, ri, 4] storage
    MB = _mb_factor(Bm).astype(st)                 # [B, a, ri, 4] storage
    rc = rw if cost_wt is None else r * cost_wt
    rca = rc.astype(acc)

    if kmax == 1 and row_period > 0 and B % row_period == 0:
        # f32 Gram operands produced directly in their dot layouts (the
        # transposed reads of the storage factors fuse into the
        # producers; the upcast IS the storage->accumulate boundary).
        # Each dot's contraction axes are MERGED into one trailing-K
        # axis — the layout where XLA CPU's cost model (and its gemm)
        # reads every operand exactly once; split contraction dims get
        # re-read penalties (measured ~3x on the pp Gram). The cross
        # blocks scatter straight into the final station-major matrix —
        # no dense O buffer, no post-hoc transpose pass.
        JTJ, JTe = _reduced_gram_baseline_major(
            wt, MA, MB, rw, B // row_period, row_period, N, sta1, sta2,
            acc)
        cost = jnp.sum(rca * rca).reshape(1)
        return JTJ, JTe, cost

    # generic multi-chunk branch: f32-path scatter structure, storage
    # elementwise arrays, f32 accumulators on every contraction
    w2 = (wt * wt).reshape(B, 2, 2, 2)
    rw2 = (rw * wt).reshape(B, 2, 2, 2)
    WMA = w2[..., None] * MA[:, None]              # [B, a, o, ri, 4] st
    WMB = w2[..., None] * MB[:, :, None]
    pp = jnp.einsum("baori,borj->baij", WMA, MA, **pet)
    qq = jnp.einsum("baorj,bari->boij", WMB, MB, **pet)
    pq = jnp.einsum("baori,barj->baoij", WMA, MB, **pet)
    jtep = jnp.einsum("baor,bori->bai", rw2, MA, **pet)
    jteq = jnp.einsum("baor,bari->boi", rw2, MB, **pet)
    D = jnp.zeros((kmax, N, 2, 4, 4), acc)
    D = D.at[chunk_id, sta1].add(pp)
    D = D.at[chunk_id, sta2].add(qq)
    O = jnp.zeros((kmax, N, N, 2, 2, 4, 4), acc)
    O = O.at[chunk_id, sta1, sta2].add(pq)
    JTe = jnp.zeros((kmax, N, 2, 4), acc)
    JTe = JTe.at[chunk_id, sta1].add(jtep)
    JTe = JTe.at[chunk_id, sta2].add(jteq)
    cost = jnp.zeros((kmax,), acc).at[chunk_id].add(
        jnp.sum(rca * rca, axis=1))
    Off = O.transpose(0, 1, 2, 3, 5, 4, 6).reshape(kmax, N, N, 8, 8)
    JTJ = Off + jnp.swapaxes(jnp.swapaxes(Off, 1, 2), -1, -2)
    eye2 = jnp.eye(2, dtype=acc)
    Dfull = jnp.einsum("knaij,ab->knaibj", D, eye2).reshape(kmax, N, 8, 8)
    idx = jnp.arange(N)
    JTJ = JTJ.at[:, idx, idx].add(Dfull)
    JTJ = JTJ.transpose(0, 1, 3, 2, 4).reshape(kmax, 8 * N, 8 * N)
    return JTJ, JTe.reshape(kmax, 8 * N), cost


def _ma_entry(a8, o: int, ri: int, i: int):
    """:func:`_ma_factor`'s MA[o, ri, i = (d, ci)] as (plane of ``a8``,
    sign): the (Re, Im) rows (Ar, -Ai) / (Ai, Ar) of A[d, o]."""
    d, ci = divmod(i, 2)
    return a8[2 * (2 * d + o) + (ri ^ ci)], -1 if (ri, ci) == (0, 1) else 1


def _mb_entry(bm8, a: int, ri: int, j: int):
    """:func:`_mb_factor`'s MB[a, ri, j = (d, ci)] as (plane of ``bm8``,
    sign): the (Re, Im) rows (Br, Bi) / (Bi, -Br) of Bm[a, d]."""
    d, ci = divmod(j, 2)
    return bm8[2 * (2 * a + d) + (ri ^ ci)], -1 if (ri, ci) == (1, 1) else 1


def _signed_sum(terms):
    """Sum of (sign, plane) terms without a negation per term."""
    pos = [t for sg, t in terms if sg > 0]
    neg = [t for sg, t in terms if sg < 0]
    if not neg:
        return sum(pos[1:], pos[0])
    return sum(pos[1:], pos[0]) - sum(neg[1:], neg[0]) if pos \
        else -sum(neg[1:], neg[0])


#: the ten (i, j), i <= j, of a symmetric 4 x 4 block in the order
#: :func:`_gram_planes` emits them, and each (i, j)'s place among them
_TRI = [(i, j) for i in range(4) for j in range(i, 4)]
_TRI_OF = np.array([[_TRI.index((min(i, j), max(i, j))) for j in range(4)]
                    for i in range(4)])


def _gram_planes(w2, a8, bm8, tsum):
    """A baseline's Gram blocks from planes: (pp [2 * 10, R], qq
    [2 * 10, R], pq [64, R]), each entry ``tsum`` (the sum over time) of
    a sum over (o, ri) (pp), (a, ri) (qq) or ri (pq) of products
    ``w2 * MA * MA``, ``w2 * MB * MB``, ``w2 * MA * MB`` of the squared
    sqrt-weight planes ``w2`` (index 2 (2 a + o) + ri) with the entries
    of :func:`_ma_factor` and :func:`_mb_factor`, read off the Wirtinger
    factors' planes ``a8``, ``bm8`` with their signs. pp[a] and qq[o]
    are symmetric: their ten entries ``_TRI``; pq is ordered
    (a, i, o, j). A weighted factor that several entries share is
    written once per entry and left to the compiler's
    common-subexpression pass."""
    def entry(terms):
        # terms: (a, o, ri, (plane, sign) of one factor's column, the
        # same of the other's)
        return tsum(_signed_sum(
            [(si * sj, w2[2 * (2 * a + o) + ri] * pi * pj)
             for a, o, ri, (pi, si), (pj, sj) in terms]))

    pp = [entry([(a, o, ri, _ma_entry(a8, o, ri, i), _ma_entry(a8, o, ri, j))
                 for o in range(2) for ri in range(2)])
          for a in range(2) for i, j in _TRI]
    qq = [entry([(a, o, ri, _mb_entry(bm8, a, ri, i),
                  _mb_entry(bm8, a, ri, j))
                 for a in range(2) for ri in range(2)])
          for o in range(2) for i, j in _TRI]
    pq = [entry([(a, o, ri, _ma_entry(a8, o, ri, i),
                  _mb_entry(bm8, a, ri, j)) for ri in range(2)])
          for a in range(2) for i in range(4)
          for o in range(2) for j in range(4)]
    return jnp.stack(pp), jnp.stack(qq), jnp.stack(pq)


@jax.named_scope("assemble")     # sage/sweep/assemble in a solve
def plane_equations(rows: RowPlanes, P, w8=None, cost_w8=None):
    """:func:`normal_equations` from row data in plane form: the weighted
    Gauss-Newton (JTJ [K, 8N, 8N], JTe [K, 8N], cost [K]) of ONE cluster
    with ``K`` chunks whose rows lie ``[tilesz, nbase]``
    (``rows.periodic``), at the stations' Jones ``P [K, N, 8]`` (real
    planes, :func:`jones_c2r` order).

    ``w8``: the sqrt-weight planes ``[8, *rows.rows]`` JTJ and JTe use
    (default ``rows.w``; a robust solve hands its curvature weights);
    ``cost_w8``: an optional second set for the cost alone
    (:func:`normal_equations`' ``cost_wt``).

    Real elementwise arithmetic on planes with the rows on the minor
    axes and a sum over each chunk's timeslots, nothing else at row
    size: the Jones are gathered for ``nbase`` rows a chunk and broadcast
    over time, ``A = C J_q^H``, ``Bm = J_p C`` and ``V`` come from
    :func:`row_model`, JTe is :func:`row_grad` of the twice-weighted
    residual, and the Gram blocks of a baseline are :func:`_gram_planes`'
    written-out products in the accumulation dtype. Only the
    ``K x nbase`` blocks are placed: the station-diagonal ones by one
    segment sum, the cross blocks by one scatter of ``K x nbase`` rows of
    64 into ``[K, N, N]`` station pairs, which a transposition brings to
    ``[K, 8N, 8N]`` and a second one symmetrizes. A caller that keeps
    JTJ alone (``rtr.make_hess``) leaves V, the residual, JTe and the
    cost to the compiler's dead-code pass."""
    if not rows.periodic or rows.c.ndim != 3:
        raise ValueError("plane_equations wants one cluster's rows laid "
                         "[tilesz, nbase] (RowPlanes.periodic)")
    N, K = rows.n_stations, rows.kmax
    # the chunks lead the station-sized arrays where there are several
    lead = (K,) if K > 1 else ()
    w8 = rows.w if w8 is None else w8
    jp8, jq8 = rows.gather(P)
    v8, a8, bm8 = row_model(jp8, jq8, rows.c)
    # the residual stream stays in the data's storage dtype; every
    # product below is made in the accumulation dtype
    r = rows.x - dtp.to_storage(v8, rows.x.dtype)
    rw = r * w8
    rca = dtp.acc(rw if cost_w8 is None else r * cost_w8)
    cost = rows.chunk_sum(rca * rca)
    wa = dtp.acc(w8)
    gp, gq = row_grad(wa * dtp.acc(rw), a8, bm8)
    JTe = rows.station_sum(rows.time_sum(gp), rows.time_sum(gq))
    pp, qq, pq = _gram_planes(wa * wa, a8, bm8, rows.time_sum)
    dt = pq.dtype
    # station-diagonal blocks: ten entries a block summed to the
    # stations, mirrored afterwards (so D is symmetric to the last digit)
    D = rows.station_sum(pp, qq).reshape(lead + (N, 2, 10))[..., _TRI_OF]
    eye2, eyeN = jnp.eye(2, dtype=dt), jnp.eye(N, dtype=dt)
    Dfull = (D[..., None, :]
             * eye2[None, :, None, :, None]).reshape(lead + (N, 8, 8))
    # cross blocks: [(a, i), (o, j)] of baseline (p, q) at (p, q) of its
    # chunk; the other triangle is the transpose of the whole matrix
    pair = rows.i1 * N + rows.i2
    if K > 1:       # both hold the chunk's offset: once is enough
        pair = pair - (jnp.arange(K, dtype=pair.dtype) * N)[:, None]
    U = jnp.zeros((K * N * N, 64), dt).at[pair].add(jnp.moveaxis(pq, 0, -1))
    Mx = jnp.swapaxes(U.reshape(lead + (N, N, 8, 8)), -3, -2).reshape(
        lead + (8 * N, 8 * N))
    JTJ = Mx + jnp.swapaxes(Mx, -1, -2) + (
        Dfull[..., None, :] * eyeN[:, None, :, None]).reshape(
            lead + (8 * N, 8 * N))
    return JTJ.reshape(K, 8 * N, 8 * N), JTe.reshape(K, 8 * N), cost


@jax.named_scope("assemble")     # sage/sweep/assemble in a solve
def normal_equations(x8, J, coh, sta1, sta2, chunk_id, wt, n_stations: int,
                     kmax: int, cost_wt=None, row_period: int = 0):
    """Weighted Gauss-Newton normal equations, batched over time chunks.

    Returns (JTJ [K, 8N, 8N], JTe [K, 8N], cost [K]) where the weighted cost
    is sum_b ||wt_b * r_b||^2. ``wt`` [B, 8] are sqrt-weights (0 for flagged
    rows; robust sqrt(w) for Student's-t IRLS, robustlm.c weighting).

    ``cost_wt``: optional second sqrt-weight set the COST output uses
    instead of ``wt`` while JTJ/JTe keep ``wt`` — the ordered-subsets LM
    body needs full-data acceptance costs next to subset normal
    equations (clmfit.c:1404), and sharing one residual/model evaluation
    between them is a full [B]-pass cheaper than two calls.

    ``row_period``: the visibility rows' baseline period — callers lay
    rows out as [tilesz, nbase] with sta1/sta2 repeating every ``nbase``
    rows (the same invariant :func:`lm.os_subset_ids` builds on) and the
    hybrid chunk of a row its timeslot's (``rime.predict.chunk_indices``).
    When set and dividing the rows (:func:`periodic_rows`), the rows go
    to plane form and the equations are :func:`plane_equations`': real
    elementwise arithmetic on ``[tilesz, nbase]`` planes summed over
    each chunk's timeslots, ``kmax x nbase`` blocks placed. 0 (any other
    chunk map) or a row count the period does not divide take the
    generic assembly below.

    The generic assembly: the per-baseline real Jacobians are never
    materialized. The Wirtinger blocks have only 16 independent reals
    each — Gp = I_2 (x) MA(A) over a == c and Gq = I_2 (x) MB(B) over
    o == c (A = C J_q^H, B = J_p C) — so all Gram products reduce to
    4x4 contractions of the [B, 2, 2, 4] factors with the squared
    weights folded in, scattered per (chunk, station[, station]), and
    the station-pair cross blocks are aggregated ONCE and symmetrized
    densely afterwards. tests/test_lm.py and
    tests/test_assemble_planes.py hold both against
    :func:`_normal_equations_dense`.

    Dtype policy: data arriving in a reduced storage dtype (bf16/f16,
    sagecal_tpu.dtypes) dispatches to the storage/accumulate assembly
    :func:`_normal_equations_reduced`.
    """
    if dtp.is_reduced(x8.dtype):
        return _normal_equations_reduced(x8, J, coh, sta1, sta2, chunk_id,
                                         wt, n_stations, kmax,
                                         cost_wt=cost_wt,
                                         row_period=row_period)
    N = n_stations
    B = x8.shape[0]
    if periodic_rows(row_period, B):
        rows = RowPlanes(x8, coh, wt, sta1, sta2, chunk_id, kmax, N,
                         row_period)
        return plane_equations(
            rows, jones_c2r(J),
            cost_w8=None if cost_wt is None else rows.planes(cost_wt))
    Jp = J[chunk_id, sta1]                         # [B, 2, 2]
    Jq = J[chunk_id, sta2]
    A = coh @ jnp.conj(jnp.swapaxes(Jq, -1, -2))   # dV/dJp factor
    Bm = Jp @ coh                                  # dV/dconj(Jq) factor
    V = Jp @ A                                     # = Jp C Jq^H
    vf = V.reshape(-1, 4)
    r = x8 - jnp.stack([vf.real, vf.imag], -1).reshape(-1, 8)
    rw = r * wt
    MA = _ma_factor(A)                             # [B, o, ri, 4]
    MB = _mb_factor(Bm)                            # [B, a, ri, 4]
    rc = rw if cost_wt is None else r * cost_wt

    w2 = (wt * wt).reshape(B, 2, 2, 2)             # [B, a, o, ri]
    rw2 = (rw * wt).reshape(B, 2, 2, 2)            # w^2 r
    # Gram blocks: station-diagonal [4, 4] sub-blocks (block-diag
    # over the first complex index) + the full [2, 2, 4, 4] cross
    # block. The weights are folded into ONE [B, 2, 2, 2, 4]
    # product each so every contraction below is a plain batched
    # dot_general — a naive 3-operand einsum materializes
    # [B, .., 4, 4] broadcast intermediates that double the traffic
    # of this whole function.
    WMA = w2[..., None] * MA[:, None]              # [B, a, o, ri, 4]
    WMB = w2[..., None] * MB[:, :, None]           # [B, a, o, ri, 4]
    pp = jnp.einsum("baori,borj->baij", WMA, MA)   # [B, 2, 4, 4]
    qq = jnp.einsum("baorj,bari->boij", WMB, MB)
    pq = jnp.einsum("baori,barj->baoij", WMA, MB)  # [B,2,2,4,4]
    jtep = jnp.einsum("baor,bori->bai", rw2, MA)   # [B, 2, 4]
    jteq = jnp.einsum("baor,bari->boi", rw2, MB)

    # aggregate per (chunk, station[, station]) BEFORE the 8x8
    # expansion
    D = jnp.zeros((kmax, N, 2, 4, 4), rw.dtype)
    D = D.at[chunk_id, sta1].add(pp)
    D = D.at[chunk_id, sta2].add(qq)
    O = jnp.zeros((kmax, N, N, 2, 2, 4, 4), rw.dtype)
    O = O.at[chunk_id, sta1, sta2].add(pq)
    JTe = jnp.zeros((kmax, N, 2, 4), rw.dtype)
    JTe = JTe.at[chunk_id, sta1].add(jtep)
    JTe = JTe.at[chunk_id, sta2].add(jteq)
    cost = jnp.zeros((kmax,), rw.dtype).at[chunk_id].add(
        jnp.sum(rc * rc, axis=1))

    # dense expansion (tiny next to the [B]-length passes above):
    # off-diagonal station blocks [8, 8] = pq blocks at (row c, col c'),
    # symmetrized from the single aggregated scatter; station-diagonal
    # blocks are block-diag embeddings of D
    Off = O.transpose(0, 1, 2, 3, 5, 4, 6).reshape(kmax, N, N, 8, 8)
    JTJ = Off + jnp.swapaxes(jnp.swapaxes(Off, 1, 2), -1, -2)
    eye2 = jnp.eye(2, dtype=rw.dtype)
    Dfull = jnp.einsum("knaij,ab->knaibj", D, eye2).reshape(kmax, N, 8, 8)
    idx = jnp.arange(N)
    JTJ = JTJ.at[:, idx, idx].add(Dfull)
    JTJ = JTJ.transpose(0, 1, 3, 2, 4).reshape(kmax, 8 * N, 8 * N)

    return JTJ, JTe.reshape(kmax, 8 * N), cost


def weighted_cost(x8, J, coh, sta1, sta2, chunk_id, wt, kmax: int):
    """Weighted residual cost per chunk [K] (no Jacobians). The norm
    reduction accumulates in the policy's accumulator dtype (dtp.acc is
    the identity for f32/f64 data)."""
    r = dtp.acc(residual8(x8, J, coh, sta1, sta2, chunk_id) * wt)
    return jnp.zeros((kmax,), r.dtype).at[chunk_id].add(jnp.sum(r * r, axis=1))


# ---------------------------------------------------------------------------
# matrix-free Gauss-Newton operator (inexact-Newton inner solver)
#
# The damped normal system (JTJ + mu I [+ rho I]) dp = JTe never needs the
# [K, 8N, 8N] matrix: JTJ is the Gram of the block-sparse weighted real
# Jacobian whose only free parts are the two [B, 2, 2, 4] Wirtinger
# factors MA/MB (see the module docstring). A Krylov solver therefore
# needs exactly (a) those factors + the squared weights, (b) the
# gradient/cost (one assembly-like [B]-pass, minus the station-pair
# cross-block scatter the dense expansion pays), and (c) the
# [K, N, 2, 4, 4] station-diagonal blocks D as a block-Jacobi
# preconditioner. Each matvec is then one [B]-pass of batched dot
# products — no O((8N)^2) residency, no O((8N)^3) triangular work.
# ---------------------------------------------------------------------------


class GNFactors(NamedTuple):
    """Per-iteration invariants of the matrix-free GN operator.

    MA/MB: [B, 2, 2, 4] unweighted Wirtinger factors of the current
    point (MA[b, o, ri, j], MB[b, a, ri, j] — see _ma_factor/_mb_factor);
    w2: [B, 2, 2, 2] squared sqrt-weights laid out (a, o, ri);
    D: [K, N, 2, 4, 4] weight-folded station-diagonal Gram blocks — the
    dense JTJ's [8, 8] station-diagonal block is block_diag(D[k,n,0],
    D[k,n,1]) (the preconditioner AND the mu0 = tau*max(diag) seed).
    """

    MA: jax.Array
    MB: jax.Array
    w2: jax.Array
    D: jax.Array


@jax.named_scope("assemble")     # sage/sweep/assemble in a solve
def gn_factors(x8, J, coh, sta1, sta2, chunk_id, wt, n_stations: int,
               kmax: int, cost_wt=None, row_period=0):
    """Matrix-free analogue of :func:`normal_equations`.

    Same weighted Gauss-Newton linearization, but instead of the dense
    (JTJ, JTe, cost) it returns (:class:`GNFactors`, JTe [K, 8N],
    cost [K]) from ONE [B]-pass — everything :func:`gn_matvec` and the
    station-block preconditioner need, skipping the [K, N, N, 2, 2, 4, 4]
    cross-block scatter and the [K, 8N, 8N] dense expansion entirely.
    ``cost_wt``/``row_period`` follow normal_equations (the OS body's
    shared acceptance cost; the baseline-major aggregation for
    single-chunk clusters).

    Dtype policy: reduced-storage data (bf16/f16) keeps MA/MB/w2 in the
    storage dtype — the matrix-free operator's per-row factors are
    exactly the arrays the traffic melt targets — while D/JTe/cost
    accumulate f32 (``preferred_element_type`` on every contraction).
    All casts below are identities for f32/f64 data.
    """
    N = n_stations
    B = x8.shape[0]
    st = x8.dtype
    acc = dtp.acc_dtype(st)
    pet = dtp.pet(st)
    Jp = J[chunk_id, sta1]
    Jq = J[chunk_id, sta2]
    A = coh @ jnp.conj(jnp.swapaxes(Jq, -1, -2))
    Bm = Jp @ coh
    V = Jp @ A
    vf = V.reshape(-1, 4)
    r = x8 - dtp.to_storage(
        jnp.stack([vf.real, vf.imag], -1).reshape(-1, 8), st)
    rw = r * wt
    MA = dtp.to_storage(_ma_factor(A), st)         # [B, o, ri, 4]
    MB = dtp.to_storage(_mb_factor(Bm), st)        # [B, a, ri, 4]
    rc = rw if cost_wt is None else r * cost_wt
    rca = dtp.acc(rc)
    w2 = (wt * wt).reshape(B, 2, 2, 2)             # [B, a, o, ri]

    if kmax == 1 and row_period > 0 and B % row_period == 0:
        # baseline-major aggregation (normal_equations fast path, minus
        # the cross blocks): every Gram/gradient product contracts over
        # the time axis straight onto [nbase, ...] station blocks
        T = B // row_period
        nb = row_period
        wv = wt.reshape(T, nb, 2, 2, 2)
        WMAh = wv[..., None] * MA.reshape(T, nb, 1, 2, 2, 4)
        WMBh = wv[..., None] * MB.reshape(T, nb, 2, 1, 2, 4)
        rwv = rw.reshape(T, nb, 2, 2, 2)
        pp = jnp.einsum("tnaori,tnaorj->naij", WMAh, WMAh, **pet)
        qq = jnp.einsum("tnaori,tnaorj->noij", WMBh, WMBh, **pet)
        jtep = jnp.einsum("tnaori,tnaor->nai", WMAh, rwv, **pet)
        jteq = jnp.einsum("tnaori,tnaor->noi", WMBh, rwv, **pet)
        s1b, s2b = sta1[:nb], sta2[:nb]
        D = jnp.zeros((1, N, 2, 4, 4), acc)
        D = D.at[0, s1b].add(pp).at[0, s2b].add(qq)
        JTe = jnp.zeros((1, N, 2, 4), acc)
        JTe = JTe.at[0, s1b].add(jtep).at[0, s2b].add(jteq)
        cost = jnp.sum(rca * rca).reshape(1)
    else:
        rw2 = (rw * wt).reshape(B, 2, 2, 2)        # w^2 r
        WMA = w2[..., None] * MA[:, None]          # [B, a, o, ri, 4]
        WMB = w2[..., None] * MB[:, :, None]
        pp = jnp.einsum("baori,borj->baij", WMA, MA, **pet)
        qq = jnp.einsum("baorj,bari->boij", WMB, MB, **pet)
        jtep = jnp.einsum("baor,bori->bai", rw2, MA, **pet)
        jteq = jnp.einsum("baor,bari->boi", rw2, MB, **pet)
        D = jnp.zeros((kmax, N, 2, 4, 4), acc)
        D = D.at[chunk_id, sta1].add(pp)
        D = D.at[chunk_id, sta2].add(qq)
        JTe = jnp.zeros((kmax, N, 2, 4), acc)
        JTe = JTe.at[chunk_id, sta1].add(jtep)
        JTe = JTe.at[chunk_id, sta2].add(jteq)
        cost = jnp.zeros((kmax,), acc).at[chunk_id].add(
            jnp.sum(rca * rca, axis=1))

    return GNFactors(MA=MA, MB=MB, w2=w2, D=D), \
        JTe.reshape(kmax, 8 * N), cost


def gn_matvec(fac: GNFactors, v, sta1, sta2, chunk_id, kmax: int,
              n_stations: int, shift=None, row_period: int = 0):
    """(JTJ + shift I) @ v without materializing JTJ: one [B]-pass.

    ``v``: [K, 8N] (the parameter layout of :func:`normal_equations`'s
    JTe — station-major, 8 reals per station). ``shift``: [K] (or
    scalar) diagonal shift — callers fold mu + jitter and the ADMM rho
    here; None adds nothing. The product is computed directly from the
    Wirtinger factors: u = J v via MA/MB (Gp/Gq are block-diagonal over
    one complex index each, so both halves are [B, 2, 4]x[B, 2, 2, 4]
    batched dots), then y = J^T (w^2 u) scatters back through the same
    factors. ``row_period`` enables the baseline-major time-axis
    contraction for single-chunk clusters (same invariant as
    normal_equations).
    """
    N = n_stations
    B = fac.MA.shape[0]
    st = fac.MA.dtype
    pet = dtp.pet(st)
    vr = v.reshape(kmax, N, 2, 4)
    if kmax == 1 and row_period > 0 and B % row_period == 0:
        T = B // row_period
        nb = row_period
        s1b, s2b = sta1[:nb], sta2[:nb]
        MA_r = fac.MA.reshape(T, nb, 2, 2, 4)      # [t, n, o, ri, j]
        MB_r = fac.MB.reshape(T, nb, 2, 2, 4)      # [t, n, a, ri, j]
        # storage-dtype Krylov operands (identity for f32/f64): under a
        # reduced policy the per-product quantization of v rides the
        # same trajectory-tolerance contract as the factors themselves
        vpn = dtp.to_storage(vr[0, s1b], st)       # [n, a, j]
        vqn = dtp.to_storage(vr[0, s2b], st)       # [n, o, j]
        u = (jnp.einsum("tnorj,naj->tnaor", MA_r, vpn, **pet)
             + jnp.einsum("tnarj,noj->tnaor", MB_r, vqn, **pet))
        uw = dtp.to_storage(u * fac.w2.reshape(T, nb, 2, 2, 2), st)
        ypn = jnp.einsum("tnaor,tnorj->naj", uw, MA_r, **pet)
        yqn = jnp.einsum("tnaor,tnarj->noj", uw, MB_r, **pet)
        y = jnp.zeros((1, N, 2, 4), v.dtype)
        y = y.at[0, s1b].add(ypn).at[0, s2b].add(yqn)
    else:
        vp = dtp.to_storage(vr[chunk_id, sta1], st)   # [B, a, j]
        vq = dtp.to_storage(vr[chunk_id, sta2], st)   # [B, o, j]
        # u[b, a, o, ri] = (J v)_b: station-p block contracts MA over
        # its 4 free columns (block-diag over a), station-q over MB
        u = (jnp.einsum("borj,baj->baor", fac.MA, vp, **pet)
             + jnp.einsum("barj,boj->baor", fac.MB, vq, **pet))
        uw = dtp.to_storage(u * fac.w2, st)
        yp = jnp.einsum("baor,borj->baj", uw, fac.MA, **pet)
        yq = jnp.einsum("baor,barj->boj", uw, fac.MB, **pet)
        y = jnp.zeros((kmax, N, 2, 4), v.dtype)
        y = y.at[chunk_id, sta1].add(yp).at[chunk_id, sta2].add(yq)
    y = y.reshape(kmax, 8 * N)
    if shift is not None:
        y = y + jnp.asarray(shift)[..., None] * v
    return y


def gn_precond_factor(D, shift):
    """Batched tiny Cholesky of the station-block preconditioner.

    M = block_diag over (k, n, a) of (D[k, n, a] + shift_k I) — the
    EXACT station-diagonal blocks of (JTJ + shift I) (see
    :class:`GNFactors`), factored as [K, N, 2] independent mdim x mdim
    Cholesky decompositions (mdim = 4 full / 2 diag / 1 phase — read
    off D's trailing shape, so the full path traces identically).
    Returns the (L, lower) pair for :func:`gn_precond_apply`.
    ``shift``: [K] (mu + jitter [+ rho]) — always > 0 on the solve
    path, so M is PD even for stations with no usable rows in a chunk.
    """
    eye = jnp.eye(D.shape[-1], dtype=D.dtype)
    A = D + jnp.asarray(shift)[..., None, None, None, None] * eye
    return jax.scipy.linalg.cho_factor(A, lower=True)


def gn_precond_apply(Lfac, r, kmax: int, n_stations: int):
    """z = M^-1 r with the factored station-block preconditioner.

    The per-station block width (mdim) comes off the factor's static
    shape, so reduced-mode solves (:func:`gn_factors_mode`) ride the
    same apply and the full path stays bit-frozen."""
    md = Lfac[0].shape[-1]
    rr = r.reshape(kmax, n_stations, 2, md)
    z = jax.scipy.linalg.cho_solve(Lfac, rr[..., None])[..., 0]
    return z.reshape(kmax, 2 * md * n_stations)


# ---------------------------------------------------------------------------
# Constrained-Jones parameterizations (jones_mode in {full, diag, phase})
#
# CubiCal-style constrained terms (arXiv:1805.03410) as a PROJECTION of the
# existing Wirtinger factors, not a new solver. Per station the real
# parameter vector shrinks 8 -> 4 (diag: Re/Im of j00, j11) -> 2 (phase:
# theta0, theta1 with J(theta) = diag(J0) * exp(i theta), amplitudes
# frozen at the entry Jones). The Gram structure is unchanged: the
# station-p Jacobian block stays block-diagonal over the diagonal index c
# (full mode: the complex row a), with an inner mdim-wide factor
#
#   Gp[b, (a, o, ri), (c, m)] = delta_{ac} * FA[b, c, o, ri, m]
#   Gq[b, (a, o, ri), (c, m)] = delta_{oc} * FB[b, c, a, ri, m]
#
# mdim = 4 (full, FA == MA independent of c) / 2 (diag) / 1 (phase), so
# every per-station Gram block is [2, mdim, mdim] and the per-baseline
# cross block [2, 2, mdim, mdim] — 8x8-real melting to 2x2 for phase.
# The full-mode functions above are byte-untouched; the *_mode entry
# points below delegate to them verbatim when mode == "full".
# ---------------------------------------------------------------------------

#: valid RunConfig.jones_mode / --jones values
JONES_MODES = ("full", "diag", "phase")

#: positions of the diag-mode parameters inside the full 8-real station
#: vector (jones_c2r layout): (Re j00, Im j00, Re j11, Im j11)
_DIAG_IDX = (0, 1, 6, 7)


def jones_mdim(mode: str) -> int:
    """Per-(station, diagonal-index) Gram block width for ``mode``."""
    return {"full": 4, "diag": 2, "phase": 1}[mode]


def jones_npar(mode: str) -> int:
    """Real parameters per station for ``mode`` (2 * mdim)."""
    return 2 * jones_mdim(mode)


def jones_constrain(J, mode: str):
    """Project a Jones chain onto the mode's feasible set (zero the
    off-diagonal entries for diag/phase; identity for full)."""
    if mode == "full":
        return J
    return J * jnp.eye(2, dtype=J.real.dtype)


def params_from_jones(J, mode: str):
    """[..., 2, 2] complex Jones -> [..., npar] reduced real params.

    phase mode encodes the ZERO rotation (theta = 0): the caller holds
    the constrained entry Jones as the amplitude reference ``Jref``
    and retracts multiplicatively via :func:`jones_from_params`.
    """
    if mode == "full":
        return jones_c2r(J)
    if mode == "diag":
        return jones_c2r(J)[..., jnp.array(_DIAG_IDX)]
    return jnp.zeros(J.shape[:-2] + (2,), J.real.dtype)


def jones_from_params(p, mode: str, Jref=None):
    """[..., npar] reduced real params -> [..., 2, 2] complex Jones.

    diag: additive coordinates on the diagonal entries. phase: the
    manifold retraction J(theta) = diag(Jref) * exp(i theta) — the
    accumulated-rotation parameterization whose additive update
    ``p + dp`` IS the multiplicative phase retraction.
    """
    if mode == "full":
        return jones_r2c(p)
    if mode == "diag":
        d0 = p[..., 0] + 1j * p[..., 1]
        d1 = p[..., 2] + 1j * p[..., 3]
    else:
        rot = jnp.exp(1j * p)
        d0 = Jref[..., 0, 0] * rot[..., 0]
        d1 = Jref[..., 1, 1] * rot[..., 1]
    z = jnp.zeros_like(d0)
    return jnp.stack([jnp.stack([d0, z], -1),
                      jnp.stack([z, d1], -1)], -2)


def _mode_factors(A, Bm, Jp, Jq, mode: str):
    """Reduced Wirtinger factors (FA, FB), each [B, 2, 2, 2, mdim].

    FA[b, c, o, ri, m] = d(V[c, o])_ri / d(p-param (c, m));
    FB[b, c, a, ri, m] = d(V[a, c])_ri / d(q-param (c, m)).
    A = C Jq^H (A[d, o]), Bm = Jp C (Bm[a, d]) as in the full path;
    diag/phase only touch the d == c planes.
    """
    if mode == "diag":
        # complex-linear in j_cc: columns (Re, Im) exactly like the
        # d == c entries of _ma_factor / _mb_factor
        Ar, Ai = A.real, A.imag                        # [B, c, o]
        FA = jnp.stack([jnp.stack([Ar, -Ai], -1),      # ri = Re
                        jnp.stack([Ai, Ar], -1)], -2)  # ri = Im
        Br = jnp.swapaxes(Bm.real, -1, -2)             # [B, c, a]
        Bi = jnp.swapaxes(Bm.imag, -1, -2)
        FB = jnp.stack([jnp.stack([Br, Bi], -1),
                        jnp.stack([Bi, -Br], -1)], -2)
        return FA, FB
    # phase: dV[c, o]/dtheta_p_c = i * Jp_cc * A[c, o]
    #        dV[a, c]/dtheta_q_c = -i * conj(Jq_cc) * Bm[a, c]
    jpd = jnp.stack([Jp[..., 0, 0], Jp[..., 1, 1]], -1)    # [B, c]
    jqd = jnp.stack([Jq[..., 0, 0], Jq[..., 1, 1]], -1)
    u = jpd[..., None] * A                                 # [B, c, o]
    w = jnp.conj(jqd)[..., None] * jnp.swapaxes(Bm, -1, -2)
    FA = jnp.stack([-u.imag, u.real], -1)[..., None]       # [B,c,o,ri,1]
    FB = jnp.stack([w.imag, -w.real], -1)[..., None]
    return FA, FB


def _mode_blocks(FA, FB, w2, rw2, pet):
    """Per-baseline reduced Gram/gradient blocks from the mode factors.

    Returns (pp [B, 2, md, md], qq, pq [B, 2, 2, md, md],
    jtep [B, 2, md], jteq) — the direct analogue of the full path's
    4x4 contractions, with the station-diagonal index c explicit.
    ``w2``/``rw2``: [B, a, o, ri] squared weights / w^2 r.
    """
    WFA = w2[..., None] * FA                       # [B, c, o, ri, md]
    w2q = jnp.swapaxes(w2, 1, 2)                   # [B, o, a, ri]
    WFB = w2q[..., None] * FB                      # [B, c, a, ri, md]
    pp = jnp.einsum("bcorm,bcorn->bcmn", WFA, FA, **pet)
    qq = jnp.einsum("bcarm,bcarn->bcmn", WFB, FB, **pet)
    # pq[(c, m), (c', n)] = sum_ri w2[c, c', ri] FA[c, c', ri, m]
    #                        * FB[c', c, ri, n]
    FBy = jnp.swapaxes(FB, 1, 2)                   # [B, c, c', ri, n]
    pq = jnp.einsum("bcorm,bcorn->bcomn", WFA, FBy, **pet)
    jtep = jnp.einsum("bcor,bcorm->bcm", rw2, FA, **pet)
    rw2q = jnp.swapaxes(rw2, 1, 2)
    jteq = jnp.einsum("bcar,bcarm->bcm", rw2q, FB, **pet)
    return pp, qq, pq, jtep, jteq


def _mode_dense(pp, qq, pq, jtep, jteq, sta1, sta2, chunk_id,
                kmax: int, N: int, acc):
    """Scatter per-baseline reduced blocks into the dense station-major
    normal equations: (JTJ [K, npar N, npar N], JTe [K, npar N])."""
    md = pp.shape[-1]
    npar = 2 * md
    D = jnp.zeros((kmax, N, 2, md, md), acc)
    D = D.at[chunk_id, sta1].add(pp)
    D = D.at[chunk_id, sta2].add(qq)
    O = jnp.zeros((kmax, N, N, 2, 2, md, md), acc)
    O = O.at[chunk_id, sta1, sta2].add(pq)
    JTe = jnp.zeros((kmax, N, 2, md), acc)
    JTe = JTe.at[chunk_id, sta1].add(jtep)
    JTe = JTe.at[chunk_id, sta2].add(jteq)
    Off = O.transpose(0, 1, 2, 3, 5, 4, 6).reshape(kmax, N, N, npar, npar)
    JTJ = Off + jnp.swapaxes(jnp.swapaxes(Off, 1, 2), -1, -2)
    eye2 = jnp.eye(2, dtype=acc)
    Dfull = jnp.einsum("knaij,ab->knaibj", D, eye2).reshape(
        kmax, N, npar, npar)
    idx = jnp.arange(N)
    JTJ = JTJ.at[:, idx, idx].add(Dfull)
    JTJ = JTJ.transpose(0, 1, 3, 2, 4).reshape(kmax, npar * N, npar * N)
    return JTJ, JTe.reshape(kmax, npar * N)


@jax.named_scope("assemble")     # sage/sweep/assemble in a solve
def normal_equations_mode(x8, J, coh, sta1, sta2, chunk_id, wt,
                          n_stations: int, kmax: int, mode: str = "full",
                          cost_wt=None, row_period: int = 0):
    """Mode-aware :func:`normal_equations`: reduced-dimension
    (JTJ [K, npar N, npar N], JTe, cost [K]) for diag/phase; verbatim
    delegation (bit-frozen) for full. ``J`` is projected onto the
    mode's feasible set at entry, so the factor algebra's diagonal
    assumption always holds. Weights are arbitrary (OS masks and IRLS
    sqrt-weights ride through unchanged); ``cost_wt`` keeps the
    full-data acceptance-cost contract of the full path.
    """
    if mode == "full":
        return normal_equations(x8, J, coh, sta1, sta2, chunk_id, wt,
                                n_stations, kmax, cost_wt=cost_wt,
                                row_period=row_period)
    N = n_stations
    B = x8.shape[0]
    st = x8.dtype
    acc = dtp.acc_dtype(st)
    pet = dtp.pet(st)
    J = jones_constrain(J, mode)
    Jp = J[chunk_id, sta1]
    Jq = J[chunk_id, sta2]
    A = coh @ jnp.conj(jnp.swapaxes(Jq, -1, -2))
    Bm = Jp @ coh
    V = Jp @ A
    vf = V.reshape(-1, 4)
    r = x8 - dtp.to_storage(
        jnp.stack([vf.real, vf.imag], -1).reshape(-1, 8), st)
    rw = r * wt
    FA, FB = _mode_factors(A, Bm, Jp, Jq, mode)
    FA = dtp.to_storage(FA, st)
    FB = dtp.to_storage(FB, st)
    rc = rw if cost_wt is None else r * cost_wt
    rca = dtp.acc(rc)
    w2 = (wt * wt).reshape(B, 2, 2, 2)
    rw2 = (rw * wt).reshape(B, 2, 2, 2)
    pp, qq, pq, jtep, jteq = _mode_blocks(FA, FB, w2, rw2, pet)
    JTJ, JTe = _mode_dense(pp, qq, pq, jtep, jteq, sta1, sta2,
                           chunk_id, kmax, N, acc)
    cost = jnp.zeros((kmax,), acc).at[chunk_id].add(
        jnp.sum(rca * rca, axis=1))
    return JTJ, JTe, cost


@jax.named_scope("assemble")     # sage/sweep/assemble in a solve
def os_subset_equations_mode(x8, J, coh, sta1, sta2, wt, os_id, subset,
                             ntper: int, row_period: int,
                             n_stations: int, cost_wt,
                             mode: str = "full"):
    """Mode-aware :func:`os_subset_equations` (reduced-dtype OS body):
    full delegates verbatim; diag/phase assemble the reduced blocks
    from the subset's rows only, keeping the one whole-[B] model pass
    for the acceptance cost."""
    if mode == "full":
        return os_subset_equations(x8, J, coh, sta1, sta2, wt, os_id,
                                   subset, ntper, row_period,
                                   n_stations, cost_wt)
    N = n_stations
    B = x8.shape[0]
    st = x8.dtype
    acc = dtp.acc_dtype(st)
    pet = dtp.pet(st)
    nb = row_period
    os_id = jnp.asarray(os_id)
    bs = ntper * nb
    start = jnp.minimum(subset * bs, B - bs)
    J = jones_constrain(J, mode)
    Jp = J[0][sta1]                            # kmax == 1
    Jq = J[0][sta2]
    Bm = Jp @ coh
    V = Bm @ jnp.conj(jnp.swapaxes(Jq, -1, -2))
    vf = V.reshape(-1, 4)
    r = x8 - jnp.stack([vf.real, vf.imag], -1).reshape(-1, 8).astype(st)
    rca = (r * cost_wt).astype(acc)
    cost = jnp.sum(rca * rca).reshape(1)
    sl = lambda a: jax.lax.dynamic_slice_in_dim(a, start, bs, 0)
    wts = sl(wt) * (sl(os_id) == subset).astype(st)[:, None]
    rs = sl(r)
    cohs = sl(coh)
    Jps = sl(Jp)
    Jqs = sl(Jq)
    As = cohs @ jnp.conj(jnp.swapaxes(Jqs, -1, -2))
    Bms = sl(Bm)
    FA, FB = _mode_factors(As, Bms, Jps, Jqs, mode)
    FA = FA.astype(st)
    FB = FB.astype(st)
    rws = rs * wts
    w2 = (wts * wts).reshape(bs, 2, 2, 2)
    rw2 = (rws * wts).reshape(bs, 2, 2, 2)
    pp, qq, pq, jtep, jteq = _mode_blocks(FA, FB, w2, rw2, pet)
    zc = jnp.zeros((bs,), jnp.int32)
    JTJ, JTe = _mode_dense(pp, qq, pq, jtep, jteq, sl(sta1), sl(sta2),
                           zc, 1, N, acc)
    return JTJ, JTe, cost


class GNFactorsMode(NamedTuple):
    """Reduced-mode analogue of :class:`GNFactors` (diag/phase).

    FA/FB: [B, 2, 2, 2, mdim] mode Wirtinger factors
    (:func:`_mode_factors` layout); w2: [B, 2, 2, 2] squared
    sqrt-weights (a, o, ri); D: [K, N, 2, mdim, mdim] station-diagonal
    Gram blocks (preconditioner + mu0 seed, exactly like the full
    operator's).
    """

    FA: jax.Array
    FB: jax.Array
    w2: jax.Array
    D: jax.Array


@jax.named_scope("assemble")     # sage/sweep/assemble in a solve
def gn_factors_mode(x8, J, coh, sta1, sta2, chunk_id, wt,
                    n_stations: int, kmax: int, mode: str = "full",
                    cost_wt=None, row_period=0):
    """Mode-aware :func:`gn_factors`: (:class:`GNFactorsMode`,
    JTe [K, npar N], cost [K]) for diag/phase from one [B]-pass; full
    delegates verbatim (bit-frozen, returns :class:`GNFactors`)."""
    if mode == "full":
        return gn_factors(x8, J, coh, sta1, sta2, chunk_id, wt,
                          n_stations, kmax, cost_wt=cost_wt,
                          row_period=row_period)
    N = n_stations
    B = x8.shape[0]
    st = x8.dtype
    acc = dtp.acc_dtype(st)
    pet = dtp.pet(st)
    md = jones_mdim(mode)
    J = jones_constrain(J, mode)
    Jp = J[chunk_id, sta1]
    Jq = J[chunk_id, sta2]
    A = coh @ jnp.conj(jnp.swapaxes(Jq, -1, -2))
    Bm = Jp @ coh
    V = Jp @ A
    vf = V.reshape(-1, 4)
    r = x8 - dtp.to_storage(
        jnp.stack([vf.real, vf.imag], -1).reshape(-1, 8), st)
    rw = r * wt
    FA, FB = _mode_factors(A, Bm, Jp, Jq, mode)
    FA = dtp.to_storage(FA, st)
    FB = dtp.to_storage(FB, st)
    rc = rw if cost_wt is None else r * cost_wt
    rca = dtp.acc(rc)
    w2 = (wt * wt).reshape(B, 2, 2, 2)
    rw2 = (rw * wt).reshape(B, 2, 2, 2)
    WFA = w2[..., None] * FA
    w2q = jnp.swapaxes(w2, 1, 2)
    WFB = w2q[..., None] * FB
    pp = jnp.einsum("bcorm,bcorn->bcmn", WFA, FA, **pet)
    qq = jnp.einsum("bcarm,bcarn->bcmn", WFB, FB, **pet)
    jtep = jnp.einsum("bcor,bcorm->bcm", rw2, FA, **pet)
    rw2q = jnp.swapaxes(rw2, 1, 2)
    jteq = jnp.einsum("bcar,bcarm->bcm", rw2q, FB, **pet)
    D = jnp.zeros((kmax, N, 2, md, md), acc)
    D = D.at[chunk_id, sta1].add(pp)
    D = D.at[chunk_id, sta2].add(qq)
    JTe = jnp.zeros((kmax, N, 2, md), acc)
    JTe = JTe.at[chunk_id, sta1].add(jtep)
    JTe = JTe.at[chunk_id, sta2].add(jteq)
    cost = jnp.zeros((kmax,), acc).at[chunk_id].add(
        jnp.sum(rca * rca, axis=1))
    return GNFactorsMode(FA=FA, FB=FB, w2=w2, D=D), \
        JTe.reshape(kmax, 2 * md * N), cost


def gn_matvec_mode(fac: GNFactorsMode, v, sta1, sta2, chunk_id,
                   kmax: int, n_stations: int, shift=None):
    """(JTJ + shift I) @ v through the reduced factors: one [B]-pass
    of mdim-wide batched dots — the matrix-free operator the PCG/tCG
    inner solvers ride under diag/phase modes."""
    N = n_stations
    md = fac.FA.shape[-1]
    st = fac.FA.dtype
    pet = dtp.pet(st)
    vr = v.reshape(kmax, N, 2, md)
    vp = dtp.to_storage(vr[chunk_id, sta1], st)    # [B, c, m]
    vq = dtp.to_storage(vr[chunk_id, sta2], st)
    u = (jnp.einsum("baorm,bam->baor", fac.FA, vp, **pet)
         + jnp.einsum("boarm,bom->baor", fac.FB, vq, **pet))
    uw = dtp.to_storage(u * fac.w2, st)
    yp = jnp.einsum("baor,baorm->bam", uw, fac.FA, **pet)
    yq = jnp.einsum("baor,boarm->bom", uw, fac.FB, **pet)
    y = jnp.zeros((kmax, N, 2, md), v.dtype)
    y = y.at[chunk_id, sta1].add(yp).at[chunk_id, sta2].add(yq)
    y = y.reshape(kmax, 2 * md * N)
    if shift is not None:
        y = y + jnp.asarray(shift)[..., None] * v
    return y
