"""2 x 2 complex algebra on real planes, the rows on the minor axes.

The measurement model of a row is ``V = J_p C J_q^H`` with 2 x 2 complex
factors: sixteen complex multiply-adds. A 2 x 2 product fed to a
128 x 128 systolic array at f32 ``highest`` costs a hundred times its
arithmetic, and a ``[B, 2, 2]`` array tiles with 64 times its bytes in
padding. So every program that evaluates the model at the size of the
rows does it here: a 2 x 2 complex matrix is EIGHT REAL PLANES, (Re, Im)
of the entries 00, 01, 10, 11 (the order of :func:`jones_c2r`, which is
the data's XX, XY, YX, YY (re, im): Dirac.h:1541-1546) on the LEADING
axis, whatever follows it (clusters, channels, ``[tilesz, nbase]`` rows)
on the others, and a product is real elementwise arithmetic on them.
Nothing in this module is a contraction, so nothing reaches the matrix
unit.

The ONE copy of those multiply-adds: the solvers (``solvers/normal_eq``,
``solvers/rtr``, ``solvers/sage``: model, Wirtinger factors, gradient and
tangent) and the programs that leave them (``rime/predict.predict_model``:
the residual and the simulated column) both evaluate the form through
:func:`mm`.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def jones_c2r(J):
    """[..., 2, 2] complex -> [..., 8] real (Re,Im interleaved, row-major)."""
    flat = J.reshape(J.shape[:-2] + (4,))
    return jnp.stack([flat.real, flat.imag], axis=-1).reshape(
        J.shape[:-2] + (8,))


def jones_r2c(p):
    """[..., 8] real -> [..., 2, 2] complex."""
    pr = p.reshape(p.shape[:-1] + (4, 2))
    return (pr[..., 0] + 1j * pr[..., 1]).reshape(p.shape[:-1] + (2, 2))


def mm(a, b, adj_a: bool = False, adj_b: bool = False):
    """The eight real planes of the 2 x 2 complex product op(a) op(b),
    op = identity or conjugate transpose, by written-out multiply-adds.

    ``a``, ``b``: eight real planes each ((Re, Im) of 00, 01, 10, 11,
    the :func:`jones_c2r` order) that broadcast against each other."""
    def entry(m, i, j, adj):
        k = 2 * (2 * j + i if adj else 2 * i + j)
        return m[k], (-m[k + 1] if adj else m[k + 1])

    out = []
    for i in range(2):
        for j in range(2):
            (ar, ai), (br, bi) = entry(a, i, 0, adj_a), entry(b, 0, j, adj_b)
            (cr, ci), (dr, di) = entry(a, i, 1, adj_a), entry(b, 1, j, adj_b)
            out += [ar * br - ai * bi + cr * dr - ci * di,
                    ar * bi + ai * br + cr * di + ci * dr]
    return jnp.stack(out)


def row_model(jp8, jq8, c8):
    """The row model on real planes: (V, A, Bm), eight planes each, of
    V = J_p C J_q^H and the Wirtinger factors A = C J_q^H, Bm = J_p C it
    computes on the way.

    ``jp8``, ``jq8``, ``c8``: the gathered Jones of both stations and the
    coherency as ``[8, *rows]`` real planes (:func:`jones_c2r` order, the
    ROWS ON THE MINOR AXES; they may broadcast against each other).
    Real elementwise arithmetic only: nothing here is a contraction, so
    nothing reaches the matrix unit and the planes tile without the 64x
    padding of a ``[B, 2, 2]`` array."""
    a8 = mm(c8, jq8, adj_b=True)
    return mm(jp8, a8), a8, mm(jp8, c8)


def row_grad(g8, a8, bm8):
    """(G A^H, G^H Bm) on planes: with G the complex form of a row's
    cost derivative dc/dV, the row's share of dc/dJ_p and of dc/dJ_q
    (dV = dJ_p A + Bm dJ_q^H, Re tr(G^H dV) = Re tr((G A^H)^H dJ_p)
    + Re tr((G^H Bm)^H dJ_q))."""
    return mm(g8, a8, adj_b=True), mm(g8, bm8, adj_a=True)


def row_tangent(dp8, dq8, a8, bm8):
    """dV = dJ_p A + Bm dJ_q^H on planes: the row model's derivative
    along a change (dJ_p, dJ_q) of its two stations' Jones, from the
    Wirtinger factors :func:`row_model` returned. Exact: V is bilinear
    in (J_p, conj J_q)."""
    return mm(dp8, a8) + mm(bm8, dq8, adj_b=True)


def periodic_rows(row_period: int, B: int) -> bool:
    """Whether ``B`` rows lie ``[tilesz, row_period]``: the stations
    repeat every ``row_period`` rows and the hybrid chunk of a row is
    its timeslot's, whatever the clusters' chunk counts (what
    ``rime/predict.chunk_indices`` builds; :func:`check_chunk_rows`
    holds a concrete map to it). The ONE decision ``normal_eq.RowPlanes``,
    ``normal_eq.normal_equations``, ``predict.predict_model`` and the
    ``*_rows`` records of ``solvers/sage.py`` take their layout by; a
    caller with another chunk map passes ``row_period=0``."""
    return row_period > 0 and B % row_period == 0


def check_chunk_rows(chunk_id, row_period: int):
    """Hold a CONCRETE chunk map ``[(M,) B]`` to what ``row_period``
    promises (:func:`periodic_rows`): inside each run of ``row_period``
    rows, one timeslot, the chunk is constant. Raises ValueError where
    it is not; a caller with such a map passes ``row_period=0`` and
    keeps flat rows."""
    c = np.asarray(chunk_id)
    if not periodic_rows(row_period, c.shape[-1]):
        return
    c = c.reshape(c.shape[:-1] + (-1, row_period))
    if (c != c[..., :1]).any():
        raise ValueError(
            f"chunk map varies inside a timeslot of {row_period} rows: "
            "row_period (nbase) promises rows [tilesz, nbase] whose chunk "
            "is their timeslot's (rime.predict.chunk_indices); pass "
            "row_period=0 (nbase=0) for any other map")


def take(P, idx):
    """Station planes P [K, N, 8], flat station indices -> [8, *idx.shape]."""
    return jnp.take(P.reshape(-1, 8).T, idx, axis=1)


def gather_period(P, idx, tchunk=None):
    """The Jones of one station of each row of ``[tilesz, R]`` rows, to
    broadcast against the rows' planes: station planes ``P [K, N, 8]``
    -> ``[8, *lead, 1, R]`` with one chunk a cluster (``idx
    [*lead, R]``, ``tchunk`` None: gathered for ``R`` rows, every
    timeslot the same), ``[8, *lead, tilesz, R]`` with ``kmax`` (``idx
    [*lead, kmax, R]``, the flat station index per chunk and baseline;
    ``tchunk [*lead, tilesz]``, the chunk of a timeslot): gathered for
    ``kmax x R`` rows, a timeslot then picks its chunk's by ``kmax - 1``
    selects along the time axis."""
    j = take(P, idx)
    if tchunk is None:
        return j[..., None, :]
    out = j[..., :1, :]
    for k in range(1, idx.shape[-2]):
        out = jnp.where((tchunk == k)[..., None], j[..., k:k + 1, :], out)
    return out
