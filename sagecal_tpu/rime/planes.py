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


def periodic_rows(kmax: int, row_period: int, B: int) -> bool:
    """Whether ``B`` rows of clusters with ``kmax`` chunks each lie
    ``[tilesz, row_period]`` with the stations repeating every
    ``row_period`` rows (what ``normal_eq.RowPlanes`` and
    ``predict.predict_model`` decide their layout by)."""
    return kmax == 1 and row_period > 0 and B % row_period == 0


def take(P, idx):
    """Station planes P [K, N, 8], flat station indices -> [8, *idx.shape]."""
    return jnp.take(P.reshape(-1, 8).T, idx, axis=1)
