"""Manifold averaging: resolving the per-frequency unitary ambiguity.

Capability parity with reference ``src/lib/Dirac/manifold_average.c``
(``calculate_manifold_average``:204, ``project_procrustes[_block]``:266/346):
per direction, each frequency's solution block (viewed as a 2N x 2 complex
matrix) is defined only up to a right 2x2 unitary; averaging across
frequency first rotates every block onto a reference block (Procrustes),
then iterates {mean -> project each block onto the mean}, and finally
applies exactly ONE unitary to each original block (manifold_average.c:
147-177) so solutions are modified only by a phase/unitary factor.

TPU re-architecture: the 2x2 complex SVD-based Procrustes factor
U V^H = polar(A) is computed with a closed-form 2x2 polar decomposition
(no LAPACK), fully batched over (direction, frequency) — and on the mesh
the frequency mean is a ``psum`` (SURVEY.md P10 "manifold averaging at
iter 0").
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _herm_invsqrt_2x2(H, eps=1e-12):
    """Inverse square root of a 2x2 Hermitian PSD matrix, closed form.

    sqrt(H) = (H + sqrt(det) I) / sqrt(trace + 2 sqrt(det));
    inv via adjugate. Batched over leading axes.
    """
    t = H[..., 0, 0] + H[..., 1, 1]
    d = H[..., 0, 0] * H[..., 1, 1] - H[..., 0, 1] * H[..., 1, 0]
    sd = jnp.sqrt(jnp.maximum(d.real, 0.0)).astype(H.dtype)
    denom = jnp.sqrt(jnp.maximum((t + 2 * sd).real, eps)).astype(H.dtype)
    sq = (H + sd[..., None, None] * jnp.eye(2, dtype=H.dtype)) \
        / denom[..., None, None]
    det_sq = sq[..., 0, 0] * sq[..., 1, 1] - sq[..., 0, 1] * sq[..., 1, 0]
    det_sq = jnp.where(jnp.abs(det_sq) < eps, eps, det_sq)
    adj = jnp.stack([
        jnp.stack([sq[..., 1, 1], -sq[..., 0, 1]], -1),
        jnp.stack([-sq[..., 1, 0], sq[..., 0, 0]], -1),
    ], -2)
    return adj / det_sq[..., None, None]


def polar_unitary_2x2(A):
    """U V^H of the SVD of a 2x2 complex A == its polar unitary factor:
    A (A^H A)^(-1/2). Batched."""
    AH_A = jnp.einsum("...ji,...jk->...ik", jnp.conj(A), A)
    return A @ _herm_invsqrt_2x2(AH_A)


def procrustes_project(X, Y):
    """Rotate Y onto X: Y <- Y U, U = argmin ||X - Y U||_F over unitaries.

    X, Y: [..., 2N, 2] complex stacked solution blocks
    (project_procrustes_block, manifold_average.c:346). U = polar(Y^H X).
    """
    A = jnp.einsum("...ji,...jk->...ik", jnp.conj(Y), X)  # [..., 2, 2]
    return Y @ polar_unitary_2x2(A)


def jones_to_blocks(J):
    """[..., N, 2, 2] Jones -> [..., 2N, 2] stacked blocks X = [J_1; J_2; ...].

    With this stacking the per-frequency gauge freedom of the unpolarized
    calibration problem (J_p -> J_p U, same 2x2 unitary U for every
    station; V = J_p C J_q^H invariant when C is diagonal-dominated) is a
    RIGHT multiplication X -> X U, exactly what the Procrustes projection
    removes (the role of the reference's 2N x 2 J-format blocks,
    manifold_average.c:86-96).
    """
    return J.reshape(J.shape[:-3] + (2 * J.shape[-3], 2))


def blocks_to_jones(X):
    """Inverse of :func:`jones_to_blocks`."""
    n = X.shape[-2] // 2
    return X.reshape(X.shape[:-2] + (n, 2, 2))


def _givens_from_eigvec(Z):
    """Unit eigenvector of the 3x3 rotation objective -> Givens (c, s)
    (manifold_average.c:497-506, with the sign-flip branch)."""
    pos = Z[0] >= 0.0
    Zs = jnp.where(pos, Z, -Z)
    c = jnp.sqrt(0.5 + 0.5 * Zs[0]).astype(jnp.result_type(Z, 1j))
    s = 0.5 * (Zs[1] - 1j * Zs[2]) / c
    return c, s


def extract_phases(J, niter: int = 10):
    """Phase-only diagonal Jones by joint diagonalization
    (``extract_phases``, manifold_average.c:400): iteratively rotate all
    stations' 2x2 blocks by a common Givens unitary (one sweep targets
    element (1,2), the next (2,1)) chosen as the top eigenvector of the
    accumulated 3x3 quadratic form; finally keep only unit-modulus
    diagonal entries.

    J: [N, 2, 2] complex -> [N, 2, 2] complex (diag(e^{i th0}, e^{i th1})).
    """
    cdt = J.dtype

    def h_vec(Jc, flip: bool):
        a00, a01 = Jc[:, 0, 0], Jc[:, 0, 1]
        a10, a11 = Jc[:, 1, 0], Jc[:, 1, 1]
        if not flip:
            h = jnp.stack([a00 - a11, a01 + a10, 1j * (a10 - a01)], -1)
        else:
            h = jnp.stack([a11 - a00, a10 + a01, 1j * (a01 - a10)], -1)
        return jnp.conj(h)                    # [N, 3]

    def sweep(Jc, flip: bool):
        h = h_vec(Jc, flip)
        H = jnp.einsum("ni,nj->ij", h, jnp.conj(h)).real   # 3x3 symmetric
        _, V = jnp.linalg.eigh(H)
        c, s = _givens_from_eigvec(V[:, -1])
        # row-major G = [[c, conj(s)], [-s, conj(c)]] — the reference
        # stores the same matrix column-major (manifold_average.c:505-509:
        # G[0]=c, G[1]=-s, G[2]=conj(s), G[3]=conj(c))
        G = jnp.stack([jnp.stack([c, jnp.conj(s)]),
                       jnp.stack([-s, jnp.conj(c)])]).astype(cdt)
        return jnp.einsum("nij,kj->nik", Jc, jnp.conj(G))  # J G^H

    def body(_, Jc):
        Jc = sweep(Jc, False)
        Jc = sweep(Jc, True)
        return Jc

    Jr = jax.lax.fori_loop(0, niter, body, J)
    d0 = Jr[:, 0, 0]
    d1 = Jr[:, 1, 1]
    d0 = d0 / jnp.maximum(jnp.abs(d0), 1e-30)
    d1 = d1 / jnp.maximum(jnp.abs(d1), 1e-30)
    zero = jnp.zeros_like(d0)
    return jnp.stack([jnp.stack([d0, zero], -1),
                      jnp.stack([zero, d1], -1)], -2)


@jax.named_scope("sage/manifold")
def manifold_average(J, niter: int = 3, ref_index: int = 0):
    """Frequency-average solutions up to unitary ambiguity.

    J: [Nf, M, N, 2, 2] complex per-frequency per-direction Jones.
    Returns J with each (f, m) block replaced by the original block rotated
    by one unitary toward the cross-frequency average
    (calculate_manifold_average semantics; ``ref_index`` stands in for the
    reference's random initial block).

    Note: this host-mesh-agnostic version computes means over axis 0;
    in the distributed ADMM the same math runs with a psum.
    """
    X0 = jones_to_blocks(J)                        # [Nf, M, 2N, 2]
    nf = X0.shape[0]

    # initial alignment to the reference frequency's block
    ref = X0[ref_index]
    X = procrustes_project(ref[None], X0)

    # iterate mean -> project
    def body(X, _):
        mean = jnp.mean(X, axis=0, keepdims=True)
        return procrustes_project(mean, X), None
    X, _ = jax.lax.scan(body, X, None, length=niter)

    # final: ONE unitary applied to the original blocks, toward the mean
    mean = jnp.mean(X, axis=0, keepdims=True)
    Xout = procrustes_project(mean, X0)
    return blocks_to_jones(Xout)
