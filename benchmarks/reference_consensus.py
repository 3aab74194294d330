"""The plain reference of the consensus deployment: numpy, float64,
nothing of the program (and no jax).  Beside ``reference.py``, which it
uses for the array, the uvw tracks, the measurement equation and the
solutions text format, it holds what calibrating several subbands
together adds:

- the subbands of one observation: ``reference.Observation``s that share
  array, sky, hour angle and seed, each at its own frequency, with the
  catalogue's fluxes taken along the sky's spectral index, its own noise,
  and a true Jones that is a first-order polynomial in frequency;
- the published consensus algebra (Yatawatta 2015; upstream
  ``consensus_poly.c``, ``sagecal_master.cpp:739-779``,
  ``sagecal_slave.cpp:686-770``):

      B_f[p] = C(P-1, p) x^p (1-x)^(P-1-p),  x = (f - fmin)/(fmax - fmin)
      Z      = (sum_f rho_f B_f B_f^T)^+  sum_f B_f (Y_f + rho_f J_f)
      Y_f   += rho_f (J_f - B_f Z)

  per cluster, ``Z`` holding ``P`` coefficient blocks shaped like ``J``;
- the text of the two kinds of solutions file a consensus run writes.

Departures from the published algebra, all of them the upstream
program's own and followed here so that the program can be held to it:

1. Bernstein type 2 spans ``[fmin, fmax]`` of the subbands present, not
   the band of the instrument (``consensus_poly.c:39``); with one subband
   ``x`` is 0 and the basis is ``[1, 0, ...]``.
2. The pseudo-inverse drops singular values under ``1e-12`` of the
   largest (``sum_inv_threadfn``, ``consensus_poly.c:301``).
3. ``rho_f`` of subband ``f`` and cluster ``m`` is the cluster's ``rho``
   times the subband's unflagged fraction (master ``:646-650``); every
   cell here has no flagged sample, so the fraction is 1.
4. In the FIRST iteration of an interval ``Y_f`` is not ``0 + rho_f J_f``
   but ``rho_f J_f`` rotated per cluster by one unitary towards the mean
   over the subbands (``manifold_average.c:204``): a Jones solution is
   only defined up to a unitary per cluster, and the average removes that
   freedom between subbands before the polynomial is fitted.  The
   reference does NOT implement that rotation: nothing it checks needs
   it, because every check compares what was WRITTEN (J per subband, Z)
   and a unitary common to ``J_f`` and ``B_f Z`` leaves models and norms
   alone.
"""

from __future__ import annotations

import math
import os

import numpy as np

import reference


# -- the published algebra ----------------------------------------------------

def bernstein_basis(freqs, npoly: int) -> np.ndarray:
    """[F, P]: the Bernstein polynomials of degree ``P - 1`` on
    ``[min(freqs), max(freqs)]`` at each frequency."""
    f = np.asarray(freqs, np.float64)
    span = f.max() - f.min()
    x = (f - f.min()) / span if span > 0 else np.zeros_like(f)
    return np.stack([math.comb(npoly - 1, p) * x ** p
                     * (1 - x) ** (npoly - 1 - p)
                     for p in range(npoly)], axis=1)


def _per_cluster(rho, like):
    """``rho`` [F, M] shaped to multiply ``like`` [F, M, ...]."""
    return np.asarray(rho, np.float64).reshape(
        np.shape(rho) + (1,) * (np.ndim(like) - 2))


def z_update(basis, y, jones, rho) -> np.ndarray:
    """``Z`` [M, P, ...] from ``basis`` [F, P], ``y`` and ``jones``
    [F, M, ...] and ``rho`` [F, M]."""
    basis = np.asarray(basis, np.float64)
    rho = np.asarray(rho, np.float64)
    sent = y + _per_cluster(rho, y) * jones             # what a slave sends
    zsum = np.einsum("fp,fm...->mp...", basis, sent)
    gram = np.einsum("fm,fp,fq->mpq", rho, basis, basis)
    inv = np.linalg.pinv(gram, rcond=1e-12, hermitian=True)
    return np.einsum("mpq,mq...->mp...", inv, zsum)


def bz(basis, z) -> np.ndarray:
    """``B_f Z`` at every subband: [F, M, ...]."""
    return np.einsum("fp,mp...->fm...", np.asarray(basis, np.float64), z)


def dual_update(y, jones, basis, z, rho) -> np.ndarray:
    return y + _per_cluster(rho, y) * (jones - bz(basis, z))


def primal_residual(jones, basis, z) -> float:
    """``||J - B Z|| / sqrt(number of real entries)``: the master's
    convergence axis.  ``jones`` [F, M, N, 2, 2] complex."""
    d = jones - bz(basis, z)
    return float(np.sqrt(np.sum(np.abs(d) ** 2) / (2 * d.size)))


# -- the subbands of one observation ------------------------------------------

def spectral_fluxes(sky_lines, cluster_lines, freq: float) -> np.ndarray:
    """[M, S] Stokes I at ``freq`` from the LSM text: the catalogue flux
    times ``exp(si r + si1 r^2 + si2 r^3)``, ``r = ln(freq / f0)``
    (upstream ``readsky.c:347-370``; a source whose first index is 0 is
    not scaled).  One spectral term or three, by the token count."""
    flux = {}
    for ln in sky_lines:
        t = ln.split()
        three = len(t) == 19
        si = [float(x) for x in t[11:14 if three else 12]] + [0.0, 0.0]
        r = math.log(freq / float(t[-1]))
        scale = math.exp(si[0] * r + si[1] * r * r + si[2] * r ** 3) \
            if si[0] != 0.0 else 1.0
        flux[t[0]] = float(t[7]) * scale
    return np.asarray([[flux[nm] for nm in ln.split()[2:]]
                       for ln in cluster_lines], np.float64)


class Subband(reference.Observation):
    """Subband ``k`` of an observation of ``len(freqs)`` subbands."""

    def __init__(self, cfg: dict, seed: int, k: int, freqs):
        super().__init__(cfg, seed)     # the array, the sky text, ha0
        self.k, self.freqs = int(k), [float(f) for f in freqs]
        self.freq = self.freqs[self.k]
        ll, mm, nn, _ = self.sky
        self.sky = (ll, mm, nn, spectral_fluxes(
            self.sky_lines, self.cluster_lines, self.freq))
        span = max(self.freqs) - min(self.freqs)
        self.x = (self.freq - min(self.freqs)) / span if span > 0 else 0.0

    def jones(self, interval: int = 0) -> np.ndarray:
        """True Jones [M, N, 2, 2]: ``(1 - x) J_a + x J_b`` with two
        draws of ``I + scale CN(0, 1)`` that belong to the observation,
        the same in every subband and interval."""
        a, b = (reference.draw_jones(
            self.n_dir, self.n_sta, float(self.cfg["jones_scale"]),
            np.random.default_rng([self.seed, 1, end])) for end in (0, 1))
        return (1.0 - self.x) * a + self.x * b

    def noise(self, tile: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, 2, tile, self.k])
        return reference.draw_noise(
            self.nrows, float(self.cfg["noise_sigma"]), rng)


def subbands(cfg: dict, seed: int):
    freqs = cfg["subband_freqs_hz"]
    return [Subband(cfg, seed, k, freqs) for k in range(len(freqs))]


# -- what a consensus run writes ----------------------------------------------

def read_z_file(path: str, npoly: int):
    """The global solutions file: a list, one per interval, of ``Z``
    [M, P, N, 2, 2] complex.  The file is in the solutions text format
    with ``M * P`` "effective clusters": clusters last first as always,
    and within a cluster its ``P`` coefficient blocks in order."""
    out = []
    for cols in reference.read_solutions(path):     # [M * P, N, 2, 2]
        m = cols.shape[0] // npoly
        z = cols.reshape((m, npoly) + cols.shape[1:])
        out.append(z[:, ::-1])      # read_solutions undid a plain reversal
    return out


def subband_solutions_path(ms_path: str) -> str:
    """Where the program writes subband ``ms_path``'s own solutions
    (upstream: "always create default solution file name
    MS+'.solutions'", ``sagecal_slave.cpp:167``)."""
    return ms_path.rstrip(os.sep) + ".solutions"
