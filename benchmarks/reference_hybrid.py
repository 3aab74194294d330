"""The plain reference of the hybrid deployment: numpy, float64, nothing of
the program (and no jax).  Beside ``reference.py``, which it uses for the
array, the uvw tracks, the sky's text and the scalar coherencies, it holds
what upstream's cluster-file format adds to a calibration:

- the cluster file's text, ``cluster_id chunk_size source ...`` a line
  (upstream README, "Cluster file"): ``chunk_size`` solutions a tile for
  that direction ("hybrid" time chunks), and a NEGATIVE ``cluster_id`` for
  a direction that is solved for and never subtracted from the data;
- the row -> chunk map of the published rule (upstream ``lmfit.c:893-899``):
  a tile of ``tilesz`` timeslots and a cluster of ``K`` chunks give chunks
  of ``ceil(tilesz / K)`` timeslots each, the last chunk taking what is
  left: chunk of timeslot ``t`` = ``min(t // ceil(tilesz / K), K - 1)``;
- the measurement equation with a Jones per (cluster, chunk),

      V_pq(t) = sum_m J_{p,m,k(m,t)} C_{pq,m}(t) J_{q,m,k(m,t)}^H ;

- the subtract mask: the written residual is the data minus the model of
  the clusters with ``cluster_id >= 0`` only;
- its own writer and reader of the upstream solutions text layout at
  ``effective_clusters = sum(chunk_size)``: one column per (cluster,
  chunk), the LAST cluster first, a cluster's chunks in time order
  (upstream ``fullbatch_mode.cpp:583-593``, ``readsky.c:681-733``).

Departures from upstream, each followed or stated here:

1. A chunk count that does not divide ``tilesz`` is allowed, as upstream
   allows it: with ``K`` 3 and ten timeslots the chunks hold 4, 4 and 2.
   A count that leaves a chunk empty under the rule (above ``tilesz``, or
   4 of 6: chunks of 2, 2, 2, 0) is upstream's to accept; no cell has
   one and ``chunk_of_slot`` refuses it.
2. Sources are unpolarised points observed at the catalogue frequency
   (``reference.py``'s sky), so a cluster's coherency is a scalar a row.
3. ``chunk_of_slot(..., rule="floor")``, ``model(..., clusters=...)``
   with another set than the positive ids and ``model(..., dtype=...)``
   exist for the CONTROLS only: what a program with chunk boundaries at
   ``floor(tilesz / K)``, one that subtracted the kept cluster, or one
   whose Jones products were made in a narrower type would have written.
   Nothing but a control passes them.
4. The true Jones of (cluster ``m``, chunk ``k``) is the cluster's draw
   of ``reference.Observation.jones`` plus ``chunk_jones_scale * CN(0, 1)``
   per (cluster, chunk, station): constant over the observation, as the
   base deployment's truth is, so chunk ``k`` of every tile sees the same
   Jones.  Upstream has no truth; this is the synthetic observation's.
"""

from __future__ import annotations

import numpy as np

import reference


# -- the cluster file ---------------------------------------------------------

def flux_ranks(sky) -> np.ndarray:
    """[M]: each cluster's rank by summed catalogue flux, 0 the
    brightest (ties by the file's order)."""
    order = np.argsort(-np.sum(sky[3], axis=1), kind="stable")
    ranks = np.empty(len(order), np.int64)
    ranks[order] = np.arange(len(order))
    return ranks


def cluster_text(cluster_lines, nchunk, kept):
    """The lines of a cluster file (``id chunks names``) with
    ``nchunk[m]`` in the second column and the id negative where
    ``kept[m]``, positive elsewhere."""
    out = []
    for ln, k, keep in zip(cluster_lines, nchunk, kept):
        t = ln.split()
        cid = -abs(int(t[0])) if keep else abs(int(t[0]))
        out.append(" ".join([str(cid), str(int(k))] + t[2:]))
    return out


def read_cluster_text(cluster_lines):
    """(ids [M], nchunk [M]) of a cluster file's lines: this file's own
    reading of the first two columns."""
    ids, nchunk = [], []
    for ln in cluster_lines:
        t = ln.split()
        if not t or t[0].startswith("#"):
            continue
        ids.append(int(t[0]))
        nchunk.append(max(1, int(t[1])))
    return np.asarray(ids, np.int64), np.asarray(nchunk, np.int64)


# -- the row -> chunk map -----------------------------------------------------

def chunk_of_slot(tilesz: int, nchunk: int, rule: str = "ceil") -> np.ndarray:
    """[tilesz]: the chunk of each timeslot of a tile for a cluster of
    ``nchunk`` chunks.  ``rule`` "ceil" is upstream's (lmfit.c:893-899);
    "floor" is the control's."""
    per = -(-tilesz // nchunk) if rule == "ceil" else tilesz // nchunk
    if nchunk < 1 or per < 1 or (nchunk - 1) * per >= tilesz:
        raise ValueError(f"{nchunk} chunks in a tile of {tilesz} timeslots "
                         f"leave a chunk empty")
    return np.minimum(np.arange(tilesz) // per, nchunk - 1)


def chunk_of_row(tilesz: int, nbase: int, nchunks, rule: str = "ceil"):
    """[M, tilesz * nbase]: rows are ordered [timeslot, baseline]."""
    return np.stack([np.repeat(chunk_of_slot(tilesz, int(k), rule), nbase)
                     for k in nchunks])


# -- the measurement equation -------------------------------------------------

def model(jones, nchunk, coh, sta1, sta2, chunk, clusters=None,
          dtype=None, passes: int = 1) -> np.ndarray:
    """sum over the clusters ``m`` in ``clusters`` (all of them when None)
    of ``coh[m, b] J[m, chunk[m, b], p_b] J[m, chunk[m, b], q_b]^H``
    -> [B, 2, 2] complex.  ``jones`` is [M, kmax, N, 2, 2]; slots at and
    above ``nchunk[m]`` are never read.  ``dtype`` and ``passes`` (the
    control): both products of the sandwich as ``reference.product``
    makes them, as in ``reference.model``."""
    out = np.zeros((coh.shape[1], 2, 2), np.complex128)
    for m in (range(coh.shape[0]) if clusters is None else clusters):
        if chunk[m].max() >= nchunk[m]:
            raise ValueError(f"cluster {m}: a row in chunk "
                             f"{chunk[m].max()} of {nchunk[m]}")
        left = reference.product(
            lambda j, c: j * c[:, None, None],
            jones[m][chunk[m], sta1], coh[m], dtype, passes)
        out += reference.product(
            lambda a, j: np.einsum("bij,bkj->bik", a, j.conj()),
            left, jones[m][chunk[m], sta2], dtype, passes)
    return out


# -- the upstream solutions text layout at sum(nchunk) columns ----------------

#: (row, column) of the Jones matrix held by each pair of a station's 8
#: reals: [S0+jS1, S4+jS5; S2+jS3, S6+jS7] (upstream README)
_PAIRS = ((0, 0), (1, 0), (0, 1), (1, 1))


def _columns(nchunk):
    """(cluster, chunk) of each column: the last cluster first, chunks in
    time order within a cluster."""
    return [(m, k) for m in range(len(nchunk) - 1, -1, -1)
            for k in range(int(nchunk[m]))]


def write_solutions(path: str, jones_per_interval, nchunk, freq0: float,
                    fdelta: float, interval_min: float) -> None:
    """``jones_per_interval``: [M, kmax, N, 2, 2] complex each."""
    n_sta = jones_per_interval[0].shape[2]
    where = _columns(nchunk)
    with open(path, "w") as f:
        f.write("# solution file (benchmarks/reference_hybrid.py)\n")
        f.write("# freq(MHz) bandwidth(MHz) time_interval(min) stations "
                "clusters effective_clusters\n")
        f.write(f"{freq0 * 1e-6:f} {fdelta * 1e-6:f} {interval_min:f} "
                f"{n_sta} {len(nchunk)} {len(where)}\n")
        for jones in jones_per_interval:
            cols = np.empty((8 * n_sta, len(where)))
            for c, (m, k) in enumerate(where):
                for i, (a, b) in enumerate(_PAIRS):
                    cols[2 * i::8, c] = jones[m, k, :, a, b].real
                    cols[2 * i + 1::8, c] = jones[m, k, :, a, b].imag
            f.write("".join(
                f"{r} " + " ".join(f"{x:.9e}" for x in cols[r]) + "\n"
                for r in range(8 * n_sta)))


def read_solutions(path: str, nchunk):
    """List of [M, kmax, N, 2, 2] complex, one per solve interval; slots
    at and above ``nchunk[m]`` hold NaN.  The header's cluster counts
    have to be ``len(nchunk)`` and ``sum(nchunk)``."""
    where, kmax = _columns(nchunk), int(max(nchunk))
    header, rows, out = None, [], []
    with open(path) as f:
        for ln in f:
            t = ln.split()
            if not t or t[0].startswith("#"):
                continue
            if header is None:
                header = t
                n_sta = int(t[3])
                if (int(t[4]), int(t[5])) != (len(nchunk), len(where)):
                    raise ValueError(
                        f"{path}: header says {t[4]} clusters, {t[5]} "
                        f"effective; the cluster file gives {len(nchunk)} "
                        f"and {len(where)}")
                continue
            if len(t) != 1 + len(where):
                raise ValueError(f"{path}: a row of {len(t) - 1} columns, "
                                 f"not {len(where)}")
            rows.append([float(x) for x in t[1:]])
            if len(rows) == 8 * n_sta:
                cols = np.asarray(rows)
                jones = np.full((len(nchunk), kmax, n_sta, 2, 2),
                                np.nan + 0j, np.complex128)
                for c, (m, k) in enumerate(where):
                    for i, (a, b) in enumerate(_PAIRS):
                        jones[m, k, :, a, b] = (cols[2 * i::8, c]
                                                + 1j * cols[2 * i + 1::8, c])
                out.append(jones)
                rows = []
    if rows:
        raise ValueError(f"{path}: ends inside an interval "
                         f"({len(rows)} of {8 * n_sta} rows)")
    return out


# -- the observation ----------------------------------------------------------

class Observation(reference.Observation):
    """``reference.Observation`` (same array, sky, hour angle and noise
    from the same seed) under a hybrid cluster file.

    The configuration gives ``nchunk_by_flux_rank`` (the chunk count of
    the brightest cluster first), ``kept_flux_ranks`` (the ranks whose
    id is negative) and ``chunk_jones_scale``."""

    def __init__(self, cfg: dict, seed: int):
        super().__init__(cfg, seed)
        ranks = flux_ranks(self.sky)
        by_rank = [int(k) for k in cfg["nchunk_by_flux_rank"]]
        if len(by_rank) != self.n_dir:
            raise ValueError(f"{len(by_rank)} chunk counts for "
                             f"{self.n_dir} clusters")
        kept = set(int(r) for r in cfg["kept_flux_ranks"])
        self.cluster_lines = cluster_text(
            self.cluster_lines, [by_rank[r] for r in ranks],
            [r in kept for r in ranks])
        # what the file says, as this reference reads it back
        self.ids, self.nchunk = read_cluster_text(self.cluster_lines)
        self.kmax = int(self.nchunk.max())
        self.n_eff = int(self.nchunk.sum())
        self.subtracted = np.flatnonzero(self.ids >= 0)

    def jones(self, interval: int = 0) -> np.ndarray:
        """True Jones [M, kmax, N, 2, 2] (departure 4); slots at and
        above ``nchunk[m]`` hold NaN."""
        base = super().jones(interval)
        rng = np.random.default_rng([self.seed, 3])
        shape = (self.n_dir, self.kmax, self.n_sta, 2, 2)
        j = base[:, None] + float(self.cfg["chunk_jones_scale"]) * (
            rng.normal(size=shape) + 1j * rng.normal(size=shape))
        j[np.arange(self.kmax)[None, :] >= self.nchunk[:, None]] = np.nan
        return j

    def chunk_of_row(self, rule: str = "ceil") -> np.ndarray:
        return chunk_of_row(self.tilesz, self.nbase, self.nchunk, rule)

    def coherencies(self, tile: int):
        u, v, w, s1, s2 = self.geometry(tile)
        return reference.coherencies(self.sky, u, v, w, self.freq,
                                     self.fdelta), s1, s2

    def model(self, tile: int, jones: np.ndarray, clusters=None,
              rule: str = "ceil", nchunk=None, coh=None, dtype=None,
              passes: int = 1) -> np.ndarray:
        """Model visibilities [B, 2, 2] of ``tile`` under ``jones``
        [M, kmax', N, 2, 2], summed over ``clusters`` (all when None).
        ``nchunk`` (the cluster file's when None) says how many chunks
        ``jones`` has a cluster: the control that solved under another
        cluster file passes its own.  ``coh`` is ``coherencies(tile)``
        where the caller has it already; ``dtype`` and ``passes`` as in
        :func:`model`."""
        nchunk = self.nchunk if nchunk is None else np.asarray(nchunk)
        c, s1, s2 = self.coherencies(tile) if coh is None else coh
        chunk = chunk_of_row(self.tilesz, self.nbase, nchunk, rule)
        return model(jones, nchunk, c, s1, s2, chunk, clusters, dtype,
                     passes)

    def data(self, tile: int) -> np.ndarray:
        """Observed visibilities: every cluster under the true Jones of
        its chunks, plus noise."""
        return self.model(tile, self.jones(tile)) + self.noise(tile)
