"""Warm-start solution prior store: content-keyed J/ρ reuse across jobs.

The compile cache (serve/cache.py) made the *program* reusable across
jobs; this module does the same for the *solution state*. Production
traffic re-observes the same fields constantly — same sky model, same
station set, same band — and every such job used to cold-start its
Jones chain from identity even though the previous job on that field
already measured a good J (the warm-vs-cold gap is what the
store is for; cpu counts only, no chip reading). The store banks a
finished job's final per-(station, cluster, interval) Jones chain plus
its per-cluster ADMM ρ schedule, keyed by everything that determines
solution compatibility, and seeds the NEXT job on that key by
*interpolating* the stored chain onto the new job's solve intervals
and subbands.

Key contract (:func:`prior_key`): sky-model content digest + cluster
content digest + station count + band center + solver family. Content
digests (file bytes, not paths) mean a re-pointed symlink or an edited
sky model can never alias a stale prior; the solver family
(:func:`solver_family`) keeps an LM chain from seeding an NSD run.
The token is header-only computable — the serve router prices it for
placement without opening any data (serve/fleet.py
``job_prior_token``).

Interpolation contract (:func:`interpolate`):

- *temporal*: target intervals at exactly stored mid-times take the
  stored Jones bit-exactly; anything else linearly blends the two
  bracketing stored intervals (clamped to nearest at the ends).
- *spectral*: per target subband, the stored subband with the nearest
  band center is used (nearest-match, never blended across bands).
- *refusal*: a mismatched station set or cluster count raises — a
  prior must never PARTIALLY seed a chain. The store-level
  :meth:`PriorStore.seed` converts that refusal into a counted cold
  start (returns None) so serving never fails on a bad prior.

Tolerance contract: seeding changes iteration COUNTS, never the
convergence target — warm runs are gated against a cold control in
tests/test_priors.py (residual ratio within 0.05) and
``prior_cache="off"`` (the default) never touches this module, so
every bit-parity gate stays frozen.

Layering: numpy + stdlib + serve.cache (token) only — importable from
the router/placement layer, no jax.
"""

from __future__ import annotations

import hashlib
import os
import threading
from collections import OrderedDict

import numpy as np

from sagecal_tpu.analysis import threadsan
from sagecal_tpu.obs import metrics as obs
from sagecal_tpu.serve import cache as pcache

#: prior_cache mode values (RunConfig.prior_cache / --prior-cache):
#: "off" never consults or writes the store (bit-frozen default),
#: "read" seeds from it but banks nothing, "readwrite" does both.
MODES = ("off", "read", "readwrite")


def reads(mode) -> bool:
    """True when ``mode`` consults the store for seeding."""
    return mode in ("read", "readwrite")


def writes(mode) -> bool:
    """True when ``mode`` banks finished solutions."""
    return mode == "readwrite"


def solver_family(solver_mode, jones_mode="full") -> str:
    """Coarse solver-compatibility class of a fullbatch solver mode.

    Seeds only flow between runs whose accepted-step geometry is
    comparable: the OS-LM/LBFGS modes (0-3) share one family, the
    Riemannian trust-region modes (4-5) another, NSD (6) its own.
    Consensus runs pass the literal ``"admm"`` instead (cli_mpi).
    A constrained Jones parameterization (``jones_mode`` of "diag" or
    "phase", round 20) suffixes the family: a full-Jones chain has
    off-diagonal structure a phase-only job cannot represent, so the
    parameterizations must never cross-seed."""
    m = int(solver_mode)
    if m <= 3:
        fam = "lm"
    elif m <= 5:
        fam = "rtr"
    else:
        fam = "nsd"
    jm = str(jones_mode)
    return fam if jm == "full" else f"{fam}+{jm}"


def _file_digest(path) -> str:
    """Content digest of one input file (the sky/cluster half of the
    key). Unreadable inputs raise — a key built from a missing file
    would alias every other missing file."""
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for blk in iter(lambda: f.read(1 << 20), b""):
            h.update(blk)
    return h.hexdigest()[:32]


def prior_key(sky_model, cluster_file, n_stations, freq0,
              family: str) -> str:
    """The store key (module doc "Key contract"). Returns None when
    either content input is absent/unreadable — no key, no seeding,
    cold start (never an error on the serving path)."""
    try:
        sky_d = _file_digest(sky_model)
        clus_d = _file_digest(cluster_file)
    except (OSError, TypeError):
        return None
    return pcache.token("prior", sky_d, clus_d, int(n_stations),
                        f"{float(freq0):.6e}", str(family))


def make_prior(J, times, freqs, rho=None, quality=None,
               jones_mode="full") -> dict:
    """Validate + normalize one store entry.

    ``J``: [F, T, M, N, 2, 2] complex — per (subband, solve interval,
    cluster, station) Jones; fullbatch runs bank F=1 at the band
    center. ``times``: [T] ascending interval mid-times (seconds from
    observation start). ``freqs``: [F] band centers. ``rho``: optional
    [M] per-cluster consensus ρ (ADMM runs). ``quality``: optional
    convergence figure of merit (lower is better — the pipeline banks
    its mean accepted per-tile residual); the store uses it to refuse
    replacing a better entry with a worse one. ``jones_mode``: the
    Jones parameterization the chain was solved under ("full",
    "diag", "phase") — recorded so :func:`interpolate` can refuse a
    cross-parameterization seed even if a key ever aliases."""
    J = np.asarray(J)
    times = np.asarray(times, dtype=np.float64)
    freqs = np.asarray(freqs, dtype=np.float64)
    if J.ndim != 6 or J.shape[-2:] != (2, 2):
        raise ValueError(f"prior J shape {J.shape}: expected "
                         "[F, T, M, N, 2, 2]")
    if not np.iscomplexobj(J):
        raise ValueError(f"prior J dtype {J.dtype}: expected complex")
    if times.shape != (J.shape[1],):
        raise ValueError(f"prior times shape {times.shape} vs "
                         f"T={J.shape[1]}")
    if np.any(np.diff(times) < 0):
        raise ValueError("prior times must be ascending")
    if freqs.shape != (J.shape[0],):
        raise ValueError(f"prior freqs shape {freqs.shape} vs "
                         f"F={J.shape[0]}")
    if rho is not None:
        rho = np.asarray(rho, dtype=np.float64)
        if rho.shape != (J.shape[2],):
            raise ValueError(f"prior rho shape {rho.shape} vs "
                             f"M={J.shape[2]}")
    jm = str(jones_mode)
    if jm not in ("full", "diag", "phase"):
        raise ValueError(f"prior jones_mode {jm!r}: expected one of "
                         "full/diag/phase")
    return {"J": J, "times": times, "freqs": freqs, "rho": rho,
            "quality": None if quality is None else float(quality),
            "n_stations": int(J.shape[3]),
            "n_clusters": int(J.shape[2]),
            "jones_mode": jm}


def _interp_band(Jb, times, t) -> np.ndarray:
    """One subband's [M, N, 2, 2] Jones at target mid-time ``t``:
    bit-exact on an exact stored time, linear between the bracketing
    intervals otherwise, clamped to the nearest end outside the
    stored range."""
    ix = int(np.searchsorted(times, t))
    if ix < len(times) and times[ix] == t:
        return Jb[ix].copy()
    if ix <= 0:
        return Jb[0].copy()
    if ix >= len(times):
        return Jb[-1].copy()
    t0, t1 = times[ix - 1], times[ix]
    w = 0.5 if t1 == t0 else (t - t0) / (t1 - t0)
    return (1.0 - w) * Jb[ix - 1] + w * Jb[ix]


def interpolate(prior: dict, times, freq, n_stations,
                n_clusters, jones_mode="full") -> np.ndarray:
    """Seed J0 for one band: [M, K, N, 2, 2] at the K target interval
    mid-times, from the stored subband nearest ``freq``. Raises
    ValueError on a station-set, cluster-count, or Jones-
    parameterization mismatch — a prior never partially seeds
    (module doc "refusal"). The jones_mode check is belt-and-braces
    on top of :func:`solver_family` keying: a full-Jones chain must
    never seed a phase-only job (off-diagonal leakage the constrained
    solve cannot correct), nor the reverse."""
    if str(jones_mode) != prior.get("jones_mode", "full"):
        raise ValueError(
            f"prior jones_mode mismatch: stored "
            f"{prior.get('jones_mode', 'full')!r} chain, job solves "
            f"{str(jones_mode)!r}; refusing to seed")
    if int(n_stations) != prior["n_stations"]:
        raise ValueError(
            f"prior station set mismatch: stored {prior['n_stations']} "
            f"stations, job has {int(n_stations)}; refusing to seed")
    if int(n_clusters) != prior["n_clusters"]:
        raise ValueError(
            f"prior cluster mismatch: stored {prior['n_clusters']} "
            f"clusters, job has {int(n_clusters)}; refusing to seed")
    fi = int(np.argmin(np.abs(prior["freqs"] - float(freq))))
    Jb = prior["J"][fi]                       # [T, M, N, 2, 2]
    out = np.stack([_interp_band(Jb, prior["times"], float(t))
                    for t in np.asarray(times, dtype=np.float64)])
    # [K, M, N, 2, 2] -> [M, K, N, 2, 2] (the pipeline J0 layout)
    return np.ascontiguousarray(np.swapaxes(out, 0, 1))


class PriorStore:
    """Process-wide LRU of solution priors (thread-safe).

    Mirrors :class:`sagecal_tpu.serve.cache.ProgramCache` in shape:
    one singleton (:data:`PRIORS`), explicit content keys, LRU
    eviction, hit/miss counters the serve layer exports. Each key
    holds ONE entry — a repeat field's latest finished solution
    supersedes the previous one UNLESS both carry a quality figure
    and the newcomer's is worse (refuse-to-degrade: without it, a
    warm-seeded job re-banking its own slightly-noisier chain would
    compound generation over generation, each repeat seeding from
    the previous repeat's drift instead of the best converged state).
    One entry per key bounds memory at ``maxsize * sizeof(chain)``.
    """

    def __init__(self, maxsize: int = 16):
        self.maxsize = int(maxsize)
        self._d: OrderedDict = OrderedDict()      # key -> prior dict
        self._lock = threadsan.make_lock("PriorStore._lock")
        self.hits = 0
        self.misses = 0
        self.banked = 0
        self.refused = 0
        self.kept = 0

    # -- write side ---------------------------------------------------------

    def bank(self, key, J, times, freqs, rho=None,
             quality=None, jones_mode="full") -> bool:
        """Bank one finished job's chain under ``key`` (validated via
        :func:`make_prior`). No-op on a None key. When the held entry
        and the newcomer BOTH carry a quality figure and the held one
        is at least as good, the held entry is kept (counted in
        ``kept``) — an entry without a quality figure is always
        superseded. Returns whether the new entry landed."""
        if key is None:
            return False
        entry = make_prior(J, times, freqs, rho=rho, quality=quality,
                           jones_mode=jones_mode)
        with self._lock:
            threadsan.guard(self._lock, "PriorStore._d")
            old = self._d.get(key)
            if (old is not None and old["quality"] is not None
                    and entry["quality"] is not None
                    and old["quality"] <= entry["quality"]):
                self._d.move_to_end(key)   # still this key's freshest use
                self.kept += 1
                obs.inc("serve_prior_bank_kept_total")
                return False
            self._d[key] = entry
            self._d.move_to_end(key)
            while len(self._d) > self.maxsize:
                self._d.popitem(last=False)
            self.banked += 1
        obs.inc("serve_prior_banked_total")
        return True

    # -- read side ----------------------------------------------------------

    def lookup(self, key) -> dict | None:
        """The newest entry under ``key`` (hit/miss counted), or
        None."""
        with self._lock:
            threadsan.guard(self._lock, "PriorStore._d")
            if key is not None and key in self._d:
                self._d.move_to_end(key)
                self.hits += 1
                obs.inc("serve_prior_hits_total")
                return self._d[key]
            self.misses += 1
            obs.inc("serve_prior_misses_total")
            return None

    def seed(self, key, times, freq, n_stations, n_clusters,
             jones_mode="full"):
        """(J0, rho) seed for one band, or (None, None) on a miss OR a
        refusal — the serving path never raises on a bad prior, it
        cold-starts and counts why. A full-Jones entry asked to seed a
        phase-only job (or any parameterization mismatch) is one such
        counted refusal."""
        entry = self.lookup(key)
        if entry is None:
            return None, None
        try:
            J0 = interpolate(entry, times, freq, n_stations,
                             n_clusters, jones_mode=jones_mode)
        except ValueError:
            with self._lock:
                self.refused += 1
            obs.inc("serve_prior_refused_total")
            return None, None
        rho = None if entry["rho"] is None else entry["rho"].copy()
        return J0, rho

    # -- introspection ------------------------------------------------------

    def inventory(self) -> list:
        """The held keys, LRU-oldest first — what a fleet worker
        publishes over its heartbeat so the router can route repeat
        fields at the worker already holding their priors."""
        with self._lock:
            return list(self._d)

    def stats(self) -> dict:
        with self._lock:
            n = self.hits + self.misses
            return {"entries": len(self._d), "hits": self.hits,
                    "misses": self.misses,
                    "hit_rate": (self.hits / n) if n else 0.0,
                    "banked": self.banked, "refused": self.refused,
                    "kept": self.kept}

    def clear(self) -> None:
        with self._lock:
            self._d.clear()
            self.hits = self.misses = 0
            self.banked = self.refused = self.kept = 0


#: the process singleton every seeding/banking site goes through
PRIORS = PriorStore(maxsize=int(os.environ.get(
    "SAGECAL_PRIOR_CACHE_SIZE", "16")))
