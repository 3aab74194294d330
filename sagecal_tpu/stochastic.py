"""Stochastic (minibatch) calibration modes.

Capability parity with the reference application layer:

- ``run_minibatch`` — ``src/MS/minibatch_mode.cpp:47``: epochs x
  minibatches over each solve interval, the interval's ``tilesz`` split
  into ``ceil(tilesz/minibatches)``-timeslot minibatches, ``nsolbw``
  frequency mini-bands each carrying its own full solution vector and its
  own persistent LBFGS memory (``lbfgs_persist_init`` per band,
  minibatch_mode.cpp:345), solved jointly over all clusters by robust
  LBFGS (``bfgsfit_minibatch_visibilities``,
  robust_batchmode_lbfgs.c:1446), residuals written per minibatch, and
  the reference's divergence policy (per-band reset when a band's
  residual exceeds ``res_ratio`` x the band average, global reset + LBFGS
  memory reset on 0/NaN/growing residuals, minibatch_mode.cpp:516-542).

- ``run_minibatch_consensus`` — ``minibatch_consensus_mode.cpp:47``:
  wraps the same epoch/minibatch sweep in an ADMM loop that couples the
  mini-bands through a frequency polynomial Z: per minibatch, each band
  solves the augmented Lagrangian (``bfgsfit_minibatch_consensus``,
  robust_batchmode_lbfgs.c:1504: cost += y^T(p - BZ) + rho/2 ||p - BZ||^2),
  then Y <- Y + rho(J - BZ) and Z <- Bii sum_b B_b (Y_b + rho_b J_b)
  (minibatch_consensus_mode.cpp:446-590), with diverged bands flagged out
  of the Z update (``fband``, :528-546) and per-band/global resets.

Hybrid time-chunking follows the reference exactly: the solve interval's
chunk map is built for the *minibatch* length (``iodata.tilesz =
time_per_minibatch``, minibatch_mode.cpp:71), and residuals are computed
per minibatch with that same map.

TPU re-architecture: one jitted band solver (cost by autodiff, persistent
LBFGS state as a pytree) is reused across every (band, minibatch, epoch)
combination — band data are padded to a common channel width so a single
compiled program serves all bands, and the padded device arrays are
prepared once per tile and reused across epochs/ADMM iterations; the
reference instead re-reads the MS and re-enters a hand-written C gradient
kernel per band per epoch.
"""

from __future__ import annotations

import time
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from sagecal_tpu import sched, skymodel, utils
from sagecal_tpu.config import RunConfig
from sagecal_tpu.consensus import poly as cpoly
from sagecal_tpu.diag import trace as dtrace
from sagecal_tpu.io import dataset as ds
from sagecal_tpu.io import solutions as sol
from sagecal_tpu.rime import beam as bm
from sagecal_tpu.rime import predict as rp
from sagecal_tpu.rime import residual as rr
from sagecal_tpu.solvers import lbfgs as lbfgs_mod
from sagecal_tpu.solvers import normal_eq as ne

RES_RATIO = 5.0  # minibatch_mode.cpp res_ratio


def band_plan(nchan_total: int, nsolbw: int):
    """Channel ranges for the frequency mini-bands.

    Parity: minibatch_mode.cpp:89-114 — ``nchanpersol = ceil(Nchan/nsolbw)``
    bands, the last band taking the remainder; bands that end up empty
    (e.g. Nchan=4, nsolbw=3) are dropped. Returns
    (chanstart [nsolbw'], nchan [nsolbw'], nchanpersol).
    """
    nsolbw = min(nsolbw, nchan_total)
    nchanpersol = (nchan_total + nsolbw - 1) // nsolbw
    chanstart, nchan = [], []
    count = 0
    for _ in range(nsolbw):
        nc = nchanpersol if count + nchanpersol < nchan_total else \
            nchan_total - count
        if nc <= 0:
            break
        nchan.append(nc)
        chanstart.append(count)
        count += nc
    return np.asarray(chanstart), np.asarray(nchan), nchanpersol


def minibatch_rows(tilesz: int, nbase: int, minibatches: int):
    """Row ranges per minibatch (rows ordered t*nbase + bl).

    Parity: minibatch_mode.cpp:57 ``time_per_minibatch =
    ceil(TileSize/minibatches)`` and loadDataMinibatch's time slicing;
    ``minibatches`` is clamped to ``tilesz`` so no minibatch is empty.
    Returns (row_start [nmb], n_timeslots [nmb], time_per_minibatch).
    """
    minibatches = max(min(minibatches, tilesz), 1)
    tpm = (tilesz + minibatches - 1) // minibatches
    starts, nts = [], []
    for nmb in range(minibatches):
        t0 = nmb * tpm
        t1 = min(t0 + tpm, tilesz)
        if t1 <= t0:
            break
        starts.append(t0 * nbase)
        nts.append(t1 - t0)
    return np.asarray(starts), np.asarray(nts), tpm


def model8_multifreq(J, coh, sta1, sta2, chunk_idx):
    """Sum over clusters of J_p C_m(f) J_q^H as [B, F, 8] reals.

    J: [M, K, N, 2, 2] complex; coh: [M, B, F, 2, 2] complex.
    The multichannel analogue of ``minimize_viz_full_pth``
    (robust_batchmode_lbfgs.c ``minimize_viz_full_multifreq``).
    """
    def body(acc, xs):
        J_m, coh_m, cidx_m = xs
        Jp = J_m[cidx_m, sta1]                       # [B, 2, 2]
        Jq = J_m[cidx_m, sta2]
        V = jnp.einsum("bij,bfjk,blk->bfil", Jp, coh_m, jnp.conj(Jq))
        return acc + V, None
    B, F = coh.shape[1], coh.shape[2]
    init = jnp.zeros((B, F, 2, 2), coh.dtype)
    V, _ = jax.lax.scan(body, init, (J, coh, chunk_idx))
    vf = V.reshape(B, F, 4)
    return jnp.stack([vf.real, vf.imag], -1).reshape(B, F, 8)


def _x8f_to_complex(x8F):
    """[B, F, 8] reals -> [B, F, 2, 2] complex (on device)."""
    B, F = x8F.shape[0], x8F.shape[1]
    return utils.r2c(x8F.reshape(B, F, 4, 2)).reshape(B, F, 2, 2)


class BandSolverOutputs(NamedTuple):
    p: jax.Array
    mem: lbfgs_mod.LBFGSMemory
    res_0: jax.Array
    res_1: jax.Array
    iters: jax.Array        # executed LBFGS iterations (MFU accounting)


def make_band_cost(chunk_idx, chunk_mask, n_stations: int, nu: float,
                   consensus: bool, loss: str = "robust"):
    """Build the band objective used by :func:`make_band_solver`:
    ``cost_of(x8F, coh, wtF, sta1, sta2, Y, BZ, rho) -> cost_fn(pflat)``.

    Factored out so that whoever prices or lowers an LBFGS iteration
    lowers the SAME objective the solver minimizes —
    a hand-copied objective would silently drift if this one changes.
    """
    M, kmax = chunk_mask.shape
    cidx = jnp.asarray(chunk_idx)
    cmask3 = jnp.asarray(chunk_mask)[..., None, None]     # [M, K, 1, 1]

    def cost_of(x8F, coh, wtF, sta1, sta2, Y=None, BZ=None, rho=None):
        def cost_fn(pflat):
            p = pflat.reshape(M, kmax, n_stations, 8)
            J = ne.jones_r2c(p)
            r = (x8F - model8_multifreq(J, coh, sta1, sta2, cidx)) * wtF
            if loss == "huber":
                # Huber threshold-nu loss (func_huber_th,
                # robust_batchmode_lbfgs.c:66): r^2 inside, linear outside
                a = jnp.abs(r)
                c = jnp.sum(jnp.where(a <= nu, r * r,
                                      2.0 * nu * a - nu * nu))
            else:
                c = jnp.sum(jnp.log1p(r * r / nu))
            if consensus:
                # augmented Lagrangian (robust_batchmode_lbfgs.c:1504):
                # y^T(p - BZ) + rho/2 ||p - BZ||^2 per effective cluster
                d = jnp.where(cmask3, p - BZ, 0.0)
                c = c + jnp.sum(Y * d)
                c = c + 0.5 * jnp.sum(
                    rho[:, None, None, None] * jnp.sum(d * d, axis=(2, 3)))
            return c
        return cost_fn

    return cost_of


def make_band_solver(dsky, n_stations: int, chunk_idx, chunk_mask,
                     fdelta_chan: float, nu: float, max_lbfgs: int,
                     consensus: bool, dobeam: int = 0,
                     loss: str = "robust"):
    """Build the jitted per-(band, minibatch) robust LBFGS solve.

    Parity: ``bfgsfit_minibatch_visibilities`` (plain) /
    ``bfgsfit_minibatch_consensus`` (adds the ADMM augmentation) in
    robust_batchmode_lbfgs.c:1446/:1504. Cost is the Student's-t robust
    objective sum log(1 + r^2/nu) over all real residual components of the
    band's channels; the gradient is autodiff (the reference hand-writes
    ``cpu_calc_deriv_multifreq``). The persistent LBFGS memory rides
    through as a pytree (persistent_data_t).
    """
    M, kmax = chunk_mask.shape
    cost_of = make_band_cost(chunk_idx, chunk_mask, n_stations, nu,
                             consensus, loss=loss)

    def solve(x8F, u, v, w, sta1, sta2, wtF, freqsF, tslot, p0, mem,
              Y=None, BZ=None, rho=None, beam=None):
        # x8F/wtF: [B, Fp, 8]; freqsF: [Fp]; p0: [M, K, N, 8] reals
        coh = rp.coherencies(dsky, u, v, w, freqsF, fdelta_chan,
                             per_channel_flux=True, beam=beam,
                             dobeam=dobeam, tslot=tslot,
                             sta1=sta1, sta2=sta2)       # [M, B, Fp, 2, 2]
        from sagecal_tpu import dtypes as _dtp
        nreal = jnp.maximum(jnp.sum(wtF > 0), 1).astype(
            _dtp.acc_dtype(x8F.dtype))
        cost_fn = cost_of(x8F, coh, wtF, sta1, sta2, Y=Y, BZ=BZ, rho=rho)
        grad_fn = jax.grad(cost_fn)
        p0f = p0.reshape(-1)
        res_0 = cost_fn(p0f) / nreal
        p1f, mem1, k = lbfgs_mod.lbfgs_fit_minibatch(cost_fn, grad_fn,
                                                     p0f, mem,
                                                     itmax=max_lbfgs)
        res_1 = cost_fn(p1f) / nreal
        return BandSolverOutputs(p1f.reshape(M, kmax, n_stations, 8),
                                 mem1, res_0, res_1, k)

    return jax.jit(solve)


def make_band_solver_batched(dsky, n_stations: int, chunk_idx, chunk_mask,
                             fdelta_chan: float, nu: float, max_lbfgs: int,
                             consensus: bool, dobeam: int = 0,
                             loss: str = "robust"):
    """All-band variant of :func:`make_band_solver`: ONE device program
    solves every mini-band at once (vmap over the band axis).

    The reference loops bands on the host (minibatch_mode.cpp:359-437,
    minibatch_consensus_mode.cpp:446-590) because each band is a separate
    pthread-parallel solve; on a device the band axis is embarrassingly
    parallel (P7: shard band axis across TPU cores). Band-stacked inputs:
    x8F/wtF [W, B, Fp, 8], freqsF [W, Fp], p0 [W, M, K, N, 8], mem
    (stacked pytree); consensus adds Y [W, ...], BZ [W, ...], rho [W, M].
    Shared per-minibatch geometry (u, v, w, sta1, sta2, tslot, beam) is
    broadcast. Returns stacked BandSolverOutputs.

    Execution-time note: one call is ONE device execution over all W
    bands; typical -w band counts (<= 8) keep that execution short
    because each minibatch is tilesz/minibatches slim. Callers with unusually many bands should
    block the band axis like the pipeline blocks -b 1 channels.
    """
    scalar = make_band_solver(dsky, n_stations, chunk_idx, chunk_mask,
                              fdelta_chan, nu, max_lbfgs, consensus,
                              dobeam=dobeam, loss=loss)
    # re-wrap the UNJITTED math: jit of vmap of the inner function
    raw = scalar.__wrapped__

    def pos(x8F, u, v, w, sta1, sta2, wtF, freqsF, tslot, p0, mem,
            Y, BZ, rho, beam):
        return raw(x8F, u, v, w, sta1, sta2, wtF, freqsF, tslot, p0, mem,
                   Y=Y, BZ=BZ, rho=rho, beam=beam)

    band = (0, 0, 0) if consensus else (None, None, None)
    in_axes = (0, None, None, None, None, None, 0, 0, None, 0, 0) \
        + band + (None,)
    return jax.jit(jax.vmap(pos, in_axes=in_axes))


class _StochasticRunner:
    """Shared machinery for both stochastic modes."""

    def __init__(self, cfg: RunConfig, ms: ds.SimMS, sky, log=print):
        self.cfg = cfg
        self.ms = ms
        self.sky = sky
        self.log = log
        meta = ms.meta
        self.meta = meta
        # f32 on accelerators (the reference's float GPU stochastic
        # path); f64 on the CPU mesh when x64 is on, so host-state vs
        # device-state comparisons (the federated sharding-invariance
        # oracle) are exact
        import jax as _jax
        self.rdt = (jnp.float64
                    if (_jax.devices()[0].platform == "cpu"
                        and _jax.config.read("jax_enable_x64"))
                    else jnp.float32)
        # --dtype-policy storage dtype for staged visibilities/weights
        # and the residual readback (sagecal_tpu.dtypes; identity at
        # "f32", so sdt == rdt on default runs)
        from sagecal_tpu import dtypes as _dtp
        _pol = getattr(cfg, "dtype_policy", "f32")
        if _pol != "f32" and self.rdt == jnp.float64:
            # reduced policies pair with the f32/c64 pipeline (the
            # accumulator contract is f32; see pipeline.py)
            self.rdt = jnp.float32
        self.sdt = _dtp.storage_dtype(_pol, self.rdt)
        self.dsky = rp.sky_to_device(sky, self.rdt)
        self.n = meta["n_stations"]
        self.nbase = meta["nbase"]
        self.tilesz = meta["tilesz"]
        self.freqs = np.asarray(meta["freqs"], np.float64)
        self.nchan_total = len(self.freqs)
        self.fdelta_chan = meta["fdelta"] / self.nchan_total

        self.kmax = int(sky.nchunk.max())
        self.cmask = np.arange(self.kmax)[None, :] < sky.nchunk[:, None]
        self.M = sky.n_clusters

        self.chanstart, self.nchan, self.fpad = band_plan(
            self.nchan_total, max(cfg.channel_avg_per_band, 1))
        self.nsolbw = len(self.chanstart)
        self.row0, self.nts, self.tpm = minibatch_rows(
            self.tilesz, self.nbase, max(cfg.n_minibatches, 1))
        self.minibatches = len(self.row0)
        self.bmb = self.tpm * self.nbase     # padded rows per minibatch
        # chunk map for the MINIBATCH length (minibatch_mode.cpp:71)
        self.cidx = rp.chunk_indices(self.tpm, self.nbase, sky.nchunk)

        log(f"Stochastic calibration with {cfg.n_epochs} epochs (passes) of "
            f"{self.minibatches} minibatches each for each solution "
            f"interval.")
        log(f"Time per minibatch: {self.tpm}")
        log(f"Finding {self.nsolbw} solutions, each "
            f"{(self.nchan_total + self.nsolbw - 1) // self.nsolbw} "
            f"channels wide")

        # beam (-B): the reference's stochastic loaders carry the same
        # beam chain as fullbatch (minibatch_mode.cpp uses the _withbeam
        # precalculate/residual variants when doBeam is set)
        self.dobeam = int(cfg.beam_mode)
        self.beam_info = bm.resolve_beaminfo(self.dobeam, ms, meta, log=log)
        self.tile_beam = None
        self._warned_no_times = False

        self.nparam = self.M * self.kmax * self.n * 8
        self._tile_inputs = None
        self._tile_inputs_id = None
        self._resid_jit = self._build_residual_fn()

    def initial_p(self):
        """Per-band [M, K, N, 8] identity Jones (or warm start via -q).

        A multi-band warm-start file (our stochastic writer's format) maps
        band-for-band when the band counts match; otherwise all bands start
        from its first band. Single-band files replicate across bands
        (minibatch_mode.cpp:229-232).
        """
        J0 = np.tile(np.eye(2, dtype=np.complex128),
                     (self.M, self.kmax, self.n, 1, 1))
        per_band = None
        if self.cfg.init_solutions:
            _, blocks = sol.read_solutions(self.cfg.init_solutions,
                                           self.sky.nchunk)
            if blocks:
                last = blocks[-1]
                if isinstance(last, list):
                    per_band = last if len(last) == self.nsolbw \
                        else [last[0]] * self.nsolbw
                else:
                    J0 = last
        pinit = utils.jones_c2r_np(J0).astype(np.float32)
        if per_band is not None:
            return pinit, [utils.jones_c2r_np(Jb).astype(np.float32)
                           for Jb in per_band]
        return pinit, [pinit.copy() for _ in range(self.nsolbw)]

    def prepare_tile(self, tile: ds.VisTile):
        """Pad + upload every (minibatch, band) slice once per tile."""
        self._tile_inputs, self.tile_beam = self.build_tile_inputs(tile)

    def build_tile_inputs(self, tile: ds.VisTile):
        """The staging body of :meth:`prepare_tile`, returning
        ``(inputs, tile_beam)`` WITHOUT touching runner state — safe
        to run on a background reader thread (the serve scheduler's
        tile-interleaved stochastic path stages tile t+1 while tile t
        solves; the solve state the step half mutates lives on the
        StochasticStepper, never here)."""
        tile_inputs = {}
        tile_beam = None
        rdt = self.rdt
        # -x/-y uv window (Data::loadData applies it at load in the
        # reference, so minibatch mode respects it too): solve-scoped
        # flag-2 rows on a COPY — tile.flags is written back verbatim
        rowflags = rp.apply_uvcut(tile.flags, tile,
                                  self.cfg.uvmin, self.cfg.uvmax)
        if self.dobeam:
            if tile.time_mjd is None and not self._warned_no_times:
                self.log("WARNING: dataset tiles carry no timestamps; beam "
                         "az/el will be evaluated at the J2000 placeholder "
                         "epoch")
                self._warned_no_times = True
            tile_beam = bm.beam_to_device(
                self.beam_info, self.meta["freq0"], rdt,
                time_jd=tile.time_jd)
        for nmb in range(self.minibatches):
            r0 = self.row0[nmb]
            nrow = self.nts[nmb] * self.nbase
            sel = slice(r0, r0 + nrow)
            u = np.zeros(self.bmb); v = np.zeros(self.bmb)
            w = np.zeros(self.bmb)
            u[:nrow] = tile.u[sel]; v[:nrow] = tile.v[sel]
            w[:nrow] = tile.w[sel]
            sta1 = np.zeros(self.bmb, np.int32)
            sta2 = np.ones(self.bmb, np.int32)
            sta1[:nrow] = tile.sta1[sel]; sta2[:nrow] = tile.sta2[sel]
            flags = rowflags[sel]
            good = (flags == 0)[:, None]
            uj, vj, wj = (jnp.asarray(u, rdt), jnp.asarray(v, rdt),
                          jnp.asarray(w, rdt))
            s1j, s2j = jnp.asarray(sta1), jnp.asarray(sta2)
            # GLOBAL tile timeslot per row (for beam gathers); padded rows
            # clamp to the last valid slot of this minibatch
            tsg = np.minimum((r0 + np.arange(self.bmb)) // self.nbase,
                             self.tilesz - 1).astype(np.int32)
            tsj = jnp.asarray(tsg)
            for b in range(self.nsolbw):
                c0, nc = self.chanstart[b], self.nchan[b]
                x = np.zeros((self.bmb, self.fpad, 2, 2), np.complex128)
                x[:nrow, :nc] = tile.x[sel, c0:c0 + nc]
                x8F = np.stack(
                    [x.reshape(self.bmb, self.fpad, 4).real,
                     x.reshape(self.bmb, self.fpad, 4).imag],
                    -1).reshape(self.bmb, self.fpad, 8)
                wtF = np.zeros((self.bmb, self.fpad, 8), np.float32)
                ok = np.broadcast_to(good, (nrow, nc))
                if tile.cflags is not None:
                    # per-channel flags (incl. rows flagged in a subset
                    # of a MultiSimMS merge) zero those channels' weights
                    ok = ok & (tile.cflags[sel, c0:c0 + nc] == 0)
                wtF[:nrow, :nc] = np.where(ok[..., None], 1.0, 0.0)
                freqsF = np.full(self.fpad, self.freqs[c0], np.float64)
                freqsF[:nc] = self.freqs[c0:c0 + nc]
                tile_inputs[(nmb, b)] = (
                    jnp.asarray(x8F, self.sdt), uj, vj, wj, s1j, s2j,
                    jnp.asarray(wtF, self.sdt), jnp.asarray(freqsF, rdt),
                    tsj)
        return tile_inputs, tile_beam

    def band_inputs(self, nmb: int, band: int):
        return self._tile_inputs[(nmb, band)]

    def band_inputs_all(self, nmb: int):
        """Band-stacked inputs of one minibatch for the batched solver:
        (x8F [W,B,Fp,8], u, v, w, sta1, sta2, wtF [W,B,Fp,8],
        freqsF [W,Fp], tslot) — geometry is band-invariant."""
        items = [self._tile_inputs[(nmb, b)] for b in range(self.nsolbw)]
        x8F = jnp.stack([it[0] for it in items])
        wtF = jnp.stack([it[6] for it in items])
        freqsF = jnp.stack([it[7] for it in items])
        first = items[0]
        return (x8F, first[1], first[2], first[3], first[4], first[5],
                wtF, freqsF, first[8])

    def stack_state(self, pfreq, mems):
        """Per-band host state -> stacked device state for the batched
        solver."""
        pstack = jnp.asarray(np.stack(pfreq), self.rdt)
        memstack = jax.tree.map(lambda *xs: jnp.stack(xs), *mems)
        return pstack, memstack

    def unstack_state(self, pstack, memstack, pfreq, mems):
        """Write stacked device state back into the per-band host lists
        (in place: end_of_tile's reset logic owns those lists)."""
        p_np = np.asarray(pstack)
        for b in range(self.nsolbw):
            pfreq[b] = p_np[b]
            mems[b] = jax.tree.map(lambda a: a[b], memstack)

    def _build_residual_fn(self):
        """Jitted per-(minibatch, band) residual, reused across tiles.

        Uses the SAME minibatch-length chunk map as the solver, matching
        the reference's per-minibatch calculate_residuals_multifreq calls
        (minibatch_mode.cpp:450-492)."""
        sub = jnp.asarray(self.sky.subtract_mask())
        cidx = jnp.asarray(self.cidx)
        correct_idx = None
        if self.cfg.correct_cluster is not None:
            matches = np.where(self.sky.cluster_ids
                               == self.cfg.correct_cluster)[0]
            if len(matches):
                correct_idx = int(matches[0])

        def resid(x8F, u, v, w, sta1, sta2, freqsF, tslot, J_r8, beam):
            res = rr.calculate_residuals_multifreq(
                self.dsky, J_r8, _x8f_to_complex(x8F),
                u, v, w, freqsF, self.fdelta_chan, sta1, sta2, cidx, sub,
                correct_idx=correct_idx, rho=self.cfg.mmse_rho,
                beam=beam, dobeam=self.dobeam,
                tslot=tslot)
            B, F = x8F.shape[0], x8F.shape[1]
            # storage-dtype writeback emission (identity at "f32")
            return rr.residual_writeback(
                res.reshape(B, F, 4), self.sdt).reshape(B, F, 8)

        return jax.jit(resid)

    def write_residuals(self, tile, ti, pfreq, aw=None):
        """Per-minibatch, per-band residual subtract + write back
        (minibatch_mode.cpp:450-492).

        With an enabled :class:`sched.AsyncWriter` every residual
        program is dispatched up front, the device->host copies start
        non-blocking, and the fetch + assembly + MS write run as ONE
        ordered writer-thread job — the next tile's prepare/solve
        overlaps the whole writeback instead of serializing behind
        per-band ``np.asarray`` fetches. Returns the seconds blocked
        on writer backpressure (bubble accounting)."""
        jobs = []
        for nmb in range(self.minibatches):
            r0 = self.row0[nmb]
            nrow = self.nts[nmb] * self.nbase
            for b in range(self.nsolbw):
                c0, nc = self.chanstart[b], self.nchan[b]
                x8F, u, v, w, s1, s2, _, freqsF, tsj = \
                    self.band_inputs(nmb, b)
                out = self._resid_jit(
                    x8F, u, v, w, s1, s2, freqsF, tsj,
                    jnp.asarray(pfreq[b], self.rdt), self.tile_beam)
                jobs.append((r0, nrow, c0, nc, out))
        if aw is not None and aw.enabled:
            sched.start_host_copy(*[j[-1] for j in jobs])
            return aw.submit(self._assemble_write, tile, ti, jobs)
        self._assemble_write(tile, ti, jobs, bg=False)
        return 0.0

    def _assemble_write(self, tile, ti, jobs, bg=True):
        """Fetch dispatched residual outputs, assemble the channel
        window of every (minibatch, band) slice, write the tile."""
        with dtrace.phase("write", tile=ti, bg=bg):
            with dtrace.phase("convert"):
                xout = np.array(tile.x)
                for r0, nrow, c0, nc, out in jobs:
                    # fetch through float64: numpy-side r2c has no
                    # ml_dtypes bf16 path, and the MS stores complex128
                    res = utils.r2c(np.asarray(out, np.float64).reshape(
                        self.bmb, self.fpad, 4, 2))
                    xout[r0:r0 + nrow, c0:c0 + nc] = res.reshape(
                        self.bmb, self.fpad, 2, 2)[:nrow, :nc]
                tile.x = xout
            with dtrace.phase("put"):
                self.ms.write_tile(ti, tile)

    def solution_writer(self):
        if not self.cfg.solutions_file:
            return None
        return sol.SolutionWriter(
            self.cfg.solutions_file, self.meta["freq0"], self.meta["fdelta"],
            self.tilesz * self.meta["tdelta"] / 60.0, self.n,
            self.M, self.sky.n_eff_clusters,
            nchan=self.nchan_total if self.nsolbw > 1 else None,
            nsolbw=self.nsolbw if self.nsolbw > 1 else None)

    def end_of_tile(self, tile, ti, state, resband, res_0, res_1, t0,
                    writer, history, aw=None, bubble_s=None, overlap=0):
        """Shared per-tile tail: residual write-back, solution rows,
        per-band + global divergence resets, telemetry
        (minibatch_mode.cpp:448-546). ``aw``: ordered writer thread
        (sched.AsyncWriter) — residual + solution writes overlap the
        next tile when enabled; solution blocks are materialized HERE
        (before the reset logic rebinds pfreq entries) so the deferred
        write sees this tile's values. ``bubble_s`` arrives as the io
        wait and accumulates writer backpressure below; ``overlap`` is
        the EFFECTIVE prefetch depth (already clamped to >= 0)."""
        pfreq, mems, pinit = state["pfreq"], state["mems"], state["pinit"]
        wb = self.write_residuals(tile, ti, pfreq, aw=aw)
        if writer:
            blocks = [utils.jones_r2c_np(p.astype(np.float64))
                      for p in pfreq]
            if aw is not None and aw.enabled:
                wb += aw.submit(writer.write_interval_multiband, blocks,
                                self.sky.nchunk)
            else:
                writer.write_interval_multiband(blocks, self.sky.nchunk)

        # per-band reset (minibatch_mode.cpp:516-523)
        for b in range(self.nsolbw):
            if resband[b] > RES_RATIO * res_1:
                self.log(f"Resetting solution for band {b}")
                pfreq[b] = pinit.copy()
                mems[b] = lbfgs_mod.lbfgs_memory_reset(mems[b])
        # global reset (minibatch_mode.cpp:526-542); res_prev forgets a
        # 0/NaN residual entirely so one bad tile cannot ratchet resets
        res_prev = state["res_prev"]
        if res_1 == 0.0 or not np.isfinite(res_1) or (
                res_prev is not None and res_1 > RES_RATIO * res_prev):
            self.log("Resetting Solution")
            for b in range(self.nsolbw):
                pfreq[b] = pinit.copy()
            state["res_prev"] = res_1 if (np.isfinite(res_1) and res_1 > 0) \
                else None
        else:
            state["res_prev"] = res_1 if res_prev is None \
                else min(res_prev, res_1)

        dt = (time.time() - t0) / 60.0
        self.log(f"Timeslot: {ti} Residual: initial={res_0:.6g}, "
                 f"final={res_1:.6g}, Time spent={dt:.3g} minutes")
        history.append({"tile": ti, "res_0": res_0, "res_1": res_1,
                        "minutes": dt})
        extra = {}
        if bubble_s is not None:
            extra = dict(bubble_s=float(bubble_s) + wb,
                         overlap=int(overlap))
        dtrace.emit("tile", tile=ti, res_0=res_0, res_1=res_1,
                    minutes=dt, **extra)


def _open(cfg: RunConfig, log):
    if getattr(cfg, "resume", False):
        # checkpoint/resume is a sequential-fullbatch contract (the
        # minibatch epoch/PRNG chain has no tile-boundary watermark)
        log("resume: unsupported in stochastic mode; starting fresh")
    ms = ds.open_dataset(cfg.ms, cfg.ms_list, tilesz=cfg.tile_size,
                         data_column=cfg.input_column,
                         out_column=cfg.output_column)
    meta = ms.meta
    sky = skymodel.read_sky_cluster(cfg.sky_model, cfg.cluster_file,
                                    meta["ra0"], meta["dec0"], meta["freq0"],
                                    cfg.format_3)
    return ms, sky


def _tile_source(ms, cfg):
    """(source, depth): tile iterator yielding ``(ti, tile, io_wait)``
    with --prefetch read-ahead on a background thread (depth 0 reads
    inline — the synchronous reference path); the io phase records the
    host WAIT, the thread's read time is emitted ``bg``-tagged."""
    depth = max(0, int(getattr(cfg, "prefetch", 1)))
    n = ms.n_tiles
    if cfg.max_timeslots:
        n = min(n, cfg.max_timeslots)

    def src():
        # the io phase (the host's wait) is the Prefetcher's own
        yield from sched.Prefetcher(ms.read_tile, n, depth=depth)

    return src(), depth


class StochasticStepper:
    """The minibatch runner as a resumable per-tile unit — the same
    ``stage``/``step``/``close`` contract as ``pipeline.TileStepper``,
    so the serve scheduler's device-owner loops interleave stochastic
    jobs' tiles with everyone else's instead of running them as one
    opaque blocking unit (ISSUE 12; MIGRATION.md "Fleet mode").

    All mutable solve state (per-band parameter/LBFGS-memory chains,
    reset bookkeeping, the per-job ordered writer) lives HERE;
    :meth:`stage` only builds device inputs (pure w.r.t. this state,
    safe on a reader thread). Outputs are bit-identical to the
    pre-stepper ``run_minibatch`` loop — the epoch/minibatch chain is
    byte-for-byte the same code, stepped one tile at a time. No
    checkpoint sidecar (the minibatch epoch chain has no tile-boundary
    watermark), so stochastic jobs are interleavable and
    cancel/deadline-interruptible at tile boundaries but NOT
    migratable (``ckpt_path`` None)."""

    def __init__(self, cfg: RunConfig, log=print, trace_ctx=None):
        self.cfg = cfg
        self.log = log
        ms, sky = _open(cfg, log)
        self.ms = ms
        self.rn = rn = _StochasticRunner(cfg, ms, sky, log=log)
        self.solver = make_band_solver_batched(
            rn.dsky, rn.n, rn.cidx, rn.cmask, rn.fdelta_chan,
            nu=cfg.robust_nulow, max_lbfgs=cfg.max_lbfgs,
            consensus=False, dobeam=rn.dobeam, loss=cfg.stochastic_loss)
        pinit, pfreq = rn.initial_p()
        self.mems = [lbfgs_mod.lbfgs_memory_init(rn.nparam, cfg.lbfgs_m,
                                                 rn.rdt)
                     for _ in range(rn.nsolbw)]
        self.pfreq = pfreq
        self.writer = rn.solution_writer()
        self.state = {"pfreq": pfreq, "mems": self.mems, "pinit": pinit,
                      "res_prev": None}
        self.n_tiles = ms.n_tiles
        if cfg.max_timeslots:
            self.n_tiles = min(self.n_tiles, cfg.max_timeslots)
        self.start_tile = 0         # no checkpoint: always from 0
        self.ckpt_path = None       # not migratable (see class doc)
        self.depth = max(0, int(getattr(cfg, "prefetch", 1)))
        self.history: list = []
        self.aw = sched.AsyncWriter(enabled=self.depth > 0,
                                    context=trace_ctx)

    # -- reader-thread half --------------------------------------------------

    def stage(self, ti, tile):
        with dtrace.phase("stage", tile=ti, bg=self.depth > 0):
            inputs, beam = self.rn.build_tile_inputs(tile)
        return {"inputs": inputs, "beam": beam}

    # -- device-owner half ---------------------------------------------------

    def step(self, ti, tile, stg, io_wait=0.0):
        cfg, rn, log = self.cfg, self.rn, self.log
        self.aw.check()  # async write failure -> fail at this boundary
        t0 = time.time()
        rn._tile_inputs = stg["inputs"]
        rn.tile_beam = stg["beam"]
        pfreq, mems = self.pfreq, self.mems
        resband = np.zeros(rn.nsolbw)
        res_0 = res_1 = 0.0
        # all bands ride one device program (P7); host state restacks
        # only at tile boundaries where the reset logic lives
        pstack, memstack = rn.stack_state(pfreq, mems)
        for nepch in range(cfg.n_epochs):
            for nmb in range(rn.minibatches):
                args = rn.band_inputs_all(nmb)
                out = self.solver(*args, pstack, memstack, None, None,
                                  None, rn.tile_beam)
                pstack, memstack = out.p, out.mem
                r0s = np.asarray(out.res_0)
                r1s = np.asarray(out.res_1)
                resband[:] = r1s
                if cfg.verbose:
                    for b in range(rn.nsolbw):
                        log(f"epoch={nepch} minibatch={nmb} band={b} "
                            f"{r0s[b]:.6f} {r1s[b]:.6f}")
                res_0, res_1 = float(np.mean(r0s)), float(np.mean(r1s))
                if dtrace.active():
                    dtrace.emit("minibatch", tile=ti, epoch=nepch,
                                minibatch=nmb, res_0=res_0,
                                res_1=res_1,
                                iters=int(np.asarray(out.iters).sum()))
        rn.unstack_state(pstack, memstack, pfreq, mems)

        rn.end_of_tile(tile, ti, self.state, resband, res_0, res_1, t0,
                       self.writer, self.history, aw=self.aw,
                       bubble_s=io_wait, overlap=self.depth)
        return self.history[-1]

    def close(self, raise_pending: bool = True):
        try:
            self.aw.close(raise_pending=raise_pending)
        finally:
            if self.writer:
                self.writer.close()


def stepper(cfg: RunConfig, log=print, trace_ctx=None) -> StochasticStepper:
    """Factory mirroring ``FullBatchPipeline.stepper`` (the serve
    scheduler's entry point for tile-interleaved stochastic jobs)."""
    return StochasticStepper(cfg, log=log, trace_ctx=trace_ctx)


def run_minibatch(cfg: RunConfig, log=print):
    """Stochastic minibatch calibration (minibatch_mode.cpp:47).

    Drives :class:`StochasticStepper` tile by tile — the same unit
    the serve fleet interleaves — with --prefetch read-ahead; outputs
    are bit-identical to the pre-stepper monolithic loop (the solve
    chain is the same code, one tile per step)."""
    st = StochasticStepper(cfg, log=log)
    source, _depth = _tile_source(st.ms, cfg)
    try:
        for ti, tile, io_wait in source:
            st.step(ti, tile, st.stage(ti, tile), io_wait)
    finally:
        st.close()
    return st.history


def run_minibatch_consensus(cfg: RunConfig, log=print):
    """Stochastic minibatch calibration with single-node frequency
    consensus (minibatch_consensus_mode.cpp:47)."""
    ms, sky = _open(cfg, log)
    rn = _StochasticRunner(cfg, ms, sky, log=log)
    if rn.nchan_total == 1:
        raise ValueError("consensus optimization needs more than 1 channel "
                         "(minibatch_consensus_mode.cpp:90)")
    log(f"ADMM iterations={cfg.n_admm} polynomial order={cfg.n_poly} "
        f"regularization={cfg.admm_rho}")

    # per-band polynomial basis at band-center frequencies
    fcen = np.array([rn.freqs[c0:c0 + nc].mean()
                     for c0, nc in zip(rn.chanstart, rn.nchan)])
    B = cpoly.setup_polynomials(fcen, ms.meta["freq0"], cfg.n_poly,
                                cfg.poly_type)                 # [nb, P]

    # per-cluster rho (from -G file or -r), replicated per band
    arho = np.full(rn.M, cfg.admm_rho)
    if cfg.rho_file:
        arho = skymodel.read_cluster_rho(cfg.rho_file, sky.cluster_ids,
                                         cfg.admm_rho)
    rhok = np.tile(arho[None, :], (rn.nsolbw, 1))              # [nb, M]

    Bii = np.asarray(cpoly.find_prod_inverse(B, rhok.T))       # [M, P, P]

    solver = make_band_solver_batched(
        rn.dsky, rn.n, rn.cidx, rn.cmask, rn.fdelta_chan,
        nu=cfg.robust_nulow, max_lbfgs=cfg.max_lbfgs, consensus=True,
        dobeam=rn.dobeam, loss=cfg.stochastic_loss)

    pinit, pfreq = rn.initial_p()
    mems = [lbfgs_mod.lbfgs_memory_init(rn.nparam, cfg.lbfgs_m, rn.rdt)
            for _ in range(rn.nsolbw)]
    writer = rn.solution_writer()
    state = {"pfreq": pfreq, "mems": mems, "pinit": pinit, "res_prev": None}

    pshape = (rn.M, rn.kmax, rn.n, 8)
    cmask4 = rn.cmask[..., None, None]                         # [M, K, 1, 1]
    history = []
    source, depth = _tile_source(ms, cfg)
    aw = sched.AsyncWriter(enabled=depth > 0)
    try:
        for ti, tile, io_wait in source:
            aw.check()
            t0 = time.time()
            rn.prepare_tile(tile)
            Y = np.zeros((rn.nsolbw,) + pshape)                # dual, per band
            Z = np.zeros((rn.M, cfg.n_poly, rn.kmax, rn.n, 8))
            resband = np.zeros(rn.nsolbw)
            res_0 = res_1 = 0.0
            pstack, memstack = rn.stack_state(pfreq, mems)
            rho_d = jnp.asarray(rhok, rn.rdt)
            for nadmm in range(cfg.n_admm):
                for nepch in range(cfg.n_epochs):
                    for nmb in range(rn.minibatches):
                        # ONE device program solves all bands (P7); the
                        # host keeps only the cheap Z/Y consensus updates
                        BZ_all = np.einsum("bp,mpkns->bmkns", B, Z)
                        args = rn.band_inputs_all(nmb)
                        out = solver(*args, pstack, memstack,
                                     jnp.asarray(Y, rn.rdt),
                                     jnp.asarray(BZ_all, rn.rdt),
                                     rho_d, rn.tile_beam)
                        pstack, memstack = out.p, out.mem
                        p_np = np.asarray(pstack, np.float64)
                        r0s = np.asarray(out.res_0)
                        r1s = np.asarray(out.res_1)
                        # -ve residual marks a bad solve
                        resband[:] = np.where((r0s > 0) & (r1s > 0), r1s,
                                              np.inf)
                        if cfg.verbose:
                            for b in range(rn.nsolbw):
                                primal = float(np.linalg.norm(
                                    (p_np[b] - BZ_all[b]) * cmask4)
                                    / np.sqrt(p_np[b].size))
                                log(f"admm={nadmm} epoch={nepch} "
                                    f"minibatch={nmb} band={b} primal "
                                    f"{primal:.6f} {r0s[b]:.6f} {r1s[b]:.6f}")
                        res_0, res_1 = float(np.mean(r0s)), float(np.mean(r1s))
                        if dtrace.active():
                            primal = float(np.linalg.norm(
                                (p_np - BZ_all) * cmask4[None])
                                / np.sqrt(p_np.size))
                            dtrace.emit("minibatch", tile=ti, admm=nadmm,
                                        epoch=nepch, minibatch=nmb,
                                        res_0=res_0, res_1=res_1,
                                        primal=primal,
                                        iters=int(np.asarray(out.iters).sum()))
                        # flag diverged bands out of the Z update (:528-546)
                        fband = resband > RES_RATIO * res_1

                        # ADMM updates (minibatch_consensus_mode.cpp:551-590)
                        good = ~fband
                        for b in np.where(good)[0]:
                            Y[b] += rhok[b][:, None, None, None] * p_np[b]
                        zsum = np.einsum("b,bp,bmkns->mpkns",
                                         good.astype(float), B, Y)
                        Zold = Z.copy()
                        Z = np.asarray(cpoly.z_from_contributions(
                            jnp.asarray(zsum), jnp.asarray(Bii)))
                        dual = np.linalg.norm(Z - Zold) / np.sqrt(Z.size)
                        if cfg.verbose:
                            log(f"ADMM : {nadmm} dual residual={dual:.6f}")
                        if dtrace.active():
                            dtrace.emit("admm_iter", interval=ti, iter=nadmm,
                                        r1_mean=res_1, dual=float(dual),
                                        rho_mean=float(np.mean(rhok)))
                        for b in np.where(good)[0]:
                            BZb = np.einsum("p,mpkns->mkns", B[b], Z)
                            Y[b] -= rhok[b][:, None, None, None] * BZb
            rn.unstack_state(pstack, memstack, pfreq, mems)

            if cfg.use_global_solution:
                log("Using Global")
                for b in range(rn.nsolbw):
                    pfreq[b] = np.einsum("p,mpkns->mkns", B[b], Z).astype(
                        np.float32)

            rn.end_of_tile(tile, ti, state, resband, res_0, res_1, t0,
                           writer, history, aw=aw, bubble_s=io_wait,
                           overlap=depth)
    finally:
        aw.close()
    if writer:
        writer.close()
    return history
