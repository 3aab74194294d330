"""Pallas fused-sweep kernel: the cluster visit's [B]-pass in ONE grid.

The per-cluster solve floor is data movement over the baseline axis, not
arithmetic (arXiv:1910.13908, arXiv:1410.8706; this repo's own older
CPU record, in git before PR 32: a ~34 ms/cluster B-independent floor under ``chol``
and a 13.6-16.6x loss for ``cg`` because every PCG trip re-pays a full
[B]-row pass). The XLA assembly (solvers/normal_eq.py) walks the rows
several times per damping iteration — model eval, residual, Wirtinger
factors MA/MB, then the Gram/gradient contractions — materializing
[B]-sized intermediates between fused regions. This module melts that
structurally, in two pieces (reference GPU analogue: the hand-fused
mderiv.cu / lmfit_cuda.c kernels):

1. :func:`sweep_blocks` — ONE streaming pass over the [B] rows per
   cluster visit (per hybrid chunk). Each grid cell loads a
   [bt, nbase] time-block of the visibility rows, evaluates the model
   (Jp C Jq^H), the residual, and the Wirtinger factors entirely in
   registers/VMEM, and accumulates PER-BASELINE Gram blocks (pp/qq/pq),
   gradients (jtep/jteq) and the acceptance cost with f32 (acc-dtype)
   accumulators over bf16/f16 storage operands. NOTHING [B]-sized is
   written back — the outputs are [K, nbase]-sized, B-independent
   partials.
2. :func:`gn_matvec_blocks` — the matrix-free PCG/tCG product computed
   from those per-baseline blocks: y = (JTJ + shift I) v becomes one
   VMEM-resident pass over [K, nbase] 8x8-structured blocks (gather v
   per baseline, block products, scatter-add per station). Exact up to
   summation order: JTJ is the sum of per-baseline outer blocks, so
   contracting the time axis into the blocks FIRST (once per outer
   point, in the fused sweep) turns every inner trip from a full
   [B]-row pass into an O(nbase) pass — the structural reason
   ``--inner cg`` stops re-paying row traffic per trip.

Wrappers (:func:`normal_equations_fused`, :func:`gn_blocks`) return the
same (op, JTe, cost) contract as normal_eq.normal_equations /
gn_factors, so lm.py / rtr.py dispatch on a ``kernel='xla'|'pallas'``
config flag. Dispatch follows the ops/coh_pallas.py precedent:
:func:`supported` gating (baseline-major layout, kmax <= MAX_CHUNKS) +
``interpret=`` for CPU correctness — CPU executions run the SAME kernel
through the Pallas interpreter (parity-gated in
tests/test_sweep_pallas.py), while the ``kernel='xla'`` default stays
bit-frozen. Summation-order freedom: the fused pass contracts (time,
component) axes in a different order than the XLA einsums, so parity vs
the dense reference is tolerance-gated (tight at f32/f64; per-policy
envelopes under bf16/f16 — MIGRATION.md "Pallas kernels").

Hybrid chunks: cluster time chunks are contiguous time blocks
(rime.predict.chunk_indices), but their boundaries are traced
per-cluster values, so the kernel cannot slice rows per chunk
statically. Instead the grid is (K, time-blocks): chunk k's cells
re-stream the rows with a ``chunk_id == k`` row mask folded into the
weights and chunk k's per-baseline Jones planes. K <= MAX_CHUNKS keeps
the re-read factor bounded (K == 1, the single-chunk common case, skips
the mask entirely).

Layout: rows arrive [tilesz * nbase, 8] baseline-major (the same
row_period invariant normal_eq builds on) and are VIEWED [T, nbase, ...]
— no transposes, no copies. Inside the kernel every quantity is a
[bt, nbase] plane (baselines ride the trailing/lane axis); the 2x2
complex algebra unrolls over the tiny station-component indices with the
factor-matrix sign structure folded in at trace time (MA/MB are +/-
aliases of the A/Bm planes — see normal_eq._ma_factor/_mb_factor).
Complex inputs are split re/im OUTSIDE the kernel (Pallas has no
complex dtype); the Jones gathers are [K, nbase]-sized (per-baseline,
not per-row).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from sagecal_tpu import dtypes as dtp

#: flop estimate per visibility-row visit for one fused sweep pass
#: (model eval + residual + factor Grams + gradients + cost); feeds the
#: pl.CostEstimate AND diag/roofline's pallas pricing
#: (cost_analysis cannot see inside a compiled pallas_call)
SWEEP_FLOPS_PER_ROW = 1100
#: flop estimate per (chunk, baseline) block for one blocks matvec
MATVEC_FLOPS_PER_BASELINE = 300
#: hybrid-chunk cap: the grid re-streams the rows once per chunk, so
#: the fused pass stops paying above a few chunks (reference hybrid
#: clusters use 1-2)
MAX_CHUNKS = 4


def supported(kmax: int, row_period: int, B: int) -> bool:
    """True when the fused kernels apply: baseline-major
    [tilesz, nbase] row layout (the normal_eq row_period invariant) and
    a bounded hybrid-chunk count. Host-side static decision."""
    return (1 <= kmax <= MAX_CHUNKS and row_period > 0
            and B % row_period == 0)


def interpret_default() -> bool:
    """Pallas interpreter on every non-TPU backend (the coh_pallas
    CPU-correctness contract); compiled Mosaic on TPU."""
    return jax.default_backend() != "tpu"


def check_kernel(kernel: str) -> None:
    """Refuse ``--kernel pallas`` on the TPU backend BEFORE any solve
    (called where SageConfig.kernel is filled: pipeline.py, cli_mpi.py).

    The fused sweep (:func:`sweep_blocks`) lowers for Mosaic but its
    TPU compile does not return (PERF.md "Bring-up on v5e": no answer
    within 10 minutes for v5e even at N=8, T=4, K=1), so a run that
    reached it would hang on its first cluster solve. The interpreter
    path on CPU is unaffected."""
    if kernel == "pallas" and not interpret_default():
        raise ValueError(
            "--kernel pallas is not available on the TPU backend: the "
            "fused sweep kernel (ops/sweep_pallas.sweep_blocks) does "
            "not compile under Mosaic yet (the compile never returns); "
            "use the default --kernel xla, or --platform cpu for the "
            "interpreter path")


class GNBlocks(NamedTuple):
    """Per-(chunk, baseline) Gram blocks of the Gauss-Newton operator
    at the current point — the ``kernel='pallas'`` analogue of
    normal_eq.GNFactors. All leaves accumulate in the acc dtype.

    pp: [K, nb, 2, md, md] station-p diagonal sub-blocks (block-diag
        over the first complex index — the dense [2md, 2md] station
        block is I2 (x) pp); md = 4/2/1 per jones mode full/diag/phase;
    qq: [K, nb, 2, md, md] station-q diagonal sub-blocks;
    pq: [K, nb, 2, 2, md, md] station-pair cross blocks (row (a, i),
        col (o, j) of the dense off-diagonal block);
    D:  [K, N, 2, md, md] station-aggregated diagonal blocks (the exact
        preconditioner / mu0 seed — identical quantity to GNFactors.D).
    """

    pp: jax.Array
    qq: jax.Array
    pq: jax.Array
    D: jax.Array


def _pick_bt(T: int, nb: int, itemsize: int) -> int:
    """Largest divisor of T keeping one grid cell's INPUT set under
    ~4 MB (the VMEM working-set budget; on CPU interpret this usually
    means bt == T — a single fused region per chunk). Per time-row the
    cell loads 3 row-blocks (x/w/cw: 8 components each) + 2 coherency
    blocks (4 components each) = 32 elements/baseline — budgeting only
    one block would overshoot VMEM ~4x at exactly the large shapes the
    kernel targets."""
    budget = 4 << 20
    bt = max(1, min(T, budget // max(nb * 32 * itemsize, 1)))
    while T % bt:
        bt -= 1
    return bt


def _cplx_mats(x, tag):
    """[..., 2, 2] array -> {(tag, i, j): plane} dict of planes."""
    return {(tag, i, j): x[..., i, j] for i in range(2)
            for j in range(2)}


# factor-matrix sign structure (normal_eq._ma_factor/_mb_factor), as
# trace-time tables: MA[o, ri, (d, ci)] over the A = C Jq^H planes and
# MB[a, ri, (d, ci)] over the Bm = Jp C planes. Each entry is
# (sign, part, row, col) with part "r"/"i" selecting the re/im plane.
def _ma_entry(o, ri, d, ci):
    if ri == 0 and ci == 0:
        return (1.0, "r", d, o)
    if ri == 0 and ci == 1:
        return (-1.0, "i", d, o)
    if ri == 1 and ci == 0:
        return (1.0, "i", d, o)
    return (1.0, "r", d, o)                     # ri == 1, ci == 1


def _mb_entry(a, ri, d, ci):
    if ri == 0 and ci == 0:
        return (1.0, "r", a, d)
    if ri == 0 and ci == 1:
        return (1.0, "i", a, d)
    if ri == 1 and ci == 0:
        return (1.0, "i", a, d)
    return (-1.0, "r", a, d)                    # ri == 1, ci == 1


def _sweep_body(x, w, cw, chre, chim, jpr, jpi, jqr, jqi, *, acc,
                reduced, st, jones="full"):
    """The fused sweep's per-cell math, shared by the per-visit kernel
    (:func:`_sweep_kernel`) and the multi-visit K-major kernel
    (:func:`_visits_kernel`).

    Inputs: x/w/cw [bt, nb, 8] in acc (weights already chunk-masked);
    chre/chim [bt, nb, 2, 2]; jpr/jpi/jqr/jqi [nb, 2, 2]. Returns the
    time-contracted per-baseline partials (pp [2, md, md, nb],
    qq [2, md, md, nb], pq [2, 2, md, md, nb], jte [2, 2, md, nb] side
    p/q first, cost [nb]) — elementwise the same accumulation chains
    the pre-refactor kernel wrote per (a, i, j), just stacked.

    ``jones`` (static) picks the constrained-Jones factor algebra at
    TRACE time: md = 4 (full — the factor lookup reduces to the exact
    MA/MB alias tables, so the emitted chain is unchanged), 2 (diag) or
    1 (phase). No runtime branch: the mode only changes which +/-
    aliases of the A/Bm (and Jones-rotated, for phase) planes the
    unrolled loops read and how far the block indices range.
    """
    Cr = _cplx_mats(chre, "C")                  # [bt, nb] planes
    Ci = _cplx_mats(chim, "C")
    Pr = _cplx_mats(jpr, "P")                   # [nb] planes
    Pi = _cplx_mats(jpi, "P")
    Qr = _cplx_mats(jqr, "Q")
    Qi = _cplx_mats(jqi, "Q")

    def cpx_mm(Xr, Xi, xn, Yr, Yi, yn, conj_t=False):
        """2x2 complex matmul on plane dicts: X @ Y (or X @ Y^H)."""
        Zr, Zi = {}, {}
        for a in range(2):
            for o in range(2):
                zr = None
                zi = None
                for d in range(2):
                    xr, xi = Xr[(xn, a, d)], Xi[(xn, a, d)]
                    if conj_t:
                        yr, yi = Yr[(yn, o, d)], -Yi[(yn, o, d)]
                    else:
                        yr, yi = Yr[(yn, d, o)], Yi[(yn, d, o)]
                    tr = xr * yr - xi * yi
                    ti = xr * yi + xi * yr
                    zr = tr if zr is None else zr + tr
                    zi = ti if zi is None else zi + ti
                Zr[("Z", a, o)] = zr
                Zi[("Z", a, o)] = zi
        return Zr, Zi

    # A = C Jq^H, Bm = Jp C, V = Jp A — all [bt, nb] plane sets
    Ar, Ai = cpx_mm(Cr, Ci, "C", Qr, Qi, "Q", conj_t=True)
    Br, Bi = cpx_mm(Pr, Pi, "P", Cr, Ci, "C")
    Vr, Vi = cpx_mm(Pr, Pi, "P", Ar, Ai, "Z")

    def q(p):
        """Storage-quantization boundary for the reduced policies: the
        XLA path stores the model emission and the Wirtinger factors in
        the storage dtype before contracting with f32 accumulators —
        the kernel rounds the SAME planes at the same boundary
        (identity at f32/f64)."""
        return p.astype(st).astype(acc) if reduced else p

    fA = {("r", i, j): q(Ar[("Z", i, j)]) for i in range(2)
          for j in range(2)}
    fA.update({("i", i, j): q(Ai[("Z", i, j)]) for i in range(2)
               for j in range(2)})
    fB = {("r", i, j): q(Br[("Z", i, j)]) for i in range(2)
          for j in range(2)}
    fB.update({("i", i, j): q(Bi[("Z", i, j)]) for i in range(2)
               for j in range(2)})

    md = {"full": 4, "diag": 2, "phase": 1}[jones]
    if jones == "full":
        # exact MA/MB alias tables (normal_eq._ma_factor/_mb_factor);
        # the station-diagonal index c is vacuous (FA is c-independent
        # in full mode), so the emitted chain matches the pre-mode
        # kernel term for term
        def FAf(c, o, ri, m):
            s, part, i_, j_ = _ma_entry(o, ri, m // 2, m % 2)
            return s, fA[(part, i_, j_)]

        def FBf(c, a, ri, m):
            s, part, i_, j_ = _mb_entry(a, ri, m // 2, m % 2)
            return s, fB[(part, i_, j_)]
    elif jones == "diag":
        # d == c planes of the same tables: params (Re, Im) of j_cc
        def FAf(c, o, ri, m):
            s, part, i_, j_ = _ma_entry(o, ri, c, m)
            return s, fA[(part, i_, j_)]

        def FBf(c, a, ri, m):
            s, part, i_, j_ = _mb_entry(a, ri, c, m)
            return s, fB[(part, i_, j_)]
    else:
        # phase: FA from u = i Jp_cc A[c, o], FB from -i conj(Jq_cc)
        # B[a, c] — Jones-rotated planes built from the UNQUANTIZED
        # A/Bm planes then rounded at the same storage boundary as the
        # XLA mode path (normal_eq._mode_factors + to_storage)
        fAp, fBp = {}, {}
        for c in range(2):
            for o in range(2):
                ur = (jpr[..., c, c] * Ar[("Z", c, o)]
                      - jpi[..., c, c] * Ai[("Z", c, o)])
                ui = (jpr[..., c, c] * Ai[("Z", c, o)]
                      + jpi[..., c, c] * Ar[("Z", c, o)])
                fAp[(c, o, 0)] = q(-ui)           # ri = Re
                fAp[(c, o, 1)] = q(ur)            # ri = Im
            for a in range(2):
                wr = (jqr[..., c, c] * Br[("Z", a, c)]
                      + jqi[..., c, c] * Bi[("Z", a, c)])
                wi = (jqr[..., c, c] * Bi[("Z", a, c)]
                      - jqi[..., c, c] * Br[("Z", a, c)])
                fBp[(c, a, 0)] = q(wi)            # ri = Re
                fBp[(c, a, 1)] = q(-wr)           # ri = Im

        def FAf(c, o, ri, m):
            return 1.0, fAp[(c, o, ri)]

        def FBf(c, a, ri, m):
            return 1.0, fBp[(c, a, ri)]

    # residual planes r[a][o][ri] (x is storage-exact in acc; the model
    # quantizes at q) and the weight planes
    comp = lambda arr, a, o, ri: arr[..., (a * 2 + o) * 2 + ri]
    w2, rw2, rc = {}, {}, None
    for a in range(2):
        for o in range(2):
            for ri in range(2):
                vm = q(Vr[("Z", a, o)] if ri == 0 else Vi[("Z", a, o)])
                r_ = comp(x, a, o, ri) - vm
                wv = comp(w, a, o, ri)
                w2[(a, o, ri)] = wv * wv
                rw2[(a, o, ri)] = r_ * wv * wv
                rcp = r_ * comp(cw, a, o, ri)
                rc = rcp * rcp if rc is None else rc + rcp * rcp
    cost = jnp.sum(rc, axis=0)

    def tsum(p):                                # [bt, nb] -> [nb]
        return jnp.sum(p, axis=0)

    # per-baseline Gram/gradient partials, signs folded at trace time.
    # Loops range over the mode's block width md; under full the FAf/FBf
    # lookups alias MA/MB exactly, so the a/o names below ARE the old
    # complex row/col indices and the chain is unchanged.
    pp_rows = []
    for a in range(2):
        rows = []
        for i in range(md):
            cols = []
            for j in range(md):
                accu = None
                for o in range(2):
                    for ri in range(2):
                        si, mi = FAf(a, o, ri, i)
                        sj, mj = FAf(a, o, ri, j)
                        t = (si * sj) * (w2[(a, o, ri)] * mi * mj)
                        accu = t if accu is None else accu + t
                cols.append(tsum(accu))
            rows.append(jnp.stack(cols))
        pp_rows.append(jnp.stack(rows))
    pp = jnp.stack(pp_rows)                     # [2, md, md, nb]
    qq_rows = []
    for o in range(2):
        rows = []
        for i in range(md):
            cols = []
            for j in range(md):
                accu = None
                for a in range(2):
                    for ri in range(2):
                        si, mi = FBf(o, a, ri, i)
                        sj, mj = FBf(o, a, ri, j)
                        t = (si * sj) * (w2[(a, o, ri)] * mi * mj)
                        accu = t if accu is None else accu + t
                cols.append(tsum(accu))
            rows.append(jnp.stack(cols))
        qq_rows.append(jnp.stack(rows))
    qq = jnp.stack(qq_rows)                     # [2, md, md, nb]
    pq_outer = []
    for a in range(2):
        pq_inner = []
        for o in range(2):
            rows = []
            for i in range(md):
                cols = []
                for j in range(md):
                    accu = None
                    for ri in range(2):
                        si, mi = FAf(a, o, ri, i)
                        sj, mj = FBf(o, a, ri, j)
                        t = (si * sj) * (w2[(a, o, ri)] * mi * mj)
                        accu = t if accu is None else accu + t
                    cols.append(tsum(accu))
                rows.append(jnp.stack(cols))
            pq_inner.append(jnp.stack(rows))
        pq_outer.append(jnp.stack(pq_inner))
    pq = jnp.stack(pq_outer)                    # [2, 2, md, md, nb]
    jp_rows = []
    for a in range(2):
        cols = []
        for i in range(md):
            accu = None
            for o in range(2):
                for ri in range(2):
                    si, mi = FAf(a, o, ri, i)
                    t = si * (rw2[(a, o, ri)] * mi)
                    accu = t if accu is None else accu + t
            cols.append(tsum(accu))
        jp_rows.append(jnp.stack(cols))
    jq_rows = []
    for o in range(2):
        cols = []
        for i in range(md):
            accu = None
            for a in range(2):
                for ri in range(2):
                    si, mi = FBf(o, a, ri, i)
                    t = si * (rw2[(a, o, ri)] * mi)
                    accu = t if accu is None else accu + t
            cols.append(tsum(accu))
        jq_rows.append(jnp.stack(cols))
    jte = jnp.stack([jnp.stack(jp_rows), jnp.stack(jq_rows)])
    return pp, qq, pq, jte, cost


def _sweep_kernel(x_ref, w_ref, cw_ref, cid_ref, chr_ref, chi_ref,
                  jpr_ref, jpi_ref, jqr_ref, jqi_ref, pp_ref, qq_ref,
                  pq_ref, jte_ref, cost_ref, *, acc, reduced, st,
                  kmax, jones="full"):
    """One (chunk, time-block) grid cell of the fused sweep.

    Refs: x/w/cw [bt, nb, 8] storage; cid [bt, nb] int32 (row chunk
    ids); chr/chi [bt, nb, 2, 2] acc (coherency re/im); jp*/jq*
    [1, nb, 2, 2] acc (THIS chunk's per-baseline Jones re/im). Outputs
    accumulate across time cells per chunk (out index_map pinned to the
    chunk axis): pp/qq [1, 2, 4, 4, nb], pq [1, 2, 2, 4, 4, nb],
    jte [1, 2, 2, 4, nb] (side p/q first), cost [1, nb] — acc dtype.
    """
    k = pl.program_id(0)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        pp_ref[...] = jnp.zeros_like(pp_ref)
        qq_ref[...] = jnp.zeros_like(qq_ref)
        pq_ref[...] = jnp.zeros_like(pq_ref)
        jte_ref[...] = jnp.zeros_like(jte_ref)
        cost_ref[...] = jnp.zeros_like(cost_ref)

    x = x_ref[...].astype(acc)                  # [bt, nb, 8]
    w = w_ref[...].astype(acc)
    cw = cw_ref[...].astype(acc)
    if kmax > 1:
        # hybrid-chunk row mask: this cell contributes chunk k's rows
        # only (chunk blocks are time-contiguous, so whole planes
        # usually mask 0/1; the multiply keeps it branch-free)
        mk = (cid_ref[...] == k).astype(acc)    # [bt, nb]
        w = w * mk[..., None]
        cw = cw * mk[..., None]
    pp, qq, pq, jte, cost = _sweep_body(
        x, w, cw, chr_ref[...], chi_ref[...], jpr_ref[0], jpi_ref[0],
        jqr_ref[0], jqi_ref[0], acc=acc, reduced=reduced, st=st,
        jones=jones)
    pp_ref[0] += pp
    qq_ref[0] += qq
    pq_ref[0] += pq
    jte_ref[0] += jte
    cost_ref[0, :] += cost


def _visits_kernel(x_ref, w_ref, cw_ref, cid_ref, chr_ref, chi_ref,
                   jpr_ref, jpi_ref, jqr_ref, jqi_ref, pp_ref, qq_ref,
                   pq_ref, jte_ref, cost_ref, *, acc, reduced, st,
                   kmax, jones="full"):
    """One (time-block, visit*chunk) grid cell of the MULTI-VISIT
    K-major sweep: V cluster visits share one grid so the per-call
    floor (and any row operand the visits share — weights, cost
    weights, chunk ids — see :func:`sweep_blocks_visits`) amortizes
    across directions.

    The grid is (T//bt, V*K) with the time axis OUTER: for a fixed
    time block the inner axis sweeps every (visit, chunk) cell, so a
    shared row block's index_map is constant across consecutive cells
    (fetched once per time block, not once per visit). Each output
    block is written exactly ONCE (cell (t, vk) owns out[t, vk]) — the
    cross-time reduction happens outside the kernel, keeping the
    revisit pattern trivially legal for compiled Mosaic. Refs carry a
    leading singleton visit axis (shared operands are pinned to index
    0 by their spec); jones refs are [1, 1, nb, 2, 2] (visit, chunk).
    """
    k = pl.program_id(1) % kmax

    x = x_ref[0].astype(acc)                    # [bt, nb, 8]
    w = w_ref[0].astype(acc)
    cw = cw_ref[0].astype(acc)
    if kmax > 1:
        mk = (cid_ref[0] == k).astype(acc)      # [bt, nb]
        w = w * mk[..., None]
        cw = cw * mk[..., None]
    pp, qq, pq, jte, cost = _sweep_body(
        x, w, cw, chr_ref[0], chi_ref[0], jpr_ref[0, 0], jpi_ref[0, 0],
        jqr_ref[0, 0], jqi_ref[0, 0], acc=acc, reduced=reduced, st=st,
        jones=jones)
    pp_ref[0, 0] = pp
    qq_ref[0, 0] = qq
    pq_ref[0, 0] = pq
    jte_ref[0, 0] = jte
    cost_ref[0, 0, :] = cost


@functools.partial(jax.jit, static_argnames=("row_period", "kmax",
                                             "block_t", "interpret",
                                             "jones"))
def sweep_blocks(x8, J, coh, sta1, sta2, chunk_id, wt, cost_wt,
                 row_period: int, kmax: int, block_t: int = 0,
                 interpret: bool | None = None, jones: str = "full"):
    """The fused cluster-visit pass: per-(chunk, baseline) Gram blocks,
    gradient partials and the acceptance cost from one streaming
    [B]-pass per chunk.

    x8/wt/cost_wt: [B, 8] (storage dtype; ``cost_wt`` may equal
    ``wt``); J: [K, N, 2, 2] complex; coh: [B, 2, 2] complex;
    sta1/sta2/chunk_id: [B] (baseline-periodic stations — only the
    first ``row_period`` entries are used). ``jones`` (static) selects
    the constrained parameterization (normal_eq.JONES_MODES): the block
    trailing dims shrink 4 -> md (diag 2, phase 1) at trace time.
    Returns (pp [K, nb, 2, md, md], qq [K, nb, 2, md, md],
    pq [K, nb, 2, 2, md, md], jtep [K, nb, 2, md], jteq [K, nb, 2, md],
    cost [K]), all in the acc dtype of the data.
    """
    md = {"full": 4, "diag": 2, "phase": 1}[jones]
    if jones != "full":
        J = J * jnp.eye(2, dtype=J.real.dtype)
    B = x8.shape[0]
    nb = int(row_period)
    T = B // nb
    K = int(kmax)
    st = x8.dtype
    acc = dtp.acc_dtype(st)
    reduced = dtp.is_reduced(st)
    if interpret is None:
        interpret = interpret_default()
    s1b, s2b = sta1[:nb], sta2[:nb]
    Jp = jnp.take(J, s1b, axis=1)               # [K, nb, 2, 2] complex
    Jq = jnp.take(J, s2b, axis=1)
    bt = block_t if block_t else _pick_bt(T, nb, jnp.dtype(acc).itemsize)
    if T % bt:
        raise ValueError(
            f"block_t={bt} does not divide the {T} timeslots — the "
            f"(K, T//bt) grid would silently drop the tail rows")
    grid = (K, T // bt)
    rows = lambda a: a.reshape(T, nb, 8)        # free view, no copy
    row_spec = pl.BlockSpec((bt, nb, 8), lambda k, t: (t, 0, 0))
    cid_spec = pl.BlockSpec((bt, nb), lambda k, t: (t, 0))
    coh_spec = pl.BlockSpec((bt, nb, 2, 2), lambda k, t: (t, 0, 0, 0))
    jones_spec = pl.BlockSpec((1, nb, 2, 2), lambda k, t: (k, 0, 0, 0))
    def kernel(*refs):
        # plain def (not functools.partial) so jaxlint's traced-body
        # closure follows pallas_call -> kernel -> _sweep_kernel
        _sweep_kernel(*refs, acc=acc, reduced=reduced, st=st, kmax=K,
                      jones=jones)
    n_flops = SWEEP_FLOPS_PER_ROW * B * 8 * K
    n_bytes = int(K * (3 * B * 8 * jnp.dtype(st).itemsize
                       + 2 * B * 4 * jnp.dtype(acc).itemsize)
                  + K * (2 * (2 * md * md) + 4 * md * md + 4 * md + 1)
                  * nb * jnp.dtype(acc).itemsize)
    pp, qq, pq, jte, cost = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[row_spec, row_spec, row_spec, cid_spec, coh_spec,
                  coh_spec, jones_spec, jones_spec, jones_spec,
                  jones_spec],
        out_specs=[
            pl.BlockSpec((1, 2, md, md, nb),
                         lambda k, t: (k, 0, 0, 0, 0)),
            pl.BlockSpec((1, 2, md, md, nb),
                         lambda k, t: (k, 0, 0, 0, 0)),
            pl.BlockSpec((1, 2, 2, md, md, nb),
                         lambda k, t: (k, 0, 0, 0, 0, 0)),
            pl.BlockSpec((1, 2, 2, md, nb),
                         lambda k, t: (k, 0, 0, 0, 0)),
            pl.BlockSpec((1, nb), lambda k, t: (k, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((K, 2, md, md, nb), acc),
            jax.ShapeDtypeStruct((K, 2, md, md, nb), acc),
            jax.ShapeDtypeStruct((K, 2, 2, md, md, nb), acc),
            jax.ShapeDtypeStruct((K, 2, 2, md, nb), acc),
            jax.ShapeDtypeStruct((K, nb), acc),
        ],
        cost_estimate=pl.CostEstimate(flops=n_flops,
                                      bytes_accessed=n_bytes,
                                      transcendentals=0),
        interpret=interpret,
    )(rows(x8), rows(wt), rows(cost_wt),
      chunk_id.reshape(T, nb).astype(jnp.int32),
      coh.real.astype(acc).reshape(T, nb, 2, 2),
      coh.imag.astype(acc).reshape(T, nb, 2, 2),
      Jp.real.astype(acc), Jp.imag.astype(acc),
      Jq.real.astype(acc), Jq.imag.astype(acc))
    # [K, .., nb] -> [K, nb, ..] caller layouts (all [nbase]-sized)
    pp = jnp.moveaxis(pp, -1, 1)                # [K, nb, 2, md, md]
    qq = jnp.moveaxis(qq, -1, 1)
    pq = jnp.moveaxis(pq, -1, 1)                # [K, nb, 2, 2, md, md]
    jtep = jnp.moveaxis(jte[:, 0], -1, 1)       # [K, nb, 2, md]
    jteq = jnp.moveaxis(jte[:, 1], -1, 1)
    return pp, qq, pq, jtep, jteq, jnp.sum(cost, axis=-1)


@functools.partial(jax.jit, static_argnames=("row_period", "kmax",
                                             "vsize", "batched",
                                             "block_t", "interpret",
                                             "jones"))
def sweep_blocks_visits(x8, J, coh, sta1, sta2, chunk_id, wt, cost_wt,
                        row_period: int, kmax: int, vsize: int,
                        batched: tuple, block_t: int = 0,
                        interpret: bool | None = None,
                        jones: str = "full"):
    """V cluster visits in ONE K-major grid: the multi-cluster schedule
    that amortizes the per-visit pallas_call floor (and every SHARED
    row operand's traffic) across directions.

    ``batched`` is a static 6-tuple of bools for (x8, J, coh, chunk_id,
    wt, cost_wt): True means the operand carries a leading [V] visit
    axis, False means ONE array is shared by all visits — the kernel
    body is identical either way; only the BlockSpec index_map changes
    (shared operands pin the visit index to 0, so with the time axis
    outer a shared row block is fetched once per time block instead of
    once per (visit, chunk) cell). sta1/sta2 are always shared (global
    station geometry). Outputs are per-cell [T//bt, V*K, ...] blocks
    written exactly once, reduced over the time axis OUTSIDE the
    kernel — same values as ``jax.vmap(sweep_blocks)`` up to that sum
    order. Returns the :func:`sweep_blocks` tuple with a leading [V]
    axis on every output.
    """
    xb, jb, cb, cidb, wb, cwb = batched
    md = {"full": 4, "diag": 2, "phase": 1}[jones]
    if jones != "full":
        J = J * jnp.eye(2, dtype=J.real.dtype)
    V = int(vsize)
    B = x8.shape[-2]
    nb = int(row_period)
    T = B // nb
    K = int(kmax)
    st = x8.dtype
    acc = dtp.acc_dtype(st)
    reduced = dtp.is_reduced(st)
    if interpret is None:
        interpret = interpret_default()
    s1b, s2b = sta1[:nb], sta2[:nb]
    Jp = jnp.take(J, s1b, axis=-3)          # [(V,) K, nb, 2, 2] complex
    Jq = jnp.take(J, s2b, axis=-3)
    bt = block_t if block_t else _pick_bt(T, nb, jnp.dtype(acc).itemsize)
    if T % bt:
        raise ValueError(
            f"block_t={bt} does not divide the {T} timeslots — the "
            f"(T//bt, V*K) grid would silently drop the tail rows")
    grid = (T // bt, K * V)                     # time OUTER, visits inner

    def vmap_ix(b):
        return (lambda t, vk: (vk // K, t, 0, 0)) if b \
            else (lambda t, vk: (0, t, 0, 0))

    def row_spec(b):
        return pl.BlockSpec((1, bt, nb, 8), vmap_ix(b))

    def coh_spec(b):
        return pl.BlockSpec((1, bt, nb, 2, 2),
                            (lambda t, vk: (vk // K, t, 0, 0, 0)) if b
                            else (lambda t, vk: (0, t, 0, 0, 0)))

    cid_spec = pl.BlockSpec((1, bt, nb),
                            (lambda t, vk: (vk // K, t, 0)) if cidb
                            else (lambda t, vk: (0, t, 0)))
    jones_spec_b = pl.BlockSpec(
        (1, 1, nb, 2, 2), lambda t, vk: (vk // K, vk % K, 0, 0, 0))
    jones_spec_s = pl.BlockSpec(
        (1, 1, nb, 2, 2), lambda t, vk: (0, vk % K, 0, 0, 0))
    jones_spec = jones_spec_b if jb else jones_spec_s

    def rows(a, b):                             # free view, no copy
        return a.reshape(((V,) if b else (1,)) + (T, nb, 8))

    def cohv(a, b):
        return a.reshape(((V,) if b else (1,)) + (T, nb, 2, 2))

    def jonesv(a, b):
        return a.reshape(((V,) if b else (1,)) + (K, nb, 2, 2))

    def kernel(*refs):
        _visits_kernel(*refs, acc=acc, reduced=reduced, st=st, kmax=K,
                       jones=jones)

    nt = T // bt
    n_flops = SWEEP_FLOPS_PER_ROW * B * 8 * K * V
    n_bytes = int(K * V * (3 * B * 8 * jnp.dtype(st).itemsize
                           + 2 * B * 4 * jnp.dtype(acc).itemsize)
                  + nt * K * V
                  * (2 * (2 * md * md) + 4 * md * md + 4 * md + 1)
                  * nb * jnp.dtype(acc).itemsize)
    pp, qq, pq, jte, cost = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[row_spec(xb), row_spec(wb), row_spec(cwb), cid_spec,
                  coh_spec(cb), coh_spec(cb), jones_spec, jones_spec,
                  jones_spec, jones_spec],
        out_specs=[
            pl.BlockSpec((1, 1, 2, md, md, nb),
                         lambda t, vk: (t, vk, 0, 0, 0, 0)),
            pl.BlockSpec((1, 1, 2, md, md, nb),
                         lambda t, vk: (t, vk, 0, 0, 0, 0)),
            pl.BlockSpec((1, 1, 2, 2, md, md, nb),
                         lambda t, vk: (t, vk, 0, 0, 0, 0, 0)),
            pl.BlockSpec((1, 1, 2, 2, md, nb),
                         lambda t, vk: (t, vk, 0, 0, 0, 0)),
            pl.BlockSpec((1, 1, nb), lambda t, vk: (t, vk, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nt, V * K, 2, md, md, nb), acc),
            jax.ShapeDtypeStruct((nt, V * K, 2, md, md, nb), acc),
            jax.ShapeDtypeStruct((nt, V * K, 2, 2, md, md, nb), acc),
            jax.ShapeDtypeStruct((nt, V * K, 2, 2, md, nb), acc),
            jax.ShapeDtypeStruct((nt, V * K, nb), acc),
        ],
        cost_estimate=pl.CostEstimate(flops=n_flops,
                                      bytes_accessed=n_bytes,
                                      transcendentals=0),
        interpret=interpret,
    )(rows(x8, xb), rows(wt, wb), rows(cost_wt, cwb),
      chunk_id.reshape(((V,) if cidb else (1,)) + (T, nb))
      .astype(jnp.int32),
      cohv(coh.real.astype(acc), cb), cohv(coh.imag.astype(acc), cb),
      jonesv(Jp.real.astype(acc), jb), jonesv(Jp.imag.astype(acc), jb),
      jonesv(Jq.real.astype(acc), jb), jonesv(Jq.imag.astype(acc), jb))
    # reduce the per-cell time axis, split (V, K), restore caller
    # layouts ([V, K, nb, ...] — everything stays [nbase]-sized)
    pp = jnp.sum(pp, axis=0).reshape((V, K) + pp.shape[2:])
    qq = jnp.sum(qq, axis=0).reshape((V, K) + qq.shape[2:])
    pq = jnp.sum(pq, axis=0).reshape((V, K) + pq.shape[2:])
    jte = jnp.sum(jte, axis=0).reshape((V, K) + jte.shape[2:])
    cost = jnp.sum(cost, axis=0).reshape(V, K, nb)
    pp = jnp.moveaxis(pp, -1, 2)                # [V, K, nb, 2, md, md]
    qq = jnp.moveaxis(qq, -1, 2)
    pq = jnp.moveaxis(pq, -1, 2)                # [V, K, nb, 2, 2, md, md]
    jtep = jnp.moveaxis(jte[:, :, 0], -1, 2)    # [V, K, nb, 2, md]
    jteq = jnp.moveaxis(jte[:, :, 1], -1, 2)
    return pp, qq, pq, jtep, jteq, jnp.sum(cost, axis=-1)


@functools.lru_cache(maxsize=None)
def _sweep_vmappable(row_period: int, kmax: int, block_t: int,
                     interpret, jones: str = "full"):
    """:func:`sweep_blocks` wrapped in jax.custom_batching.custom_vmap,
    specialized per static signature (cached so repeated traces reuse
    one callable — custom_vmap identity is object identity).

    Un-vmapped calls behave exactly like sweep_blocks. Under jax.vmap
    (the SAGE driver's in-flight group lanes: ``_group_update`` vmaps
    the whole per-cluster solve), the batching rule routes the V
    stacked visits onto the K-major visits grid
    (:func:`sweep_blocks_visits`) instead of jax's default
    prepend-a-grid-dim rule — one kernel call whose SHARED operands
    (typically the row weights and chunk ids) are fetched once per
    time block rather than broadcast per visit. Batched station maps
    (never produced by the solvers — station geometry is global) fall
    back to a serial lax.map."""

    @jax.custom_batching.custom_vmap
    def fn(x8, J, coh, sta1, sta2, chunk_id, wt, cost_wt):
        return sweep_blocks(x8, J, coh, sta1, sta2, chunk_id, wt,
                            cost_wt, row_period, kmax, block_t=block_t,
                            interpret=interpret, jones=jones)

    @fn.def_vmap
    def _rule(axis_size, in_batched, x8, J, coh, sta1, sta2, chunk_id,
              wt, cost_wt):
        xb, jb, cb, s1bt, s2bt, cidb, wb, cwb = in_batched
        out_b = (True,) * 6
        if s1bt or s2bt:
            def one(i):
                def pick(a, b):
                    return jax.lax.dynamic_index_in_dim(
                        a, i, 0, keepdims=False) if b else a
                return fn(pick(x8, xb), pick(J, jb), pick(coh, cb),
                          pick(sta1, s1bt), pick(sta2, s2bt),
                          pick(chunk_id, cidb), pick(wt, wb),
                          pick(cost_wt, cwb))
            return jax.lax.map(one, jnp.arange(axis_size)), out_b
        outs = sweep_blocks_visits(
            x8, J, coh, sta1, sta2, chunk_id, wt, cost_wt, row_period,
            kmax, axis_size, (xb, jb, cb, cidb, wb, cwb),
            block_t=block_t, interpret=interpret, jones=jones)
        return outs, out_b

    return fn


def _sweep_dispatch(x8, J, coh, sta1, sta2, chunk_id, wt, cw,
                    row_period: int, kmax: int, block_t: int,
                    interpret, jones: str = "full"):
    """The wrapper entry both operator assemblies route through: plain
    sweep_blocks semantics outside vmap, the K-major multi-visit grid
    under it (see :func:`_sweep_vmappable`)."""
    return _sweep_vmappable(int(row_period), int(kmax), int(block_t),
                            interpret, str(jones))(
        x8, J, coh, sta1, sta2, chunk_id, wt, cw)


def _station_aggregates(pp, qq, jtep, jteq, s1b, s2b, N: int):
    """(D [K, N, 2, md, md], JTe [K, 2*md*N]) from the per-baseline
    partials — the [nbase]-sized scatter shared by the dense and
    matrix-free wrappers (identical structure to normal_eq's station
    aggregation). md is read off the block shapes (4/2/1 per jones
    mode)."""
    K = pp.shape[0]
    md = pp.shape[-1]
    acc = pp.dtype
    D = jnp.zeros((K, N, 2, md, md), acc)
    D = D.at[:, s1b].add(pp).at[:, s2b].add(qq)
    JTe = jnp.zeros((K, N, 2, md), acc)
    JTe = JTe.at[:, s1b].add(jtep).at[:, s2b].add(jteq)
    return D, JTe.reshape(K, 2 * md * N)


def gn_blocks(x8, J, coh, sta1, sta2, chunk_id, wt, n_stations: int,
              kmax: int, row_period: int, cost_wt=None, block_t: int = 0,
              interpret: bool | None = None, jones: str = "full"):
    """Matrix-free operator assembly under ``kernel='pallas'``: the
    fused sweep's per-baseline Gram blocks become the PCG/tCG operator
    (:class:`GNBlocks`), plus (JTe [K, 8N], cost [K]) — the same
    contract as normal_eq.gn_factors, with the [B]-pass fused and the
    carried operator B-INDEPENDENT ([K, nbase]-sized). ``jones``
    specializes the blocks per constrained mode (JTe is [K, 2*md*N])."""
    cw = wt if cost_wt is None else cost_wt
    pp, qq, pq, jtep, jteq, cost = _sweep_dispatch(
        x8, J, coh, sta1, sta2, chunk_id, wt, cw, row_period, kmax,
        block_t, interpret, jones)
    nb = int(row_period)
    s1b, s2b = sta1[:nb], sta2[:nb]
    D, JTe = _station_aggregates(pp, qq, jtep, jteq, s1b, s2b,
                                 n_stations)
    return GNBlocks(pp=pp, qq=qq, pq=pq, D=D), JTe, cost


def _assemble_damped(fac: GNBlocks, shift, sta1, sta2,
                     n_stations: int):
    """Dense [K, 8N, 8N] (damped) normal matrix from the per-baseline
    blocks — the ONE place the blocks expand densely, shared by the
    dense wrapper (:func:`normal_equations_fused`, ``shift=None``) and
    the fused-Cholesky solve stage (:func:`chol_solve_blocks_shift`).

    ``shift`` (None or [K]) folds into the [K, N, 2, md, md] station
    diagonals BEFORE the dense (2*md)x(2*md) expansion: the assembled matrix's
    diagonal lives entirely in D (pq couples distinct stations only),
    so this is elementwise identical to ``JTJ + shift * I`` on the
    dense matrix while skipping the [K, 8N, 8N] eye-add pass the
    dense carry used to pay per damping trip."""
    K, nb = fac.pp.shape[0], fac.pp.shape[1]
    md = fac.pp.shape[-1]
    npar = 2 * md
    N = n_stations
    acc = fac.pp.dtype
    s1b, s2b = sta1[:nb], sta2[:nb]
    D = fac.D
    if shift is not None:
        eyem = jnp.eye(md, dtype=acc)
        D = D + shift[:, None, None, None, None] * eyem
    eye2 = jnp.eye(2, dtype=acc)
    Dfull = jnp.einsum("knaij,ab->knaibj", D,
                       eye2).reshape(K, N, npar, npar)
    pq8 = jnp.transpose(fac.pq,
                        (0, 1, 2, 4, 3, 5)).reshape(K, nb, npar, npar)
    pq8T = jnp.transpose(fac.pq,
                         (0, 1, 3, 5, 2, 4)).reshape(K, nb, npar, npar)
    idx = jnp.arange(N, dtype=sta1.dtype)
    JTJ = jnp.zeros((K, N, npar, N, npar), acc)
    for k in range(K):                          # K <= MAX_CHUNKS, static
        JTJ = JTJ.at[k, s1b, :, s2b, :].add(pq8[k])
        JTJ = JTJ.at[k, s2b, :, s1b, :].add(pq8T[k])
    JTJ = JTJ.at[:, idx, :, idx, :].add(jnp.swapaxes(Dfull, 0, 1))
    return JTJ.reshape(K, npar * N, npar * N)


def chol_solve_blocks_shift(fac: GNBlocks, JTe, shift, sta1, sta2,
                            n_stations: int, reduced: bool = False):
    """ONE batched assemble+factor+solve attempt of the damped system
    (JTJ(fac) + shift I) dp = JTe from the per-baseline blocks; returns
    (dp, ok) with ok = dp all-finite per chunk.

    This is the executed all-ok body of :func:`solve_damped_blocks` —
    whoever prices a trip prices THIS function under
    (kernel='pallas', inner='chol') because XLA cost analysis sums
    both branches of the retry lax.cond (the same phantom-bytes class
    lm._chol_solve_shift exists for). The assembled matrix is exactly
    symmetric by construction (pp/qq are elementwise symmetric in
    (i, j); pq enters with its exact transpose), so the factorization
    skips cho_factor's symmetrize pass (``symmetrize_input=False``)
    with bit-identical results: (a + a)/2 == a exactly in binary
    floating point. ``reduced`` routes the bf16/f16 storage policies
    through the LU body (jnp.linalg.solve) — the same
    trajectory-tolerance contract as lm._lu_solve_shift."""
    A = _assemble_damped(fac, shift, sta1, sta2, n_stations)
    if reduced:
        dp = jnp.linalg.solve(A, JTe[..., None])[..., 0]
    else:
        L = jax.lax.linalg.cholesky(A, symmetrize_input=False)
        dp = jax.scipy.linalg.cho_solve((L, True), JTe[..., None])[..., 0]
    return dp, jnp.all(jnp.isfinite(dp), axis=-1)


def solve_damped_blocks(fac: GNBlocks, JTe, mu, jitter, sta1, sta2,
                        n_stations: int, rho=0.0,
                        reduced: bool = False):
    """lm._solve_damped on the per-baseline blocks carry: solve
    (JTJ + (mu + jitter [+ rho]) I) dp = JTe batched over chunks
    without ever CARRYING the dense [K, 8N, 8N] matrix — the blocks
    assemble, factor and solve inside this call, so the LM state stays
    [K, nbase]-sized and the eye-add / symmetrize / dense-select
    passes of the dense carry disappear.

    Retry semantics preserved exactly: a failed factorization
    (non-finite dp) gets ONE jittered retry with the regularization
    floor boosted to 1e-3 * max|diag| per chunk — the diagonal read
    straight from the [K, N, 2, 4, 4] D blocks (the dense diagonal
    lives entirely there) plus the ADMM ``rho`` shift, matching the
    dense path's boost on its rho-augmented matrix. Chunks that still
    fail return dp = 0 and recover through mu-growth. The retry hides
    behind a lax.cond so the all-ok common case pays one
    factorization; ``rho`` rides the solve shift (the blocks are never
    rho-augmented), mirroring the inner='cg' convention."""
    shift = mu + jitter + rho

    def solve(sh):
        return chol_solve_blocks_shift(fac, JTe, sh, sta1, sta2,
                                       n_stations, reduced=reduced)

    dp, ok = solve(shift)

    def done():
        return jnp.where(ok[:, None], dp, 0.0), ok

    def retry():
        dd = jnp.diagonal(fac.D, axis1=-2, axis2=-1)    # [K, N, 2, 4]
        diag_max = jnp.max(jnp.abs(dd.reshape(dd.shape[0], -1)),
                           axis=-1) + rho
        dp2, ok2 = solve(shift + 1e-3 * jnp.maximum(diag_max, 1e-30))
        dpw = jnp.where(ok[:, None], dp,
                        jnp.where(ok2[:, None], dp2, 0.0))
        return dpw, ok | ok2

    return jax.lax.cond(jnp.all(ok), done, retry)


def normal_equations_fused(x8, J, coh, sta1, sta2, chunk_id, wt,
                           n_stations: int, kmax: int, row_period: int,
                           cost_wt=None, block_t: int = 0,
                           interpret: bool | None = None,
                           jones: str = "full"):
    """Dense-path analogue of normal_eq.normal_equations under
    ``kernel='pallas'``: the fused sweep produces the per-baseline
    blocks in one [B]-pass per chunk; the dense [K, 8N, 8N] expansion
    is the same [nbase]/[N]-sized scatter tail as the XLA
    baseline-major path (shared with the fused-Cholesky solve stage —
    :func:`_assemble_damped` with ``shift=None`` is bit-identical to
    the pre-refactor inline tail)."""
    N = n_stations
    cw = wt if cost_wt is None else cost_wt
    pp, qq, pq, jtep, jteq, cost = _sweep_dispatch(
        x8, J, coh, sta1, sta2, chunk_id, wt, cw, row_period, kmax,
        block_t, interpret, jones)
    nb = int(row_period)
    s1b, s2b = sta1[:nb], sta2[:nb]
    D, JTe = _station_aggregates(pp, qq, jtep, jteq, s1b, s2b, N)
    fac = GNBlocks(pp=pp, qq=qq, pq=pq, D=D)
    return _assemble_damped(fac, None, sta1, sta2, N), JTe, cost


def _matvec_kernel(pp_ref, qq_ref, pq_ref, vp_ref, vq_ref, yp_ref,
                   yq_ref):
    """One VMEM-resident blocks matvec (per chunk grid cell): inputs
    pp/qq [1, 2, md, md, nb], pq [1, 2, 2, md, md, nb], vp/vq
    [1, 2, md, nb]; outputs yp/yq [1, 2, md, nb] (md unrolled at
    trace time from the ref block shapes).

    yp[a, i] = sum_j pp[a, i, j] vp[a, j]
             + sum_{o, j} pq[a, o, i, j] vq[o, j]
    yq[o, j] = sum_i qq[o, j, i] vq[o, i]
             + sum_{a, i} pq[a, o, i, j] vp[a, i]
    (the exact action of the dense station blocks the same pq/pp/qq
    scatter into — see normal_equations_fused)."""
    pp = pp_ref[0]
    qq = qq_ref[0]
    pq = pq_ref[0]
    vp = vp_ref[0]
    vq = vq_ref[0]
    md = pp_ref.shape[2]
    for a in range(2):
        for i in range(md):
            accu = None
            for j in range(md):
                t = pp[a, i, j, :] * vp[a, j, :]
                accu = t if accu is None else accu + t
            for o in range(2):
                for j in range(md):
                    accu = accu + pq[a, o, i, j, :] * vq[o, j, :]
            yp_ref[0, a, i, :] = accu
    for o in range(2):
        for j in range(md):
            accu = None
            for i in range(md):
                t = qq[o, j, i, :] * vq[o, i, :]
                accu = t if accu is None else accu + t
            for a in range(2):
                for i in range(md):
                    accu = accu + pq[a, o, i, j, :] * vp[a, i, :]
            yq_ref[0, o, j, :] = accu


@functools.partial(jax.jit, static_argnames=("n_stations", "interpret"))
def _matvec_blocks_jit(pp, qq, pq, v, s1b, s2b, n_stations: int,
                       interpret: bool):
    N = n_stations
    K, nb = pp.shape[0], pp.shape[1]
    md = pp.shape[-1]
    acc = pp.dtype
    vr = v.reshape(K, N, 2, md).astype(acc)
    vp = jnp.moveaxis(jnp.take(vr, s1b, axis=1), 1, -1)  # [K, 2, md, nb]
    vq = jnp.moveaxis(jnp.take(vr, s2b, axis=1), 1, -1)
    spec_g = pl.BlockSpec((1, 2, md, md, nb), lambda k: (k, 0, 0, 0, 0))
    spec_x = pl.BlockSpec((1, 2, 2, md, md, nb),
                          lambda k: (k, 0, 0, 0, 0, 0))
    spec_v = pl.BlockSpec((1, 2, md, nb), lambda k: (k, 0, 0, 0))
    n_bytes = int(K * (2 * (2 * md * md) + 4 * md * md + 4 * (2 * md))
                  * nb * jnp.dtype(acc).itemsize)
    yp, yq = pl.pallas_call(
        _matvec_kernel,
        grid=(K,),
        in_specs=[spec_g, spec_g, spec_x, spec_v, spec_v],
        out_specs=[spec_v, spec_v],
        out_shape=[jax.ShapeDtypeStruct((K, 2, md, nb), acc),
                   jax.ShapeDtypeStruct((K, 2, md, nb), acc)],
        cost_estimate=pl.CostEstimate(
            flops=MATVEC_FLOPS_PER_BASELINE * nb * K,
            bytes_accessed=n_bytes, transcendentals=0),
        interpret=interpret,
    )(jnp.moveaxis(pp, 1, -1), jnp.moveaxis(qq, 1, -1),
      jnp.moveaxis(pq, 1, -1), vp, vq)
    y = jnp.zeros((K, N, 2, md), acc)
    y = y.at[:, s1b].add(jnp.moveaxis(yp, -1, 1))
    y = y.at[:, s2b].add(jnp.moveaxis(yq, -1, 1))
    return y.reshape(K, 2 * md * N).astype(v.dtype)


def gn_matvec_blocks(fac: GNBlocks, v, sta1, sta2, n_stations: int,
                     shift=None, interpret: bool | None = None):
    """(JTJ + shift I) @ v from the per-baseline Gram blocks: one
    O(nbase), B-independent pass (drop-in for normal_eq.gn_matvec under
    ``kernel='pallas'``; same [K, 8N] v/y layout and [K]-shaped
    ``shift`` contract)."""
    nb = fac.pp.shape[1]
    if interpret is None:
        interpret = interpret_default()
    y = _matvec_blocks_jit(fac.pp, fac.qq, fac.pq, v, sta1[:nb],
                           sta2[:nb], n_stations, bool(interpret))
    if shift is not None:
        y = y + jnp.asarray(shift)[..., None] * v
    return y
