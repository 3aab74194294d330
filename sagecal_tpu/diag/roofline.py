"""Program pricing from XLA's cost analysis, for tests.

What is left of the retired round's roofline account (its driver and
records went in PR 32): tests price a program with ``lower_cost`` /
``program_cost`` / ``pallas_cost`` to pin WHICH body executes, and
``roofline_fields`` classifies a priced step. No speed is measured
here: ``benchmarks/`` does that on the chip, with its own peaks file
(``benchmarks/peaks.json``, which the table below doubles).

The question it answers, per compiled program: how many FLOPs and how
many HBM bytes does one execution touch (XLA's own cost analysis via
``lowered.compile().cost_analysis()``), what does a given wall-clock
make of that in achieved GFLOP/s and GB/s, and which side of the device
ridge point (peak FLOP/s ÷ peak bytes/s) does the program's operational
intensity fall on. Both CubiCal (arXiv:1805.03410) and the SAGECal GPU
work (arXiv:1910.13908) ground their speedup claims in this per-kernel
op/byte accounting.

Known slack, inherited from XLA's static analysis: loop bodies are
priced once regardless of trip count (the solvers' executed-trip
counters, lm.TRIP_KEYS, say how often), and "bytes accessed"
is the optimistic each-buffer-moves-once figure, so achieved GB/s is a
lower bound on real traffic.
"""

from __future__ import annotations

import numpy as np

# Per-chip peaks by device kind substring: (bf16 peak FLOP/s, HBM
# bytes/s). Sources: published TPU spec sheets (v2 45 TF/700 GB/s,
# v3 123 TF/900 GB/s, v4 275 TF/1228 GB/s, v5e 197 TF/819 GB/s,
# v5p 459 TF/2765 GB/s, v6e 918 TF/1640 GB/s). Order matters: "v5p"
# must match before "v5".
_PEAKS = (
    ("v6", 918e12, 1640e9),
    ("v5p", 459e12, 2765e9),
    ("v5", 197e12, 819e9),
    ("v4", 275e12, 1228e9),
    ("v3", 123e12, 900e9),
    ("v2", 45e12, 700e9),
)

# Nominal single-core host fallback so a CPU run still classifies:
# ~one AVX2 core (16 f32 FLOP/cycle x ~3 GHz) against ~25 GB/s of the
# socket's memory bandwidth. Coarse on purpose — the *ridge* (~2
# FLOP/byte) is what the bound verdict needs, and CPU ridges sit within
# a small factor of it across a decade of hardware.
_CPU_PEAKS = (1e11, 25e9)


def device_peaks(device):
    """(peak FLOP/s, peak bytes/s, nominal?) for ``device``; Nones when
    the device kind is unrecognized."""
    if getattr(device, "platform", None) == "cpu":
        return _CPU_PEAKS[0], _CPU_PEAKS[1], True
    kind = getattr(device, "device_kind", "").lower().replace(" ", "")
    for key, pf, pb in _PEAKS:
        if key in kind:
            return pf, pb, False
    return None, None, False


# ---------------------------------------------------------------------------
# per-program cost extraction
# ---------------------------------------------------------------------------

def zero_cost() -> dict:
    return {"flops": 0.0, "bytes_accessed": 0.0}


def _from_cost_analysis(ca) -> dict:
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0))}


def pallas_cost(jfn, args, kwargs=None) -> dict:
    """{flops, bytes_accessed} summed over the COMPILED pallas_call
    equations in ``jfn``'s jaxpr.

    XLA's cost analysis cannot see inside a Mosaic-compiled
    ``pallas_call`` — on TPU the kernel lowers to an opaque custom call
    priced at ~zero, silently dropping the kernel's traffic from every
    figure. This walks the (pre-lowering) jaxpr instead: each
    pallas_call carries its author's ``cost_estimate`` (absent that,
    as in ops/coh_pallas.py, bytes fall back to the operand+result
    aval sizes — the same each-buffer-moves-once convention as XLA's
    own figure, with flops unknown = 0).
    INTERPRET-mode calls are skipped: the interpreter lowering is plain
    HLO, which cost_analysis already prices — adding the estimate there
    would double-count."""
    import jax
    from jax.extend.core import ClosedJaxpr
    out = zero_cost()

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                if eqn.params.get("interpret"):
                    continue
                ce = eqn.params.get("cost_estimate")
                if ce is not None and (getattr(ce, "flops", 0)
                                       or getattr(ce, "bytes_accessed",
                                                  0)):
                    out["flops"] += float(ce.flops)
                    out["bytes_accessed"] += float(ce.bytes_accessed)
                else:
                    out["bytes_accessed"] += float(sum(
                        v.aval.size * v.aval.dtype.itemsize
                        for v in list(eqn.invars) + list(eqn.outvars)
                        if hasattr(v, "aval")))
            for v in eqn.params.values():
                # sub-jaxprs hide in several param shapes: a bare
                # ClosedJaxpr (pjit/scan/while), an object with .eqns,
                # or a TUPLE of ClosedJaxprs (lax.cond/switch
                # 'branches') — missing the tuple case would silently
                # drop any kernel sitting under a solver-mode cond
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    if isinstance(sub, ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif hasattr(sub, "eqns"):
                        walk(sub)

    # a pricing walk that breaks raises: a silent zero here would price
    # every compiled Pallas call on the chip at nothing
    walk(jax.make_jaxpr(jfn)(*args, **(kwargs or {})).jaxpr)
    return out


def program_cost(jfn, args, kwargs=None) -> dict:
    """FLOPs + bytes accessed of ONE execution of the compiled program
    ``jfn(*args, **kwargs)`` via XLA cost analysis, plus the
    :func:`pallas_cost` correction for Mosaic-compiled kernels the
    analysis cannot see into. Static figures: loop bodies price once
    (callers correct with executed trip counts)."""
    comp = jfn.lower(*args, **(kwargs or {})).compile()
    cost = _from_cost_analysis(comp.cost_analysis())
    return combine(cost, pallas_cost(jfn, args, kwargs))


def lower_cost(fn, *specs) -> dict:
    """Price ``fn`` at abstract shapes (jax.ShapeDtypeStruct) — lowering
    + cost analysis only, nothing executes."""
    import jax
    return program_cost(jax.jit(fn), specs, {})


def combine(*costs) -> dict:
    """Field-wise sum; None entries are skipped."""
    out = zero_cost()
    for c in costs:
        if c is None:
            continue
        out["flops"] += c["flops"]
        out["bytes_accessed"] += c["bytes_accessed"]
    return out


def scale(cost, k) -> dict:
    if cost is None:
        return None
    return {"flops": cost["flops"] * k,
            "bytes_accessed": cost["bytes_accessed"] * k}


def nbytes_of(tree) -> int:
    """Total host bytes of every array leaf in a pytree — the staging
    accountant (how much crosses host->device per tile)."""
    import jax
    return int(sum(getattr(leaf, "nbytes", 0)
                   for leaf in jax.tree.leaves(tree)))


# ---------------------------------------------------------------------------
# classification
# ---------------------------------------------------------------------------

def roofline_fields(cost, wall_s, device) -> dict:
    """Roofline record for one timed step: achieved rates + bound verdict.

    ``cost``: {"flops", "bytes_accessed"} of the step (trip-corrected by
    the caller); ``wall_s``: measured seconds per step. Returns a dict
    of:

    - ``flops``, ``bytes_accessed`` — the step's totals;
    - ``achieved_flops_per_s``, ``achieved_gbps`` — vs wall-clock;
    - ``intensity`` — FLOPs per byte accessed;
    - ``ridge`` — the device's peak-FLOPs/peak-bandwidth ridge point;
    - ``bound`` — "compute" | "bandwidth": which roof the program's
      intensity puts it under (below the ridge = bandwidth-bound);
    - ``pct_peak_flops`` / ``pct_peak_bw`` — achieved fraction of each
      roof (absent when device peaks are unknown);
    - ``peaks_nominal`` — True when the CPU fallback peaks were used.
    """
    flops = float(cost["flops"])
    bts = float(cost["bytes_accessed"])
    out = {"flops": flops, "bytes_accessed": bts}
    if wall_s and wall_s > 0:
        out["achieved_flops_per_s"] = flops / wall_s
        out["achieved_gbps"] = bts / wall_s / 1e9
    intensity = flops / bts if bts > 0 else float("inf")
    out["intensity"] = intensity if np.isfinite(intensity) else None
    pf, pb, nominal = device_peaks(device)
    if pf and pb:
        ridge = pf / pb
        out["ridge"] = ridge
        out["bound"] = "bandwidth" if intensity < ridge else "compute"
        out["peaks_nominal"] = bool(nominal)
        if wall_s and wall_s > 0:
            out["pct_peak_flops"] = 100.0 * flops / wall_s / pf
            out["pct_peak_bw"] = 100.0 * bts / wall_s / pb
    else:
        # no peak table for this device: classify against the observed
        # machine balance so 'bound' is always present — a program doing
        # >100 FLOPs per byte is compute-bound on any current hardware
        out["bound"] = "compute" if intensity >= 100.0 else "bandwidth"
    return out
