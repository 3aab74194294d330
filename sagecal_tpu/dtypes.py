"""Mixed-precision dtype policy: reduced storage, f32 accumulation.

An XLA-priced roofline of rounds 5-7 (cpu: 0.73 FLOP/B; no chip reading
bears it out, PERF.md section 5) took bytes as what buys wall-clock, and after the wins of
rounds 6-7 the remaining factor-of-2 on the dominant [B]-pass traffic is
the storage dtype. The policy here is the storage/accumulate split the
CubiCal per-kernel op/byte accounting motivates (arXiv:1805.03410) and
the complex-Wirtinger formulation tolerates (arXiv:1410.8706):

- **storage** (``bf16``/``f16``): the [B]-proportional data arrays —
  visibilities ``x8``, sqrt-weights ``wt``, residual streams, and the
  Wirtinger factors MA/MB — quantize to the policy dtype the moment
  they are materialized;
- **accumulation** (always f32, or the pipeline dtype when no reduction
  is active): every Gram product, matvec, JTe, cost and residual-norm
  reduction names an f32 accumulator — either ``preferred_element_type``
  on the contraction or an explicit upcast fused into the reduce. Silent
  bf16 accumulation is a jaxlint finding (``storage-accum``).

What NEVER takes the storage dtype (MIGRATION.md "Dtype policy"):
solutions J (c64 end to end), the dense JTJ + Cholesky factors, the
consensus state (Y/Z/BZ), uvw geometry and fringe phases (the RIME
phase 2*pi*u*l*f needs every f32 bit), and the robust-nu grid root-find
(deliberately f64, robust.py). Complex coherencies stay c64 on the
solve path because XLA has no sub-f32 complex type; their share of one
priced LM trip is ~1% (PERF.md round 9), so the melt rides the real
factor arrays instead.

The ``"f32"`` policy is the identity: every helper here returns its
input unchanged (``lax.convert_element_type`` short-circuits on equal
dtypes), so the plumbing is bit-transparent for default runs — gated by
tests/test_dtype_policy.py.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

# user-facing policy names (--dtype-policy on both CLIs)
POLICIES = ("f32", "bf16", "f16")

_REDUCED = {
    "bf16": jnp.bfloat16,
    "f16": jnp.float16,
}


def validate(policy: str) -> str:
    if policy not in POLICIES:
        raise ValueError(
            f"unknown dtype policy {policy!r}; choose from {POLICIES}")
    return policy


def storage_dtype(policy: str, default=jnp.float32):
    """Storage dtype of ``policy``; ``"f32"`` maps to ``default`` (the
    pipeline real dtype), so the default policy never changes anything —
    including f64-under-x64 CPU runs."""
    validate(policy)
    return _REDUCED.get(policy, default)


def is_reduced(dtype) -> bool:
    """True for sub-f32 storage dtypes (bf16/f16)."""
    return jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                jnp.dtype(jnp.float16))


def acc_dtype(dtype):
    """Accumulator dtype paired with storage ``dtype``: f32 for reduced
    storage, the dtype itself otherwise (f32 stays f32, f64 under x64
    stays f64 — existing paths are untouched)."""
    return jnp.float32 if is_reduced(dtype) else jnp.dtype(dtype)


def acc(x):
    """Upcast a storage array to its accumulator dtype at the point of
    reduction. No-op (returns ``x``) when the input is not reduced."""
    return x.astype(acc_dtype(x.dtype))


def to_storage(x, dtype):
    """Emit ``x`` in the storage dtype. No-op when ``dtype`` is not a
    reduced dtype (so the f32 policy costs the default path nothing and
    stays bit-identical)."""
    if not is_reduced(dtype):
        return x
    return x.astype(dtype)


def storage_np(policy: str, default=None):
    """Numpy dtype for HOST-side staging under ``policy`` — the
    casting boundary where [B]-data leaves numpy for the device
    (cli_mpi interval staging, the sharded-path ``pad_rows`` buffers,
    the 2-D mesh batch staging). Reduced dtypes resolve through
    ml_dtypes' numpy registration, so ``np.asarray(a,
    storage_np("bf16"))`` quantizes on the host and the transfer
    itself ships half the bytes. ``default`` (a jnp or np dtype)
    is returned for "f32", mirroring :func:`storage_dtype`."""
    validate(policy)
    if policy in _REDUCED:
        return np.dtype(_REDUCED[policy])
    return np.dtype(jnp.float32 if default is None else default)


def pet(dtype):
    """``preferred_element_type`` kwargs for contractions over storage
    arrays: names the f32 accumulator under a reduced policy, empty
    otherwise (the default path's einsums lower exactly as before)."""
    if is_reduced(dtype):
        return {"preferred_element_type": jnp.float32}
    return {}
