"""sagecal_tpu.obs: production observability over the diag tracer.

Three pieces, one contract:

- :mod:`obs.metrics` — a zero-dependency, thread-safe metrics registry
  (counters, gauges, fixed-bucket histograms with percentile readout)
  with the same no-op-when-disabled promise as ``diag.trace``: until
  :func:`metrics.enable` installs a registry, every emit helper costs
  one attribute load and one ``is None`` test, and emit sites whose
  field conversion would force a device sync gate on
  ``metrics.active()`` exactly like ``dtrace.active()`` (both gates
  are blessed by the jaxlint host-sync checker).
- :mod:`obs.health` — live convergence health: streaming
  stall/divergence detection over per-solve residual records (a
  monotone-residual watermark with configurable patience), so a
  diverging job is visible *before* it burns its full tile budget.
- :mod:`obs.export` — Prometheus text exposition of a registry plus
  the stdlib HTTP endpoint serving ``/metrics`` and ``/healthz`` for
  the serve daemon (``--metrics-port``).

Layering: stdlib only, like ``diag.trace`` — the solver and pipeline
layers import ``obs.metrics`` unconditionally and an import that
pulled in jax from inside ``sagecal_tpu.solvers.sage`` would be a
layering inversion.
"""

from sagecal_tpu.obs import metrics  # noqa: F401  (the common entry)
