"""``sagecal_tpu.diag`` — runtime telemetry, program pricing for tests,
and convergence tracing.

Three small modules, layered so the hot paths stay clean:

- :mod:`sagecal_tpu.diag.trace` — a zero-dependency (stdlib-only) JSONL
  event emitter with phase timers and per-iteration convergence records.
  The application/solver layers call ``trace.emit(...)`` /
  ``trace.phase(...)`` unconditionally; both are cheap no-ops until a
  CLI (or a test) calls ``trace.enable(path)``. Nothing here touches
  jax, so importing it from the solver layer costs nothing and cannot
  retrace a program.
- :mod:`sagecal_tpu.diag.roofline` — FLOPs and bytes-accessed
  extraction from XLA's per-program cost analysis
  (``lowered.compile().cost_analysis()``): what tests price a program
  with (``lower_cost``, ``pallas_cost``). Nothing measures a speed with
  it: ``benchmarks/`` does that, from shapes and a chip's trace.
- :mod:`sagecal_tpu.diag.guard` — a jit-compilation counter (via
  ``jax.monitoring``) so tests can assert that telemetry-off — and
  telemetry-on — add zero retraces.
"""

from sagecal_tpu.diag import trace  # noqa: F401  (zero-dep, always safe)

__all__ = ["trace"]
