"""Compilation-count guard: prove telemetry adds zero retraces.

``jax.monitoring`` fires an event per XLA compile request; a
process-lifetime listener counts them. Tests (and careful perf work)
snapshot the counter around a workload twice — diag off, then diag on —
and assert the deltas match: the tracing hooks are host-side emits, so
any difference means a hook leaked into a traced program.

The listeners are installed by ``utils.setup_backend`` (so that set-up
is covered; lazily on first use otherwise) and never removed (jax
exposes no unregister); they run per compile, never per call, which is
noise next to the compile itself.

Two things are kept. The COUNT (:func:`compile_count`,
:class:`CompileGuard`) is of compile requests that go through the
persistent cache: it reads 0 in a process without one. The LOG
(:func:`compile_log`) is of JAX's duration events, which fire with or
without the cache and carry the function's name: which function was
traced, lowered or compiled (or read from the cache), when, for how
long. While a diag tracer is active each backend compile is also an
``ev: "compile"`` record.
"""

from __future__ import annotations

import collections
import time

from sagecal_tpu.diag import trace as dtrace

_STATE = {"installed": False, "count": 0, "logged": 0}

# one event per compile request that goes through the persistent
# compilation cache (jax 0.9.0 fires it only when the cache is in use,
# utils.setup_backend turns it on); keep as a
# tuple so a rename can be tracked by adding the new name
_COMPILE_EVENTS = ("/jax/compilation_cache/compile_requests_use_cache",)


# JAX's duration events -> the log's stage names. The backend event is
# timed around compile-or-read-from-cache, so a cache read is logged
# too: that is what a warm start pays.
_DURATION_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
#: every jnp call inside a traced function is a (cached) trace event of
#: its own, microseconds long and inside its caller's event: traces
#: under this many seconds are not logged
TRACE_LOG_FLOOR_S = 1e-3
LOG_MAXLEN = 8192
_LOG: collections.deque = collections.deque(maxlen=LOG_MAXLEN)


def _listener(event, **kwargs):
    if event in _COMPILE_EVENTS:
        _STATE["count"] += 1


def _duration_listener(event, duration, **kwargs):
    stage = _DURATION_EVENTS.get(event)
    if stage is None or (stage == "trace"
                         and duration < TRACE_LOG_FLOOR_S):
        return
    tm, dur = time.perf_counter(), float(duration)
    fun = str(kwargs.get("fun_name", ""))
    _LOG.append((tm, stage, fun, dur))
    _STATE["logged"] += 1
    if stage == "backend_compile":
        dtrace.emit("compile", fun=fun, dur_s=dur, tm=tm)


def install() -> None:
    if _STATE["installed"]:
        return
    import jax.monitoring
    jax.monitoring.register_event_listener(_listener)
    jax.monitoring.register_event_duration_secs_listener(
        _duration_listener)
    _STATE["installed"] = True


def compile_log() -> list:
    """``[(tm, stage, fun_name, dur_s), ...]``, oldest first: the last
    ``LOG_MAXLEN`` trace / lower / backend_compile events since
    :func:`install`. ``tm`` is ``time.perf_counter()`` at the event's
    END (the clock of the diag records' ``tm``), ``stage`` one of
    ``trace``, ``lower``, ``backend_compile``."""
    install()
    return list(_LOG)


def compiles_logged() -> int:
    """Events ever logged (the log itself is bounded): whether anything
    was traced or compiled between two readings."""
    install()
    return _STATE["logged"]


def compile_count() -> int:
    """Compile requests observed since :func:`install` (auto-installs)."""
    install()
    return _STATE["count"]


class CompileGuard:
    """Context manager: ``with CompileGuard() as g: ...; g.compiles``."""

    def __enter__(self):
        install()
        self._c0 = _STATE["count"]
        return self

    def __exit__(self, *exc):
        self.compiles = _STATE["count"] - self._c0
        return False
