"""Compilation-count guard: prove telemetry adds zero retraces.

``jax.monitoring`` fires an event per XLA compile request; a
process-lifetime listener counts them. Tests (and careful perf work)
snapshot the counter around a workload twice — diag off, then diag on —
and assert the deltas match: the tracing hooks are host-side emits, so
any difference means a hook leaked into a traced program.

The listener is installed lazily on first use and never removed (jax
exposes no unregister); it is one integer increment per compile, which
is noise next to the compile itself.
"""

from __future__ import annotations

_STATE = {"installed": False, "count": 0}

# one event per compile request that goes through the persistent
# compilation cache (jax 0.9.0 fires it only when the cache is in use,
# utils.setup_backend turns it on); keep as a
# tuple so a rename can be tracked by adding the new name
_COMPILE_EVENTS = ("/jax/compilation_cache/compile_requests_use_cache",)


def _listener(event, **kwargs):
    if event in _COMPILE_EVENTS:
        _STATE["count"] += 1


def install() -> None:
    if _STATE["installed"]:
        return
    import jax.monitoring
    jax.monitoring.register_event_listener(_listener)
    _STATE["installed"] = True


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
