"""Test harness: run everything on a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding correctness is
validated on XLA's host-platform virtual devices, exactly how the driver's
``dryrun_multichip`` exercises the code.

Note: pytest plugins import jax before this conftest runs, so env vars are
too late — use jax.config updates (valid until a backend is initialized).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_x64", True)

assert jax.devices()[0].platform == "cpu"
assert len(jax.devices()) == 8


# ---------------------------------------------------------------------------
# Full-suite stability (VERDICT r4 weak 3): one `python -m pytest tests`
# invocation accumulated ~200 XLA:CPU compiled executables in a single
# 1-core process and died with a Python-fatal segfault inside
# backend_compile_and_load near test 198/200, while every module passes
# in isolation. Dropping the compiled-program caches at each module
# boundary bounds the accumulation; modules rarely share programs, so
# the recompilation cost is small.
# ---------------------------------------------------------------------------

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _clear_xla_caches_per_module():
    yield
    try:
        jax.clear_caches()
    except Exception:
        pass


# ---------------------------------------------------------------------------
# Retrace gate (ISSUE 4): diag/guard.py's compile counter promoted to a
# reusable fixture — the runtime complement of the static jaxlint
# retrace checker. A workload is warmed once, then an identically
# shaped re-run must add ZERO compile requests: any delta means a
# weak-type flip, an unhashable static, or a per-call jit wrapper
# leaked into the hot path.
# ---------------------------------------------------------------------------


@pytest.fixture
def retrace_guard():
    def assert_zero_retrace(thunk, warmups: int = 1):
        """Run ``thunk`` ``warmups`` times (compiles allowed), then once
        more under the compile counter asserting no new programs. The
        thunk must stage fresh inputs per call (donated buffers!) with
        identical shapes/dtypes/statics."""
        from sagecal_tpu.diag import guard
        for _ in range(max(warmups, 1)):
            jax.block_until_ready(thunk())
        with guard.CompileGuard() as g:
            out = thunk()
            jax.block_until_ready(out)
        assert g.compiles == 0, (
            f"{g.compiles} compile request(s) on an identically shaped "
            f"re-run — a retrace leaked into the hot path")
        return out
    return assert_zero_retrace


def pytest_addoption(parser):
    parser.addoption(
        "--sanitize", action="store_true", default=False,
        help="run under jax_enable_checks + debug-NaNs (the CI slow "
             "lane around the fast solver subset)")
    parser.addoption(
        "--sanitize-threads", action="store_true", default=False,
        help="arm analysis/threadsan: instrumented locks record "
             "per-thread acquisition orders and fail the test on an "
             "observed order inversion or an unlocked access to a "
             "registered shared structure (the CI lane around the "
             "serve/stream fast subsets)")


def pytest_configure(config):
    if config.getoption("--sanitize"):
        jax.config.update("jax_enable_checks", True)
        jax.config.update("jax_debug_nans", True)
    if config.getoption("--sanitize-threads"):
        # armed before collection: every threadsan.make_lock() in
        # structures the tests construct returns an instrumented lock
        from sagecal_tpu.analysis import threadsan
        threadsan.enable()


@pytest.fixture(autouse=True)
def _threadsan_sweep(request):
    """Per-test sweep under --sanitize-threads: violations raise at
    the acquire site, but a broad except (or a background thread's
    swallowed traceback) can hide one — the sweep fails the test that
    provoked it regardless."""
    yield
    if not request.config.getoption("--sanitize-threads"):
        return
    from sagecal_tpu.analysis import threadsan
    bad = threadsan.violations(clear=True)
    assert not bad, "thread sanitizer violations:\n" + "\n".join(bad)
