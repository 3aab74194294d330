"""Small shared utilities.

Complex boundary shims: every jit boundary in this framework passes
complex quantities as stacked real pairs [..., 2] (Jones as [..., 8])
and forms/splits them on device, so host<->device transfers only ever
move real arrays. Complex math *on* device is used freely.

:func:`setup_backend` is the one place that picks the JAX platform and
the persistent compile cache; every entry point calls it first.
"""

from __future__ import annotations

import hashlib
import os

import numpy as np
import jax.numpy as jnp

#: the checkout (parent of the package): the default compile cache
#: lives at the FIXED path <checkout>/.jax_cache — the path is part of
#: the cache key, so a directory that moves never hits
CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cpu_fingerprint() -> str:
    """Short hash of this host's CPU feature set. CPU executables in
    the persistent compile cache must not be served to a host with a
    different CPU profile ("cached code's CPU features mismatch the
    host ... could lead to execution errors such as SIGILL")."""
    try:
        with open("/proc/cpuinfo") as f:
            flags = [ln for ln in f if ln.startswith("flags")][:1]
        blob = (flags[0] if flags else "none").encode()
    except OSError:
        blob = b"none"
    return hashlib.sha256(blob).hexdigest()[:10]


def setup_backend(platform: str | None = None,
                  cpu_devices: int | None = 0) -> str:
    """Pick the JAX platform and the persistent compile cache, before
    first device use. Called at the top of every entry point (cli,
    cli_mpi, serve, serve.loadgen, benchmarks/run.py, chip_smoke.py)
    so they all agree; returns the cache directory in force.

    - ``platform`` / ``cpu_devices`` are the ``--platform`` /
      ``--cpu-devices`` options (``JAX_PLATFORMS`` works too; the
      options let one command line carry the choice to a subprocess);
    - where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own reading of
      it stands and no other directory is set here; otherwise the cache
      goes to ``<checkout>/.jax_cache`` (a per-host ``cpu-<features>``
      subdirectory when the CPU platform was asked for — fixed for a
      host). Never a temporary name, a pid or a time;
    - programs that took >= 0.5 s to compile are kept (every solver
      program; JAX's default of 1 s would drop the staging programs a
      cold chip run recompiles by the dozen);
    - f32 contractions multiply in f32 (``jax_default_matmul_precision
      = highest``). The TPU's default is ONE bf16 pass, which left the
      f32 pipeline's final residuals at twice the CPU's on the same
      data (PERF.md "Bring-up on v5e"); the CPU backend computes f32
      either way;
    - the diag layer is wired to jax here, since it may not import it
      itself: ``dtrace.phase`` gets ``jax.profiler.TraceAnnotation`` to
      mark its spans on the profiler's clock, and ``diag.guard``'s
      compile listeners are installed before anything compiles.
    """
    import jax
    import jax.profiler
    from sagecal_tpu.diag import guard, trace as dtrace
    dtrace.set_annotator(jax.profiler.TraceAnnotation)
    guard.install()
    if platform:
        jax.config.update("jax_platforms", platform)
    if cpu_devices:
        jax.config.update("jax_num_cpu_devices", int(cpu_devices))
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache:
        asked = platform or os.environ.get("JAX_PLATFORMS", "")
        cache = os.path.join(CHECKOUT, ".jax_cache")
        if asked.split(",")[0] == "cpu":
            cache = os.path.join(cache, f"cpu-{cpu_fingerprint()}")
        jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    # the cache key covers the operations' names (JAX strips them by
    # default): an executable carries the op metadata it was compiled
    # with, the profiler trace reads the scopes (sage/*, rime/*) from
    # it, and a cache entry written before a scope existed must not be
    # served in its place
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_default_matmul_precision", "highest")
    return cache


def platform_line(n_devices: int | None = None) -> str:
    """The line every entry point prints once the backend is up: the
    platform the run is on, its device count and kind, and which tile
    packer is loaded (chip_smoke.py reads the device from it)."""
    import jax
    from sagecal_tpu.io import native
    dev = jax.devices()
    n = len(dev) if n_devices is None else n_devices
    return (f"Platform: {dev[0].platform} ({n} device(s), "
            f"{dev[0].device_kind}); tile packer: {native.packer_name()}")


def c2r(x):
    """Complex [...,] -> real [..., 2] (device or host)."""
    if isinstance(x, np.ndarray):
        return np.stack([x.real, x.imag], axis=-1)
    return jnp.stack([x.real, x.imag], axis=-1)


def r2c(x):
    """Real [..., 2] -> complex [...] (device or host)."""
    return x[..., 0] + 1j * x[..., 1]


def to_np_complex(x) -> np.ndarray:
    """Device complex array -> host numpy complex via two real transfers."""
    return np.asarray(x.real) + 1j * np.asarray(x.imag)


def vis_to_x8(xa: np.ndarray) -> np.ndarray:
    """[B, 2, 2] complex visibilities -> [B, 8] reals in data order
    (XX re, im, XY, YX, YY — Dirac.h:1541-1546)."""
    f = xa.reshape(-1, 4)
    return np.stack([f.real, f.imag], -1).reshape(-1, 8)


def jones_c2r_np(J: np.ndarray) -> np.ndarray:
    """Host [..., 2, 2] complex Jones -> [..., 8] reals (pure numpy)."""
    flat = J.reshape(J.shape[:-2] + (4,))
    return np.stack([flat.real, flat.imag], axis=-1).reshape(
        J.shape[:-2] + (8,))


def jones_r2c_np(p: np.ndarray) -> np.ndarray:
    """Host [..., 8] reals -> [..., 2, 2] complex Jones (pure numpy)."""
    pr = p.reshape(p.shape[:-1] + (4, 2))
    return (pr[..., 0] + 1j * pr[..., 1]).reshape(p.shape[:-1] + (2, 2))
