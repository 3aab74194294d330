"""Compile the main path's device programs for a DESCRIBED TPU v5e.

The TPU compiler is installed in the CPU sandbox and compiles for a chip
that is described, not attached. These cases hold the kernels and the
solve that ``chip_smoke.py`` runs, at its shapes (N=62 stations -> 1891
baselines, tilesz=10, M=8 clusters, f32): they catch a Mosaic refusal
(unaligned slice, VMEM overflow) or a program that cannot fit the
device's memory, at no chip time. A compile that passes is not a chip
run.

The topology is described inside a module-scoped fixture, never at
import: one process at a time may load the TPU library, and under
pytest-xdist every worker imports every test file. Keep all such cases
in THIS file (a second file would land on another worker, whose fixture
then skips). ``sweep_pallas.sweep_blocks`` stays OUT while its TPU
compile does not return (PERF.md "Bring-up on v5e"): there is no
per-test time limit installed, so one such case would hang the suite.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

N, TILESZ, M = 62, 10, 8
NB = N * (N - 1) // 2
B = NB * TILESZ
HBM_BYTES = 16 * 2 ** 30        # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _as_on_the_chip():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without a chip (the next one warns
    and recompiles): keep the cache off around these cases. And trace
    with x64 off, as every run on the chip does (conftest turns it on
    for the CPU suite; Mosaic refuses the i64 block indices it makes)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with jax.enable_x64(False):
        yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


@pytest.mark.parametrize("S", [3, 128])
def test_coherency_kernel_compiles(one_chip, S):
    from sagecal_tpu.ops import coh_pallas
    sd = _spec(one_chip)
    f32 = jnp.float32
    compiled = coh_pallas.coherencies_points.lower(
        sd((3, B), f32), sd((M, 3, S), f32), sd((M, 1, 4, S), f32),
        sd((M, 11, S), f32), sd((1,), f32), sd((), f32),
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("md", [4, 2, 1])
def test_blocks_matvec_kernel_compiles(one_chip, md):
    from sagecal_tpu.ops import sweep_pallas
    sd = _spec(one_chip)
    f32, i32, K = jnp.float32, jnp.int32, 1
    compiled = sweep_pallas._matvec_blocks_jit.lower(
        sd((K, NB, 2, md, md), f32), sd((K, NB, 2, md, md), f32),
        sd((K, NB, 2, 2, md, md), f32), sd((K, N * 2 * md), f32),
        sd((NB,), i32), sd((NB,), i32), n_stations=N,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_cluster_update_fits(one_chip):
    """The XLA per-cluster solve of the default fullbatch path
    (sagefit_host -> _jit_cluster_update; robust RTR at N > LMCUT),
    with the pipeline's SageConfig, compiles for v5e and fits its HBM."""
    from sagecal_tpu.solvers import lm as lm_mod, sage
    sd = _spec(one_chip)
    f32, i32, c64 = jnp.float32, jnp.int32, jnp.complex64
    cfg = sage.SageConfig(nbase=NB)._replace(max_emiter=0)
    os_ids, os_nsub = lm_mod.os_subset_ids(TILESZ, NB)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    flag = sd((), jnp.bool_)
    lowered = sage._jit_cluster_update.lower(
        sd((), i32),                                    # cj
        sd((M, 1, N, 2, 2), c64), sd((B, 8), f32),      # J, xres
        sd((M,), f32), sd((M,), f32),                   # nerr_acc, nuM
        sd((B, 8), f32), sd((M, B, 2, 2), c64),         # x8, coh
        sd((B,), i32), sd((B,), i32),                   # sta1, sta2
        sd((M, B), i32), sd((M, 1), jnp.bool_),         # chunk idx/mask
        sd((B, 8), f32), sd((M,), f32),                 # wt, nerr_prev
        flag, flag, sd(key.shape, key.dtype),           # weighted/last/key
        None, sd(np.shape(os_ids), i32),                # admm, os_ids
        N, cfg, M * cfg.max_iter, 8, os_nsub)
    mem = lowered.compile().memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < need < HBM_BYTES, mem


def test_refine_program_searches_on_the_line(one_chip):
    """The host-driven plan's joint refine at the ``cal-m8x3`` cell's
    shape (robust, ``-l 10 -m 7``). While the Fletcher search walked the
    whole model at every trial, the cost and its gradient were inlined
    into the program five times over (phase 1, ``cubic``, phase 2) and
    the compiled text held 967 fusions (PERF.md section 5, PR 26; 975
    as counted here); on the restricted line the model is there for the
    restriction and the loop's gradients only: 315."""
    from sagecal_tpu.config import SolverMode
    from sagecal_tpu.solvers import sage
    sd = _spec(one_chip)
    f32, i32, c64 = jnp.float32, jnp.int32, jnp.complex64
    cfg = sage.SageConfig(nbase=NB)._replace(
        max_lbfgs=10, lbfgs_m=7,
        solver_mode=int(SolverMode.RTR_OSRLM_RLBFGS))
    text = sage._jit_refine.lower(
        sd((B, 8), f32), sd((M, B, 2, 2), c64),         # x8, coh
        sd((B,), i32), sd((B,), i32), sd((M, B), i32),  # sta1/2, chunk idx
        sd((M, 1, N, 2, 2), c64), sd((B, 8), f32),      # J, wt
        sd((), f32), N, cfg, True).compile().as_text()
    assert "restrict" in text
    assert 0 < text.count(" fusion(") < 967 // 2


def test_residual_program_compiles(one_chip):
    """The per-tile residual program (pipeline._residuals /
    cli_mpi residual_fn: real pairs in and out, donated input). Its
    complex-subtract-then-restack form aborted the TPU compiler on the
    v5e (rime/residual.calculate_residuals_pairs says why); a CHECK
    failure there kills this worker, which is the test failing."""
    from problems import make_sky
    from sagecal_tpu.rime import predict as rp, residual as rr
    from sagecal_tpu.solvers import normal_eq as ne
    sky = make_sky(M, srcs_per_cluster=3)
    dsky = rp.sky_to_device(sky, jnp.float32)
    sd = _spec(one_chip)
    f32, i32 = jnp.float32, jnp.int32

    def residuals(J_r8, x_r, u, v, w, sta1, sta2, cidx):
        return rr.calculate_residuals_pairs(
            dsky, ne.jones_r2c(J_r8), x_r, u, v, w,
            jnp.asarray([150e6], f32), 0.18e6, sta1, sta2, cidx,
            jnp.ones((M,), bool), out_dtype=f32)

    jax.jit(residuals, donate_argnums=(1,)).lower(
        sd((M, 1, N, 8), f32), sd((B, 1, 2, 2, 2), f32), sd((B,), f32),
        sd((B,), f32), sd((B,), f32), sd((B,), i32), sd((B,), i32),
        sd((M, B), i32)).compile()
