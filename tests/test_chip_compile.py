"""Compile the main path's device programs for a DESCRIBED TPU v5e.

The TPU compiler is installed in the CPU sandbox and compiles for a chip
that is described, not attached. These cases hold the kernels and the
solve that ``chip_smoke.py`` runs, at its shapes (N=62 stations -> 1891
baselines, tilesz=10, M=8 clusters, f32; ``test_production_tile_fits``
at tilesz=120, upstream's default): they catch a Mosaic refusal
(unaligned slice, VMEM overflow) or a program that cannot fit the
device's memory, at no chip time. A compile that passes is not a chip
run.

The topology is described inside a module-scoped fixture, never at
import: one process at a time may load the TPU library, and under
pytest-xdist every worker imports every test file. A second file of
such cases lands on another worker, whose fixture skips unless the
command allows several loads of the library (the driver's sets
``ALLOW_MULTIPLE_LIBTPU_LOAD=1``): ``tests/test_chip_variants.py`` is the
one such file, and uses this file's helpers. There is no per-test time
limit installed: a compile that may not return gets one of its own
(``test_folded_consensus_program_compiles_and_fits``).
"""

import functools
import os
import re
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
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
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


@functools.cache
def _compiled_cluster_update(one_chip):
    """The XLA per-cluster solve of the default fullbatch path
    (sagefit_host -> _jit_cluster_update; robust RTR at N > LMCUT),
    with the pipeline's SageConfig, compiled once a session."""
    from sagecal_tpu.solvers import lm as lm_mod, sage
    sd = _spec(one_chip)
    f32, i32, c64 = jnp.float32, jnp.int32, jnp.complex64
    cfg = sage.SageConfig(nbase=NB)._replace(max_emiter=0)
    os_ids, os_nsub = lm_mod.os_subset_ids(TILESZ, NB)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    flag = sd((), jnp.bool_)
    return sage._jit_cluster_update.lower(
        sd((), i32),                                    # cj
        sd((M, 1, N, 2, 2), c64),                       # J
        sd((8, TILESZ, NB), f32),                       # xres, on planes
        sd((M,), f32), sd((M,), f32),                   # nerr_acc, nuM
        sd((M, B, 2, 2), c64),                          # coh
        sd((B,), i32), sd((B,), i32),                   # sta1, sta2
        sd((M, B), i32), sd((M, 1), jnp.bool_),         # chunk idx/mask
        sd((B, 8), f32), sd((M,), f32),                 # wt, nerr_prev
        flag, flag, sd(key.shape, key.dtype),           # weighted/last/key
        None, sd(np.shape(os_ids), i32),                # admm, os_ids
        N, cfg, M * cfg.max_iter, 8, os_nsub).compile()


def test_cluster_update_fits(one_chip):
    """The per-cluster solve compiles for v5e and fits its HBM."""
    mem = _compiled_cluster_update(one_chip).memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < need < HBM_BYTES, mem


def test_cluster_update_assembles_on_planes(one_chip):
    """The Gauss-Newton matrix of the per-cluster solve comes from
    ``normal_eq.plane_equations``: real elementwise arithmetic on
    ``[8, tilesz, nbase]`` planes and a sum over time, so nothing whose
    name holds the scope ``assemble`` is a contraction (the TPU compiler
    writes a ``dot`` as a ``convolution``; until PR 39 the scope held
    nine of them, six with ``[B, 2, 2]`` complex operands and three
    Gram contractions batched over 1891 baselines: PERF.md section 5)."""
    text = _compiled_cluster_update(one_chip).as_text()
    scoped = [ln for ln in text.splitlines() if "/assemble/" in ln]
    assert scoped, "no operation names the scope sage/sweep/.../assemble"
    assert any(" reduce(" in ln for ln in scoped)
    contractions = [ln.strip()[:160] for ln in scoped
                    if " convolution(" in ln or " dot(" in ln]
    assert not contractions, contractions


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


def _lower_residual_program(one_chip, rows, correct_idx=None, m=M, kmax=1,
                            kept=()):
    """The per-tile residual program over ``m`` clusters of ``kmax`` chunk
    slots, the clusters ``kept`` solved and not subtracted."""
    from problems import make_sky
    from sagecal_tpu.rime import predict as rp, residual as rr
    sky = make_sky(m, srcs_per_cluster=3)
    dsky = rp.sky_to_device(sky, jnp.float32)
    sd = _spec(one_chip)
    f32, i32 = jnp.float32, jnp.int32
    subtract = np.ones((m,), bool)
    subtract[list(kept)] = False

    def residuals(J_r8, x_r, u, v, w, sta1, sta2, cidx):
        return rr.calculate_residuals_pairs(
            dsky, J_r8, x_r, u, v, w,
            jnp.asarray([150e6], f32), 0.18e6, sta1, sta2, cidx,
            jnp.asarray(subtract), out_dtype=f32, row_period=NB,
            correct_idx=correct_idx)

    return jax.jit(residuals, donate_argnums=(1,)).lower(
        sd((m, kmax, N, 8), f32), sd((rows, 1, 2, 2, 2), f32),
        sd((rows,), f32), sd((rows,), f32), sd((rows,), f32),
        sd((rows,), i32), sd((rows,), i32), sd((m, rows), i32))


@pytest.mark.parametrize("correct_idx", [None, 0], ids=["plain", "-k"])
def test_residual_program_compiles(one_chip, correct_idx):
    """The per-tile residual program (pipeline._residuals /
    cli_mpi residual_fn: real pairs in and out, donated input). Its
    complex-subtract-then-restack form aborted the TPU compiler on the
    v5e (rime/residual.calculate_residuals_pairs says why); a CHECK
    failure there kills this worker, which is the test failing.  Under
    ``-k`` the residual goes through complex and back around the
    correction, which is the same sandwich on planes (PR 43)."""
    _holds_the_three_scopes(_lower_residual_program(
        one_chip, B, correct_idx).compile().as_text())


def _holds_the_three_scopes(text):
    """The compiled text names the three scopes the benchmark's device
    metrics read (``phasor_dev_ms*``, ``corrupt_dev_ms*``,
    ``subtract_dev_ms``), and the Jones sandwich under ``rime/corrupt``
    is elementwise arithmetic on planes: nothing of it is lowered for
    the matrix unit (PR 43; its ``[B, F, 2, 2]`` complex ``einsum`` was a
    ``convolution`` of 2 x 2 operands a row)."""
    for scope in ("rime/phasor", "rime/corrupt", "rime/residual"):
        assert scope in text, scope
    contractions = [ln.strip()[:160] for ln in text.splitlines()
                    if "rime/corrupt" in ln
                    and (" convolution(" in ln or " dot(" in ln)]
    assert not contractions, contractions


def _lower_simulate_program(one_chip, tilesz, mode, sky=None):
    """``run_simulation``'s ``sim_fn`` as the pipeline builds it for
    ``-a <mode> -p -z`` on an 8 x 128 sky (of flat points, or the
    ``ClusterSky`` given): pairs in and out, the tile's Jones as
    [M, K, N, 8], no beam, and the chunk map, the timeslots, the channel
    and the ignore mask (one cluster, the target, left out) closed over
    as constants."""
    from problems import make_sky
    from sagecal_tpu.io import dataset as ds
    from sagecal_tpu.rime import predict as rp, residual as rr
    sky = sky or make_sky(M, srcs_per_cluster=128)
    dsky = rp.sky_to_device(sky, jnp.float32)
    rows = NB * tilesz
    cidx = rp.chunk_indices(tilesz, NB, sky.nchunk)
    tslot = ds.row_tslot(rows, NB)
    ignore_mask = np.arange(M) != 0
    sd = _spec(one_chip)
    f32, i32 = jnp.float32, jnp.int32

    def sim_fn(x_r, u, v, w, sta1, sta2, J_r8, beam):
        return rr.simulate_pairs(
            dsky, x_r, u, v, w, jnp.asarray([150e6], f32), 0.18e6, sta1,
            sta2, mode=mode, J=J_r8,
            chunk_idx=jnp.asarray(cidx), ignore_mask=ignore_mask,
            beam=beam, dobeam=0, tslot=jnp.asarray(tslot), row_period=NB)

    with jax.default_matmul_precision("highest"):
        return jax.jit(sim_fn).lower(
            sd((rows, 1, 2, 2, 2), f32), sd((rows,), f32), sd((rows,), f32),
            sd((rows,), f32), sd((rows,), i32), sd((rows,), i32),
            sd((M, 1, N, 8), f32), None)


@pytest.mark.parametrize("mode", [1, 2, 3])
def test_simulate_program_compiles(one_chip, mode):
    """The simulation modes' program (``pipeline.run_simulation``:
    ``-a`` 1 replace, 2 add, 3 subtract, under a solutions file).  While
    ``sim_fn`` made a complex ``x`` of the pairs and restacked the
    result, modes 2 and 3 aborted the TPU compiler after half a minute
    (``Check failed: fusion_util::IsFusibleUnalignedDUS``, exit 134;
    mode 1 never reads ``x``); a CHECK failure there kills this worker,
    which is the test failing."""
    _holds_the_three_scopes(
        _lower_simulate_program(one_chip, TILESZ, mode).compile().as_text())


# -- the production solve interval: -t 120, 226 920 rows a tile --------------

TILESZ_120 = 120
B_120 = NB * TILESZ_120
#: what the chip reported as its own in PR 25's RESOURCE_EXHAUSTED
CHIP_BYTES = int(15.75 * 2 ** 30)
#: one ``f32[8, 226920, 2, 2]`` temporary tiled ``T(2,128)``: 27 MB of data
PADDED_TEMP_BYTES = int(1.73 * 2 ** 30)
#: one ``c64[226920, 1, 2, 2]`` temporary tiled ``T(2,128)``: 7 MB of data
PADDED_MODEL_BYTES = B_120 * 2 * 128 * 8
#: a ceiling of its own where a program has been given room to lose:
#: what it compiled to (refine: PR 36; the two that hold a sweep: PR 41;
#: the two that leave the solver: PR 43) plus ONE such temporary
CEILING = {"refine": int(0.42 * 2 ** 30) + PADDED_TEMP_BYTES,
           "sagefit": int(0.49 * 2 ** 30) + PADDED_TEMP_BYTES,
           "cluster_update": int(0.10 * 2 ** 30) + PADDED_TEMP_BYTES,
           "residual": int(0.09 * 2 ** 30) + PADDED_MODEL_BYTES,
           "simulate": int(0.09 * 2 ** 30) + PADDED_MODEL_BYTES}


def _lower_solve_program(one_chip, name, tilesz, m=M, kmax=1, **override):
    """``sagefit``, ``refine`` or ``cluster_update`` lowered for the
    described chip at ``tilesz * NB`` rows, ``m`` clusters of ``kmax``
    chunk slots, with the solver cells' flags (``-e 4 -g 2 -l 10 -m 7
    -j 5``, N 62) but for the ``SageConfig`` fields ``override`` names.
    The sweep's running residual is handed to the per-cluster update in
    the layout ``sage.sweep_rows`` says."""
    from sagecal_tpu.config import SolverMode
    from sagecal_tpu.solvers import lm as lm_mod, sage
    sd = _spec(one_chip)
    f32, i32, c64 = jnp.float32, jnp.int32, jnp.complex64
    rows = tilesz * NB
    cfg = sage.SageConfig(nbase=NB)._replace(
        max_emiter=4, max_iter=2, max_lbfgs=10, lbfgs_m=7,
        solver_mode=int(SolverMode.RTR_OSRLM_RLBFGS))._replace(**override)
    os_ids, os_nsub = lm_mod.os_subset_ids(tilesz, NB)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    flag = sd((), jnp.bool_)
    # x8, coh, sta1, sta2, chunk idx: what every solve program is handed
    data = (sd((rows, 8), f32), sd((m, rows, 2, 2), c64),
            sd((rows,), i32), sd((rows,), i32), sd((m, rows), i32))
    J, wt = sd((m, kmax, N, 2, 2), c64), sd((rows, 8), f32)
    cmask = sd((m, kmax), jnp.bool_)
    if name == "sagefit":
        return sage._jit_sagefit.lower(
            *data, cmask, J, N, wt, sd((), f32), cfg,
            sd(np.shape(os_ids), i32), os_nsub, sd(key.shape, key.dtype))
    if name == "refine":
        return sage._jit_refine.lower(
            *data, J, wt, sd((), f32), N, cfg._replace(max_emiter=0), True)
    assert name == "cluster_update", name
    cfg0 = cfg._replace(max_emiter=0)
    xres = (sd((8, tilesz, NB), f32)
            if sage.sweep_rows(cfg0, rows) == "periodic"
            else sd((8, rows), f32))
    return sage._jit_cluster_update.lower(
        sd((), i32), J, xres, sd((m,), f32), sd((m,), f32),
        *data[1:], cmask, wt, sd((m,), f32), flag, flag,
        sd(key.shape, key.dtype), None, sd(np.shape(os_ids), i32), N,
        cfg0, m * cfg0.max_iter, 2, os_nsub)


@functools.cache
def _need_120(one_chip, name):
    """Bytes (argument + output + temp) the program ``name`` asks of a
    described v5e at 226 920 rows, with ``cal-t120``'s flags (``-e 4 -g
    2 -l 10 -m 7 -j 5``, N 62, M 8) and f32 contractions in f32 as
    ``utils.setup_backend`` gives every entry point (at the one-pass
    default the solve asks 0.44 GiB less); compiled once a session."""
    with jax.default_matmul_precision("highest"):
        if name == "simulate":
            lowered = _lower_simulate_program(one_chip, TILESZ_120, 3)
        elif name == "residual":
            lowered = _lower_residual_program(one_chip, B_120)
        else:
            lowered = _lower_solve_program(one_chip, name, TILESZ_120)
        mem = lowered.compile().memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)


@pytest.mark.parametrize("program", ["sagefit", "refine", "cluster_update",
                                     "residual", "simulate"])
def test_production_tile_fits(one_chip, program):
    """``-t 120`` (upstream's default solve interval: 226 920 rows a
    tile at N 62) compiles for the described v5e and fits the 15.75 GiB
    a chip reports as its own: the promoted whole-solve program (what a
    warm ``cal-t120`` tile runs), the host-driven plan's joint refine
    and per-cluster update (tile 0's first sweep), the residual
    program, and the simulation modes' (``-a 3 -p -z`` over 8 x 128
    sources, PR 37: no cell runs it at this size, because the reference
    takes 53 s to make one such tile's sky).  Argument + output + temp
    as compiled here at PR 43 (PR 41's, PR 39's, PR 36's and PR 34's
    beside them, with the temporaries the chip's own compile asked for
    then: PERF.md section 5):

    ==============  ===========  ========  ========  =========  =========  ==============
    program         -t 120 here  at PR 41  at PR 39  at PR 36   at PR 34   the chip, PR 34
    ==============  ===========  ========  ========  =========  =========  ==============
    sagefit          0.49 GiB    0.49 GiB  4.95 GiB   5.64 GiB  13.56 GiB  13.48 GiB
    refine           0.42 GiB    0.42 GiB  0.42 GiB   0.42 GiB  13.55 GiB  13.47 GiB
    cluster_update   0.09 GiB    0.09 GiB  5.50 GiB   7.02 GiB   7.02 GiB  not read
    residual         0.09 GiB    2.30 GiB  2.30 GiB   2.30 GiB   2.30 GiB  2.27 GiB
    simulate         0.08 GiB    2.29 GiB  2.29 GiB   2.29 GiB   aborts    not run
    ==============  ===========  ========  ========  =========  =========  ==============

    Arguments are 0.076 GiB.  Until PR 36 nearly all of the solve was
    ``f32[8, 226920, 2, 2]`` temporaries tiled ``T(2,128)``, 1.73 GiB
    for 27 MB of data each, about seven live at once in the joint
    refine's model passes.  The refine (PR 36), the sweeps' assembly
    (PR 39) and the sweep's running residual with the cluster models it
    adds and subtracts (PR 41) work on ``[8, (8,) 120, 1891]`` planes
    (7 MB a cluster, no padding) and hold NO such temporary: each of the
    three solve programs has a ceiling of what it compiled to plus one
    of them, so the old construction coming back into the refine, into
    the assembly or into the sweep's ``update`` is what this case
    notices.  The residual and the simulate programs' 2.3 GiB were the
    ``[B, F, 2, 2]`` complex operands of their Jones sandwich, tiled
    ``T(2,128)``; since PR 43 the sandwich runs on ``[8, 8, 1, 120,
    1891]`` planes (0.063 GiB of temporaries) and the pairs ``[B, 1, 2,
    2, 2]`` get a device layout with the rows minor, 7 MB: their ceiling
    is what they compiled to plus ONE complex ``[B, 1, 2, 2]`` temporary
    (0.43 GiB), a fifth of what coming back it would notice.  The
    solve's and the residual's TOGETHER are 0.58 GiB."""
    need = _need_120(one_chip, program)
    assert 0 < need < CEILING.get(program, CHIP_BYTES), need / 2 ** 30


# -- the folded consensus program (PR 42) -------------------------------------

#: eight subbands of 18 910 rows: what the program compiled to (0.368 GiB
#: of temporaries on the parent and with the trips among its outputs,
#: PERF.md section 5) with a tenth of room, plus ONE subband's
#: ``f32[8, 18910, 2, 2]`` tiled ``T(2,128)`` (0.144 GiB for 2.4 MB of
#: data): the ``[B, 2, 2]`` construction coming back under the batch axis
#: would bring eight of them a live value
FOLD_TEMP_CEILING = int(0.40 * 2 ** 30) + 8 * B * 2 * 128 * 4
FOLD_COMPILE_LIMIT_S = 600


def _lower_consensus_program(devices, conf_file):
    """``make_admm_runner``'s one program of all ADMM iterations of an
    interval as a consensus cell runs it (``conf_file`` below
    ``benchmarks/configs``: its own sky, flags and subbands) over a mesh
    of ``devices``, lowered: ``(lowered, args, Fl)``."""
    import tempfile
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "benchmarks"))
    import datagen
    import harness
    import reference
    from sagecal_tpu import cli_mpi, skymodel
    from sagecal_tpu.consensus import admm as cadmm, poly as cpoly
    from sagecal_tpu.rime import predict as rp

    conf = harness.load_config("benchmarks/configs/" + conf_file)
    freqs = np.asarray(conf["subband_freqs_hz"])
    Fl = len(freqs)
    obs = reference.Observation(conf, 7)
    assert (obs.n_sta, obs.nrows) == (N, B)
    with tempfile.TemporaryDirectory() as tmp:
        sky_path, cl_path = datagen.write_sky(obs, tmp)
        args = cli_mpi.build_parser().parse_args(
            ["-f", "x", "-s", sky_path, "-c", cl_path, *conf["cli"]])
        sky = skymodel.read_sky_cluster(
            sky_path, cl_path, obs.ra0, obs.dec0, float(freqs.mean()),
            bool(args.format))
    assert sky.n_clusters == M
    # host constants: nothing is placed on a device that is not there
    dsky = jax.tree.map(np.asarray, rp.sky_to_device(sky, jnp.float32))
    kmax = int(sky.nchunk.max())
    cmask = np.arange(kmax)[None, :] < sky.nchunk[:, None]
    cidx = rp.chunk_indices(TILESZ, NB, sky.nchunk)
    _, _, _, s1, s2 = obs.geometry(0)
    cfg = cadmm.ADMMConfig(
        n_admm=args.admm, npoly=args.npoly, poly_type=args.polytype,
        rho=np.full(M, float(conf["cluster_rho"])),
        sage=cli_mpi.sage_config(args))
    mesh = Mesh(np.array(list(devices)), ("freq",))
    sh = NamedSharding(mesh, P("freq"))

    def sd(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sh)

    with jax.default_matmul_precision("highest"):
        runner = cadmm.make_admm_runner(
            dsky, s1, s2, cidx, cmask, N, obs.fdelta,
            cpoly.setup_polynomials(freqs, float(freqs.mean()),
                                    args.npoly, args.polytype),
            cfg, mesh, Fl, nbase=NB)
        return runner.lower(
            sd(Fl, B, 8), sd(Fl, B), sd(Fl, B), sd(Fl, B), sd(Fl),
            sd(Fl, B, 8), sd(Fl), sd(Fl, M, kmax, N, 8)), args, Fl


def test_folded_consensus_program_compiles_and_fits(one_chip):
    """``cli_mpi``'s default plan for more subbands than devices, as the
    cell ``admm-f8-fold`` runs it: ``make_admm_runner``'s one program of
    all ten ADMM iterations of an interval over a ONE-device mesh, the
    eight J updates of an iteration under ``jax.vmap`` (``Fl`` = 8), at
    N = 62, 18 910 rows a subband, the configuration's own sky and flags
    (``benchmarks/configs/lofar62-f8-fold-m8x3.json``) and f32
    contractions in f32.  It lowers in about 20 s and compiles in about
    30 s here, at ``-A 10`` as the cell has it (the scan's body compiles
    once whatever ``-A`` is).  A time limit of its own: past it the
    process is ended with every thread's traceback, which fails this
    case and not the suite's clock."""
    import faulthandler
    faulthandler.dump_traceback_later(FOLD_COMPILE_LIMIT_S, exit=True)
    try:
        lowered, args, Fl = _lower_consensus_program(
            one_chip.device_set, "lofar62-f8-fold-m8x3.json")
        assert (args.admm, Fl) == (10, 8)
        compiled = lowered.compile()
    finally:
        faulthandler.cancel_dump_traceback_later()
    mem = compiled.memory_analysis()
    # J, Z, rho, res0, res1, r1s, duals, Y0 and the trips
    assert len(compiled.out_info) == 9
    assert compiled.out_info[8].shape == (args.admm, Fl, 2)
    assert 0 < mem.temp_size_in_bytes < FOLD_TEMP_CEILING, \
        mem.temp_size_in_bytes / 2 ** 30
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES


# -- the hybrid cluster file (PR 44): 16 clusters, kmax 5; on planes (PR 45) --

M_HYB, KMAX_HYB = 16, 5
#: one ``f32[18910, 2, 2, 4, 4]`` Gram-block temporary of the generic
#: assembly, tiled ``T(4,128)``: 4.8 MB of data
PADDED_GRAM_BYTES = B * 2 * 2 * 4 * 128 * 4
#: temporaries each program compiled to here at PR 45, GiB (PR 44, on flat
#: rows and the generic assembly: 0.447, 0.145, 0.410, 0.172)
HYBRID_TEMP = {"sagefit": 0.0421, "residual": 0.0,
               "cluster_update": 0.0096, "refine": 0.0275}
#: the two that a warm tile of the cell runs are tier-1 (32 s); the
#: host-driven plan's two (tiles 0 and 1) cost 33 s more and are slow
HYBRID_PROGRAMS = ["sagefit", "residual",
                   pytest.param("cluster_update", marks=pytest.mark.slow),
                   pytest.param("refine", marks=pytest.mark.slow)]


@pytest.mark.parametrize("program", HYBRID_PROGRAMS)
def test_hybrid_tile_compiles_and_fits(one_chip, program):
    """Upstream's hybrid cluster file at the cell ``cal-m16x3-hybrid``'s
    shape: sixteen clusters padded to five chunk slots.  A chunk is a
    run of whole timeslots, so every program stays on the ``[tilesz,
    nbase]`` planes (``planes.periodic_rows``; until PR 45 one cluster
    with more than one chunk took them all to flat rows and the generic
    scatter assembly, ``[B, 2, 2]`` complex products among them, the
    form the TPU compiler aborted on twice: PR 23, PR 37).  Each
    compiles for the described v5e (a CHECK failure kills this worker,
    which is the test failing) under the scopes every other cell has,
    so ``benchmarks/scopes.py`` reads it by the same names, and nothing
    under ``assemble`` is lowered for the matrix unit.  Temporaries as
    compiled here, with f32 contractions in f32 (at ``-t 120``, 226 920
    rows, from one compile each for the described chip):

    ==============  ==========  =========  ==========  =========
    program         -t 10       (PR 44)    -t 120      (PR 44)
    ==============  ==========  =========  ==========  =========
    sagefit         0.0421 GiB  0.447 GiB  1.069 GiB   4.48 GiB
    cluster_update  0.0096 GiB  0.410 GiB  0.033 GiB   3.96 GiB
    refine          0.0275 GiB  0.172 GiB  0.939 GiB   2.60 GiB
    residual        0.0000 GiB  0.145 GiB  0.454 GiB   2.06 GiB
    ==============  ==========  =========  ==========  =========

    What went: ``f32[18910, 2, 2, 4, 4]`` Gram blocks and the scatter's
    ``f32[5, 62, 62, 2, 2, 4, 4]`` tiled ``T(4,128)`` (148 MiB for 4.8 MB
    of data each, five and one of them in the solve), and the Jones
    gathered a row, ``f32[16, 18910, 8]`` tiled ``T(8,128)`` (148 MiB for
    9.7 MB).  The ceiling is what a program compiled to with a tenth of
    room plus ONE such block: a second padded ``[B]``-long temporary
    held live is what this case notices."""
    from sagecal_tpu.solvers import sage
    cfg = sage.SageConfig(nbase=NB)
    assert sage.sweep_rows(cfg, B) == "periodic"
    assert sage.assemble_rows(cfg, B) == "periodic"
    with jax.default_matmul_precision("highest"):
        if program == "residual":       # cluster 5: the negative id
            lowered = _lower_residual_program(
                one_chip, B, m=M_HYB, kmax=KMAX_HYB, kept=(5,))
        else:
            lowered = _lower_solve_program(one_chip, program, TILESZ,
                                           m=M_HYB, kmax=KMAX_HYB)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    ceiling = int(1.1 * HYBRID_TEMP[program] * 2 ** 30) + PADDED_GRAM_BYTES
    assert 0 <= mem.temp_size_in_bytes < ceiling, \
        mem.temp_size_in_bytes / 2 ** 30
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES
    text = compiled.as_text()
    contractions = [ln.strip()[:160] for ln in text.splitlines()
                    if ("/assemble/" in ln or "rime/corrupt" in ln)
                    and (" convolution(" in ln or " dot(" in ln)]
    assert not contractions, contractions
    want = {"sagefit": ("sage/sweep", "/assemble/", "/inner/", "/update/",
                        "sage/refine", "/restrict/"),
            "cluster_update": ("sage/sweep", "/assemble/", "/inner/",
                               "/update/"),
            "refine": ("sage/refine", "/restrict/", "/linesearch/"),
            "residual": ("rime/phasor", "rime/corrupt", "rime/residual")}
    for scope in want[program]:
        assert scope in text, f"no operation under {scope}"


# -- the array beam, -B 1 (PR 48): 8 x 128 sources, 48 element slots ----------

EMAX_BEAM = 48
#: temporaries each program compiled to here at PR 48, GiB
BEAM_TEMP = {("coh", 10): 0.0, ("residual", 10): 0.0014,
             ("coh", 120): 0.3269, ("residual", 120): 0.3857}


def _lower_beam_program(one_chip, name, tilesz):
    """The two programs of a ``dosage-beam`` tile that form coherencies
    under ``-B 1``, as the pipeline builds them on lofar62-m8x128's sky:
    ``coh`` (``_build_solver``'s ``coh_fn`` off the Pallas kernel:
    complex ``[M, B, 2, 2]``) and ``residual`` (``_residuals``: pairs in
    and out, donated), the beam a ``BeamArrays`` argument of 62 stations
    with 48 element slots and the tile's ``gmst`` track."""
    from problems import make_sky
    from sagecal_tpu.io import dataset as ds
    from sagecal_tpu.rime import beam as bm, predict as rp, residual as rr
    sky = make_sky(M, srcs_per_cluster=128)
    dsky = rp.sky_to_device(sky, jnp.float32)
    rows = NB * tilesz
    tslot = jnp.asarray(ds.row_tslot(rows, NB))
    cidx = jnp.asarray(rp.chunk_indices(tilesz, NB, sky.nchunk))
    sd = _spec(one_chip)
    f32, i32 = jnp.float32, jnp.int32
    freq = jnp.asarray([150e6], f32)
    beam = bm.BeamArrays(
        longitude=sd((N,), f32), latitude=sd((N,), f32),
        gmst=sd((tilesz,), f32), ra0=sd((), f32), dec0=sd((), f32),
        freq0=sd((), f32), elem_xyz=sd((N, EMAX_BEAM, 3), f32),
        elem_mask=sd((N, EMAX_BEAM), jnp.bool_), n_elem=sd((N,), f32),
        patt_theta=sd((28, 2), f32), patt_phi=sd((28, 2), f32),
        elem_beta=sd((), f32))
    uvw = (sd((rows,), f32),) * 3
    sta = (sd((rows,), i32),) * 2
    if name == "coh":
        def coh_fn(u, v, w, sta1, sta2, beam):
            return rp.coherencies(dsky, u, v, w, freq, 0.18e6, beam=beam,
                                  dobeam=1, tslot=tslot, sta1=sta1,
                                  sta2=sta2)[:, :, 0]
        return jax.jit(coh_fn).lower(*uvw, *sta, beam)
    assert name == "residual", name

    def residuals(J_r8, x_r, u, v, w, sta1, sta2, beam):
        return rr.calculate_residuals_pairs(
            dsky, J_r8, x_r, u, v, w, freq, 0.18e6, sta1, sta2, cidx,
            jnp.ones((M,), bool), out_dtype=f32, beam=beam, dobeam=1,
            tslot=tslot, row_period=NB)
    return jax.jit(residuals, donate_argnums=(1,)).lower(
        sd((M, 1, N, 8), f32), sd((rows, 1, 2, 2, 2), f32), *uvw, *sta,
        beam)


@pytest.mark.parametrize("tilesz", [
    TILESZ, pytest.param(TILESZ_120, marks=pytest.mark.slow)])
@pytest.mark.parametrize("program", ["coh", "residual"])
def test_beam_programs_compile_and_fit(one_chip, program, tilesz):
    """``-B 1`` takes the calibrate path off the Pallas coherency kernel:
    the solve's coherencies come from ``rp.coherencies`` through
    ``lax.map`` as COMPLEX ``[M, B, 2, 2]``, with the beam's tables
    ``[M, 1, S, T, N]`` made first under ``rime/beam`` and gathered to
    rows inside the source sum; the residual program makes them again.
    Complex arrays stacked on a minor axis of two are where the TPU
    compiler aborted twice (PR 23, PR 37: a CHECK failure kills this
    worker, which is the test failing).  Both compile for the described
    v5e and name the scope the cell's ``beam_dev_ms.beam`` reads.
    Temporaries as compiled here, f32 contractions in f32 (``-t 120`` is
    the fit guard, ``slow``):

    ========  ==========  ==========
    program   -t 10       -t 120
    ========  ==========  ==========
    coh       0.0000 GiB  0.3269 GiB
    residual  0.0014 GiB  0.3857 GiB
    ========  ==========  ==========

    The ceiling is what a program compiled to with a tenth of room plus
    one cluster's cosines over the elements, ``f32[S, T, N, Emax]``
    (15 MB at ``-t 10``, 183 MB at ``-t 120``): a second one held live,
    or all eight clusters' at once (a ``vmap`` in the ``lax.map``'s
    place), is what this case notices."""
    with jax.default_matmul_precision("highest"):
        compiled = _lower_beam_program(one_chip, program, tilesz).compile()
    mem = compiled.memory_analysis()
    one_table = 128 * tilesz * N * EMAX_BEAM * 4
    print(f"beam {program} -t {tilesz}: temp "
          f"{mem.temp_size_in_bytes / 2 ** 30:.4f} GiB")
    ceiling = int(1.1 * BEAM_TEMP[program, tilesz] * 2 ** 30) + one_table
    assert 0 <= mem.temp_size_in_bytes < ceiling, \
        mem.temp_size_in_bytes / 2 ** 30
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES
    text = compiled.as_text()
    want = ("rime/beam", "rime/phasor") + (
        ("rime/corrupt", "rime/residual") if program == "residual" else ())
    for scope in want:
        assert scope in text, scope


# -- the source sum's register tiles (PR 49) ----------------------------------

def _phasor_arrays(text):
    """``(line, dims, minor_to_major, tile)`` of every f32 array that an
    operation under ``rime/phasor`` makes in the compiled ``text``."""
    got = []
    for ln in text.splitlines():
        if "rime/phasor" not in ln or " = " not in ln:
            continue
        made = ln.split(" = ", 1)[1].split("(%", 1)[0]
        for dims, order, tile in re.findall(
                r"f32\[([\d,]+)\]\{([\d,]+):T\((\d+,\d+)\)", made):
            got.append((ln.strip()[:100],
                        [int(d) for d in dims.split(",")],
                        [int(d) for d in order.split(",")],
                        tuple(int(d) for d in tile.split(","))))
    return got


@pytest.mark.parametrize("program", [
    "simulate", "residual",
    pytest.param("residual:120", marks=pytest.mark.slow), "beam-residual",
    "admm-fold", "admm-mesh"])
def test_source_sum_fills_its_registers(topo, one_chip, program):
    """The sum over a cluster's sources is handed to the compiler as a
    contraction (``rime/predict._cluster_coherency``, PR 49).  Written as
    four ``jnp.sum(phasor * b, axis=1)`` it compiled, in every program but
    the beam's, to ``cos`` and ``sin`` on ``f32[1, rows, S]`` arrays tiled
    ``T(1,128)``: ONE row (or one source) to a register tile, an eighth of
    every vector register at work, 22.9 ms a tile of ``predict-m8x128``
    where the beam program's full tiles took 5.1 ms for the same pairs
    (PERF.md section 6, PR 49).  The pin: every ``cos`` under
    ``rime/phasor`` is made on an array of rows x sources whose register
    tile holds more than one of its sublanes (``T(8,128)``; ``T(4,128)``
    where a cluster has three sources), and no operation under that scope
    makes an array of rows x sources tiled ``T(1,128)``.  Which of the two
    the compiler lays on the lanes is its own choice and both fill the
    registers: the sources at 128 of them (the beam program's layout on
    the parent), the rows at three.  The two consensus programs are held too
    (``admm-f4-mesh``'s, under ``shard_map``, and ``admm-f8-fold``'s,
    eight subbands on one chip): they make their subbands' coherencies
    once an interval, a subband at a time, BEFORE the ``vmap`` of the
    solves (``consensus/admm.py``, ``coh_subbands``), because the
    contraction under that ``vmap`` is a batched ``dot`` the compiler
    lowers as a dilated ``convolution``, five times the time of the sums
    it replaced."""
    name, _, tilesz = program.partition(":")
    rows = NB * int(tilesz or TILESZ)
    with jax.default_matmul_precision("highest"):
        if name == "simulate":
            low, S = _lower_simulate_program(one_chip, TILESZ, 3), 128
        elif name == "residual":
            low, S = _lower_residual_program(one_chip, rows), 3
        elif name == "beam-residual":
            low, S = _lower_beam_program(one_chip, "residual", TILESZ), 128
        else:
            low, S = _lower_consensus_program(
                topo.devices[:1 if name == "admm-fold" else 4],
                "lofar62-f8-fold-m8x3.json" if name == "admm-fold"
                else "lofar62-f4-m8x3.json")[0], 3
        arrays = _phasor_arrays(low.compile().as_text())
    cos = [a for a in arrays if " cosine" in a[0]]
    assert cos, "no cos under rime/phasor"
    for line, dims, order, tile in cos:
        assert sorted(dims[d] for d in order[:2]) == sorted((rows, S)), line
        assert tile[0] > 1 and tile[1] == 128, line
    one_row = [line for line, dims, _, tile in arrays
               if tile == (1, 128) and rows in dims and S in dims]
    assert not one_row, one_row


# -- a sky that is not points (PR 51) -----------------------------------------

def _estimated_cycles(text):
    """``(cycles, operation's source name)`` of every instruction of the
    compiled ``text`` that carries the TPU compiler's own estimate."""
    got = []
    for ln in text.splitlines():
        cycles = re.search(r'"estimated_cycles":"(\d+)"', ln)
        if cycles:
            name = re.search(r'op_name="([^"]*)"', ln)
            got.append((int(cycles.group(1)), name.group(1) if name else ""))
    return got


@pytest.mark.parametrize("tilesz", [TILESZ, TILESZ_120])
def test_extended_sky_simulate_program_compiles_and_fits(one_chip, tmp_path,
                                                         tilesz):
    """The simulate program of ``run_simulation`` (``-a 1 -p -F 1``) at
    the sky of ``lofar62-m8x128-ext``, read through the program's own
    reader from the text the benchmark's reference writes: 18 910 rows
    (226 920 at ``-t 120``), 8 x 128 sources, four of them shapelets,
    ``n0max`` 10.  It compiles for the described v5e and fits with room
    to spare; the shapelet basis has a scope of its own below
    ``rime/phasor``; and the compiler's ``estimated_cycles`` for ONE trip
    of the map over clusters are printed under that scope beside the
    rest.  The basis is evaluated on the model's compact pack of shapelet
    sources, ``[S_sh, B]`` with ``S_sh`` 1 here (``rime/predict.
    ShapeletPack``, PR 52).  As compiled here (cpu, PR 52), ``-t 10``:
    1 597 312 cycles a trip, 1 357 818 in operations named under
    ``rime/phasor``, 28 803 under ``rime/phasor/shapelet`` (multiply-adds
    on ``f32[18910]{0:T(1024)}``), 0.0041 GiB of temporaries; what is left
    are the two fusions of the Gaussian, disk and ring envelopes with the
    fringe (890 396 and 370 209).  ``-t 120``: 17 649 060 a trip, 65 285
    under the scope, 0.328 GiB.  While one static flag compiled the basis
    in for every one of the model's 1024 source slots (PR 51) it was
    54 425 492 cycles a trip, 41 584 471 under the scope in two
    ``multiply_reduce`` fusions over ``f32[18910, 128, 10]`` tiled
    ``T(1,128)``, 0.481 GiB of temporaries at ``-t 10`` and 5.8 GiB at
    ``-t 120`` (sized), which this chip could not have held beside a
    beam's tables; the same program on 8 x 128 points is 382 545 a trip
    and has no temporaries.  Held here: the scope is there and is under a
    twentieth of the trip, the temporaries are under 0.05 GiB a 18 910
    rows, and no operation under ``rime/phasor`` makes an array tiled
    ``T(1,128)`` with the rows ABOVE its minor axis (one row to a
    register tile: PR 49's finding, and the first suspect if the basis is
    ever slow again)."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "benchmarks"))
    import harness
    import reference_extended as rx
    from sagecal_tpu import skymodel
    conf = harness.load_config("benchmarks/configs/lofar62-m8x128-ext.json")
    lines, clusters, modes = rx.draw_sky(conf)
    sky_path = tmp_path / "sky.txt"
    sky_path.write_text("\n".join(lines) + "\n")
    (tmp_path / "sky.txt.cluster").write_text("\n".join(clusters) + "\n")
    for name, text in modes.items():
        (tmp_path / (name + ".fits.modes")).write_text(text)
    sky = skymodel.read_sky_cluster(
        str(sky_path), str(sky_path) + ".cluster", conf["ra0_rad"],
        conf["dec0_rad"], conf["freq_hz"], format_3=True)
    assert sky.smask.shape == (M, 128) and sky.smask.all()
    assert sky.sh_modes.shape[-1] == 100 and (sky.sh_n0 > 0).sum() == 4
    rows = NB * tilesz
    compiled = _lower_simulate_program(one_chip, tilesz, 1, sky=sky).compile()
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < need < 2 * 2 ** 30, need / 2 ** 30
    assert mem.temp_size_in_bytes < 0.05 * 2 ** 30 * rows / (NB * TILESZ)
    text = compiled.as_text()
    _holds_the_three_scopes(text)
    cycles = _estimated_cycles(text)
    total = sum(c for c, _ in cycles)
    phasor = sum(c for c, name in cycles if "rime/phasor" in name)
    basis = sum(c for c, name in cycles if "(shapelet)" in name)
    print(f"extended simulate -t {tilesz}: temp "
          f"{mem.temp_size_in_bytes / 2 ** 30:.4f} GiB; estimated_cycles a "
          f"trip of the map: {total} in all, {phasor} named under "
          f"rime/phasor, {basis} under rime/phasor/shapelet, "
          f"{phasor - basis} the rest of the source sum")
    assert basis > 0, "no operation names the scope rime/phasor/shapelet"
    # a corner of the program, now that only the pack evaluates the basis
    assert basis < 0.05 * total
    one_row = [line for line, dims, order, tile in _phasor_arrays(text)
               if tile == (1, 128) and rows in dims
               and dims[order[0]] != rows]
    assert not one_row, one_row
