"""Compile for a DESCRIBED TPU v5e what a user can select and no cell runs.

Every cell of the benchmark, and every case of
``tests/test_chip_compile.py``, solves with ``--inner chol --jones full
--dtype-policy f32 -j 5`` on one chip or folds its subbands on one. The
cases here hold the other values of those options, one changed at a
time, and the four-chip mesh program, to the chip's compiler: a refusal
(an abort, a program that does not fit) shows here and not on a user's
first run. A compile that passes is not a chip run.

A file of its own, so that ``--dist loadfile`` gives it to another
worker than ``test_chip_compile.py`` (whose helpers it uses): the two
then load the TPU library in two processes, which the driver's command
allows (``ALLOW_MULTIPLE_LIBTPU_LOAD=1``); without that the ``topo``
fixture skips.
"""

import jax
import pytest

import test_chip_compile as tcc
# that file's fixtures, made anew for this module: the topology is
# described in this worker, inside a fixture; tracing as on the chip
from test_chip_compile import _as_on_the_chip, one_chip, topo  # noqa: F401


#: one ``SageConfig`` field moved off the cells' value, named by its flag
VARIANTS = {"inner-cg": dict(inner="cg"),
            "jones-diag": dict(jones_mode="diag"),
            "jones-phase": dict(jones_mode="phase"),
            "dtype-bf16": dict(dtype_policy="bf16"),
            "dtype-f16": dict(dtype_policy="f16"),
            "j1": dict(solver_mode=1),      # -j is config.SolverMode's number
            "j3": dict(solver_mode=3)}


@pytest.mark.parametrize("override", VARIANTS.values(), ids=VARIANTS.keys())
def test_selectable_variant_compiles_for_the_chip(one_chip, override):
    """The per-cluster update at the cells' shape (N 62, 18 910 rows, 8
    clusters) with ONE option moved off the cells' value compiles for
    the described v5e and fits its memory."""
    with jax.default_matmul_precision("highest"):
        mem = tcc._lower_solve_program(
            one_chip, "cluster_update", tcc.TILESZ,
            **override).compile().memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < need < tcc.HBM_BYTES, need / 2 ** 30


def test_mesh_consensus_program_compiles(topo):
    """``admm-f4-mesh``'s program: ``make_admm_runner``'s ADMM iterations
    of an interval over the four devices of the described topology, one
    subband a chip, with the configuration's own sky and flags
    (``benchmarks/configs/lofar62-f4-m8x3.json``). Under the folded
    case's time limit, for the same reason."""
    import faulthandler
    faulthandler.dump_traceback_later(tcc.FOLD_COMPILE_LIMIT_S, exit=True)
    try:
        lowered, args, Fl = tcc._lower_consensus_program(
            topo.devices[:4], "lofar62-f4-m8x3.json")
        assert Fl == 4
        compiled = lowered.compile()
    finally:
        faulthandler.cancel_dump_traceback_later()
    mem = compiled.memory_analysis()
    # the same nine outputs as the folded program, the trips last
    assert len(compiled.out_info) == 9
    assert compiled.out_info[8].shape == (args.admm, Fl, 2)
    assert "all-reduce" in compiled.as_text()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < tcc.HBM_BYTES
