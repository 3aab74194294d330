"""Whether two trees compile a program to the same thing for the chip.

    python3 tools_dev/hlo_same.py TREE_A TREE_B PROGRAM [PROGRAM ...]

A ``perf_opt`` PR names cells "where nothing may move" because the code
it changed is shared; a timing can only say "not by more than the
noise".  This says it exactly, here, with no chip: each PROGRAM is
lowered by ``tests/test_chip_compile.py``'s helpers of EACH tree (in a
process of its own, from the tree's root) for a described v5e at the
cells' shapes and ``highest`` contractions, and the optimized HLO is
compared with source locations and instruction numbers taken out (they
follow line numbers and the order of tracing).  Exit code 1 where one
differs, if only in the order of its instructions, and the two texts
are kept for a ``diff``.

PROGRAM: ``sagefit|refine|cluster_update[:tilesz[:m[:kmax]]]`` (the
solver cells' programs), ``residual[:tilesz[:m[:kmax]]]``,
``simulate[:tilesz]``, ``admm-fold`` (``admm-f8-fold``'s: one device,
eight subbands under vmap), ``admm-mesh`` (``admm-f4-mesh``'s: four
devices).  A tree older than PR 45 has no helper for the last two.
"""

import hashlib
import os
import re
import shutil
import subprocess
import sys
import tempfile


def _dump(out, specs):
    """In a tree's own process: the normalised HLO of each program, its
    text written below ``out``."""
    sys.path[:0] = [os.getcwd(), os.path.join(os.getcwd(), "tests")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    import test_chip_compile as t
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    for spec in specs:
        name, *nums = spec.split(":")
        tilesz, m, kmax = [int(n) for n in nums] + [10, 8, 1][len(nums):]
        with jax.enable_x64(False), jax.default_matmul_precision("highest"):
            if name == "residual":
                low = t._lower_residual_program(chip, t.NB * tilesz, m=m,
                                                kmax=kmax)
            elif name == "simulate":
                low = t._lower_simulate_program(chip, tilesz, 3)
            elif name in ("admm-fold", "admm-mesh"):
                low = t._lower_consensus_program(
                    topo.devices[:1 if name == "admm-fold" else 4],
                    "lofar62-f8-fold-m8x3.json" if name == "admm-fold"
                    else "lofar62-f4-m8x3.json")[0]
            else:
                low = t._lower_solve_program(chip, name, tilesz, m=m,
                                             kmax=kmax)
            text = low.compile().as_text()
        # source locations: the tables at the head, and each
        # instruction's metadata and stack frame
        text = re.sub(r", metadata=\{[^}]*\}", "", text)
        text = re.sub(r",? ?stack_frame_id=\d+", "", text)
        text = "\n".join(ln for ln in text.splitlines()
                         if not re.match(r'\d+ ("|\{)', ln))
        # instruction numbers
        text = re.sub(r"(%?[A-Za-z_][\w-]*?)(\.\d+)+\b", r"\1", text)
        with open(os.path.join(out, spec.replace(":", "_")), "w") as f:
            f.write(text + "\n")
        def sha(t):
            return hashlib.sha256(t.encode()).hexdigest()
        print("HLO", spec, sha(text), sha("\n".join(sorted(
            text.splitlines()))), text.count(" fusion("),
            len(text.splitlines()), flush=True)


def main(tree_a, tree_b, *specs):
    got, outs = [], [tempfile.mkdtemp(prefix="hlo_same_") for _ in "ab"]
    for tree, out in zip((tree_a, tree_b), outs):
        run = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--dump", out,
             *specs],
            cwd=tree, env={**os.environ, "JAX_PLATFORMS": "cpu"}, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE)
        if run.returncode:
            sys.exit(f"{tree}: {run.stderr[-2000:]}")
        got.append({ln.split()[1]: ln.split()[2:]
                    for ln in run.stdout.splitlines()
                    if ln.startswith("HLO ")})
    differ = 0
    for spec in specs:
        (ha, sa, *a), (hb, sb, *b) = got[0][spec], got[1][spec]
        differ += ha != hb
        print(f"{spec}:", "same" if ha == hb else
              "the same instructions in another order" if sa == sb
              else "DIFFERENT",
              f"(fusions {a[0]} / {b[0]}, lines {a[1]} / {b[1]})")
    if differ:
        print("diff -r", *outs)
    else:
        for out in outs:
            shutil.rmtree(out)
    return 1 if differ else 0


if __name__ == "__main__":
    if sys.argv[1] == "--dump":
        _dump(sys.argv[2], sys.argv[3:])
    else:
        sys.exit(main(*sys.argv[1:]))
