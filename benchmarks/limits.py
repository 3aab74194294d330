#!/usr/bin/env python3
"""Read the numbers that ``correct`` compares, over many seeds and under
the control, in ONE process (set-up is most of a run):

    python3 benchmarks/limits.py --workload cal-m8x3 --seeds 11,12,13 \
        --control-seeds 11,12,13 --seconds 20 --precisions highest,high,default

For each precision in turn (``highest`` is the configuration's own; the
others are the control: f32 contractions in three bf16 passes, then in
one) it runs the cell's driver and its check on each seed and prints the
compared numbers, then the largest sound and the smallest control
reading of each.  Limits in ``configs/*.json`` are set from these lines
(PERF.md lists the readings).  It reports no metric and prints no result
line: a short window at the cell's own load is all it needs.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as runner    # noqa: E402  (puts the checkout on sys.path)
import harness          # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--precisions", default="highest,high,default")
    ap.add_argument("--cells", default=None)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)

    cell = harness.Cell(args.workload,
                        args.cells and harness.load_json(args.cells))
    devices = runner.open_backend(args.allow_cpu, cell.chips)
    if devices is None:
        return 3
    import jax
    dev = devices[0]
    print(f"[limits] {cell.name} on {dev.platform} ({dev.device_kind})")
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = [int(s) for s in args.control_seeds.split(",") if s] or seeds
    readings = {}
    for precision in args.precisions.split(","):
        jax.config.update("jax_default_matmul_precision", precision)
        for seed in (seeds if precision == "highest" else control):
            run = runner.Run(cell, seed, args.seconds, trace=False)
            outcome = cell.driver.run(run)
            checks = cell.driver.check(run)
            for c in checks:
                readings.setdefault((precision, c.name), []).append(c.value)
            print(f"[limits] {precision} seed {seed}: " + ", ".join(
                f"{c.name} {c.value:.6g}" for c in checks)
                + f"; {outcome['attempted']} tiles, {outcome['failed']} "
                f"failed, tile_s.p50 "
                f"{run.window.end_to_end()['tile_s.p50']:.4g} s",
                flush=True)
    for (precision, name), vals in readings.items():
        print(f"[limits] {precision:8s} {name}: min {min(vals):.6g} "
              f"max {max(vals):.6g} over {len(vals)} seeds")
    # beside the cells' work directories, which each run empties
    with open(os.path.join(harness.HERE, ".work",
                           f"limits_{cell.name}.json"), "w") as f:
        json.dump({f"{p}:{n}": v for (p, n), v in readings.items()}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
