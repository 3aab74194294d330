#!/usr/bin/env python
"""One-command TPU burn-down (ISSUE 17 tentpole c).

Every kernel/scaling verdict in this repo is still interpret-mode-on-
CPU, and chip time is budgeted. This harness converts ONE chip session
into every owed hardware verdict unattended: it queues the pending
experiments, runs each as a bounded subprocess, continues past
failures (a dead leg must not strand the rest of the window), stamps
the banked records, and finishes with a sentinel pass over what
landed. The queue:

1. ``probe``          — platform + one real compile+step round-trip
                        (a device list alone does not prove that a
                        dispatch completes; in real mode a failed
                        probe aborts the whole queue — nothing else
                        can land).
2. ``mosaic-kernels`` — tests/test_sweep_pallas.py fast subset on the
                        live platform: on TPU this compiles the REAL
                        Mosaic sweep + fused-chol kernels and gates
                        their parity vs the dense reference — the
                        verdict interpret mode cannot give.
3. ``kernel-cache``   — the sentinel's zero-compile probe_kernel
                        (xla -> pallas chol -> pallas cg -> xla adds
                        zero compiles; chol re-entry cached).
4. ``b-scaling``      — northstar --b-scaling --inner both --kernel
                        both: the kernel on/off x chol/cg ladder at
                        equal executed trips (cg-vs-chol on the MXU,
                        the fused-chol melt per B rung); banks
                        BSCALING_r17.json into the bank dir.
5. ``bf16-kernels``   — the per-policy bf16/f16 envelope subset of
                        test_sweep_pallas.py: the dtype melt THROUGH
                        the kernels (quantize-at-load storage dtypes
                        feeding the fused sweep/chol path).
6. ``mesh2d``         — northstar --mesh2d --dtype-policy bf16: the
                        64x100x32 2-D (freq x time) mesh north star
                        with the melt active; banks MESH2D_rNN.json.
7. ``fleet``          — bench config 9-fleet-throughput (compute-
                        bound scaling); stamps FLEET_rNN.json via
                        SAGECAL_BANK_DIR.
8. ``warm-start``     — bench config 12-warm-start (warm-vs-cold
                        sweeps saving, prior/router hit rates, the
                        off bit-identity gate); stamps WARM_rNN.json
                        via SAGECAL_BANK_DIR.
9. ``jones-melt``     — bench config 13-jones-melt (constrained-Jones
                        diag/phase vs full bytes/trip at equal
                        executed trips + the constrained-truth
                        residual envelope): on TPU the reduced Gram
                        blocks compile through REAL Mosaic — the
                        compiled verdict for the 8x8 -> 2x2 melt;
                        stamps JONES_rNN.json via SAGECAL_BANK_DIR.
10. ``sentinel``      — sagecal_tpu.obs.sentinel --fast over the bank
                        dir: every record this run stamped is judged
                        by its tolerance family (KMELT/MESH2D/FLEET/
                        WARM/JONES) before the window closes.

``--dry-run`` rehearses the SAME queue on CPU at small shapes into a
scratch bank dir (interpret-mode kernels, virtual devices), so the
orchestration itself is CI-testable: every verdict queues, stamps and
sentinel-checks without touching a committed record. CI runs exactly
``python tools_dev/burndown.py --dry-run``.

The summary lands as ``BURNDOWN.json`` in the bank dir: per-step rc /
wall / timeout plus the record files the run created. Exit 0 iff every
step passed.
"""

import argparse
import glob
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PY = sys.executable

_PROBE = r"""
import time, jax, jax.numpy as jnp
import sys
want = sys.argv[1]
plat = jax.devices()[0].platform
# a clean TPU-init failure makes JAX fall back to CPU and the matmul
# "succeed" — that must fail the gate
assert plat == want, f"platform {plat!r}, want {want!r}: {jax.devices()}"
t0 = time.time()
y = jax.jit(lambda a: (a @ a).sum())(jnp.ones((256, 256), jnp.bfloat16))
y.block_until_ready()
print(f"probe ok: compile+step {time.time()-t0:.1f}s on {plat}")
"""

_KERNEL_CACHE = r"""
import json, sys
from sagecal_tpu.obs import sentinel
viol = sentinel.probe_kernel()
print(json.dumps(viol, indent=1))
sys.exit(1 if viol else 0)
"""


def build_steps(args):
    """The verdict queue as (name, cmd, timeout_s, env-overrides)
    dicts. One builder for both modes so the dry run rehearses the
    REAL queue — only shapes, platform pins and timeouts differ."""
    dry = args.dry_run
    bank = args.bank_dir
    ns = [PY, os.path.join(HERE, "northstar.py")]
    pytest_base = [PY, "-m", "pytest", "-q", "-p", "no:cacheprovider"]
    # dry mode pins CPU everywhere; real mode scrubs a leaked
    # JAX_PLATFORMS=cpu, so a stale export cannot fake a missing chip
    env = ({"JAX_PLATFORMS": "cpu"} if dry
           else {"JAX_PLATFORMS": None})
    plat = "cpu" if dry else "tpu"
    steps = [
        dict(name="probe", env=env, timeout=90 if dry else 150,
             abort_on_fail=not dry,
             cmd=[PY, "-c", _PROBE, plat]),
        dict(name="mosaic-kernels", env=env,
             timeout=900 if dry else 1200,
             cmd=pytest_base + ["tests/test_sweep_pallas.py",
                                "-m", "not slow",
                                "-k", "not envelope"]),
        dict(name="kernel-cache", env=env, timeout=600,
             cmd=[PY, "-c", _KERNEL_CACHE]),
        dict(name="b-scaling", env=env, timeout=900 if dry else 2400,
             cmd=ns + ["--b-scaling", "--inner", "both",
                       "--kernel", "both", "--bank-dir", bank]
             + (["--cpu", "--stations", "8", "--dirs", "3",
                 "--reps", "1"] if dry
                else ["--dirs", "48"])),
        dict(name="bf16-kernels", env=env, timeout=600,
             cmd=pytest_base + ["tests/test_sweep_pallas.py",
                                "-k", "envelope"]),
        dict(name="mesh2d", env=env, timeout=1200 if dry else 3600,
             cmd=ns + ["--mesh2d", "--dtype-policy", "bf16",
                       "--bank-dir", bank]
             + (["--stations", "8", "--dirs", "3", "--subbands", "4",
                 "--intervals", "2", "--devices-f", "2",
                 "--devices-t", "2", "--maxit", "1",
                 "--drift-subbands", "2", "--stale-subbands", "2",
                 "--stale-admm", "2"] if dry else [])),
        dict(name="fleet",
             env={**env, "SAGECAL_BANK_DIR": bank,
                  **({"SAGECAL_BENCH_CPU": "1"} if dry else {})},
             timeout=600 if dry else 900,
             cmd=[PY, os.path.join(ROOT, "bench.py"),
                  "--config", "9-fleet-throughput"]),
        dict(name="warm-start",
             env={**env, "SAGECAL_BANK_DIR": bank,
                  **({"SAGECAL_BENCH_CPU": "1"} if dry else {})},
             timeout=900 if dry else 1200,
             cmd=[PY, os.path.join(ROOT, "bench.py"),
                  "--config", "12-warm-start"]),
        dict(name="jones-melt",
             env={**env, "SAGECAL_BANK_DIR": bank,
                  **({"SAGECAL_BENCH_CPU": "1"} if dry else {})},
             timeout=600 if dry else 900,
             cmd=[PY, os.path.join(ROOT, "bench.py"),
                  "--config", "13-jones-melt"]),
        dict(name="sentinel", env=env, timeout=600,
             cmd=[PY, "-m", "sagecal_tpu.obs.sentinel", "--fast",
                  "--platform", plat, "--bank-dir", bank]
             + (["--no-probes"] if dry else [])),
    ]
    return steps


def run_step(step, log=print):
    t0 = time.time()
    env = dict(os.environ)
    for k, v in (step.get("env") or {}).items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    shown = " ".join("<inline>" if "\n" in c else c
                     for c in step["cmd"])
    log(f"== {step['name']} (timeout {step['timeout']}s) ==",
        flush=True)
    log("   " + shown, flush=True)
    try:
        rc = subprocess.run(step["cmd"], cwd=ROOT, env=env,
                            timeout=step["timeout"]).returncode
    except subprocess.TimeoutExpired:
        rc = -9
        log(f"   {step['name']}: TIMEOUT after {step['timeout']}s",
            flush=True)
    wall = time.time() - t0
    res = {"name": step["name"], "cmd": shown,
           "rc": rc, "ok": rc == 0, "wall_s": round(wall, 1),
           "timeout_s": step["timeout"]}
    log(f"   {step['name']}: {'ok' if rc == 0 else f'FAILED rc={rc}'}"
        f" ({wall:.0f}s)", flush=True)
    return res


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="queue every pending hardware verdict on the live "
                    "chip, bank the records, sentinel-check them "
                    "(one command; see module docstring)")
    ap.add_argument("--dry-run", action="store_true",
                    help="rehearse the full queue on CPU at small "
                         "shapes into a scratch bank dir (interpret-"
                         "mode kernels; the CI lane)")
    ap.add_argument("--bank-dir", default=None,
                    help="where stamped records land (default: the "
                         "repo root in real mode, a scratch dir under "
                         "/tmp in --dry-run)")
    ap.add_argument("--only", default=None,
                    help="comma-separated step names to run (queue "
                         "debugging; the summary marks the rest "
                         "skipped)")
    args = ap.parse_args(argv)
    if args.bank_dir is None:
        args.bank_dir = (os.path.join(
            ROOT, ".burndown_scratch") if args.dry_run else ROOT)
    args.bank_dir = os.path.abspath(args.bank_dir)
    os.makedirs(args.bank_dir, exist_ok=True)

    steps = build_steps(args)
    only = set(args.only.split(",")) if args.only else None
    if only:
        unknown = only - {s["name"] for s in steps}
        if unknown:
            ap.error(f"--only: unknown step(s) {sorted(unknown)}")
    pre = set(glob.glob(os.path.join(args.bank_dir, "*.json")))
    results = []
    for step in steps:
        if only and step["name"] not in only:
            results.append({"name": step["name"], "skipped": True,
                            "ok": True})
            continue
        res = run_step(step)
        results.append(res)
        if not res["ok"] and step.get("abort_on_fail"):
            print(f"burndown: {step['name']} failed — chip not "
                  "usable, aborting the queue", file=sys.stderr)
            break
    banked = sorted(os.path.basename(p) for p in
                    set(glob.glob(os.path.join(args.bank_dir,
                                               "*.json"))) - pre)
    ran = [r for r in results if not r.get("skipped")]
    summary = {"dry_run": args.dry_run, "bank_dir": args.bank_dir,
               "steps": results, "banked": banked,
               "ok": bool(ran) and all(r["ok"] for r in ran)}
    out = os.path.join(args.bank_dir, "BURNDOWN.json")
    with open(out, "w") as f:
        json.dump(summary, f, indent=1)
    print(f"burndown: {sum(r['ok'] for r in ran)}/{len(ran)} steps ok, "
          f"banked {banked or 'nothing'} -> {out}")
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
