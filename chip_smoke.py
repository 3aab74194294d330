#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the main path starts on the chip.

Drives the three user entry points, each as a child process through
``python -m sagecal_tpu.…`` and one at a time (the chip belongs to one
process), at a LOFAR-sized problem: 62 stations (1891 baselines),
8 clusters x 3 point sources, 10-timeslot tiles, f32, default solver.

  fullbatch   python -m sagecal_tpu.cli, 3 tiles, run twice (the second
              run's time to first tile shows the compile cache being
              hit), then tile 0 again on ``--platform cpu`` as reference
  serve       python -m sagecal_tpu.serve holds the chip; two jobs of
              the fullbatch shape over the JSON-lines API from this
              process (which stays off JAX); drain
  consensus   python -m sagecal_tpu.cli_mpi, 4 subbands folded onto the
              one device, 3 ADMM iterations, the default traced plan,
              one interval = ONE device execution (105 s warm on a v5e)

``--chips 4`` runs ONLY the mesh comparison: cli_mpi over a four-device
('freq',) mesh with 8 subbands, and the same data on one device
(``--mesh-devices 1 --block-f 2``: the traced plan does not fit one
chip's memory at 8 subbands).

This process never imports jax. Any phase failing, or any child not on
``tpu``, ends the script non-zero without a result line. The last line
of a passing run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
with the device fields as a child that ran on the chip reported them.
``--rehearse-cpu`` runs the same control flow at a tiny size on the CPU
platform to find wrong paths and arguments; it pins every child to the
CPU and never prints an ``ok`` line.
"""

import argparse
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".chip_smoke")        # gitignored scratch
PY = sys.executable
BUDGET_S = 1150.0       # the whole script must end inside 1200 s
T_START = time.time()

#: |tile-0 (res_1/res_0) on the chip / the same on --platform cpu - 1|.
#: Measured 6.85e-05 on TPU v5 lite (2026-09-26, PERF.md "Bring-up on
#: v5e") once f32 contractions multiply in f32; with the TPU's default
#: single bf16 pass it was 1.17.
FULLBATCH_CPU_TOL = 0.01
#: four-device mesh vs --mesh-devices 1, same data: relative difference
#: of per-subband final residuals, and of the consensus Z solutions
#: relative to max|Z|
MESH_RES_TOL = 0.05
MESH_Z_TOL = 0.05

PLATFORM_RE = re.compile(
    r"Platform: (\w+) \((\d+) device\(s\), ([^)]*)\)")

DATAGEN = r'''
import os, sys
import numpy as np
import jax.numpy as jnp
from sagecal_tpu import skymodel
from sagecal_tpu.io import dataset as ds
from sagecal_tpu.rime import predict as rp
out, seed, n_sta, n_dir, n_src, tilesz, n_tiles, n_sub = (
    sys.argv[1], *(int(a) for a in sys.argv[2:9]))
rng = np.random.default_rng(seed)
ra0, dec0 = 1.2, 0.7
sky_lines, clus_lines = [], []
for m in range(n_dir):
    names = []
    for s in range(n_src):
        nm = f"P{m:03d}_{s}"        # 'P' prefix: POINT source
        ra = ra0 + rng.normal(0, 0.03)
        dec = dec0 + rng.normal(0, 0.03)
        h = (ra % (2 * np.pi)) * 12 / np.pi
        rah, rm_ = int(h), int((h - int(h)) * 60)
        rs = ((h - rah) * 60 - rm_) * 60
        dd = np.degrees(dec)
        deg, dm = int(dd), int((dd - int(dd)) * 60)
        dsec = ((dd - deg) * 60 - dm) * 60
        flux = float(np.exp(rng.normal(0.5, 0.8)))
        sky_lines.append(
            f"{nm} {rah} {rm_} {rs:.4f} {deg} {dm} {dsec:.4f} "
            f"{flux:.4f} 0 0 0 -0.7 0 0 0 0 150e6")
        names.append(nm)
    clus_lines.append(f"{m} 1 " + " ".join(names))
skyp = os.path.join(out, "sky.txt")
with open(skyp, "w") as f:
    f.write("\n".join(sky_lines) + "\n")
with open(skyp + ".cluster", "w") as f:
    f.write("\n".join(clus_lines) + "\n")
sky = skymodel.read_sky_cluster(skyp, skyp + ".cluster", ra0, dec0, 150e6)
dsky = rp.sky_to_device(sky, jnp.float32)
Jbase = ds.random_jones(sky.n_clusters, sky.nchunk, n_sta, seed=seed + 1,
                        scale=0.15)
slope = ds.random_jones(sky.n_clusters, sky.nchunk, n_sta, seed=seed + 2,
                        scale=0.04) - np.eye(2)
for f_i in range(n_sub):
    fr = 120e6 * (1 + 0.004 * f_i)
    Jf = Jbase + slope * (fr - 120e6) / 120e6
    tiles = [ds.simulate_dataset(
        dsky, n_stations=n_sta, tilesz=tilesz, freqs=[fr], ra0=ra0,
        dec0=dec0, jones=Jf, nchunk=sky.nchunk, noise_sigma=0.02,
        seed=100 * seed + 20 + t) for t in range(n_tiles)]
    ds.SimMS.create(os.path.join(out, f"sb{f_i:02d}.ms"), tiles)
print(f"data: {n_sub} subband(s) x {n_tiles} tile(s), N={n_sta} "
      f"M={n_dir}x{n_src} tilesz={tilesz}")
'''

PROBE = ("import jax; d = jax.devices(); "
         "print('Platform: %s (%d device(s), %s)' "
         "% (d[0].platform, len(d), d[0].device_kind))")


class Failed(Exception):
    pass


def say(msg):
    print(msg, flush=True)


def remaining(limit_s):
    left = BUDGET_S - (time.time() - T_START)
    if left < 5:
        raise Failed("out of time: the script's 1200 s limit is near")
    return min(limit_s, left)


def child(name, cmd, limit_s, env=None):
    """Run one child to its end under a hard time limit; return
    (stdout+stderr text, wall seconds). Non-zero exit fails the run."""
    t0 = time.time()
    shown = ["python", "-c", "<inline>"] if cmd[1] == "-c" \
        else ["python"] + cmd[1:]
    say(f"[{name}] $ {' '.join(shown)}")
    try:
        r = subprocess.run(cmd, cwd=HERE, env=env, text=True,
                           stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                           timeout=remaining(limit_s))
    except subprocess.TimeoutExpired as e:
        tail = (e.stdout or "")[-2000:] if isinstance(e.stdout, str) \
            else (e.stdout or b"")[-2000:].decode(errors="replace")
        raise Failed(f"{name}: no end after {limit_s:.0f} s\n{tail}")
    wall = time.time() - t0
    if r.returncode != 0:
        raise Failed(f"{name}: exit {r.returncode}\n{r.stdout[-4000:]}")
    return r.stdout, wall


def device_of(name, out, want):
    """(platform, kind, count) from a child's own ``Platform:`` line;
    fails unless the platform is ``want``."""
    m = PLATFORM_RE.search(out)
    if not m:
        raise Failed(f"{name}: printed no Platform line\n{out[-2000:]}")
    plat, count, kind = m.group(1), int(m.group(2)), m.group(3)
    if plat != want:
        raise Failed(f"{name}: ran on '{plat}', not on '{want}'")
    return plat, kind, count


def read_diag(path):
    with open(path) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


def tiles_of(name, recs, n_expect):
    """Tile records of a diag trace: every tile finite and falling."""
    tiles = [r for r in recs if r.get("ev") == "tile"]
    if len(tiles) != n_expect:
        raise Failed(f"{name}: {len(tiles)} tile records, "
                     f"expected {n_expect}")
    for t in tiles:
        r0, r1 = t["res_0"], t["res_1"]
        if not (np.isfinite(r0) and np.isfinite(r1) and r1 < r0):
            raise Failed(f"{name}: tile {t['tile']} residual "
                         f"{r0} -> {r1} is not a finite fall")
    return tiles


def first_tile_s(recs):
    t0 = next(r["t"] for r in recs if r.get("ev") == "run_start")
    return next(r["t"] for r in recs if r.get("ev") == "tile") - t0


def longest_exec_s(recs):
    """Longest single solve phase of a diag trace (the traced consensus
    plan runs one device execution per interval)."""
    d = [r["dur_s"] for r in recs
         if r.get("ev") == "phase" and r.get("name") == "solve"]
    return max(d) if d else float("nan")


def cache_files():
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(HERE, ".jax_cache")
    return sum(len(fs) for _, _, fs in os.walk(d))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def make_data(S, n_sub):
    env = dict(os.environ, JAX_PLATFORMS="cpu")     # data, not the path
    out, wall = child(
        "data", [PY, "-c", DATAGEN, S["data"], str(S["seed"]),
                 str(S["n_sta"]), str(S["n_dir"]), str(S["n_src"]),
                 str(S["tilesz"]), str(S["n_tiles"]), str(n_sub)],
        300, env=env)
    say(f"[data] {out.strip().splitlines()[-1]} in {wall:.1f} s "
        f"(CPU child, seed {S['seed']})")


def sky_args(S):
    sky = os.path.join(S["data"], "sky.txt")
    return ["-s", sky, "-c", sky + ".cluster", "-t", str(S["tilesz"])]


def phase_fullbatch(S):
    ms = os.path.join(S["data"], "sb00.ms")
    runs = []
    n0 = cache_files()
    for i in (1, 2):
        diag = os.path.join(WORK, f"fullbatch{i}.jsonl")
        out, wall = child(
            f"fullbatch run {i}",
            [PY, "-m", "sagecal_tpu.cli", "-d", ms, *sky_args(S),
             "-p", os.path.join(WORK, f"fullbatch{i}.sol"),
             "--diag", diag, *S["plat_args"]], 420)
        dev = device_of(f"fullbatch run {i}", out, S["want"])
        if S["want"] == "tpu" and "Coherency path: pallas" not in out:
            raise Failed("fullbatch: the Pallas coherency path was not "
                         f"taken\n{out[-2000:]}")
        recs = read_diag(diag)
        tiles = tiles_of(f"fullbatch run {i}", recs, S["n_tiles"])
        runs.append((out, wall, recs, tiles, dev))
        path = re.search(r"^Coherency path: (.*)$", out, re.M)
        pack = re.search(r"tile packer: (.*)$", out, re.M)
        say(f"[fullbatch run {i}] platform {dev[0]} ({dev[1]}), coherency "
            f"path {path.group(1) if path else '?'}, packer "
            f"{pack.group(1) if pack else '?'}; wall {wall:.1f} s, "
            f"first tile after {first_tile_s(recs):.1f} s; res_0 -> res_1: "
            + ", ".join(f"{t['res_0']:.6g} -> {t['res_1']:.6g}"
                        for t in tiles))
    n1 = cache_files()
    t1, t2 = first_tile_s(runs[0][2]), first_tile_s(runs[1][2])
    say(f"[fullbatch] time to first tile: run 1 {t1:.1f} s, run 2 "
        f"{t2:.1f} s; compile cache files {n0} -> {n1}")
    if n1 == 0:
        raise Failed("fullbatch: the persistent compile cache is empty "
                     "after two runs")
    if n0 == 0 and not t2 < t1:
        raise Failed("fullbatch: the second run was not faster to its "
                     "first tile than the cold first run: the compile "
                     "cache was not hit")
    # the same command on --platform cpu, f32, one tile
    diag = os.path.join(WORK, "fullbatch_cpu.jsonl")
    out, wall = child(
        "fullbatch cpu reference",
        [PY, "-m", "sagecal_tpu.cli", "-d", ms, *sky_args(S), "-T", "1",
         "-p", os.path.join(WORK, "fullbatch_cpu.sol"), "--diag", diag,
         "--platform", "cpu"], 420)
    device_of("fullbatch cpu reference", out, "cpu")
    ref = tiles_of("fullbatch cpu reference", read_diag(diag), 1)[0]
    got = runs[0][3][0]
    ratio_dev = got["res_1"] / got["res_0"]
    ratio_cpu = ref["res_1"] / ref["res_0"]
    rel = abs(ratio_dev / ratio_cpu - 1.0)
    say(f"[fullbatch] tile 0 res_1/res_0: {S['want']} {ratio_dev:.6g}, "
        f"cpu reference {ratio_cpu:.6g} ({wall:.1f} s), relative "
        f"difference {rel:.3g} (tolerance {FULLBATCH_CPU_TOL})")
    if not rel <= FULLBATCH_CPU_TOL:
        raise Failed("fullbatch: tile 0 disagrees with the CPU reference")
    return runs[0][4]


def api(sock_path, req, timeout=60):
    """One request/reply over the daemon's JSON-lines socket."""
    with socket.socket(socket.AF_UNIX, socket.SOCK_STREAM) as s:
        s.settimeout(timeout)
        s.connect(sock_path)
        s.sendall((json.dumps(req) + "\n").encode())
        buf = b""
        while not buf.endswith(b"\n"):
            chunk = s.recv(1 << 16)
            if not chunk:
                break
            buf += chunk
    resp = json.loads(buf)
    if not resp.get("ok"):
        raise Failed(f"serve: {req.get('op')} refused: {resp}")
    return resp


def phase_serve(S):
    sock = os.path.join(WORK, "serve.sock")
    log_path = os.path.join(WORK, "serve.out")
    jobs = []
    for i in (1, 2):        # each job calibrates its own copy
        ms = os.path.join(WORK, f"job{i}.ms")
        shutil.copytree(os.path.join(S["data"], "sb00.ms"), ms)
        jobs.append(ms)
    sky = os.path.join(S["data"], "sky.txt")
    cmd = [PY, "-m", "sagecal_tpu.serve", "--socket", sock,
           *S["plat_args"]]
    say(f"[serve] $ python {' '.join(cmd[1:])}")
    t0 = time.time()
    with open(log_path, "w") as logf:
        daemon = subprocess.Popen(cmd, cwd=HERE, stdout=logf,
                                  stderr=subprocess.STDOUT,
                                  start_new_session=True)
    S["procs"].append(daemon)

    def log_text():
        with open(log_path) as f:
            return f.read()

    def wait_until(what, cond, limit_s):
        end = time.time() + remaining(limit_s)
        while time.time() < end:
            if daemon.poll() is not None:
                raise Failed(f"serve: daemon exited {daemon.returncode} "
                             f"while {what}\n{log_text()[-3000:]}")
            v = cond()
            if v:
                return v
            time.sleep(0.5)
        raise Failed(f"serve: still {what} after {limit_s} s\n"
                     f"{log_text()[-3000:]}")

    wait_until("starting", lambda: "listening on" in log_text(), 180)
    say(f"[serve] daemon listening after {time.time() - t0:.1f} s")
    hits = api(sock, {"op": "metrics"})["metrics"]["hits"]
    for i, ms in enumerate(jobs, 1):
        trace = os.path.join(WORK, f"job{i}.jsonl")
        t1 = time.time()
        jid = api(sock, {"op": "submit", "trace": trace, "config": {
            "ms": ms, "sky_model": sky, "cluster_file": sky + ".cluster",
            "tile_size": S["tilesz"],
            "solutions_file": os.path.join(WORK, f"job{i}.sol")}})["job_id"]

        def finished():
            j = api(sock, {"op": "status", "job_id": jid})["job"]
            return j if j["state"] in ("done", "failed",
                                       "cancelled") else None
        job = wait_until(f"running job {i}", finished, 420)
        if job["state"] != "done":
            raise Failed(f"serve: job {i} ended '{job['state']}': "
                         f"{job.get('error')}\n{job.get('error_tb')}")
        tiles = tiles_of(f"serve job {i}", read_diag(trace), S["n_tiles"])
        m = api(sock, {"op": "metrics"})["metrics"]
        say(f"[serve] job {i} done in {time.time() - t1:.1f} s; "
            f"program cache hits {hits} -> {m['hits']} (misses "
            f"{m['misses']}); res_0 -> res_1: "
            + ", ".join(f"{t['res_0']:.6g} -> {t['res_1']:.6g}"
                        for t in tiles))
        if i == 2 and not m["hits"] > hits:
            raise Failed("serve: the second job reported no "
                         "program-cache hit")
        hits = m["hits"]
    api(sock, {"op": "drain", "wait": True}, timeout=remaining(120))
    try:
        rc = daemon.wait(timeout=remaining(60))
    except subprocess.TimeoutExpired:
        raise Failed("serve: daemon did not exit after drain")
    if rc != 0:
        raise Failed(f"serve: daemon exit {rc}\n{log_text()[-3000:]}")
    out = log_text()
    dev = device_of("serve", out, S["want"])
    if S["want"] == "tpu" and "Coherency path: pallas" not in out:
        raise Failed("serve: the Pallas coherency path was not taken")
    say(f"[serve] daemon on {dev[0]} ({dev[1]}) drained and exited 0; "
        f"total {time.time() - t0:.1f} s")


def subband_falls(name, out, n_sub, n_tiles):
    """Per-subband residual pairs from cli_mpi -V output; every one a
    finite fall. Returns the final residuals [n_tiles, n_sub]."""
    pairs = [(float(a), float(b)) for a, b in re.findall(
        r"^  subband \d+: (\S+) -> (\S+)$", out, re.M)]
    if len(pairs) != n_sub * n_tiles:
        raise Failed(f"{name}: {len(pairs)} per-subband residual lines, "
                     f"expected {n_sub * n_tiles}\n{out[-2000:]}")
    for r0, r1 in pairs:
        if not (np.isfinite(r0) and np.isfinite(r1) and r1 < r0):
            raise Failed(f"{name}: subband residual {r0} -> {r1} is "
                         "not a finite fall")
    return np.array([p[1] for p in pairs]).reshape(n_tiles, n_sub)


def run_consensus(S, name, n_sub, n_tiles, extra=()):
    pattern = os.path.join(S["data"], "sb0[0-%d].ms" % (n_sub - 1))
    diag = os.path.join(WORK, f"{name}.jsonl")
    solp = os.path.join(WORK, f"{name}.zsol")
    out, wall = child(
        name, [PY, "-m", "sagecal_tpu.cli_mpi", "-f", pattern,
               *sky_args(S), "-A", "3", "-P", "2", "-T", str(n_tiles),
               "-V", "-p", solp, "--diag", diag, *S["plat_args"],
               *extra], 540)
    dev = device_of(name, out, S["want"])
    res1 = subband_falls(name, out, n_sub, n_tiles)
    recs = read_diag(diag)
    shards = re.search(r"^Shard devices: (.*)$", out, re.M)
    say(f"[{name}] platform {dev[0]} ({dev[1]}, {dev[2]} device(s)); "
        f"wall {wall:.1f} s, first interval after "
        f"{first_tile_s(recs):.1f} s, longest solve phase (ONE device "
        f"execution under the traced plan) {longest_exec_s(recs):.2f} s; "
        f"shard devices: "
        f"{shards.group(1) if shards else '?'}")
    for ln in re.findall(r"^Timeslot:.*$", out, re.M):
        say(f"[{name}] {ln}")
    return dev, res1, solp, (shards.group(1).split() if shards else [])


def read_z(path):
    rows = []
    with open(path) as f:
        for ln in f:
            p = ln.split()
            if not p or p[0].startswith("#"):
                continue
            try:
                rows.append([float(x) for x in p])
            except ValueError:
                continue
    w = max(len(r) for r in rows)
    return np.array([r for r in rows if len(r) == w])


def phase_mesh4(S):
    n_sub, n_tiles = 8, 1
    extra4 = ["--cpu-devices", "4"] if S["want"] == "cpu" else []
    dev, res_m, z_m, shards = run_consensus(S, "mesh4", n_sub, n_tiles,
                                            extra4)
    if dev[2] != 4 or len(set(shards)) != 4:
        raise Failed(f"mesh4: expected shards on four distinct devices, "
                     f"got {dev[2]} visible and shards on {shards}")
    # one device cannot hold the traced plan at 8 subbands: compiled for
    # a described v5e it needs 25.6 GB of temporaries against 16 GB of
    # HBM (PERF.md "Bring-up on v5e"), so the one-device run takes the
    # same mathematics in blocks of 2 subbands per execution
    say("[mesh1] --mesh-devices 1 with --block-f 2: the traced plan "
        "needs 25.6 GB for 8 subbands on one 16 GB device")
    _, res_1, z_1, _ = run_consensus(
        S, "mesh1", n_sub, n_tiles,
        extra4 + ["--mesh-devices", "1", "--block-f", "2"])
    d_res = float(np.max(np.abs(res_m / res_1 - 1.0)))
    Zm, Z1 = read_z(z_m), read_z(z_1)
    if Zm.shape != Z1.shape:
        raise Failed(f"mesh4: Z solution shapes differ {Zm.shape} "
                     f"vs {Z1.shape}")
    d_z = float(np.max(np.abs(Zm - Z1)) / np.max(np.abs(Z1)))
    say(f"[mesh4] four devices vs --mesh-devices 1: per-subband final "
        f"residuals differ by at most {d_res:.3g} (relative, tolerance "
        f"{MESH_RES_TOL}); consensus Z by {d_z:.3g} of max|Z| "
        f"(tolerance {MESH_Z_TOL})")
    if not (d_res <= MESH_RES_TOL and d_z <= MESH_Z_TOL):
        raise Failed("mesh4: the four-device mesh disagrees with one "
                     "device")
    return dev


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7,
                    help="seed of the synthetic sky, gains and noise")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the four-device mesh phase and its "
                         "one-device comparison")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny shapes on the CPU platform; finds wrong "
                         "paths and arguments, never prints an ok line")
    args = ap.parse_args(argv)

    S = dict(seed=args.seed, n_sta=62, n_dir=8, n_src=3, tilesz=10,
             n_tiles=3, want="tpu", plat_args=[], procs=[],
             data=os.path.join(WORK, "data"))
    if args.rehearse_cpu:
        S.update(n_sta=8, n_dir=3, n_src=2, tilesz=2, n_tiles=2,
                 want="cpu", plat_args=["--platform", "cpu"])
        os.environ["JAX_PLATFORMS"] = "cpu"     # every child inherits it
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(S["data"])
    try:
        if not args.rehearse_cpu:
            # fail in seconds where JAX finds no accelerator; the probe
            # exits before any other child needs the chip
            out, _ = child("probe", [PY, "-c", PROBE], 120)
            device_of("probe", out, "tpu")
        if args.chips == 4:
            make_data(S, 8)
            dev = phase_mesh4(S)
        else:
            make_data(S, 4)
            dev = phase_fullbatch(S)
            phase_serve(S)
            dev_c, _, _, _ = run_consensus(S, "consensus", 4, 1)
            if dev_c != dev:
                raise Failed(f"children disagree on the device: {dev} "
                             f"vs {dev_c}")
        if dev[2] != args.chips and not args.rehearse_cpu:
            raise Failed(f"--chips {args.chips} but the children saw "
                         f"{dev[2]} device(s)")
    except Failed as e:
        say(f"FAILED: {e}")
        return 1
    finally:
        for p in S["procs"]:
            if p.poll() is None:
                try:
                    os.killpg(p.pid, signal.SIGKILL)
                except OSError:
                    pass
                p.wait()
    say(f"total {time.time() - T_START:.1f} s")
    if args.rehearse_cpu:
        say(json.dumps({"rehearsal": "passed", "device": {
            "platform": dev[0], "kind": dev[1], "count": dev[2]}}))
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0], "kind": dev[1], "count": dev[2]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
