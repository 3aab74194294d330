"""Traffic-replay load generator for the serve fleet.

Synthesizes a *replayable* tenant workload — mixed shape-buckets,
priorities, deadlines, and a seedable arrival process — and drives a
live daemon with it through the JSON-lines client, so fleet numbers
(throughput-per-device, p99 queue wait, per-device cache hit rate)
are measured against a *defined* traffic mix instead of hand-run
jobs. Everything is deterministic from the spec: the same ``seed``
produces the same datasets (content seeds), the same arrival times,
the same priorities/deadlines — replaying a spec against two fleet
sizes is an apples-to-apples comparison (what ROADMAP's
``serve-jobs`` cell is to be built from).

A spec is a JSON object (all fields defaulted — ``{}`` is valid)::

    {
      "seed": 12,
      "n_jobs": 8,
      "arrival": {"process": "poisson", "rate_per_s": 4.0},
      "templates": [
        {"name": "bucketA", "weight": 1.0,
         "n_stations": 16, "tilesz": 4, "n_tiles": 6, "nchan": 24,
         "noise_sigma": 0.02,
         "priority": [0], "deadline_s": null,
         "config": {"solver_mode": 0, "max_em_iter": 1, ...}}
      ]
    }

``arrival.process``: ``"poisson"`` (exponential inter-arrival at
``rate_per_s``), ``"uniform"`` (fixed spacing ``1/rate_per_s``) or
``"burst"`` (everything at t=0 — the backlog-drain regime whose
queue-wait tail shows fleet capacity). A template's ``repeat``
(default 0) grows its draw weight with every draw — repeat-field
traffic, the regime the warm-start prior cache (serve/priors.py)
is built for. Template ``config`` fields are
RunConfig names (serve ``submit`` semantics); ``tile_arrival_s``
there turns on streaming-ingest pacing (config.py) — the
ingest-limited regime where per-device throughput is bounded by
tenant data rate, not device compute.

Each scheduled job gets its OWN copy of its template's dataset (jobs
write residuals in place), so per-job outputs are independently
comparable against a solo run of the same template — the
bit-identity gate of tests/test_fleet.py.

Layering: stdlib + numpy + the serve Client; jax only inside
:func:`build_fixtures` (dataset synthesis).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import random
import shutil
import time

import numpy as np

#: small two-cluster sky shared by every template:
#: enough structure for a real solve, cheap enough for a replay
SKY = """\
P0A 0 40 0 40 0 0 3.0 0 0 0 0 0 0 0 0 150e6
P1A 1 20 0 38 0 0 2.5 0 0 0 0 0 0 0 0 150e6
"""
CLUSTER = """\
0 1 P0A
1 2 P1A
"""

DEFAULT_TEMPLATE = dict(
    name="bucketA", weight=1.0, repeat=0.0, n_stations=16, tilesz=4,
    n_tiles=6, nchan=24, noise_sigma=0.02, priority=[0],
    deadline_s=None, config={})

DEFAULT_SPEC = dict(
    seed=12, n_jobs=8,
    arrival=dict(process="burst", rate_per_s=4.0),
    templates=[dict(DEFAULT_TEMPLATE)])

#: solver knobs every template starts from (pinned solve plan — the
#: zero-compile/bit-identity contract of tests/test_serve.py)
BASE_CONFIG = dict(solver_mode=0, max_em_iter=1, max_iter=4,
                   max_lbfgs=2, solve_fuse="on", solve_promote="off",
                   prefetch=2)


def load_spec(spec) -> dict:
    """Spec from a dict, JSON text, or a path; defaults filled in."""
    if isinstance(spec, str):
        if os.path.exists(spec):
            with open(spec) as f:
                spec = json.load(f)
        else:
            spec = json.loads(spec)
    out = dict(DEFAULT_SPEC)
    out.update(spec or {})
    out["arrival"] = dict(DEFAULT_SPEC["arrival"],
                          **(out.get("arrival") or {}))
    tmpls = []
    for t in out["templates"]:
        tmpls.append(dict(DEFAULT_TEMPLATE, **t))
    names = [t["name"] for t in tmpls]
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate template names: {names}")
    out["templates"] = tmpls
    return out


def schedule(spec) -> list:
    """The deterministic arrival schedule: ``[{t, template, job_id,
    priority, deadline_s, seq}, ...]`` sorted by arrival time. Pure
    function of the spec (``random.Random(seed)`` — no wall clock)."""
    spec = load_spec(spec)
    rng = random.Random(int(spec["seed"]))
    tmpls = spec["templates"]
    arr = spec["arrival"]
    # "repeat" models repeat-field traffic (the warm-start prior-cache
    # regime): each draw of a template multiplies its effective weight
    # by (1 + repeat * draws_so_far), so a re-observed field grows
    # stickier the more it is observed. repeat=0 (default) is the old
    # static mix — same seed, same schedule, bit for bit.
    draws = {t_["name"]: 0 for t_ in tmpls}
    t = 0.0
    out = []
    for i in range(int(spec["n_jobs"])):
        weights = [float(t_["weight"])
                   * (1.0 + float(t_.get("repeat", 0.0))
                      * draws[t_["name"]])
                   for t_ in tmpls]
        tmpl = rng.choices(tmpls, weights=weights)[0]
        draws[tmpl["name"]] += 1
        prio = rng.choice(list(tmpl["priority"]))
        out.append(dict(t=round(t, 6), template=tmpl["name"],
                        job_id=f"replay-{spec['seed']}-{i:03d}",
                        priority=int(prio),
                        deadline_s=tmpl["deadline_s"], seq=i))
        if arr["process"] == "poisson":
            t += rng.expovariate(float(arr["rate_per_s"]))
        elif arr["process"] == "uniform":
            t += 1.0 / float(arr["rate_per_s"])
        elif arr["process"] == "burst":
            pass                        # everything arrives at t=0
        else:
            raise ValueError(
                f"unknown arrival process {arr['process']!r}")
    return out


def build_fixtures(spec, workdir: str) -> dict:
    """Materialize the sky + one prototype dataset per template
    (content-seeded: same spec -> same bytes). Returns
    ``{template_name: {"ms": protodir, "sky": ..., "cluster": ...}}``."""
    import jax.numpy as jnp
    from sagecal_tpu import skymodel
    from sagecal_tpu.io import dataset as ds
    from sagecal_tpu.rime import predict as rp
    spec = load_spec(spec)
    os.makedirs(workdir, exist_ok=True)
    skyf = os.path.join(workdir, "sky.txt")
    clusf = skyf + ".cluster"
    with open(skyf, "w") as f:
        f.write(SKY)
    with open(clusf, "w") as f:
        f.write(CLUSTER)
    ra0 = (41 / 60) * math.pi / 12
    dec0 = 40 * math.pi / 180
    srcs = skymodel.parse_sky_model(skyf, ra0, dec0, 150e6)
    sky = skymodel.build_cluster_sky(
        srcs, skymodel.parse_cluster_file(clusf))
    dsky = rp.sky_to_device(sky, jnp.float32)
    seed0 = int(spec["seed"])
    out = {}
    for tn, tmpl in enumerate(spec["templates"]):
        Jt = ds.random_jones(sky.n_clusters, sky.nchunk,
                             tmpl["n_stations"], seed=seed0 + 5 + tn,
                             scale=0.15)
        freqs = np.linspace(149e6, 151e6, int(tmpl["nchan"]))
        tiles = [ds.simulate_dataset(
            dsky, n_stations=int(tmpl["n_stations"]),
            tilesz=int(tmpl["tilesz"]), freqs=freqs, ra0=ra0,
            dec0=dec0, jones=Jt, nchunk=sky.nchunk,
            noise_sigma=float(tmpl["noise_sigma"]),
            seed=seed0 + 100 * (tn + 1) + t)
            for t in range(int(tmpl["n_tiles"]))]
        proto = os.path.join(workdir, f"proto_{tmpl['name']}.ms")
        ds.SimMS.create(proto, tiles)
        out[tmpl["name"]] = {"ms": proto, "sky": skyf,
                             "cluster": clusf}
    return out


def job_config(spec, tmpl_name: str, msdir: str, solutions: str) -> dict:
    """The serve ``submit`` config for one replay job of a template
    (BASE_CONFIG <- template overrides <- this job's paths)."""
    spec = load_spec(spec)
    tmpl = {t["name"]: t for t in spec["templates"]}[tmpl_name]
    cfg = dict(BASE_CONFIG)
    cfg.update(tmpl["config"])
    cfg.update(ms=msdir, tile_size=int(tmpl["tilesz"]),
               solutions_file=solutions)
    return cfg


def replay(client, spec, fixtures, workdir: str, log=print,
           drain: bool = True, timeout_s: float = 3600.0,
           tag: str | None = None) -> dict:
    """Drive a live daemon (or fleet router — the same API) with the
    spec's schedule. ``client``: a connected ``serve.api.Client``;
    ``fixtures``: from :func:`build_fixtures` (per-template prototype
    datasets — each job gets its own copy under ``workdir``). Blocks
    until every submitted job is terminal — by default via a
    server-side drain wait (no status polling stealing host cycles
    mid-replay); ``drain=False`` instead polls with ONE pipelined
    status batch per interval, leaving the server accepting, so a
    caller can run several replays against one warm fleet.
    Returns the replay record: wall, throughput,
    queue-wait/e2e percentiles, per-job rows, and the output paths
    for the caller's bit-identity gate."""
    spec = load_spec(spec)
    sched_rows = schedule(spec)
    if tag:
        # several replays of ONE spec against one long-lived server
        # (warm legs after a first replay) need distinct job ids —
        # registries, daemon and router alike, refuse duplicates
        sched_rows = [dict(row, job_id=f"{row['job_id']}-{tag}")
                      for row in sched_rows]
    fix = {n: dict(v) for n, v in fixtures.items()}
    jobs = []
    for row in sched_rows:
        f = fix[row["template"]]
        msdir = os.path.join(workdir, f"{row['job_id']}.ms")
        if os.path.exists(msdir):
            shutil.rmtree(msdir)
        shutil.copytree(f["ms"], msdir)
        sol = os.path.join(workdir, f"{row['job_id']}.sol")
        cfg = job_config(spec, row["template"], msdir, sol)
        cfg.update(sky_model=f["sky"], cluster_file=f["cluster"])
        jobs.append(dict(row, ms=msdir, solutions=sol, config=cfg))
    t0 = time.perf_counter()
    for job in jobs:
        # honour the arrival process (monotonic offsets from t0)
        delay = job["t"] - (time.perf_counter() - t0)
        if delay > 0:
            time.sleep(delay)
        kw = dict(job_id=job["job_id"], priority=job["priority"])
        if job["deadline_s"] is not None:
            kw["deadline_s"] = float(job["deadline_s"])
        client.submit(job["config"], **kw)
    if drain:
        client.drain(wait=True)
    else:
        terminal = ("done", "failed", "cancelled", "deadline_exceeded")
        deadline = time.monotonic() + timeout_s
        ids = [job["job_id"] for job in jobs]
        while True:
            if all(s["state"] in terminal
                   for s in client.status_many(ids)):
                break
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"replay: jobs not terminal after {timeout_s}s")
            time.sleep(0.1)
    wall = time.perf_counter() - t0
    waits, e2es, states = [], [], {}
    rows = []
    # ONE pipelined round-trip for the whole post-replay sweep (the
    # api.Client persistent-connection pipelining): N per-op network
    # round-trips collapse to one — against a router front-end every
    # status also fans out a proxy hop, so the saving doubles
    snaps = client.status_many([job["job_id"] for job in jobs])
    for job, snap in zip(jobs, snaps):
        states[snap["state"]] = states.get(snap["state"], 0) + 1
        qw = (snap["started_t"] - snap["submitted_t"]
              if snap["started_t"] else None)
        e2e = (snap["finished_t"] - snap["submitted_t"]
               if snap["finished_t"] else None)
        if qw is not None:
            waits.append(qw)
        if e2e is not None:
            e2es.append(e2e)
        row = dict(job_id=job["job_id"], template=job["template"],
                   state=snap["state"], device=snap["device"],
                   queue_wait_s=qw, e2e_s=e2e,
                   migrations=snap["migrations"],
                   solver_iters=int(snap.get("solver_iters") or 0),
                   ms=job["ms"], solutions=job["solutions"])
        if snap.get("kind") == "stream" or snap.get("tiles_late"):
            # streaming tenants (a template whose config carries
            # stream_source): per-tile lateness rides the row so a
            # caller can gate on it without re-polling
            row["tiles_late"] = snap.get("tiles_late", 0)
            row["tiles_degraded"] = snap.get("tiles_degraded", 0)
        if "worker" in snap:
            # router replay: which worker PROCESS ran the job (the
            # per-worker routing view; "device" is worker-local)
            row["worker"] = snap["worker"]
            row["hops"] = snap.get("hops", [])
        rows.append(row)
    n_done = states.get("done", 0)
    # per-template sweeps-to-convergence: total executed solver sweeps
    # per finished job of each template (Job.snapshot solver_iters) —
    # the warm start's primary axis (warm vs cold at equal
    # convergence quality is fewer sweeps, not a different answer)
    sweeps = {}
    for row in rows:
        if row["state"] == "done":
            sweeps.setdefault(row["template"], []).append(
                row["solver_iters"])
    rec = dict(
        n_jobs=len(jobs), states=states, wall_s=round(wall, 3),
        throughput_jobs_per_s=round(n_done / wall, 4) if wall else 0.0,
        queue_wait_p50_s=_pct(waits, 50), queue_wait_p99_s=_pct(waits, 99),
        e2e_p50_s=_pct(e2es, 50), e2e_p99_s=_pct(e2es, 99),
        sweeps_by_template={k: round(float(np.mean(v)), 3)
                            for k, v in sorted(sweeps.items()) if v},
        jobs=rows)
    log(f"loadgen: {n_done}/{len(jobs)} done in {wall:.2f}s "
        f"({rec['throughput_jobs_per_s']:.3f} jobs/s, p99 queue wait "
        f"{rec['queue_wait_p99_s']}s)")
    return rec


def _pct(vals, p) -> float | None:
    """Exact (nearest-rank, interpolated) percentile of the measured
    per-job values — no histogram-bucket clamping."""
    if not vals:
        return None
    v = sorted(vals)
    k = (len(v) - 1) * p / 100.0
    lo, hi = int(math.floor(k)), int(math.ceil(k))
    if lo == hi:
        return round(v[lo], 6)
    return round(v[lo] + (v[hi] - v[lo]) * (k - lo), 6)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m sagecal_tpu.serve.loadgen",
        description="replay a synthetic traffic spec against a live "
                    "serve daemon and print the replay record")
    g = p.add_mutually_exclusive_group(required=True)
    g.add_argument("--socket", metavar="PATH")
    g.add_argument("--port", type=int)
    g.add_argument("--router", metavar="ADDR",
                   help="drive a fleet ROUTER instead of a daemon "
                        "(HOST:PORT or unix socket path — the same "
                        "JSON-lines API, serve/router.py); replay "
                        "records then measure the whole multi-process "
                        "fleet behind it")
    p.add_argument("--spec", default="{}",
                   help="JSON spec (inline or a path); {} = defaults")
    p.add_argument("--workdir", default=None,
                   help="dataset scratch dir (default: a tempdir)")
    p.add_argument("--platform", default=None,
                   help="force the jax platform for dataset synthesis")
    args = p.parse_args(argv)
    from sagecal_tpu import utils
    utils.setup_backend(args.platform)
    import tempfile
    workdir = args.workdir or tempfile.mkdtemp(prefix="sagecal_loadgen_")
    spec = load_spec(args.spec)
    fixtures = build_fixtures(spec, workdir)
    from sagecal_tpu.serve.api import Client
    sock, port = args.socket, args.port
    if args.router:
        from sagecal_tpu.serve.router import parse_router_addr
        addr = parse_router_addr(args.router)
        sock, port = addr.get("socket"), addr.get("port")
    with Client(socket_path=sock, port=port) as c:
        rec = replay(c, spec, fixtures, workdir)
    print(json.dumps(rec, indent=1, default=float))
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
