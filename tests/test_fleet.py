"""Fleet-mode gates (serve/fleet.py + the fleet scheduler, ISSUE 12).

The contracts under test (MIGRATION.md "Fleet mode"):

- the placement layer (pure): bucket affinity routes same-bucket jobs
  to the device whose compile cache is warm; capacity (inflight +
  staged bytes) is per device; a lone job always admits somewhere; a
  migration pin wins; least-load tie-breaks;
- the queue's fleet admission path (pure): MIGRATING resumes ahead of
  QUEUED, pinned jobs only admit on their pinned device, per-device
  budgets, strict head-of-line fleet-wide;
- the loadgen (pure): the arrival schedule is a deterministic
  function of the spec seed — replaying one spec against two fleet
  sizes is apples-to-apples;
- the live 2-virtual-device fleet: bucket-affine jobs land on the
  SAME device as their bucket peers (so the second job of a bucket
  adds zero compiles on its device), every job's outputs are
  bit-identical to a solo run, and the metrics surface carries the
  per-device snapshot (busy/running/tiles/cache hit rate/watermark).

Single-device compatibility is gated where it lives: the unmodified
tests/test_serve.py suite runs the daemon with devices=None and must
stay green (ISSUE 12 acceptance).
"""

import math
import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from sagecal_tpu import pipeline, skymodel  # noqa: E402
from sagecal_tpu.io import dataset as ds  # noqa: E402
from sagecal_tpu.rime import predict as rp  # noqa: E402
from sagecal_tpu.serve import fleet  # noqa: E402
from sagecal_tpu.serve import loadgen  # noqa: E402
from sagecal_tpu.serve import queue as jq  # noqa: E402
from sagecal_tpu.serve.api import Client, Server, config_from_dict  # noqa: E402

SKY = """\
P0A 0 40 0 40 0 0 3.0 0 0 0 0 0 0 0 0 150e6
P1A 1 20 0 38 0 0 2.5 0 0 0 0 0 0 0 0 150e6
"""
CLUSTER = """\
0 1 P0A
1 2 P1A
"""


@pytest.fixture(autouse=True)
def _fresh_obs_registry():
    from sagecal_tpu.obs import metrics as ometrics
    ometrics.disable()
    yield
    ometrics.disable()


def _make_dataset(tmp_path, name, n_tiles=3, n_stations=8, tilesz=4,
                  nchan=2, seed=11):
    sky_path = tmp_path / "sky.txt"
    if not sky_path.exists():
        sky_path.write_text(SKY)
        (tmp_path / "sky.txt.cluster").write_text(CLUSTER)
    ra0 = (41 / 60) * math.pi / 12
    dec0 = 40 * math.pi / 180
    srcs = skymodel.parse_sky_model(str(sky_path), ra0, dec0, 150e6)
    sky = skymodel.build_cluster_sky(
        srcs, skymodel.parse_cluster_file(str(tmp_path / "sky.txt.cluster")))
    dsky = rp.sky_to_device(sky, jnp.float64)
    Jt = ds.random_jones(sky.n_clusters, sky.nchunk, n_stations, seed=5,
                         scale=0.15)
    freqs = np.linspace(149e6, 151e6, nchan)
    tiles = [ds.simulate_dataset(dsky, n_stations=n_stations,
                                 tilesz=tilesz, freqs=freqs, ra0=ra0,
                                 dec0=dec0, jones=Jt, nchunk=sky.nchunk,
                                 noise_sigma=0.02, seed=seed + t)
             for t in range(n_tiles)]
    msdir = tmp_path / name
    ds.SimMS.create(str(msdir), tiles)
    return str(msdir), str(sky_path), str(tmp_path / "sky.txt.cluster")


def _base_config(skyf, clusf, **kw):
    cfg = dict(sky_model=skyf, cluster_file=clusf, solver_mode=0,
               max_em_iter=1, max_iter=4, max_lbfgs=2, tile_size=4,
               solve_fuse="on", solve_promote="off")
    cfg.update(kw)
    return cfg


def _corrected(msdir):
    out = ds.SimMS(msdir, data_column="CORRECTED_DATA")
    return [out.read_tile(i).x.copy() for i in range(out.n_tiles)]


# ---------------------------------------------------------------------------
# placement units (pure)
# ---------------------------------------------------------------------------

def _job(job_id, bucket=None, est=10, pin=None):
    j = jq.Job(job_id, cfg=None)
    j.bucket = bucket
    j.est_bytes = est
    j.pinned_device = pin
    return j


def test_placer_affinity_capacity_and_pins():
    p = fleet.Placer(2, max_inflight=2, max_staged_bytes=100)
    idle = lambda: [{"running": 0, "staged_bytes": 0},
                    {"running": 0, "staged_bytes": 0}]

    # first job of a bucket: least-load -> device 0; affinity recorded
    a1 = _job("a1", bucket="A")
    assert p.place(a1, idle()) == 0
    p.assign(a1, 0)
    # second job of the bucket FOLLOWS the warm cache even though
    # device 1 is emptier
    st = idle()
    st[0]["running"] = 1
    a2 = _job("a2", bucket="A")
    assert p.place(a2, st) == 0
    # a new bucket balances to the other device
    b1 = _job("b1", bucket="B")
    assert p.place(b1, st) == 1
    p.assign(b1, 1)

    # per-device capacity: affinity home full -> overflow to the
    # device with room (better a cold compile than an idle device)
    st = [{"running": 2, "staged_bytes": 20},
          {"running": 0, "staged_bytes": 0}]
    assert p.place(_job("a3", bucket="A"), st) == 1
    # both full -> head-of-line block
    st = [{"running": 2, "staged_bytes": 20},
          {"running": 2, "staged_bytes": 20}]
    assert p.place(_job("a4", bucket="A"), st) is None

    # staged-bytes budget is per device; a lone job always admits
    st = [{"running": 1, "staged_bytes": 95},
          {"running": 0, "staged_bytes": 0}]
    big = _job("big", est=50)
    assert p.place(big, st) == 1          # device 1 empty: lone-job rule
    st[1] = {"running": 1, "staged_bytes": 95}
    assert p.place(big, st) is None       # both over budget

    # a migration pin wins over affinity and load
    pinned = _job("m1", bucket="A", pin=1)
    st = [{"running": 0, "staged_bytes": 0},
          {"running": 1, "staged_bytes": 10}]
    assert p.place(pinned, st) == 1
    # rehome moves the bucket's affinity (post-migration)
    p.rehome("A", 1)
    assert p.place(_job("a5", bucket="A"), idle()) == 1


def test_queue_fleet_admission_and_migration_requeue():
    q = jq.JobQueue(max_inflight=1, max_staged_bytes=1000)
    p = fleet.Placer(2, max_inflight=1, max_staged_bytes=1000)
    j1 = q.submit(_job("j1", bucket="A"))
    j2 = q.submit(_job("j2", bucket="A"))
    j3 = q.submit(_job("j3", bucket="B"))
    est = lambda j: 10

    # the head job places to device 0 (least-load tie-break); worker 1
    # must NOT take it — ITS pass returns None until a job is placed
    # to it (strict head-of-line, fleet-wide)
    assert q.next_admissible(est, worker_ix=1, placer=p) is None
    got0 = q.next_admissible(est, worker_ix=0, placer=p)
    assert got0 is j1 and j1.device == 0
    # j2 (bucket A) is affine to device 0 — which is full
    # (max_inflight=1), so it overflows to device 1 and worker 1
    # takes it; j3 waits behind it
    got1 = q.next_admissible(est, worker_ix=1, placer=p)
    assert got1 is j2 and j2.device == 1
    assert q.next_admissible(est, worker_ix=0, placer=p) is None
    assert q.next_admissible(est, worker_ix=1, placer=p) is None

    # migration requeue: RUNNING -> MIGRATING, pinned; resumes AHEAD
    # of queued j3 and ONLY on the pinned device
    q.requeue_for_migration(j1, target=1)
    assert j1.state == jq.MIGRATING and j1.pinned_device == 1
    assert q.counts()["migrating"] == 1 and not q.idle()
    assert q.next_admissible(est, worker_ix=0, placer=p) is None
    q.finish(j2, jq.DONE)               # free device 1's slot
    got = q.next_admissible(est, worker_ix=1, placer=p)
    assert got is j1 and j1.state == jq.RUNNING and j1.device == 1
    # queue-wait observed once: started_t survived the migration
    assert j1.started_t is not None

    # an aborted migration (pin None) admits anywhere; cancel of a
    # MIGRATING job is immediate. j3's new bucket B balances AWAY from
    # bucket A's claimed device (fewest-owned-buckets tie-break)
    q.finish(j1, jq.DONE)
    assert q.next_admissible(est, worker_ix=0, placer=p) is None
    got = q.next_admissible(est, worker_ix=1, placer=p)
    assert got is j3
    q.requeue_for_migration(j3, target=None)
    assert j3.pinned_device is None
    assert q.cancel("j3") == jq.CANCELLED


# ---------------------------------------------------------------------------
# loadgen (pure)
# ---------------------------------------------------------------------------

def test_loadgen_schedule_is_deterministic():
    spec = {"seed": 7, "n_jobs": 6,
            "arrival": {"process": "poisson", "rate_per_s": 3.0},
            "templates": [
                {"name": "a", "weight": 1, "priority": [0, 5]},
                {"name": "b", "weight": 1, "tilesz": 6}]}
    s1 = loadgen.schedule(spec)
    s2 = loadgen.schedule(spec)
    assert s1 == s2                       # pure function of the spec
    assert len(s1) == 6
    assert [r["t"] for r in s1] == sorted(r["t"] for r in s1)
    assert {r["template"] for r in s1} <= {"a", "b"}
    assert all(r["job_id"].startswith("replay-7-") for r in s1)
    # a different seed reshuffles arrivals/mix
    assert loadgen.schedule(dict(spec, seed=8)) != s1
    # burst: everything at t=0
    burst = loadgen.schedule(dict(spec, arrival={"process": "burst"}))
    assert all(r["t"] == 0.0 for r in burst)
    with pytest.raises(ValueError, match="duplicate template"):
        loadgen.load_spec({"templates": [{"name": "x"}, {"name": "x"}]})
    with pytest.raises(ValueError, match="arrival process"):
        loadgen.schedule({"arrival": {"process": "nope"}})


# ---------------------------------------------------------------------------
# the live 2-virtual-device fleet
# ---------------------------------------------------------------------------

@pytest.mark.slow  # ~71 s (round-17 tier-1 rebalance); still a CI
# fail-fast gate — ci.yml runs it by -k without the 'not slow' filter
def test_fleet_two_devices_bucket_affine_and_bit_identical(tmp_path):
    """Four bucket-affine jobs (2x tilesz 4, 2x tilesz 5) through a
    2-device fleet: same-bucket jobs land on the same device (the
    placer following the warm compile cache), the metrics surface
    carries the per-device fleet snapshot, and every job's residuals
    and solutions are bit-identical to solo runs of the same
    configs."""
    assert len(jax.devices()) >= 2
    msA, skyf, clusf = _make_dataset(tmp_path, "a.ms", seed=11)
    msB, _, _ = _make_dataset(tmp_path, "b.ms", seed=50)
    msC, _, _ = _make_dataset(tmp_path, "c.ms", tilesz=5, seed=80)
    msD, _, _ = _make_dataset(tmp_path, "d.ms", tilesz=5, seed=95)
    base4 = _base_config(skyf, clusf)
    base5 = _base_config(skyf, clusf, tile_size=5)

    srv = Server(port=0, max_inflight=2, devices=2)
    # pin the placement outcome: these short jobs could otherwise be
    # work-stolen once a device runs dry, which is ITS OWN test below
    srv.scheduler.MIGRATE_MIN_REMAINING_TILES = 10 ** 6
    try:
        srv.start()
        with Client(port=srv.port) as c:
            ids = [
                c.submit(dict(base4, ms=msA,
                              solutions_file=str(tmp_path / "sA.txt"))),
                c.submit(dict(base4, ms=msB,
                              solutions_file=str(tmp_path / "sB.txt"))),
                c.submit(dict(base5, ms=msC,
                              solutions_file=str(tmp_path / "sC.txt"))),
                c.submit(dict(base5, ms=msD,
                              solutions_file=str(tmp_path / "sD.txt"))),
            ]
            snaps = [c.wait(j, timeout_s=300) for j in ids]
            assert all(s["state"] == jq.DONE for s in snaps)
            # bucket affinity: the two tilesz-4 jobs share a device,
            # the two tilesz-5 jobs share a device
            devs = [s["device"] for s in snaps]
            assert None not in devs
            assert devs[0] == devs[1], devs
            assert devs[2] == devs[3], devs
            m = c.metrics()
            assert m["n_devices"] == 2 and len(m["devices"]) == 2
            per_dev = {d["device"]: d for d in m["devices"]}
            # every device worked, and the per-device tile counters
            # account for exactly the jobs placed there (3 tiles/job)
            for s in snaps:
                per_dev[s["device"]]["expect"] = \
                    per_dev[s["device"]].get("expect", 0) + 3
            for d in m["devices"]:
                assert d["tiles_done"] == d.get("expect", 0)
                assert d["busy_s"] > 0
                assert "hit_rate" in d["cache"]
            assert m["tiles_done"] == 12
            # the fleet healthz carries per-device liveness
            h = srv.healthz()
            assert len(h["devices"]) == 2
            assert all(d["last_progress_age_s"] >= 0.0
                       for d in h["devices"])
    finally:
        srv.stop()

    # bit-identity: each job vs a solo run of its config on a fresh
    # copy of the same data
    for name, seed, tilesz, msdir, solf in (
            ("a2.ms", 11, 4, msA, "sA.txt"),
            ("b2.ms", 50, 4, msB, "sB.txt"),
            ("c2.ms", 80, 5, msC, "sC.txt"),
            ("d2.ms", 95, 5, msD, "sD.txt")):
        ms2, _, _ = _make_dataset(tmp_path, name, tilesz=tilesz,
                                  seed=seed)
        cfg = config_from_dict(_base_config(
            skyf, clusf, tile_size=tilesz, ms=ms2,
            solutions_file=str(tmp_path / f"solo_{solf}")))
        pipeline.run(cfg, log=lambda *a: None)
        for x, y in zip(_corrected(msdir), _corrected(ms2)):
            assert np.array_equal(x, y)
        assert (tmp_path / solf).read_text() \
            == (tmp_path / f"solo_{solf}").read_text()


@pytest.mark.slow  # ~31 s (round-17 tier-1 rebalance); still a CI
# fail-fast gate — ci.yml runs it by -k without the 'not slow' filter
def test_fleet_work_steals_to_idle_device(tmp_path):
    """Work stealing: two paced jobs forced onto device 0 (same
    bucket) while device 1 idles with an empty queue — the controller
    migrates one across at a tile boundary, it finishes on device 1,
    and its outputs stay bit-identical to a solo run."""
    assert len(jax.devices()) >= 2
    msA, skyf, clusf = _make_dataset(tmp_path, "wa.ms", n_tiles=6,
                                     seed=11)
    msB, _, _ = _make_dataset(tmp_path, "wb.ms", n_tiles=6, seed=50)
    # pacing keeps both jobs mid-flight long enough for the
    # controller's rebalance pass to observe the imbalance
    base = _base_config(skyf, clusf, tile_arrival_s=0.25)

    srv = Server(port=0, max_inflight=2, devices=2)
    try:
        srv.start()
        with Client(port=srv.port) as c:
            ja = c.submit(dict(base, ms=msA,
                               solutions_file=str(tmp_path / "wA.txt")))
            jb = c.submit(dict(base, ms=msB,
                               solutions_file=str(tmp_path / "wB.txt")))
            snapA = c.wait(ja, timeout_s=300)
            snapB = c.wait(jb, timeout_s=300)
            assert snapA["state"] == jq.DONE
            assert snapB["state"] == jq.DONE
            # both jobs are bucket-affine to device 0; the steal moved
            # exactly one of them to the idle device 1 at a boundary
            moved = [s for s in (snapA, snapB) if s["migrations"]]
            assert len(moved) == 1, (snapA["migrations"],
                                     snapB["migrations"])
            mig = moved[0]["migrations"][0]
            assert mig["dst_actual"] == 1 and mig["tiles_rerun"] == 0
            assert moved[0]["device"] == 1
            assert moved[0]["tiles_done"] == 6
            m = c.metrics()
            assert m["migrations"] == 1
    finally:
        srv.stop()

    # the stolen job's outputs are bit-identical to a solo run
    for msdir, solf, seed in ((msA, "wA.txt", 11), (msB, "wB.txt", 50)):
        ms2, _, _ = _make_dataset(tmp_path, f"solo_{solf}.ms",
                                  n_tiles=6, seed=seed)
        cfg = config_from_dict(_base_config(
            skyf, clusf, ms=ms2,
            solutions_file=str(tmp_path / f"solo_{solf}")))
        pipeline.run(cfg, log=lambda *a: None)
        for x, y in zip(_corrected(msdir), _corrected(ms2)):
            assert np.array_equal(x, y)
        assert (tmp_path / solf).read_text() \
            == (tmp_path / f"solo_{solf}").read_text()


@pytest.mark.slow
def test_fleet_loadgen_replay_end_to_end(tmp_path):
    """The loadgen drives a live 2-device fleet with a mixed-bucket
    burst spec; every job completes, the replay record carries the
    measured queue-wait percentiles, and per-job outputs are
    bit-identical to solo runs of the same template configs."""
    assert len(jax.devices()) >= 2
    spec = {
        "seed": 21, "n_jobs": 4,
        "arrival": {"process": "burst"},
        "templates": [
            {"name": "a", "n_stations": 8, "tilesz": 4, "n_tiles": 3,
             "nchan": 2, "config": {"max_iter": 4}},
            {"name": "b", "n_stations": 8, "tilesz": 5, "n_tiles": 3,
             "nchan": 2, "config": {"max_iter": 4}}]}
    work = str(tmp_path / "replay")
    fixtures = loadgen.build_fixtures(spec, work)
    srv = Server(port=0, max_inflight=2, devices=2)
    try:
        srv.start()
        with Client(port=srv.port) as c:
            rec = loadgen.replay(c, spec, fixtures, work,
                                 log=lambda *a: None)
    finally:
        srv.stop()
    assert rec["states"] == {"done": rec["n_jobs"]}
    assert rec["throughput_jobs_per_s"] > 0
    assert rec["queue_wait_p99_s"] is not None
    assert rec["queue_wait_p99_s"] >= rec["queue_wait_p50_s"]
    # bit-identity of every replay job vs a solo run of its template
    solo_out = {}
    for name, f in fixtures.items():
        msdir = os.path.join(work, f"solo_{name}.ms")
        import shutil
        shutil.copytree(f["ms"], msdir)
        cfg = loadgen.job_config(spec, name, msdir,
                                 os.path.join(work, f"solo_{name}.sol"))
        cfg.update(sky_model=f["sky"], cluster_file=f["cluster"])
        pipeline.run(config_from_dict(cfg), log=lambda *a: None)
        solo_out[name] = (_corrected(msdir),
                          open(os.path.join(
                              work, f"solo_{name}.sol")).read())
    for row in rec["jobs"]:
        res, sol_text = solo_out[row["template"]]
        for x, y in zip(_corrected(row["ms"]), res):
            assert np.array_equal(x, y)
        assert open(row["solutions"]).read() == sol_text


def test_mesh_span_surfaces_in_fleet_view():
    """ISSUE 14 satellite: an mpi/mesh job stays opaque, but the
    device span of its consensus mesh is no longer invisible — the
    span registry is fed under the job scope (cli_mpi.note_mesh path),
    and the scheduler's metrics list the job under EVERY device its
    mesh covers, plus a metrics-level mesh_spans map. Cleared when the
    job finishes."""
    from jax.sharding import Mesh
    from sagecal_tpu.serve import scheduler as sched_mod

    # outside any job scope: a no-op (solo CLI runs never register)
    mesh2 = Mesh(np.array(jax.devices()[:2]), ("freq",))
    fleet.note_mesh(mesh2)
    assert "j-mesh" not in fleet.mesh_spans()

    with fleet.job_scope("j-mesh"):
        assert fleet.current_job() == "j-mesh"
        fleet.note_mesh(mesh2)
    assert fleet.current_job() is None
    spans = fleet.mesh_spans()
    assert spans["j-mesh"]["devices"] == [str(d) for d in
                                          jax.devices()[:2]]
    assert spans["j-mesh"]["axes"] == ["freq"]

    try:
        q = jq.JobQueue(max_inflight=2, max_staged_bytes=1 << 30)
        sch = sched_mod.Scheduler(
            q, log=lambda *a: None,
            devices=fleet.fleet_devices(2))
        m = sch.metrics()
        assert m["mesh_spans"]["j-mesh"]["shape"] == [2]
        by_dev = {d["device"]: d for d in m["devices"]}
        assert by_dev[0]["mesh_jobs"] == ["j-mesh"]
        assert by_dev[1]["mesh_jobs"] == ["j-mesh"]
    finally:
        fleet.clear_mesh_span("j-mesh")
    assert "j-mesh" not in fleet.mesh_spans()
    # registry empty again: snapshots stop carrying the key (the PR 8
    # metrics surface is unchanged when no mesh job is live)
    m = sch.metrics()
    assert "mesh_spans" not in m
    assert all("mesh_jobs" not in d for d in m["devices"])
