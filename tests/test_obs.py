"""sagecal_tpu.obs gates (ISSUE 9): the metrics registry's no-op /
thread-safety / percentile contracts, Prometheus exposition, the
convergence-health state machine, and the acceptance gate: metrics OFF
is bit-identical with zero added compiles (retrace-guard gated).
"""

import json
import os
import sys
import threading

import numpy as np
import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from sagecal_tpu.obs import export as oexport  # noqa: E402
from sagecal_tpu.obs import health as ohealth  # noqa: E402
from sagecal_tpu.obs import metrics as omet  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_leaked_registry():
    """Every test leaves the module-level registry disabled."""
    yield
    omet.disable()


# ---------------------------------------------------------------------------
# metrics.py: registry units
# ---------------------------------------------------------------------------

def test_noop_when_disabled_and_enable_idempotent():
    assert not omet.active() and omet.get() is None
    # module helpers must be safe (and do nothing) when disabled
    omet.inc("c", 2)
    omet.set_gauge("g", 1.5)
    omet.observe("h", 0.25)
    assert omet.get() is None
    r1 = omet.enable()
    r2 = omet.enable()
    assert r1 is r2 and omet.active()
    omet.inc("c", 2)
    assert r1.get("c").value() == 2.0
    omet.disable()
    assert not omet.active()
    omet.inc("c", 5)                     # back to no-op, no resurrect
    assert omet.get() is None


def test_counter_gauge_histogram_basics():
    reg = omet.enable()
    omet.inc("jobs", 1, state="done")
    omet.inc("jobs", 2, state="done")
    omet.inc("jobs", 1, state="failed")
    assert reg.get("jobs").value(state="done") == 3.0
    assert reg.get("jobs").value(state="failed") == 1.0
    with pytest.raises(ValueError):
        reg.get("jobs")._inc({}, -1)     # counters only go up

    omet.set_gauge("depth", 4)
    omet.set_gauge("depth", 2)
    assert reg.get("depth").value() == 2.0

    h = reg.histogram("lat", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0):
        omet.observe("lat", v)
    st = h.stats()
    assert st["count"] == 4 and st["sum"] == pytest.approx(6.05)
    # p50 falls in the (0.1, 1.0] bucket, interpolated
    assert 0.1 < st["p50"] <= 1.0
    assert 1.0 < st["p99"] <= 10.0
    # +Inf bucket clamps to the last finite edge
    omet.observe("lat", 1e6)
    assert h.percentile(1.0) == 10.0
    # declared kind is sticky
    with pytest.raises(TypeError):
        reg.counter("lat")
    with pytest.raises(ValueError):
        reg.histogram("bad", buckets=(1.0, 1.0))


def test_histogram_percentile_empty_and_single():
    reg = omet.enable()
    h = reg.histogram("x", buckets=(1.0, 2.0, 4.0))
    assert h.percentile(0.5) is None
    assert h.stats()["p50"] is None
    omet.observe("x", 1.5)
    assert 1.0 < h.percentile(0.5) <= 2.0


def test_scope_labels_thread_local_and_overflow_fold():
    reg = omet.enable()
    seen = []

    def worker(job, n):
        with omet.scope_labels(job=job):
            for _ in range(n):
                omet.inc("tiles")
            seen.append(omet.get().get("tiles").value(job=job))

    ths = [threading.Thread(target=worker, args=("a", 2)),
           threading.Thread(target=worker, args=("b", 3))]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    omet.inc("tiles")                    # unscoped: no label
    c = reg.get("tiles")
    assert c.value(job="a") == 2.0 and c.value(job="b") == 3.0
    assert c.value() == 1.0
    # explicit labels win over the scope (innermost merge)
    with omet.scope_labels(job="a"):
        omet.inc("tiles", job="z")
    assert c.value(job="z") == 1.0 and c.value(job="a") == 2.0

    # cardinality bound: past max_series, labelsets fold to _overflow
    m = reg.counter("fold")
    m.max_series = 2
    for i in range(5):
        omet.inc("fold", job=f"j{i}")
    assert m.value(job="j0") == 1.0 and m.value(job="j1") == 1.0
    assert m.value(job="_overflow") == 3.0   # nothing dropped


def test_registry_thread_safety_totals():
    reg = omet.enable()

    def spin():
        for _ in range(500):
            omet.inc("n")
            omet.observe("d", 0.01)

    ths = [threading.Thread(target=spin) for _ in range(8)]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    assert reg.get("n").value() == 4000.0
    assert reg.get("d").stats()["count"] == 4000


def test_dump_shape():
    reg = omet.enable()
    omet.inc("c", 2, state="done")
    omet.observe("h", 0.3)
    d = reg.dump()
    assert d["c"]["type"] == "counter"
    assert d["c"]["series"]["state=done"] == 2.0
    hs = d["h"]["series"][""]
    assert hs["count"] == 1 and "p50" in hs and "buckets" in hs
    json.dumps(d)                        # JSON-serializable, whole


# ---------------------------------------------------------------------------
# export.py: Prometheus text + HTTP endpoint
# ---------------------------------------------------------------------------

def test_prometheus_rendering_golden():
    reg = omet.enable()
    omet.inc("serve_jobs_total", 2, state="done")
    omet.set_gauge("depth", 3)
    reg.histogram("lat", buckets=(0.1, 1.0))
    omet.observe("lat", 0.05)
    omet.observe("lat", 0.5)
    text = oexport.render_prometheus(reg)
    assert "# TYPE sagecal_serve_jobs_total counter" in text
    assert 'sagecal_serve_jobs_total{state="done"} 2' in text
    assert "# TYPE sagecal_depth gauge" in text
    assert "sagecal_depth 3" in text
    # histogram: CUMULATIVE buckets + sum/count
    assert 'sagecal_lat_bucket{le="0.1"} 1' in text
    assert 'sagecal_lat_bucket{le="1"} 2' in text
    assert 'sagecal_lat_bucket{le="+Inf"} 2' in text
    assert "sagecal_lat_sum 0.55" in text
    assert "sagecal_lat_count 2" in text


def test_obs_http_endpoint_metrics_and_healthz():
    import http.client

    reg = omet.enable()
    omet.inc("up", 1)
    health = {"status": "ok", "queued": 0}
    srv = oexport.ObsHTTPServer(
        0, lambda: oexport.render_prometheus(reg), lambda: dict(health))
    try:
        def get(path):
            conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                              timeout=10)
            conn.request("GET", path)
            r = conn.getresponse()
            body = r.read().decode()
            conn.close()
            return r.status, body

        code, body = get("/metrics")
        assert code == 200 and "sagecal_up 1" in body
        code, body = get("/healthz")
        assert code == 200 and json.loads(body)["status"] == "ok"
        health["status"] = "degraded"    # degraded -> 503, the LB shape
        code, body = get("/healthz")
        assert code == 503 and json.loads(body)["status"] == "degraded"
        code, _ = get("/nope")
        assert code == 404
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# health.py: the stall/divergence state machine
# ---------------------------------------------------------------------------

def test_health_states():
    h = ohealth.ConvergenceHealth(patience=3, min_improvement=1e-3)
    assert h.update(10.0) == "ok"            # watermark seeds
    assert h.update(8.0) == "ok"             # improving
    assert h.update(8.0) == "ok"             # stale 1
    assert h.update(8.0) == "ok"             # stale 2
    assert h.update(8.0) == "stalled"        # patience hit
    assert h.update(4.0) == "ok"             # recovery resets
    assert h.stale == 0 and h.best == 4.0
    # divergence: ratio vs the WATERMARK, the pipeline's RES_RATIO idiom
    assert h.update(4.0 * 5.0 + 1) == "diverging"
    # non-finite is immediately diverging, watermark untouched
    h2 = ohealth.ConvergenceHealth()
    assert h2.update(float("nan")) == "diverging"
    assert h2.update(1.0) == "ok"            # a finite residual recovers
    h3 = ohealth.ConvergenceHealth()
    assert h3.update(float("inf")) == "diverging"
    # res == 0.0 (fully flagged data) neither progresses nor diverges
    h4 = ohealth.ConvergenceHealth(patience=2)
    h4.update(2.0)
    assert h4.update(0.0) == "ok" and h4.best == 2.0
    snap = h4.snapshot()
    assert snap["state"] == "ok" and snap["observations"] == 2
    json.dumps(snap)


def test_health_replay_from_trace_records():
    recs = [{"ev": "tile", "t": float(i), "res_1": 5.0}
            for i in range(5)]
    recs.insert(0, {"ev": "run_start", "t": -1.0})
    h = ohealth.health_of_records(recs, patience=3)
    assert h.state == "stalled" and h.n == 5


# ---------------------------------------------------------------------------
# lm.executed_trips: one definition of "trips" for all readouts
# ---------------------------------------------------------------------------

def test_executed_trips():
    from sagecal_tpu.solvers import lm as lm_mod
    info = {"solver_iters": jnp.asarray([3, 4]),
            "cg_iters": np.asarray([0, 2]),
            "lbfgs_iters": 5, "res_0": 1.0}
    trips = lm_mod.executed_trips(info)
    assert trips == {"solver_iters": 7, "cg_iters": 2,
                     "lbfgs_iters": 5}
    assert lm_mod.executed_trips(None) == {}
    assert lm_mod.executed_trips({"res_0": 1.0}) == {}


# ---------------------------------------------------------------------------
# the acceptance gate: metrics OFF = bit-identical + zero added
# compiles; metrics ON = zero added compiles AND populated registry
# ---------------------------------------------------------------------------

def _tiny_solve():
    """One host-driven SAGE solve (the instrumented hot path), small
    enough for the retrace gate; returns the solution bytes."""
    from sagecal_tpu.config import SolverMode
    from sagecal_tpu.solvers import sage

    rng = np.random.default_rng(3)
    N, M, K, tsz = 5, 2, 1, 4
    pairs = [(i, j) for i in range(N) for j in range(i + 1, N)]
    sta1 = jnp.asarray(np.tile([p[0] for p in pairs], tsz), jnp.int32)
    sta2 = jnp.asarray(np.tile([p[1] for p in pairs], tsz), jnp.int32)
    B = len(pairs) * tsz
    coh = jnp.asarray(rng.normal(size=(M, B, 2, 2))
                      + 1j * rng.normal(size=(M, B, 2, 2)))
    cidx = jnp.zeros((M, B), jnp.int32)
    cmask = jnp.ones((M, K), bool)
    J0 = jnp.asarray(np.tile(np.eye(2, dtype=np.complex128),
                             (M, K, N, 1, 1)))
    x8 = sage.full_model8(J0, coh, sta1, sta2, cidx)
    wt = jnp.ones((B, 8), jnp.float64)
    cfg = sage.SageConfig(max_emiter=1, max_iter=2, max_lbfgs=2,
                          solver_mode=int(SolverMode.OSLM_LBFGS),
                          promote="off")
    J, info = sage.sagefit_host(x8, coh, sta1, sta2, cidx, cmask, J0,
                                N, wt, config=cfg)
    return np.asarray(jax.block_until_ready(J))


def test_metrics_bit_identity_and_zero_added_compiles():
    """Metrics off -> on -> off around an identical solve: compile
    counts IDENTICAL (the emits live outside every traced program —
    the test_diag.py diag contract, extended to obs) and the solution
    bit-identical; the enabled run actually populated the registry
    (per-sweep latency histogram + sweep counter)."""
    from sagecal_tpu.diag import guard

    # absorb cold compiles AND the fuse-plan learning (run 1 learns,
    # run 2 compiles the fused sweep; steady from run 3 — see
    # test_diag.test_no_retrace_with_diag_on)
    _tiny_solve()
    J_ref = _tiny_solve()
    with guard.CompileGuard() as g_off:
        J_off = _tiny_solve()
    reg = omet.enable()
    try:
        with guard.CompileGuard() as g_on:
            J_on = _tiny_solve()
        assert reg.get("solver_sweeps_total").value() > 0
        assert reg.get("em_sweep_seconds").stats()["count"] > 0
        assert reg.get("solver_solver_iters_total") is None  # pipeline-only
    finally:
        omet.disable()
    with guard.CompileGuard() as g_off2:
        J_off2 = _tiny_solve()
    assert g_on.compiles == g_off.compiles == g_off2.compiles, (
        g_off.compiles, g_on.compiles, g_off2.compiles)
    for J in (J_off, J_on, J_off2):
        assert np.array_equal(J, J_ref)


def test_obs_emission_zero_retrace(retrace_guard):
    """The registry's own promise under the retrace_guard fixture: a
    jitted hot loop with LIVE obs emission per step re-runs with ZERO
    compile requests — emission is host-side by construction and can
    never leak a trace dependency."""
    f = jax.jit(lambda a: (a * 2 + 1).sum())
    omet.enable()
    try:
        def thunk():
            out = f(jnp.ones((128,)))
            if omet.active():
                omet.observe("step_seconds", 1e-3)
                omet.inc("steps_total")
                omet.set_gauge("last_sum", float(np.asarray(out)))
            return out

        retrace_guard(thunk)
        assert omet.get().get("steps_total").value() >= 2
    finally:
        omet.disable()
