"""sagecal_tpu.diag: trace schema round-trip, roofline cost extraction,
staging bytes-accounting, and the no-retrace guard.

The no-retrace guard is the subsystem's core promise: telemetry-off adds
zero jit compiles (the hooks are no-ops), and telemetry-ON also adds
zero jit compiles (the hooks are host-side emits, never traced code).
"""

import json
import os
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from sagecal_tpu.diag import guard, roofline, trace  # noqa: E402


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    """Every test leaves the module-level tracer disabled."""
    yield
    trace.disable()


# ---------------------------------------------------------------------------
# trace.py
# ---------------------------------------------------------------------------

def test_trace_schema_round_trip(tmp_path):
    path = tmp_path / "run.jsonl"
    trace.enable(str(path), entry="test", argv=["-d", "x"])
    assert trace.active()
    trace.emit("tile", tile=0, res_0=2.5, res_1=1.25, mean_nu=3.0,
               solver_iters=17)
    with trace.phase("solve", tile=0):
        pass
    trace.emit("admm_iter", iter=1, r1_mean=0.5, dual=0.01, rho_mean=5.0)
    trace.disable()
    assert not trace.active()

    recs = trace.read(str(path))
    evs = [r["ev"] for r in recs]
    assert evs == ["run_start", "tile", "phase", "admm_iter", "run_end"]
    for r in recs:                       # required fields on every line
        assert isinstance(r["t"], float) and isinstance(r["ev"], str)
    tile = recs[1]
    assert tile["res_0"] == 2.5 and tile["solver_iters"] == 17
    ph = recs[2]
    assert ph["name"] == "solve" and ph["dur_s"] >= 0.0
    assert recs[-1]["wall_s"] >= 0.0
    # raw file is line-delimited JSON (parseable without the reader)
    for line in path.read_text().splitlines():
        json.loads(line)


def test_trace_noop_when_disabled(tmp_path):
    # module-level emit/phase must be safe (and do nothing) untraced
    trace.emit("tile", tile=0)
    with trace.phase("solve"):
        pass
    assert trace.get() is None


def test_trace_survives_unserializable_field(tmp_path):
    path = tmp_path / "run.jsonl"
    trace.enable(str(path))
    trace.emit("tile", arr=object())     # must not raise
    trace.disable()
    recs = trace.read(str(path))
    assert recs[1]["ev"] == "tile" and isinstance(recs[1]["arr"], str)


def test_trace_read_rejects_malformed(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text('{"t": 1.0, "ev": "x"}\nnot json\n')
    with pytest.raises(ValueError):
        trace.read(str(p))
    p.write_text('{"t": 1.0}\n')         # missing required "ev"
    with pytest.raises(ValueError):
        trace.read(str(p))


# ---------------------------------------------------------------------------
# roofline.py
# ---------------------------------------------------------------------------

def test_program_cost_and_classification():
    dev = jax.devices()[0]
    f = jax.jit(lambda a, b: (a @ b).sum())
    x = jnp.ones((128, 128), jnp.float32)
    cost = roofline.program_cost(f, (x, x))
    assert cost["flops"] > 0 and cost["bytes_accessed"] > 0
    rec = roofline.roofline_fields(cost, 1e-3, dev)
    for k in ("flops", "bytes_accessed", "achieved_gbps",
              "achieved_flops_per_s", "intensity", "bound"):
        assert k in rec, k
        assert rec[k] is not None
    assert rec["bound"] in ("compute", "bandwidth")
    assert np.isfinite(rec["achieved_gbps"]) and rec["achieved_gbps"] > 0

    # an elementwise program is bandwidth-bound, a big matmul is
    # compute-bound — on any device whose ridge sits between ~0.25
    # (copy) and ~n/12 (matmul at n=2048) FLOP/byte
    ew = roofline.lower_cost(lambda a: a + 1.0,
                             jax.ShapeDtypeStruct((1 << 16,), jnp.float32))
    mm = roofline.lower_cost(
        lambda a, b: a @ b,
        jax.ShapeDtypeStruct((2048, 2048), jnp.float32),
        jax.ShapeDtypeStruct((2048, 2048), jnp.float32))
    assert roofline.roofline_fields(ew, 1.0, dev)["bound"] == "bandwidth"
    assert roofline.roofline_fields(mm, 1.0, dev)["bound"] == "compute"


def test_pallas_cost_prices_compiled_kernels_only():
    """A COMPILED pallas_call is priced by the jaxpr walk: from its
    author's ``cost_estimate`` where it has one, else from the sizes of
    its operands and results (``ops/coh_pallas.py`` gives none); an
    interpret-mode call is skipped, because cost_analysis already prices
    its HLO lowering; and ``program_cost`` folds the walk in."""
    from jax.experimental import pallas as pl

    def twice(x, interpret, estimate):
        def kernel(x_ref, o_ref):
            o_ref[...] = 2.0 * x_ref[...]
        return pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=interpret, cost_estimate=estimate)(x)

    x = jnp.ones((8, 128), jnp.float32)
    est = pl.CostEstimate(flops=1024, transcendentals=0,
                          bytes_accessed=12345)
    assert roofline.pallas_cost(lambda a: twice(a, False, est), (x,)) == {
        "flops": 1024.0, "bytes_accessed": 12345.0}
    # no estimate: the operand and the result, each moved once
    assert roofline.pallas_cost(lambda a: twice(a, False, None), (x,)) == {
        "flops": 0.0, "bytes_accessed": 2.0 * 8 * 128 * 4}
    # under a cond's branches too (the tuple-of-jaxprs case)
    both = roofline.pallas_cost(
        lambda a: jax.lax.cond(a[0, 0] > 0, lambda: twice(a, False, est),
                               lambda: a), (x,))
    assert both["bytes_accessed"] == 12345.0
    interp = jax.jit(lambda a: twice(a, True, est))
    assert roofline.pallas_cost(interp, (x,)) == roofline.zero_cost()
    assert roofline.program_cost(interp, (x,))["bytes_accessed"] > 0


def test_cost_algebra():
    a = {"flops": 2.0, "bytes_accessed": 10.0}
    b = {"flops": 3.0, "bytes_accessed": 5.0}
    c = roofline.combine(a, None, b)
    assert c == {"flops": 5.0, "bytes_accessed": 15.0}
    assert roofline.scale(a, 3) == {"flops": 6.0, "bytes_accessed": 30.0}
    assert roofline.scale(None, 3) is None


def test_device_peaks_table():
    class FakeDev:
        platform = "tpu"
        device_kind = "TPU v5p"
    pf, pb, nominal = roofline.device_peaks(FakeDev())
    assert pf == 459e12 and pb == 2765e9 and not nominal
    # the CPU fallback is nominal but present (classify() must have
    # peaks to divide by on the CPU too)
    pf, pb, nominal = roofline.device_peaks(jax.devices()[0])
    if jax.devices()[0].platform == "cpu":
        assert nominal and pf and pb
    assert roofline.nbytes_of({"a": np.zeros((4, 2), np.float64),
                               "b": np.zeros(3, np.float32)}) == 76


# ---------------------------------------------------------------------------
# guard.py: the no-retrace contract
# ---------------------------------------------------------------------------

def _tiny_solve(tmp_trace=None):
    """One host-driven SAGE solve (the jitted hot path the tracer hooks
    into), optionally traced."""
    from sagecal_tpu.config import SolverMode
    from sagecal_tpu.solvers import sage

    if tmp_trace is not None:
        trace.enable(str(tmp_trace))
    try:
        rng = np.random.default_rng(3)
        N, M, K, tsz = 5, 2, 1, 4
        pairs = [(i, j) for i in range(N) for j in range(i + 1, N)]
        B = len(pairs) * tsz
        sta1 = jnp.asarray(np.tile([p[0] for p in pairs], tsz), jnp.int32)
        sta2 = jnp.asarray(np.tile([p[1] for p in pairs], tsz), jnp.int32)
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
        jax.block_until_ready(J)
        return float(info["res_1"])
    finally:
        if tmp_trace is not None:
            trace.disable()


def test_no_retrace_with_diag_on(tmp_path):
    """jit compile counts must be IDENTICAL across diag off / on / off
    for the same workload — the telemetry hooks live outside every
    traced program."""
    # absorb cold compiles AND the execution-plan learning: run 1
    # learns the sweep-fusion verdict, run 2 compiles the fused sweep
    # program; from run 3 the per-shape program set is steady
    _tiny_solve()
    _tiny_solve()
    with guard.CompileGuard() as g_off:
        _tiny_solve()
    with guard.CompileGuard() as g_on:
        _tiny_solve(tmp_trace=tmp_path / "t.jsonl")
    with guard.CompileGuard() as g_off2:
        _tiny_solve()
    assert g_on.compiles == g_off.compiles == g_off2.compiles, (
        g_off.compiles, g_on.compiles, g_off2.compiles)
    # and the traced run actually produced convergence records
    recs = trace.read(str(tmp_path / "t.jsonl"))
    assert any(r["ev"] == "em_sweep" for r in recs)
    sweep = next(r for r in recs if r["ev"] == "em_sweep")
    assert sweep["solver_iters"] > 0 and sweep["wall_s"] >= 0


def test_compile_guard_counts_compiles():
    guard.install()
    c0 = guard.compile_count()
    f = jax.jit(lambda a: a * 3 + 1)
    f(jnp.ones((7,))).block_until_ready()        # new program: compiles
    assert guard.compile_count() > c0
    c1 = guard.compile_count()
    f(jnp.ones((7,))).block_until_ready()        # cached: no compile
    assert guard.compile_count() == c1


# ---------------------------------------------------------------------------
# end-to-end: CLI --diag produces a parseable convergence trace
# ---------------------------------------------------------------------------

def _make_sim_dataset(tmp_path, n_stations=6, tilesz=4, n_tiles=2):
    import math

    from sagecal_tpu.io import dataset as ds
    from sagecal_tpu.rime import predict as rp
    from sagecal_tpu import skymodel

    sky_file = tmp_path / "sky.txt"
    sky_file.write_text(
        "P0A 0 40 0 40 0 0 3.0 0 0 0 0 0 0 0 0 150e6\n")
    (tmp_path / "sky.txt.cluster").write_text("0 1 P0A\n")
    ra0 = (41 / 60) * math.pi / 12
    dec0 = 40 * math.pi / 180
    srcs = skymodel.parse_sky_model(str(sky_file), ra0, dec0, 150e6)
    sky = skymodel.build_cluster_sky(
        srcs,
        skymodel.parse_cluster_file(str(tmp_path / "sky.txt.cluster")))
    dsky = rp.sky_to_device(sky, jnp.float64)
    Jt = ds.random_jones(1, sky.nchunk, n_stations, seed=5, scale=0.1)
    tiles = [ds.simulate_dataset(dsky, n_stations=n_stations,
                                 tilesz=tilesz, freqs=np.array([150e6]),
                                 ra0=ra0, dec0=dec0, jones=Jt,
                                 nchunk=sky.nchunk, noise_sigma=0.01,
                                 seed=11 + t)
             for t in range(n_tiles)]
    msdir = tmp_path / "sim.ms"
    ds.SimMS.create(str(msdir), tiles)
    return msdir, sky_file


def test_cli_diag_trace_end_to_end(tmp_path):
    from sagecal_tpu import cli

    msdir, sky_file = _make_sim_dataset(tmp_path)
    tr = tmp_path / "diag.jsonl"
    rc = cli.main([
        "-d", str(msdir), "-s", str(sky_file),
        "-c", str(sky_file) + ".cluster",
        "-e", "2", "-g", "3", "-l", "2", "-j", "1", "-B", "0",
        "--diag", str(tr)])
    assert rc == 0
    recs = trace.read(str(tr))
    evs = {r["ev"] for r in recs}
    assert recs[0]["ev"] == "run_start"
    assert recs[-1]["ev"] == "run_end"
    # per-iteration convergence records + phase timers made it out
    assert "em_sweep" in evs and "tile" in evs and "phase" in evs
    tiles = [r for r in recs if r["ev"] == "tile"]
    assert len(tiles) == 2
    for r in tiles:
        assert np.isfinite(r["res_0"]) and np.isfinite(r["res_1"])
        assert r["res_1"] <= r["res_0"]
    phases = {r["name"] for r in recs if r["ev"] == "phase"}
    assert {"io", "stage", "solve", "residual", "write"} <= phases
    # tracer is closed and uninstalled after main()
    assert not trace.active()


def test_diag_overlap_attribution(tmp_path):
    """Sync-vs-async io attribution (ISSUE 5): under --prefetch N>0
    the "io" phase records the host WAIT for the next tile (the
    bubble) while the background thread's read time is emitted as a
    ``bg``-tagged record, and tile records carry the bubble_s/overlap
    accounting pair; under --prefetch 0 there are no bg records and
    overlap is 0. ONE pipeline serves both runs (compile once); the
    CLI plumbing of --prefetch/--diag is covered by
    test_cli_diag_trace_end_to_end."""
    from sagecal_tpu import cli, pipeline, skymodel
    from sagecal_tpu.io import dataset as ds

    msdir, sky_file = _make_sim_dataset(tmp_path)
    args = cli.build_parser().parse_args([
        "-d", str(msdir), "-s", str(sky_file),
        "-c", str(sky_file) + ".cluster",
        "-e", "1", "-g", "3", "-l", "2", "-j", "1", "-B", "0"])
    cfg = cli.config_from_args(args)
    ms = ds.SimMS(str(msdir))
    sky = skymodel.read_sky_cluster(
        str(sky_file), str(sky_file) + ".cluster", ms.meta["ra0"],
        ms.meta["dec0"], ms.meta["freq0"])
    pipe = pipeline.FullBatchPipeline(cfg, ms, sky, log=lambda *a: None)

    def run(depth, path):
        trace.enable(str(path))
        try:
            pipe.run(prefetch=depth, log=lambda *a: None)
        finally:
            trace.disable()

    tr_async = tmp_path / "async.jsonl"
    run(1, tr_async)
    recs = trace.read(str(tr_async))
    tiles = [r for r in recs if r["ev"] == "tile"]
    assert tiles and all(r["overlap"] == 1 for r in tiles)
    assert all(r["bubble_s"] >= 0.0 for r in tiles)
    # the background thread's read + stage time is bg-tagged...
    bg = [r for r in recs if r["ev"] == "phase" and r.get("bg")]
    assert {"read", "stage"} <= {r["name"] for r in bg}
    # ...and the consumer-side io phase (the wait) is NOT bg
    ios = [r for r in recs if r["ev"] == "phase" and r["name"] == "io"]
    assert ios and not any(r.get("bg") for r in ios)

    tr_sync = tmp_path / "sync.jsonl"
    run(0, tr_sync)
    recs = trace.read(str(tr_sync))
    tiles = [r for r in recs if r["ev"] == "tile"]
    assert tiles and all(r["overlap"] == 0 for r in tiles)
    assert not any(r.get("bg") for r in recs)
    # sync io phase = the inline read+stage (production) time; the
    # stage phase exists un-tagged
    phases = {r["name"] for r in recs if r["ev"] == "phase"}
    assert {"io", "stage", "solve", "residual", "write"} <= phases

    # overlap_stats classifies both traces
    st = trace.overlap_stats(trace.read(str(tr_async)))
    assert st["tiles"] == 2 and st["overlap"] == 1
    assert st["wall_s"] > 0 and 0.0 <= st["busy_frac"] <= 1.5
    st0 = trace.overlap_stats(trace.read(str(tr_sync)))
    assert st0["overlap"] == 0 and st0["bubble_s"] >= 0.0


def test_diag_arrival_wait_split_from_io_bubble(tmp_path):
    """ISSUE 16 satellite: time spent waiting for a tile to ARRIVE
    (ingest pacing / a live stream transport) is emitted as the
    ``arrival_wait`` phase — the producer's wall wait bg-tagged, the
    consumer's overlapping block un-tagged — and ``overlap_stats``
    reports it as ``arrival_wait_s``, excluded from BOTH busy and
    bubble (a tenant's data rate is not a pipeline stall)."""
    from sagecal_tpu import sched

    tr = tmp_path / "arrival.jsonl"
    trace.enable(str(tr))
    try:
        pf = sched.Prefetcher(lambda i: i * 2, 3, depth=1, pace_s=0.03)
        assert [x for _, x, _ in pf] == [0, 2, 4]
    finally:
        trace.disable()
    recs = trace.read(str(tr))
    arr = [r for r in recs if r["ev"] == "phase"
           and r["name"] == "arrival_wait"]
    assert arr, "paced production emitted no arrival_wait phase"
    # the producer thread's true wall wait is bg-tagged (tiles 1, 2
    # each paced 30 ms behind the previous)
    bg_wait = sum(r["dur_s"] for r in arr if r.get("bg"))
    assert bg_wait >= 0.04
    st = trace.overlap_stats(recs)
    assert st["arrival_wait_s"] > 0.0
    # split OUT of the io bubble: nothing here blocked on data
    # movement, so the arrival wait must not surface as bubble/busy.
    # The Prefetcher emits the consumer's "io" phase itself (ISSUE 26):
    # what is left of each block once the arrival wait is carved out
    # (the producer's lambda and the thread hand-off)
    ios = [r for r in recs if r["ev"] == "phase" and r["name"] == "io"]
    assert [r["tile"] for r in ios] == [0, 1, 2]
    assert st["bubble_s"] < 0.5 * st["arrival_wait_s"]
    assert st["busy_s"] == 0.0


def test_overlap_stats_math():
    recs = [
        {"t": 0.0, "ev": "run_start"},
        {"t": 0.1, "ev": "phase", "name": "read", "dur_s": 5.0,
         "bg": True},
        {"t": 0.2, "ev": "phase", "name": "io", "dur_s": 0.25},
        {"t": 0.3, "ev": "phase", "name": "solve", "dur_s": 6.0},
        {"t": 0.4, "ev": "phase", "name": "residual", "dur_s": 1.0},
        {"t": 0.5, "ev": "tile", "tile": 0, "res_0": 1.0, "res_1": 0.5,
         "bubble_s": 0.5, "overlap": 2},
        {"t": 0.6, "ev": "run_end", "wall_s": 10.0},
    ]
    st = trace.overlap_stats(recs)
    assert st["tiles"] == 1 and st["overlap"] == 2
    assert st["wall_s"] == 10.0
    assert st["busy_s"] == 7.0          # solve + residual, bg excluded
    assert st["bubble_s"] == 0.5        # tile bubble_s wins over io sum
    assert st["busy_frac"] == 0.7 and st["bubble_frac"] == 0.05
    # sync attribution: no bubble_s on tiles -> io + write phases
    recs2 = [r.copy() for r in recs]
    del recs2[5]["bubble_s"]
    recs2.insert(5, {"t": 0.45, "ev": "phase", "name": "write",
                     "dur_s": 0.75})
    st2 = trace.overlap_stats(recs2)
    assert st2["bubble_s"] == 1.0       # io 0.25 + write 0.75


# ---------------------------------------------------------------------------
# scope-stack thread-locality + per-job obs attribution (ISSUE 9 sat. 2)
# ---------------------------------------------------------------------------

def test_scope_stacks_strictly_thread_local(tmp_path):
    """The metrics-era contract pinned in trace.py: a dtrace.scope
    entered on one thread changes NOTHING about any other thread's
    routing — not the main thread's, and not a thread spawned WHILE
    the scope is live (threading.local starts empty per thread)."""
    import threading

    trace.enable(str(tmp_path / "proc.jsonl"))
    trA = trace.Tracer(str(tmp_path / "a.jsonl"))
    trB = trace.Tracer(str(tmp_path / "b.jsonl"))
    inner_tracer = []
    barrier = threading.Barrier(2, timeout=10)

    def worker(tr, name):
        with trace.scope(tr):
            barrier.wait()        # both scopes live simultaneously
            trace.emit("tile", tile=0, who=name)
            if name == "a":
                # a thread spawned inside a live scope must NOT
                # inherit it: it sees the process tracer
                t = threading.Thread(
                    target=lambda: inner_tracer.append(trace.get()))
                t.start()
                t.join()

    ths = [threading.Thread(target=worker, args=(trA, "a")),
           threading.Thread(target=worker, args=(trB, "b"))]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    # the main thread never saw a scope
    assert trace.get() is not None and trace.get().path.endswith(
        "proc.jsonl")
    trace.emit("tile", tile=0, who="main")
    trA.close()
    trB.close()
    trace.disable()

    for path, who in ((tmp_path / "a.jsonl", "a"),
                      (tmp_path / "b.jsonl", "b"),
                      (tmp_path / "proc.jsonl", "main")):
        tiles = [r for r in trace.read(str(path)) if r["ev"] == "tile"]
        assert [r["who"] for r in tiles] == [who], (path, tiles)
    # the spawned-inside-a-scope thread resolved the PROCESS tracer
    assert len(inner_tracer) == 1
    assert inner_tracer[0].path.endswith("proc.jsonl")


def test_obs_emission_in_scoped_thread_attributes_to_job(tmp_path):
    """obs metric emission inside a job-scoped thread attributes to
    the owning job (scope_labels keeps the same thread-local stack
    semantics as dtrace.scope); the serve scheduler's ONE context
    factory (job_telemetry_ctx) installs both scopes together."""
    import threading

    from sagecal_tpu.obs import metrics as ometrics
    from sagecal_tpu.serve.scheduler import job_telemetry_ctx

    reg = ometrics.enable()
    try:
        trA = trace.Tracer(str(tmp_path / "ja.jsonl"))
        ctxA = job_telemetry_ctx(trA, "job-a")
        ctxB = job_telemetry_ctx(None, "job-b")
        barrier = threading.Barrier(2, timeout=10)

        def worker(ctx, n):
            with ctx():
                barrier.wait()
                for _ in range(n):
                    ometrics.inc("tiles_solved_total")
                trace.emit("tile", tile=0)

        ths = [threading.Thread(target=worker, args=(ctxA, 2)),
               threading.Thread(target=worker, args=(ctxB, 3))]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        # unscoped main-thread emission: no job label
        ometrics.inc("tiles_solved_total")
        c = reg.get("tiles_solved_total")
        assert c.value(job="job-a") == 2.0
        assert c.value(job="job-b") == 3.0
        assert c.value() == 1.0
        # and the trace records went ONLY to job A's tracer (job B has
        # none; the process tracer is off in this test)
        trA.close()
        tiles = [r for r in trace.read(str(tmp_path / "ja.jsonl"))
                 if r["ev"] == "tile"]
        assert len(tiles) == 1
    finally:
        ometrics.disable()


def test_cli_legacy_flag_warning(capsys):
    from sagecal_tpu import cli

    p = cli.build_parser()
    args = p.parse_args(["-d", "x", "-s", "s", "-c", "c", "-y", "1",
                         "-o", "2.0"])
    warnings = cli.warn_legacy_flags(args, err=sys.stderr)
    assert len(warnings) == 2
    err = capsys.readouterr().err
    assert "uvmax" in err and "mmse" in err.lower()
    # sane values warn about nothing
    args = p.parse_args(["-d", "x", "-s", "s", "-c", "c"])
    assert cli.warn_legacy_flags(args, err=sys.stderr) == []
