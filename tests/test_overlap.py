"""Overlapped-execution gates (sagecal_tpu.sched + --prefetch).

The contract under test (MIGRATION.md "Overlapped execution"):

- ``--prefetch N`` is BIT-INVISIBLE: solutions written to the
  solutions file AND residuals written back to the dataset are
  bit-identical between the synchronous reference loop (0) and the
  overlapped loop (N>0), across the solo, tile-batch T>1, beam, and
  minibatch paths — only data movement overlaps, the warm-start solve
  chain stays sequential;
- a failing asynchronous MS/solutions write FAILS the run at the next
  tile boundary with the original traceback, never swallowed;
- the sched primitives themselves: ordered production/writes,
  exception propagation, bounded depth.
"""

import math
import os
import sys
import threading
import time

import numpy as np
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from sagecal_tpu import cli, pipeline, sched, skymodel, stochastic  # noqa: E402
from sagecal_tpu.io import dataset as ds  # noqa: E402
from sagecal_tpu.rime import predict as rp  # noqa: E402


# ---------------------------------------------------------------------------
# sched primitives
# ---------------------------------------------------------------------------

def test_sched_prefetcher_orders_and_waits():
    seen_threads = set()

    def produce(i):
        seen_threads.add(threading.current_thread().name)
        return i * 10

    out = list(sched.Prefetcher(produce, 5, depth=2))
    assert [(i, v) for i, v, _ in out] == [(i, i * 10) for i in range(5)]
    assert all(w >= 0.0 for _, _, w in out)
    assert all("prefetch" in t for t in seen_threads)
    # depth 0: inline, same items, produced on THIS thread
    seen_threads.clear()
    out = list(sched.Prefetcher(produce, 3, depth=0))
    assert [(i, v) for i, v, _ in out] == [(i, i * 10) for i in range(3)]
    assert seen_threads == {threading.current_thread().name}


def test_sched_prefetcher_hides_the_producer_behind_the_consumer():
    """A sleep-shaped stream (8 items, 30 ms to produce, 30 ms to
    consume) at depth 2 runs well under the same stream at depth 0,
    the synchronous path, measured moments apart on the same host so
    that load stretches both alike; and the consumer's waits (the
    bubble a tile record books) shrink from the whole production time
    to less than half of it."""
    n, dt = 8, 0.03

    def produce(i):
        time.sleep(dt)
        return i

    def run(depth):
        t0 = time.perf_counter()
        bubble = 0.0
        for _i, _item, w in sched.Prefetcher(produce, n, depth=depth,
                                             name="overlap-test"):
            bubble += w
            time.sleep(dt)
        return time.perf_counter() - t0, bubble

    serial, serial_bubble = run(0)
    wall, bubble = run(2)
    assert serial_bubble >= n * dt
    assert wall < 0.9 * serial
    assert bubble < 0.5 * serial_bubble


def test_sched_prefetcher_propagates_producer_error():
    def produce(i):
        if i == 2:
            raise ValueError("injected read failure")
        return i

    it = iter(sched.Prefetcher(produce, 5, depth=1))
    assert next(it)[0] == 0
    assert next(it)[0] == 1
    with pytest.raises(ValueError, match="injected read failure"):
        for _ in it:
            pass


def test_sched_asyncwriter_ordered_and_failfast():
    done = []
    aw = sched.AsyncWriter(enabled=True, maxsize=2)
    for k in range(6):
        aw.submit(done.append, k)
    aw.drain()
    assert done == list(range(6))       # strict submission order

    def boom():
        raise RuntimeError("injected write failure")

    aw.submit(boom)
    aw.submit(done.append, 99)          # must never run after a failure
    with pytest.raises(RuntimeError, match="injected write failure") as ei:
        aw.drain()
    # the original traceback (the failing job's frame) is preserved
    import traceback
    assert "boom" in "".join(traceback.format_tb(ei.value.__traceback__))
    assert 99 not in done
    aw.close(raise_pending=False)

    # disabled: inline execution, exceptions surface at the call site
    aw = sched.AsyncWriter(enabled=False)
    with pytest.raises(RuntimeError, match="injected write failure"):
        aw.submit(boom)
    aw.close()


# ---------------------------------------------------------------------------
# end-to-end bit-identity, sync vs async
# ---------------------------------------------------------------------------

SKY = """\
P0A 0 40 0 40 0 0 3.0 0 0 0 0 0 0 0 0 150e6
P1A 1 20 0 38 0 0 2.5 0 0 0 0 0 0 0 0 150e6
"""

CLUSTER = """\
0 1 P0A
1 2 P1A
"""


def _make_dataset(tmp_path, n_tiles=3, n_stations=8, tilesz=4, nchan=2):
    sky_path = tmp_path / "sky.txt"
    sky_path.write_text(SKY)
    clus_path = tmp_path / "sky.txt.cluster"
    clus_path.write_text(CLUSTER)
    ra0 = (41 / 60) * math.pi / 12
    dec0 = 40 * math.pi / 180
    srcs = skymodel.parse_sky_model(str(sky_path), ra0, dec0, 150e6)
    sky = skymodel.build_cluster_sky(
        srcs, skymodel.parse_cluster_file(str(clus_path)))
    dsky = rp.sky_to_device(sky, jnp.float64)
    Jt = ds.random_jones(sky.n_clusters, sky.nchunk, n_stations, seed=5,
                         scale=0.15)
    freqs = np.linspace(149e6, 151e6, nchan)
    tiles = [ds.simulate_dataset(dsky, n_stations=n_stations,
                                 tilesz=tilesz, freqs=freqs, ra0=ra0,
                                 dec0=dec0, jones=Jt, nchunk=sky.nchunk,
                                 noise_sigma=0.02, seed=11 + t)
             for t in range(n_tiles)]
    msdir = tmp_path / "sim.ms"
    ds.SimMS.create(str(msdir), tiles)
    return str(msdir), str(sky_path), str(clus_path)


def _cfg(msdir, sky_path, clus_path, extra=()):
    args = cli.build_parser().parse_args([
        "-d", msdir, "-s", sky_path, "-c", clus_path,
        "-j", "0", "-e", "1", "-g", "4", "-l", "2", "-t", "4",
        *extra])
    return cli.config_from_args(args)


def _corrected(msdir, n_tiles):
    ms = ds.SimMS(msdir, data_column="CORRECTED_DATA")
    return [ms.read_tile(i).x.copy() for i in range(n_tiles)]


def _assert_bitident(msdir, n_tiles, tmp_path, run, tag=""):
    """Run ``run(prefetch, sol_path)`` at depth 0 then 2; assert the
    written residual tiles AND solutions files are bit-identical."""
    sol0 = str(tmp_path / f"sol0{tag}.txt")
    sol1 = str(tmp_path / f"sol1{tag}.txt")
    h0 = run(0, sol0)
    res0 = _corrected(msdir, n_tiles)
    h1 = run(2, sol1)
    res1 = _corrected(msdir, n_tiles)
    for a, b in zip(res0, res1):
        assert np.array_equal(a, b)     # bit-identical residuals
    with open(sol0) as f0, open(sol1) as f1:
        assert f0.read() == f1.read()   # bit-identical solutions
    for a, b in zip(h0, h1):
        assert a["res_0"] == b["res_0"] and a["res_1"] == b["res_1"]
    return h0


@pytest.mark.slow  # ~77 s (round-17 tier-1 rebalance — full-suite
# CI lane; the beam-path bit-identity variant below stays in-window)
def test_bitident_solo(tmp_path):
    msdir, skyf, clusf = _make_dataset(tmp_path)
    cfg = _cfg(msdir, skyf, clusf)
    ms = ds.SimMS(msdir)
    sky = skymodel.read_sky_cluster(skyf, clusf, ms.meta["ra0"],
                                    ms.meta["dec0"], ms.meta["freq0"])
    pipe = pipeline.FullBatchPipeline(cfg, ms, sky, log=lambda *a: None)

    def run(depth, sol):
        return pipe.run(solution_path=sol, prefetch=depth,
                        log=lambda *a: None)

    h = _assert_bitident(msdir, 3, tmp_path, run)
    assert len(h) == 3
    assert all(np.isfinite(x["res_1"]) for x in h)


@pytest.mark.slow
def test_bitident_tile_batch(tmp_path):
    """--tile-batch 2 (the batched driver, solo boost tile + one
    2-tile group) under overlap == sync, bit for bit. Slow-marked
    (PR 1 precedent: the tier-1 wall holds its budget; the full CI
    suite runs it every push)."""
    msdir, skyf, clusf = _make_dataset(tmp_path)
    cfg = _cfg(msdir, skyf, clusf, extra=("--tile-batch", "2"))
    ms = ds.SimMS(msdir)
    sky = skymodel.read_sky_cluster(skyf, clusf, ms.meta["ra0"],
                                    ms.meta["dec0"], ms.meta["freq0"])
    pipe = pipeline.FullBatchPipeline(cfg, ms, sky, log=lambda *a: None)
    assert pipe.batch_ok

    def run(depth, sol):
        return pipe.run(solution_path=sol, prefetch=depth,
                        log=lambda *a: None)

    _assert_bitident(msdir, 3, tmp_path, run, tag="T2")


def test_bitident_beam(tmp_path):
    """-B 1 (synthetic beam tables staged per tile, incl. on the
    prefetch thread) under overlap == sync, bit for bit."""
    msdir, skyf, clusf = _make_dataset(tmp_path, n_tiles=2)
    cfg = _cfg(msdir, skyf, clusf, extra=("-B", "1"))
    ms = ds.SimMS(msdir)
    sky = skymodel.read_sky_cluster(skyf, clusf, ms.meta["ra0"],
                                    ms.meta["dec0"], ms.meta["freq0"])
    pipe = pipeline.FullBatchPipeline(cfg, ms, sky, log=lambda *a: None)
    assert pipe.dobeam

    def run(depth, sol):
        return pipe.run(solution_path=sol, prefetch=depth,
                        log=lambda *a: None)

    _assert_bitident(msdir, 2, tmp_path, run, tag="B")


@pytest.mark.slow
def test_bitident_minibatch(tmp_path):
    """Stochastic minibatch runner (-N 1 -M 2 -w 2): prefetched reads
    + async residual/solution writeback == the sync loop, bit for
    bit. Slow-marked to hold the tier-1 budget; full CI runs it."""
    msdir, skyf, clusf = _make_dataset(tmp_path, n_tiles=2, nchan=4)

    def run(depth, sol):
        args = cli.build_parser().parse_args([
            "-d", msdir, "-s", skyf, "-c", clusf, "-t", "4",
            "-N", "1", "-M", "2", "-w", "2", "-l", "3", "-p", sol,
            "--prefetch", str(depth)])
        cfg = cli.config_from_args(args)
        return stochastic.run_minibatch(cfg, log=lambda *a: None)

    _assert_bitident(msdir, 2, tmp_path, run, tag="mb")


# ---------------------------------------------------------------------------
# writer-thread failure semantics
# ---------------------------------------------------------------------------

def test_writer_failure_fails_run_with_original_traceback(
        tmp_path, monkeypatch):
    """An exception in the async MS write must fail the run at the
    next tile boundary with the ORIGINAL traceback — never swallowed.
    (--prefetch 0 is the documented debugging escape hatch: the same
    failure then raises inline at the write site itself.)"""
    msdir, skyf, clusf = _make_dataset(tmp_path)
    cfg = _cfg(msdir, skyf, clusf)
    ms = ds.SimMS(msdir)
    sky = skymodel.read_sky_cluster(skyf, clusf, ms.meta["ra0"],
                                    ms.meta["dec0"], ms.meta["freq0"])
    pipe = pipeline.FullBatchPipeline(cfg, ms, sky, log=lambda *a: None)

    real_write = ds.SimMS.write_tile
    calls = []

    def failing_write(self, i, tile, column=None):
        calls.append(i)
        if i == 1:
            raise OSError("injected MS write failure")
        return real_write(self, i, tile, column=column)

    monkeypatch.setattr(ds.SimMS, "write_tile", failing_write)
    with pytest.raises(OSError, match="injected MS write failure") as ei:
        pipe.run(prefetch=1, log=lambda *a: None)
    import traceback
    tb = "".join(traceback.format_tb(ei.value.__traceback__))
    assert "failing_write" in tb        # original frames preserved
    # the failure stopped the run: tile 2's write never happened
    assert 2 not in calls

    # sync escape hatch: same failure, raised inline
    calls.clear()
    with pytest.raises(OSError, match="injected MS write failure"):
        pipe.run(prefetch=0, log=lambda *a: None)


def test_sched_slow_writer_backpressure_bounded():
    """A slow writer never grows the queue without bound: submit
    blocks once maxsize jobs are pending (the bubble the diag records
    as write backpressure)."""
    aw = sched.AsyncWriter(enabled=True, maxsize=1)
    release = threading.Event()
    aw.submit(release.wait)             # occupies the writer
    aw.submit(lambda: None)             # fills the 1-slot queue
    t0 = time.perf_counter()
    threading.Timer(0.15, release.set).start()
    blocked = aw.submit(lambda: None)   # must block until release
    assert time.perf_counter() - t0 >= 0.1
    assert blocked >= 0.1
    aw.close()


# ---------------------------------------------------------------------------
# the simulation loop (-a 1|2|3, pipeline.run_simulation): read and
# stage ahead, one program in flight, the write behind
# ---------------------------------------------------------------------------

SIM_TILES = 5


@pytest.fixture(scope="module")
def sim_obs(tmp_path_factory):
    """Five tiles, a solutions file with an interval a tile, an ignore
    list that names the second cluster."""
    from sagecal_tpu.io import solutions as sol

    tmp = tmp_path_factory.mktemp("simloop")
    msdir, skyf, clusf = _make_dataset(tmp, n_tiles=SIM_TILES)
    sky = skymodel.read_sky_cluster(skyf, clusf, (41 / 60) * math.pi / 12,
                                    40 * math.pi / 180, 150e6)
    solf = str(tmp / "given.solutions")
    with sol.SolutionWriter(solf, 150e6, 1e6, 0.5, 8, sky.n_clusters,
                            sky.n_eff_clusters) as wr:
        for i in range(SIM_TILES):
            wr.write_interval(ds.random_jones(
                sky.n_clusters, sky.nchunk, 8, seed=7 + i, scale=0.3),
                sky.nchunk)
    ignoref = tmp / "ignore.txt"
    ignoref.write_text("1\n")
    return {"ms": msdir, "sky": skyf, "clusters": clusf, "solutions": solf,
            "ignore": str(ignoref)}


def _run_sim(o, tmp_path, prefetch, mode=1, extra=()):
    """``-a mode -p`` at ``--prefetch`` on a copy of the dataset: the
    output column's tiles, as written."""
    import shutil
    msdir = str(tmp_path / f"sim-{mode}-{prefetch}.ms")
    shutil.copytree(o["ms"], msdir)
    cfg = cli.config_from_args(cli.build_parser().parse_args(
        ["-d", msdir, "-s", o["sky"], "-c", o["clusters"], "-t", "4",
         "-a", str(mode), "-p", o["solutions"],
         "--prefetch", str(prefetch), *extra]))
    pipeline.run(cfg, log=lambda *a: None)
    return _corrected(msdir, SIM_TILES)


@pytest.mark.parametrize("mode,ignore", [(1, False), (2, False), (3, True)],
                         ids=["a1-p", "a2-p", "a3-p-z"])
def test_simulation_output_is_bit_identical_across_prefetch(
        sim_obs, tmp_path, mode, ignore):
    extra = ("-z", sim_obs["ignore"]) if ignore else ()
    want = _run_sim(sim_obs, tmp_path, 0, mode, extra)
    assert all(np.abs(x).mean() > 0.1 for x in want)
    for depth in (1, 2):
        got = _run_sim(sim_obs, tmp_path, depth, mode, extra)
        for a, b in zip(want, got):
            assert np.array_equal(a, b)


def test_simulation_writer_failure_raises_and_stops_the_writes(
        sim_obs, tmp_path, monkeypatch):
    """A ``write_tile`` that raises on tile 2 fails ``run_simulation``
    with that exception and its frames; no later tile is written."""
    real_write = ds.SimMS.write_tile
    calls = []

    def failing_write(self, i, tile, column=None):
        calls.append(i)
        if i == 2:
            raise RuntimeError("injected MS write failure")
        return real_write(self, i, tile, column=column)

    import traceback
    monkeypatch.setattr(ds.SimMS, "write_tile", failing_write)
    for depth in (1, 0):
        calls.clear()
        before = set(threading.enumerate())     # other tests' threads
        with pytest.raises(RuntimeError, match="injected MS write") as ei:
            _run_sim(sim_obs, tmp_path, depth)
        tb = "".join(traceback.format_tb(ei.value.__traceback__))
        assert "failing_write" in tb
        assert calls == [0, 1, 2]
        # the reader and the writer it started are gone
        assert not [t.name for t in set(threading.enumerate()) - before]


@pytest.mark.parametrize("depth", [1, 2])
def test_simulation_writes_in_the_order_read_and_all_before_it_returns(
        sim_obs, tmp_path, monkeypatch, depth):
    """A slow writer: ``write_tile`` is called in the order ``tiles()``
    yielded, one call at a time, and the last call has returned when
    ``run_simulation`` does."""
    real_write, real_tiles = ds.SimMS.write_tile, ds.SimMS.tiles
    read, began, ended = [], [], []

    def tiles(self):
        for i, tile in real_tiles(self):
            read.append(i)
            yield i, tile

    def slow_write(self, i, tile, column=None):
        assert len(began) == len(ended)     # one writer, one job at a time
        began.append(i)
        time.sleep(0.05)
        out = real_write(self, i, tile, column=column)
        ended.append(i)
        return out

    monkeypatch.setattr(ds.SimMS, "tiles", tiles)
    monkeypatch.setattr(ds.SimMS, "write_tile", slow_write)
    _run_sim(sim_obs, tmp_path, depth)
    assert read == list(range(SIM_TILES))
    assert began == read and ended == read


@pytest.mark.parametrize("depth", [1, 0])
def test_simulation_stages_the_same_tile_again_after_a_transient_failure(
        sim_obs, tmp_path, monkeypatch, depth):
    """``sched.Prefetcher`` tries a production that failed transiently
    again: the second try has to stage the tile the first one read, not
    the next one. Every tile is written, and what is written is what a
    run without the fault writes."""
    from sagecal_tpu import faults, utils

    (tmp_path / "want").mkdir()
    want = _run_sim(sim_obs, tmp_path / "want", 0)
    real_c2r, real_write = utils.c2r, ds.SimMS.write_tile
    staged, written = [], []

    def c2r(x):     # the first call of stage
        staged.append(len(staged))
        if len(staged) == 3:
            raise faults.TransientFault("injected staging failure")
        return real_c2r(x)

    def write_tile(self, i, tile, column=None):
        written.append(i)
        return real_write(self, i, tile, column=column)

    monkeypatch.setattr(faults, "RETRY_BASE_S", 1e-3)
    monkeypatch.setattr(ds.SimMS, "write_tile", write_tile)
    monkeypatch.setattr(utils, "c2r", c2r)
    if depth == 0:
        # the step stages, outside the retried read: the failure is the
        # run's, as it was before the loop was overlapped
        with pytest.raises(faults.TransientFault, match="staging"):
            _run_sim(sim_obs, tmp_path, depth)
        assert written == [0, 1]
        return
    got = _run_sim(sim_obs, tmp_path, depth)
    assert len(staged) == SIM_TILES + 1
    assert written == list(range(SIM_TILES))
    for a, b in zip(want, got):
        assert np.array_equal(a, b)


@pytest.mark.parametrize("depth", [1, 0])
def test_simulation_read_failure_is_raised_and_not_read_as_the_end(
        sim_obs, tmp_path, monkeypatch, depth):
    """``tiles()`` is a generator: one that raised has ended, and the
    ``next()`` of the Prefetcher's second try would read as a clean end
    of the data. The failure is the run's; the tiles before it are
    written."""
    from sagecal_tpu import faults

    real_write, real_tiles = ds.SimMS.write_tile, ds.SimMS.tiles
    asked, written = [], []

    def tiles(self):
        for i, tile in real_tiles(self):
            asked.append(i)
            if i == 3:
                raise OSError("flaky read")
            yield i, tile

    def write_tile(self, i, tile, column=None):
        written.append(i)
        return real_write(self, i, tile, column=column)

    monkeypatch.setattr(faults, "RETRY_BASE_S", 1e-3)
    monkeypatch.setattr(ds.SimMS, "tiles", tiles)
    monkeypatch.setattr(ds.SimMS, "write_tile", write_tile)
    with pytest.raises(OSError, match="flaky read"):
        _run_sim(sim_obs, tmp_path, depth)
    assert asked == [0, 1, 2, 3]
    assert written == [0, 1, 2][:len(written)]    # in order, none after
    if depth == 0:
        assert written == [0, 1, 2]


def test_simulation_keeps_under_eight_tiles_between_read_and_disk(
        sim_obs, tmp_path, monkeypatch):
    """A dataset that cycles its disk tiles, as the benchmark's does,
    under a slow writer and ``--prefetch 5``: never more than seven
    tiles read and not yet on disk, so a disk tile of eight is not read
    again while the write of its last cycle is pending."""
    real_write, real_read = ds.SimMS.write_tile, ds.SimMS.read_tile
    cycles = 4 * SIM_TILES
    read, ended, most = [], [], [0]

    def tiles(self):
        for k in range(cycles):
            read.append(k)
            most[0] = max(most[0], len(read) - len(ended))
            yield k % SIM_TILES, real_read(self, k % SIM_TILES)

    def slow_write(self, i, tile, column=None):
        time.sleep(0.03)
        out = real_write(self, i, tile, column=column)
        ended.append(i)
        return out

    monkeypatch.setattr(ds.SimMS, "tiles", tiles)
    monkeypatch.setattr(ds.SimMS, "write_tile", slow_write)
    _run_sim(sim_obs, tmp_path, 5)
    assert ended == [k % SIM_TILES for k in range(cycles)]
    assert 4 <= most[0] <= 7, most


def test_simulation_threads_take_the_callers_tracer_and_device(
        sim_obs, tmp_path, monkeypatch):
    """``serve`` runs a simulation job inside thread-local scopes (the
    job's tracer, its device): the reader and the writer that
    ``run_simulation`` starts have to be inside them too."""
    import jax
    from sagecal_tpu import utils
    from sagecal_tpu.diag import trace as dtrace

    device = jax.devices()[-1]
    seen = []
    real_c2r = utils.c2r

    def c2r(x):     # called by stage, on the reader's thread
        seen.append((threading.current_thread().name,
                     jax.config.jax_default_device))
        return real_c2r(x)

    monkeypatch.setattr(utils, "c2r", c2r)
    tracer = dtrace.Tracer(str(tmp_path / "job.jsonl"))
    try:
        with dtrace.scope(tracer), jax.default_device(device):
            _run_sim(sim_obs, tmp_path, 1)
    finally:
        tracer.close()
    assert seen == [("prefetch-read", device)] * SIM_TILES
    phases = [r for r in dtrace.read(str(tmp_path / "job.jsonl"))
              if r["ev"] == "phase"]
    for name, thread in (("stage", "prefetch-read"),
                         ("write", "async-writer")):
        got = [r for r in phases if r["name"] == name]
        assert len(got) == SIM_TILES
        assert all(r["thread"] == thread for r in got)
