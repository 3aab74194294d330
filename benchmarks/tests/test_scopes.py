"""``benchmarks/scopes.py`` and the eight metrics that read it (ISSUE 26).
By hand, with the rest of the benchmark's tests:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

The arithmetic on synthetic nested events (a scope's seconds are its
LEAF operations', counts, idle seconds inside a host span, selection by
``tm``), the HLO table read out of a hand-built trace file, what the
readers do on a program that has none of the names (the parent), and the
tiny rehearsal cells traced end to end.  Since ISSUE 29 also: the one
walk against the parent's two (kept here as the reference), a profile of
four device planes, how often a profile's events are gone through, and
where the profiler starts and stops with and without ``profile_tiles``.
"""

import json
import os
import random
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import harness      # noqa: E402
import scopes       # noqa: E402
import xplane       # noqa: E402

CELLS = os.path.join(HERE, "rehearsal", "cells.json")
NEW = ("sweep_dev_s", "refine_dev_s", "solve_ops_per_tile", "phasor_dev_ms",
       "corrupt_dev_ms", "bubble_ms.predict", "recompiles_in_window",
       "compile_s.setup")


# -- names --------------------------------------------------------------------

@pytest.mark.parametrize("text, want", [
    ("jit(_jit_sagefit)/jit(main)/sage/sweep/while/body/inner/while/body/"
     "assemble/baor,bari->boi/dot_general", ("sage/sweep", "assemble")),
    ("jit(f)/sage/sweep/while/body/closed_call/inner/mul",
     ("sage/sweep", "inner")),
    ("jit(f)/sage/sweep/update/rime/corrupt/gather", ("sage/sweep", "update")),
    ("jit(_jit_refine)/sage/refine/while/body/linesearch/while/body/cond/"
     "branch_0_fun/transpose(jvp())/mul", ("sage/refine", "linesearch")),
    ("jit(_jit_refine)/sage/refine/transpose(jvp())/while/body/add",
     ("sage/refine", None)),
    ("rime/phasor/vmap()/reduce_sum", ("rime/phasor", None)),
    ("jit(sim_fn)/rime/corrupt/while/body/closed_call/rime/corrupt/"
     "bij,bfjk,bkl->bfil/dot_general", ("rime/corrupt", None)),
    ('%fusion.12 = f32[8,2]{1,0} fusion(%p), kind=kLoop, calls=%fc, '
     'metadata={op_name="jit(f)/jit(main)/sage/final/sub" source_file="a.py"}',
     ("sage/final", None)),
    ("jit(f)/jit(main)/mul", None),
    ("fusion.3758", None),
    ("jit(f)/message/sweep/mul", None),      # "sage/" inside another name
])
def test_scope_path(text, want):
    assert scopes.scope_path(text) == want


# -- the slice's arithmetic ---------------------------------------------------

class Ev:
    def __init__(self, name, start_us, dur_us, **stats):
        self.name, self.stats = name, list(stats.items())
        self.start_ns, self.duration_ns = start_us * 1e3, dur_us * 1e3


def fake_profile(monkeypatch, planes):
    """``xplane.load`` gives planes built here: {plane: {line: [Ev]}}."""
    pd = types.SimpleNamespace(planes=[
        types.SimpleNamespace(name=p, lines=[
            types.SimpleNamespace(name=ln, events=ev)
            for ln, ev in lines.items()])
        for p, lines in planes.items()])
    monkeypatch.setattr(xplane, "load", lambda path: pd)


def tpu_op(op, scope, start_us, dur_us):
    meta = f', metadata={{op_name="jit(f)/jit(main)/{scope}/x"}}' if scope \
        else ""
    return Ev(f"%{op} = f32[2]{{0}} fusion(%p), kind=kLoop{meta}",
              start_us, dur_us)


def test_slice_counts_leaves_and_idle_inside_spans(monkeypatch):
    fake_profile(monkeypatch, {
        "/device:TPU:0": {
            "XLA Ops": [
                # a while of 100 us under sage/sweep holding three leaves:
                # its own 100 us are NOT the scope's seconds, theirs are
                tpu_op("while.1", "sage/sweep", 0, 100),
                tpu_op("fusion.1", "sage/sweep/inner", 10, 20),
                tpu_op("fusion.2", "sage/sweep/while/body/assemble", 40, 30),
                # made by the compiler, no source name: the loop's work
                tpu_op("copy.3", None, 80, 10),
                # after a gap of 50 us, one leaf of the refine
                tpu_op("fusion.4", "sage/refine/linesearch", 150, 50),
                # no name and no loop around it: stays unscoped
                tpu_op("copy.9", None, 200, 4),
            ],
            "Steps": [Ev("step", 0, 1000)],
        },
        "/host:CPU": {"python": [
            Ev("sagecal/write", 95, 60, tile=3),   # 95..155: holds the gap
            Ev("sagecal/solve", 0, 95),
            Ev("tile_cycle", 0, 300),              # the harness's: not ours
        ]},
    })
    sl = scopes.Slice("unused")
    assert sl.n_devices == 1 and sl.scoped()
    sec, n = sl.first_level("sage/sweep")
    assert (round(sec * 1e6), n) == (60, 3)
    assert sl.leaf[("sage/sweep", "inner")] == [pytest.approx(20e-6), 1]
    assert sl.leaf[("sage/sweep", "assemble")] == [pytest.approx(30e-6), 1]
    assert sl.leaf[("sage/sweep", None)] == [pytest.approx(10e-6), 1]
    assert sl.made == {"sage/sweep": pytest.approx(10e-6)}
    assert sl.first_level("sage/refine") == (pytest.approx(50e-6), 1)
    assert sl.first_level("rime/phasor") == (0, 0)
    assert sl.unscoped == {"copy.9": pytest.approx(4e-6)}
    # busy: the union of the leaves
    assert sl.busy_s == pytest.approx(114e-6)
    assert sl.how == {"the event's text"}
    idle = sl.idle_in_spans()
    assert set(idle) == {"write", "solve"}
    n, sec, idl = idle["write"]
    # 95..155 holds no leaf until 150: 55 us idle, 5 us busy
    assert (n, round(sec * 1e6), round(idl * 1e6)) == (1, 60, 55)
    n, sec, idl = idle["solve"]
    assert (n, round(sec * 1e6), round(idl * 1e6)) == (1, 95, 35)
    rows = "\n".join(sl.table_lines())
    assert "sage/sweep/assemble" in rows and "unscoped copy.9" in rows
    assert "the compiler made" in rows


def test_per_tile_says_when_the_trace_has_no_scoped_event(monkeypatch,
                                                          capsys):
    fake_profile(monkeypatch, {"/device:TPU:0": {"XLA Ops": [
        tpu_op("fusion.3758", None, 0, 10), tpu_op("fusion.3882", None, 20, 5),
    ]}})
    run = types.SimpleNamespace(
        profile={"busy_s": 1}, slice_tiles=2, profile_dir="unused",
        diag_path="unused", window=types.SimpleNamespace(t_open=None))
    monkeypatch.setattr(xplane, "newest_trace", lambda d: "unused")
    assert scopes.per_tile(run, "sage/sweep") is None
    assert "no scoped event in the trace" in capsys.readouterr().out
    solve_ops = harness.load_module("layer_metrics", "solve_ops_per_tile")
    assert solve_ops.read(run) is None


def test_per_tile_divides_by_the_tiles_begun_in_the_slice(monkeypatch):
    fake_profile(monkeypatch, {"/device:TPU:0": {"XLA Ops": [
        tpu_op("fusion.1", "rime/phasor", 0, 3000),
        tpu_op("fusion.2", "rime/corrupt", 4000, 1000),
        tpu_op("fusion.3", "rime/phasor", 6000, 3000),
    ]}})
    monkeypatch.setattr(xplane, "newest_trace", lambda d: "unused")
    run = types.SimpleNamespace(
        profile={"busy_s": 1}, slice_tiles=2, profile_dir="unused",
        diag_path="unused", window=types.SimpleNamespace(t_open=None))
    phasor = harness.load_module("layer_metrics", "phasor_dev_ms")
    corrupt = harness.load_module("layer_metrics", "corrupt_dev_ms")
    assert phasor.read(run) == pytest.approx(3.0)
    assert corrupt.read(run) == pytest.approx(0.5)


# -- the HLO table of a trace file --------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _ld(num, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def test_hlo_table_reads_the_modules_stored_in_a_trace(tmp_path):
    def instruction(name, op_name):
        return _ld(2, _ld(1, name) + _ld(2, "fusion")
                   + (_ld(7, _ld(1, "mul") + _ld(2, op_name))
                      if op_name else b"") + _varint(35 << 3) + _varint(7))

    module = (_ld(1, "jit_sim_fn")
              + _ld(3, _ld(1, "main") + instruction(
                  "fusion.5", "jit(sim_fn)/rime/phasor/vmap()/reduce_sum")
                  + instruction("copy.1", "") + instruction(
                      "dot.8", "jit(sim_fn)/rime/corrupt/dot_general")))
    hlo_proto = _ld(1, module)
    stat = _varint(1 << 3) + _varint(9) + _ld(6, hlo_proto)
    other = _varint(1 << 3) + _varint(4) + _ld(6, b"\xff\xff\xff")
    metadata = (_varint(1 << 3) + _varint(1) + _ld(2, "jit_sim_fn")
                + _ld(5, stat) + _ld(5, other))
    entry = _varint(1 << 3) + _varint(1) + _ld(2, metadata)
    plane = _ld(2, "/host:metadata") + _ld(4, entry)
    host = _ld(2, "/host:CPU") + _ld(4, entry)      # not looked at
    # a fixed64 field (1 << 3 | 1) in the space is skipped
    space = _ld(1, host) + _ld(1, plane) + bytes([9]) + bytes(8)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space)
    assert scopes.hlo_table(str(path)) == {"jit_sim_fn": {
        "fusion.5": "jit(sim_fn)/rime/phasor/vmap()/reduce_sum",
        "dot.8": "jit(sim_fn)/rime/corrupt/dot_general"}}


def test_cpu_events_find_their_scope_in_the_table(monkeypatch):
    fake_profile(monkeypatch, {"/host:CPU": {"tf_XLAPjRtCpuClient/1": [
        Ev("fusion.5", 0, 10, hlo_op="fusion.5", hlo_module="jit_sim_fn"),
        Ev("copy.1", 20, 5, hlo_op="copy.1", hlo_module="jit_sim_fn"),
        Ev("fusion.5", 30, 10, hlo_op="fusion.5", hlo_module="jit_other"),
    ]}})
    monkeypatch.setattr(scopes, "hlo_table", lambda path: {
        "jit_sim_fn": {"fusion.5": "rime/phasor/vmap()/reduce_sum"}})
    sl = scopes.Slice("unused")
    assert sl.first_level("rime/phasor") == (pytest.approx(10e-6), 1)
    assert sl.unscoped == {"copy.1": pytest.approx(5e-6),
                           "fusion.5": pytest.approx(10e-6)}
    assert sl.how == {"the HLO modules stored in the trace"}


def test_tpu_events_find_their_module_on_the_xla_modules_line(monkeypatch):
    """The v5e's events: the HLO line without its metadata, no module
    stat. The module is the ``XLA Modules`` event that holds the
    operation in time."""
    line = "%fusion.5 = f32[2]{0:T(256)} fusion(f32[2]{0:T(256)} %p), kind=kLoop"
    fake_profile(monkeypatch, {"/device:TPU:0": {
        "XLA Modules": [Ev("jit_sim_fn(123456789)", 0, 100),
                        Ev("jit_other(42)", 200, 100)],
        "XLA Ops": [Ev(line, 10, 20, device_offset_ps=1),
                    Ev(line, 210, 30, device_offset_ps=2),
                    Ev(line, 400, 5)],          # outside every module
    }})
    monkeypatch.setattr(scopes, "hlo_table", lambda path: {
        "jit_sim_fn": {"fusion.5": "jit(sim_fn)/rime/phasor/vmap()/cos"},
        "jit_other": {"fusion.5": "jit(other)/jit(main)/mul"}})
    sl = scopes.Slice("unused")
    assert sl.first_level("rime/phasor") == (pytest.approx(20e-6), 1)
    assert sl.unscoped == {"fusion.5": pytest.approx(35e-6)}
    assert sl.how == {"the HLO modules stored in the trace"}


# -- one read and one walk, for every reader (ISSUE 29) -----------------------

def ref_self_times(events):
    """The parent's ``xplane.self_times``, kept as the reference: (leaf
    intervals, {name: self seconds}) of ``(name, start, end)`` events."""
    order = sorted(events, key=lambda e: (e[1], -(e[2] - e[1])))
    stack, leaves, total = [], [], {}

    def close(item):
        name, start, end, child_ns, has_child = item
        total[name] = total.get(name, 0.0) + max(
            0.0, (end - start) - child_ns) * scopes.NS
        if not has_child:
            leaves.append((start, end))

    for name, start, end in order:
        while stack and stack[-1][2] <= start:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(end, stack[-1][2]) - start
            stack[-1][4] = True
        stack.append([name, start, end, 0.0, False])
    while stack:
        close(stack.pop())
    return leaves, total


def ref_leaves(events):
    """The parent's ``Slice._leaves``: of ``(label, start, end)`` events,
    a label a (first, second) pair or the name of an unscoped operation,
    the leaves with the label they count under, and ``made``."""
    order = sorted(events, key=lambda ev: (ev[1], -(ev[2] - ev[1])))
    stack, items, made = [], [], {}
    for lab, start, end in order:
        while stack and stack[-1][0] <= start:
            stack.pop()
        near = None
        if stack:
            near = stack[-1][1]
            stack[-1][2][4] = False
        placed = isinstance(lab, str) and near is not None
        if placed:
            lab = near
        item = [lab, start, end, placed, True]
        items.append(item)
        stack.append([end, near if isinstance(lab, str) else lab, item])
    leaves = []
    for lab, start, end, placed, is_leaf in items:
        if is_leaf:
            leaves.append((lab, start, end))
            if placed:
                made[lab[0]] = (made.get(lab[0], 0.0)
                                + (end - start) * scopes.NS)
    return leaves, made


def nested_events(rng, n_top=40):
    """Random nested operations, some with a scope, some made by the
    compiler, some touching, a few outlasting their parent."""
    labels = [("sage/sweep", "inner"), ("sage/sweep", None),
              ("sage/refine", "restrict"), ("rime/phasor", None), None, None]
    out, t = [], 0.0

    def grow(start, end, depth):
        out.append((f"op.{rng.randrange(12)}", rng.choice(labels),
                    start, end))
        if depth < 3 and end - start > 40 and rng.random() < 0.7:
            at = start + rng.choice((0.0, 3.0))
            while at < end - 10:
                stop = min(at + rng.uniform(5, (end - start) / 2),
                           end + rng.choice((0.0, 0.0, 0.0, 4.0)))
                grow(at, stop, depth + 1)
                at = stop + rng.choice((0.0, 1.5, 7.25))

    for _ in range(n_top):
        length = rng.uniform(10, 400)
        grow(t, t + length, 0)
        t += length + rng.choice((0.0, 0.125, 30.0))
    out.sort(key=lambda ev: (ev[2], -(ev[3] - ev[2])))
    return out


@pytest.mark.parametrize("seed", range(6))
def test_one_walk_gives_what_the_parents_two_walks_gave(seed):
    events = nested_events(random.Random(seed))
    dev = xplane.walk(((name, lab), s, e) for name, lab, s, e in events)
    assert dev.n_events == len(events)
    leaves, total = ref_self_times([(n, s, e) for n, _, s, e in events])
    assert dev.self_s == total          # the same sums in the same order
    assert dev.merged == xplane.union(leaves)
    labelled, made = ref_leaves([(lab or n, s, e) for n, lab, s, e in events])
    leaf, unscoped = {}, {}
    for lab, s, e in labelled:
        if isinstance(lab, str):
            unscoped[lab] = unscoped.get(lab, 0.0) + (e - s) * scopes.NS
            lab = (scopes.UNSCOPED, None)
        acc = leaf.setdefault(lab, [0.0, 0])
        acc[0] += (e - s) * scopes.NS
        acc[1] += 1
    assert (dev.leaf, dev.made, dev.unscoped) == (leaf, made, unscoped)


class Counted(list):
    """A line's events that count how often they are gone through."""
    walks = 0

    def __iter__(self):
        self.walks += 1
        return super().__iter__()


def four_planes():
    """Four chips, unequal busy time, an ``all-reduce`` on each (its
    halves on chip 3) and a host span."""
    planes = {}
    for n in range(4):
        busy = 100 + 50 * n
        ops = [tpu_op("while.1", "sage/sweep", 0, busy + 40),
               tpu_op("fusion.1", "sage/sweep/inner", 0, busy)]
        if n < 3:
            ops.append(tpu_op("all-reduce.7", "sage/consensus",
                              busy, 10 * (n + 1)))
        else:
            ops += [tpu_op("all-reduce-start.7", "sage/consensus", busy, 4),
                    tpu_op("all-reduce-done.7", "sage/consensus",
                           busy + 20, 6)]
        planes[f"/device:TPU:{n}"] = {"XLA Ops": Counted(ops),
                                      "Steps": [Ev("step", 0, 1000)]}
    planes["/host:CPU"] = {"python": Counted([
        Ev("sagecal/solve", 0, 300), Ev("tile_cycle", 0, 300)])}
    return planes


def test_four_device_planes_give_per_device_busy_and_collectives(
        monkeypatch):
    fake_profile(monkeypatch, four_planes())
    sl = scopes.Slice("unused")
    red = sl.profile.reduce()
    assert red["devices"] == [f"/device:TPU:{n}" for n in range(4)]
    per = red["per_device"]
    want_busy = [110e-6, 170e-6, 230e-6, 260e-6]    # fusion + collective
    want_coll = [10e-6, 20e-6, 30e-6, 10e-6]
    for n, (busy, coll) in enumerate(zip(want_busy, want_coll)):
        d = per[f"/device:TPU:{n}"]
        assert d["busy_s"] == pytest.approx(busy)
        assert d["n_events"] == (3 if n < 3 else 4)
        assert d["collective_s"] == pytest.approx(coll)
        assert d["collectives"] == {"all-reduce": pytest.approx(coll)}
        assert d["class_s"]["fusion"] == pytest.approx((100 + 50 * n) * 1e-6)
        # the while's own seconds: what its children do not cover
        assert d["class_s"]["while"] == pytest.approx(40e-6 - coll)
    # the averaged busy time, as before: the mean over the devices
    assert red["busy_s"] == pytest.approx(sum(want_busy) / 4)
    assert red["n_device_events"] == 13
    assert sl.n_devices == 4 and len(sl.merged) == 4
    assert sl.busy_s == pytest.approx(red["busy_s"])
    # scope seconds are summed over the devices
    assert sl.first_level("sage/sweep") == (pytest.approx(700e-6), 4)
    assert sl.first_level("sage/consensus") == (pytest.approx(70e-6), 5)
    assert red["device_ops"][0] == ["fusion.1", pytest.approx(175e-6)]
    # chip 3's gap between the halves of its all-reduce, inside the span
    assert red["idle_gaps"][0] == ["tile_cycle", pytest.approx(16e-6)]


@pytest.mark.parametrize("n_planes", [1, 4])
def test_a_run_loads_its_trace_once_and_walks_its_events_once(
        monkeypatch, tmp_path, n_planes, capsys):
    import run as runner
    planes = four_planes()
    for n in range(n_planes, 4):
        del planes[f"/device:TPU:{n}"]
    fake_profile(monkeypatch, planes)
    real, loads = xplane.load, []
    monkeypatch.setattr(xplane, "load",
                        lambda path: loads.append(path) or real(path))
    trace = tmp_path / "t.xplane.pb"
    trace.write_bytes(b"")
    run = runner.Run(harness.Cell("cal-tiny", harness.load_json(CELLS)),
                     5, 1.0, trace=True)
    run._prof_t0, run._prof_t1, run.slice_tiles = 10.0, 10.5, 2
    run.trace_path = str(trace)
    run._read_profile()
    # every reader of the slice, the tables and the breakdown's source
    assert scopes.load(run) is run.slice
    for name in ("sweep_dev_s", "refine_dev_s", "solve_ops_per_tile",
                 "device_ms_per_tile", "device_idle_pct", "phasor_dev_ms"):
        harness.load_module("layer_metrics", name).read(run)
    scopes.span_table(run, ("solve",))
    assert run.profile["device_ops"] and run.profile["idle_gaps"]
    assert run.profile["window_s"] == 0.5
    assert loads == [str(trace)]
    lines = [ev for lines in planes.values() for ev in lines.values()
             if isinstance(ev, Counted)]
    assert len(lines) == n_planes + 1
    assert [ev.walks for ev in lines] == [1] * len(lines)
    assert set(run.profile["per_device"]) == {
        f"/device:TPU:{n}" for n in range(n_planes)}
    assert run.clock.notes["device_planes"] == n_planes
    assert {"load", "walk", "reduce"} <= set(run.clock.phases)
    assert "[scope] sage/sweep" in capsys.readouterr().out


# -- where the profiler starts and stops --------------------------------------

def traced_run(monkeypatch, seconds, **keys):
    """A traced run of the tiny cell on a clock the test moves, with the
    profiler's calls recorded: (run, now, calls)."""
    import jax.profiler
    import run as runner
    cell = harness.Cell("cal-tiny", harness.load_json(CELLS))
    cell.traffic = {**cell.traffic, "profile_slice_s": 8.0, **keys}
    now, calls = [1000.0], []
    run = runner.Run(cell, 5, seconds, trace=True)
    run.window = harness.Window(seconds, 0.0, clock=lambda: now[0])
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", now[0])))
    monkeypatch.setattr(
        xplane, "stop_session",
        lambda d: calls.append(("stop", now[0])) or ("unused", "a fake"))
    monkeypatch.setattr(runner.Run, "_read_profile", lambda self: None)
    return run, now, calls


def drive(run, now, cycle, drain_s=0.5, n_tiles=None):
    """What a driver does: ask ``due()`` at each boundary, enter the
    tile, let ``cycle`` seconds pass; then drain.  One whose observation
    has ``n_tiles`` window tiles ends with them, and says at each
    boundary how many are left."""
    k = 0
    while k != n_tiles and not run.window.due():
        run.enter_tile(k, 100,
                       left=None if n_tiles is None else n_tiles - 1 - k)
        now[0] += cycle
        k += 1
    now[0] += drain_s
    run.drain()
    return k


def test_without_the_keys_the_profiler_starts_and_stops_where_it_did(
        monkeypatch):
    """The parent's rule on 6.45 s tiles in a 51 s window with an 8 s
    slice: on at the first boundary ``b`` with ``b + T >= 51 - 8`` (tile
    6, at 38.7 s), off at the drain, after the first boundary past 51 s:
    two whole tiles in it."""
    run, now, calls = traced_run(monkeypatch, 51.0)
    assert drive(run, now, 6.45) == 8
    assert calls == [("start", pytest.approx(1038.7)),
                     ("stop", pytest.approx(1052.1))]
    assert run.slice_tiles == 2
    assert run._prof_t1 - run._prof_t0 == pytest.approx(13.4)
    # with shorter tiles the boundary lands elsewhere: three tiles
    run, now, calls = traced_run(monkeypatch, 51.0)
    assert drive(run, now, 2.9) == 18
    assert calls[0] == ("start", pytest.approx(1000 + 14 * 2.9))
    assert calls[1] == ("stop", pytest.approx(1000 + 18 * 2.9 + 0.5))
    assert run.slice_tiles == 4
    assert "stop_trace_in_window_s" not in run.clock.notes


def test_profile_tiles_stops_at_the_boundary_that_many_tiles_on(monkeypatch):
    """The same window runs one tile past the start of the profile: with
    ``profile_tiles: 1`` the profile holds one tile, not two."""
    run, now, calls = traced_run(monkeypatch, 51.0, profile_tiles=1)
    assert drive(run, now, 6.45) == 8
    assert calls == [("start", pytest.approx(1038.7)),
                     ("stop", pytest.approx(1045.15))]
    assert run.slice_tiles == 1
    assert run._prof_t1 - run._prof_t0 == pytest.approx(6.45)
    assert "stop_trace_in_window_s" in run.clock.notes
    # a window that ends first stops it at the drain, as without the key
    run, now, calls = traced_run(monkeypatch, 51.0, profile_tiles=5)
    drive(run, now, 6.45)
    assert calls[1] == ("stop", pytest.approx(1052.1))
    assert run.slice_tiles == 2
    # the clock's phases still add up to the wall
    assert sum(run.clock.phases.values()) == pytest.approx(run.clock.total())


@pytest.mark.parametrize(
    "cycle, n_tiles, keys, start, stop, slice_tiles", [
        # cal-t120's shape at 5.3 s a tile: on at the fourth window
        # tile's boundary, two whole tiles, off at the drain
        (5.3, 5, {}, 3 * 5.3, 5 * 5.3 + 0.5, 2),
        # admm-f4-mesh's at 1.68 s: on at the 19th interval, off one on
        (1.68, 22, {"profile_slice_s": 6.6, "profile_tiles": 1},
         18 * 1.68, 19 * 1.68, 1),
        # cal-m8x3's at 0.55 s: on at 25.3 s of a 33.55 s window
        (0.55, 61, {}, 46 * 0.55, 61 * 0.55 + 0.5, 15),
        # two tiles: the last one, whatever the slice asks for
        (30.0, 2, {}, 30.0, 60.5, 1),
    ], ids=["cal-t120", "admm-f4-mesh", "cal-m8x3", "two-tiles"])
def test_a_window_the_observation_ends_has_its_last_tiles_profiled(
        monkeypatch, cycle, n_tiles, keys, start, stop, slice_tiles):
    """The solver cells once a tile is fast: the observation's window
    tiles are over before ``seconds - profile_slice_s`` (51 s less 8 or
    6.6), so the parent's rule records no ``start`` call at all in any
    of these shapes and the traced run has no profile.  With ``left``
    the profiler starts against the observation's projected end."""
    run, now, calls = traced_run(monkeypatch, 51.0, **keys)
    assert drive(run, now, cycle, n_tiles=n_tiles) == n_tiles
    assert run.window.t_due is None         # the observation ended it
    assert calls == [("start", pytest.approx(1000 + start)),
                     ("stop", pytest.approx(1000 + stop))]
    assert run.slice_tiles == slice_tiles
    assert ("stop_trace_in_window_s" in run.clock.notes) \
        == ("profile_tiles" in keys)
    assert run.clock.notes["tiles"] == n_tiles
    assert sum(run.clock.phases.values()) == pytest.approx(run.clock.total())


def test_left_changes_nothing_where_the_window_ends_on_its_seconds(
        monkeypatch):
    """Today's cells: 61 tiles of 6.45 s (or of 2.9 s) are far past
    51 s, so the projected end is ``seconds`` and the profiler starts
    and stops where it does without ``left``."""
    run, now, calls = traced_run(monkeypatch, 51.0)
    assert drive(run, now, 6.45, n_tiles=61) == 8
    assert calls == [("start", pytest.approx(1038.7)),
                     ("stop", pytest.approx(1052.1))]
    assert run.slice_tiles == 2
    run, now, calls = traced_run(monkeypatch, 51.0)
    assert drive(run, now, 2.9, n_tiles=61) == 18
    assert calls[0] == ("start", pytest.approx(1000 + 14 * 2.9))
    assert run.slice_tiles == 4
    # a window of one tile has no boundary inside it to start at
    run, now, calls = traced_run(monkeypatch, 51.0)
    assert drive(run, now, 6.45, n_tiles=1) == 1
    assert calls == [] and run.slice_tiles == 0


def test_profile_tiles_is_refused_where_the_boundary_lies_in_a_span(
        monkeypatch):
    """The predict driver's boundary lies inside the program's
    ``sagecal/io``: a stop there would be booked as io, so a mix of that
    driver that sets the key is refused before anything runs."""
    import run as runner
    cell = harness.Cell("predict-tiny", harness.load_json(CELLS))
    assert not hasattr(cell.driver, "BOUNDARY_OUTSIDE_SPANS")
    runner.Run(cell, 5, 1.0, trace=True)        # without the key: fine
    cell.traffic = {**cell.traffic, "profile_tiles": 1}
    with pytest.raises(ValueError, match="BOUNDARY_OUTSIDE_SPANS"):
        runner.Run(cell, 5, 1.0, trace=True)


# -- records of the window ----------------------------------------------------

def fake_run(tmp_path, records):
    path = tmp_path / "diag.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in records))
    window = types.SimpleNamespace(t_open=100.0, t_drain=200.0)
    return types.SimpleNamespace(diag_path=str(path), window=window,
                                 profile=None, slice_tiles=0,
                                 profile_dir=str(tmp_path))


def test_window_records_are_selected_by_tm_not_by_tile(tmp_path, capsys):
    recs = [{"t": 0.0, "tm": tm, "ev": "tile", "tile": tile,
             "bubble_s": b, "overlap": 0}
            for tm, tile, b in ((50.0, 0, 9.0),       # warm-up, disk tile 0
                                (120.0, 0, 0.010),    # window, disk tile 0
                                (180.0, 1, 0.020),
                                (250.0, 2, 9.0))]     # after the drain
    recs += [{"t": 0.0, "tm": 130.0, "ev": "phase", "name": "write",
              "dur_s": 0.004, "tile": 0},
             {"t": 0.0, "tm": 60.0, "ev": "phase", "name": "write",
              "dur_s": 5.0, "tile": 0},
             {"t": 0.0, "ev": "tile", "tile": 1, "bubble_s": 9.0}]  # no tm
    run = fake_run(tmp_path, recs)
    assert len(scopes.window_records(run)) == 3
    bubble = harness.load_module("layer_metrics", "bubble_ms.predict")
    assert bubble.read(run) == pytest.approx(15.0)
    out = capsys.readouterr().out
    assert "sagecal/write" in out and "4.0000 ms over 1 window records" in out
    assert "sagecal/fetch      no window record" in out


def test_compile_metrics_split_the_log_at_the_windows_edges(tmp_path,
                                                            monkeypatch,
                                                            capsys):
    from sagecal_tpu.diag import guard
    log = [(20.0, "trace", "f", 4.0),                   # 16..20
           (19.0, "trace", "inner_of_f", 2.0),          # 17..19, inside it
           (30.0, "lower", "jit(f)", 5.0),              # 25..30
           (60.0, "backend_compile", "jit(f)", 20.0),   # 40..60
           (150.0, "backend_compile", "jit(g)", 1.0),   # in the window
           (150.5, "trace", "g", 0.1),
           (300.0, "backend_compile", "jit(h)", 1.0)]   # after the drain
    monkeypatch.setattr(guard, "compile_log", lambda: log, raising=False)
    run = fake_run(tmp_path, [])
    re_in = harness.load_module("layer_metrics", "recompiles_in_window")
    setup = harness.load_module("layer_metrics", "compile_s.setup")
    assert re_in.read(run) == 1
    assert "jit(g)" in capsys.readouterr().out
    # the union: 4 + 5 + 20, the nested trace counted once
    assert setup.read(run) == pytest.approx(29.0)
    assert scopes.union_seconds(log[:2]) == pytest.approx(4.0)


def test_on_a_program_without_the_names_every_reader_returns_nothing(
        tmp_path, monkeypatch):
    """The parent commit: no ``tm``, no compile log, no profile. Nothing
    raises, every new metric is left out."""
    from sagecal_tpu.diag import guard
    monkeypatch.delattr(guard, "compile_log")
    run = fake_run(tmp_path, [
        {"t": 0.0, "ev": "tile", "tile": 0, "bubble_s": 1.0},
        {"t": 0.0, "ev": "phase", "name": "solve", "dur_s": 1.0}])
    for name in NEW:
        assert harness.load_module("layer_metrics", name).read(run) is None


# -- the manifest, and the tiny cells end to end ------------------------------

def test_new_metrics_are_appended_and_found_by_name():
    man = harness.load_json(ROOT, "BENCHMARK.json")
    entries = {m["name"]: m for m in man["per_layer"]}
    assert set(NEW) | {"refine_passes"} <= set(entries)
    cal = {m["name"] for m in harness.Cell("cal-m8x3").metrics("per_layer")}
    pred = {m["name"]
            for m in harness.Cell("predict-m8x128").metrics("per_layer")}
    assert {"sweep_dev_s", "refine_dev_s", "solve_ops_per_tile",
            "refine_passes"} <= cal - pred
    assert {"phasor_dev_ms", "corrupt_dev_ms",
            "bubble_ms.predict"} <= pred - cal
    assert {"recompiles_in_window", "compile_s.setup"} <= cal & pred
    for name in NEW + ("refine_passes",):
        m, mod = entries[name], harness.load_module("layer_metrics", name)
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
            m["name"], m["unit"], m["layer"], m["moves"])


CAL_TINY = (("sweep_dev_s", "refine_dev_s", "solve_ops_per_tile",
             "refine_passes", "recompiles_in_window", "compile_s.setup"),
            ("[scope] sage/sweep", "[scope] sage/refine", "sage/sweep/inner",
             "sage/refine/linesearch", "sage/refine/restrict",
             "[compile] set-up"))


@pytest.mark.parametrize("workload, seconds, metrics, tables", [
    ("predict-tiny", "5",
     ("phasor_dev_ms", "corrupt_dev_ms", "bubble_ms.predict",
      "recompiles_in_window", "compile_s.setup"),
     ("[scope] rime/phasor", "[span] sagecal/write", "[span] sagecal/fetch",
      "[compile] set-up")),
    ("cal-tiny", "5", *CAL_TINY),
    # far beyond the observation's nine window tiles AND its
    # profile_slice_s: the window ends with the observation.  On the
    # parent of PR 35 this run printed "no profiler trace in this run"
    # and had no busy_s, window_s, breakdown or device_idle_pct
    ("cal-tiny", "600", *CAL_TINY),
], ids=["predict-tiny", "cal-tiny", "cal-tiny-beyond-its-observation"])
def test_tiny_cell_traced_end_to_end(workload, seconds, metrics, tables):
    """A child, as the driver runs it: the profiler and the program's
    tracer are the process's own."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--cells", CELLS,
         "--workload", workload, "--seed", str(2 ** 31 + 26),
         "--seconds", seconds, "--trace", "1", "--allow-cpu"],
        capture_output=True, text=True, timeout=900, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    for name in metrics:
        # a value, or the reader's printed reason for having none
        assert name in line["metrics"] or "no scoped event" in out.stdout, name
    # on the CPU the HLO modules stored in the trace carry the scopes
    assert set(metrics) <= set(line["metrics"])
    for needle in tables:
        assert needle in out.stdout, needle
    m = line["metrics"]
    assert m["recompiles_in_window"]["value"] == 0 \
        == m["compiles_in_window"]["value"]
    assert m["compile_s.setup"]["value"] > 0
    # the profile: whole tiles from the end of the window, wherever the
    # window ended
    assert "no profiler trace in this run" not in out.stdout
    dev = line["device"]
    assert 0 < dev["busy_s"] < dev["window_s"] < float(seconds)
    assert line["breakdown"]["device_ops"] and line["breakdown"]["idle_gaps"]
    assert 0 <= m["device_idle_pct"]["value"] < 100
    if seconds == "600":
        assert line["attempted"] == 9
    # the line before the result line: every phase of the run, adding up
    clock = out.stdout.strip().splitlines()[-2]
    assert clock.startswith("[clock] backend ")
    phases, rest = clock[len("[clock] "):].split("; total ")
    for name in ("data", "warmup", "window", "drain", "stop_trace", "load",
                 "walk", "reduce", "check", "scopes", "readers"):
        assert f" {name} " in " " + phases, name
    assert sum(float(p.rsplit(" ", 1)[1]) for p in phases.split(", ")) \
        == pytest.approx(float(rest.split(" s")[0]), abs=0.1)
    assert "device_events" in rest and "device_planes 1" in rest
    if workload == "predict-tiny":
        assert m["bubble_ms.predict"]["value"] == pytest.approx(
            m["io_ms.predict"]["value"], rel=0.5)
