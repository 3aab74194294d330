"""``benchmarks/scopes.py`` and the eight metrics that read it (ISSUE 26).
By hand, with the rest of the benchmark's tests:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

The arithmetic on synthetic nested events (a scope's seconds are its
LEAF operations', counts, idle seconds inside a host span, selection by
``tm``), the HLO table read out of a hand-built trace file, what the
readers do on a program that has none of the names (the parent), and the
tiny rehearsal cells traced end to end.
"""

import json
import os
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
    names = [m["name"] for m in man["per_layer"]]
    assert tuple(names[-len(NEW):]) == NEW
    cal = {m["name"] for m in harness.Cell("cal-m8x3").metrics("per_layer")}
    pred = {m["name"]
            for m in harness.Cell("predict-m8x128").metrics("per_layer")}
    assert {"sweep_dev_s", "refine_dev_s", "solve_ops_per_tile"} <= cal - pred
    assert {"phasor_dev_ms", "corrupt_dev_ms",
            "bubble_ms.predict"} <= pred - cal
    assert {"recompiles_in_window", "compile_s.setup"} <= cal & pred
    for m in man["per_layer"][-len(NEW):]:
        mod = harness.load_module("layer_metrics", m["name"])
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
            m["name"], m["unit"], m["layer"], m["moves"])


@pytest.mark.parametrize("workload, metrics, tables", [
    ("predict-tiny", ("phasor_dev_ms", "corrupt_dev_ms", "bubble_ms.predict",
                      "recompiles_in_window", "compile_s.setup"),
     ("[scope] rime/phasor", "[span] sagecal/write", "[span] sagecal/fetch",
      "[compile] set-up")),
    ("cal-tiny", ("sweep_dev_s", "refine_dev_s", "solve_ops_per_tile",
                  "recompiles_in_window", "compile_s.setup"),
     ("[scope] sage/sweep", "[scope] sage/refine", "sage/sweep/inner",
      "sage/refine/linesearch", "[compile] set-up")),
])
def test_tiny_cell_traced_end_to_end(workload, metrics, tables):
    """A child, as the driver runs it: the profiler and the program's
    tracer are the process's own."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--cells", CELLS,
         "--workload", workload, "--seed", str(2 ** 31 + 26),
         "--seconds", "5", "--trace", "1", "--allow-cpu"],
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
    if workload == "predict-tiny":
        assert m["bubble_ms.predict"]["value"] == pytest.approx(
            m["io_ms.predict"]["value"], rel=0.5)
