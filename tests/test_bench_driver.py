"""bench.py driver control flow: a run is on the chip, or on the CPU
because ``--cpu`` asked for it.

No subprocesses and no device work in the control-flow cases —
``device_platform`` and ``run_config_subprocess`` are stubbed:
 1. no chip and no ``--cpu`` exits non-zero before any config runs;
 2. ``--cpu`` runs label every record ``cpu``;
 3. a config that fails on the chip is recorded as failed and is not
    re-run on the CPU;
 4. ``JAX_COMPILATION_CACHE_DIR`` from the environment is what a child
    uses (utils.setup_backend sets no other directory).
Plus the two refusals that keep the device visible on the main path:
``--kernel pallas`` on a ``tpu`` backend, and a failing coherency kernel
raising instead of falling back to the XLA path.
"""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import bench  # noqa: E402


@pytest.fixture
def sandbox(monkeypatch, tmp_path):
    """Redirect every file bench.main() touches into tmp_path."""
    monkeypatch.setattr(bench, "HERE", str(tmp_path))
    monkeypatch.setattr(bench, "_ROUND_STAMP", {})
    monkeypatch.setattr(bench, "_LIVE_GUARD", {})
    monkeypatch.setattr(sys, "argv", ["bench.py", "--budget", "1700"])
    monkeypatch.delenv("SAGECAL_BENCH_CPU", raising=False)
    monkeypatch.delenv("SAGECAL_BENCH_OVERWRITE", raising=False)
    monkeypatch.delenv("SAGECAL_BENCH_ROUND", raising=False)
    return tmp_path


def _drive(monkeypatch, sandbox, *, platform, result, argv=()):
    """Run bench.main() with a stubbed platform answer and config child.
    ``result(name, cpu)`` -> the child's record. Returns (calls,
    results) where calls is [(name, cpu), ...]."""
    calls = []

    def fake_run(name, timeout_s=570, cpu=False):
        calls.append((name, cpu))
        return dict(result(name, cpu))

    monkeypatch.setattr(bench, "device_platform", lambda **kw: platform)
    monkeypatch.setattr(bench, "run_config_subprocess", fake_run)
    monkeypatch.setattr(sys, "argv", sys.argv + list(argv))
    bench.main()
    with open(sandbox / "bench_results.json") as f:
        return calls, json.load(f)


def _ok(platform):
    return {"value": 100.0, "unit": "vis/s", "platform": platform,
            "res_0": 1.0, "res_1": 0.1}


def test_no_chip_and_no_cpu_flag_exits_nonzero(monkeypatch, sandbox,
                                               capsys):
    """Nothing runs, nothing is written and no result line is printed
    when JAX finds no TPU and --cpu was not given."""
    calls = []
    monkeypatch.setattr(bench, "device_platform", lambda **kw: "cpu")
    monkeypatch.setattr(bench, "run_config_subprocess",
                        lambda *a, **k: calls.append(a) or _ok("cpu"))
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code not in (0, None)
    assert calls == []
    assert not (sandbox / "bench_results.json").exists()
    assert capsys.readouterr().out.strip() == ""


def test_cpu_flag_labels_every_record_cpu(monkeypatch, sandbox, capsys):
    calls, rec = _drive(monkeypatch, sandbox, platform="tpu",
                        result=lambda n, cpu: _ok("cpu" if cpu else "tpu"),
                        argv=["--cpu"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert all(cpu for _, cpu in calls)
    assert [n for n, _ in calls] == [n for n, _ in bench.CONFIGS]
    assert rec["platform"] == "cpu" and out["device"] == "cpu"
    assert all(r["platform"] == "cpu" for r in rec["results"].values())


def test_chip_failure_is_recorded_not_rerun_on_cpu(monkeypatch, sandbox,
                                                   capsys):
    def result(name, cpu):
        if name == "3-rtr-16cluster":
            return {"error": "rc=1: kernel fault"}
        return _ok("tpu")

    calls, rec = _drive(monkeypatch, sandbox, platform="tpu",
                        result=result)
    capsys.readouterr()
    # every config ran exactly once, on the chip; none on the CPU
    assert calls == [(n, False) for n, _ in bench.CONFIGS]
    assert "error" in rec["results"]["3-rtr-16cluster"]
    assert rec["platform"] == "tpu"
    assert all(r.get("platform") == "tpu"
               for n, r in rec["results"].items()
               if n != "3-rtr-16cluster")


def test_child_uses_the_environments_compile_cache(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, a child (here: the same
    setup_backend call bench.run_one_config makes, in a fresh process)
    leaves JAX's own reading of it standing; unset, the cache goes to
    the fixed <checkout>/.jax_cache."""
    src = ("from sagecal_tpu import utils; import jax; "
           "print(utils.setup_backend('cpu')); "
           "print(jax.config.jax_compilation_cache_dir)")
    root = os.path.join(os.path.dirname(__file__), "..")

    def child(env_dir):
        env = dict(os.environ)
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        if env_dir:
            env["JAX_COMPILATION_CACHE_DIR"] = env_dir
        r = subprocess.run([sys.executable, "-c", src], cwd=root, env=env,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]
        return r.stdout.split()

    assert child(str(tmp_path)) == [str(tmp_path)] * 2
    got = child(None)
    assert got[0] == got[1]
    assert os.path.dirname(got[0]) == os.path.join(
        os.path.realpath(root), ".jax_cache")


def test_kernel_pallas_refused_on_tpu_backend(monkeypatch):
    """The fused sweep's TPU compile never returns, so --kernel pallas
    is refused before any solve when the backend reports tpu; on CPU
    (the interpreter path) the same call passes."""
    from sagecal_tpu.ops import sweep_pallas
    sweep_pallas.check_kernel("pallas")          # cpu: fine
    monkeypatch.setattr(sweep_pallas.jax, "default_backend",
                        lambda: "tpu")
    sweep_pallas.check_kernel("xla")
    with pytest.raises(ValueError, match="does not compile under Mosaic"):
        sweep_pallas.check_kernel("pallas")


def test_coherency_kernel_failure_raises_no_fallback(monkeypatch,
                                                     tmp_path):
    """On a tpu platform the Pallas coherency path is CHOSEN, not
    probed: when the kernel call fails (here: a compiled pallas_call
    cannot run on the CPU backend) the solve raises; it never carries
    on with the XLA path."""
    import jax.numpy as jnp
    from sagecal_tpu import pipeline, skymodel
    from sagecal_tpu.config import RunConfig
    from sagecal_tpu.io import dataset as ds
    from sagecal_tpu.rime import predict as rp
    sky_p = tmp_path / "sky.txt"
    sky_p.write_text("P0A 0 0 0.0 40 0 0.0 1.0 0 0 0 0 0 0 0 0 150e6\n")
    (tmp_path / "sky.txt.cluster").write_text("0 1 P0A\n")
    sky = skymodel.read_sky_cluster(str(sky_p), str(sky_p) + ".cluster",
                                    0.0, 0.7, 150e6)
    tile = ds.simulate_dataset(rp.sky_to_device(sky, jnp.float32),
                               n_stations=5, tilesz=2, freqs=[150e6],
                               ra0=0.0, dec0=0.7)
    ms = ds.SimMS.create(str(tmp_path / "a.ms"), [tile])
    cfg = RunConfig(ms=str(tmp_path / "a.ms"), sky_model=str(sky_p),
                    cluster_file=str(sky_p) + ".cluster", tile_size=2)
    monkeypatch.setattr(pipeline, "_device_platform", lambda: "tpu")
    lines = []
    pipe = pipeline.FullBatchPipeline(cfg, ms, sky, real_dtype=jnp.float32,
                                      log=lines.append)
    assert pipe.use_pallas
    assert "Coherency path: pallas" in lines
    with pytest.raises(Exception):
        pipe.run(log=lines.append)
    assert not any("XLA path" in ln for ln in lines)


def test_bank_vs_live_hygiene(sandbox):
    """A live run always writes its round-stamped record and refuses to
    overwrite a committed table/record from a DIFFERENT backend
    (VERDICT r5 weak #7: a CPU-fallback driver run shadowed the banked
    TPU record on disk)."""
    json.dump({"platform": "tpu",
               "results": {"1-fullbatch-lm": {"value": 2878.5,
                                              "unit": "vis/s"}}},
              open(sandbox / "bench_results.json", "w"))
    res = {"1-fullbatch-lm": {"value": 300.0, "unit": "vis/s",
                              "platform": "cpu", "shape": "x"}}
    bench.write_table(res, "cpu", stamp=True)
    with open(sandbox / "bench_results.json") as f:
        live = json.load(f)
    assert live["platform"] == "tpu"                    # bank preserved
    assert live["results"]["1-fullbatch-lm"]["value"] == 2878.5
    stamped = sorted(sandbox.glob("BENCH_CPU_r*.json"))
    assert stamped, "round-stamped record must exist"
    with open(stamped[-1]) as f:
        rec = json.load(f)
    assert rec["results"]["1-fullbatch-lm"]["value"] == 300.0
    # same-backend runs keep overwriting the live record as before
    bench.write_table(res, "tpu", stamp=True)
    with open(sandbox / "bench_results.json") as f:
        assert json.load(f)["results"]["1-fullbatch-lm"]["value"] == 300.0


def test_round_stamp_increments_and_pins(sandbox):
    json.dump({"platform": "cpu", "results": {}},
              open(sandbox / "BENCH_CPU_r07.json", "w"))
    p = bench._stamp_path("cpu")
    assert p.endswith("BENCH_CPU_r08.json")
    assert bench._stamp_path("cpu") == p       # pinned per process


def test_bytes_baseline_stamped_records_win(sandbox):
    """Round-stamped records are the ONLY bank once one exists: the live
    ``bench_results.json`` is overwritten by every run — including
    discarded trials — so it must never shadow a committed stamped
    record (the round-7 Δbytes-poisoning fix). It remains the
    first-round bootstrap when no stamped record exists."""
    json.dump({"platform": "cpu",
               "results": {"1-fullbatch-lm": {"bytes_accessed": 4.4e10}}},
              open(sandbox / "bench_results.json", "w"))
    # bootstrap: no stamped record yet -> the live record is the bank
    assert bench._bytes_baseline("cpu") == {"1-fullbatch-lm": 4.4e10}
    # a stamped record exists (even without usable bytes): the live
    # record is no longer consulted
    json.dump({"platform": "cpu",
               "results": {"1-fullbatch-lm": {"bytes_accessed": None}}},
              open(sandbox / "BENCH_CPU_r05.json", "w"))
    assert bench._bytes_baseline("cpu") == {}
    # the newest stamped record carrying bytes wins
    json.dump({"platform": "cpu",
               "results": {"1-fullbatch-lm": {"bytes_accessed": 3.3e10}}},
              open(sandbox / "BENCH_CPU_r06.json", "w"))
    assert bench._bytes_baseline("cpu") == {"1-fullbatch-lm": 3.3e10}
    assert bench._bytes_baseline("tpu") == {}
