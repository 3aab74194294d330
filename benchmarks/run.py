#!/usr/bin/env python3
"""Run ONE cell of the benchmark once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process.  It picks the platform and the compile cache as every entry
point of the program does (``sagecal_tpu.utils.setup_backend``: the TPU,
``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` is set,
f32 contractions in f32), makes the cell's data from ``--seed``, lets the
cell's driver warm up and drive the window, checks what the window wrote
against ``reference.py``, and prints one JSON line last.  Without a TPU
it exits 3 and prints no result, unless ``--allow-cpu`` asks for a
rehearsal, whose line says ``"platform": "cpu"``.

``--trace 0`` reports the cell's end-to-end metrics with every tracer
off.  ``--trace 1`` turns the program's ``--diag`` records on for the
whole run and the device profiler for a slice at the end of the window,
and reports the cell's per-layer metrics and a breakdown.  The trace is
loaded once and its device events are gone through once, after the
drain.  Either way the line before the result line is ``[clock]``: the
seconds of every phase of the run, which add up to its wall.
"""

import time

T_PROCESS_START = time.perf_counter()

import argparse     # noqa: E402
import contextlib   # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

import harness      # noqa: E402
import reference    # noqa: E402


class Run:
    """What a driver and the metric readers see of one run."""

    def __init__(self, cell: harness.Cell, seed: int, seconds: float,
                 trace: bool, clock: harness.Clock | None = None):
        self.cell, self.seed, self.trace = cell, int(seed), bool(trace)
        self.config, self.traffic = cell.config, cell.traffic
        self.work = harness.work_dir(cell.name)
        self.window = harness.Window(seconds, T_PROCESS_START)
        self.clock = clock or harness.Clock(T_PROCESS_START)
        self.obs = reference.Observation(cell.config, self.seed)
        self._log = open(os.path.join(self.work, "program.log"), "w")
        self.diag_path = os.path.join(self.work, "diag.jsonl")
        self.profile_dir = os.path.join(self.work, "profile")
        self.profile = None          # the slice's reduction, a dict
        self.slice = None            # scopes.Slice of the same one walk
        # the profiler: on from _prof_t0 to _prof_t1 on the window's clock
        self._prof_t0 = self._prof_t1 = None
        self.stop_trace_s = 0.0
        self.trace_path = None       # the trace file, once stopped
        self.slice_tiles = 0         # the tiles begun in it
        # a stop at a boundary (profile_tiles) takes its seconds there,
        # so the driver has to say that its boundary is in no span
        if self.traffic.get("profile_tiles") and not getattr(
                cell.driver, "BOUNDARY_OUTSIDE_SPANS", False):
            raise ValueError(
                f"{cell.entry['traffic']}.json sets profile_tiles, but "
                f"driver {self.traffic['driver']!r} does not say "
                f"BOUNDARY_OUTSIDE_SPANS: the profiler's stop would be "
                f"booked in whatever span holds its tile boundary")
        self._ann = None
        self.compiles = [None, None]
        self.counters = {}           # a driver's own numbers, by name
        self._diag = None

    # the program's chatter goes to a file: hundreds of tiles a run
    def log(self, *a):
        print(*a, file=self._log, flush=True)

    def _profiling(self) -> bool:
        return self._prof_t0 is not None and self._prof_t1 is None

    def annotate(self, name: str):
        """A span of the harness's own in the profiler's trace (a null
        context when the profiler is off)."""
        if not self._profiling():
            return contextlib.nullcontext()
        import jax.profiler
        return jax.profiler.TraceAnnotation(name)

    def _close_annotation(self):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None

    def enter_tile(self, tile: int, n_vis: int,
                   left: int | None = None) -> None:
        """A tile boundary of the window: the driver calls this as the
        tile's cycle begins, after asking ``window.due()``, and outside
        any span of the program's where it can: the profiler is started
        here and, with ``profile_tiles``, stopped here.  A driver whose
        observation ends says how many tiles it has ``left`` AFTER this
        one; one that cycles its tiles says nothing."""
        self._close_annotation()
        w = self.window
        if w.t_open is None:
            from sagecal_tpu.diag import guard
            self.compiles[0] = guard.compile_count()
        elif self.trace and self._prof_t0 is None:
            if self._slice_is_next(left):
                import jax.profiler
                jax.profiler.start_trace(self.profile_dir)
                self._prof_t0 = w.clock()
        elif self._profiling() and (
                self.slice_tiles == self.traffic.get("profile_tiles")):
            self._stop_profile()
        now = w.enter(tile, n_vis)
        if len(w.entries) == 1:
            self.clock.mark("warmup", at=now)
        if self._profiling():
            self.slice_tiles += 1
            self._ann = self.annotate("tile_cycle")
            self._ann.__enter__()

    def _slice_is_next(self, left: int | None) -> bool:
        """At a tile boundary: whether the profiler should start now so
        that it holds about the mix's ``profile_slice_s`` last seconds
        of the window, in whole tiles.  True once a cycle as long as the
        last one would end inside those seconds.  The window ends at its
        ``seconds`` or, where the observation has only ``left`` tiles
        after this one, when those have run at the last cycle's pace,
        whichever comes first: at the observation's last tile at the
        latest."""
        w = self.window
        now, last_entry = w.clock(), w.entries[-1][1]
        elapsed, t_last = now - w.t_open, now - last_entry
        end = w.seconds
        if left is not None:
            end = min(end, elapsed + (left + 1) * t_last)
        return elapsed + t_last >= end - float(
            self.traffic["profile_slice_s"])

    def _stop_profile(self) -> None:
        """At the drain, or where the mix says ``profile_tiles`` at the
        boundary that many tiles on (``enter_tile``)."""
        import xplane
        self._prof_t1 = self.window.clock()
        self.trace_path, how = xplane.stop_session(self.profile_dir)
        self.stop_trace_s = self.window.clock() - self._prof_t1
        self.clock.notes["stopped_by"] = how

    def drain(self) -> None:
        """The writer has drained: the window ends here.  Whatever is
        read of the trace is read from here on."""
        self._close_annotation()
        w, c = self.window, self.clock
        w.drain()
        from sagecal_tpu.diag import guard
        self.compiles[1] = guard.compile_count()
        # a window that ran out of tiles before its seconds has no
        # closing boundary: all of it is the window
        c.mark("window", at=w.t_drain if w.t_due is None else w.t_due)
        c.mark("drain", at=w.t_drain)
        c.notes["tiles"] = len(w.entries)
        if self._prof_t0 is None:
            return
        if self._profiling():
            self._stop_profile()
        else:
            # stopped at a boundary of the window: those seconds are
            # the window's
            c.notes["stop_trace_in_window_s"] = round(self.stop_trace_s, 2)
        c.mark("stop_trace")
        self._read_profile()

    def _read_profile(self) -> None:
        """Load the trace once and go through it once."""
        import scopes
        import xplane
        c = self.clock
        path = self.trace_path
        pd = xplane.load(path)
        c.mark("load")
        self.slice = scopes.Slice(path, pd)
        c.carve("hlo_table", self.slice.table_s)
        c.mark("walk")
        self.profile = self.slice.profile.reduce()
        self.profile["window_s"] = self._prof_t1 - self._prof_t0
        self.profile["slice_tiles"] = self.slice_tiles
        c.mark("reduce")
        c.notes["device_events"] = self.profile["n_device_events"]
        c.notes["device_planes"] = len(self.profile["devices"])
        c.notes["host_events"] = self.slice.profile.n_host_events
        c.notes["trace_mb"] = round(os.path.getsize(path) / 1e6, 1)

    def diag_records(self):
        """The program's ``--diag`` records of the window's tiles (the
        traced run only; read once, after the tracer is closed)."""
        if self._diag is None:
            from sagecal_tpu.diag import trace as dtrace
            tiles = set(self.window.tiles)
            self._diag = [r for r in dtrace.read(self.diag_path)
                          if r.get("tile") in tiles] \
                if os.path.exists(self.diag_path) else []
        return self._diag


def open_backend(allow_cpu: bool, chips: int):
    """The platform and compile cache as every entry point of the program
    picks them, then the look for the chip: JAX's devices, or None (said
    on stderr) without a TPU of a kind ``peaks.json`` knows, or with
    fewer chips than the cell asks for."""
    from sagecal_tpu import utils
    utils.setup_backend("cpu" if allow_cpu else None)
    import jax
    devices = jax.devices()
    kind = devices[0].device_kind
    if devices[0].platform != "tpu":
        if allow_cpu:
            return devices
        print(f"no TPU: JAX found platform {devices[0].platform!r}; "
              "--allow-cpu rehearses on the CPU", file=sys.stderr)
    elif len(devices) < chips:
        print(f"the cell needs {chips} chip(s), JAX found {len(devices)}",
              file=sys.stderr)
    elif kind not in harness.load_json(HERE, "peaks.json"):
        print(f"device kind {kind!r} is not in benchmarks/peaks.json",
              file=sys.stderr)
    else:
        return devices
    return None


def device_block(run: Run, devices) -> dict:
    stats = [d.memory_stats() or {} for d in devices[:run.cell.chips]]
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices),
           "memory_peak_bytes": max(
               int(s.get("peak_bytes_in_use", 0)) for s in stats)}
    if run.profile is not None:
        dev["busy_s"] = run.profile["busy_s"]
        dev["window_s"] = run.profile["window_s"]
    return dev


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse on the CPU platform; never a result "
                         "about the device")
    ap.add_argument("--cells", default=None,
                    help="a file of further configs and workloads (the "
                         "tiny rehearsal cells under benchmarks/tests)")
    ap.add_argument("--precision", default="highest",
                    choices=("highest", "high", "default"),
                    help="the control: multiply f32 contractions in "
                         "fewer bf16 passes than the configuration "
                         "states; the line then says so")
    args = ap.parse_args(argv)

    clock = harness.Clock(T_PROCESS_START)
    cell = harness.Cell(args.workload,
                        args.cells and harness.load_json(args.cells))
    devices = open_backend(args.allow_cpu, cell.chips)
    if devices is None:
        return 3
    clock.mark("backend")
    import jax
    if args.precision != "highest":
        jax.config.update("jax_default_matmul_precision", args.precision)

    run = Run(cell, args.seed, args.seconds, bool(args.trace), clock)
    if run.trace:
        from sagecal_tpu.diag import trace as dtrace
        dtrace.enable(run.diag_path, entry="benchmarks/run.py",
                      argv=sys.argv[1:])
    try:
        outcome = cell.driver.run(run)
    finally:
        if run.trace:
            dtrace.disable()

    # outside the window and outside setup_s: the reference
    t_check = time.perf_counter()
    checks = cell.driver.check(run)
    for c in checks:
        print(c.line())
    longest = sorted(zip(run.window.tile_seconds(), run.window.tiles),
                     reverse=True)[:5]
    print("[window] longest cycles: " + ", ".join(
        f"{s:.4g} s (tile {t})" for s, t in longest))
    print(f"[check] reference took {time.perf_counter() - t_check:.2f} s; "
          f"window {run.window.length_s():.2f} s, "
          f"{len(run.window.entries)} tiles; compile requests in the "
          f"window: {run.compiles[1] - run.compiles[0]}")
    clock.mark("check")

    if run.trace:
        import scopes
        scopes.load(run)            # prints the [scope] table
        clock.mark("scopes")
        metrics = {}
        for m in cell.metrics("per_layer"):
            value = harness.load_module("layer_metrics",
                                        m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        clock.mark("readers")
    else:
        e2e = run.window.end_to_end()
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.metrics("end_to_end")}

    result = {
        "correct": bool(checks) and all(c.ok for c in checks),
        "attempted": outcome["attempted"], "failed": outcome["failed"],
        "metrics": metrics, "device": device_block(run, devices),
        "workload": cell.name, "seed": run.seed,
    }
    if args.precision != "highest":
        result["control"] = f"matmul precision {args.precision}"
    if run.profile is not None:
        result["breakdown"] = {
            "device_ops": run.profile["device_ops"][:10],
            "idle_gaps": run.profile["idle_gaps"][:10]}
    # each number compared beside its limit: last in the line, and the
    # last lines on standard error
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    print(clock.line())
    print(json.dumps(result))
    sys.stdout.flush()
    for c in checks:
        print(c.line(note=False), file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
