"""``python -m sagecal_tpu.serve``: the calibration job server.

Example::

    python -m sagecal_tpu.serve --socket /tmp/sagecal.sock &
    printf '%s\\n' '{"op": "submit", "config": {"ms": "sim.ms", \
"sky_model": "sky.txt", "cluster_file": "sky.txt.cluster"}}' \
        | nc -U /tmp/sagecal.sock

SIGTERM drains gracefully: in-flight tiles finish, writers flush, new
submissions are refused, then the process exits.
"""

from __future__ import annotations

import argparse
import signal
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m sagecal_tpu.serve",
        description="persistent multi-tenant calibration job server "
                    "(JSON-lines over a local socket)")
    g = p.add_mutually_exclusive_group(required=True)
    g.add_argument("--socket", metavar="PATH",
                   help="unix socket path to listen on")
    g.add_argument("--port", type=int,
                   help="TCP port on 127.0.0.1 (0 = ephemeral)")
    p.add_argument("--devices", type=int, default=1, metavar="N",
                   help="fleet size: one device-owner loop per device, "
                        "jobs routed by shape-bucket affinity, "
                        "tile-boundary migration/work-stealing between "
                        "devices (0 = every visible device; default 1 "
                        "= the single-device daemon, bit-identical to "
                        "pre-fleet behavior)")
    p.add_argument("--max-inflight", type=int, default=2,
                   help="concurrently RUNNING jobs PER DEVICE "
                        "(admission control; queued jobs wait)")
    p.add_argument("--max-staged-bytes", type=int, default=2 << 30,
                   help="staged-tile byte budget across running jobs, "
                        "PER DEVICE (each job stages ~(prefetch+3) "
                        "tiles)")
    p.add_argument("--diag", default=None, metavar="PATH",
                   help="server-level JSONL trace (per-job traces come "
                        "from each submit's 'trace' field)")
    p.add_argument("--metrics-port", type=int, default=None,
                   metavar="PORT",
                   help="serve Prometheus /metrics and /healthz over "
                        "HTTP on 127.0.0.1:PORT (0 = ephemeral; "
                        "default: no HTTP endpoint — the JSON-lines "
                        "'metrics'/'metrics_full' ops always work)")
    p.add_argument("--worker", action="store_true",
                   help="run as a FLEET WORKER: serve jobs as usual "
                        "AND register with the --router front-end "
                        "over one persistent control connection "
                        "(leased heartbeats carrying job snapshots + "
                        "compile-cache bucket inventory; MIGRATION.md "
                        "'Multi-process fleet')")
    p.add_argument("--router", default=None, metavar="ADDR",
                   help="router control address: HOST:PORT or a unix "
                        "socket path (requires --worker)")
    p.add_argument("--worker-id", default=None, metavar="ID",
                   help="stable worker identity (default "
                        "w-<hostname>-<pid>)")
    p.add_argument("--faults", default=None, metavar="SPEC",
                   help="deterministic fault-injection plan "
                        "(sagecal_tpu.faults.enable_spec — process-"
                        "global, so meant for dedicated worker "
                        "processes: the worker_crash chaos point "
                        "lives behind it)")
    p.add_argument("--platform", default=None,
                   help="force the jax platform (e.g. 'cpu')")
    p.add_argument("--cpu-devices", type=int, default=None,
                   metavar="N",
                   help="request N virtual CPU devices (with "
                        "--platform cpu: the fleet substrate on a "
                        "chipless host; must land before first device "
                        "use, same as the solo CLIs)")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if bool(args.worker) != (args.router is not None):
        raise SystemExit("--worker and --router ADDR go together")
    if args.faults:
        from sagecal_tpu import faults
        faults.enable_spec(args.faults)
    from sagecal_tpu import utils
    utils.setup_backend(args.platform, args.cpu_devices)
    if args.diag:
        from sagecal_tpu.diag import trace as dtrace
        dtrace.enable(args.diag, entry="sagecal-serve",
                      argv=list(argv) if argv is not None
                      else sys.argv[1:])
    from sagecal_tpu.serve.api import Server
    srv = Server(socket_path=args.socket, port=args.port,
                 max_inflight=args.max_inflight,
                 max_staged_bytes=args.max_staged_bytes,
                 metrics_port=args.metrics_port,
                 devices=args.devices)
    # graceful drain on SIGTERM/SIGINT: finish in-flight tiles, flush
    # writers, refuse new submissions, exit when idle
    signal.signal(signal.SIGTERM, lambda *a: srv.drain())
    signal.signal(signal.SIGINT, lambda *a: srv.drain())
    srv.start()
    where = args.socket or f"127.0.0.1:{srv.port}"
    print(f"sagecal-serve: listening on {where} "
          f"(devices={len(srv.scheduler.workers)}, "
          f"max_inflight={args.max_inflight}/device)", flush=True)
    agent = None
    if args.worker:
        # the job API is live (srv.port resolved), so register now;
        # the agent heartbeats at the router-granted cadence until
        # drain
        from sagecal_tpu.serve.router import WorkerAgent
        agent = WorkerAgent(srv, args.router, worker_id=args.worker_id)
        agent.start()
        print(f"sagecal-serve: worker {agent.worker_id} -> router "
              f"{args.router}", flush=True)
    try:
        srv.serve_forever()
    finally:
        if agent is not None:
            agent.stop()
        if args.diag:
            dtrace.disable()
    return 0


if __name__ == "__main__":
    sys.exit(main())
