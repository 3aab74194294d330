"""Calibration-as-a-service: a persistent multi-tenant job server.

The batch pipeline solves one MS per process and throws every compiled
program away at exit. This package keeps the device busy across *jobs*:

- :mod:`sagecal_tpu.serve.cache` — the process-wide compile cache keyed
  by shape-bucket + solver flags, so concurrent jobs with
  bucket-compatible shapes share warm-compiled programs (hits are
  assertable via the ``diag.guard`` compile counter);
- :mod:`sagecal_tpu.serve.queue` — job registry + FIFO-with-priorities
  queue with admission control (bounded in-flight jobs and bounded
  staged bytes) and fail-stop per-job isolation;
- :mod:`sagecal_tpu.serve.scheduler` — device-owner loops (one per
  fleet device) that interleave ready tiles from many jobs through
  per-job ``sched.Prefetcher`` instances and one ordered
  ``sched.AsyncWriter`` per job, preserving each job's sequential
  warm-start/PRNG chain (per-job outputs are bit-identical to a solo
  CLI run), with tile-boundary migration/work-stealing between
  devices;
- :mod:`sagecal_tpu.serve.fleet` — device scopes, shape-bucket
  affinity tokens and the placement layer (``--devices N``);
- :mod:`sagecal_tpu.serve.loadgen` — the seedable traffic-replay
  load generator (open-loop arrival processes, job templates);
- :mod:`sagecal_tpu.serve.api` — a zero-dependency JSON-lines protocol
  over a local socket (submit/status/cancel/migrate/drain/metrics)
  with graceful drain on SIGTERM, and a client with persistent
  connections + request pipelining;
- :mod:`sagecal_tpu.serve.router` — the CROSS-PROCESS fleet: a router
  front-end speaking the same API over worker daemons (``--worker
  --router ADDR``) with a leased worker registry, bucket-affinity
  routing over reported compile-cache inventories, and
  checkpoint-based cross-process migration / worker-loss recovery
  (zero completed tiles re-run, bit-identical outputs).

Run it: ``python -m sagecal_tpu.serve --socket /tmp/sagecal.sock``.
See MIGRATION.md "Service mode" / "Fleet mode" / "Multi-process
fleet" for the protocol and the per-job bit-identity / bucketing /
migration contracts.
"""

from sagecal_tpu.serve import cache  # noqa: F401
