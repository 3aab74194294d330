"""The benchmark's own tests (``benchmarks/tests``: the yardstick, the
tiny rehearsal cells, the controls that have to come out not correct)
are not collected by this suite: they build whole tiny runs, share one
work directory and take minutes.  This runs them once, in a process of
its own with a time limit of its own, so that a PR's test run guards the
harness too."""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIMIT_S = 900       # 140 s alone on this sandbox (PR 30), beside 5 workers


def test_the_benchmarks_own_tests_pass():
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_")}       # not a worker of ours
    env["JAX_PLATFORMS"] = "cpu"
    run = subprocess.run(
        [sys.executable, "-m", "pytest", "benchmarks/tests", "-q", "-x",
         "-p", "no:cacheprovider", "-p", "no:randomly"],
        cwd=ROOT, env=env, timeout=LIMIT_S, text=True,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    assert run.returncode == 0, run.stdout[-4000:]
    assert " passed" in run.stdout.splitlines()[-1]
