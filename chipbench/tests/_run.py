"""Helpers shared by the tests: run ``chipbench/run.py`` as the driver
does, in a process of its own, and read what it printed."""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(workload, seed=7, seconds=2, trace=0, extra=(), script=None,
             timeout=600):
    """``(returncode, json lines printed, last line as text)`` of one
    rehearsed run."""
    cmd = [sys.executable, script or os.path.join(ROOT, "chipbench", "run.py"),
           "--workload", workload, "--seed", str(seed), "--seconds",
           str(seconds), "--trace", str(trace), "--rehearse", *extra]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=timeout)
    lines = []
    for ln in p.stdout.splitlines():
        if ln.startswith("{"):
            try:
                lines.append(json.loads(ln))
            except ValueError:
                pass
    last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
    return p.returncode, lines, last, p.stderr


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
