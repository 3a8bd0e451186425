"""TIPC-style benchmark driver.

Parity: reference ``benchmarks/test_tipc/gpt/hybrid_parallel/
benchmark_common/run_benchmark.sh`` — build an ``-o`` override list
for a topology, run training for a few hundred steps, grep the logs
for the throughput keyword (``ips_total:`` tokens/s) and the
convergence keyword (``loss:``), and emit a summary record. Topology
scripts under ``benchmarks/test_tipc/`` call this driver exactly like
the reference's per-topology shells call run_benchmark.sh.

Runs on whatever platform jax sees; pass ``--cpu-devices N`` to force
the N-device virtual CPU mesh (topology correctness runs without a
pod, SURVEY §4).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

IPS_RE = re.compile(r"ips_total: (\d+) tokens/s")
LOSS_RE = re.compile(r"loss: ([\d.]+)")


def get_args(argv=None):
    """Parse the TIPC-style benchmark CLI."""
    p = argparse.ArgumentParser()
    p.add_argument("--model_item", default="gpt_345M")
    p.add_argument("--config", required=True)
    p.add_argument("--overrides", nargs="*", default=[],
                   action="extend",
                   help="-o style dotted overrides; repeatable — the "
                        "TIPC scripts pass their topology overrides "
                        "and forward \"$@\" so callers can APPEND "
                        "more (a second flag must not replace the "
                        "first)")
    p.add_argument("--max_steps", type=int, default=100)
    p.add_argument("--skip_steps", type=int, default=2,
                   help="warmup log lines excluded from the ips average")
    p.add_argument("--cpu-devices", type=int, default=0,
                   help="force an N-device virtual CPU mesh")
    p.add_argument("--log_file", default=None)
    p.add_argument("--speed_unit", default="tokens/s")
    return p.parse_args(argv)


def run(args) -> dict:
    """Run tools/train.py with the benchmark overrides and scrape
    ips/loss from its log into the result dict."""
    cmd = [sys.executable, os.path.join(REPO, "tools", "train.py"),
           "-c", args.config,
           "-o", f"Engine.max_steps={args.max_steps}"]
    for ov in args.overrides:
        cmd += ["-o", ov]
    env = dict(os.environ)
    if args.cpu_devices:
        # tools/train.py routes this through jax.config
        env["PFX_CPU_DEVICES"] = str(args.cpu_devices)
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          cwd=REPO)
    log = proc.stdout + proc.stderr
    if args.log_file:
        with open(args.log_file, "w") as f:
            f.write(log)

    ips = [int(m) for m in IPS_RE.findall(log)]
    losses = [float(m) for m in LOSS_RE.findall(log)]
    steady = ips[args.skip_steps:] or ips
    result = {
        "model_item": args.model_item,
        "ok": proc.returncode == 0 and bool(ips),
        "ips": round(sum(steady) / len(steady), 1) if steady else 0.0,
        "speed_unit": args.speed_unit,
        "first_loss": losses[0] if losses else None,
        "last_loss": losses[-1] if losses else None,
        "converging": bool(losses) and losses[-1] <= losses[0],
    }
    if not result["ok"]:
        result["tail"] = log[-2000:]
    return result


def main(argv=None):
    args = get_args(argv)
    result = run(args)
    print(json.dumps(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
