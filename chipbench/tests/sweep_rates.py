"""Find the knee of an open-loop serving cell: one sweep on the chip.

    python3 chipbench/tests/sweep_rates.py --workload <cell> --seed <n> \
        --seconds <window> --rates 2,3,4,5

Builds the cell's server once (as ``run.py`` does), then for each rate
offers a ramp and one window of that rate with the cell's own mix and
drains the server before the next. Prints one JSON line per rate: the
backlog in the middle and at the end of the window, requests due and
completed, the tails. The knee is the highest rate at which the backlog
at the end is no larger than in the middle and every due request
completes; the cell's ``rate_per_s`` is 4/5 of it, written into the
traffic file by hand. A later benchmark PR finds the knee again with
this script after the program has moved it.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "chipbench"))


def main():
    import run
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    _bench, cell, config, mix, extra = run.load_cell(args.workload,
                                                     args.rehearse)
    devices = run.gate_devices(int(cell["chips"]), args.rehearse)
    drv = run.load_module("drivers", mix["driver"])
    from paddlefleetx_tpu.utils.env import setup_compilation_cache
    setup_compilation_cache()
    from chipbench import traffic_gen
    ctx = run.Context(config=config, mix=mix, seed=args.seed,
                      seconds=args.seconds, root=ROOT, devices=devices,
                      extra_overrides=extra, trace=False, control=None)
    srv, mcfg, _abstract, _dtype = drv.build(ctx)
    pct = traffic_gen.percentile
    try:
        drv.warm(ctx, srv, mcfg.vocab_size)
        for rate in [float(x) for x in args.rates.split(",")]:
            m = dict(mix, rate_per_s=rate)
            t_open = time.time() + float(m["ramp_s"]) + 0.2
            loop = drv.Loop(srv, traffic_gen.open_loop_blocks(
                m, args.seed, mcfg.vocab_size, args.seconds), t_open)
            loop.run_until(lambda now: now >= t_open)
            tokens0 = loop.reg.counter("serving/decode_tokens")
            loop.run_until(lambda now: now >= t_open + args.seconds / 2)
            mid = srv.pending
            end = loop.run_until(lambda now: now >= t_open + args.seconds)
            backlog = srv.pending
            tokens = loop.reg.counter("serving/decode_tokens") - tokens0
            loop.next = (float("inf"), [])          # arrivals stop
            limit = end + 120.0
            loop.run_until(lambda now: now >= limit
                           or not srv.work_pending())
            due = [r for r in loop.reqs.values()
                   if t_open <= r["due"] < t_open + args.seconds]
            ok = [r for r in due if r["completion"] is not None
                  and r["completion"].ttft_ms is not None]
            ttft = [(r["submitted"] - r["due"]) * 1e3
                    + r["completion"].ttft_ms for r in ok]
            tpot = [(r["seen"] - r["submitted"]
                     - r["completion"].ttft_ms / 1e3) * 1e3
                    / max(1, len(r["completion"].tokens) - 1) for r in ok]
            rt = [s * 1e3 for _, s in loop.roundtrips]
            print(json.dumps({
                "rate_per_s": rate, "window_s": end - t_open,
                "due": len(due), "completed": len(ok),
                "backlog_mid": mid, "backlog_end": backlog,
                "tokens_per_s": tokens / (end - t_open),
                "ttft_p50_ms": pct(ttft, 50), "ttft_p95_ms": pct(ttft, 95),
                "tpot_p50_ms": pct(tpot, 50), "tpot_p95_ms": pct(tpot, 95),
                "roundtrip_p50_ms": pct(rt, 50), "drain_s":
                time.time() - end}), flush=True)
    finally:
        srv.close()


if __name__ == "__main__":
    main()
