"""Thin fleet-serving entrypoint: a FleetRouter demo you can scrape.

Ad hoc: ``python scripts/serve_fleet.py --replicas 2 --requests 24``
builds N interpret-friendly GenerationServer replicas behind a
prefix-affinity FleetRouter (core/fleet.py), feeds them a seeded
mixed-prefix trace (a few "system prompts" shared by many requests —
the fleet workload shape), optionally performs a rolling restart
mid-run, and prints the fleet summary as JSON. Set
``PFX_METRICS_PORT`` to also expose the live ``/metrics`` +
aggregated ``/healthz`` endpoints while it runs
(docs/fleet_serving.md).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # repo root, from any cwd


def build_trace(num_requests: int, num_prefixes: int, prefix_len: int,
                tail_len: int, vocab: int, seed: int):
    """A seeded fleet-shaped trace: every request is one of
    ``num_prefixes`` shared system prompts plus a per-request tail."""
    import numpy as np
    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(1, vocab - 2, prefix_len).tolist()
                for _ in range(num_prefixes)]
    prompts = []
    for i in range(num_requests):
        tail = rng.integers(1, vocab - 2, tail_len).tolist()
        prompts.append(prefixes[i % num_prefixes] + tail)
    return prompts


def main() -> int:
    """Build the fleet, serve the trace, print the summary JSON."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--prefill-replicas", type=int, default=0,
                    help="first K replicas take the prefill role "
                         "(0 = mixed fleet)")
    ap.add_argument("--handoff", choices=("device", "host"),
                    default="device")
    ap.add_argument("--slots", type=int, default=2,
                    help="slots per replica")
    ap.add_argument("--page-size", type=int, default=128,
                    help="KV page size (0 = contiguous slots; paged "
                         "is required for prefix affinity and "
                         "prefill/decode split)")
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="pages per replica pool (0 = server default)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--prefixes", type=int, default=2,
                    help="distinct shared system prompts in the trace")
    ap.add_argument("--prefix-len", type=int, default=128)
    ap.add_argument("--tail-len", type=int, default=16)
    ap.add_argument("--max-dec-len", type=int, default=16)
    ap.add_argument("--async-workers", action="store_true",
                    help="overlapped per-replica worker threads "
                         "(docs/fleet_serving.md \"Async router\")")
    ap.add_argument("--rolling-restart", action="store_true",
                    help="restart every replica mid-run (drain -> "
                         "failover -> fresh server)")
    ap.add_argument("--events", default="",
                    help="events.jsonl path shared by the router and "
                         "every replica")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    if jax.default_backend() != "tpu":
        # CPU drive: the Pallas kernels run in interpret mode. Never
        # where a TPU is attached — there the chip does the work.
        os.environ.setdefault("PFX_PALLAS_INTERPRET", "1")
    from paddlefleetx_tpu.utils.env import setup_compilation_cache
    setup_compilation_cache()

    from paddlefleetx_tpu.core.fleet import FleetRouter
    from paddlefleetx_tpu.core.serving import GenerationServer
    from paddlefleetx_tpu.models.gpt import GPTConfig, GPTForPretraining
    from paddlefleetx_tpu.models.gpt.generation import GenerationConfig

    vocab = 96
    capacity = args.prefix_len + args.tail_len + args.max_dec_len
    if args.page_size:
        capacity = -(-capacity // args.page_size) * args.page_size
    cfg = GPTConfig(vocab_size=vocab, hidden_size=32, num_layers=2,
                    num_attention_heads=4,
                    max_position_embeddings=capacity,
                    hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0)
    model = GPTForPretraining(cfg)
    params = model.init({"params": jax.random.key(args.seed)},
                        jnp.zeros((1, 8), jnp.int32))["params"]
    gen_cfg = GenerationConfig(max_dec_len=args.max_dec_len,
                               decode_strategy="greedy_search",
                               eos_token_id=vocab - 1,
                               pad_token_id=vocab - 1)

    def factory(name: str) -> GenerationServer:
        kw = {}
        if args.page_size:
            kw["page_size"] = args.page_size
            if args.pool_pages:
                kw["pool_pages"] = args.pool_pages
        return GenerationServer(
            model, params, gen_cfg, num_slots=args.slots,
            rng=jax.random.PRNGKey(args.seed),
            events_path=args.events or None, **kw)

    fleet = FleetRouter(factory, args.replicas,
                        prefill_replicas=args.prefill_replicas,
                        events_path=args.events or None,
                        handoff=args.handoff,
                        async_workers=args.async_workers)
    prompts = build_trace(args.requests, args.prefixes,
                          args.prefix_len, args.tail_len, vocab,
                          args.seed)
    ids = [fleet.submit(p) for p in prompts]
    done = {}
    restarted = False
    while fleet.busy:
        for c in fleet.step():
            done[c.request_id] = c
        if args.rolling_restart and not restarted and \
                len(done) >= len(ids) // 4:
            for c in fleet.rolling_restart():
                done[c.request_id] = c
            restarted = True
    missing = [i for i in ids if i not in done]
    summary = fleet.summary()
    summary["requests"] = len(ids)
    summary["completed"] = len(ids) - len(missing)
    print(json.dumps(summary, default=str))
    fleet.close()
    return 1 if missing else 0


if __name__ == "__main__":
    sys.exit(main())
