"""MFU tuning harness: per-component timings at the bench operating
point (345M, b=8, s=1024) on the real chip.

Not part of the test suite — run ad hoc: python scripts/profile_mfu.py
[component ...].  Components: attn, ce, gemm, micro, opt, e2e.
"""

import functools
import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np
import optax

from paddlefleetx_tpu.models.gpt import GPTConfig, GPTForPretraining
from paddlefleetx_tpu.models.gpt.model import chunked_lm_loss
from paddlefleetx_tpu.observability.flops import (
    causal_attn_flops, model_flops_per_token, peak_flops,
)
from paddlefleetx_tpu.ops.pallas.flash_attention import flash_attention

PEAK = peak_flops() or 197e12

B, S, H, L, NH, D, V, FFN = 8, 1024, 1024, 24, 16, 64, 50304, 4096


def _sync(out):
    # fetching one value forces the device queue (in-order execution)
    # to drain. Slice device-side first: transferring a whole array to
    # the host would be timed along with the work.
    leaf = jax.tree.leaves(out)[0]
    float(jnp.ravel(leaf)[0].astype(jnp.float32))


def timeit(fn, *args, n=20, warmup=2):
    for _ in range(warmup):
        out = fn(*args)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / n


def report(name, dt, flops):
    print(f"{name:<40s} {dt*1e3:8.3f} ms  {flops/dt/1e12:7.2f} TF/s "
          f"({flops/dt/PEAK*100:5.1f}% of peak)")


REPEAT = 30


def repeat_jit(fn):
    """Chain REPEAT dependent applications inside one jit so a single
    dispatch covers REPEAT device executions. fn
    must map its first arg to a same-shaped output."""
    @jax.jit
    def many(x, *rest):
        def body(x, _):
            return fn(x, *rest), None
        return jax.lax.scan(body, x, None, length=REPEAT)[0]
    return many


def timeit_rep(fn, x, *rest, n=3):
    many = repeat_jit(fn)
    out = many(x, *rest)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = many(x, *rest)
    _sync(out)
    return (time.perf_counter() - t0) / (n * REPEAT)


def bench_attn():
    """Sweep flash-attention block sizes and report TFLOP/s."""
    rng = np.random.default_rng(0)
    shape = (B, S, NH, D)
    q = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    fwd_flops = causal_attn_flops(B, NH, S, D)
    for bq, bkv in [(256, 256), (256, 512), (512, 512), (512, 1024),
                    (1024, 512), (1024, 1024), (512, 256)]:
        if bq > S or bkv > S:
            continue
        f = functools.partial(flash_attention, causal=True,
                              block_q=bq, block_kv=bkv)
        dt = timeit_rep(lambda q, k, v: f(q, k, v), q, k, v)
        report(f"attn fwd bq={bq} bkv={bkv}", dt, fwd_flops)

        def gstep(q, k, v, f=f):
            g = jax.grad(
                lambda q: jnp.sum(f(q, k, v).astype(jnp.float32)))(q)
            return g.astype(q.dtype)
        dt = timeit_rep(gstep, q, k, v)
        report(f"attn fwd+bwd(dq-chain) bq={bq} bkv={bkv}", dt,
               3.5 * fwd_flops)


def bench_ce():
    """Time the chunked cross-entropy head at several chunk counts."""
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((B, S, H)), jnp.bfloat16)
    emb = jnp.asarray(rng.standard_normal((V, H)) * 0.02, jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
    mask = jnp.ones((B, S), jnp.float32)
    fwd_flops = 2 * B * S * H * V

    from paddlefleetx_tpu.models.gpt.model import (
        masked_nll_sums, tied_logits,
    )

    for chunks in [1, 4, 8, 16]:
        csz = S // chunks

        def ce(h, emb, labels, mask, chunks=chunks, csz=csz):
            """Chunk-scanned masked NLL over the tied LM head."""
            hc = h.reshape(B, chunks, csz, H).swapaxes(0, 1)
            lc = labels.reshape(B, chunks, csz).swapaxes(0, 1)
            mc = mask.reshape(B, chunks, csz).swapaxes(0, 1)

            @jax.checkpoint
            def body(carry, xs):
                hh, ll, mm = xs
                nll, ms = masked_nll_sums(tied_logits(hh, emb), ll, mm)
                return (carry[0] + nll, carry[1] + ms), None

            (nll, ms), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32),
                       jnp.zeros((), jnp.float32)), (hc, lc, mc))
            return nll / ms

        g = jax.jit(jax.grad(ce, argnums=(0, 1)))
        dt = timeit(g, h, emb, labels, mask)
        # fwd + recompute + 2 bwd matmuls = 4x fwd matmul flops
        report(f"CE fwd+bwd chunks={chunks}", dt, 4 * fwd_flops)


def bench_gemm():
    """Mimic of one layer's linear stack, fwd+bwd, x24."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((B * S, H)), jnp.bfloat16)
    wqkv = jnp.asarray(rng.standard_normal((H, 3 * H)) * .02, jnp.bfloat16)
    wo = jnp.asarray(rng.standard_normal((H, H)) * .02, jnp.bfloat16)
    w1 = jnp.asarray(rng.standard_normal((H, FFN)) * .02, jnp.bfloat16)
    w2 = jnp.asarray(rng.standard_normal((FFN, H)) * .02, jnp.bfloat16)

    def layer_stack(x, wqkv, wo, w1, w2):
        def body(x, _):
            a = x @ wqkv
            x = x + a[:, :H] @ wo
            x = x + jax.nn.gelu(x @ w1, approximate=True) @ w2
            return x, None
        x, _ = jax.lax.scan(body, x, None, length=L)
        return jnp.sum(x.astype(jnp.float32))

    g = jax.jit(jax.grad(layer_stack, argnums=(0, 1, 2, 3, 4)))
    flops = 3 * L * 2 * B * S * H * (3 * H + H + FFN + FFN)
    dt = timeit(g, x, wqkv, wo, w1, w2)
    report("24-layer linear mimic fwd+bwd", dt, flops)


def _model_and_batch(**kw):
    cfg = GPTConfig(
        vocab_size=V, hidden_size=H, num_layers=L,
        num_attention_heads=NH, ffn_hidden_size=FFN,
        max_position_embeddings=S, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0, dtype="bfloat16",
        use_flash_attention=True, use_recompute=True,
        recompute_granularity="save_dots", loss_chunks=8, **kw)
    model = GPTForPretraining(cfg)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
    labels = jnp.roll(ids, -1, axis=1)
    mask = jnp.ones((B, S), jnp.float32)
    params = jax.jit(model.init)({"params": jax.random.key(0)},
                                 ids[:1])["params"]
    return cfg, model, params, ids, labels, mask


def bench_micro():
    """Time one microbatch fwd and fwd+bwd against the MFU formula."""
    cfg, model, params, ids, labels, mask = _model_and_batch()

    def loss_fn(p, ids, labels, mask):
        return chunked_lm_loss(model, p, ids, labels, mask,
                               chunks=cfg.loss_chunks,
                               deterministic=True)

    fwd = jax.jit(loss_fn)
    dt = timeit(fwd, params, ids, labels, mask)
    tok = B * S
    # fwd-only = one third of the Megatron fwd+bwd count; derive it
    # from the shared formula rather than keeping a second copy
    fpt_fwd = model_flops_per_token(L, H, V, S) / 3.0
    report("microbatch fwd", dt, fpt_fwd * tok)

    g = jax.jit(jax.value_and_grad(loss_fn))
    dt = timeit(g, params, ids, labels, mask)
    report("microbatch fwd+bwd", dt, 3 * fpt_fwd * tok)


def bench_opt():
    """Time the optimizer update in isolation."""
    cfg, model, params, *_ = _model_and_batch()
    tx = optax.chain(optax.clip_by_global_norm(1.0),
                     optax.adamw(2e-4, weight_decay=0.01,
                                 mu_dtype=jnp.bfloat16))
    opt_state = tx.init(params)
    grads = jax.tree.map(lambda p: jnp.ones(p.shape, jnp.float32),
                         params)

    @jax.jit
    def upd(params, opt_state, grads):
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    dt = timeit(lambda: upd(params, opt_state, grads), n=10)
    print(f"optimizer update: {dt*1e3:.3f} ms")


def main():
    which = set(sys.argv[1:]) or {"attn", "ce", "gemm", "micro", "opt"}
    print(f"device: {jax.devices()[0].device_kind}")
    for name in ["attn", "ce", "gemm", "micro", "opt"]:
        if name in which:
            print(f"--- {name} ---")
            globals()[f"bench_{name}"]()


if __name__ == "__main__":
    main()
