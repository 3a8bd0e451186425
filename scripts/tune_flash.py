"""One-shot flash-attention tuning sweep for the real chip.

Times the Pallas kernel at the training operating points across block
sizes, against dense XLA attention, fwd and fwd+bwd — one run prints
the whole decision table, so a scarce TPU allocation yields the full
tuning picture in a single session (the d=64 exp path is the named
single-chip MFU floor).

Usage (TPU): ``python scripts/tune_flash.py [--points 345m,longctx,67b]``
"""

import argparse
import functools
import sys
import time

sys.path.insert(0, ".")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

POINTS = {
    # (batch, heads, seq, head_dim) per microbatch
    "345m": (8, 16, 1024, 64),
    "longctx": (1, 16, 8192, 64),
    "67b": (2, 32, 2048, 128),
}


def _time(fn, q, k, v, reps=20):
    """Median-of-3 per-iteration ms, with reps CHAINED through a data
    dependency inside one jitted scan.

    Independent back-to-back dispatches under-measure badly here (the
    r5 chip session recorded 0.018 ms "forwards" at s=8192 — 40x the
    chip's peak FLOPs — because nothing forces iteration i to wait for
    i-1). Feeding a tiny function of output i into input i+1 makes the
    chain sequential on device; 1e-30*out is numerically negligible
    but cannot be dead-code-eliminated."""
    def body(qq, _):
        out = fn(qq, k, v)
        lead = out[0] if isinstance(out, tuple) else out
        bump = (1e-30 * lead.ravel()[0]).astype(qq.dtype)
        return qq + bump, None

    @jax.jit
    def run(q):
        final, _ = jax.lax.scan(body, q, None, length=reps)
        return final

    jax.block_until_ready(run(q))  # compile + warm
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(run(q))
        times.append((time.perf_counter() - t0) / reps * 1e3)
    return sorted(times)[1]


def sweep(point: str, b: int, h: int, s: int, d: int):
    """Print ms for kernel block-size variants + dense, fwd and
    value_and_grad, at one operating point."""
    from paddlefleetx_tpu.ops.attention import _xla_attention
    from paddlefleetx_tpu.ops.pallas import flash_attention as fa

    rng = np.random.default_rng(0)
    shape = (b, s, h, d)
    q = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)

    def flash_loss(q, k, v, bq, bkv):
        o = fa.flash_attention(q, k, v, causal=True, block_q=bq,
                               block_kv=bkv)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def dense_loss(q, k, v):
        o = _xla_attention(q, k, v, None, True, 0, 0.0, None, True,
                           True, kv_cache_layout=False)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    try:
        from paddlefleetx_tpu.observability.flops import (
            causal_attn_flops, peak_flops,
        )
        floor_ms = causal_attn_flops(b, h, s, d) / peak_flops() * 1e3
    except Exception as e:
        floor_ms = None
        floor_err = f"{type(e).__name__}: {e}"
    print(f"== {point}: b={b} h={h} s={s} d={d} (bf16) ==")
    if floor_ms is not None:
        # self-check: any fwd below this is a measurement artifact
        # (the r5 session's unchained timing read 40x past peak)
        print(f"  roofline floor     : fwd {floor_ms:7.3f} ms "
              f"(peak-bound; trust nothing faster)")
    else:
        print(f"  roofline floor unavailable ({floor_err[:80]}) — "
              f"timings below are UNCHECKED against peak")
    blocks = sorted({min(512, s), min(1024, s), min(2048, s)})
    for bq in blocks:
        for bkv in blocks:
            if s % bq or s % bkv:
                continue
            try:
                # close over the config instead of jit(partial(...)):
                # the jit boundary then carries exactly q/k/v and no
                # unbound kernel param can ever arrive as a tracer
                fwd = _time(jax.jit(lambda q, k, v: fa.flash_attention(
                    q, k, v, causal=True, block_q=bq,
                    block_kv=bkv)), q, k, v)
                vag = _time(jax.jit(jax.grad(functools.partial(
                    flash_loss, bq=bq, bkv=bkv), argnums=(0, 1, 2))),
                    q, k, v)
                print(f"  flash bq={bq:5d} bkv={bkv:5d}: "
                      f"fwd {fwd:7.3f} ms   fwd+bwd {vag:7.3f} ms")
            except Exception as e:
                print(f"  flash bq={bq:5d} bkv={bkv:5d}: FAILED "
                      f"({type(e).__name__}: {str(e)[:80]})")
    try:
        fwd = _time(jax.jit(lambda q, k, v: _xla_attention(
            q, k, v, None, True, 0, 0.0, None, True, True,
            kv_cache_layout=False)), q, k, v)
        vag = _time(jax.jit(jax.grad(dense_loss, argnums=(0, 1, 2))),
                    q, k, v)
        print(f"  dense XLA          : fwd {fwd:7.3f} ms   "
              f"fwd+bwd {vag:7.3f} ms")
    except Exception as e:
        print(f"  dense XLA          : FAILED ({str(e)[:80]})")


def main():
    """Run the sweep at the selected operating points."""
    p = argparse.ArgumentParser()
    p.add_argument("--points", default="345m,longctx,67b")
    args = p.parse_args()
    d = jax.devices()[0]
    print(f"device: {d.platform} {d.device_kind}")
    for point in args.points.split(","):
        b, h, s, hd = POINTS[point.strip()]
        sweep(point.strip(), b, h, s, hd)


if __name__ == "__main__":
    main()
