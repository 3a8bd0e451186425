"""Record the small trace kept under ``chipbench/testdata/``.

    python3 chipbench/tests/record_small_trace.py <out_dir>     (on a TPU)

A program whose device time is known by construction, so that
``test_trace_reduce.py`` can hold the reduction to numbers: LAUNCHES
launches of one jitted program (a matmul, then a ``fori_loop`` of three
matmuls, so the op line nests), each under a ``work/launch`` annotation
and followed by a host sleep of SLEEP_S under ``host/sleep``. The
markers bound the window as they do in a run.
"""

import os
import sys
import time

LAUNCHES = 6
SLEEP_S = 0.004
EDGE_S = 0.01


def main(out):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def small_program(x):
        y = x @ x
        return jax.lax.fori_loop(0, 3, lambda i, a: a @ x, y)

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    small_program(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("chipbench/trace_begin"):
        pass
    # the device's clock runs a millisecond or two off the host's in
    # the trace: keep the work clear of both markers
    time.sleep(EDGE_S)
    for _ in range(LAUNCHES):
        with jax.profiler.TraceAnnotation("work/launch"):
            small_program(x).block_until_ready()
        with jax.profiler.TraceAnnotation("host/sleep"):
            time.sleep(SLEEP_S)
    time.sleep(EDGE_S)
    with jax.profiler.TraceAnnotation("chipbench/trace_end"):
        pass
    jax.profiler.stop_trace()
    print(jax.devices()[0].device_kind, os.listdir(out))


if __name__ == "__main__":
    main(sys.argv[1])
