"""``run.py`` with the timed path broken underneath (tests only).

    python3 chipbench/tests/broken_run.py <fault> --workload ... --rehearse

``frozen_step``  the Engine's compiled step returns its parameters
                 unchanged: the job "trains" at full speed and learns
                 nothing
``wrong_token``  the decode tick's token for every slot is altered where
                 it is produced
The rest of the run is the benchmark's own: it must come out with
``correct`` false.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "chipbench"))


def frozen_step():
    from paddlefleetx_tpu.core import engine as eng
    build = eng.Engine._build_steps

    def patched(self):
        build(self)
        step = self._train_step

        def frozen(state, batch):
            import jax
            import jax.numpy as jnp
            kept = jax.tree.map(jnp.copy, state["params"])
            new, metrics = step(state, batch)      # donates ``state``
            return dict(new, params=kept), metrics
        self._train_step = frozen
    eng.Engine._build_steps = patched


def wrong_token():
    from paddlefleetx_tpu.core import serving
    decode = serving.decode_step

    def altered(model, *a, **k):
        cache, state, tok = decode(model, *a, **k)
        return cache, state, (tok + 1) % (model.config.vocab_size - 1)
    serving.decode_step = altered


if __name__ == "__main__":
    fault = sys.argv.pop(1)
    import run
    # JAX must be configured by run.main before the program is imported:
    # patch lazily, at the driver's first import of the program
    real = run.gate_devices

    def gate(*a, **k):
        devices = real(*a, **k)
        {"frozen_step": frozen_step, "wrong_token": wrong_token}[fault]()
        return devices
    run.gate_devices = gate
    try:
        sys.exit(run.main())
    except run.Refused as e:
        sys.stderr.write(f"chipbench: {e}\n")
        sys.exit(2)
