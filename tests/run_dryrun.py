"""Run the driver's multi-chip dry run on a virtual CPU mesh.

Usage: ``python tests/run_dryrun.py [n_devices]`` (default 8). Forces
the CPU platform through jax.config before any backend initializes,
then executes
``__graft_entry__.dryrun_multichip`` — one real training step of the
full pp/tp/dp/fsdp/(cp) composite on tiny shapes.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

if __name__ == "__main__":
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    from paddlefleetx_tpu.parallel.mesh import cpu_mesh_env
    cpu_mesh_env(n)
    import __graft_entry__
    __graft_entry__.dryrun_multichip(n)
