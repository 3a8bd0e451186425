"""Weights from the seed, made by the benchmark and not by the program.

One jitted call fills a parameter tree of the given shapes: LayerNorm
scales 1, every bias 0, everything else N(0, 0.02) — the GPT-2
initialisation — drawn in float32 and stored in ``dtype``. The program
under test and the float32 reference are both given these values, so
the reference takes nothing the program has made.
"""

import zlib

import jax
import jax.numpy as jnp

INIT_STD = 0.02


def _leaf(key, path, shape, dtype):
    names = [str(getattr(k, "key", k)) for k in path]
    if names[-1] == "scale":
        return jnp.ones(shape, dtype)
    if names[-1] == "bias":
        return jnp.zeros(shape, dtype)
    # the stream of a leaf depends on its path, not on its place in
    # the tree: a stacked and an unrolled layout differ, two runs of
    # one layout never do
    sub = jax.random.fold_in(key, zlib.crc32("/".join(names).encode())
                             & 0x7FFFFFFF)
    return (INIT_STD * jax.random.normal(sub, shape, jnp.float32)
            ).astype(dtype)


def seeded_params(abstract, seed, dtype=None, shardings=None):
    """A tree shaped like ``abstract`` (anything with ``.shape`` and
    ``.dtype`` at its leaves), filled from ``seed`` on the device in one
    jitted call. ``dtype`` overrides the leaves' own; ``shardings`` is a
    matching tree of shardings for the result."""
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, dtype or a.dtype), abstract)

    def make(key):
        return jax.tree_util.tree_map_with_path(
            lambda path, a: _leaf(key, path, a.shape, a.dtype), shapes)
    # seeds run past 2**31: fold the high bits in separately
    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(make, out_shardings=shardings)(key)


def spread(abstract, devices):
    """Shardings that cut every leaf's largest divisible axis (the last
    of equals) over ``devices``, whole leaves where none divides: how
    the reference holds a model too large for one chip. A stacked
    layer axis is never the largest, so one layer's slice keeps its
    cut."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(devices), ("all",))
    n = len(devices)

    def one(a):
        fit = [(size, ax) for ax, size in enumerate(a.shape)
               if n > 1 and size % n == 0]
        if not fit:
            return NamedSharding(mesh, P())
        ax = max(fit)[1]
        return NamedSharding(mesh, P(*([None] * ax + ["all"])))
    return jax.tree.map(one, abstract)
