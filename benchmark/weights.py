"""Seeded random weights, made on the device in one jitted call.

The tree (names, shapes) is whatever the program's ``init_params`` builds
for the configuration, read through ``jax.eval_shape`` so nothing is
allocated; the VALUES are the benchmark's own: matrices normal(0, 0.02),
biases normal(0, 0.02) (the program's init leaves them zero, which would
leave the qkv-bias path unchecked by the reference), norm gains
1 + normal(0, 0.1). Large leaves are generated in slices under
``lax.map`` so the generator's temporaries stay under a few hundred MB
beside a model that fills most of the chip.
"""

import math

import jax
import jax.numpy as jnp

_SLICE_ELEMS = 1 << 26


def fold_seed(seed: int) -> jax.Array:
    """A key for any whole number: ``--seed`` may exceed 32 signed bits."""
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _normal(key, shape, dtype, std, mean=0.0):
    def draw(k, s):
        x = jax.random.normal(k, s, jnp.float32) * std + mean
        return x.astype(dtype)

    n = math.prod(shape)
    if n <= _SLICE_ELEMS or len(shape) < 2:
        return draw(key, shape)
    lead = shape[0]
    parts = next(
        (d for d in range(2, lead + 1)
         if lead % d == 0 and n // d <= _SLICE_ELEMS),
        lead,
    )
    sub = (lead // parts,) + tuple(shape[1:])
    out = jax.lax.map(lambda k: draw(k, sub), jax.random.split(key, parts))
    return out.reshape(shape)


def _kind(path) -> str:
    names = [getattr(p, "key", str(p)) for p in path]
    leaf = names[-1]
    if any(n.startswith("ln") or n.endswith("_ln") or n.endswith("_norm")
           for n in names):
        return "bias" if leaf == "bias" else "gain"
    if leaf.startswith("b"):
        return "bias"
    return "matrix"


def make_weights(shapes, seed: int, dtype, out_shardings=None):
    """``shapes``: the pytree of ``jax.ShapeDtypeStruct`` the program's
    init would return. Returns the same tree filled from ``seed``."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def fill(key):
        out = []
        for i, (path, s) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            kind = _kind(path)
            if kind == "gain":
                out.append(_normal(k, s.shape, dtype, 0.1, mean=1.0))
            else:
                out.append(_normal(k, s.shape, dtype, 0.02))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(fill, out_shardings=out_shardings)(fold_seed(seed))
