"""Random weights from ``--seed``, made on the device in one jitted call, in
the layout and type the served model takes: the pytree of shapes that the
configuration's block gives (``blocks/<block>.py`` ``shapes``), in the type
the configuration states (``torch_dtype``). The benchmark makes them, not
the program, so the plain reference can make the same ones again after the
program has been freed.

Scales, by the leaf's name: norm weights (a name holding ``norm`` or
``ln``) N(0, 0.1^2) around the program's ``x * (1 + w)`` convention, so
every norm weight matters to the result; the embedding N(0, 1); every other
matrix N(0, 1/fan_in). Leaf ``i`` in the tree's order draws from
``fold_in(key, i)``.
"""
from __future__ import annotations

import numpy as np

NORM_STD = 0.1


def jax_seed(seed: int) -> int:
    """A 31-bit key seed from any whole number (``--seed`` may exceed 32
    bits), through numpy's SeedSequence."""
    return int(np.random.SeedSequence(abs(int(seed))).generate_state(1)[0]
               & 0x7FFFFFFF)


def abstract(block, model: dict) -> dict:
    """The pytree ``make`` returns, as shapes and types only."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(model["torch_dtype"])
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, dt),
                        block.shapes(model),
                        is_leaf=lambda x: isinstance(x, tuple))


def make(block, model: dict, seed: int, shardings=None):
    """The weights of ``block`` at the sizes ``model`` from ``seed``, on the
    device (placed per ``shardings`` as they are made, where given)."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(model["torch_dtype"])
    tree = block.shapes(model)
    leaves, treedef = jax.tree.flatten(tree, is_leaf=lambda x: isinstance(
        x, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 tree, is_leaf=lambda x: isinstance(x, tuple))[0]]

    def build(key):
        out = []
        for i, (path, shape) in enumerate(zip(paths, leaves)):
            k = jax.random.fold_in(key, i)
            x = jax.random.normal(k, shape, jnp.float32)
            if "norm" in path or "ln" in path:
                x = x * NORM_STD
            elif "embed" not in path or "unembed" in path:
                x = x / np.sqrt(shape[-2])          # fan_in of a matrix
            out.append(x.astype(dt))
        return jax.tree.unflatten(treedef, out)

    fn = jax.jit(build, out_shardings=shardings)
    return fn(jax.random.PRNGKey(jax_seed(seed)))
