"""Random weights from ``--seed``, made on the device in one jitted call, in
the layout and type the served model takes (the ``repro.models``
transformer pytree: ``embed``, ``final_norm``, ``unembed`` and stacked
``layers``). The benchmark makes them, not the program, so the plain
reference can make the same ones again after the program has been freed.

Scales: matrices N(0, 1/fan_in), the embedding N(0, 1), norm weights
N(0, 0.1^2) around the program's ``x * (1 + w)`` convention, so every
norm weight matters to the result.
"""
from __future__ import annotations

import numpy as np

NORM_STD = 0.1


def jax_seed(seed: int) -> int:
    """A 31-bit key seed from any whole number (``--seed`` may exceed 32
    bits), through numpy's SeedSequence."""
    return int(np.random.SeedSequence(abs(int(seed))).generate_state(1)[0]
               & 0x7FFFFFFF)


def shapes(cfg) -> dict:
    """Leaf shapes of the pytree ``make`` returns."""
    d, hd, L = cfg.d_model, cfg.resolved_head_dim, cfg.n_layers
    hq, hkv, ff, v = cfg.n_heads, cfg.n_kv_heads, cfg.d_ff, cfg.vocab_size
    return {
        "embed": (v, d), "final_norm": (d,), "unembed": (d, v),
        "layers": {
            "ln1": (L, d), "ln2": (L, d),
            "attn": {"wq": (L, d, hq * hd), "wk": (L, d, hkv * hd),
                     "wv": (L, d, hkv * hd), "wo": (L, hq * hd, d)},
            "mlp": {"wi": (L, d, 2 * ff), "wo": (L, ff, d)},
        },
    }


def abstract(cfg) -> dict:
    """The pytree ``make`` returns, as shapes and types only."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(cfg.dtype)
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, dt), shapes(cfg),
                        is_leaf=lambda x: isinstance(x, tuple))


def make(cfg, seed: int, shardings=None):
    """The weights for ``cfg`` from ``seed``, on the device (placed per
    ``shardings`` as they are made, where given)."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(cfg.dtype)
    tree = shapes(cfg)
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
