"""The benchmark's own data: parameter trees made from ``--seed``.

A tree has the shapes and dtypes of the program's parameter template
(``model.param_shapes()``) and values from this module's own RNG, so the
data never depends on the program's init code. It is made on the device
in one jitted call, then copied to the host once for publishing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

SCALE = 0.02        # the spread of trained transformer weights


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number, 64 bits and more."""
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def path_str(path) -> str:
    """'a/b/0' for a pytree key path."""
    parts = []
    for k in path:
        for attr in ("key", "idx", "name"):
            if hasattr(k, attr):
                parts.append(str(getattr(k, attr)))
                break
        else:
            parts.append(str(k))
    return "/".join(parts)


def flat(tree) -> dict:
    """{path: leaf} of a pytree, in sorted path order."""
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return dict(sorted((path_str(p), leaf) for p, leaf in leaves))


def tree_maker(template):
    """A jitted ``make(key, step) -> tree`` of `template`'s structure.

    Leaf ``i`` of checkpoint ``step`` is drawn from
    ``fold_in(fold_in(key, step), i)``, so every step of a run gives
    every chunk new bytes, and one seed always gives the same trees."""
    names = list(flat(template))
    index = {n: i for i, n in enumerate(names)}

    def make(key, step):
        k = jax.random.fold_in(key, step)

        def leaf(path, spec):
            lk = jax.random.fold_in(k, index[path_str(path)])
            dt = jnp.dtype(spec.dtype)
            if jnp.issubdtype(dt, jnp.floating):
                return (SCALE * jax.random.normal(lk, spec.shape, jnp.float32)
                        ).astype(dt)
            return jax.random.randint(lk, spec.shape, -100, 100).astype(dt)

        return jax.tree_util.tree_map_with_path(leaf, template)

    return jax.jit(make)


def host_tree(make, key, step: int = 0):
    """Checkpoint `step` of the seed's sequence as numpy arrays on the host."""
    return jax.tree.map(np.asarray, jax.device_get(make(key, step)))


def tree_bytes(tree) -> int:
    return sum(int(np.asarray(a).nbytes) for a in jax.tree.leaves(tree))
