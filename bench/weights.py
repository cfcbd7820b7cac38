"""Random weights from ``--seed``, made by the benchmark and not by the
program, so that the plain reference can make the same numbers alone.

A family lists its leaves (``leaf_specs``): ``(path, shape of one layer
or of the leaf, std, layers)``.  Every leaf is drawn from its own key,
``fold_in(key(seed), leaf index)``, and a leaf with a layer axis from
``fold_in(that key, layer)``: the reference regenerates one layer of one
leaf without the rest.  Values are normal with the family's std, rounded
to bfloat16 (the type the engine is given); std 0 gives ones (norm
scales).
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

Spec = Tuple[str, tuple, float, int]


def root_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(seed % 2 ** 32)


def _draw(key, shape, std, dtype):
    if not std:
        return jnp.ones(shape, dtype)
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def layer_leaf(key, specs: List[Spec], index: int, layer,
               dtype=jnp.bfloat16):
    """Leaf ``index`` of ``specs`` for one layer (or the whole leaf when
    it has no layer axis)."""
    _, shape, std, layers = specs[index]
    k = jax.random.fold_in(key, index)
    if layers:
        k = jax.random.fold_in(k, layer)
    return _draw(k, shape, std, dtype)


def init_params(key, specs: List[Spec], dtype=jnp.bfloat16) -> Dict:
    """The whole tree in ``dtype``: a layer-stacked leaf leads with its
    number of layers."""
    tree: Dict = {}
    for i, (path, _, _, layers) in enumerate(specs):
        if layers:
            leaf = jax.vmap(lambda l, i=i: layer_leaf(key, specs, i, l,
                                                      dtype))(
                jnp.arange(layers))
        else:
            leaf = layer_leaf(key, specs, i, None, dtype)
        node = tree
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return tree
