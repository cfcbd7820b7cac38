"""Random weights from ``--seed``, made by the benchmark and not by the
program, so that the plain reference can make the same numbers alone.

Every leaf is drawn from its own key, ``fold_in(key(seed), leaf index)``,
and a leaf with a layer axis from ``fold_in(that key, layer)``: the
reference regenerates one layer of one leaf without the rest.  Values are
normal with the usual ``1 / sqrt(fan_in)`` scale, rounded to bfloat16 (the
type the engine is given); norm scales are ones.  The tree has the layout
the engine's parameters have (``models/onerec.init_onerec``), which a CPU
test checks.
"""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp


def n_experts_padded(m: dict) -> int:
    return int(math.ceil(m["n_experts"] / m["ep_degree"]) * m["ep_degree"])


def leaf_specs(m: dict) -> List[Tuple[str, tuple, float, bool]]:
    """``(path, shape of one layer or of the leaf, std, per-layer)`` in a
    fixed order; std 0 means ones (norm scales)."""
    d, hd, f = m["d_model"], m["head_dim"], m["d_expert"]
    h, kv, v, e = m["n_heads"], m["n_kv_heads"], m["vocab_size"], \
        n_experts_padded(m)
    layer = "backbone/stacks/0/p0/"
    return [
        ("backbone/embed/table", (v, d), 1 / math.sqrt(d), False),
        (layer + "attn_norm/scale", (d,), 0.0, True),
        (layer + "attn/q_proj/kernel", (d, h * hd), 1 / math.sqrt(d), True),
        (layer + "attn/k_proj/kernel", (d, kv * hd), 1 / math.sqrt(d), True),
        (layer + "attn/v_proj/kernel", (d, kv * hd), 1 / math.sqrt(d), True),
        (layer + "attn/o_proj/kernel", (h * hd, d), 1 / math.sqrt(h * hd),
         True),
        (layer + "mlp_norm/scale", (d,), 0.0, True),
        (layer + "moe/router/kernel", (d, e), 1 / math.sqrt(d), True),
        (layer + "moe/experts/gate", (e, d, f), 1 / math.sqrt(d), True),
        (layer + "moe/experts/up", (e, d, f), 1 / math.sqrt(d), True),
        (layer + "moe/experts/down", (e, f, d), 1 / math.sqrt(f), True),
        ("backbone/final_norm/scale", (d,), 0.0, False),
        ("backbone/lm_head/kernel", (d, v), 1 / math.sqrt(d), False),
        ("profile_proj/kernel", (m["profile_dim"], d),
         1 / math.sqrt(m["profile_dim"]), False),
    ]


def root_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(seed % 2 ** 32)


def _draw(key, shape, std, dtype):
    if not std:
        return jnp.ones(shape, dtype)
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def layer_leaf(key, m: dict, index: int, layer, dtype=jnp.bfloat16):
    """Leaf ``index`` of ``leaf_specs`` for one layer (or the whole leaf
    when it has no layer axis)."""
    _, shape, std, per_layer = leaf_specs(m)[index]
    k = jax.random.fold_in(key, index)
    if per_layer:
        k = jax.random.fold_in(k, layer)
    return _draw(k, shape, std, dtype)


def init_params(key, m: dict, dtype=jnp.bfloat16) -> Dict:
    """The whole tree in ``dtype``: layer-stacked leaves lead with
    ``n_layers``."""
    tree: Dict = {}
    layers = jnp.arange(m["n_layers"])
    for i, (path, _, _, per_layer) in enumerate(leaf_specs(m)):
        if per_layer:
            leaf = jax.vmap(lambda l, i=i: layer_leaf(key, m, i, l,
                                                      dtype))(layers)
        else:
            leaf = layer_leaf(key, m, i, None, dtype)
        node = tree
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return tree
