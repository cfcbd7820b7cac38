"""The plain float32 OneRec forward pass the served tokens are checked
against.

Straight ``jax.numpy`` at ``highest`` matmul precision, with no cache, no
paging, no batching rule and no expert capacity: every token goes through
its top-k experts.  It follows the engine's model definition (the paper
states no more): a profile token then the semantic-ID tokens, pre-norm
RMSNorm blocks of GQA attention with half-split RoPE and a top-k softmax
router (weights not renormalised) over SiLU-gated experts, a final norm and
an untied head.  It imports nothing of the program; its weights come from
``bench/weights.py`` and the seed, one layer at a time, so that the
float32 tree (about 20 GB at full width) never has to fit.

``weight_bits=4`` gives the control: the same forward with the weights the
fp8 policy quantizes (attention projections and experts) rounded to int4,
one scale per output channel.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

_LAYER_LEAVES = {  # leaf_specs index -> name in the layer dict
    1: "attn_norm", 2: "q", 3: "k", 4: "v", 5: "o", 6: "mlp_norm",
    7: "router", 8: "gate", 9: "up", 10: "down"}
_QUANTIZED = ("q", "k", "v", "o", "gate", "up", "down")


def _fake_quant(w, bits: int):
    """Symmetric round-to-nearest with one scale per output channel (the
    last axis; the input axis is the one before it)."""
    qmax = 2 ** (bits - 1) - 1
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / qmax
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(w / scale), -qmax, qmax) * scale


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[..., None, None] * freqs   # (T, 1, hd/2)
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _layer(w, x, lengths, m):
    """One block over rows ``x`` (B, T, D) whose first ``lengths[b]``
    positions are real."""
    b, t, d = x.shape
    h, kv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    pos = jnp.arange(t)
    y = _rms(x, w["attn_norm"], m["norm_eps"])
    q = _rope((y @ w["q"]).reshape(b, t, h, hd), pos, m["rope_theta"])
    k = _rope((y @ w["k"]).reshape(b, t, kv, hd), pos, m["rope_theta"])
    v = (y @ w["v"]).reshape(b, t, kv, hd)
    q = q.reshape(b, t, kv, h // kv, hd)
    s = jnp.einsum("btkgh,bskh->bkgts", q, k) / np.sqrt(hd)
    ok = (pos[None, :] <= pos[:, None])[None] \
        & (pos[None, None, :] < lengths[:, None, None])      # (B, T, S)
    s = jnp.where(ok[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(ok[:, None, None], p, 0.0)
    a = jnp.einsum("bkgts,bskh->btkgh", p, v).reshape(b, t, h * hd)
    x = x + a @ w["o"]

    y = _rms(x, w["mlp_norm"], m["norm_eps"]).reshape(b * t, d)
    probs = jax.nn.softmax(y @ w["router"], axis=-1)          # real experts
    top_v, top_i = jax.lax.top_k(probs, m["top_k"])
    gate_w = jnp.zeros_like(probs).at[
        jnp.arange(b * t)[:, None], top_i].set(top_v)         # (N, E)

    def expert(acc, e):
        g = y @ w["gate"][e]
        u = y @ w["up"][e]
        out = (jax.nn.silu(g) * u) @ w["down"][e]
        return acc + gate_w[:, e][:, None] * out, None

    ff, _ = jax.lax.scan(expert, jnp.zeros_like(y),
                         jnp.arange(m["n_experts"]))
    return x + ff.reshape(b, t, d)


def _layer_weights(key, layer, m: dict, bits: Optional[int]):
    w = {}
    for i, name in _LAYER_LEAVES.items():
        leaf = weights.layer_leaf(key, m, i, layer).astype(jnp.float32)
        if name in ("router", "gate", "up", "down"):
            # the padded expert slots are never routed to
            leaf = leaf[..., :m["n_experts"]] if name == "router" \
                else leaf[:m["n_experts"]]
        if bits and name in _QUANTIZED:
            leaf = _fake_quant(leaf, bits)
        w[name] = leaf
    return w


def _embed(key, m, tokens, profile):
    table = weights.layer_leaf(key, m, 0, None).astype(jnp.float32)
    proj = weights.layer_leaf(key, m, 13, None).astype(jnp.float32)
    return jnp.concatenate([(profile @ proj)[:, None], table[tokens]], axis=1)


def _head(key, m, x, at):
    norm = weights.layer_leaf(key, m, 11, None).astype(jnp.float32)
    head = weights.layer_leaf(key, m, 12, None).astype(jnp.float32)
    rows = jnp.take_along_axis(x, at[:, :, None], axis=1)    # (B, n, D)
    return _rms(rows, norm, m["norm_eps"]) @ head


def reference_logits(m: dict, seed: int,
                     rows: Sequence[Tuple[np.ndarray, np.ndarray,
                                          np.ndarray]],
                     *, weight_bits: Optional[int] = None,
                     block_rows: int = 16) -> np.ndarray:
    """Logits (n_rows, decode_len, vocab) at the positions that produced
    each served token.  ``rows[i]`` is (history tokens, profile, served
    tokens); the reference reads the history and all served tokens but the
    last, as the engine did."""
    n_dec = m["decode_len"]
    seqs = [np.concatenate([np.asarray(t, np.int32),
                            np.asarray(s, np.int32)[:n_dec - 1]])
            for t, _, s in rows]
    t_max = -(-(1 + max(len(s) for s in seqs)) // 64) * 64
    n_blocks = -(-len(rows) // block_rows)
    n_pad = n_blocks * block_rows
    tok = np.zeros((n_pad, t_max - 1), np.int32)
    prof = np.zeros((n_pad, m["profile_dim"]), np.float32)
    lengths = np.zeros((n_pad,), np.int32)
    at = np.zeros((n_pad, n_dec), np.int32)
    for i, ((t, p, _), s) in enumerate(zip(rows, seqs)):
        tok[i, :len(s)] = s
        prof[i] = p
        lengths[i] = len(s) + 1
        at[i] = len(t) + np.arange(n_dec)
    key = weights.root_key(seed)
    blk = lambda a, j: jnp.asarray(a[j * block_rows:(j + 1) * block_rows])
    with jax.default_matmul_precision("highest"):
        embed = jax.jit(partial(_embed, m=m))
        xs = [embed(key, tokens=blk(tok, j), profile=blk(prof, j))
              for j in range(n_blocks)]
        gen = jax.jit(partial(_layer_weights, m=m, bits=weight_bits))
        layer = jax.jit(partial(_layer, m=m))
        for l in range(m["n_layers"]):
            w = gen(key, l)
            xs = [layer(w, x, blk(lengths, j)) for j, x in enumerate(xs)]
            del w
        head = jax.jit(partial(_head, m=m))
        out = [np.asarray(head(key, x=x, at=blk(at, j)))
               for j, x in enumerate(xs)]
    return np.concatenate(out)[:len(rows)]


def token_gaps(ref: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far each token's reference logit lies below the reference's
    best at that position (0 where the token is the reference's argmax)."""
    tokens = np.asarray(tokens, np.int64)
    picked = np.take_along_axis(ref, tokens[..., None], axis=-1)[..., 0]
    return ref.max(axis=-1) - picked


def readings(ref: np.ndarray, tokens: np.ndarray) -> dict:
    """The numbers a run is judged by, from the reference's logits and the
    served (or the control's) tokens: the widest gap, the mean gap, and
    the share of tokens that are not the reference's argmax."""
    gaps = token_gaps(ref, tokens)
    return {"max_gap": float(gaps.max()), "mean_gap": float(gaps.mean()),
            "flip_share": float(np.mean(gaps > 0))}
