"""What every family's reference check shares: the control's weight
rounding, and the numbers a run is judged by from the reference's logits.

A family's plain forward pass (``families/<family>.reference_logits``)
gives float32 logits at the positions that produced each served token.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def fake_quant(w, bits: int):
    """Symmetric round-to-nearest with one scale per output channel (the
    last axis; the input axis is the one before it)."""
    qmax = 2 ** (bits - 1) - 1
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / qmax
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(w / scale), -qmax, qmax) * scale


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
