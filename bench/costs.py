"""Operations and bytes the served work needs, from the model's shapes.

Counts are of useful work: real (unpadded) tokens, the top-k experts a
token is routed to (not the padded expert slots), causal attention over
the positions a token may see, and the logits head only where a token is
sampled.  Copied in spirit from ``benchmarks/analytic.py`` (2 x active
parameters per token) and ``benchmarks/bench_kernels.py`` (paged-decode
bytes: ``head_dim + 4`` bytes per fp8 position-head, ``2 * head_dim`` in
bf16); kept here so that no later change to the program can move them.
"""

from __future__ import annotations

from typing import Iterable, Sequence


def layer_params_active(m: dict) -> int:
    """Weights one token multiplies in one layer: q/k/v/o projections,
    the router and its ``top_k`` experts' gate/up/down."""
    d, hd = m["d_model"], m["head_dim"]
    attn = d * m["n_heads"] * hd * 2 + d * m["n_kv_heads"] * hd * 2
    experts = 3 * d * m["d_expert"] * m["top_k"]
    router = d * m["n_experts"]
    return attn + experts + router


def attn_flops(m: dict, n_keys: int) -> int:
    """Scores and weighted values of one query over ``n_keys`` positions,
    summed over layers (QK^T and PV: 2 x 2 x keys x heads x head_dim)."""
    return 4 * n_keys * m["n_heads"] * m["head_dim"] * m["n_layers"]


def token_flops(m: dict, n_keys: int, sampled: bool) -> int:
    """One token through every layer, attending over ``n_keys`` positions
    (itself included), plus the logits head when it is sampled."""
    f = 2 * layer_params_active(m) * m["n_layers"] + attn_flops(m, n_keys)
    if sampled:
        f += 2 * m["d_model"] * m["vocab_size"]
    return f


def prefill_flops(m: dict, n_tokens: Sequence[int],
                  starts: Sequence[int] = ()) -> int:
    """A prefill group: row i runs ``n_tokens[i]`` positions starting at
    ``starts[i]`` (0 = fresh prefill, whose first position is the profile
    token and costs the profile projection).  Only the last position of a
    row is sampled."""
    starts = list(starts) or [0] * len(n_tokens)
    total = 0
    for n, s in zip(n_tokens, starts):
        if n <= 0:
            continue
        total += sum(token_flops(m, s + j + 1, j == n - 1) for j in range(n))
        if s == 0:
            total += 2 * m["profile_dim"] * m["d_model"]
    return total


def decode_flops(m: dict, positions: Iterable[int]) -> int:
    """One decode step: each active row writes one token at absolute
    position ``p`` and attends over ``p + 1`` positions."""
    return sum(token_flops(m, p + 1, True) for p in positions)


def kv_bytes_per_position_head(m: dict, kv_dtype: str) -> int:
    """K and V bytes of one position of one KV head: the fp8 payload plus
    its f32 scale, or the bf16 payload."""
    hd = m["head_dim"]
    one = hd + 4 if kv_dtype == "float8_e4m3fn" else 2 * hd
    return 2 * one


def paged_decode_cost(m: dict, kv_dtype: str, page_size: int,
                      positions: Iterable[int]) -> tuple:
    """(flops, bytes) of the paged-decode attention kernel over all layers
    of one decode step: row r reads every page its table maps for
    ``positions[r] + 1`` positions, and reads q / writes its output once."""
    n_heads, n_kv, hd, n_l = (m["n_heads"], m["n_kv_heads"], m["head_dim"],
                              m["n_layers"])
    flops = nbytes = 0
    for p in positions:
        n_pos = -(-(p + 1) // page_size) * page_size
        flops += 4 * (p + 1) * n_heads * hd * n_l
        nbytes += (n_pos * n_kv * kv_bytes_per_position_head(m, kv_dtype)
                   + 2 * 2 * n_heads * hd) * n_l
    return flops, nbytes
