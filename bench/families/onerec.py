"""The OneRec family (arXiv:2603.11486): everything of the benchmark that
is particular to this model, behind the names the harness calls.

- engine: ``leaf_specs`` (the weights' layout), ``build_engine``;
- requests: ``fresh_request``, ``sessions``;
- warm-up: ``warm_up``;
- call records: ``CALLS``, the executor's prefill, resume and decode calls
  as ``{"kind", "tokens", "starts" | "positions"}``;
- reference: ``reference_logits``, ``reference_row``, ``answer_tokens``;
- costs: ``prefill_flops``, ``decode_flops``, ``paged_decode_cost``.

A request is a user's behaviour history (``items`` of ``n_codebooks``
semantic-ID tokens each) and a profile feature; its answer is the next
item, ``decode_len`` tokens.  The model is a pre-norm transformer of GQA
attention and a top-k softmax MoE over a profile token then the history.
"""

from __future__ import annotations

import dataclasses
import math
import time
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.reference import fake_quant


# -- engine -------------------------------------------------------------------

def n_experts_padded(m: dict) -> int:
    return int(math.ceil(m["n_experts"] / m["ep_degree"]) * m["ep_degree"])


def leaf_specs(m: dict) -> List[Tuple[str, tuple, float, int]]:
    """``(path, shape of one layer or of the leaf, std, layers)`` in a
    fixed order, in the layout of ``models/onerec.init_onerec``; std 0
    means ones (norm scales), layers 0 a leaf with no layer axis."""
    d, hd, f = m["d_model"], m["head_dim"], m["d_expert"]
    h, kv, v, e = m["n_heads"], m["n_kv_heads"], m["vocab_size"], \
        n_experts_padded(m)
    n = m["n_layers"]
    layer = "backbone/stacks/0/p0/"
    return [
        ("backbone/embed/table", (v, d), 1 / math.sqrt(d), 0),
        (layer + "attn_norm/scale", (d,), 0.0, n),
        (layer + "attn/q_proj/kernel", (d, h * hd), 1 / math.sqrt(d), n),
        (layer + "attn/k_proj/kernel", (d, kv * hd), 1 / math.sqrt(d), n),
        (layer + "attn/v_proj/kernel", (d, kv * hd), 1 / math.sqrt(d), n),
        (layer + "attn/o_proj/kernel", (h * hd, d), 1 / math.sqrt(h * hd),
         n),
        (layer + "mlp_norm/scale", (d,), 0.0, n),
        (layer + "moe/router/kernel", (d, e), 1 / math.sqrt(d), n),
        (layer + "moe/experts/gate", (e, d, f), 1 / math.sqrt(d), n),
        (layer + "moe/experts/up", (e, d, f), 1 / math.sqrt(d), n),
        (layer + "moe/experts/down", (e, f, d), 1 / math.sqrt(f), n),
        ("backbone/final_norm/scale", (d,), 0.0, 0),
        ("backbone/lm_head/kernel", (d, v), 1 / math.sqrt(d), 0),
        ("profile_proj/kernel", (m["profile_dim"], d),
         1 / math.sqrt(m["profile_dim"]), 0),
    ]


def model_config(m: dict):
    """The engine's ``OneRecConfig`` from a configuration file's ``model``."""
    from repro.configs.base import OneRecConfig, TransformerConfig

    t_keys = {f.name for f in dataclasses.fields(TransformerConfig)}
    o_keys = {f.name for f in dataclasses.fields(OneRecConfig)}
    backbone = TransformerConfig(
        name=m["backbone_name"],
        **{k: v for k, v in m.items() if k in t_keys and k != "name"})
    return OneRecConfig(
        transformer=backbone,
        **{k: v for k, v in m.items() if k in o_keys and k != "transformer"})


def engine_config(e: dict):
    from repro.serving import EngineConfig

    return EngineConfig(
        batch_size=e["n_slots"], n_slots=e["n_slots"], use_fp8=e["use_fp8"],
        kv_dtype=e["kv_dtype"], mode="continuous", paged=e["paged"],
        page_size=e["page_size"], n_pages=e["n_pages"],
        prefix_cache=e["prefix_cache"], prefix_rows=e["prefix_rows"],
        fused_decode=e["fused_decode"])


def build_engine(conf: dict, seed: int):
    """The served engine with the benchmark's weights for ``seed``: bf16
    leaves go in as they are; under a quantizing policy the program's
    leaf-at-a-time builder quantizes them (the bf16 tree and its fp8 copy
    do not fit one chip together)."""
    from repro.core.ptq import build_quantized_params
    from repro.serving import ServingEngine
    from repro.serving.engine import resolve_quant_policy

    cfg = model_config(conf["model"])
    ecfg = engine_config(conf["engine"])
    policy, _ = resolve_quant_policy(ecfg)
    key = weights.root_key(seed)
    specs = leaf_specs(conf["model"])
    init = lambda k: weights.init_params(k, specs)
    if policy.enabled:
        params = build_quantized_params(init, key, policy)
    else:
        params = jax.jit(init)(key)
    engine = ServingEngine(params, cfg, ecfg)
    jax.block_until_ready((engine.executor.params, engine.executor.cache))
    return engine


# -- requests -----------------------------------------------------------------

def _request(tokens, profile) -> Dict:
    return {"tokens": np.asarray(tokens, np.int32),
            "profile": np.asarray(profile, np.float32)}


def fresh_request(m: dict, items: int, rng) -> Dict:
    """A first-visit user: ``items`` history items and a profile from
    ``rng``."""
    return _request(rng.integers(0, m["codebook_size"],
                                 size=items * m["n_codebooks"]),
                    rng.normal(size=m["profile_dim"]))


def sessions(mix: dict, m: dict, n: int, rng) -> List[Dict]:
    """Visits of returning users.  The visit sequence (who comes back and
    by how much the history grows) is drawn from ``mix_seed``, so every
    seed does the same work; users' items and profiles come from ``rng``."""
    s = mix["sessions"]
    ncb, vocab = m["n_codebooks"], m["codebook_size"]
    cap = m["history_len"]
    fixed = np.random.default_rng(mix["mix_seed"])
    ranks = np.arange(1, s["users"] + 1, dtype=np.float64)
    p = ranks ** -s["zipf"]
    p /= p.sum()
    lo, hi = s["start_items"]
    start = [int(round(lo + (hi - lo) * (j + 0.5) / s["users"]))
             for j in range(s["users"])]
    start = list(fixed.permutation(start))
    g_lo, g_hi = s["grow_items"]
    visits = fixed.choice(s["users"], size=n, p=p)
    grows = fixed.integers(g_lo, g_hi + 1, size=n)
    hist = [None] * s["users"]
    profiles = [None] * s["users"]
    requests = []
    for u, g in zip(visits, grows):
        if hist[u] is None:
            hist[u] = list(rng.integers(0, vocab, size=start[u] * ncb))
            profiles[u] = rng.normal(size=m["profile_dim"])
        else:
            room = cap * ncb - len(hist[u])
            hist[u] += list(rng.integers(0, vocab, size=min(g * ncb, room)))
        requests.append(_request(hist[u], profiles[u]))
    return requests


# -- warm-up ------------------------------------------------------------------

def warm_up(engine, workload, seed: int, log=lambda s: None) -> int:
    """Serve, for every prefill length bucket the traffic's histories fall
    in, one group of each batch bucket (1, 2, 4 .. slots), so the window
    finds every prefill, select, decode and page-release program compiled.
    Returns the number of warm-up requests served."""
    from repro.models.onerec import PROFILE_DIM as pdim
    from repro.serving.executor import bucket_length

    cfg, ex = engine.cfg, engine.executor
    ncb = cfg.n_codebooks
    cap = cfg.history_len * ncb
    rep: Dict[int, int] = {}
    for items in (len(r["tokens"]) // ncb for r in workload.requests):
        b = min(bucket_length(items * ncb, ex.prefill_bucket_min), cap)
        rep[b] = max(rep.get(b, 0), items)
    rng = np.random.default_rng(seed + 1)
    vocab = cfg.codebook_size
    served = 0
    for items in sorted(rep.values()):
        t0 = time.perf_counter()
        b = 1
        while b <= engine.n_slots:
            for _ in range(b):
                engine.submit({"tokens": rng.integers(0, vocab,
                                                      size=items * ncb),
                               "profile": rng.normal(size=pdim)})
            engine.drain()
            served += b
            b *= 2
        log(f"[warm-up] {items}-item histories, groups of 1..{b // 2}: "
            f"{time.perf_counter() - t0:.3f} s")
    if "sessions" in workload.mix and engine.prefix_store is not None:
        # returning users resume from a stored prefix: one resume group of
        # each batch bucket (their suffix of a few items is one bucket)
        grow = workload.mix["sessions"]["grow_items"][1]
        base = min(32, cfg.history_len - grow) * ncb
        grow *= ncb
        b = 1
        while b <= engine.n_slots:
            hist = [rng.integers(0, vocab, size=base) for _ in range(b)]
            prof = [rng.normal(size=pdim) for _ in range(b)]
            for extra in (0, grow):
                for h, p in zip(hist, prof):
                    engine.submit({"tokens": np.concatenate(
                        [h, rng.integers(0, vocab, size=extra)])
                        if extra else h, "profile": p})
                engine.drain()
            served += 2 * b
            b *= 2
    if ex.paged:
        # retiring requests and evicting stored prefixes release pages in
        # batches of any size: compile each release bucket (the sentinel
        # page is already virgin, so the writes change nothing)
        most = bucket_length(engine.n_slots * ex._p_max, 1)
        b = 1
        while b <= most:
            ex._free_pages_device([ex._sentinel] * b)
            b *= 2
    engine.reset_window()
    return served


# -- call records -------------------------------------------------------------

def _prefill(tokens_list, profiles, slots):
    # a fresh prefill's first position is the profile token
    return {"kind": "prefill",
            "tokens": [len(t) + 1 for t in tokens_list],
            "starts": [0] * len(tokens_list)}


def _resume(tokens_list, slots, starts):
    return {"kind": "resume", "tokens": [len(t) for t in tokens_list],
            "starts": [int(s) for s in starts]}


def _decode(tokens, lengths):
    return {"kind": "decode",
            "positions": [int(p) for p in np.asarray(lengths) if p > 0]}


# executor method -> its record, from the call's arguments
CALLS = {"prefill_insert": _prefill, "resume_prefill": _resume,
         "decode": _decode}


# -- reference ----------------------------------------------------------------
#
# The plain float32 forward pass the served tokens are checked against.
# Straight ``jax.numpy`` at ``highest`` matmul precision, with no cache, no
# paging, no batching rule and no expert capacity: every token goes through
# its top-k experts.  It follows the engine's model definition (the paper
# states no more): a profile token then the semantic-ID tokens, pre-norm
# RMSNorm blocks of GQA attention with half-split RoPE and a top-k softmax
# router (weights not renormalised) over SiLU-gated experts, a final norm
# and an untied head.  It imports nothing of the program; its weights come
# from ``bench/weights.py`` and the seed, one layer at a time, so that the
# float32 tree (about 20 GB at full width) never has to fit.
#
# ``weight_bits=4`` gives the control: the same forward with the weights
# the fp8 policy quantizes (attention projections and experts) rounded to
# int4, one scale per output channel.

_LAYER_LEAVES = {  # leaf_specs index -> name in the layer dict
    1: "attn_norm", 2: "q", 3: "k", 4: "v", 5: "o", 6: "mlp_norm",
    7: "router", 8: "gate", 9: "up", 10: "down"}
_QUANTIZED = ("q", "k", "v", "o", "gate", "up", "down")


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


def _leaf(key, m, index, layer=None):
    return weights.layer_leaf(key, leaf_specs(m), index,
                              layer).astype(jnp.float32)


def _layer_weights(key, layer, m: dict, bits: Optional[int]):
    w = {}
    for i, name in _LAYER_LEAVES.items():
        leaf = _leaf(key, m, i, layer)
        if name in ("router", "gate", "up", "down"):
            # the padded expert slots are never routed to
            leaf = leaf[..., :m["n_experts"]] if name == "router" \
                else leaf[:m["n_experts"]]
        if bits and name in _QUANTIZED:
            leaf = fake_quant(leaf, bits)
        w[name] = leaf
    return w


def _embed(key, m, tokens, profile):
    table = _leaf(key, m, 0)
    proj = _leaf(key, m, 13)
    return jnp.concatenate([(profile @ proj)[:, None], table[tokens]], axis=1)


def _head(key, m, x, at):
    norm = _leaf(key, m, 11)
    head = _leaf(key, m, 12)
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


def reference_row(request: Dict, item) -> tuple:
    """What the reference reads of a finished request: (history tokens,
    profile, served tokens)."""
    return request["tokens"], request["profile"], np.asarray(item)


def answer_tokens(item) -> np.ndarray:
    """The served tokens an answer holds: the item's semantic IDs."""
    return np.asarray(item)


# -- costs --------------------------------------------------------------------
#
# Operations and bytes the served work needs, from the model's shapes.
# Counts are of useful work: real (unpadded) tokens, the top-k experts a
# token is routed to (not the padded expert slots), causal attention over
# the positions a token may see, and the logits head only where a token is
# sampled.  Copied in spirit from ``benchmarks/analytic.py`` (2 x active
# parameters per token) and ``benchmarks/bench_kernels.py`` (paged-decode
# bytes: ``head_dim + 4`` bytes per fp8 position-head, ``2 * head_dim`` in
# bf16); kept here so that no later change to the program can move them.

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


def decode_flops(m: dict, positions: Sequence[int]) -> int:
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
                      positions: Sequence[int]) -> tuple:
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
