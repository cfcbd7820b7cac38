"""Fused paged-decode attention (paper §4.2 "Attention optimization" on the
PR 7 paged KV pool).

Decode under the paged layout used to materialize each request's logically
dense pool view on device — an O(max_len) gather + dequant + masked softmax
per step, even for a request three tokens deep.  This kernel keeps the page
INDIRECTION on device instead: the grid runs (slot x kv-head x page-table
entries) with the page axis innermost/sequential, and the per-request page
table rides in as a SCALAR-PREFETCH operand so each K/V block's index map
resolves ``table[slot, entry]`` — the Pallas grid pipeline then DMAs exactly
the physical pages a slot maps, overlapping the next page's HBM->VMEM copy
with the current page's compute (the TPU paged-attention idiom).

Everything the host-side chain did per step happens in registers:

  * FP8 e4m3 K/V payloads dequantize against their per-(position, head)
    f32 scales right after the block lands in VMEM (``dequantize_kv``
    semantics: f32 payload x scale, cast to the compute dtype),
  * the branch-tree mask — (logical < prefix start) | (own branch span) —
    plus position validity (``pos >= 0 && pos <= length``) applies to each
    score tile; unmapped table entries point at the pool's sentinel page
    whose ``pos`` lane is permanently -1, so they contribute exactly zero,
  * online softmax (m/l/acc f32 scratch) folds the page blocks into one
    normalized output, zeroing rows with no valid key (inactive slots).

Single-token decode is the degenerate tree: one branch whose ``starts``
entry is pushed past every logical position, so the "shared prefix" covers
the whole row and the span term is dead — one kernel serves both modes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _decode_kernel(tabs_ref, len_ref, st_ref, *refs, scale: float,
                   page_size: int, group: int, n_p: int, branch_stride: int,
                   quantized: bool, out_dtype):
    """Blocks: q (1,1,CG,hd); k/v (ps,hd) — KV head h's lanes of physical
    page tab[b,p]; pos (1,1,ps); [k/v scales (ps,Kv), all heads of the
    page]; o (1,1,CG,hd); scratch m/l (CG,1) f32, acc (CG,hd) f32.  Rows
    fold (branch, group-head): r = c * group + g."""
    if quantized:
        (q_ref, k_ref, v_ref, pos_ref, ks_ref, vs_ref,
         o_ref, m_ref, l_ref, acc_ref) = refs
    else:
        q_ref, k_ref, v_ref, pos_ref, o_ref, m_ref, l_ref, acc_ref = refs
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    h = pl.program_id(1)
    p_idx = pl.program_id(2)

    @pl.when(p_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cg = q_ref.shape[2]
    q = q_ref[0, 0]                                        # (CG, hd)
    k = k_ref[...]                                         # (ps, hd)
    v = v_ref[...]
    if quantized:
        # in-register dequant, bit-compatible with core.quant.dequantize_kv:
        # f32 payload x per-(position, head) scale, cast to the compute
        # dtype.  The scale block holds every KV head of the page; head h's
        # column is picked with a one-hot lane sum (exact: one term)
        head = jax.lax.broadcasted_iota(jnp.int32, ks_ref.shape, 1) == h
        ks = jnp.sum(jnp.where(head, ks_ref[...], 0.0), axis=1, keepdims=True)
        vs = jnp.sum(jnp.where(head, vs_ref[...], 0.0), axis=1, keepdims=True)
        k = (k.astype(jnp.float32) * ks).astype(q.dtype)
        v = (v.astype(jnp.float32) * vs).astype(q.dtype)
    elif k.dtype != q.dtype:
        k = k.astype(q.dtype)
        v = v.astype(q.dtype)

    scores = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale        # (CG, ps)

    # table entries are dense in logical position: entry p of any table
    # covers logical span [p*ps, (p+1)*ps), whatever physical page it maps
    length = len_ref[b]
    start = st_ref[b]
    posv = pos_ref[0]                                      # (1, ps) stored pos
    logical = p_idx * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)                      # (1, ps)
    c_idx = jax.lax.broadcasted_iota(jnp.int32, (cg, 1), 0) // group
    own_lo = start + c_idx * branch_stride                 # (CG, 1)
    shared = logical < start
    own = (logical >= own_lo) & (logical < own_lo + branch_stride)
    valid = ((posv >= 0) & (posv <= length) & (shared | own))  # (CG, ps)
    scores = jnp.where(valid, scores, NEG_INF)

    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, jnp.max(scores, axis=-1, keepdims=True))
    alpha = jnp.exp(m_old - m_new)
    p = jnp.exp(scores - m_new)
    p = jnp.where(valid, p, 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(p_idx == n_p - 1)
    def _finalize():
        l = l_ref[...]
        out = jnp.where(l > 0, acc_ref[...] / jnp.maximum(l, 1e-20), 0.0)
        o_ref[0, 0] = out.astype(out_dtype)


def paged_decode_pallas(q, k, v, pos, k_scale, v_scale, tables, lengths,
                        starts, *, page_size: int, group: int,
                        branch_stride: int, scale: float,
                        out_dtype=jnp.bfloat16, interpret: bool = False):
    """q (B, Kv, C*G, hd) with rows r = c*G + g (``group`` = G); k/v
    (NPos, Kv, hd) flat pool payload (NPos = (n_pages + 1) * page_size,
    sentinel page last); pos (NPos // page_size, page_size); k_scale /
    v_scale (NPos, Kv) f32 or None (BF16 pool); tables (B, P) int32
    physical page per logical entry (sentinel = unmapped); lengths/starts
    (B,) int32.

    Every block's last two dims must tile the TPU's (8, 128) vreg or span
    the whole array dim, so the kernel sees views of the same buffers:
    k/v as (NPos, Kv*hd) — block (ps, hd) at (page, head) — pos as
    (pages, 1, ps), and the scales as (ps, Kv) blocks of whole pages."""
    bb, kv, cg, hd = q.shape
    n_p = tables.shape[1]
    quantized = k_scale is not None
    grid = (bb, kv, n_p)
    k = k.reshape(k.shape[0], kv * hd)
    v = v.reshape(v.shape[0], kv * hd)
    pos = pos.reshape(pos.shape[0], 1, page_size)

    def _q_map(b, h, p, tabs, lens, sts):
        return (b, h, 0, 0)

    def _kv_map(b, h, p, tabs, lens, sts):
        return (tabs[b, p], h)

    def _pos_map(b, h, p, tabs, lens, sts):
        return (tabs[b, p], 0, 0)

    def _scale_map(b, h, p, tabs, lens, sts):
        return (tabs[b, p], 0)

    in_specs = [
        pl.BlockSpec((1, 1, cg, hd), _q_map),
        pl.BlockSpec((page_size, hd), _kv_map),
        pl.BlockSpec((page_size, hd), _kv_map),
        pl.BlockSpec((1, 1, page_size), _pos_map),
    ]
    args = [q, k, v, pos]
    if quantized:
        in_specs += [pl.BlockSpec((page_size, kv), _scale_map),
                     pl.BlockSpec((page_size, kv), _scale_map)]
        args += [k_scale, v_scale]
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, page_size=page_size,
                          group=group, n_p=n_p, branch_stride=branch_stride,
                          quantized=quantized, out_dtype=out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, cg, hd), _q_map),
            scratch_shapes=[
                pltpu.VMEM((cg, 1), jnp.float32),
                pltpu.VMEM((cg, 1), jnp.float32),
                pltpu.VMEM((cg, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((bb, kv, cg, hd), out_dtype),
        interpret=interpret,
        # a stable kernel name in profiler traces (paged_decode_roofline)
        name="paged_decode",
    )(tables, lengths, starts, *args)
