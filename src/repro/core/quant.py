"""FP8 quantization primitives (the paper's §4.1 scheme).

Implements the numerics of "Quantized Inference for OneRec-V2":

  * Linear layers:   per-CHANNEL weight scales (offline, from the
                     high-precision parameters) x per-TOKEN dynamic
                     activation scales (runtime amax over the feature dim).
  * MoE grouped GEMM: BLOCK-wise scales — activations ``1 x 128`` along the
                     last dim, weights ``128 x 128``.
  * Matmuls run in FP8 (e4m3) with FP32 accumulation and are cast back to
    the high-precision compute dtype (bf16 on TPU) afterwards.
  * Quantized weights are stored as ``(fp8 data, fp32 scale)`` pairs.

Everything here is pure jnp and jit-safe; the Pallas kernels in
``repro.kernels`` implement fused versions of the same contracts and are
tested against these functions.
"""

from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# FP8 formats
# ---------------------------------------------------------------------------

E4M3 = jnp.float8_e4m3fn
E5M2 = jnp.float8_e5m2

# e4m3fn has no inf; out-of-range casts produce NaN, so we always clamp to
# the finite max before casting.
FP8_MAX = {E4M3: 448.0, E5M2: 57344.0}

DEFAULT_BLOCK = 128  # the paper's 1x128 / 128x128 block granularity
_EPS = 1e-12


def fp8_finfo_max(dtype) -> float:
    return FP8_MAX[jnp.dtype(dtype).type if not isinstance(dtype, type) else dtype] \
        if dtype in FP8_MAX else float(jnp.finfo(dtype).max)


# ---------------------------------------------------------------------------
# QuantizedTensor pytree
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedTensor:
    """An fp8 tensor plus its fp32 scale(s).

    ``granularity`` is one of:
      * ``"per_tensor"``  — scale shape ``()``.
      * ``"per_channel"`` — scale broadcastable against ``data`` with exactly
        one non-singleton axis (the quantized-output-channel axis).
      * ``"per_token"``   — scale has data's leading shape, last dim 1.
      * ``"block"``       — 2-D blocked: ``data`` logically tiled in
        ``block x block`` tiles (or ``1 x block`` for activations), scale has
        one entry per tile.

    Dequantized value == ``data.astype(f32) * broadcast(scale)``.

    ``act_scale`` (optional, third pytree CHILD) is a CALIBRATED static
    activation scale for the matmul that consumes this weight: when set,
    ``fp8_linear`` casts the incoming activation straight onto the fp8 grid
    with it instead of running the per-token runtime amax reduction.  Shaped
    ``(*data.shape[:-2], 1, 1)`` so scan-stacked leaves slice per layer and
    the scale still broadcasts against ``(..., tokens, features)``.

    ``tag`` (aux data) names the param path this weight came from; aux
    survives ``tree_map`` slicing, so per-layer slices of a stacked leaf
    keep the tag — it keys activation-amax capture during calibration.
    """

    data: jax.Array          # fp8
    scale: jax.Array         # fp32
    granularity: str = "per_channel"
    block: int = DEFAULT_BLOCK
    act_scale: Optional[jax.Array] = None   # f32, static act scale (or None)
    tag: Optional[str] = None               # param path (capture key)

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        return ((self.data, self.scale, self.act_scale),
                (self.granularity, self.block, self.tag))

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, scale, act_scale = children
        return cls(data=data, scale=scale, granularity=aux[0], block=aux[1],
                   act_scale=act_scale, tag=aux[2])

    # -- helpers -------------------------------------------------------------
    @property
    def shape(self):
        return self.data.shape

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def ndim(self):
        return self.data.ndim

    def dequantize(self, dtype=jnp.float32) -> jax.Array:
        if self.granularity in ("block", "block_act"):
            return _dequantize_block(self, dtype)
        return (self.data.astype(jnp.float32) * self.scale).astype(dtype)

    def nbytes(self) -> int:
        n = int(np.prod(self.data.shape)) + 4 * int(np.prod(self.scale.shape))
        if self.act_scale is not None:
            n += 4 * int(np.prod(self.act_scale.shape))
        return n


def is_quantized(x: Any) -> bool:
    return isinstance(x, QuantizedTensor)


# ---------------------------------------------------------------------------
# Scale computation + casting
# ---------------------------------------------------------------------------


def amax_to_scale(amax, fmt=E4M3) -> jax.Array:
    """scale s.t. x/s fits the fp8 grid: s = amax / fp8_max (floored at eps).

    Public seam for calibration (``repro.core.ptq``) and the auto-tuner:
    accepts device arrays or plain floats.
    """
    return jnp.maximum(jnp.asarray(amax, jnp.float32), _EPS) / FP8_MAX[fmt]


_amax_to_scale = amax_to_scale  # internal alias (historical name)


def cast_to_fp8(x: jax.Array, scale: jax.Array, fmt=E4M3) -> jax.Array:
    """Divide by scale, clamp into the finite fp8 range, round-to-nearest."""
    fmax = FP8_MAX[fmt]
    y = x.astype(jnp.float32) / scale
    y = jnp.clip(y, -fmax, fmax)
    return y.astype(fmt)


def quantize_per_tensor(w: jax.Array, fmt=E4M3) -> QuantizedTensor:
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)))
    scale = _amax_to_scale(amax, fmt)
    return QuantizedTensor(cast_to_fp8(w, scale, fmt), scale, "per_tensor")


def quantize_per_channel(w: jax.Array, contract_axis: int = -2, fmt=E4M3) -> QuantizedTensor:
    """Offline weight quantization, one scale per output channel (paper §4.1).

    Reduces ONLY over the contraction (input) axis, so a scan-stacked kernel
    ``(L, in, out)`` gets independent ``(L, 1, out)`` scales per layer.  The
    scale folds out of the matmul: ``X @ (Wq * s) == (X @ Wq) * s``.
    """
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=contract_axis, keepdims=True)
    scale = _amax_to_scale(amax, fmt)
    return QuantizedTensor(cast_to_fp8(w, scale, fmt), scale, "per_channel")


def is_fp8_dtype(dtype) -> bool:
    """True when ``dtype`` is one of the FP8 storage formats."""
    return jnp.dtype(dtype).type in FP8_MAX


def quantize_kv(x: jax.Array, fmt=E4M3) -> Tuple[jax.Array, jax.Array]:
    """KV-cache quantization: one dynamic scale per (position, head).

    ``x`` is (..., heads, head_dim); the amax reduces over head_dim only, so
    every appended token of every KV head carries its own scale — the
    per-row scale is recomputed from the token's own amax at write time
    (amax tracking at the finest granularity the cache layout stores).
    Returns ``(fp8 data, f32 scale)`` with ``scale.shape == x.shape[:-1]``.
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = _amax_to_scale(amax, fmt)
    return cast_to_fp8(x, scale[..., None], fmt), scale


def dequantize_kv(data: jax.Array, scale: jax.Array,
                  dtype=jnp.bfloat16) -> jax.Array:
    """Inverse of ``quantize_kv``: broadcast the per-(position, head) scale
    back over head_dim.  This is the in-register dequant at the attention
    read — FP8 is the storage/bandwidth format, compute stays ``dtype``."""
    return (data.astype(jnp.float32) * scale[..., None]).astype(dtype)


def quantize_per_token(x: jax.Array, fmt=E4M3) -> QuantizedTensor:
    """Runtime dynamic activation quantization: one scale per row/token.

    Reduces over the last (feature) dim; any leading dims are "tokens".
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = _amax_to_scale(amax, fmt)
    return QuantizedTensor(cast_to_fp8(x, scale, fmt), scale, "per_token")


def _pad_to_multiple(x: jax.Array, mult: int, axis: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def quantize_blockwise(
    w: jax.Array, block: int = DEFAULT_BLOCK, fmt=E4M3, act: bool = False
) -> QuantizedTensor:
    """Block-wise quantization (paper's MoE grouped-GEMM granularity).

    * ``act=False`` (weights): ``block x block`` tiles over the LAST TWO dims;
      leading dims (e.g. the expert dim of a stacked ``(E, in, out)`` tensor)
      each get their own tile grid. Scale shape ``(..., in/b, out/b)``.
    * ``act=True`` (activations): ``1 x block`` tiles along the last dim only.
      Scale shape ``(..., tokens, in/b)``.

    Shapes must be multiples of ``block`` (all production dims here are).
    """
    if act:
        if w.shape[-1] % block:
            raise ValueError(f"act dim {w.shape[-1]} not a multiple of {block}")
        nb = w.shape[-1] // block
        xb = w.reshape(*w.shape[:-1], nb, block)
        amax = jnp.max(jnp.abs(xb.astype(jnp.float32)), axis=-1)          # (..., nb)
        scale = _amax_to_scale(amax, fmt)                                  # (..., nb)
        q = cast_to_fp8(xb, scale[..., None], fmt).reshape(w.shape)
        return QuantizedTensor(q, scale, "block_act", block)

    if w.ndim < 2:
        raise ValueError("block weight quantization needs >=2 dims")
    if w.shape[-1] % block or w.shape[-2] % block:
        raise ValueError(f"weight dims {w.shape[-2:]} not multiples of {block}")
    bi, bo = w.shape[-2] // block, w.shape[-1] // block
    xb = w.reshape(*w.shape[:-2], bi, block, bo, block)
    amax = jnp.max(jnp.abs(xb.astype(jnp.float32)), axis=(-3, -1))        # (..., bi, bo)
    scale = _amax_to_scale(amax, fmt)
    q = cast_to_fp8(xb, scale[..., :, None, :, None], fmt).reshape(w.shape)
    return QuantizedTensor(q, scale, "block", block)


def _dequantize_block(q: QuantizedTensor, dtype=jnp.float32) -> jax.Array:
    b = q.block
    d = q.data.astype(jnp.float32)
    if q.granularity == "block_act":  # activation: 1 x block tiles on last dim
        nb = d.shape[-1] // b
        xb = d.reshape(*d.shape[:-1], nb, b) * q.scale[..., None]
        return xb.reshape(d.shape).astype(dtype)
    bi, bo = d.shape[-2] // b, d.shape[-1] // b
    xb = d.reshape(*d.shape[:-2], bi, b, bo, b) * q.scale[..., :, None, :, None]
    return xb.reshape(d.shape).astype(dtype)


# ---------------------------------------------------------------------------
# Activation-amax capture (calibration; eager-only, free under jit)
# ---------------------------------------------------------------------------

_ACT_AMAX: Optional[Dict[str, float]] = None


@contextlib.contextmanager
def capture_act_amax():
    """Record the running max |activation| per consuming weight ``tag``.

    While active, every ``fp8_linear`` call on a tagged weight with a
    CONCRETE input folds ``max|x|`` into the yielded ``{tag: amax}`` dict.
    Tracers are ignored (like ``repro.core.stats.tap``), so calibration
    must run eagerly — e.g. ``forward(..., unroll_layers=True)`` — and the
    capture costs nothing in jitted production code.
    """
    global _ACT_AMAX
    prev = _ACT_AMAX
    _ACT_AMAX = {}
    try:
        yield _ACT_AMAX
    finally:
        _ACT_AMAX = prev


def _record_act_amax(tag: Optional[str], x) -> None:
    if _ACT_AMAX is None or tag is None or isinstance(x, jax.core.Tracer):
        return
    amax = float(jnp.max(jnp.abs(x.astype(jnp.float32))))  # lint: allow[hidden-host-sync]
    if amax > _ACT_AMAX.get(tag, 0.0):
        _ACT_AMAX[tag] = amax


# ---------------------------------------------------------------------------
# FP8 matmuls (XLA path; the Pallas kernels fuse the same math)
# ---------------------------------------------------------------------------


def fp8_linear(
    x: jax.Array,
    wq: QuantizedTensor,
    *,
    fmt=E4M3,
    out_dtype=None,
    precomputed_xq: Optional[QuantizedTensor] = None,
    act_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """The paper's Linear-layer FP8 path (Fig. 2).

    per-token dynamic act quant -> fp8 x fp8 dot with f32 accumulation ->
    rescale by (act scale ⊗ channel scale) -> cast back to compute dtype.

    ``wq`` must be per-channel over the OUTPUT axis of a ``(in, out)`` kernel
    so both scales fold outside the dot.

    When a STATIC activation scale is available — passed as ``act_scale`` or
    carried on the weight (``wq.act_scale``, attached from a calibration
    artifact) — the runtime per-token amax reduction is skipped entirely:
    the input is cast straight onto the fp8 grid with the calibrated scale.
    """
    out_dtype = out_dtype or x.dtype
    if wq.granularity not in ("per_channel", "per_tensor"):
        raise ValueError(f"fp8_linear needs per_channel/per_tensor weights, got {wq.granularity}")
    _record_act_amax(wq.tag, x)
    w_scale = wq.scale  # (1, out) or ()
    if wq.granularity == "per_channel":
        w_scale = wq.scale.reshape(-1)  # (out,)
    if act_scale is None:
        act_scale = wq.act_scale
    if precomputed_xq is None and act_scale is not None:
        xd = cast_to_fp8(x, act_scale, fmt)      # no runtime amax reduce
        acc = jnp.dot(xd, wq.data, preferred_element_type=jnp.float32)
        out = acc * act_scale * w_scale
        return out.astype(out_dtype)
    xq = precomputed_xq if precomputed_xq is not None else quantize_per_token(x, fmt)
    acc = jnp.dot(xq.data, wq.data, preferred_element_type=jnp.float32)
    out = acc * xq.scale * w_scale
    return out.astype(out_dtype)


# ---------------------------------------------------------------------------
# Block-scaled fp8 GEMM (XLA path; the Pallas kernels fuse the same math)
# ---------------------------------------------------------------------------

# Trace-time tally of the form each lowered block-scaled GEMM took; module
# state like ``_ACT_AMAX``.  A jitted program adds its GEMMs once, when it
# lowers, so the counts say which programs run which form.
_GEMM_FORMS: Dict[str, int] = {"scaled_out": 0, "dequant": 0}


def gemm_form_counts() -> Dict[str, int]:
    """``{"scaled_out": n, "dequant": m}``: block-scaled GEMMs lowered in
    each form so far in this process (``_block_gemm``)."""
    return dict(_GEMM_FORMS)


def _scales_on_partials(rows: int, block: int) -> bool:
    """Pick the form of ``_block_gemm`` that moves fewer bytes.

    Scaling the partials keeps one f32 partial per K-block,
    ``E * (K/b) * rows * N * 4`` B; dequantizing writes one bf16 copy of the
    weight, ``E * K * N * 2`` B.  The partials are the smaller while
    ``4 * rows < 2 * b``, i.e. ``rows < b / 2``.
    """
    return 2 * rows < block


def _block_gemm(x: jax.Array, data: jax.Array, scale: jax.Array, block: int,
                fmt) -> jax.Array:
    """x (E, C, K) @ block-quantized (data (E, K, N), scale (E, K/b, N/b)).

    The paper's ``1 x b`` activation / ``b x b`` weight scheme with f32
    accumulation; the fp8 values and the scales are the same in both forms,
    which differ only in where the scales are applied (chosen from C by
    ``_scales_on_partials``).  Neither materializes a scale of the weight's
    size.  Returns f32 (E, C, N).
    """
    b = block
    E, C, K = x.shape
    N = data.shape[-1]
    kb = K // b
    xq = quantize_blockwise(x, block=b, fmt=fmt, act=True)       # scale (E, C, kb)
    form = "scaled_out" if _scales_on_partials(C, b) else "dequant"
    _GEMM_FORMS[form] += 1
    with jax.named_scope(f"moe_gemm.{form}"):
        if form == "scaled_out":
            # out = sum_kb (Xq_kb . Wq_kb) * s_x[c, kb] * s_w[kb, n]: the fp8
            # payloads are exact in bf16, one batched dot over (E, kb) merged
            # (XLA:CPU refuses a bf16 dot with two batch dims), and each
            # weight scale repeated along its own b columns only.
            xd = (xq.data.reshape(E, C, kb, b).transpose(0, 2, 1, 3)
                  .reshape(E * kb, C, b).astype(jnp.bfloat16))
            wd = data.reshape(E * kb, b, N).astype(jnp.bfloat16)
            part = jnp.einsum("gcb,gbn->gcn", xd, wd,
                              preferred_element_type=jnp.float32)
            part = (part.reshape(E, kb, C, N)
                    * jnp.swapaxes(xq.scale, 1, 2)[..., None]
                    * jnp.repeat(scale, b, axis=-1)[:, :, None, :])
            return jnp.sum(part, axis=1)
        # Fold each block scale into its fp8-grid operand, then ONE dot: on
        # v5e (no fp8 MXU path) fp8 is the storage and bandwidth format.
        # The barrier keeps the weight's dequant one elementwise pass that
        # writes the bf16 copy: fused into the dot instead, XLA:TPU
        # materializes the scale broadcast to the weight's size in f32.
        xd = (xq.data.reshape(E, C, kb, b).astype(jnp.float32)
              * xq.scale[..., None]).astype(jnp.bfloat16).reshape(E, C, K)
        wd = jax.lax.optimization_barrier(data.reshape(E, kb, b, N))
        wd = (wd.astype(jnp.float32)
              * jnp.repeat(scale, b, axis=-1)[:, :, None, :]
              ).astype(jnp.bfloat16).reshape(E, K, N)
        return jnp.einsum("eck,ekn->ecn", xd, wd,
                          preferred_element_type=jnp.float32)


def fp8_block_matmul(
    x: jax.Array,
    wq: QuantizedTensor,
    *,
    fmt=E4M3,
    out_dtype=None,
) -> jax.Array:
    """Block-scaled matmul (paper: 1x128 act, 128x128 w): x (..., K) @ wq
    (K, N), as one group of ``_block_gemm`` over the flattened rows.  The
    Pallas kernel (``repro.kernels.fp8_gemm``) performs the identical math
    with the accumulator resident in VMEM."""
    out_dtype = out_dtype or x.dtype
    if wq.granularity != "block":
        raise ValueError("fp8_block_matmul needs block-quantized weights")
    out = _block_gemm(x.reshape(1, -1, x.shape[-1]), wq.data[None],
                      wq.scale[None], wq.block, fmt)
    return out.reshape(*x.shape[:-1], -1).astype(out_dtype)


def fp8_grouped_matmul(
    x: jax.Array,
    wq: QuantizedTensor,
    *,
    fmt=E4M3,
    out_dtype=None,
) -> jax.Array:
    """Grouped (per-expert) block-scaled GEMM: x (E, C, K) @ wq (E, K, N)."""
    out_dtype = out_dtype or x.dtype
    if wq.granularity != "block":
        raise ValueError("fp8_grouped_matmul needs block-quantized weights")
    return _block_gemm(x, wq.data, wq.scale, wq.block, fmt).astype(out_dtype)


# ---------------------------------------------------------------------------
# INT8 (beyond-paper: the Limitations section leaves the lower-precision
# frontier unexplored; INT8 shares the scaling machinery, symmetric scheme)
# ---------------------------------------------------------------------------

INT8_MAX = 127.0


def _amax_to_scale_int8(amax: jax.Array) -> jax.Array:
    return jnp.maximum(amax.astype(jnp.float32), _EPS) / INT8_MAX


def cast_to_int8(x: jax.Array, scale: jax.Array) -> jax.Array:
    y = jnp.round(x.astype(jnp.float32) / scale)
    return jnp.clip(y, -INT8_MAX, INT8_MAX).astype(jnp.int8)


def quantize_per_channel_int8(w: jax.Array,
                              contract_axis: int = -2) -> QuantizedTensor:
    """Symmetric per-output-channel INT8 weights."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=contract_axis,
                   keepdims=True)
    scale = _amax_to_scale_int8(amax)
    return QuantizedTensor(cast_to_int8(w, scale), scale, "per_channel")


def quantize_per_token_int8(x: jax.Array) -> QuantizedTensor:
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = _amax_to_scale_int8(amax)
    return QuantizedTensor(cast_to_int8(x, scale), scale, "per_token")


def int8_linear(x: jax.Array, wq: QuantizedTensor, *,
                out_dtype=None) -> jax.Array:
    """W8A8: int8 x int8 -> int32 accumulation, dequant epilogue."""
    out_dtype = out_dtype or x.dtype
    xq = quantize_per_token_int8(x)
    acc = jnp.dot(xq.data, wq.data, preferred_element_type=jnp.int32)
    w_scale = wq.scale.reshape(-1) if wq.granularity == "per_channel" \
        else wq.scale
    out = acc.astype(jnp.float32) * xq.scale * w_scale
    return out.astype(out_dtype)


def fp8_grouped_linear(
    x: jax.Array,
    wq: QuantizedTensor,
    *,
    fmt=E4M3,
    out_dtype=None,
) -> jax.Array:
    """Grouped GEMM with per-channel weight scales (non-128-aligned fallback).

    x (E, C, K) @ wq (E, K, N), scale (E, 1, N): both scales fold outside the
    per-expert dot, so true fp8 operands + f32 accumulation are used.
    """
    out_dtype = out_dtype or x.dtype
    if wq.data.dtype == jnp.int8:                       # W8A8 grouped
        xq = quantize_per_token_int8(x)
        acc = jnp.einsum("eck,ekn->ecn", xq.data, wq.data,
                         preferred_element_type=jnp.int32
                         ).astype(jnp.float32)
    else:
        xq = quantize_per_token(x, fmt)                 # scale (E, C, 1)
        acc = jnp.einsum("eck,ekn->ecn", xq.data, wq.data,
                         preferred_element_type=jnp.float32)
    sw = wq.scale if wq.granularity == "per_channel" else \
        jnp.reshape(wq.scale, (1, 1, 1))
    out = acc * xq.scale * sw
    return out.astype(out_dtype)


# ---------------------------------------------------------------------------
# Convenience dispatch used by layers: dense() with either raw or fp8 kernels
# ---------------------------------------------------------------------------


def matmul_any(x: jax.Array, w, *, out_dtype=None) -> jax.Array:
    """``x @ w`` where ``w`` is a raw array OR a QuantizedTensor.

    This is the single dispatch point the whole model zoo funnels through,
    so PTQ'ing a model == swapping leaves of its param pytree.
    """
    if isinstance(w, QuantizedTensor):
        if w.granularity == "block":
            return fp8_block_matmul(x, w, out_dtype=out_dtype or x.dtype)
        if w.data.dtype == jnp.int8:
            return int8_linear(x, w, out_dtype=out_dtype or x.dtype)
        return fp8_linear(x, w, out_dtype=out_dtype or x.dtype)
    out_dtype = out_dtype or x.dtype
    return jnp.dot(x, w.astype(x.dtype),
                   preferred_element_type=jnp.float32).astype(out_dtype)


def quant_error(x: jax.Array, q: QuantizedTensor) -> jax.Array:
    """Relative L2 quantization error (used by tests + distribution report)."""
    xf = x.astype(jnp.float32)
    err = jnp.linalg.norm(xf - q.dequantize()) / (jnp.linalg.norm(xf) + _EPS)
    return err
