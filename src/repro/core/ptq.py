"""Post-training quantization pass over parameter pytrees.

``quantize_params`` walks a trained high-precision param pytree and replaces
every policy-matched leaf with a :class:`~repro.core.quant.QuantizedTensor`
storing ``(fp8 data, fp32 scale)`` — exactly the paper's deployment format
("all model weights are pre-quantized and stored in a (FP8 weight, FP32
scale) pair").  Because every matmul in the model zoo funnels through
``repro.core.quant.matmul_any``, the quantized pytree is a drop-in
replacement: no architecture changes, no re-tracing differences beyond the
fp8 ops themselves.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import QuantPolicy, PAPER_POLICY
from repro.core import quant
from repro.core.quant import QuantizedTensor


def _path_str(path) -> str:
    parts = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            parts.append(str(p.key))
        elif isinstance(p, jax.tree_util.SequenceKey):
            parts.append(str(p.idx))
        elif isinstance(p, jax.tree_util.GetAttrKey):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


@dataclasses.dataclass
class PTQReport:
    """What got quantized, how well, and what it saved."""

    entries: List[Dict[str, Any]] = dataclasses.field(default_factory=list)

    def add(self, path: str, kind: str, shape, rel_err: float,
            bytes_before: int, bytes_after: int, *,
            granularity: Optional[str] = None,
            pattern: Optional[str] = None) -> None:
        """``kind`` is the scheme actually APPLIED ('linear'|'block'|'int8'),
        ``granularity`` the produced ``QuantizedTensor.granularity``, and
        ``pattern`` the policy glob that decided this leaf (the tuner's
        group key)."""
        self.entries.append(dict(path=path, kind=kind, shape=tuple(shape),
                                 rel_err=float(rel_err),
                                 bytes_before=bytes_before,
                                 bytes_after=bytes_after,
                                 granularity=granularity,
                                 pattern=pattern))

    @property
    def n_quantized(self) -> int:
        return len(self.entries)

    @property
    def bytes_before(self) -> int:
        return sum(e["bytes_before"] for e in self.entries)

    @property
    def bytes_after(self) -> int:
        return sum(e["bytes_after"] for e in self.entries)

    @property
    def max_rel_err(self) -> float:
        return max((e["rel_err"] for e in self.entries), default=0.0)

    @property
    def mean_rel_err(self) -> float:
        if not self.entries:
            return 0.0
        return float(np.mean([e["rel_err"] for e in self.entries]))

    def summary(self) -> str:
        if not self.entries:
            return "PTQ: nothing quantized (policy disabled or no matches)"
        ratio = self.bytes_before / max(self.bytes_after, 1)
        return (f"PTQ: {self.n_quantized} tensors -> fp8 "
                f"({self.bytes_before / 1e6:.1f} MB -> "
                f"{self.bytes_after / 1e6:.1f} MB, {ratio:.2f}x), "
                f"rel_err mean={self.mean_rel_err:.2e} max={self.max_rel_err:.2e}")


def _policy_fmt(policy: QuantPolicy):
    """The fp8 storage format of ``policy`` (None = its int8 scheme)."""
    if policy.fmt == "int8":
        return None
    return quant.E4M3 if policy.fmt == "e4m3" else quant.E5M2


def _quantize_as(leaf, kind: str, policy: QuantPolicy, fmt
                 ) -> Tuple[QuantizedTensor, str]:
    """Quantize one leaf under the scheme ``policy.match`` chose; returns
    the tensor and the scheme actually applied.  Every scheme reduces over
    the last two dims only, so each leading index (a stacked layer, an
    expert) quantizes on its own."""
    if fmt is None or kind == "int8":
        # int8: per-channel everywhere (block int8 unneeded) — either the
        # policy-wide fmt or a per-group "int8" override.  The report
        # records the scheme actually applied, not the pattern-list kind
        # (a block-matched group under fmt="int8" used to be mislabeled
        # "block" while per-channel int8 was what ran).
        return quant.quantize_per_channel_int8(leaf, contract_axis=-2), "int8"
    if kind == "block":
        return (quant.quantize_blockwise(leaf, block=policy.block, fmt=fmt),
                "block")
    return quant.quantize_per_channel(leaf, contract_axis=-2, fmt=fmt), "linear"


def quantize_params(
    params: Any,
    policy: QuantPolicy = PAPER_POLICY,
    *,
    with_report: bool = False,
    compute_errors: bool = False,
):
    """Apply the paper's PTQ scheme to a param pytree.

    Returns the quantized pytree (and a :class:`PTQReport` when
    ``with_report=True``).  ``compute_errors`` additionally measures the
    per-tensor relative L2 quantization error (costs one dequantize each).
    Leaves that already are :class:`QuantizedTensor` pass through, so a
    tree from :func:`build_quantized_params` is returned as it is.
    """
    fmt = _policy_fmt(policy)
    report = PTQReport()

    def _maybe_quantize(path, leaf):
        if isinstance(leaf, QuantizedTensor):
            return leaf
        if not isinstance(leaf, (jax.Array, np.ndarray)) or not hasattr(leaf, "ndim"):
            return leaf
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf
        p = _path_str(path)
        kind, pattern = policy.match(p, leaf.ndim, leaf.shape)
        if kind is None:
            return leaf
        q, applied = _quantize_as(leaf, kind, policy, fmt)
        q.tag = p  # key for activation-amax capture / static-scale attach
        if with_report:
            err = float(quant.quant_error(leaf, q)) if compute_errors else float("nan")
            report.add(p, applied, leaf.shape, err,
                       bytes_before=leaf.size * leaf.dtype.itemsize,
                       bytes_after=q.nbytes(),
                       granularity=q.granularity, pattern=pattern)
        return q

    quantized = jax.tree_util.tree_map_with_path(
        _maybe_quantize, params,
        is_leaf=lambda x: isinstance(x, QuantizedTensor))
    if with_report:
        return quantized, report
    return quantized


def quantized_leaf_programs(init_fn: Callable[[jax.Array], Any], key,
                            policy: QuantPolicy):
    """One jitted program per leaf of the tree ``init_fn(key)`` builds.

    Program i returns leaf i alone, already quantized under ``policy``:
    XLA drops the random-number work of every other leaf, and a leaf with
    leading (stacked-layer) axes is quantized one leading index at a time.
    So no program holds more than one high-precision leaf, and the leaves
    equal those of ``quantize_params(init_fn(key), policy)`` run under one
    ``jax.jit`` bit for bit.
    ``key`` may be an array or a ``ShapeDtypeStruct`` (ahead-of-time
    compiles).  Returns ``(treedef, [(path, program)])``."""
    fmt = _policy_fmt(policy)
    shapes = jax.eval_shape(init_fn, key)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    programs = []
    for i, (path, leaf) in enumerate(flat):
        p = _path_str(path)
        kind = None
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            kind, _ = policy.match(p, leaf.ndim, leaf.shape)

        def program(k, i=i, kind=kind, p=p):
            w = jax.tree_util.tree_leaves(init_fn(k))[i]
            if kind is None:
                return w
            one = lambda x: _quantize_as(x, kind, policy, fmt)[0]
            q = jax.lax.map(one, w) if w.ndim > 2 else one(w)
            q.tag = p
            return q

        programs.append((p, jax.jit(program)))
    return treedef, programs


def build_quantized_params(init_fn: Callable[[jax.Array], Any], key,
                           policy: QuantPolicy) -> Any:
    """``quantize_params(init_fn(key), policy)``, built one leaf at a time
    (:func:`quantized_leaf_programs`): the device never holds the
    high-precision tree next to the quantized one, only one leaf of it."""
    treedef, programs = quantized_leaf_programs(init_fn, key, policy)
    return jax.tree_util.tree_unflatten(
        treedef, [program(key) for _, program in programs])


def dequantize_params(params: Any, dtype=jnp.bfloat16) -> Any:
    """Inverse transform (for elastic reload / requantization workflows)."""

    def _dq(leaf):
        if isinstance(leaf, QuantizedTensor):
            return leaf.dequantize(dtype)
        return leaf

    return jax.tree_util.tree_map(
        _dq, params, is_leaf=lambda x: isinstance(x, QuantizedTensor))


# ---------------------------------------------------------------------------
# Optional static activation calibration (beyond the paper's dynamic scheme)
# ---------------------------------------------------------------------------


def calibrate_activation_scales(
    apply_fn: Callable[..., Tuple[Any, Dict[str, jax.Array]]],
    params: Any,
    batches,
    *,
    momentum: float = 0.9,
) -> Dict[str, jax.Array]:
    """EMA-of-amax calibration over sample batches.

    ``apply_fn(params, batch)`` must return ``(out, taps)`` where ``taps``
    maps activation names to tensors (models expose this via
    ``capture_stats=True``).  The paper itself uses *dynamic* per-token
    scales at runtime; static scales are provided as an optional mode that
    removes the runtime amax reduction (one of our beyond-paper knobs).
    """
    ema: Dict[str, jax.Array] = {}
    for batch in batches:
        _, taps = apply_fn(params, batch)
        for name, act in taps.items():
            amax = jnp.max(jnp.abs(act.astype(jnp.float32)))
            if name in ema:
                ema[name] = momentum * ema[name] + (1 - momentum) * amax
            else:
                ema[name] = amax
    return {k: quant.amax_to_scale(v) for k, v in ema.items()}


def calibrate_static_act_scales(
    forward_fn: Callable[[Any, Any], Any],
    qparams: Any,
    batches,
    *,
    fmt=None,
) -> Dict[str, float]:
    """Max-of-amax static activation calibration keyed by param path.

    ``forward_fn(qparams, batch)`` must run EAGERLY (e.g. with
    ``unroll_layers=True``) so :func:`quant.capture_act_amax` sees concrete
    values: every fp8 linear folds ``max|x|`` into a dict keyed by the
    consuming weight's ``tag`` (set to its param path by
    :func:`quantize_params`).  Returns plain-float scales ready to ride in
    a policy artifact and be attached via :func:`apply_static_act_scales`.
    """
    fmt = fmt or quant.E4M3
    amax: Dict[str, float] = {}
    for batch in batches:
        with quant.capture_act_amax() as cap:
            forward_fn(qparams, batch)
        for k, v in cap.items():
            if v > amax.get(k, 0.0):
                amax[k] = v
    return {k: float(quant.amax_to_scale(v, fmt)) for k, v in amax.items()}


def apply_static_act_scales(qparams: Any,
                            scales: Mapping[str, float]) -> Any:
    """Attach calibrated static activation scales to quantized leaves.

    Only per-channel / per-tensor FP8 leaves consume a static scale (the
    ``fp8_linear`` static path); block and int8 leaves keep the dynamic
    scheme and are left untouched, as are leaves with no calibrated scale.
    The scale is shaped ``(*data.shape[:-2], 1, 1)`` so scan-stacked leaves
    slice per layer and still broadcast over ``(tokens, features)``.
    """

    def _attach(leaf):
        if not isinstance(leaf, QuantizedTensor):
            return leaf
        if leaf.granularity not in ("per_channel", "per_tensor"):
            return leaf
        if leaf.data.dtype == jnp.int8 or leaf.tag not in scales:
            return leaf
        shape = (*leaf.data.shape[:-2], 1, 1)
        act_scale = jnp.full(shape, scales[leaf.tag], jnp.float32)
        return dataclasses.replace(leaf, act_scale=act_scale)

    return jax.tree_util.tree_map(
        _attach, qparams, is_leaf=lambda x: isinstance(x, QuantizedTensor))
