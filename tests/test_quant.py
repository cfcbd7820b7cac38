"""Unit + property tests for the FP8 quantization primitives (paper §4.1)."""

import jax
import jax.extend.core as jax_core
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import hnp, hypothesis, st

from repro.core import quant
from repro.kernels.fp8_grouped_gemm.ref import fp8_grouped_gemm_ref

hypothesis.settings.register_profile(
    "ci", deadline=None, max_examples=30,
    suppress_health_check=[hypothesis.HealthCheck.too_slow])
hypothesis.settings.load_profile("ci")

finite_floats = st.floats(min_value=-1e4, max_value=1e4,
                          allow_nan=False, allow_infinity=False, width=32)


@hypothesis.given(hnp.arrays(np.float32, hnp.array_shapes(
    min_dims=2, max_dims=2, min_side=2, max_side=64), elements=finite_floats))
def test_per_token_quant_error_bound(x):
    """e4m3 has 3 mantissa bits: |x - dq(q(x))| <= |x|/16 + scale*2^-9."""
    q = quant.quantize_per_token(jnp.asarray(x))
    dq = np.asarray(q.dequantize())
    scale = np.asarray(q.scale)
    bound = np.abs(x) / 16.0 + scale * 2.0 ** -9 + 1e-12
    assert np.all(np.abs(x - dq) <= bound + 1e-6)


@hypothesis.given(hnp.arrays(np.float32, (8, 16), elements=finite_floats))
def test_quant_idempotent(x):
    q1 = quant.quantize_per_token(jnp.asarray(x))
    q2 = quant.quantize_per_token(q1.dequantize(jnp.float32))
    np.testing.assert_allclose(np.asarray(q1.dequantize()),
                               np.asarray(q2.dequantize()),
                               rtol=1e-6, atol=1e-6)


@hypothesis.given(hnp.arrays(np.float32, (4, 8), elements=st.floats(
    min_value=-100, max_value=100, allow_nan=False, width=32)),
    st.integers(min_value=-3, max_value=3))
def test_per_token_scale_invariance_pow2(x, e):
    """Power-of-two rescaling rescales the dequantized output exactly."""
    c = float(2.0 ** e)
    q1 = quant.quantize_per_token(jnp.asarray(x))
    q2 = quant.quantize_per_token(jnp.asarray(x * c))
    np.testing.assert_allclose(np.asarray(q2.dequantize()),
                               c * np.asarray(q1.dequantize()),
                               rtol=1e-6, atol=1e-30)


def test_fp8_range_saturation():
    x = jnp.array([[1e9, -1e9, 0.0, 1.0]])
    q = quant.quantize_per_token(x)
    assert np.all(np.isfinite(np.asarray(q.data.astype(jnp.float32))))
    # amax maps to fp8 max exactly
    assert np.isclose(np.abs(np.asarray(q.data.astype(jnp.float32))).max(),
                      quant.FP8_MAX[quant.E4M3])


def test_per_channel_scale_shape_stacked():
    w = jax.random.normal(jax.random.PRNGKey(0), (3, 16, 32))
    q = quant.quantize_per_channel(w)
    assert q.scale.shape == (3, 1, 32)  # per (layer, out-channel)
    # independent per-layer scales
    w2 = w.at[0].multiply(100.0)
    q2 = quant.quantize_per_channel(w2)
    assert np.allclose(np.asarray(q2.scale[1:]), np.asarray(q.scale[1:]))
    assert not np.allclose(np.asarray(q2.scale[0]), np.asarray(q.scale[0]))


def test_blockwise_shapes_and_error():
    w = jax.random.normal(jax.random.PRNGKey(0), (256, 384))
    q = quant.quantize_blockwise(w)
    assert q.scale.shape == (2, 3)
    err = float(quant.quant_error(w, q))
    assert err < 0.04  # e4m3 L2 error on gaussian data

    x = jax.random.normal(jax.random.PRNGKey(1), (8, 256), jnp.bfloat16)
    qa = quant.quantize_blockwise(x, act=True)
    assert qa.granularity == "block_act"
    assert qa.scale.shape == (8, 2)


def test_block_outlier_isolation():
    """Block scales isolate an outlier to its 128x128 tile (the paper's
    motivation for 1x128/128x128 granularity)."""
    w = jax.random.normal(jax.random.PRNGKey(0), (256, 256))
    w = w.at[0, 0].set(1e6)
    q = quant.quantize_blockwise(w)
    dq = np.asarray(q.dequantize())
    # the tile NOT containing the outlier keeps small error
    clean = np.asarray(w)[128:, 128:]
    rel = np.linalg.norm(clean - dq[128:, 128:]) / np.linalg.norm(clean)
    assert rel < 0.04
    # per-TENSOR scaling would crush everything else
    qt = quant.quantize_per_tensor(w)
    dqt = np.asarray(qt.dequantize())
    rel_t = np.linalg.norm(clean - dqt[128:, 128:]) / np.linalg.norm(clean)
    assert rel_t > 10 * rel


@pytest.mark.parametrize("shape", [(8, 64, 128), (1, 128, 256)])
def test_fp8_linear_matches_f32_within_tolerance(shape):
    _, K, N = shape
    M = shape[0]
    x = jax.random.normal(jax.random.PRNGKey(0), (M, K), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N))
    out = quant.fp8_linear(x, quant.quantize_per_channel(w))
    ref = np.asarray(x.astype(jnp.float32)) @ np.asarray(w)
    rel = np.linalg.norm(np.asarray(out, np.float32) - ref) \
        / np.linalg.norm(ref)
    assert rel < 0.06


def test_grouped_matmul_paths_agree():
    E, C, K, N = 2, 16, 256, 128
    x = jax.random.normal(jax.random.PRNGKey(0), (E, C, K), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (E, K, N))
    ref = np.einsum("eck,ekn->ecn", np.asarray(x, np.float32), np.asarray(w))
    for q in (quant.quantize_blockwise(w),
              quant.quantize_per_channel(w)):
        if q.granularity == "block":
            out = quant.fp8_grouped_matmul(x, q)
        else:
            out = quant.fp8_grouped_linear(x, q)
        rel = np.linalg.norm(np.asarray(out, np.float32) - ref) \
            / np.linalg.norm(ref)
        assert rel < 0.06, (q.granularity, rel)


@pytest.mark.parametrize("C,form", [(8, "scaled_out"), (256, "dequant")])
def test_grouped_matmul_forms(C, form):
    """Few rows per expert scale the f32 partials (the kernel oracle's
    order); many rows dequantize the weight once (bit for bit the plain
    repeat-the-scales formula).  Both stay within fp8 error of an f32
    einsum."""
    E, K, N, b = 2, 256, 256, 128
    kb = K // b
    x = jax.random.normal(jax.random.PRNGKey(0), (E, C, K), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (E, K, N))
    q = quant.quantize_blockwise(w)
    before = quant.gemm_form_counts()
    out = np.asarray(quant.fp8_grouped_matmul(x, q), np.float32)
    after = quant.gemm_form_counts()
    assert {k: after[k] - before[k] for k in after} == {
        "scaled_out": int(form == "scaled_out"),
        "dequant": int(form == "dequant")}
    if form == "scaled_out":
        oracle = np.asarray(fp8_grouped_gemm_ref(x, q.data, q.scale),
                            np.float32)
        # f32 summation order alone differs: one bf16 rounding apart
        np.testing.assert_allclose(out, oracle, rtol=2.0 ** -7, atol=1e-6)
    else:
        xq = quant.quantize_blockwise(x, act=True)
        xd = (xq.data.reshape(E, C, kb, b).astype(jnp.float32)
              * xq.scale[..., None]).astype(jnp.bfloat16).reshape(E, C, K)
        sw = jnp.repeat(jnp.repeat(q.scale, b, axis=-2), b, axis=-1)
        wd = (q.data.astype(jnp.float32) * sw).astype(jnp.bfloat16)
        oracle = np.asarray(jnp.einsum(
            "eck,ekn->ecn", xd, wd, preferred_element_type=jnp.float32
        ).astype(jnp.bfloat16), np.float32)
        np.testing.assert_array_equal(out, oracle)
    ref = np.einsum("eck,ekn->ecn", np.asarray(x, np.float32), np.asarray(w))
    assert np.linalg.norm(out - ref) / np.linalg.norm(ref) < 0.06


def _eqn_outputs(jaxpr):
    """Every equation's output avals, sub-jaxprs (pjit, scan, ...) included."""
    for eqn in jaxpr.eqns:
        yield from (v.aval for v in eqn.outvars)
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                if isinstance(sub, jax_core.ClosedJaxpr):
                    yield from _eqn_outputs(sub.jaxpr)
                elif isinstance(sub, jax_core.Jaxpr):
                    yield from _eqn_outputs(sub)


def test_decode_grouped_matmul_has_no_weight_sized_f32():
    """At a decode shape no step of the block-scaled GEMM writes an f32
    array as large as the weight: no scale is broadcast to (E, K, N)."""
    E, C, K, N = 2, 8, 256, 256
    x = jax.ShapeDtypeStruct((E, C, K), jnp.bfloat16)
    q = quant.quantize_blockwise(jnp.ones((E, K, N)))
    closed = jax.make_jaxpr(lambda x, q: quant.fp8_grouped_matmul(x, q))(x, q)
    big = [a for a in _eqn_outputs(closed.jaxpr)
           if a.dtype == jnp.float32 and a.size >= E * K * N]
    assert big == []


def test_quantized_tensor_scans_and_jits():
    w = jax.random.normal(jax.random.PRNGKey(0), (4, 32, 64))
    q = quant.quantize_per_channel(w)

    @jax.jit
    def f(qt, x):
        def body(c, wl):
            return c, quant.fp8_linear(x, wl)
        _, ys = jax.lax.scan(body, 0, qt)
        return ys

    ys = f(q, jnp.ones((2, 32), jnp.bfloat16))
    assert ys.shape == (4, 2, 64)
    assert np.all(np.isfinite(np.asarray(ys, np.float32)))
