"""PTQ pass + policy tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BASELINE_POLICY, PAPER_POLICY, QuantizedTensor,
                        dequantize_params, is_quantized, quantize_params)


def _quantized_by_path(qp):
    """{param path: QuantizedTensor} over a quantized pytree (tags are set
    to param paths by quantize_params)."""
    out = {}
    for leaf in jax.tree_util.tree_leaves(
            qp, is_leaf=lambda x: isinstance(x, QuantizedTensor)):
        if isinstance(leaf, QuantizedTensor):
            out[leaf.tag] = leaf
    return out


def _fake_params(key=jax.random.PRNGKey(0)):
    return {
        "embed": {"table": jax.random.normal(key, (64, 16))},
        "stacks": {"0": {"p0": {
            "attn": {"q_proj": {"kernel": jax.random.normal(key, (2, 16, 32))},
                     "o_proj": {"kernel": jax.random.normal(key, (2, 32, 16))}},
            "attn_norm": {"scale": jnp.ones((2, 16))},
            "moe": {
                "router": {"kernel": jax.random.normal(key, (2, 16, 4))},
                "experts": {"gate": jax.random.normal(key, (2, 4, 128, 128)),
                            "up": jax.random.normal(key, (2, 4, 128, 128)),
                            "down": jax.random.normal(key, (2, 4, 128, 128))},
                "shared": {"gate": {"kernel": jax.random.normal(key, (2, 16, 32))},
                           "up": {"kernel": jax.random.normal(key, (2, 16, 32))},
                           "down": {"kernel": jax.random.normal(key, (2, 32, 16))}},
            },
        }}},
        "lm_head": {"kernel": jax.random.normal(key, (16, 64))},
    }


def test_policy_coverage():
    qp, rep = quantize_params(_fake_params(), PAPER_POLICY, with_report=True)
    l0 = qp["stacks"]["0"]["p0"]
    # quantized: qkvo, MoE experts (block), shared experts
    assert is_quantized(l0["attn"]["q_proj"]["kernel"])
    assert is_quantized(l0["attn"]["o_proj"]["kernel"])
    assert l0["moe"]["experts"]["gate"].granularity == "block"
    assert is_quantized(l0["moe"]["shared"]["gate"]["kernel"])
    # NOT quantized: embeddings, norms, router, lm_head
    assert not is_quantized(qp["embed"]["table"])
    assert not is_quantized(qp["lm_head"]["kernel"])
    assert not is_quantized(l0["attn_norm"]["scale"])
    assert not is_quantized(l0["moe"]["router"]["kernel"])
    # q, o, 3 grouped expert kernels, 3 shared-expert kernels
    assert rep.n_quantized == 8
    assert rep.bytes_after < 0.3 * rep.bytes_before


def test_baseline_policy_noop():
    params = _fake_params()
    qp = quantize_params(params, BASELINE_POLICY)
    assert not any(isinstance(l, QuantizedTensor)
                   for l in jax.tree_util.tree_leaves(
                       qp, is_leaf=lambda x: isinstance(x, QuantizedTensor)))


def test_dequantize_roundtrip_structure():
    params = _fake_params()
    qp = quantize_params(params, PAPER_POLICY)
    dq = dequantize_params(qp, jnp.float32)
    assert jax.tree_util.tree_structure(dq) == \
        jax.tree_util.tree_structure(params)
    # dequantized weights close to originals
    a = np.asarray(dq["stacks"]["0"]["p0"]["attn"]["q_proj"]["kernel"])
    b = np.asarray(params["stacks"]["0"]["p0"]["attn"]["q_proj"]["kernel"])
    assert np.linalg.norm(a - b) / np.linalg.norm(b) < 0.04


def test_quantize_params_traceable():
    """PTQ must be jax-traceable (eval_shape'd by the dry-run)."""
    shapes = jax.eval_shape(lambda: quantize_params(_fake_params(),
                                                    PAPER_POLICY))
    q = shapes["stacks"]["0"]["p0"]["moe"]["experts"]["gate"]
    assert q.data.shape == (2, 4, 128, 128)
    assert q.data.dtype == jnp.float8_e4m3fn
    assert q.scale.shape == (2, 4, 1, 1)


def test_report_kind_matches_granularity():
    """Regression: every report entry's ``kind`` must describe the scheme
    actually APPLIED, consistent with the produced tensor's granularity."""
    expected_gran = {"linear": "per_channel", "block": "block",
                     "int8": "per_channel"}
    qp, rep = quantize_params(_fake_params(), PAPER_POLICY, with_report=True)
    by_path = _quantized_by_path(qp)
    assert set(by_path) == {e["path"] for e in rep.entries}
    for e in rep.entries:
        q = by_path[e["path"]]
        assert e["granularity"] == q.granularity, e
        assert q.granularity == expected_gran[e["kind"]], e
        assert e["pattern"] is not None, e


def test_int8_report_kind_regression():
    """The ``fmt='int8'`` path applies per-channel int8 EVERYWHERE (block
    int8 is unimplemented) but used to record ``kind='block'`` for
    block-pattern groups.  The report must say what ran."""
    pol = PAPER_POLICY.replace(fmt="int8")
    qp, rep = quantize_params(_fake_params(), pol, with_report=True)
    by_path = _quantized_by_path(qp)
    expert_entries = [e for e in rep.entries if "experts" in e["path"]]
    assert expert_entries, "fixture lost its block-pattern groups"
    for e in rep.entries:
        q = by_path[e["path"]]
        assert e["kind"] == "int8", e
        assert e["granularity"] == "per_channel", e
        assert q.data.dtype == jnp.int8
        assert q.granularity == "per_channel"


def test_int8_override_on_one_group():
    """A per-group "int8" override downgrades just that group while the
    rest keeps the paper's fp8 scheme — and the report tells them apart."""
    pol = PAPER_POLICY.override("*/attn/q_proj/kernel", "int8")
    qp, rep = quantize_params(_fake_params(), pol, with_report=True)
    by_path = _quantized_by_path(qp)
    kinds = {e["path"]: e["kind"] for e in rep.entries}
    qk = "stacks/0/p0/attn/q_proj/kernel"
    assert kinds[qk] == "int8"
    assert by_path[qk].data.dtype == jnp.int8
    ok = "stacks/0/p0/attn/o_proj/kernel"
    assert kinds[ok] == "linear"
    assert by_path[ok].data.dtype == jnp.float8_e4m3fn
    assert kinds["stacks/0/p0/moe/experts/gate"] == "block"


@pytest.mark.parametrize("policy", [PAPER_POLICY, BASELINE_POLICY,
                                    PAPER_POLICY.replace(fmt="int8")],
                         ids=["fp8", "baseline", "int8"])
def test_leaf_at_a_time_build_matches_quantize_params(policy):
    """The served-param builder (one program per leaf, stacked leaves
    quantized one layer at a time) equals init + PTQ under one jit bit
    for bit, tags included, and PTQ of its output is a no-op."""
    from repro.configs import registry
    from repro.core.ptq import build_quantized_params
    from repro.models import onerec

    cfg = registry.get_arch("onerec-v2").reduced_config()
    init = lambda k: onerec.init_onerec(k, cfg, jnp.bfloat16)
    key = jax.random.PRNGKey(3)
    built = build_quantized_params(init, key, policy)
    ref = jax.jit(lambda k: quantize_params(init(k), policy))(key)
    leaves, treedef = jax.tree_util.tree_flatten(built)
    ref_leaves, ref_treedef = jax.tree_util.tree_flatten(ref)
    assert treedef == ref_treedef
    for a, b in zip(leaves, ref_leaves):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    again = jax.tree_util.tree_leaves(quantize_params(built, policy))
    assert all(x is y for x, y in zip(again, leaves))
