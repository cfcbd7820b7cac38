"""Serving engine: batching, padding, metrics, kernel-topk plumbing."""

import jax
import numpy as np
import pytest

from repro.configs.base import OneRecConfig, TransformerConfig
from repro.configs.registry import get_arch
from repro.data.onerec_data import OneRecStreamConfig, SemanticIDStream
from repro.models import onerec as onerec_model
from repro.serving import EngineConfig, ServingEngine
from repro.serving.requests import make_request


@pytest.fixture(scope="module")
def engine_setup():
    cfg = get_arch("onerec-v2").reduced_config()
    params = onerec_model.init_onerec(jax.random.PRNGKey(0), cfg)
    stream = SemanticIDStream(OneRecStreamConfig(
        codebook_size=cfg.transformer.vocab_size - 64,
        history_len=cfg.history_len, global_batch=4))
    return cfg, params, stream


def _reqs(stream, n):
    out = []
    step = 0
    while len(out) < n:
        r = stream.serve_request_at(step)
        out += [{"tokens": r["tokens"][i], "profile": r["profile"][i]}
                for i in range(r["tokens"].shape[0])]
        step += 1
    return out[:n]


def test_engine_batches_and_pads(engine_setup):
    cfg, params, stream = engine_setup
    eng = ServingEngine(params, cfg, EngineConfig(batch_size=4))
    outs, stats = eng.serve_requests(_reqs(stream, 10))  # 2 full + pad batch
    assert len(outs) == 10
    assert all(o.shape == (cfg.decode_len,) for o in outs)
    assert stats["throughput_rps"] > 0
    assert stats["p99_latency_s"] >= stats["mean_latency_s"] * 0.5


def test_engine_fp8_and_bf16_agree_mostly(engine_setup):
    cfg, params, stream = engine_setup
    reqs = _reqs(stream, 8)
    o1, _ = ServingEngine(params, cfg, EngineConfig(
        batch_size=4, use_fp8=False)).serve_requests(reqs)
    o2, _ = ServingEngine(params, cfg, EngineConfig(
        batch_size=4, use_fp8=True)).serve_requests(reqs)
    # random-init logits are near-uniform, so greedy tokens flip easily;
    # trained-model parity lives in test_system.test_fp8_serving_hitrate_parity
    agree = np.mean([np.mean(a == b) for a, b in zip(o1, o2)])
    assert agree > 0.3


def test_engine_deterministic(engine_setup):
    cfg, params, stream = engine_setup
    reqs = _reqs(stream, 4)
    eng = ServingEngine(params, cfg, EngineConfig(batch_size=4))
    a, _ = eng.serve_requests(reqs)
    b, _ = eng.serve_requests(reqs)
    np.testing.assert_array_equal(np.stack(a), np.stack(b))


def test_engine_reports_moe_gemm_forms():
    """At 128-aligned widths the experts are block-quantized: decode's few
    rows per expert take the scaled-partials form, prefill's many rows
    the dequantize-once form, and ``stats()`` counts both."""
    cfg = OneRecConfig(
        name="onerec-gemm-forms-test",
        history_len=8,
        transformer=TransformerConfig(
            name="onerec-gemm-forms-test-backbone",
            n_layers=1, d_model=128, n_heads=4, n_kv_heads=2, head_dim=32,
            d_ff=256, vocab_size=256, moe=True, n_experts=4, top_k=2,
            d_expert=128, capacity_factor=16.0, ep_degree=4,
            max_seq_len=64, remat=False),
        serve_batch=2, beam_width=4)
    params = onerec_model.init_onerec(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    req = make_request(rng.integers(0, 192, size=8 * cfg.n_codebooks),
                       rng.normal(size=onerec_model.PROFILE_DIM))
    eng = ServingEngine(params, cfg, EngineConfig(batch_size=2))
    _, stats = eng.serve_requests([req])
    assert stats["prefill_calls"] >= 1 and stats["decode_steps"] >= 1
    forms = stats["moe_gemm_forms"]
    assert forms["scaled_out"] > 0 and forms["dequant"] > 0, forms
