"""The plain f32 reference against the engine at the reduced widths, and
the control that the limits are set against."""

from __future__ import annotations

import numpy as np
import pytest

from bench import harness
from bench.tests import helpers

MODEL = helpers.tiny_conf()["model"]
ONEREC = helpers.family()


def teacher_forced(engine, requests, served):
    """The engine's own prefill and paged-decode logits, each decode step
    fed the served token, for requests in slots 0..n-1."""
    ex, n = engine.executor, len(requests)
    for s, r in enumerate(requests):
        assert ex.grant_slot(s, len(r["tokens"]) + 1 + 3)
    steps = [np.asarray(ex.prefill_insert(
        [r["tokens"] for r in requests], [r["profile"] for r in requests],
        list(range(n)))[:n], np.float32)]
    lengths = np.zeros(ex.n_slots, np.int32)
    lengths[:n] = [len(r["tokens"]) + 1 for r in requests]
    for t in range(2):
        toks = np.zeros((ex.n_slots, 1), np.int32)
        toks[:n, 0] = [s[t] for s in served]
        steps.append(np.asarray(ex.decode(toks, lengths), np.float32)[:n])
        lengths[:n] += 1
    return np.stack(steps, 1)


@pytest.mark.parametrize("fused", ["off", "interpret"])
def test_reference_matches_prefill_and_paged_decode(fused):
    conf = helpers.tiny_conf(use_fp8=False, kv_dtype="bfloat16",
                             fused_decode=fused)
    engine = harness.build_engine(ONEREC, conf, 7)
    rng = np.random.default_rng(0)
    reqs = [{"tokens": rng.integers(0, 192, size=3 * k),
             "profile": rng.normal(size=64)} for k in (2, 5, 8, 8)]
    served, _ = engine.serve_requests(reqs)
    got = teacher_forced(engine, reqs, served)
    ref = ONEREC.reference_logits(MODEL, 7, [(r["tokens"], r["profile"], s)
                                             for r, s in zip(reqs, served)])
    # bf16 weights, activations and KV against f32: ~1% of the logits'
    # scale (max |logit| ~4 here)
    assert np.abs(got - ref).max() < 0.1
    np.testing.assert_array_equal(got.argmax(-1), ref.argmax(-1))
    np.testing.assert_array_equal(np.stack(served), ref.argmax(-1))


def test_int4_control_fails_where_the_program_passes(tmp_path):
    """At the test size, through the harness: the fp8 program's run is
    correct under the limits the configuration holds, and the same run
    with the int4 control in the program's place (the reference with int4
    weights, judged by the token it puts first) is not."""
    spec, bd = helpers.tiny_bench(tmp_path, helpers.tiny_conf())
    for seed in (1, 2):
        prog = helpers.run(spec, bd, "tiny.open", seed=seed, seconds=2.0)
        ctrl = helpers.run(spec, bd, "tiny.open", seed=seed, seconds=2.0,
                           control=True)
        assert prog["correct"] is True, prog["checks"]
        assert ctrl["correct"] is False, ctrl["checks"]
        assert ctrl["failed"] == 0
