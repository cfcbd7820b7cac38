"""Unit tests of the benchmark's yardstick: traffic, operation and byte
counts, the peak table, the configurations and the weights' layout."""

from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, peaks, traffic, weights
from bench.tests import helpers

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
FULL = json.loads((ROOT / "bench/configs/onerec-v2-fp8.json").read_text())
M = FULL["model"]
ONEREC = helpers.family()


def history_items(w, m=M):
    return [len(r["tokens"]) // m["n_codebooks"] for r in w.requests]
FRESH = {"arrival": "poisson", "rate_per_s": 20.0, "mix_seed": 4,
         "lengths": {"cap_items": 128, "cap_share": 0.6, "min_items": 2,
                     "max_items": 127}}


# -- traffic ---------------------------------------------------------------

def test_generator_is_deterministic_from_the_seed():
    a = traffic.generate(FRESH, ONEREC, M, 2 ** 31 + 7, 10.0)
    b = traffic.generate(FRESH, ONEREC, M, 2 ** 31 + 7, 10.0)
    assert a.due_s == b.due_s
    for x, y in zip(a.requests, b.requests):
        np.testing.assert_array_equal(x["tokens"], y["tokens"])
        np.testing.assert_array_equal(x["profile"], y["profile"])


def test_seeds_share_sizes_and_gaps_in_another_order():
    a = traffic.generate(FRESH, ONEREC, M, 1, 10.0)
    b = traffic.generate(FRESH, ONEREC, M, 2, 10.0)
    assert len(a.requests) == len(b.requests) == 200
    # one schedule: the same lengths due at the same times ...
    assert history_items(a) == history_items(b)
    assert a.due_s == b.due_s
    assert max(a.due_s) < 10.0 and min(a.due_s) > 0
    # ... in a shuffled order of the mix's lengths and Poisson gaps
    assert history_items(a) != sorted(history_items(a))
    gaps = np.diff([0.0] + a.due_s)
    np.testing.assert_allclose(sorted(gaps), traffic.poisson_gaps(
        FRESH["rate_per_s"], 200, 10.0), rtol=1e-9)
    assert list(gaps) != sorted(gaps)
    # ... sent by other users
    assert not np.array_equal(a.requests[0]["tokens"],
                              b.requests[0]["tokens"])


def test_fresh_lengths_follow_the_mix():
    items = traffic.fresh_items(FRESH["lengths"], 200)
    assert items.count(128) == 120
    rest = [i for i in items if i != 128]
    assert min(rest) == 2 and 120 <= max(rest) <= 127


def test_the_cell_sends_every_history_at_the_configured_length():
    for cell in SPEC["workloads"]:
        mix = json.loads(harness.traffic_path(
            ROOT / "bench", cell["traffic"]).read_text())
        conf = json.loads((ROOT / harness.find(
            SPEC["configs"], cell["config"], "config")["file"]).read_text())
        w = traffic.generate(mix, ONEREC, conf["model"], 2 ** 31 + 5, 4.0)
        assert set(history_items(w, conf["model"])) == {
            conf["model"]["history_len"]}


def test_sessions_revisit_and_grow():
    mix = {"arrival": "poisson", "rate_per_s": 30.0, "mix_seed": 3,
           "sessions": {"users": 20, "zipf": 1.1, "start_items": [16, 96],
                        "grow_items": [1, 3]}}
    w = traffic.generate(mix, ONEREC, M, 9, 10.0)
    seen = {}
    grew = 0
    for r in w.requests:
        key = r["profile"].tobytes()
        if key in seen:
            prev = seen[key]
            assert np.array_equal(r["tokens"][:len(prev)], prev)
            grew += len(r["tokens"]) > len(prev)
        seen[key] = r["tokens"]
    assert len(seen) <= 20 and grew > 100
    assert max(len(r["tokens"]) for r in w.requests) <= 128 * 3


# -- counts ------------------------------------------------------------------

def test_active_params_and_flops_against_hand_values():
    # attention 2*2048*2048 + 2*2048*512, two experts 2*3*2048*4096,
    # router 2048*12
    assert ONEREC.layer_params_active(M) == 10_485_760 + 50_331_648 + 24_576
    per_layer = 2 * 60_841_984
    # one decode token at position 99 (100 keys): 12 layers of weights,
    # 12 layers of 4*100*16*128 attention, and the head 2*2048*8256
    assert ONEREC.decode_flops(M, [99]) == (
        12 * per_layer + 12 * 4 * 100 * 16 * 128 + 2 * 2048 * 8256)
    # a fresh prefill of profile + 2 tokens: keys 1, 2, 3; head once
    assert ONEREC.prefill_flops(M, [3]) == (
        3 * 12 * per_layer + 12 * 4 * (1 + 2 + 3) * 16 * 128
        + 2 * 2048 * 8256 + 2 * 64 * 2048)
    # a resume of 2 tokens at positions 10 and 11: keys 11 and 12
    assert ONEREC.prefill_flops(M, [2], [10]) == (
        2 * 12 * per_layer + 12 * 4 * (11 + 12) * 16 * 128
        + 2 * 2048 * 8256)


def test_paged_decode_bytes_against_hand_values():
    # a row at position 40 maps 2 pages of 32: 64 positions x 4 KV heads,
    # fp8 K and V with f32 scales (128 + 4 bytes each), plus q and out
    flops, nbytes = ONEREC.paged_decode_cost(M, "float8_e4m3fn", 32, [40])
    assert flops == 4 * 41 * 16 * 128 * 12
    assert nbytes == (64 * 4 * 2 * 132 + 2 * 2 * 16 * 128) * 12
    _, nb16 = ONEREC.paged_decode_cost(M, "bfloat16", 32, [40])
    assert nb16 == (64 * 4 * 2 * 256 + 2 * 2 * 16 * 128) * 12


def test_unknown_device_kind_raises():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


# -- configurations and weights ------------------------------------------------

@pytest.mark.parametrize("name", [c["name"] for c in SPEC["configs"]])
def test_configuration_is_the_served_model(name):
    from repro.configs import onerec_v2
    from repro.models.onerec import PROFILE_DIM

    conf = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
    assert ONEREC.model_config(conf["model"]) == onerec_v2.CONFIG
    assert conf["model"]["profile_dim"] == PROFILE_DIM


def test_weights_have_the_engine_layout():
    from repro.configs import onerec_v2
    from repro.models.onerec import init_onerec

    m = json.loads((ROOT / "bench/tests/data/onerec-tiny.json").read_text())
    m = m["model"]
    key = jax.random.PRNGKey(0)
    ours = jax.eval_shape(
        lambda k: weights.init_params(k, ONEREC.leaf_specs(m)), key)
    theirs = jax.eval_shape(
        lambda k: init_onerec(k, ONEREC.model_config(m), jnp.bfloat16), key)
    assert jax.tree_util.tree_structure(ours) == \
        jax.tree_util.tree_structure(theirs)
    for a, b in zip(jax.tree_util.tree_leaves(ours),
                    jax.tree_util.tree_leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    # full width too, by shape alone
    full = jax.eval_shape(
        lambda k: weights.init_params(k, ONEREC.leaf_specs(M)), key)
    cfull = jax.eval_shape(
        lambda k: init_onerec(k, onerec_v2.CONFIG, jnp.bfloat16), key)
    assert [l.shape for l in jax.tree_util.tree_leaves(full)] == \
        [l.shape for l in jax.tree_util.tree_leaves(cfull)]


def test_one_layer_regenerates_the_stacked_leaf():
    m = json.loads((ROOT / "bench/tests/data/onerec-tiny.json").read_text())
    m = m["model"]
    key = weights.root_key(3_000_000_000)
    specs = ONEREC.leaf_specs(m)
    tree = weights.init_params(key, specs)
    gate = tree["backbone"]["stacks"]["0"]["p0"]["moe"]["experts"]["gate"]
    idx = [p for p, *_ in specs].index(
        "backbone/stacks/0/p0/moe/experts/gate")
    for layer in range(m["n_layers"]):
        np.testing.assert_array_equal(
            np.asarray(gate[layer], np.float32),
            np.asarray(weights.layer_leaf(key, specs, idx, layer),
                       np.float32))


# -- the benchmark file ----------------------------------------------------------

def test_benchmark_file_names_what_exists():
    names = [c["name"] for c in SPEC["configs"]]
    assert len(set(names)) == len(names)
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).exists()
    for w in SPEC["workloads"]:
        assert w["config"] in names and w["chips"] == 1
        assert harness.traffic_path(ROOT / "bench", w["traffic"]).exists()
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    for m in metrics:
        assert harness.metric_reader(ROOT / "bench", m["name"])
    for w in SPEC["workloads"]:
        e2e = harness.cell_metrics(SPEC, w["name"], "end_to_end")
        assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
        assert harness.cell_metrics(SPEC, w["name"], "per_layer")
