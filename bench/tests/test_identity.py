"""The OneRec family reproduces, bit for bit, what the benchmark made
before its model-specific code moved into ``families/onerec.py``: the
requests of two mixes, weight leaves, reference logits and the operation
and byte counts.  ``data/onerec-identity.npz`` was written by the code
before the move, at the tiny configuration (and the full one for the
counts)."""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from bench import traffic, weights
from bench.tests import helpers

ROOT = Path(__file__).resolve().parents[2]
FIXTURE = np.load(helpers.DATA / "onerec-identity.npz")
ONEREC = helpers.family()
TINY = helpers.tiny_conf()["model"]
FULL = json.loads((ROOT / "bench/configs/onerec-v2-fp8.json").read_text())
MODELS = {"tiny": TINY, "full": FULL["model"]}
SEEDS = (1, 2_147_480_011)
LEAVES = ("backbone/embed/table", "backbone/stacks/0/p0/moe/experts/down",
          "profile_proj/kernel")
PREFILLS = [([25, 7, 1], [0, 0, 0]), ([3], [10]), ([385, 385], [0, 0]),
            ([4, 9], [100, 40])]
DECODES = [[99], [0, 5, 387], list(range(0, 388, 17))]


@pytest.mark.parametrize("mix", ["tiny-open", "tiny-revisit"])
@pytest.mark.parametrize("seed", SEEDS)
def test_requests_match_the_parent(mix, seed):
    params = json.loads((helpers.DATA / f"{mix}.json").read_text())
    w = traffic.generate(params, ONEREC, TINY, seed, 2.0)
    tag = f"{mix}.{seed}"
    np.testing.assert_array_equal(np.asarray(w.due_s), FIXTURE[f"{tag}.due_s"])
    np.testing.assert_array_equal([len(r["tokens"]) for r in w.requests],
                                  FIXTURE[f"{tag}.lengths"])
    np.testing.assert_array_equal(
        np.concatenate([r["tokens"] for r in w.requests]),
        FIXTURE[f"{tag}.tokens"])
    np.testing.assert_array_equal(np.stack([r["profile"] for r in w.requests]),
                                  FIXTURE[f"{tag}.profiles"])


@pytest.mark.parametrize("path", LEAVES)
def test_weight_leaves_match_the_parent(path):
    tree = weights.init_params(weights.root_key(SEEDS[1]),
                               ONEREC.leaf_specs(TINY))
    for p in path.split("/"):
        tree = tree[p]
    np.testing.assert_array_equal(np.asarray(tree, np.float32),
                                  FIXTURE[f"leaf.{path}"])


@pytest.mark.parametrize("bits", [None, 4], ids=["program", "int4"])
def test_reference_logits_match_the_parent(bits):
    rows = [(FIXTURE[f"row{i}.tokens"], FIXTURE[f"row{i}.profile"],
             FIXTURE[f"row{i}.served"]) for i in range(4)]
    got = ONEREC.reference_logits(TINY, SEEDS[1], rows, weight_bits=bits)
    want = FIXTURE["reference" if bits is None else "reference.int4"]
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("size", MODELS)
def test_counts_match_the_parent(size):
    m = MODELS[size]
    np.testing.assert_array_equal(
        [ONEREC.prefill_flops(m, n, s) for n, s in PREFILLS],
        FIXTURE[f"flops.{size}.prefill"])
    np.testing.assert_array_equal([ONEREC.decode_flops(m, p) for p in DECODES],
                                  FIXTURE[f"flops.{size}.decode"])
    np.testing.assert_array_equal(
        [ONEREC.paged_decode_cost(m, kv, ps, p)
         for kv, ps in (("float8_e4m3fn", 32), ("bfloat16", 8))
         for p in DECODES],
        FIXTURE[f"cost.{size}.paged_decode"])
