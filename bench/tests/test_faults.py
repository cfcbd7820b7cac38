"""A run whose timed path is broken underneath must come out not
correct: once for each fault a served cell can have."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.tests import helpers


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return helpers.tiny_bench(tmp_path_factory.mktemp("b"),
                              helpers.tiny_conf())


def _broken(monkeypatch, breaker):
    build = harness.build_engine

    def build_broken(conf, seed):
        engine = build(conf, seed)
        breaker(engine.executor)
        return engine

    monkeypatch.setattr(harness, "build_engine", build_broken)


def alter_tokens(ex):
    """Every selected token moved to its neighbour id where it is made."""
    select = ex.select_scored

    def select_altered(logits):
        vals, ids, lse = select(logits)
        return vals, (np.asarray(ids) + 1) % ex.cfg.vocab_size, lse
    ex.select_scored = select_altered


def alter_one_slot(ex):
    """Slot 0's decode tokens moved to their neighbour ids where they are
    made: a fault confined to one slot of the batch."""
    decode = ex.decode

    def decode_altered(tokens, lengths):
        logits = decode(tokens, lengths)
        return logits.at[0].set(jnp.roll(logits[0], 1))
    ex.decode = decode_altered


def drop_kv_writes(ex):
    """The decode step leaves the cache as it was: its K/V writes go to
    the dropped index."""
    decode = ex._decode_paged

    def decode_unchanged(params, pool, tokens, lengths, psc, pgi):
        return decode(params, pool, tokens, lengths,
                      np.full_like(np.asarray(psc), ex._drop), pgi)
    ex._decode_paged = decode_unchanged


def half_batch(ex):
    """Half of each prefill group is left out: its rows are served the
    other half's histories."""
    prefill = ex.prefill_insert

    def prefill_half(tokens_list, profiles, slots):
        h = (len(tokens_list) + 1) // 2
        keep = lambda xs: [xs[i % h] for i in range(len(xs))]
        return prefill(keep(tokens_list), keep(profiles), slots)
    ex.prefill_insert = prefill_half


@pytest.mark.parametrize("fault,cell", [
    (alter_tokens, "tiny.open"), (alter_one_slot, "tiny.open"),
    (drop_kv_writes, "tiny.open"),
    # groups of several requests form under a closed loop
    (half_batch, "tiny.closed")], ids=lambda f: getattr(f, "__name__", f))
def test_fault_is_not_correct(bench, monkeypatch, fault, cell):
    spec, bd = bench
    _broken(monkeypatch, fault)
    res = helpers.run(spec, bd, cell, seed=1, seconds=2.0)
    assert res["correct"] is False
    # failed by a number the configuration holds, not by a lost request
    assert res["failed"] == 0
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
