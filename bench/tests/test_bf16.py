"""The bf16 baseline at the test size (``data/onerec-tiny-bf16.json``):
its runs are correct, its control is not, and every fault a served cell
can have comes out not correct.

The control of a bf16 configuration is the program with its own path in
the precision below switched on: the fp8 policy, run on the same traffic
and seeds and judged by the bf16 configuration's limits.  The int4
control of the fp8 configuration fails it as well."""

from __future__ import annotations

import pytest

from bench.tests import helpers
from bench.tests import test_faults as faults

SEEDS = (1, 2_147_480_011)


@pytest.fixture(scope="module")
def bf16(tmp_path_factory):
    return helpers.tiny_bench(tmp_path_factory.mktemp("bf16"),
                              helpers.tiny_conf("onerec-tiny-bf16"))


@pytest.mark.parametrize("seed", SEEDS)
def test_bf16_run_is_correct_and_the_int4_control_is_not(bf16, seed):
    spec, bd = bf16
    prog = helpers.run(spec, bd, "tiny.open", seed=seed, seconds=2.0)
    ctrl = helpers.run(spec, bd, "tiny.open", seed=seed, seconds=2.0,
                       control=True)
    assert prog["correct"] is True, prog["checks"]
    assert ctrl["correct"] is False, ctrl["checks"]
    assert ctrl["failed"] == 0


@pytest.mark.parametrize("seed", SEEDS)
def test_the_fp8_program_fails_the_bf16_limits(bf16, tmp_path, seed):
    spec, bd = bf16
    limits = helpers.tiny_conf("onerec-tiny-bf16")["limits"]
    fp8_spec, fp8_bd = helpers.tiny_bench(tmp_path, helpers.tiny_conf(
        "onerec-tiny-bf16", use_fp8=True, kv_dtype="float8_e4m3fn"))
    fp8 = helpers.run(fp8_spec, fp8_bd, "tiny.open", seed=seed, seconds=2.0)
    assert fp8["failed"] == 0
    assert any(c["value"] > limits[name]
               for name, c in fp8["checks"].items()), fp8["checks"]


@pytest.mark.parametrize("fault,cell", [
    (faults.alter_tokens, "tiny.open"),
    (faults.alter_one_slot, "tiny.open"),
    (faults.drop_kv_writes, "tiny.open"),
    (faults.half_batch, "tiny.closed")],
    ids=lambda f: getattr(f, "__name__", f))
def test_bf16_fault_is_not_correct(bf16, monkeypatch, fault, cell):
    spec, bd = bf16
    faults._broken(monkeypatch, fault)
    res = helpers.run(spec, bd, cell, seed=1, seconds=2.0)
    assert res["correct"] is False
    assert res["failed"] == 0
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
