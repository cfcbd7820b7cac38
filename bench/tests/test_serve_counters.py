"""The readers of the engine's lifecycle and host-time counters: each
turns its ``stats()`` key into ms and stays silent on an engine that lacks
the key, and the fresh cell reports all three."""

from __future__ import annotations

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness
from bench.tests import helpers

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
READERS = {"queue_wait_ms": "queue_wait_p50_s",
           "decode_phase_ms": "decode_phase_p50_s",
           "host_ms_per_step": "host_s_per_step"}


@pytest.mark.parametrize("name,key", READERS.items())
def test_reader_turns_its_counter_into_ms(name, key):
    read = harness.metric_reader(harness.BENCH, name)
    assert read(SimpleNamespace(stats={key: 0.2465})) == pytest.approx(246.5)
    assert read(SimpleNamespace(stats={key: 0.0})) == 0.0


@pytest.mark.parametrize("name,key", READERS.items())
def test_reader_is_silent_without_its_counter(name, key):
    read = harness.metric_reader(harness.BENCH, name)
    others = {k: 1.0 for k in READERS.values() if k != key}
    assert read(SimpleNamespace(stats=others)) is None


def test_the_fresh_cell_reports_the_three():
    per_layer = harness.cell_metrics(SPEC, "onerec-v2-fp8.fresh",
                                     "per_layer")
    by_name = {m["name"]: m for m in per_layer}
    for name in READERS:
        m = by_name[name]
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "ms", "lower", "program_counter", "latency_p50_ms")


def test_a_traced_open_run_reads_the_three(tmp_path):
    spec, bd = helpers.tiny_bench(tmp_path, helpers.tiny_conf())
    res = helpers.run(spec, bd, "tiny.open", seed=2_147_480_011,
                      seconds=1.5, trace=True)
    m = res["metrics"]
    for name in READERS:
        assert m[name]["unit"] == "ms"
    # every item takes decode steps after its first token, and every
    # round that dispatched a program ran host code around it
    assert m["queue_wait_ms"]["value"] >= 0
    assert m["decode_phase_ms"]["value"] > 0
    assert m["host_ms_per_step"]["value"] > 0
