"""A cell, a traffic mix and a per-layer metric added only as new files
(and entries in the spec) are picked up without an edit to the harness."""

from __future__ import annotations

import json

from bench import harness
from bench.tests import helpers

NEW_READER = '''"""Requests the window answered (a test metric)."""


def read(ctx):
    return float(sum(1 for v in ctx.latencies_ms if v != float("inf")))
'''


def test_new_cell_mix_and_metric_are_found(tmp_path):
    spec, bd = helpers.tiny_bench(tmp_path, helpers.tiny_conf())
    mix = json.loads((bd / "traffic" / "tiny-open.json").read_text())
    mix["rate_per_s"] = 4.0
    (bd / "traffic" / "tiny-slow.json").write_text(json.dumps(mix))
    (bd / "metrics" / "answered.py").write_text(NEW_READER)
    spec["workloads"].append({"name": "tiny.slow", "config": "tiny",
                              "traffic": "tiny-slow", "chips": 1})
    spec["per_layer"].append({
        "name": "answered.slow", "unit": "requests", "better": "higher",
        "source": "host_clock", "layer": "serving/engine",
        "moves": "latency_p50_ms", "workloads": ["tiny.slow"]})
    assert harness.metric_reader(bd, "answered.slow") is not None
    res = helpers.run(spec, bd, "tiny.slow", seed=4, seconds=1.5,
                      trace=True)
    assert res["attempted"] == 6
    assert res["metrics"]["answered.slow"]["value"] == 6.0
    assert res["metrics"]["answered.slow"]["unit"] == "requests"
