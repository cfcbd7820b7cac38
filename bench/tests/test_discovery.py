"""A cell, a traffic mix, a per-layer metric and a model family added only
as new files (and entries in the spec) are picked up without an edit to
the harness, and the modules outside the families name no field of one
model."""

from __future__ import annotations

import json
import re

import pytest

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


COUNTING_FAMILY = '''"""A test family: the OneRec family, with a line in
``calls.log`` beside this file for every call the harness makes into it."""

from pathlib import Path

from bench import harness

_HERE = Path(__file__).resolve().parent
_base = harness.load_family(_HERE.parent, "onerec")


def _counted(name):
    fn = getattr(_base, name)

    def inner(*args, **kw):
        with open(_HERE / "calls.log", "a") as f:
            f.write(name + "\\n")
        return fn(*args, **kw)
    return inner


for _name in ("build_engine", "fresh_request", "sessions", "warm_up",
              "reference_logits", "reference_row", "answer_tokens",
              "prefill_flops", "decode_flops", "paged_decode_cost"):
    globals()[_name] = _counted(_name)
CALLS = _base.CALLS
'''


def test_a_family_added_as_one_file_runs_a_cell(tmp_path):
    conf = dict(helpers.tiny_conf(), family="counting")
    spec, bd = helpers.tiny_bench(tmp_path, conf)
    (bd / "families" / "counting.py").write_text(COUNTING_FAMILY)
    res = helpers.run(spec, bd, "tiny.open", seed=2_147_480_021,
                      seconds=1.5)
    assert res["correct"] is True and res["attempted"] == 9
    calls = (bd / "families" / "calls.log").read_text().split()
    assert calls.count("build_engine") == 1
    assert calls.count("warm_up") == 1
    assert calls.count("fresh_request") == 9
    assert calls.count("reference_logits") == 1
    sampled = conf["reference"]["sample_requests"]
    assert calls.count("reference_row") == sampled
    assert calls.count("answer_tokens") == 2 * sampled


ONEREC_FIELDS = re.compile(r"\b(profile(s|_dim)?|n_codebooks|decode_len"
                           r"|history_len|codebook_size|OneRecConfig)\b")
NEUTRAL = sorted(
    str(p.relative_to(harness.BENCH)) for p in harness.BENCH.glob("**/*.py")
    if p.parts[len(harness.BENCH.parts)] not in ("families", "tests"))


@pytest.mark.parametrize("name", NEUTRAL)
def test_neutral_modules_name_no_onerec_field(name):
    text = (harness.BENCH / name).read_text()
    assert not ONEREC_FIELDS.search(text), name
