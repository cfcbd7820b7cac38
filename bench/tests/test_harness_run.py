"""The harness end to end on the CPU at the reduced widths: the run's
chip check is skipped, everything else (build, warm-up, window, metric
readers, reference check, result object) runs as on the chip."""

from __future__ import annotations

import math

import pytest

from bench.tests import helpers


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    conf = helpers.tiny_conf(fused_decode="interpret")
    return helpers.tiny_bench(tmp_path_factory.mktemp("b"), conf)


def test_open_loop_run_through_the_pallas_kernel_is_correct(bench):
    spec, bd = bench
    res = helpers.run(spec, bd, "tiny.open", seed=3_000_000_001,
                      seconds=1.5)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True
    assert res["failed"] == 0
    # 6 requests per second, all due inside the window
    assert res["attempted"] == round(6.0 * 1.5)
    m = res["metrics"]
    assert set(m) == {"setup_s", "latency_p50_ms"}
    assert m["latency_p50_ms"]["value"] > 0
    assert m["latency_p50_ms"]["unit"] == "ms"
    assert res["device"]["platform"] == "cpu"
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())


def test_traced_closed_loop_run_reads_per_layer_metrics(bench):
    spec, bd = bench
    res = helpers.run(spec, bd, "tiny.closed", seed=2, seconds=1.0,
                      trace=True)
    assert res["correct"] is True
    m = res["metrics"]
    # the host-side readers find their numbers on any backend; the
    # device-trace readers find no chip plane on the CPU and stay silent
    assert m["build_s"]["value"] > 0
    assert 0 < m["slot_occupancy.backlog"]["value"] <= 100
    for silent in ("mfu.backlog", "idle_share.backlog"):
        assert silent not in m
    dev = res["device"]
    assert dev["window_s"] > 0 and dev["busy_s"] == 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_end_to_end_metrics_of_a_closed_cell(bench):
    spec, bd = bench
    res = helpers.run(spec, bd, "tiny.closed", seed=5, seconds=1.0)
    m = res["metrics"]
    assert set(m) == {"setup_s", "items_per_s"}
    assert m["items_per_s"]["value"] > 0
    assert res["attempted"] >= 6 and res["failed"] == 0
    assert not any(math.isinf(c["value"]) for c in res["checks"].values())


def test_returning_users_resume_from_the_prefix_cache(bench):
    spec, bd = bench
    spec = dict(spec, per_layer=spec["per_layer"] + [{
        "name": "prefix_hit_frac", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "serving/kv_cache",
        "moves": "latency_p50_ms", "workloads": ["tiny.revisit"]}])
    res = helpers.run(spec, bd, "tiny.revisit", seed=1, seconds=3.0,
                      trace=True)
    assert res["correct"] is True and res["failed"] == 0
    assert res["metrics"]["prefix_hit_frac"]["value"] > 30
