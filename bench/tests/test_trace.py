"""The reduction from trace events to device numbers."""

from __future__ import annotations

from bench import trace

MS = 1_000_000


def _trace():
    # one chip; window 0..100 ms; ops overlap in 10..30, then 50..60
    ops = {"/device:TPU:0": [("%fusion.1", 10 * MS, 25 * MS),
                             ("%fusion.2", 20 * MS, 30 * MS),
                             ("%copy.3", 50 * MS, 60 * MS),
                             ("%fusion.1", 95 * MS, 110 * MS)]}
    modules = {"/device:TPU:0": [
        ("jit_prefill_insert_paged_fn(3)", 10 * MS, 30 * MS),
        ("jit_decode_paged_fn(4)", 50 * MS, 60 * MS),
        ("jit_decode_paged_fn(4)", 95 * MS, 110 * MS)]}
    spans = [("bench.step", 5 * MS, 62 * MS), ("bench.wait", 62 * MS, 94 * MS)]
    return trace.Trace(ops, modules, spans, (0, 100 * MS))


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    t = _trace()
    assert trace.union(t.ops["/device:TPU:0"])[:2] == [(10 * MS, 30 * MS),
                                                       (50 * MS, 60 * MS)]
    # 20 + 10 + 5 (clipped at the window's end) ms
    assert abs(trace.busy_s(t) - 0.035) < 1e-12
    assert abs(trace.idle_share(t) - 0.65) < 1e-12


def test_module_time_matches_by_name():
    t = _trace()
    assert trace.module_time(t, "prefill") == (0.02, 1)
    s, n = trace.module_time(t, "decode")
    assert n == 2 and abs(s - 0.015) < 1e-12


def test_top_ops_and_idle_gaps_are_named():
    t = _trace()
    top = trace.top_ops(t, 2)
    assert top[0][0] == "%fusion.1" and abs(top[0][1] - 0.020) < 1e-12
    gaps = trace.idle_gaps(t, 3)
    assert gaps[0][0] == "bench.wait" and abs(gaps[0][1] - 0.035) < 1e-12
    assert [g[0] for g in gaps[1:]] == ["bench.step", "bench.step"]


def test_a_recorded_chip_trace_reduces():
    """A trace recorded on one TPU v5e: three runs of a small jitted
    program, each in a ``bench.step`` span followed by a 2 ms
    ``bench.wait``, all inside ``bench.window``."""
    from pathlib import Path

    t = trace.load(str(Path(__file__).parent / "data" / "tiny.xplane.pb"))
    assert list(t.ops) == ["/device:TPU:0"]
    assert [n.split("(")[0] for n, _, _ in t.modules["/device:TPU:0"]] \
        == ["jit__lambda"] * 3
    assert sorted({n for n, _, _ in t.spans}) == ["bench.step",
                                                  "bench.wait"]
    assert 0.005 < t.window_s < 0.05
    assert 0 < trace.busy_s(t) < t.window_s
    assert 0.99 < trace.idle_share(t) < 1.0
    assert trace.idle_gaps(t, 1)[0][0] == "bench.wait"
    assert trace.top_ops(t, 1)[0][0] == "%fusion"
