"""Host spans and lifecycle stamps of the serving round: a request's
latency splits exactly into queue wait, prefill phase and decode phase;
the window's medians and host time per round agree with the stamps; and a
profiler trace holds the ``serve.*`` spans nested as the round runs them.

Each case serves a handful of requests on the tiny configuration in one
scheduler mode (the paged case adds chunked prefill and the prefix
store, so ``serve.advance`` / ``serve.resume`` / ``serve.store`` run).
"""

import glob
import time

import jax
import numpy as np
import pytest

from repro.configs.base import OneRecConfig, TransformerConfig
from repro.models import onerec as onerec_model
from repro.serving import EngineConfig, ServingEngine
from repro.serving.requests import make_request

MODES = {
    "continuous": dict(batch_size=4),
    "fixed": dict(batch_size=4, mode="fixed"),
    "paged_chunked": dict(batch_size=4, paged=True, prefill_chunk=6,
                          prefix_cache=True),
}


def _cfg() -> OneRecConfig:
    return OneRecConfig(
        name="onerec-spans-test",
        history_len=8,
        transformer=TransformerConfig(
            name="onerec-spans-test-backbone",
            n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
            d_ff=128, vocab_size=256, moe=True, n_experts=4, top_k=2,
            d_expert=64, capacity_factor=64.0, ep_degree=4,
            max_seq_len=64, remat=False),
        serve_batch=4, beam_width=4)


def _requests(cfg, seed, n=6):
    """``n`` fresh requests (new histories miss the prefix store)."""
    rng = np.random.default_rng(seed)
    return [make_request(
        rng.integers(0, 192, size=int(rng.integers(2, 9)) * cfg.n_codebooks),
        rng.normal(size=onerec_model.PROFILE_DIM)) for _ in range(n)]


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    params = onerec_model.init_onerec(jax.random.PRNGKey(0), cfg)
    return cfg, params, _requests(cfg, 11)


@pytest.fixture(scope="module")
def engines(setup):
    """One warmed engine per mode (the first serve compiles)."""
    cfg, params, reqs = setup
    out = {}
    for mode, kw in MODES.items():
        eng = ServingEngine(params, cfg, EngineConfig(**kw))
        eng.serve_requests(reqs)
        out[mode] = eng
    return out


def _serve(eng, reqs):
    """Submit every request, drain, and return (completions, stats)."""
    eng.reset_window()
    handles = [eng.submit(r) for r in reqs]
    eng.drain()
    return [h.completion for h in handles], eng.stats()


@pytest.mark.parametrize("mode", list(MODES))
def test_latency_is_queue_wait_plus_prefill_plus_decode(engines, setup,
                                                        mode):
    cfg, _, _ = setup
    reqs = _requests(cfg, 12)
    done, _ = _serve(engines[mode], reqs)
    assert len(done) == len(reqs)
    for c in done:
        assert len(c.token_s) == cfg.decode_len
        assert c.token_s == sorted(c.token_s)
        assert c.queue_wait_s >= 0 and c.prefill_phase_s > 0
        assert c.decode_phase_s >= 0
        assert abs(c.queue_wait_s + c.prefill_phase_s + c.decode_phase_s
                   - c.latency_s) <= 1e-6


@pytest.mark.parametrize("mode", list(MODES))
def test_window_medians_match_the_completion_stamps(engines, setup, mode):
    cfg, _, _ = setup
    done, stats = _serve(engines[mode], _requests(cfg, 13))
    for key, phase in (("queue_wait_p50_s", "queue_wait_s"),
                       ("prefill_phase_p50_s", "prefill_phase_s"),
                       ("decode_phase_p50_s", "decode_phase_s")):
        want = float(np.median([getattr(c, phase) for c in done]))
        assert stats[key] == pytest.approx(want, abs=1e-12), key
    lat = [c.latency_s for c in done]
    assert stats["p50_latency_s"] == pytest.approx(np.median(lat))


@pytest.mark.parametrize("mode", list(MODES))
def test_host_time_per_step_lies_within_each_round(engines, setup, mode):
    cfg, _, _ = setup
    eng = engines[mode]
    eng.reset_window()
    for r in _requests(cfg, 14):
        eng.submit(r)
    eng._sched.draining = True       # fixed mode: let the tail batch form
    walls, samples = [], []
    while eng.busy:
        n = len(eng._host_s)
        t0 = time.perf_counter()
        eng.step()
        wall = time.perf_counter() - t0
        if len(eng._host_s) > n:
            walls.append(wall)
            samples.append(eng._host_s[-1])
    eng._sched.draining = False
    assert samples, "no round dispatched a device program"
    assert all(0 <= h <= w for h, w in zip(samples, walls))
    stats = eng.stats()
    assert stats["host_s_per_step"] == pytest.approx(np.mean(samples))
    assert 0 <= stats["host_s_per_step"] <= max(walls)


def _host_spans(trace_dir):
    """(name, start, end, metadata) of the trace's serving spans, and the
    names of every host event."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb",
                            recursive=True))[-1]
    spans, names = [], set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    names.add(e.name)
                    if e.name.startswith("serve."):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns,
                                      {k: v for k, v in e.stats}))
    return spans, names


def _inside(spans, outer, inner):
    """True when some ``inner`` span lies within some ``outer`` span."""
    return any(a0 <= b0 and b1 <= a1
               for n, a0, a1, _ in spans if n == outer
               for m, b0, b1, _ in spans if m == inner)


@pytest.mark.parametrize("mode", list(MODES))
def test_profiler_trace_holds_the_round_spans_nested(engines, setup, mode,
                                                     tmp_path):
    cfg, _, _ = setup
    eng = engines[mode]
    eng.reset_window()
    for r in _requests(cfg, 15, n=4):
        eng.submit(r)
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(4):           # joins, decodes and a retirement
            eng.step()
    eng.drain()
    spans, names = _host_spans(tmp_path)
    assert not any(n.startswith("bench.") for n in names)
    for outer, inner in (("serve.step", "serve.join"),
                         ("serve.step", "serve.decode_round"),
                         ("serve.join", "serve.prefill"),
                         ("serve.prefill", "serve.stage"),
                         ("serve.prefill", "serve.device_wait"),
                         ("serve.decode_round", "serve.decode"),
                         ("serve.decode", "serve.stage"),
                         ("serve.decode", "serve.device_wait"),
                         ("serve.decode_round", "serve.select"),
                         ("serve.step", "serve.retire"),
                         ("serve.step", "serve.free")):
        assert _inside(spans, outer, inner), (outer, inner)
    if mode == "paged_chunked":      # histories past the chunk resume
        assert _inside(spans, "serve.step", "serve.advance")
        assert _inside(spans, "serve.advance", "serve.resume")
        assert any(n == "serve.store" for n, _, _, _ in spans)
    # a program span names the requests it serves
    rid_sets = [set(str(st["rids"]).split()) for n, _, _, st in spans
                if n == "serve.prefill"]
    assert rid_sets and all(rid_sets)
