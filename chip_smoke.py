#!/usr/bin/env python3
"""Serve full-width onerec-v2 once on one TPU through the serving engine.

    python3 chip_smoke.py

One process, no children: it owns the chip for its whole run.  It exits
non-zero, and prints no result, when JAX finds no TPU.  Three phases run
at ``onerec_v2.CONFIG`` widths (12 layers, d_model 2048, 12-expert top-2
MoE, 128-item histories) with 32 KV slots and random weights from a seed:

  A. the served configuration — paper fp8 weight policy, fp8 KV in the
     paged pool, fused paged-decode kernel.  The requests are served
     twice through ``submit``/``step``/drain: the first pass compiles,
     the second must compile nothing.
  B. the same weights and requests through the unfused paged decode path,
     the reference for the kernel: items are compared with A, and
     teacher-forced decode logits must keep a mean top-8 overlap of at
     least ``TOP8_BOUND`` with A's.
  C. the bf16 baseline (no weight quantization, bf16 KV) after A's
     weights are released: the comparator every benchmark cell needs.

Each phase prints its wall seconds (first pass = compiles + one run,
then the steady pass), its compile counts, and the device's peak bytes
in use.  The last line of standard output is one JSON object naming the
device; it is printed only when every phase passed.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import sys
import time
import traceback
from pathlib import Path

import jax
import numpy as np

SRC = Path(__file__).resolve().parent / "src"

N_SLOTS = 32          # KV slots = the configuration's serve batch
N_REQUESTS = 48       # one full pool of joins, then a ragged second round
SEED = 0
TOP8_BOUND = 0.9      # the fp8 bound of tests/test_decode_kernel.py


class SmokeFailure(AssertionError):
    """A phase's output broke the smoke contract."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def smoke_requests(cfg, n: int, seed: int):
    """``n`` requests from the semantic-ID stream, each history cut to a
    random length between half and all of ``cfg.history_len`` items."""
    from repro.serving.requests import build_requests

    rng = np.random.default_rng(seed)
    reqs = build_requests(cfg, n, N_SLOTS, seed, ragged=False)
    lo = max(cfg.history_len // 2, 1)
    for r in reqs:
        n_items = int(rng.integers(lo, cfg.history_len + 1))
        r["tokens"] = r["tokens"][:n_items * cfg.n_codebooks]
    return reqs


def _memory(dev) -> str:
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" not in stats:
        return "peak bytes not reported by this backend"
    return (f"peak_bytes_in_use {stats['peak_bytes_in_use']} | "
            f"bytes_in_use {stats.get('bytes_in_use')} | "
            f"bytes_limit {stats.get('bytes_limit')}")


def _check_items(tag, cfg, requests, outs) -> None:
    _check(len(outs) == len(requests),
           f"{tag}: {len(outs)}/{len(requests)} requests completed")
    for i, item in enumerate(outs):
        item = np.asarray(item)
        _check(item.shape == (cfg.decode_len,),
               f"{tag}: request {i} item shape {item.shape}")
        _check(bool(np.all((item >= 0) & (item < cfg.vocab_size))),
               f"{tag}: request {i} item {item.tolist()} leaves the vocab")


def _check_fused(tag, st, expect_mode) -> None:
    _check(st["fused_decode_mode"] == expect_mode,
           f"{tag}: fused decode resolved to {st['fused_decode_mode']!r}, "
           f"expected {expect_mode!r}")
    _check(st["decode_steps"] > 0, f"{tag}: no decode step ran")
    _check(st["fused_decode_steps"] == st["decode_steps"],
           f"{tag}: {int(st['fused_decode_steps'])}/"
           f"{int(st['decode_steps'])} decode steps fused")


def _serve(tag, make_engine, requests, *, steady: bool, log):
    """Build an engine and serve ``requests`` (twice when ``steady``);
    returns (engine, outputs of the last pass, its stats)."""
    from repro.analysis.guards import CompileMonitor

    t0 = time.perf_counter()
    with CompileMonitor() as build_mon:
        engine = make_engine()
    t_build = time.perf_counter() - t0
    t0 = time.perf_counter()
    with CompileMonitor() as first_mon:
        outs, st = engine.serve_requests(requests)
    t_first = time.perf_counter() - t0
    line = (f"[{tag}] build {t_build:.3f} s ({build_mon.compiles} compiles)"
            f" | first pass {t_first:.3f} s ({first_mon.compiles} compiles)")
    if steady:
        t0 = time.perf_counter()
        with CompileMonitor() as steady_mon:
            outs_2, st = engine.serve_requests(requests)
        t_steady = time.perf_counter() - t0
        same = _agreement(outs, outs_2)
        line += (f" | steady pass {t_steady:.3f} s ({steady_mon.compiles} "
                 f"post-warmup compiles) | compile ~ first - steady = "
                 f"{t_first - t_steady:.3f} s | repeat items identical "
                 f"{same}/{len(outs)}")
        outs = outs_2
    log(line)
    log(f"[{tag}] {int(st['n_requests'])} completions, "
        f"{int(st['prefill_calls'])} prefill programs, "
        f"{int(st['decode_steps'])} decode steps "
        f"({int(st['fused_decode_steps'])} fused, mode "
        f"{st['fused_decode_mode']}), p50 {st['p50_latency_s']:.4f} s, "
        f"p99 {st['p99_latency_s']:.4f} s, "
        f"{st['throughput_rps']:.2f} req/s")
    if steady:
        _check(steady_mon.compiles == 0,
               f"{tag}: {steady_mon.compiles} compiles after warmup")
    return engine, outs, st


def teacher_forced(ex, cfg, requests, forced=None):
    """Prefill ``requests`` into slots 0..n-1 of a drained executor and
    decode ``decode_len - 1`` steps, feeding ``forced`` tokens when given
    (else the greedy ones).  Returns (per-step logits, token record)."""
    n = len(requests)
    for s, r in enumerate(requests):
        _check(ex.grant_slot(s, len(r["tokens"]) + 1 + cfg.decode_len),
               "teacher-forced run: page grant failed")
    pre = np.asarray(ex.prefill_insert([r["tokens"] for r in requests],
                                       [r["profile"] for r in requests],
                                       list(range(n)))[:n], np.float32)
    lengths = np.zeros(ex.n_slots, np.int32)
    lengths[:n] = [len(r["tokens"]) + 1 for r in requests]
    record = list(forced) if forced else [np.argmax(pre, -1)]
    steps = []
    for t in range(cfg.decode_len - 1):
        toks = np.zeros((ex.n_slots, 1), np.int32)
        toks[:n, 0] = record[t]
        logits = np.asarray(ex.decode(toks, lengths), np.float32)[:n]
        steps.append(logits)
        if not forced:
            record.append(np.argmax(logits, -1))
        lengths[:n] += 1
    ex.free_slots(list(range(n)))
    return steps, record


def _fitting_head(ex, cfg, requests):
    """The longest prefix of ``requests`` whose pages the drained pool
    holds at once, at most one request per slot."""
    pages = 0
    for n, r in enumerate(requests[:ex.n_slots]):
        pages += ex.page_pool.pages_for(len(r["tokens"]) + 1 + cfg.decode_len)
        if pages > ex.page_pool.n_free:
            return requests[:n]
    return requests[:ex.n_slots]


def top8_overlap(a, b) -> float:
    ta = np.argsort(-a, -1)[..., :8].reshape(-1, 8)
    tb = np.argsort(-b, -1)[..., :8].reshape(-1, 8)
    return float(np.mean([len(set(x) & set(y)) / 8.0
                          for x, y in zip(ta, tb)]))


def _agreement(outs_a, outs_b) -> int:
    return sum(bool((a == b).all()) for a, b in zip(outs_a, outs_b))


def run_phases(cfg, requests, *, n_slots: int, fused: str, expect_mode: str,
               seed: int = SEED, log=print) -> list:
    """Phases A, B and C (module docstring); returns the failures, one
    line each (empty when all passed)."""
    from repro.launch.serve import build_engine
    from repro.serving import EngineConfig, ServingEngine

    dev = jax.devices()[0]
    failures = []
    served = EngineConfig(batch_size=n_slots, n_slots=n_slots, use_fp8=True,
                          kv_dtype="float8_e4m3fn", paged=True,
                          fused_decode=fused)
    log(f"[setup] {cfg.name}: {cfg.transformer.n_layers} layers, d_model "
        f"{cfg.transformer.d_model}, {cfg.transformer.n_experts} experts, "
        f"history {cfg.history_len} items | {len(requests)} requests, "
        f"{n_slots} slots, history tokens "
        f"{min(len(r['tokens']) for r in requests)}.."
        f"{max(len(r['tokens']) for r in requests)}")

    engine_a = engine_b = outs_a = None
    try:
        engine_a, outs_a, st = _serve(
            "A", lambda: build_engine(cfg, served, seed), requests,
            steady=True, log=log)
        _check_fused("A", st, expect_mode)
        _check_items("A", cfg, requests, outs_a)
    except Exception as e:  # report, release, go on to the baseline
        traceback.print_exc()
        failures.append(f"phase A: {e}")
    log(f"[A] {_memory(dev)}")

    if engine_a is not None and not failures:
        try:
            engine_b, outs_b, st = _serve(
                "B", lambda: ServingEngine(
                    engine_a.executor.params, cfg,
                    dataclasses.replace(served, fused_decode="off")),
                requests, steady=False, log=log)
            _check(st["fused_decode_steps"] == 0,
                   "B: the unfused reference ran fused steps")
            _check_items("B", cfg, requests, outs_b)
            head = _fitting_head(engine_a.executor, cfg, requests)
            steps_a, record = teacher_forced(engine_a.executor, cfg, head)
            steps_b, _ = teacher_forced(engine_b.executor, cfg, head,
                                        forced=record)
            overlap = min(top8_overlap(a, b)
                          for a, b in zip(steps_a, steps_b))
            log(f"[B] items identical to A: {_agreement(outs_a, outs_b)}/"
                f"{len(requests)} | teacher-forced top-8 overlap over "
                f"{len(head)} requests, fused vs unfused, worst of "
                f"{len(steps_a)} decode steps: {overlap:.4f} "
                f"(bound {TOP8_BOUND})")
            _check(overlap >= TOP8_BOUND,
                   f"B: top-8 overlap {overlap:.4f} below {TOP8_BOUND}")
        except Exception as e:
            traceback.print_exc()
            failures.append(f"phase B: {e}")
        log(f"[B] {_memory(dev)}")

    # release phase A's weights and pool before the baseline is built
    engine_a = engine_b = None
    gc.collect()
    log(f"[C] after releasing A: {_memory(dev)}")
    try:
        baseline = dataclasses.replace(served, use_fp8=False,
                                       kv_dtype="bfloat16")
        engine_c, outs_c, st = _serve(
            "C", lambda: build_engine(cfg, baseline, seed), requests,
            steady=True, log=log)
        _check_fused("C", st, expect_mode)
        _check_items("C", cfg, requests, outs_c)
        if outs_a is not None:
            log(f"[C] bf16 items identical to fp8 (A): "
                f"{_agreement(outs_a, outs_c)}/{len(requests)}")
        engine_c = None
    except Exception as e:
        traceback.print_exc()
        failures.append(f"phase C: {e}")
    log(f"[C] {_memory(dev)}")
    return failures


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (device 0 is {dev.platform!r});"
              f" this script measures the chip only", file=sys.stderr)
        return 1
    sys.path.insert(0, str(SRC))
    from repro.configs import registry
    from repro.launch.serve import enable_compile_cache

    t0 = time.perf_counter()
    print(f"[setup] device {dev.platform} {dev.device_kind} x"
          f"{len(jax.devices())} | jax {jax.__version__} | compile cache "
          f"{enable_compile_cache()}", flush=True)
    cfg = registry.get_arch("onerec-v2").CONFIG
    failures = run_phases(cfg, smoke_requests(cfg, N_REQUESTS, SEED),
                          n_slots=N_SLOTS, fused="auto", expect_mode="tpu",
                          log=lambda s: print(s, flush=True))
    print(f"[done] {time.perf_counter() - t0:.3f} s", flush=True)
    if failures:
        for f in failures:
            print(f"FAILED {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
