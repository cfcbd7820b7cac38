"""Serving launcher: OneRec-V2 generation with the optimized FP8 stack and
the open-system continuous-batching slot engine.

  PYTHONPATH=src python -m repro.launch.serve --reduced --requests 64 \
      [--no-fp8] [--kv-fp8] [--mode fixed|continuous] [--slots 16] [--ragged] \
      [--rate 8.0] [--max-queue 64] [--hold-k 4] [--hold-ms 25] \
      [--prefix-cache [--prefix-rows 32] [--second-sight]] \
      [--prefill-chunk 32] [--preemption] [--n-candidates 4] \
      [--paged [--page-size 32] [--pages 256]]

With ``--rate`` the launcher runs a REAL arrival-driven serve loop
(``run_open_loop``): requests are submitted at wall-clock Poisson arrival
times while the engine steps between them — the open-queueing regime the
hold-window admission policy targets.  Without it, the closed-batch
``serve_requests`` shim serves everything queued up front.
"""

from __future__ import annotations

import argparse
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.configs.base import OneRecConfig
from repro.core.ptq import build_quantized_params
from repro.models import onerec as onerec_model
from repro.serving import EngineConfig, ServingEngine, run_open_loop
from repro.serving.engine import resolve_quant_policy
from repro.serving.requests import build_requests  # noqa: F401  (re-export:
#                        the benches and examples used to import it here)

# the checkout this module runs from (src/repro/launch/serve.py)
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on for this process and
    return its directory: ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX
    reads the variable itself, so nothing is set here), else the fixed
    ``<checkout>/.jax_cache`` — fixed, because the path is part of every
    cache key.  Called by the entry points only, never on import."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def build_engine(cfg: OneRecConfig, engine_cfg: EngineConfig,
                 seed: int = 0) -> ServingEngine:
    """The served engine: random weights from ``seed``, initialised in
    the bf16 compute dtype and quantized under the engine's policy one
    leaf at a time, so the device never holds the high-precision tree
    next to the served one (at ``CONFIG`` widths the f32 tree alone
    exceeds a 16 GB chip)."""
    policy, _ = resolve_quant_policy(engine_cfg)
    params = build_quantized_params(
        lambda k: onerec_model.init_onerec(k, cfg, jnp.bfloat16),
        jax.random.PRNGKey(seed), policy)
    return ServingEngine(params, cfg, engine_cfg)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--no-fp8", dest="fp8", action="store_false",
                    default=True)
    ap.add_argument("--kv-fp8", action="store_true",
                    help="store K/V in fp8 (e4m3) with per-(position, head) "
                         "scales in BOTH cache tiers (slot pool + prefix "
                         "arena) — roughly halves KV bytes per row, so an "
                         "equal device-byte budget holds ~2x the slots and "
                         "stored prefixes; reads dequantize in-register")
    ap.add_argument("--mode", choices=("continuous", "fixed"),
                    default="continuous")
    ap.add_argument("--slots", type=int, default=0,
                    help="KV-slot pool size (0 => batch size)")
    ap.add_argument("--ragged", action="store_true",
                    help="mixed history lengths")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="open-loop Poisson arrival rate in req/s: submit "
                         "each request at its wall-clock arrival instead "
                         "of queueing the whole batch up front (0 = "
                         "closed-batch serve_requests)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="admission-queue bound; a full queue rejects "
                         "submissions with AdmissionFull (0 = unbounded). "
                         "Open-loop mode sheds the rejected requests")
    ap.add_argument("--hold-k", type=int, default=0,
                    help="admission hold window: defer the join round "
                         "until K arrived requests accumulated (continuous "
                         "mode; batches small prefill programs under open "
                         "overload)")
    ap.add_argument("--hold-ms", type=float, default=0.0,
                    help="max milliseconds the hold window may defer the "
                         "oldest arrived request (bounds the latency cost "
                         "of --hold-k; either knob alone also works)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="two-tier KV cache: content-addressed prefix "
                         "reuse across requests (continuous mode)")
    ap.add_argument("--prefix-rows", type=int, default=0,
                    help="prefix-store arena rows (0 => 2x slots)")
    ap.add_argument("--second-sight", action="store_true",
                    help="TinyLFU-style prefix-store admission: record a "
                         "prefix digest on first offer, store the K/V only "
                         "on the second — one-off traffic stops churning "
                         "the arena (requires --prefix-cache)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="max history tokens per prefill program (0 = "
                         "monolithic); chunked prefill pages long "
                         "histories through the decode loop, bounding "
                         "join-step latency spikes (continuous mode)")
    ap.add_argument("--preemption", action="store_true",
                    help="free the worst decoding slot for a strictly "
                         "higher-priority arrival (continuous mode; "
                         "resumes via the prefix store when enabled)")
    ap.add_argument("--n-candidates", type=int, default=1,
                    help="candidate items decoded per request: one fused "
                         "tree-decode program advances all K branches of "
                         "every slot against its shared prefix K/V "
                         "(continuous mode; completions carry the ranked "
                         "candidate set)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV layout: ONE refcounted device page pool "
                         "+ per-request page tables replaces the contiguous "
                         "slot rows and prefix arena — a prefix hit maps "
                         "the stored pages read-only into the new request "
                         "(zero-copy, at most one boundary COW page) and "
                         "branch/chunk spans allocate pages on demand "
                         "(continuous mode only)")
    ap.add_argument("--page-size", type=int, default=32,
                    help="positions per KV page under --paged (16-64 is "
                         "the useful range: smaller pages waste less on "
                         "ragged tails, larger ones shrink the table)")
    ap.add_argument("--pages", type=int, default=0,
                    help="page-pool size under --paged (0 = auto-size to "
                         "the contiguous layout's slot+arena footprint)")
    ap.add_argument("--fused-decode", choices=("off", "auto", "interpret"),
                    default="off",
                    help="route paged decode through the fused Pallas "
                         "kernel (page-table gather on device, fp8 dequant "
                         "in registers, tree mask + online softmax + top-k "
                         "select in ONE program per step). 'auto' uses the "
                         "compiled kernel on TPU and logs a one-line "
                         "fallback to the unfused path off-TPU or without "
                         "--paged; 'interpret' forces Pallas interpret "
                         "mode (CPU parity runs)")
    ap.add_argument("--quant-policy", default=None, metavar="PATH",
                    help="load a tuned mixed-precision policy artifact "
                         "(emitted by launch/autotune.py) instead of the "
                         "all-or-nothing --no-fp8 switch: per-group "
                         "fp8/bf16/int8 assignment plus calibrated static "
                         "activation scales deploy as data")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the params AND the synthetic workload "
                         "(the engine itself is deterministic); one seed "
                         "reproduces a run")
    args = ap.parse_args()

    mod = registry.get_arch("onerec-v2")
    cfg = mod.reduced_config() if args.reduced else mod.CONFIG
    batch = args.batch or cfg.serve_batch
    enable_compile_cache()
    engine = build_engine(cfg, EngineConfig(
        batch_size=batch, use_fp8=args.fp8, mode=args.mode,
        kv_dtype="float8_e4m3fn" if args.kv_fp8 else "bfloat16",
        n_slots=args.slots, max_queue=args.max_queue,
        hold_k=args.hold_k, hold_ms=args.hold_ms,
        prefix_cache=args.prefix_cache, prefix_rows=args.prefix_rows,
        store_on_first_sight=not args.second_sight,
        prefill_chunk=args.prefill_chunk, preemption=args.preemption,
        max_candidates=args.n_candidates,
        paged=args.paged, page_size=args.page_size, n_pages=args.pages,
        fused_decode=args.fused_decode, quant_policy=args.quant_policy),
        args.seed)
    requests = build_requests(cfg, args.requests, batch, args.seed,
                              args.ragged, n_candidates=args.n_candidates)

    if args.rate > 0:
        # arrival-driven open loop: wall-clock Poisson submission
        rng = np.random.default_rng(args.seed)
        offsets = np.cumsum(rng.exponential(1.0 / args.rate,
                                            size=len(requests)))
        timed = [dict(r, arrival_s=float(t))
                 for r, t in zip(requests, offsets)]
        outs, stats = run_open_loop(engine, timed,
                                    drop_on_full=bool(args.max_queue))
        served = [o for o in outs if o is not None]
        print(f"[serve] open loop @ {args.rate:.1f} req/s offered: served "
              f"{len(served)}/{len(requests)} "
              f"(rejected {int(stats['rejected'])}), "
              f"hold rounds {int(stats['hold_rounds'])}, "
              f"prefill programs {int(stats['prefill_calls'])}")
    else:
        outs, stats = engine.serve_requests(requests)

    if args.quant_policy:
        pol = engine.executor.quant_policy
        print(f"[serve] quant policy: {args.quant_policy} "
              f"({len(pol.overrides)} overrides, "
              f"static_acts={pol.static_acts})")
    print(f"[serve] mode={args.mode} fp8={args.fp8} "
          f"kv={stats['kv_dtype']} "
          f"({int(stats['kv_row_bytes'])} B/row, "
          f"{int(stats['kv_bytes'])} B total) "
          f"requests={len(requests)} slots={int(stats['n_slots'])} "
          f"occupancy={stats['slot_occupancy']:.2f}")
    if args.paged:
        print(f"[serve] paged KV: {int(stats['pages_total'])} pages x "
              f"{int(stats['page_size'])} positions "
              f"({int(stats['pages_free'])} free, "
              f"{int(stats['kv_bytes_pinned'])} B pinned after drain) | "
              f"prefix hits: {int(stats['prefix_row_copies'])} full-row "
              f"copies, {int(stats['cow_copies'])} COW page copies")
    if args.fused_decode != "off":
        print(f"[serve] fused decode: mode={stats['fused_decode_mode']} | "
              f"{int(stats['fused_decode_steps'])}/"
              f"{int(stats['decode_steps'])} decode steps fused | "
              f"{int(stats['fused_select_hits'])} select dispatches "
              f"folded into the decode program")
    if args.prefix_cache:
        print(f"[serve] prefix cache: hit-rate "
              f"{stats['prefix_hit_rate']:.2f} "
              f"({int(stats['prefix_hits'])}/"
              f"{int(stats['prefix_admissions'])}), "
              f"saved {int(stats['prefix_tokens_saved'])} prefill tokens, "
              f"{int(stats['prefix_entries'])} entries / "
              f"{int(stats['prefix_store_bytes'])} B stored, "
              f"peak pinned {int(stats['prefix_bytes_pinned'])} B, "
              f"{int(stats['prefix_evictions'])} evictions"
              + (f", {int(stats['prefix_first_sights'])} first-sight "
                 f"record-only offers" if args.second_sight else ""))
    print(f"[serve] per-request latency: "
          f"mean={stats['mean_latency_s']*1e3:.1f}ms "
          f"p50={stats['p50_latency_s']*1e3:.1f}ms "
          f"p99={stats['p99_latency_s']*1e3:.1f}ms | "
          f"throughput={stats['throughput_rps']:.1f} req/s")
    print(f"[serve] join steps: {int(stats['join_steps'])} "
          f"(p50={stats['join_p50_s']*1e3:.1f}ms "
          f"p99={stats['join_p99_s']*1e3:.1f}ms, "
          f"decode-stall {100*stats['decode_stall_frac']:.0f}% of wall) | "
          f"preemptions={int(stats['preemptions'])}")
    print(f"[serve] request p50 split: "
          f"queue wait={stats['queue_wait_p50_s']*1e3:.1f}ms "
          f"prefill phase={stats['prefill_phase_p50_s']*1e3:.1f}ms "
          f"decode phase={stats['decode_phase_p50_s']*1e3:.1f}ms | "
          f"host time per step={stats['host_s_per_step']*1e3:.2f}ms")
    if args.n_candidates > 1:
        print(f"[serve] multi-candidate: K={args.n_candidates} | "
              f"tree-decode programs "
              f"{int(stats['decode_multi_steps'])}/"
              f"{int(stats['decode_steps'])} decode dispatches | "
              f"{stats['branches_per_decode_step']:.1f} branches/dispatch")


if __name__ == "__main__":
    main()
