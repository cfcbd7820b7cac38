"""OneRec serving engine: the open-system request-lifecycle API over the
serving subsystem (the system whose latency/throughput the paper measures,
§5.2).

The engine is an OPEN system — callers drive a request lifecycle instead
of handing over a closed batch:

  * ``submit(request) -> RequestHandle`` — non-blocking admission into a
    bounded queue; a full queue raises ``AdmissionFull`` (the explicit
    backpressure signal — callers shed or retry, the engine never blocks
    or silently drops);
  * ``step()`` — advance ONE scheduler round (resume chunked prefills ->
    retire -> join -> decode) and deliver any completions to their
    handles;
  * ``handle.poll()`` / ``handle.result()`` / ``handle.cancel()`` — the
    per-request side: non-blocking completion check, step-until-done, and
    mid-flight cancellation (frees the slot and releases prefix-store
    pins);
  * ``drain()`` — step (and idle-sleep) until every accepted request
    retired; sets the scheduler's ``draining`` flag so admission hold
    windows and fixed-mode tail batches release;
  * ``stats()`` / ``reset_window()`` — windowed metrics over whatever the
    caller defines as one measurement.

Each ``step()`` is a ``serve.step`` span (``serving/spans.py``): with a
``jax.profiler`` trace running, the round's host spans land in the trace
beside the device ops; without one they only feed the window counters.

``serve_requests`` / ``generate_batch`` — the seed engine's closed-batch
API — are thin shims implemented PURELY in terms of submit + step + drain
(token-identical to the closed-loop scheduler they replaced), and
``run_open_loop`` drives true open-loop submission: each request enters at
its wall-clock arrival, the regime the hold-window A/B measures.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.configs.base import OneRecConfig
from repro.core.policy import (BASELINE_POLICY, PAPER_POLICY, QuantPolicy,
                               load_policy_artifact)
from repro.core.quant import gemm_form_counts
from repro.serving.executor import PhaseExecutor
from repro.serving.kv_cache import PrefixStore, SlotPool
from repro.serving.requests import requests_from_arrays
from repro.serving.scheduler import (Completion, ContinuousScheduler,
                                     FixedBatchScheduler, Request,
                                     SchedulingPolicy)
from repro.serving.spans import span


class AdmissionFull(RuntimeError):
    """``submit`` backpressure: the bounded admission queue is at capacity.
    The caller decides — shed the request, retry after stepping, or route
    to another replica; the engine never blocks a submitter."""


class RequestCancelled(RuntimeError):
    """``result()`` on a handle whose request was cancelled."""


@dataclasses.dataclass
class EngineConfig:
    batch_size: int = 32           # fixed-mode batch; default pool size
    use_fp8: bool = True
    kv_dtype: str = "bfloat16"     # K/V storage dtype for BOTH cache tiers:
    #                                "bfloat16" (default, byte-for-byte the
    #                                legacy layout) | "float8_e4m3fn" (fp8
    #                                payload + per-(position, head) f32
    #                                scales; ~half the KV bytes per row)
    topk: int = 8
    use_radix_topk: bool = False   # Pallas kernel (TPU); lax.top_k otherwise
    greedy: bool = True
    mode: str = "continuous"       # "continuous" | "fixed"
    n_slots: int = 0               # KV-slot pool size; 0 => batch_size
    prefill_bucket_min: int = 16   # smallest ragged-prefill length bucket
    max_prefill_groups: int = 2    # bucket programs per continuous join round
    # -- multi-candidate tree decode (continuous mode only) --
    max_candidates: int = 1        # branch capacity: every slot row reserves
    #                                (max_candidates - 1) * (decode_len - 1)
    #                                extra cache positions; requests carry
    #                                "n_candidates" <= this (and <= topk)
    # -- open-system admission --
    max_queue: int = 0             # admission-queue bound; 0 = unbounded
    #                                (submit raises AdmissionFull when full)
    # -- tier-2 prefix cache (continuous mode only) --
    prefix_cache: bool = False     # content-addressed cross-request KV reuse
    prefix_rows: int = 0           # arena rows (cached prefixes); 0 => 2x slots
    prefix_bytes_budget: int = 0   # LRU byte budget; 0 => all rows usable
    store_on_first_sight: bool = True   # False = TinyLFU-style second-sight
    #                                admission (store a prefix only when its
    #                                content has been offered twice)
    # -- scheduling policy (continuous mode only) --
    prefill_chunk: int = 0         # max history tokens per prefill program
    #                                (0 = monolithic; bounds join-step spikes)
    preemption: bool = False       # free worst decoding slot for a strictly
    #                                higher-priority arrival (resume via the
    #                                prefix store when enabled)
    hold_k: int = 0                # admission hold window: join only when K
    hold_ms: float = 0.0           # requests or T ms accumulated (0 = off)
    # -- paged KV layout (continuous mode only) --
    paged: bool = False            # ONE refcounted page pool + per-slot page
    #                                tables replaces the contiguous slot pool
    #                                AND the prefix arena: prefix hits become
    #                                page-table edits (zero-copy), branch
    #                                spans allocate on demand (K=1 traffic
    #                                reserves nothing)
    page_size: int = 32            # logical positions per page (16-64 keeps
    #                                boundary-COW waste low without
    #                                fragmenting the gather)
    n_pages: int = 0               # device pool size; 0 => auto-size to the
    #                                contiguous layout's device bytes
    #                                ((n_slots + prefix_rows) worst-case rows)
    fused_decode: object = False   # paged decode through the fused Pallas
    #                                kernel + in-program select: False/"off" |
    #                                True/"auto" (kernel on TPU, logged
    #                                fallback to the unfused path off-TPU or
    #                                when the layout is contiguous) |
    #                                "interpret" (force Pallas interpret
    #                                mode — CPU parity tests)
    quant_policy: object = None    # tuned mixed-precision policy — a
    #                                QuantPolicy instance OR a str path to an
    #                                autotune artifact JSON (loaded with its
    #                                calibrated static act scales); overrides
    #                                the all-or-nothing use_fp8 switch


def resolve_quant_policy(engine_cfg: EngineConfig
                         ) -> Tuple[QuantPolicy, Optional[Dict[str, float]]]:
    """The weight policy an engine serves, and any calibrated static
    activation scales: a str ``quant_policy`` is an autotune artifact path
    (policy + scales travel together), a ``QuantPolicy`` applies as-is,
    and None falls back to the all-or-nothing ``use_fp8`` switch."""
    quant_policy, act_scales = engine_cfg.quant_policy, None
    if isinstance(quant_policy, str):
        artifact = load_policy_artifact(quant_policy)
        quant_policy = artifact["policy"]
        act_scales = artifact.get("act_scales") or None
    elif quant_policy is None:
        quant_policy = PAPER_POLICY if engine_cfg.use_fp8 else BASELINE_POLICY
    elif not isinstance(quant_policy, QuantPolicy):
        raise ValueError(
            f"quant_policy must be a QuantPolicy or an artifact path, "
            f"got {type(quant_policy).__name__}")
    return quant_policy, act_scales


class RequestHandle:
    """The caller's side of one submitted request.

    ``poll()`` is the non-blocking check (``Completion`` or None);
    ``result()`` steps the engine until THIS request retires and returns
    its generated item; ``cancel()`` withdraws the request wherever it is
    in the lifecycle.  Handles stay valid after completion — the
    ``Completion`` (item, latency, deadline accounting) is kept on the
    handle, not in the engine.
    """

    def __init__(self, engine: "ServingEngine", request: Request):
        self._engine = engine
        self._request = request
        self.completion: Optional[Completion] = None
        self.cancelled = False

    @property
    def rid(self) -> int:
        return self._request.rid

    @property
    def status(self) -> str:
        """``queued`` | ``running`` | ``done`` | ``cancelled``."""
        if self.cancelled:
            return "cancelled"
        if self.completion is not None:
            return "done"
        if any(q is self._request for q in self._engine._sched.queue):
            return "queued"
        return "running"

    def done(self) -> bool:
        return self.completion is not None

    def poll(self) -> Optional[Completion]:
        """Non-blocking: the ``Completion`` once retired, else None."""
        return self.completion

    def result(self) -> np.ndarray:
        """The generated item, stepping the engine until this request
        retires.  Blocking a single-threaded driver here means no more
        submissions can race in, so the engine drains toward this handle
        (hold windows and fixed-mode tails release)."""
        self._engine._drain_until(
            lambda: self.completion is not None or self.cancelled)
        if self.cancelled:
            raise RequestCancelled(f"request {self.rid} was cancelled")
        if self.completion is None:
            raise RuntimeError(f"request {self.rid} never completed "
                               f"(engine drained without retiring it)")
        return self.completion.item

    def cancel(self) -> bool:
        """Withdraw the request; True when it was still queued or in
        flight (its slot and prefix pins are released), False once it
        already completed (or was already cancelled)."""
        return self._engine.cancel(self)


class ServingEngine:
    def __init__(self, params, cfg: OneRecConfig, engine_cfg: EngineConfig):
        if engine_cfg.mode not in ("continuous", "fixed"):
            raise ValueError(f"unknown scheduler mode {engine_cfg.mode!r}")
        self.cfg = cfg
        self.ecfg = engine_cfg
        self.n_slots = engine_cfg.n_slots or engine_cfg.batch_size
        prefix_rows = 0
        if engine_cfg.prefix_cache:
            if engine_cfg.mode != "continuous":
                raise ValueError("prefix_cache requires continuous mode")
            prefix_rows = engine_cfg.prefix_rows or 2 * self.n_slots
        if not engine_cfg.store_on_first_sight and not engine_cfg.prefix_cache:
            raise ValueError("second-sight admission requires prefix_cache")
        if engine_cfg.mode != "continuous" and (
                engine_cfg.prefill_chunk or engine_cfg.preemption
                or engine_cfg.hold_k or engine_cfg.hold_ms):
            raise ValueError("prefill_chunk / preemption / hold windows "
                             "require continuous mode")
        if engine_cfg.max_candidates < 1:
            raise ValueError(f"max_candidates must be >= 1, got "
                             f"{engine_cfg.max_candidates}")
        if engine_cfg.max_candidates > 1 and engine_cfg.mode != "continuous":
            raise ValueError("multi-candidate decode requires continuous "
                             "mode (fixed mode is the seed-compat "
                             "single-item reference)")
        if engine_cfg.max_candidates > engine_cfg.topk:
            raise ValueError(
                f"max_candidates ({engine_cfg.max_candidates}) exceeds "
                f"topk ({engine_cfg.topk}): branch seeds are drawn from "
                f"the top-k select program")
        if engine_cfg.max_queue and engine_cfg.hold_k > engine_cfg.max_queue:
            raise ValueError(
                f"hold_k ({engine_cfg.hold_k}) must not exceed max_queue "
                f"({engine_cfg.max_queue}): a full admission queue could "
                f"never accumulate the hold count, livelocking submitters")
        if engine_cfg.mode == "fixed" and engine_cfg.max_queue \
                and engine_cfg.max_queue < engine_cfg.batch_size:
            raise ValueError(
                f"max_queue ({engine_cfg.max_queue}) must cover batch_size "
                f"({engine_cfg.batch_size}) in fixed mode: a full admission "
                f"queue could never form a batch, livelocking submitters")
        if engine_cfg.kv_dtype not in ("bfloat16", "float8_e4m3fn"):
            raise ValueError(
                f"kv_dtype must be 'bfloat16' or 'float8_e4m3fn', got "
                f"{engine_cfg.kv_dtype!r}")
        n_pages = 0
        if engine_cfg.paged:
            if engine_cfg.mode != "continuous":
                raise ValueError("the paged KV layout requires continuous "
                                 "mode (fixed mode is the seed-compat "
                                 "contiguous reference)")
            if engine_cfg.page_size <= 0:
                raise ValueError(f"page_size must be positive, got "
                                 f"{engine_cfg.page_size}")
            # 0 auto-sizes the pool to the CONTIGUOUS layout's device
            # bytes — (n_slots + prefix_rows) worst-case rows — so paged
            # vs contiguous A/Bs compare layouts, not budgets
            s_row = (cfg.context_len + 1
                     + (engine_cfg.max_candidates - 1)
                     * max(cfg.decode_len - 1, 0))
            n_pages = engine_cfg.n_pages or \
                -(-(self.n_slots + prefix_rows) * s_row
                  // engine_cfg.page_size)
        quant_policy, act_scales = resolve_quant_policy(engine_cfg)
        # block-scaled GEMM forms are tallied as programs lower; this
        # engine's programs lower after this point
        self._gemm_forms0 = gemm_form_counts()
        self.executor = PhaseExecutor(
            params, cfg, n_slots=self.n_slots, use_fp8=engine_cfg.use_fp8,
            topk=engine_cfg.topk, use_radix_topk=engine_cfg.use_radix_topk,
            prefill_bucket_min=engine_cfg.prefill_bucket_min,
            prefix_rows=prefix_rows,
            n_candidates=engine_cfg.max_candidates,
            kv_dtype=engine_cfg.kv_dtype,
            paged=engine_cfg.paged, page_size=engine_cfg.page_size,
            n_pages=n_pages, fused_decode=engine_cfg.fused_decode,
            quant_policy=quant_policy, act_scales=act_scales)
        # the store PERSISTS across stats windows (repeat traffic spans
        # them); its hit/miss window resets with the engine's
        if not prefix_rows:
            self.prefix_store = None
        elif engine_cfg.paged:
            # paged tier 2: entries are page refcounts, priced per page;
            # the byte budget defaults to the whole pool (live-slot
            # pressure is handled by the scheduler's evict_for_pages
            # reclaim, not a static split), and eviction releases pages
            # through the executor so freed pages read virgin
            self.prefix_store = PrefixStore(
                prefix_rows, self.executor.page_bytes,
                max_bytes=engine_cfg.prefix_bytes_budget
                or (n_pages + 1) * self.executor.page_bytes,
                n_codebooks=cfg.n_codebooks,
                store_on_first_sight=engine_cfg.store_on_first_sight,
                release_pages=self.executor.release_pages)
        else:
            self.prefix_store = PrefixStore(
                prefix_rows, self.executor.arena_row_bytes,
                max_bytes=engine_cfg.prefix_bytes_budget,
                n_codebooks=cfg.n_codebooks,
                store_on_first_sight=engine_cfg.store_on_first_sight)
        # lifecycle state: ONE pool + ONE scheduler for the engine's whole
        # life — queues, chunked-prefill segments, and preemption state
        # persist across submit/step calls (the open-system redesign)
        self.pool = SlotPool(self.n_slots)
        self._sched = self._make_scheduler(self.pool)
        self._rids = itertools.count()
        self._handles: Dict[int, RequestHandle] = {}
        # per-request latencies of the last serve_requests call
        self.metrics: Dict[str, List[float]] = {"latency_s": []}
        self.reset_window()

    def _make_scheduler(self, pool: SlotPool):
        if self.ecfg.mode == "fixed":
            return FixedBatchScheduler(self.executor, pool,
                                       self.ecfg.batch_size)
        return ContinuousScheduler(self.executor, pool,
                                   self.ecfg.max_prefill_groups,
                                   prefix_store=self.prefix_store,
                                   policy=SchedulingPolicy(
                                       prefill_chunk=self.ecfg.prefill_chunk,
                                       preemption=self.ecfg.preemption,
                                       hold_k=self.ecfg.hold_k,
                                       hold_ms=self.ecfg.hold_ms))

    # -- request lifecycle ----------------------------------------------------

    def _check_history(self, i, n_tokens: int) -> None:
        max_hist = self.cfg.history_len * self.cfg.n_codebooks
        if n_tokens > max_hist:
            raise ValueError(
                f"request {i}: history of {n_tokens} tokens "
                f"exceeds the model's context ({max_hist} = "
                f"history_len x n_codebooks); truncate upstream")

    def _check_candidates(self, request: Dict) -> Tuple[int, Optional[int]]:
        n_cand = int(request.get("n_candidates", 1))
        if not 1 <= n_cand <= self.ecfg.max_candidates:
            raise ValueError(
                f"n_candidates {n_cand} outside [1, "
                f"{self.ecfg.max_candidates}] (EngineConfig.max_candidates "
                f"sizes the branch regions of every cache row up front)")
        first = request.get("first_token")
        if first is not None and n_cand != 1:
            raise ValueError("first_token (forced seed) requires "
                             "n_candidates == 1")
        if first is not None and self.ecfg.mode != "continuous":
            raise ValueError("first_token requires continuous mode (the "
                             "fixed scheduler never forces seeds)")
        return n_cand, (int(first) if first is not None else None)

    def submit(self, request: Dict,
               base_s: Optional[float] = None) -> RequestHandle:
        """Admit one request dict (ragged "tokens" + "profile", optional
        "arrival_s" / "deadline_s" offsets from ``base_s`` — default NOW —
        an int "priority" class (lower = more important), and
        "n_candidates" (decode a ranked set of K candidate items via tree
        decode; ``Completion.items``/``scores``)) into the scheduler
        queue.

        Non-blocking: returns a ``RequestHandle`` immediately; the request
        makes progress only through ``step()`` / ``drain()`` /
        ``result()``.  Raises ``AdmissionFull`` when a bounded queue
        (``EngineConfig.max_queue``) is at capacity — the backpressure
        signal of the open system (the caller sheds or retries after
        stepping; shed requests are what ``stats()["rejected"]`` counts).
        ``base_s`` (a ``perf_counter`` timestamp) anchors the offsets for
        closed-batch drivers whose requests all share one clock — a
        submission delayed by backpressure must not shift its arrival or
        gain deadline budget.
        """
        tokens = np.asarray(request["tokens"], np.int32)
        self._check_history("<submit>", len(tokens))
        n_candidates, first_token = self._check_candidates(request)
        if self.ecfg.max_queue \
                and self._sched.queue_depth >= self.ecfg.max_queue:
            raise AdmissionFull(
                f"admission queue full ({self.ecfg.max_queue} requests); "
                f"step() or drain() to make room")
        base = time.perf_counter() if base_s is None else base_s
        r = Request(
            rid=next(self._rids), tokens=tokens,
            profile=np.asarray(request["profile"], np.float32),
            arrival_s=base + float(request.get("arrival_s", 0.0)),
            priority=int(request.get("priority", 0)),
            deadline_s=base + float(request["deadline_s"])
            if request.get("deadline_s") is not None else None,
            n_candidates=n_candidates, first_token=first_token)
        self._sched.enqueue(r)
        handle = RequestHandle(self, r)
        self._handles[r.rid] = handle
        return handle

    def step(self) -> List[Completion]:
        """Advance the scheduler one round and deliver completions to
        their handles.  Non-blocking; an idle engine no-ops.  A round that
        dispatched a device program samples its host time: the
        ``serve.step`` span less the time spent inside ``serve.device_wait``
        and ``serve.select`` (``stats()["host_s_per_step"]``)."""
        n = self.executor.counters
        programs = n["prefill_calls"] + n["decode_steps"]
        waited = n["serve.device_wait"] + n["serve.select"]
        with span("serve.step") as round_:
            done = self._sched.step()
        if n["prefill_calls"] + n["decode_steps"] > programs:
            self._host_s.append(round_.s - (n["serve.device_wait"]
                                            + n["serve.select"] - waited))
        for c in done:
            handle = self._handles.pop(c.rid, None)
            if handle is not None:
                handle.completion = c
            self._window_done.append(c)
        return done

    def cancel(self, handle: RequestHandle) -> bool:
        if handle.cancelled or handle.completion is not None:
            return False
        if not self._sched.cancel(handle._request):
            return False            # fixed-mode in-flight rows can't cancel
        handle.cancelled = True
        self._handles.pop(handle.rid, None)
        self._cancelled += 1
        return True

    @property
    def busy(self) -> bool:
        """True while any accepted request has not retired."""
        return self._sched.has_work

    def idle_wait_s(self) -> float:
        """How long ``step()`` would no-op for (next arrival / hold
        release); drive loops sleep this instead of spinning."""
        return self._sched.idle_wait_s()

    def _drain_until(self, predicate: Callable[[], bool]) -> None:
        """Step (and idle-sleep) until ``predicate`` holds or nothing is
        left to do.  The scheduler runs in ``draining`` mode: the caller
        is blocked here, so no new submissions can arrive — hold windows
        and fixed-mode tail batches may release."""
        sched = self._sched
        prev, sched.draining = sched.draining, True
        try:
            while not predicate() and sched.has_work:
                self.step()
                wait = sched.idle_wait_s()
                if wait > 0:
                    time.sleep(wait)
        finally:
            sched.draining = prev

    def drain(self) -> None:
        """Step until every accepted request has retired."""
        self._drain_until(lambda: False)

    def steady_state(self, allow_transfers: bool = False,
                     max_compiles: int = 0):
        """Guarded region asserting the POST-WARMUP serving contract:
        zero new XLA compilations and zero implicit host<->device
        transfers while the engine steps inside the ``with`` block
        (see ``repro.analysis.guards``).  Warm the engine first — run
        one representative batch through ``serve_requests``/``drain`` —
        then step inside the guard::

            engine.serve_requests(reqs)          # warmup compiles
            with engine.steady_state():
                engine.serve_requests(reqs)      # must be compile-free
        """
        from repro.analysis.guards import steady_state
        return steady_state(allow_transfers=allow_transfers,
                            max_compiles=max_compiles)

    # -- windowed metrics -----------------------------------------------------

    def reset_window(self) -> None:
        """Start a fresh measurement window: zero the executor counters,
        the scheduler accounting, and the prefix-store stats.  Entries,
        queues, and in-flight requests are untouched."""
        if self.prefix_store is not None:
            self.prefix_store.reset_window()
        for k in self.executor.counters:
            self.executor.counters[k] = 0
        self._sched.reset_window()
        self._window_done: List[Completion] = []
        self._host_s: List[float] = []
        self._rejected = 0
        self._cancelled = 0
        self._window_t0 = time.perf_counter()

    def stats(self) -> Dict[str, float]:
        """Per-window serving stats over the completions since the last
        ``reset_window()`` (wall clock runs from the reset)."""
        return self._stats(time.perf_counter() - self._window_t0)

    def _stats(self, wall: float) -> Dict[str, float]:
        done = self._window_done
        sched = self._sched
        counters = self.executor.counters
        lat = np.asarray([c.latency_s for c in done], np.float64)
        join = np.asarray(sched.join_step_s, np.float64)
        median = lambda xs: float(np.median(xs)) if len(xs) else 0.0
        return {
            "n_requests": float(len(done)),
            "wall_s": wall,
            "throughput_rps": len(done) / wall if wall else 0.0,
            "mean_latency_s": float(lat.mean()) if lat.size else 0.0,
            "p50_latency_s": float(np.percentile(lat, 50))
            if lat.size else 0.0,
            "p99_latency_s": float(np.percentile(lat, 99))
            if lat.size else 0.0,
            "slot_occupancy": float(np.mean(sched.occupancy))
            if sched.occupancy else 0.0,
            "n_slots": float(self.n_slots),
            # KV capacity accounting from ACTUAL buffer dtypes (fp8 payload
            # + scale leaves when kv_dtype is fp8, not an assumed itemsize)
            "kv_dtype": self.ecfg.kv_dtype,
            "kv_row_bytes": float(self.executor.pool_row_bytes),
            "kv_bytes": float(self.executor.kv_bytes),
            "decode_steps": float(counters["decode_steps"]),
            "prefill_calls": float(counters["prefill_calls"]),
            # multi-candidate tree decode: fused-program dispatches, real
            # branches advanced, and the amortization ratio (branches each
            # decode dispatch served; 1.0 = single-candidate traffic)
            "decode_multi_steps": float(counters["decode_multi_steps"]),
            "branch_tokens": float(counters["branch_tokens"]),
            # fused Pallas decode: steps served by the one-dispatch fused
            # program, selects answered from its stash (each hit is one
            # select program that never dispatched), and the resolved mode
            # after the off-TPU / contiguous fallback rules
            "fused_decode_steps": float(counters["fused_decode_steps"]),
            "fused_select_hits": float(counters["fused_select_hits"]),
            "select_calls": float(counters["select_calls"]),
            "fused_decode_mode": self.executor.fused_decode,
            "branches_per_decode_step":
                counters["branch_tokens"] / counters["decode_steps"]
                if counters["decode_steps"] else 0.0,
            "mode": self.ecfg.mode,
            # open-system lifecycle accounting ("rejected" = requests SHED
            # on AdmissionFull, not retried-then-served submissions)
            "rejected": float(self._rejected),
            "cancelled": float(self._cancelled),
            "hold_rounds": float(sched.holds),
            # prefill waste: bucket padding (tokens)
            "prefill_tokens": float(counters["prefill_tokens_batched"]),
            "prefill_padded_token_frac":
                1.0 - counters["prefill_tokens_real"]
                / counters["prefill_tokens_batched"]
                if counters["prefill_tokens_batched"] else 0.0,
            # join-step wall time: prefill work one engine round performed
            # (chunked prefill bounds its tail); decode-stall = the share of
            # the window's wall clock decoders spent waiting on that work
            "join_steps": float(join.size),
            "join_p50_s": float(np.percentile(join, 50))
            if join.size else 0.0,
            "join_p99_s": float(np.percentile(join, 99))
            if join.size else 0.0,
            "decode_stall_frac": sched.decode_stall_s / wall if wall else 0.0,
            # where a request's latency goes (lifecycle stamps): queue wait
            # over the requests first admitted in the window, prefill and
            # decode phases over those completed in it; and the host time
            # of a round that dispatched a device program
            "queue_wait_p50_s": median(sched.queue_wait_s),
            "prefill_phase_p50_s": median([c.prefill_phase_s for c in done]),
            "decode_phase_p50_s": median([c.decode_phase_s for c in done]),
            "host_s_per_step": float(np.mean(self._host_s))
            if self._host_s else 0.0,
            "preemptions": float(sched.preemptions),
            # block-scaled fp8 GEMMs lowered by the programs built since
            # the engine was (core/quant's form tally): decode's few rows
            # per expert scale the partials, prefill's dequantize once
            "moe_gemm_forms": {
                k: n - self._gemm_forms0[k]
                for k, n in gemm_form_counts().items()},
            **self._sla_stats(done),
            **self._prefix_stats(),
            **self._paged_stats(),
        }

    def _paged_stats(self) -> Dict[str, float]:
        """Paged-layout metrics (zeros when the contiguous layout is in
        use, mirroring ``_prefix_stats``'s always-present pattern)."""
        pp = self.executor.page_pool
        if pp is None:
            return {"pages_total": 0.0, "pages_free": 0.0,
                    "page_size": 0.0, "kv_bytes_pinned": 0.0,
                    "cow_copies": 0.0, "prefix_row_copies":
                    float(self.executor.counters["prefix_row_copies"])}
        return {"pages_total": float(pp.n_pages),
                "pages_free": float(pp.n_free),
                "page_size": float(pp.page_size),
                # bytes actually pinned by live tables + store entries —
                # the number the contiguous layout can't report better
                # than "rows x worst-case row"
                "kv_bytes_pinned": float(pp.n_used
                                         * self.executor.page_bytes),
                "cow_copies": float(self.executor.counters["cow_copies"]),
                "prefix_row_copies":
                    float(self.executor.counters["prefix_row_copies"])}

    # -- closed-batch shims (seed-engine API) ---------------------------------

    def serve_requests(self, requests: List[Dict[str, np.ndarray]]
                       ) -> Tuple[List[np.ndarray], Dict[str, float]]:
        """Closed-batch shim over submit + step + drain: serve
        ``requests`` (offsets are measured from call start) and return
        per-request outputs in input order + per-call stats.  Token-
        identical to the closed-loop scheduler it replaced — the shim adds
        no scheduling of its own."""
        for i, r in enumerate(requests):
            self._check_history(i, len(r["tokens"]))
        self.reset_window()
        if not requests:
            return [], self._stats(0.0)
        sched = self._sched
        prev, sched.draining = sched.draining, True
        try:
            handles = []
            for r in requests:
                while True:
                    try:
                        # anchor offsets at call start: a submission the
                        # bounded queue delays keeps its true arrival and
                        # gains no deadline budget
                        handles.append(self.submit(r,
                                                   base_s=self._window_t0))
                        break
                    except AdmissionFull:  # bounded queue: step to drain it
                        self._drain_until(
                            lambda: sched.queue_depth < self.ecfg.max_queue)
            self.drain()
        finally:
            sched.draining = prev
        wall = time.perf_counter() - self._window_t0

        outputs = [h.completion.item for h in handles]
        self.metrics["latency_s"] = [h.completion.latency_s for h in handles]
        return outputs, self._stats(wall)

    @staticmethod
    def _sla_stats(done: List[Completion]) -> Dict[str, object]:
        """Deadline accounting overall and per priority class.  Miss rates
        are over the requests that HAVE a deadline; ``class_stats`` keys
        are the class numbers as strings (JSON-friendly)."""
        with_dl = [c for c in done if c.deadline_s is not None]
        misses = sum(c.deadline_missed for c in with_dl)
        classes: Dict[str, List[Completion]] = {}
        for c in done:
            classes.setdefault(str(c.priority), []).append(c)
        class_stats = {}
        for cls, cs in sorted(classes.items()):
            lat = np.asarray([c.latency_s for c in cs])
            cls_dl = [c for c in cs if c.deadline_s is not None]
            class_stats[cls] = {
                "n": float(len(cs)),
                "mean_latency_s": float(lat.mean()),
                "p99_latency_s": float(np.percentile(lat, 99)),
                "deadline_misses": float(sum(c.deadline_missed
                                             for c in cls_dl)),
                "deadline_miss_rate": sum(c.deadline_missed for c in cls_dl)
                / len(cls_dl) if cls_dl else 0.0,
            }
        return {"deadline_misses": float(misses),
                "deadline_miss_rate": misses / len(with_dl)
                if with_dl else 0.0,
                "class_stats": class_stats}

    def _prefix_stats(self) -> Dict[str, float]:
        """Tier-2 prefix-store metrics (zeros when the cache is disabled)."""
        s = self.prefix_store
        if s is None:
            return {"prefix_hit_rate": 0.0, "prefix_hits": 0.0,
                    "prefix_admissions": 0.0, "prefix_tokens_saved": 0.0,
                    "prefix_entries": 0.0, "prefix_evictions": 0.0,
                    "prefix_first_sights": 0.0,
                    "prefix_store_bytes": 0.0, "prefix_bytes_pinned": 0.0}
        return {"prefix_hit_rate": s.hit_rate,
                "prefix_hits": float(s.hits),
                "prefix_admissions": float(s.admissions),
                "prefix_tokens_saved": float(s.tokens_saved),
                "prefix_entries": float(s.n_entries),
                "prefix_evictions": float(s.evictions),
                "prefix_first_sights": float(s.first_sights),
                "prefix_store_bytes": float(s.bytes_used),
                "prefix_bytes_pinned": float(s.peak_bytes_pinned)}

    def generate_batch(self, tokens: np.ndarray, profile: np.ndarray
                       ) -> np.ndarray:
        """Seed-engine compat: one uniform batch (B, H*3) -> (B, decode_len)."""
        outputs, _ = self.serve_requests(requests_from_arrays(tokens,
                                                              profile))
        return np.stack(outputs)


def run_open_loop(engine: ServingEngine, requests: List[Dict],
                  drop_on_full: bool = False
                  ) -> Tuple[List[Optional[np.ndarray]], Dict[str, float]]:
    """True open-loop serving: submit each request at its WALL-CLOCK
    arrival (its "arrival_s" offset from loop start) while stepping the
    engine between arrivals — the open-queueing-system regime, as opposed
    to the closed shim that enqueues everything up front.

    "deadline_s" offsets stay anchored to the workload clock (arrival +
    allowance), so a submission delayed by an overloaded engine does not
    get extra budget.  With ``drop_on_full`` a bounded admission queue
    sheds load (``AdmissionFull`` -> output None, counted in
    ``stats()["rejected"]``); otherwise backpressure propagates to the
    caller.  Returns (outputs in input order, window stats).
    """
    engine.reset_window()
    t0 = engine._window_t0
    order = sorted(range(len(requests)),
                   key=lambda j: requests[j].get("arrival_s", 0.0))
    handles: List[Optional[RequestHandle]] = [None] * len(requests)
    for j in order:
        target = float(requests[j].get("arrival_s", 0.0))
        while True:
            now = time.perf_counter() - t0
            if now >= target:
                break
            if engine.busy:
                counters = engine.executor.counters
                before = (counters["prefill_calls"]
                          + counters["decode_steps"])
                engine.step()
                wait = engine.idle_wait_s()
                if wait <= 0 and (counters["prefill_calls"]
                                  + counters["decode_steps"]) == before:
                    # blocked on submissions the scheduler can't foresee
                    # (fixed-mode batch formation, count-only holds):
                    # nap instead of spinning until the next arrival
                    wait = 1e-3
            else:
                wait = target - now
            if wait > 0:
                now = time.perf_counter() - t0
                time.sleep(min(wait, max(0.0, target - now)))
        rel = dict(requests[j])
        rel.pop("arrival_s", None)          # arrival IS the submit instant
        now = time.perf_counter() - t0
        if rel.get("deadline_s") is not None:
            rel["deadline_s"] = float(rel["deadline_s"]) - now
        try:
            handles[j] = engine.submit(rel)
        except AdmissionFull:
            if not drop_on_full:
                raise
            engine._rejected += 1     # shed: the request is never served
    engine.drain()
    outputs = [h.completion.item if h is not None and h.completion is not None
               else None for h in handles]
    return outputs, engine.stats()
