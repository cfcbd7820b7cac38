"""Compiled-phase executor: the jitted programs behind the serving engine.

Core programs, mirroring the paper's one-graph-per-phase design (§5.2):

  * ``prefill_insert`` — ragged prefill of a join group: runs the profile +
    history forward for ``Bp`` new requests (right-padded to a shared length
    bucket), fills a fresh per-slot cache, and scatters those rows into the
    DONATED slot pool at the target slot ids.  One XLA program per
    (Bp, T-bucket) shape; bucketing keeps the compile count small.
  * ``decode`` — one token for every slot in the pool at its own absolute
    index (length-masked attention), donated cache in / cache out.
  * ``decode_multi`` — the multi-candidate TREE-decode step: (N, C) branch
    tokens, C candidate branches per slot, one fused program; every branch
    attends the slot's shared prefix K/V in place plus its own reserved
    branch span (``n_candidates`` sizes the spans at cache init).
  * ``select`` — top-k over the logits (RadixTopK kernel or ``lax.top_k``).
  * ``select_scored`` — top-k + log-partition, so branch scores (log-probs)
    cost no extra program.
  * ``decode_fused`` / ``decode_multi_fused`` — the paged decode step
    through the Pallas ``kernels/paged_decode`` kernel (page-table gather
    on device, FP8 dequant in registers, tree mask + online softmax per
    page block) WITH the select tail folded in: one dispatch per decode
    step replaces the decode + select pair (``fused_decode`` knob).
  * ``free_slots`` — one vectorized pos-clear over a batch of retired slots
    (one dispatch per engine step, not one per request).

Prefix-store programs (tier 2 of the KV cache, ``prefix_rows > 0``): the
executor also owns a device ARENA — ``prefix_rows`` extra cache rows with
the same layout as the pool, indexed by the host-side
``kv_cache.PrefixStore`` — plus three copy/compute programs:

  * ``prefix_save`` — gather freshly prefilled pool rows into arena rows
    (admitting prefixes to the store),
  * ``prefix_copy_insert`` — scatter stored arena rows into target pool
    slots, masking positions past each prefix's length,
  * ``resume_prefill`` — ragged prefill of only the UNCACHED suffix of each
    request, starting at per-row nonzero offsets and attending over the
    prefix K/V already sitting in the slot.  This is the program that turns
    repeat traffic's prefill FLOPs into a row copy.

Quantization (FP8 PTQ vs BF16 baseline) is a parameter-tree swap via the
policy switch — the programs are precision-agnostic, exactly as the paper's
unified serving graph is.  The executor OWNS the device-side pool and arena
trees; schedulers only ever see slot ids, arena row ids, and logits.
"""

from __future__ import annotations

import logging

from functools import partial
from typing import Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import OneRecConfig
from repro.core.policy import BASELINE_POLICY, PAPER_POLICY, QuantPolicy
from repro.core.ptq import apply_static_act_scales, quantize_params
from repro.models import onerec as onerec_model
from repro.models import transformer as tfm_model
from repro.serving.kv_cache import INDEX_DTYPE, PagePool, as_index
from repro.serving.spans import span

logger = logging.getLogger(__name__)


def resolve_fused_decode(fused_decode: Union[bool, str, None],
                         paged: bool) -> str:
    """Normalize the ``fused_decode`` knob to one of ``off`` / ``tpu`` /
    ``interpret`` and apply the fallback rules, logging ONCE per resolution:

      * ``off`` / False / None — unfused paths everywhere.
      * ``auto`` / True — fused Pallas decode kernel when the pool is paged
        AND the backend is a TPU; otherwise log and fall back to the
        existing unfused path (contiguous layouts have no page tables to
        feed the kernel; off-TPU the compiled kernel cannot run).
      * ``interpret`` — force the kernel in Pallas interpret mode (CPU
        differential tests, e2e parity runs); still requires the paged
        layout.
    """
    mode = {False: "off", True: "auto", None: "off"}.get(
        fused_decode, fused_decode)
    if mode not in ("off", "auto", "interpret"):
        raise ValueError(f"fused_decode must be off/auto/interpret "
                         f"(or bool), got {fused_decode!r}")
    if mode == "off":
        return "off"
    if not paged:
        logger.warning(
            "fused_decode=%s requires the paged KV layout; falling back "
            "to the unfused contiguous decode path", mode)
        return "off"
    if mode == "interpret":
        return "interpret"
    if jax.default_backend() != "tpu":
        logger.warning(
            "fused_decode=auto on backend %r (no TPU); falling back to "
            "the unfused paged decode path", jax.default_backend())
        return "off"
    return "tpu"


def bucket_length(n: int, minimum: int = 16) -> int:
    """Smallest power-of-two >= n (floored at ``minimum``) — pads ragged
    shapes to a handful of compiled variants."""
    b = minimum
    while b < n:
        b *= 2
    return b


class PhaseExecutor:
    """Owns the quantized params, the device slot pool, and the compiled
    prefill/decode/select programs."""

    def __init__(self, params, cfg: OneRecConfig, *, n_slots: int,
                 use_fp8: bool = True, topk: int = 8,
                 use_radix_topk: bool = False,
                 prefill_bucket_min: int = 16,
                 prefix_rows: int = 0,
                 n_candidates: int = 1,
                 kv_dtype: Optional[str] = None,
                 paged: bool = False,
                 page_size: int = 32,
                 n_pages: int = 0,
                 fused_decode: Union[bool, str, None] = False,
                 quant_policy: Optional[QuantPolicy] = None,
                 act_scales: Optional[Dict[str, float]] = None):
        if n_candidates < 1:
            raise ValueError(f"n_candidates must be >= 1, got {n_candidates}")
        if n_candidates > topk:
            raise ValueError(
                f"n_candidates ({n_candidates}) exceeds topk ({topk}): "
                f"branch seeds come from the top-k select program")
        self.cfg = cfg
        self.n_slots = n_slots
        self.topk = topk
        self.prefill_bucket_min = prefill_bucket_min
        self.prefix_rows = prefix_rows
        self.n_candidates = n_candidates
        # K/V storage dtype for BOTH cache tiers (pool + arena); None
        # resolves the model config's kv_cache_dtype (bfloat16 default).
        # An fp8 dtype stores K/V quantized with per-(position, head) scale
        # leaves riding every row — all copy programs move them together.
        self.kv_dtype = jnp.dtype(kv_dtype or cfg.transformer.kv_cache_dtype)
        # tree decode: branch b's own tokens occupy a reserved span of
        # branch_stride = decode_len - 1 physical positions past the shared
        # prefix, so C branches need (C - 1) * stride rows beyond the
        # single-candidate cache length
        self.branch_stride = max(cfg.decode_len - 1, 0)
        extra = (n_candidates - 1) * self.branch_stride
        kv_dt = self.kv_dtype
        # a tuned QuantPolicy (e.g. loaded from an autotune artifact)
        # overrides the all-or-nothing use_fp8 switch; calibrated static
        # activation scales ride the quantized leaves (fp8_linear skips
        # the runtime per-token amax reduction where they are attached)
        policy = quant_policy if quant_policy is not None else \
            (PAPER_POLICY if use_fp8 else BASELINE_POLICY)
        self.quant_policy = policy
        self.params = quantize_params(params, policy)
        if act_scales:
            self.params = apply_static_act_scales(self.params, act_scales)
        # per-request worst-case footprint in positions: profile + full
        # history + first decode token, plus every reserved branch span
        s_row = cfg.context_len + 1 + extra
        self.paged = bool(paged)
        if self.paged:
            # -- PAGED layout: one flat pool of n_pages fixed-size pages
            # (plus a trailing sentinel page) replaces slot pool AND arena.
            # A slot is a host page table; a stored prefix is extra
            # refcounts on the pages it covers (zero-copy hits).
            self._p_max = -(-s_row // page_size)   # table entries per slot
            if n_pages < self._p_max:
                raise ValueError(
                    f"n_pages ({n_pages}) below one request's footprint "
                    f"({self._p_max} pages of {page_size} positions)")
            self.page_size = page_size
            self.n_pages = n_pages
            self._sentinel = n_pages               # virgin page, pos = -1
            self._drop = (n_pages + 1) * page_size  # OOB flat scatter index
            self._sp = self._p_max * page_size     # gathered view length
            self.page_pool = PagePool(n_pages, page_size)
            # dense table matrix (slot -> page per logical page index);
            # unmapped entries point at the sentinel page so empty slots
            # gather an all-masked view — exactly a contiguous freed row
            self._table_mat = np.full((n_slots, self._p_max),
                                      self._sentinel, np.int32)
            self._slot_pages: Dict[int, List[int]] = {}
            self.cache = onerec_model.init_page_pool(cfg, n_pages, page_size,
                                                     dtype=kv_dt)
            self.arena = None
        else:
            self.page_pool = None
            self.cache = onerec_model.init_slot_cache(cfg, n_slots,
                                                      dtype=kv_dt,
                                                      extra_len=extra)
            # tier-2 arena: prefix-store rows, same per-row layout as the pool
            self.arena = (onerec_model.init_slot_cache(cfg, prefix_rows,
                                                       dtype=kv_dt,
                                                       extra_len=extra)
                          if prefix_rows > 0 else None)
        # fused Pallas decode: resolve the knob against the layout and the
        # backend (one warning per fallback), and hold the pending fused
        # select results — the fused program computes top-k + logsumexp in
        # the SAME dispatch, so the scheduler's following select_scored
        # call is served from this stash instead of a second program
        self.fused_decode = resolve_fused_decode(fused_decode, self.paged)
        self._fused_select: Optional[tuple] = None
        self.counters: Dict[str, int] = {"prefill_calls": 0,
                                         "resume_calls": 0,
                                         "decode_steps": 0,
                                         "decode_multi_steps": 0,
                                         "branch_tokens": 0,
                                         "fused_decode_steps": 0,
                                         "fused_select_hits": 0,
                                         "select_calls": 0,
                                         "prefill_tokens_batched": 0,
                                         "prefill_tokens_real": 0,
                                         "prefix_row_copies": 0,
                                         "cow_copies": 0,
                                         "pages_granted": 0,
                                         # host seconds inside the spans
                                         # a round spends waiting on the
                                         # device (host_s_per_step)
                                         "serve.device_wait": 0.0,
                                         "serve.select": 0.0}
        # NOTE: every phase entry point below gates on completion via
        # block_until_ready before returning, so async dispatch can't smear
        # one phase's device work into the next host-side measurement — the
        # scheduler's join-step p99 / decode-stall metrics depend on it.
        # The serving loop is host-driven (it reads logits back every
        # step), so the gating costs no real pipelining.

        if use_radix_topk:
            from repro.kernels.radix_topk import radix_topk
            topk_fn = lambda logits, k: radix_topk(logits, k)
        else:
            topk_fn = lambda logits, k: jax.lax.top_k(logits, k)

        @partial(jax.jit, donate_argnums=(1,))
        def prefill_insert_fn(params, pool, tokens, profile, lengths, slots):
            # fresh rows share the pool's layout (dtype and scale leaves
            # included), branch regions included
            fresh = onerec_model.init_slot_cache(cfg, tokens.shape[0],
                                                 dtype=kv_dt,
                                                 extra_len=extra)
            last, filled = onerec_model.prefill_into_slots(
                params, {"tokens": tokens, "profile": profile}, cfg, fresh,
                lengths)
            # scatter whole rows into the pool (batch axis 1 under the
            # stacked-layer leading axis); duplicate slot ids only ever carry
            # identical rows (batch padding duplicates a real request)
            pool = jax.tree_util.tree_map(
                lambda p, f: p.at[:, slots].set(f.astype(p.dtype)),
                pool, filled)
            return last, pool

        @partial(jax.jit, donate_argnums=(1,))
        def decode_fn(params, pool, tokens, lengths):
            return onerec_model.decode_step_slots(params, tokens, cfg, pool,
                                                  lengths)

        @partial(jax.jit, donate_argnums=(1,))
        def decode_multi_fn(params, pool, tokens, lengths, starts, counts):
            # tree decode: ONE program advances every branch of every slot
            # (tokens (N, C)); compiles once per branch width C.  ``counts``
            # drops dummy-branch writes past each row's real width — a row
            # that later decodes at width 1 (span-blind mask) must never
            # have populated its unused spans
            return onerec_model.decode_step_slots(
                params, tokens, cfg, pool, lengths, starts=starts,
                branch_stride=self.branch_stride, branch_counts=counts)

        @jax.jit
        def select_fn(logits):
            return topk_fn(logits, topk)

        @jax.jit
        def select_scored_fn(logits):
            # top-k + the log-partition, so the host can turn any selected
            # logit into a log-prob (branch scores) without a second pass
            vals, ids = topk_fn(logits, topk)
            lse = jax.scipy.special.logsumexp(
                logits.astype(jnp.float32), axis=-1)
            return vals, ids, lse

        @partial(jax.jit, donate_argnums=(0,))
        def clear_slots_fn(pool, slots):
            # mark every position of a BATCH of slot rows empty (pos = -1)
            # so freed rows read exactly like virgin ones: their dummy
            # decodes attend to nothing instead of stale K/V, keeping pool
            # state — and therefore MoE capacity interaction — independent
            # of serving history.  One dispatch retires a whole engine
            # step's completions (duplicate padded ids are benign).
            def walk(tree):
                if "pos" in tree:
                    return {**tree, "pos": tree["pos"].at[:, slots].set(-1)}
                return {k: walk(v) for k, v in tree.items()}
            return walk(pool)

        @partial(jax.jit, donate_argnums=(1,))
        def resume_prefill_fn(params, pool, tokens, lengths, starts, slots):
            # gather the target rows (they already hold profile + prefix
            # K/V from prefix_copy_insert), run the suffix-only ragged
            # forward at per-row offsets, and scatter the rows back
            fresh = jax.tree_util.tree_map(lambda p: p[:, slots], pool)
            last, filled = onerec_model.prefill_into_slots(
                params, {"tokens": tokens}, cfg, fresh, lengths,
                starts=starts)
            pool = jax.tree_util.tree_map(
                lambda p, f: p.at[:, slots].set(f.astype(p.dtype)),
                pool, filled)
            return last, pool

        @partial(jax.jit, donate_argnums=(0,))
        def prefix_copy_insert_fn(pool, arena, rows, slots, lengths):
            # scatter stored arena rows into target pool slots; positions at
            # or past each prefix's length are masked empty so stale
            # occupancy beyond the advertised prefix can never be attended
            def walk(p, a):
                if "pos" in p:
                    picked = a["pos"][:, rows]
                    keep = (picked >= 0) & (picked < lengths[None, :, None])
                    # every non-pos leaf (k/v payload AND any fp8 scale
                    # arrays) rides the copy wholesale — pool and arena
                    # share one dtype, so a stored prefix round-trips
                    # bit-identically, scales included
                    out = {key: p[key].at[:, slots].set(
                        a[key][:, rows].astype(p[key].dtype))
                        for key in p if key != "pos"}
                    out["pos"] = p["pos"].at[:, slots].set(
                        jnp.where(keep, picked, -1))
                    return out
                return {k: walk(p[k], a[k]) for k in p}
            return walk(pool, arena)

        @partial(jax.jit, donate_argnums=(0,))
        def prefix_save_fn(arena, pool, rows, slots):
            # gather freshly prefilled pool rows into arena rows (wholesale
            # — restore masks to the entry's length, so a row may safely
            # carry more valid positions than the prefix it advertises)
            return jax.tree_util.tree_map(
                lambda a, p: a.at[:, rows].set(p[:, slots].astype(a.dtype)),
                arena, pool)

        # -- paged-layout programs: the same phases, indexed through host-
        # computed flat physical positions (page_scatter) and per-row dense
        # gather views (page_gather) instead of contiguous row arithmetic.
        # The host owns every page table, so live/drop gating moves out of
        # the programs entirely: an invalid write is simply an out-of-range
        # scatter index, dropped by XLA.

        @partial(jax.jit, donate_argnums=(1,))
        def prefill_insert_paged_fn(params, pool, tokens, profile, lengths,
                                    psc):
            # fresh prefill needs NO paged attention: run the contiguous
            # fill into a throwaway per-slot cache sized to this bucket
            # (logits only depend on the filled rows), then scatter every
            # leaf's positions to their granted pages.  psc (B, T+1) holds
            # the flat physical index of logical position l for each row
            # (out-of-range past the row's occupancy = dropped).
            b, t_eff = tokens.shape[0], tokens.shape[1] + 1
            fresh = tfm_model.init_kv_cache(cfg.transformer, b, t_eff,
                                            dtype=kv_dt, per_slot=True)
            last, filled = onerec_model.prefill_into_slots(
                params, {"tokens": tokens, "profile": profile}, cfg, fresh,
                lengths)
            pool = jax.tree_util.tree_map(
                lambda p, f: p.at[:, psc].set(f.astype(p.dtype),
                                              mode="drop"),
                pool, filled)
            return last, pool

        @partial(jax.jit, donate_argnums=(1,))
        def resume_prefill_paged_fn(params, pool, tokens, lengths, starts,
                                    psc, pgi):
            return onerec_model.prefill_into_slots(
                params, {"tokens": tokens}, cfg, pool, lengths,
                starts=starts, page_scatter=psc, page_gather=pgi)

        @partial(jax.jit, donate_argnums=(1,))
        def decode_paged_fn(params, pool, tokens, lengths, psc, pgi):
            return onerec_model.decode_step_slots(
                params, tokens, cfg, pool, lengths,
                page_scatter=psc, page_gather=pgi)

        @partial(jax.jit, donate_argnums=(1,))
        def decode_multi_paged_fn(params, pool, tokens, lengths, starts,
                                  psc, pgi):
            # dummy-branch / inactive-row writes are already redirected to
            # the drop index by the host psc builder, so no branch_counts
            # reach the program
            return onerec_model.decode_step_slots(
                params, tokens, cfg, pool, lengths, starts=starts,
                branch_stride=self.branch_stride,
                page_scatter=psc, page_gather=pgi)

        # -- fused decode programs: the Pallas paged-decode kernel replaces
        # the dense gathered-view attention, and the select (top-k + log-
        # partition) rides in the SAME program — one dispatch per decode
        # step instead of the decode + select pair.  The page table is a
        # plain int32 operand (the host's _table_mat rows, verbatim).
        fused_interp = (self.fused_decode == "interpret") or None
        fused_ps = page_size

        def _fused_select_tail(logits):
            flat = logits.reshape((-1, logits.shape[-1]))
            vals, ids = topk_fn(flat, topk)
            lse = jax.scipy.special.logsumexp(
                flat.astype(jnp.float32), axis=-1)
            return vals, ids, lse

        @partial(jax.jit, donate_argnums=(1,))
        def decode_fused_fn(params, pool, tokens, lengths, psc, tabs):
            logits, pool = onerec_model.decode_step_slots(
                params, tokens, cfg, pool, lengths, page_scatter=psc,
                page_tables=tabs, page_size=fused_ps,
                fused_interpret=fused_interp)
            vals, ids, lse = _fused_select_tail(logits)
            return logits, vals, ids, lse, pool

        @partial(jax.jit, donate_argnums=(1,))
        def decode_multi_fused_fn(params, pool, tokens, lengths, starts,
                                  psc, tabs):
            logits, pool = onerec_model.decode_step_slots(
                params, tokens, cfg, pool, lengths, starts=starts,
                branch_stride=self.branch_stride, page_scatter=psc,
                page_tables=tabs, page_size=fused_ps,
                fused_interpret=fused_interp)
            vals, ids, lse = _fused_select_tail(logits)
            return logits, vals, ids, lse, pool

        @partial(jax.jit, donate_argnums=(0,))
        def free_pages_fn(pool, pages):
            # clear the pos lane of a batch of freed pages so re-granted
            # pages read virgin (same invariant as clear_slots_fn); padded
            # ids point past the sentinel page and are dropped
            flat = (pages[:, None] * page_size
                    + jnp.arange(page_size, dtype=jnp.int32)[None, :])
            flat = flat.reshape(-1)

            def walk(tree):
                if "pos" in tree:
                    return {**tree,
                            "pos": tree["pos"].at[:, flat].set(
                                -1, mode="drop")}
                return {k: walk(v) for k, v in tree.items()}
            return walk(pool)

        @partial(jax.jit, donate_argnums=(0,))
        def page_copy_fn(pool, src, dst):
            # copy-on-write of ONE boundary page: gather the source page's
            # positions and scatter them at the destination page.  The host
            # sets dst past the match boundary to the drop index, so the
            # destination page stays virgin (pos = -1) there — every leaf
            # (k/v payload, pos, fp8 scales) copies uniformly.
            return jax.tree_util.tree_map(
                lambda p: p.at[:, dst].set(p[:, src], mode="drop"), pool)

        self._prefill_insert_paged = prefill_insert_paged_fn
        self._resume_prefill_paged = resume_prefill_paged_fn
        self._decode_paged = decode_paged_fn
        self._decode_multi_paged = decode_multi_paged_fn
        self._decode_fused = decode_fused_fn
        self._decode_multi_fused = decode_multi_fused_fn
        self._free_pages = free_pages_fn
        self._page_copy = page_copy_fn

        self._prefill_insert = prefill_insert_fn
        self._decode = decode_fn
        self._decode_multi = decode_multi_fn
        self._select = select_fn
        self._select_scored = select_scored_fn
        self._clear_slots = clear_slots_fn
        self._resume_prefill = resume_prefill_fn
        self._prefix_copy_insert = prefix_copy_insert_fn
        self._prefix_save = prefix_save_fn

    # -- phase entry points (host-side padding/bucketing) ---------------------

    def _pad_group(self, tokens_list: List[np.ndarray]
                   ) -> Tuple[np.ndarray, np.ndarray, List[int]]:
        """Shared prefill bucketing: right-pad the group to a length bucket
        and the batch to a power of two by DUPLICATING the last request.
        Returns (tokens (b_bucket, t_bucket), lengths (b_bucket,), source
        row per padded row) and updates the prefill counters — the ONE
        place the full-prefill and resume-prefill shape contracts live."""
        n = len(tokens_list)
        lens = [len(t) for t in tokens_list]
        t_bucket = bucket_length(max(lens), self.prefill_bucket_min)
        t_bucket = min(t_bucket, self.cfg.history_len * self.cfg.n_codebooks)
        b_bucket = bucket_length(n, 1)
        tok = np.zeros((b_bucket, t_bucket), np.int32)
        lengths = np.zeros((b_bucket,), np.int32)
        src = [min(i, n - 1) for i in range(b_bucket)]
        for i, j in enumerate(src):
            tok[i, :lens[j]] = tokens_list[j]
            lengths[i] = lens[j]
        self.counters["prefill_calls"] += 1
        self.counters["prefill_tokens_batched"] += b_bucket * t_bucket
        self.counters["prefill_tokens_real"] += sum(lens)
        return tok, lengths, src

    # -- paged layout: host page tables + flat index builders -----------------

    def _gather_indices(self, slot_ids) -> np.ndarray:
        """(N, Sp) flat physical index of each row's LOGICALLY DENSE pool
        view (Sp = table entries x page size).  Unmapped table entries
        point inside the sentinel page, whose ``pos`` lane is permanently
        -1 — an empty slot therefore gathers an all-masked view, reading
        exactly like a contiguous freed row."""
        tabs = self._table_mat[as_index(slot_ids)]
        flat = (tabs[:, :, None].astype(INDEX_DTYPE) * self.page_size
                + np.arange(self.page_size, dtype=INDEX_DTYPE)[None, None, :])
        return flat.reshape(len(slot_ids), -1)

    def _scatter_indices(self, slot_ids, logical, valid) -> np.ndarray:
        """Flat physical scatter index for per-row ``logical`` positions
        (any shape with a leading row axis).  Entries with ``valid`` False
        — and any position whose page is unmapped — resolve to the drop
        index, so the program's write is discarded by XLA."""
        n = len(slot_ids)
        tabs = self._table_mat[as_index(slot_ids)]
        l = as_index(logical)
        pg = np.clip(l // self.page_size, 0, self._p_max - 1)
        entry = np.take_along_axis(
            tabs, pg.reshape(n, -1), axis=1).reshape(l.shape)
        phys = entry.astype(INDEX_DTYPE) * self.page_size + l % self.page_size
        ok = (np.asarray(valid, bool) & (entry != self._sentinel)
              & (l >= 0) & (l < self._sp))
        return np.where(ok, phys, self._drop).astype(INDEX_DTYPE)

    def _free_pages_device(self, pages: List[int]) -> None:
        """Clear the ``pos`` lane of freed pages in one scatter program
        (padded ids land past the sentinel page and are dropped)."""
        if not pages:
            return
        b = bucket_length(len(pages), 1)
        ids = np.asarray(pages + [self.n_pages + 1] * (b - len(pages)),
                         np.int32)
        self.cache = self._free_pages(self.cache, jnp.asarray(ids))

    def grant_slot(self, slot: int, n_positions: int) -> bool:
        """Admission grant: allocate the pages covering ``n_positions``
        logical positions for ``slot`` (its full worst-case footprint —
        prefill + every branch span it will actually use).  All-or-nothing;
        False leaves the pool untouched so the scheduler can reclaim store
        pages and retry."""
        assert self.paged, "grant_slot requires the paged layout"
        need = self.page_pool.pages_for(n_positions)
        pages = self.page_pool.alloc(need)
        if pages is None:
            return False
        self._table_mat[slot] = self._sentinel
        self._table_mat[slot, :need] = pages
        self._slot_pages[slot] = list(pages)
        self.counters["pages_granted"] += need
        return True

    def attach_prefix(self, slot: int, entry_pages: List[int],
                      boundary: int, n_positions: int) -> bool:
        """Prefix-cache HIT admission: map a stored prefix's pages into
        ``slot`` read-only (refcount bump, ZERO device copies), COW the one
        partially-matched boundary page if the match boundary is not
        page-aligned, and allocate fresh pages for the rest of the
        footprint.  ``boundary`` is the match length in positions (profile
        + matched history tokens); ``n_positions`` the slot's footprint."""
        assert self.paged, "attach_prefix requires the paged layout"
        ps = self.page_size
        full = boundary // ps
        cow = 1 if boundary % ps else 0
        need = self.page_pool.pages_for(n_positions) - full
        if need > self.page_pool.n_free:
            return False
        fresh = self.page_pool.alloc(need) or []
        shared = self.page_pool.share(entry_pages[:full])
        table = shared + fresh
        self._table_mat[slot] = self._sentinel
        self._table_mat[slot, :len(table)] = table
        self._slot_pages[slot] = table
        self.counters["pages_granted"] += need
        if cow:
            # copy positions [full*ps, boundary) of the donor's boundary
            # page; offsets past the boundary scatter out of range, so the
            # fresh page stays virgin (pos = -1) there — the paged
            # equivalent of prefix_copy_insert's length mask
            keep = boundary % ps
            off = np.arange(ps, dtype=INDEX_DTYPE)
            src = as_index(entry_pages[full] * ps + off)
            dst = np.where(off < keep, fresh[0] * ps + off, self._drop)
            self.cache = self._page_copy(self.cache, jnp.asarray(src),
                                         jnp.asarray(as_index(dst)))
            self.counters["cow_copies"] += 1
        return True

    def share_prefix(self, slot: int, n_positions: int) -> List[int]:
        """Store-admit under the paged layout: add one reference to the
        slot's pages covering ``n_positions`` (the entry's advertised
        occupancy) and return them — the stored prefix IS those refcounts,
        no arena copy exists.  The donor keeps decoding: it only ever
        appends at positions past the boundary, and restore masks the
        boundary page's tail via COW, so shared content is immutable."""
        assert self.paged, "share_prefix requires the paged layout"
        need = self.page_pool.pages_for(n_positions)
        owned = self._slot_pages.get(slot, [])
        assert need <= len(owned), \
            f"slot {slot} holds {len(owned)} pages, prefix needs {need}"
        return self.page_pool.share(owned[:need])

    def release_pages(self, pages: List[int]) -> None:
        """Drop one reference per page (store eviction path); pages whose
        refcount hits zero get their device ``pos`` lane cleared."""
        assert self.paged, "release_pages requires the paged layout"
        self._free_pages_device(self.page_pool.release(pages))

    def prefill_insert(self, tokens_list: List[np.ndarray],
                       profiles: List[np.ndarray], slots: List[int]
                       ) -> jax.Array:
        """Prefill one join group into the pool.

        ``tokens_list[i]`` (L_i,) is request i's history; all go to
        ``slots[i]``.  The group is right-padded to a length bucket and the
        batch is padded to a power of two by DUPLICATING the last request
        (same slot id — the scatter rows are identical, so duplicate indices
        are benign).  Returns FULL-BUCKET next-token logits (b_bucket, V);
        callers slice selections to the first ``len(slots)`` rows — keeping
        the bucket shape here means downstream ``select`` compiles once per
        power-of-two bucket, not once per join-group size.
        """
        with span("serve.stage"):
            tok, lengths, src = self._pad_group(tokens_list)
            prof = np.stack([profiles[j] for j in src]).astype(np.float32)
            slot_ids = np.asarray([slots[j] for j in src], np.int32)
            if self.paged:
                # scatter each row's occupancy (profile + history) onto its
                # granted pages; duplicate padded rows write identical values
                t_eff = tok.shape[1] + 1
                logical = np.broadcast_to(
                    np.arange(t_eff, dtype=INDEX_DTYPE)[None, :],
                    (tok.shape[0], t_eff))
                valid = logical < (as_index(lengths)[:, None] + 1)
                psc = self._scatter_indices(slot_ids, logical, valid)
                logits, self.cache = self._prefill_insert_paged(
                    self.params, self.cache, jnp.asarray(tok),
                    jnp.asarray(prof), jnp.asarray(lengths),
                    jnp.asarray(psc))
            else:
                logits, self.cache = self._prefill_insert(
                    self.params, self.cache, jnp.asarray(tok),
                    jnp.asarray(prof), jnp.asarray(lengths),
                    jnp.asarray(slot_ids))
        self._device_wait(logits)
        return logits

    def resume_prefill(self, tokens_list: List[np.ndarray],
                       slots: List[int], starts: List[int]) -> jax.Array:
        """Prefill only the uncached SUFFIX of a join group.

        ``tokens_list[i]`` holds request i's history tokens PAST its cached
        prefix; ``starts[i]`` is the absolute cache position of the first
        suffix token (= prefix length in positions, profile included).  The
        target slots must already hold the prefix K/V (``prefix_copy_insert``).
        Same bucketing/padding contract as ``prefill_insert``; returns
        full-bucket next-token logits.
        """
        with span("serve.stage"):
            tok, lengths, src = self._pad_group(tokens_list)
            start_arr = np.asarray([starts[j] for j in src], np.int32)
            slot_ids = np.asarray([slots[j] for j in src], np.int32)
            if self.paged:
                t = tok.shape[1]
                logical = (start_arr[:, None].astype(INDEX_DTYPE)
                           + np.arange(t, dtype=INDEX_DTYPE)[None, :])
                valid = (np.arange(t, dtype=INDEX_DTYPE)[None, :]
                         < as_index(lengths)[:, None])
                psc = self._scatter_indices(slot_ids, logical, valid)
                pgi = self._gather_indices(slot_ids)
                logits, self.cache = self._resume_prefill_paged(
                    self.params, self.cache, jnp.asarray(tok),
                    jnp.asarray(lengths), jnp.asarray(start_arr),
                    jnp.asarray(psc), jnp.asarray(pgi))
            else:
                logits, self.cache = self._resume_prefill(
                    self.params, self.cache, jnp.asarray(tok),
                    jnp.asarray(lengths), jnp.asarray(start_arr),
                    jnp.asarray(slot_ids))
        self._device_wait(logits)
        self.counters["resume_calls"] += 1
        return logits

    # -- prefix-store (tier 2) copies ----------------------------------------

    @staticmethod
    def _pad_ids(ids: List[int]) -> np.ndarray:
        """Bucket an id list to a power-of-two length by duplicating the
        last id (duplicate scatter/gather rows carry identical data)."""
        b = bucket_length(len(ids), 1)
        return np.asarray(ids + [ids[-1]] * (b - len(ids)), np.int32)

    def prefix_copy_insert(self, arena_rows: List[int], slots: List[int],
                           lengths: List[int]) -> None:
        """Scatter stored prefix rows into target pool slots.

        ``lengths[i]`` is prefix i's occupancy in positions (profile +
        history tokens); stored positions at or past it are masked empty.
        """
        assert self.arena is not None, "executor built without prefix_rows"
        self.cache = self._prefix_copy_insert(
            self.cache, self.arena, self._pad_ids(arena_rows),
            self._pad_ids(slots), self._pad_ids(lengths))
        # full-row device copies per prefix hit — the cost the paged
        # layout's page-table edit eliminates (see the paged_kv bench)
        self.counters["prefix_row_copies"] += len(slots)

    def prefix_save(self, slots: List[int], arena_rows: List[int]) -> None:
        """Copy freshly prefilled pool rows into arena rows (store admit)."""
        assert self.arena is not None, "executor built without prefix_rows"
        self.arena = self._prefix_save(
            self.arena, self.cache, self._pad_ids(arena_rows),
            self._pad_ids(slots))

    @property
    def arena_row_bytes(self) -> int:
        """Device bytes one arena row (= one cached prefix) occupies,
        computed from the ACTUAL buffer dtypes — fp8 K/V payload plus its
        f32 scale leaves, not an assumed bf16 itemsize — so the
        ``PrefixStore`` byte budget, ``bytes_pinned`` accounting, and
        eviction thresholds mean real bytes for any KV dtype.

        Under the paged layout there is no arena: a stored prefix is page
        references, so the store's per-row price IS the page price."""
        if self.paged:
            return self.page_bytes
        if self.arena is None:
            return 0
        total = sum(leaf.nbytes
                    for leaf in jax.tree_util.tree_leaves(self.arena))
        return total // self.prefix_rows

    @property
    def page_bytes(self) -> int:
        """Device bytes one page occupies across every layer leaf (K/V
        payload + pos lane + any fp8 scales) — the allocation/accounting
        unit of the paged layout."""
        assert self.paged, "page_bytes requires the paged layout"
        total = sum(leaf.nbytes
                    for leaf in jax.tree_util.tree_leaves(self.cache))
        return total // (self.n_pages + 1)

    @property
    def pool_row_bytes(self) -> int:
        """Device bytes one slot-pool row occupies (same dtype-honest
        accounting as ``arena_row_bytes``).  Under the paged layout this
        is the WORST-CASE footprint (a full page table); real usage is
        per-request pages, which is the whole point."""
        if self.paged:
            return self._p_max * self.page_bytes
        total = sum(leaf.nbytes
                    for leaf in jax.tree_util.tree_leaves(self.cache))
        return total // self.n_slots

    @property
    def kv_bytes(self) -> int:
        """Total device bytes of both KV tiers (slot pool + prefix arena)."""
        trees = [self.cache] + ([self.arena] if self.arena is not None else [])
        return sum(leaf.nbytes for tree in trees
                   for leaf in jax.tree_util.tree_leaves(tree))

    def decode(self, tokens: np.ndarray, lengths: np.ndarray) -> jax.Array:
        """One decode step over the whole pool: tokens (N, 1) at per-slot
        absolute indices ``lengths`` (N,).  Inactive slots (freed rows and
        rows mid-way through a chunked prefill) pass index 0 and a dummy
        token; their cache writes are DROPPED by the program and their
        ``pos`` rows are cleared on free (``free_slot``), so dummy rows are
        a pure function of the free/active pattern and a paged prefill's
        partial row survives interleaved decode steps untouched.
        Note the dummy rows still occupy rows of the capacity-bounded MoE
        dispatch, so under a tight ``capacity_factor`` the active requests'
        outputs can differ (deterministically) from a smaller-batch run —
        the same effect batch composition has in any capacity-dropped MoE."""
        with span("serve.stage"):
            if self.paged and self.fused_decode != "off":
                rows = np.arange(self.n_slots)
                li = as_index(lengths)
                psc = self._scatter_indices(rows, li, li > 0)
                logits, vals, ids, lse, self.cache = self._decode_fused(
                    self.params, self.cache, jnp.asarray(tokens, np.int32),
                    jnp.asarray(lengths, np.int32), jnp.asarray(psc),
                    jnp.asarray(self._table_mat))
                self._fused_select = (logits, vals, ids, lse)
                self.counters["fused_decode_steps"] += 1
            elif self.paged:
                rows = np.arange(self.n_slots)
                li = as_index(lengths)
                psc = self._scatter_indices(rows, li, li > 0)
                pgi = self._gather_indices(rows)
                logits, self.cache = self._decode_paged(
                    self.params, self.cache, jnp.asarray(tokens, np.int32),
                    jnp.asarray(lengths, np.int32), jnp.asarray(psc),
                    jnp.asarray(pgi))
            else:
                logits, self.cache = self._decode(
                    self.params, self.cache, jnp.asarray(tokens, np.int32),
                    jnp.asarray(lengths, np.int32))
        self._device_wait(logits)
        self.counters["decode_steps"] += 1
        return logits

    def decode_multi(self, tokens: np.ndarray, lengths: np.ndarray,
                     starts: np.ndarray, counts: np.ndarray) -> jax.Array:
        """One TREE-decode step over the whole pool: tokens (N, C) carry C
        candidate branches per slot, all at that slot's logical depth
        ``lengths``; ``starts`` is each slot's branch-region base (= its
        prefix occupancy) and ``counts`` each slot's REAL branch width —
        writes of dummy branches (b >= counts[i], rows padded up to the
        program width) are dropped so unused spans stay empty.  Branch b
        of row i writes its K/V into the row's reserved span at
        ``starts[i] + b * branch_stride`` and attends over (shared
        prefix) + (own branch) — no prefix K/V is duplicated.  Inactive
        rows pass index 0 exactly as in ``decode``.  Returns per-branch
        logits (N, C, V)."""
        C = tokens.shape[1]
        if C > self.n_candidates:
            raise ValueError(f"{C} branches exceed the executor's "
                             f"n_candidates capacity ({self.n_candidates})")
        with span("serve.stage"):
            if self.paged:
                # branch b of row i writes logical position
                # starts[i] + b*stride + (lengths[i] - starts[i]); inactive
                # rows and dummy branches resolve to the drop index here,
                # on the host — the program itself is gating-free
                rows = np.arange(self.n_slots)
                li = as_index(lengths)[:, None]
                st = as_index(starts)[:, None]
                b = np.arange(C, dtype=INDEX_DTYPE)[None, :]
                logical = st + b * self.branch_stride + (li - st)
                valid = (li > 0) & (b < as_index(counts)[:, None])
                psc = self._scatter_indices(rows, logical, valid)
                if self.fused_decode != "off":
                    (logits, vals, ids, lse,
                     self.cache) = self._decode_multi_fused(
                        self.params, self.cache,
                        jnp.asarray(tokens, np.int32),
                        jnp.asarray(lengths, np.int32),
                        jnp.asarray(starts, np.int32), jnp.asarray(psc),
                        jnp.asarray(self._table_mat))
                    self._fused_select = (logits, vals, ids, lse)
                    self.counters["fused_decode_steps"] += 1
                else:
                    pgi = self._gather_indices(rows)
                    logits, self.cache = self._decode_multi_paged(
                        self.params, self.cache,
                        jnp.asarray(tokens, np.int32),
                        jnp.asarray(lengths, np.int32),
                        jnp.asarray(starts, np.int32), jnp.asarray(psc),
                        jnp.asarray(pgi))
            else:
                logits, self.cache = self._decode_multi(
                    self.params, self.cache, jnp.asarray(tokens, np.int32),
                    jnp.asarray(lengths, np.int32),
                    jnp.asarray(starts, np.int32),
                    jnp.asarray(counts, np.int32))
        self._device_wait(logits)
        self.counters["decode_steps"] += 1
        self.counters["decode_multi_steps"] += 1
        self.counters["branch_tokens"] += int(np.sum(counts))
        return logits

    def _device_wait(self, logits: jax.Array) -> None:
        """Block until a phase program's output is ready: the host time a
        round spends waiting on the device, outside its host work."""
        with span("serve.device_wait", self.counters):
            logits.block_until_ready()

    def select(self, logits) -> Tuple[np.ndarray, np.ndarray]:
        """Top-k over logits; returns host (vals, ids)."""
        self.counters["select_calls"] += 1
        with span("serve.select", self.counters):
            vals, ids = self._select(logits)
            # the scheduler's one sanctioned phase-boundary readback
            return np.asarray(vals), np.asarray(ids)  # lint: allow[hidden-host-sync]

    def select_scored(self, logits
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Top-k + log-partition over the last axis; returns host
        (vals, ids, logsumexp).  ``vals[..., j] - logsumexp[...]`` is the
        log-prob of candidate j — the branch-score currency of
        multi-candidate decode.  Accepts (N, V) or (N, C, V) logits (the
        branch axis is flattened for the kernel and restored).

        When ``logits`` came out of a FUSED decode step the answer was
        already computed inside that one program (``decode`` holds it,
        keyed by the logits array IDENTITY); it is read back from there
        and no select program dispatches."""
        shape = logits.shape
        if self._fused_select is not None and logits is self._fused_select[0]:
            _, vals, ids, lse = self._fused_select
            self._fused_select = None
            self.counters["fused_select_hits"] += 1
        else:
            self.counters["select_calls"] += 1
            if len(shape) > 2:
                logits = logits.reshape((-1, shape[-1]))
            vals, ids, lse = self._select_scored(logits)
        with span("serve.select", self.counters):
            # sanctioned phase-boundary readback (see select)
            vals, ids = np.asarray(vals), np.asarray(ids)  # lint: allow[hidden-host-sync]
            lse = np.asarray(lse)  # lint: allow[hidden-host-sync]
        if len(shape) > 2:
            vals = vals.reshape(shape[:-1] + (self.topk,))
            ids = ids.reshape(shape[:-1] + (self.topk,))
            lse = lse.reshape(shape[:-1])
        return vals, ids, lse

    def free_slots(self, slots: List[int]) -> None:
        """Wipe a batch of retired slots' position occupancy in ONE pos-only
        scatter program — see ``decode`` for why freed rows must read
        virgin.  The id list is padded to a power-of-two bucket (duplicates
        are benign), so retiring several requests in one engine step costs
        one dispatch, not one per slot."""
        if not slots:
            return
        if self.paged:
            # paged retire: drop the slot's page references; pages whose
            # refcount hits zero (not still held by a store entry) get
            # their pos lane cleared in one batched program
            freed: List[int] = []
            for s in dict.fromkeys(int(s) for s in slots):
                pages = self._slot_pages.pop(s, None)
                self._table_mat[s] = self._sentinel
                if pages:
                    freed += self.page_pool.release(pages)
            self._free_pages_device(freed)
            return
        self.cache = self._clear_slots(self.cache, self._pad_ids(list(slots)))

    def free_slot(self, slot: int) -> None:
        """Single-slot convenience wrapper over ``free_slots``."""
        self.free_slots([slot])
