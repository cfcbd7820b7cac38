"""One run of one cell: build the served engine from the cell's
configuration, warm the shapes its traffic uses, drive ``submit``/``step``
for the window, read the metrics, and check the served tokens against the
plain reference.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration (``configs/<config>.json``) and traffic mix
(``traffic/<mix>.json``), and each metric is read by
``metrics/<metric>.py`` (or, for a split metric such as ``mfu.backlog``,
by the reader of the part before the first dot).
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
DRAIN_LIMIT_S = 60.0      # an answer later than this past the close failed


# -- finding things by name ---------------------------------------------------

def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find(items: List[dict], name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def traffic_path(bench_dir: Path, mix: str) -> Path:
    return bench_dir / "traffic" / f"{mix}.json"


def metric_reader(bench_dir: Path, name: str) -> Callable:
    """``metrics/<name>.py``, else the reader of ``name``'s part before the
    first dot; the module's ``read(ctx)`` returns a number or None."""
    for stem in (name, name.split(".")[0]):
        path = bench_dir / "metrics" / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise KeyError(f"no reader for metric {name!r} under {bench_dir}/metrics")


def cell_metrics(spec: dict, cell: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
    return [m for m in spec[kind]
            if "workloads" not in m or cell in m["workloads"]]


# -- the configuration ----------------------------------------------------------

def model_config(m: dict):
    """The engine's ``OneRecConfig`` from a configuration file's ``model``."""
    from repro.configs.base import OneRecConfig, TransformerConfig

    t_keys = {f.name for f in dataclasses.fields(TransformerConfig)}
    o_keys = {f.name for f in dataclasses.fields(OneRecConfig)}
    backbone = TransformerConfig(
        name=m["backbone_name"],
        **{k: v for k, v in m.items() if k in t_keys and k != "name"})
    return OneRecConfig(
        transformer=backbone,
        **{k: v for k, v in m.items() if k in o_keys and k != "transformer"})


def engine_config(e: dict):
    from repro.serving import EngineConfig

    return EngineConfig(
        batch_size=e["n_slots"], n_slots=e["n_slots"], use_fp8=e["use_fp8"],
        kv_dtype=e["kv_dtype"], mode="continuous", paged=e["paged"],
        page_size=e["page_size"], n_pages=e["n_pages"],
        prefix_cache=e["prefix_cache"], prefix_rows=e["prefix_rows"],
        fused_decode=e["fused_decode"])


def build_engine(conf: dict, seed: int):
    """The served engine with the benchmark's weights for ``seed``: bf16
    leaves go in as they are; under a quantizing policy the program's
    leaf-at-a-time builder quantizes them (the bf16 tree and its fp8 copy
    do not fit one chip together)."""
    import jax

    from repro.core.ptq import build_quantized_params
    from repro.serving import ServingEngine
    from repro.serving.engine import resolve_quant_policy
    from bench import weights

    cfg = model_config(conf["model"])
    ecfg = engine_config(conf["engine"])
    policy, _ = resolve_quant_policy(ecfg)
    key = weights.root_key(seed)
    init = lambda k: weights.init_params(k, conf["model"])
    if policy.enabled:
        params = build_quantized_params(init, key, policy)
    else:
        params = jax.jit(init)(key)
    engine = ServingEngine(params, cfg, ecfg)
    jax.block_until_ready((engine.executor.params, engine.executor.cache))
    return engine


# -- warm-up ------------------------------------------------------------------

def warm_up(engine, workload, seed: int, log=lambda s: None) -> int:
    """Serve, for every prefill length bucket the traffic's histories fall
    in, one group of each batch bucket (1, 2, 4 .. slots), so the window
    finds every prefill, select, decode and page-release program compiled.
    Returns the number of warm-up requests served."""
    from repro.serving.executor import bucket_length

    cfg, ex = engine.cfg, engine.executor
    ncb = cfg.n_codebooks
    cap = cfg.history_len * ncb
    rep: Dict[int, int] = {}
    for items in workload.history_items(ncb):
        b = min(bucket_length(items * ncb, ex.prefill_bucket_min), cap)
        rep[b] = max(rep.get(b, 0), items)
    rng = np.random.default_rng(seed + 1)
    vocab = cfg.codebook_size
    served = 0
    for items in sorted(rep.values()):
        t0 = time.perf_counter()
        b = 1
        while b <= engine.n_slots:
            for _ in range(b):
                engine.submit({"tokens": rng.integers(0, vocab,
                                                      size=items * ncb),
                               "profile": rng.normal(size=64)})
            engine.drain()
            served += b
            b *= 2
        log(f"[warm-up] {items}-item histories, groups of 1..{b // 2}: "
            f"{time.perf_counter() - t0:.3f} s")
    if "sessions" in workload.mix and engine.prefix_store is not None:
        # returning users resume from a stored prefix: one resume group of
        # each batch bucket (their suffix of a few items is one bucket)
        grow = workload.mix["sessions"]["grow_items"][1]
        base = min(32, cfg.history_len - grow) * ncb
        grow *= ncb
        b = 1
        while b <= engine.n_slots:
            hist = [rng.integers(0, vocab, size=base) for _ in range(b)]
            prof = [rng.normal(size=64) for _ in range(b)]
            for extra in (0, grow):
                for h, p in zip(hist, prof):
                    engine.submit({"tokens": np.concatenate(
                        [h, rng.integers(0, vocab, size=extra)])
                        if extra else h, "profile": p})
                engine.drain()
            served += 2 * b
            b *= 2
    if ex.paged:
        # retiring requests and evicting stored prefixes release pages in
        # batches of any size: compile each release bucket (the sentinel
        # page is already virgin, so the writes change nothing)
        most = bucket_length(engine.n_slots * ex._p_max, 1)
        b = 1
        while b <= most:
            ex._free_pages_device([ex._sentinel] * b)
            b *= 2
    engine.reset_window()
    return served


# -- the window -----------------------------------------------------------------

@dataclasses.dataclass
class Record:
    due: float                      # due time, s from window start
    submit: Optional[float] = None
    done: Optional[float] = None
    item: Optional[np.ndarray] = None


class Recorder:
    """Host records of the prefill and decode calls the engine makes while
    the traced window is open (real tokens per row, decode positions)."""

    def __init__(self, executor):
        self.on = False
        self.calls: List[dict] = []
        for name, describe in (("prefill_insert", self._prefill),
                               ("resume_prefill", self._resume),
                               ("decode", self._decode)):
            setattr(executor, name, self._wrap(getattr(executor, name),
                                               describe))

    def _wrap(self, fn, describe):
        def inner(*args, **kw):
            if self.on:
                self.calls.append(describe(*args, **kw))
            return fn(*args, **kw)
        return inner

    @staticmethod
    def _prefill(tokens_list, profiles, slots):
        return {"kind": "prefill",
                "tokens": [len(t) + 1 for t in tokens_list],
                "starts": [0] * len(tokens_list)}

    @staticmethod
    def _resume(tokens_list, slots, starts):
        return {"kind": "resume", "tokens": [len(t) for t in tokens_list],
                "starts": [int(s) for s in starts]}

    @staticmethod
    def _decode(tokens, lengths):
        return {"kind": "decode",
                "positions": [int(p) for p in np.asarray(lengths) if p > 0]}


class Window:
    """Drives the engine for ``seconds``: open loop (each request sent at
    its due time; latency from due time to item) or closed loop (a
    completion sends the next request).  Host spans go into the profiler's
    trace when one is running."""

    def __init__(self, engine, workload, seconds: float, tracing: bool,
                 recorder: Optional[Recorder]):
        import jax

        self.engine = engine
        self.wl = workload
        self.seconds = seconds
        self.tracing = tracing
        self.recorder = recorder
        self.span = jax.profiler.TraceAnnotation
        self.records: List[Record] = []
        self.failed = 0
        self.rid: Dict[int, int] = {}
        self.stats_at_close: Optional[dict] = None
        self.compiles = 0

    def _submit(self, i: int, now: float) -> None:
        rec = self.records[i]
        from repro.serving.engine import AdmissionFull

        try:
            with self.span("bench.submit"):
                h = self.engine.submit(self.wl.requests[i])
        except (AdmissionFull, ValueError) as e:  # refused: counts failed
            print(f"[window] request {i} refused: {e}", file=sys.stderr)
            self.failed += 1
            return
        rec.submit = now
        self.rid[h.rid] = i

    def _step(self, t0: float) -> List[int]:
        with self.span("bench.step"):
            done = self.engine.step()
        t = time.perf_counter() - t0
        out = []
        for c in done:
            i = self.rid.pop(c.rid)
            self.records[i].done = t
            self.records[i].item = np.asarray(c.item)
            out.append(i)
        return out

    def run(self) -> None:
        import jax

        from repro.analysis.guards import CompileMonitor

        self.trace_dir = None
        if self.tracing:
            self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self.trace_dir)
        self.outer = self.span("bench.window")
        self.outer.__enter__()
        if self.recorder:
            self.recorder.on = True
        with CompileMonitor() as mon:
            t0 = time.perf_counter()
            if self.wl.closed:
                self._closed(t0)
            else:
                self._open(t0)
        self.compiles = mon.compiles

    def _close(self) -> None:
        """The window's time is up: stop recording and tracing; what was
        due is still served to the end."""
        import jax

        if self.recorder:
            self.recorder.on = False
        self.stats_at_close = self.engine.stats()
        self.outer.__exit__(None, None, None)
        if self.tracing:
            jax.profiler.stop_trace()

    def _open(self, t0: float) -> None:
        due, n = self.wl.due_s, len(self.wl.due_s)
        self.records = [Record(due=d) for d in due]
        i, closed = 0, False
        while True:
            now = time.perf_counter() - t0
            if not closed and now >= self.seconds:
                self._close()
                closed = True
            if closed and now > self.seconds + DRAIN_LIMIT_S:
                break
            while i < n and due[i] <= now:
                self._submit(i, now)
                i += 1
            if self.engine.busy:
                self._step(t0)
            elif i < n:
                with self.span("bench.wait"):
                    time.sleep(max(0.0, min(due[i], self.seconds)
                                   - (time.perf_counter() - t0)))
            elif closed:
                break
            else:
                with self.span("bench.wait"):
                    time.sleep(max(0.0, self.seconds
                                   - (time.perf_counter() - t0)))
        self.failed += sum(1 for r in self.records
                           if r.submit is not None and r.done is None)

    def _closed(self, t0: float) -> None:
        n, k = len(self.wl.requests), self.wl.mix["outstanding"]
        self.records = []
        closed = False

        def send():
            i = len(self.records)
            if i >= n:
                raise RuntimeError("closed-loop traffic ran out of requests;"
                                   " raise the mix's max_items_per_s")
            now = time.perf_counter() - t0
            self.records.append(Record(due=now))
            self._submit(i, now)

        for _ in range(k):
            send()
        while True:
            now = time.perf_counter() - t0
            if not closed and now >= self.seconds:
                self._close()
                closed = True
            if closed and (not self.engine.busy
                           or now > self.seconds + DRAIN_LIMIT_S):
                break
            finished = self._step(t0)
            if not closed:
                for _ in finished:
                    send()
        self.failed += sum(1 for r in self.records
                           if r.submit is not None and r.done is None)


# -- the run ------------------------------------------------------------------

def sample_rows(records: List[Record], requests: List[dict], n: int,
                seed: int) -> List[tuple]:
    """Up to ``n`` finished requests drawn from ``seed``, the longest
    history among them: (tokens, profile, served item)."""
    done = [i for i, r in enumerate(records) if r.item is not None]
    if not done:
        return []
    longest = max(done, key=lambda i: len(requests[i]["tokens"]))
    rest = [i for i in done if i != longest]
    rng = np.random.default_rng(seed + 2)
    pick = [longest] + list(rng.choice(rest, size=min(n - 1, len(rest)),
                                       replace=False))
    return [(requests[i]["tokens"], requests[i]["profile"], records[i].item)
            for i in pick]


def compare(conf: dict, seed: int, rows: List[tuple], log,
            control: bool = False) -> Dict[str, dict]:
    """The numbers that decide ``correct``, each with its limit (those the
    configuration's ``limits`` name); the others are logged.  With
    ``control`` the tokens judged are not the served ones but those the
    control puts first at each served position: the reference with its
    quantized weights rounded to ``control_weight_bits``."""
    from bench.reference import readings, reference_logits

    limits = conf["limits"]
    if rows:
        ref = reference_logits(conf["model"], seed, rows)
        if control:
            tokens = reference_logits(
                conf["model"], seed, rows,
                weight_bits=conf["reference"]["control_weight_bits"]
            ).argmax(-1)
        else:
            tokens = np.stack([r[2] for r in rows])
        got = readings(ref, tokens)
    else:
        got = {k: float("inf") for k in limits}
    for k, v in got.items():
        if k not in limits:
            log(f"[reference] {k} {v!r} (not held to a limit)")
    return {k: {"value": got[k], "limit": limits[k]} for k in limits}


@dataclasses.dataclass
class Context:
    """What the metric readers read."""
    model: dict
    engine: dict
    peaks: dict
    seconds: float
    setup_s: float
    build_s: float
    latencies_ms: List[float]     # every request due in the window
    items_in_window: int
    history_tokens: int           # history tokens submitted in the window
    stats: dict                   # engine stats at the close
    calls: List[dict]             # host records of the traced calls
    trace: object                 # bench.trace.Trace or None


def run_cell(spec: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, t_start: float, bench_dir: Path = BENCH,
             log=lambda s: print(s, file=sys.stderr, flush=True),
             control: bool = False) -> dict:
    """One run of one cell; returns the result object (the last line of
    standard output).  With ``control`` the served tokens are replaced, for
    the check alone, by the control's (``compare``)."""
    import jax

    from bench import peaks as peak_table
    from bench import trace as trace_mod
    from bench import traffic

    cell = find(spec["workloads"], cell_name, "workload")
    conf_entry = find(spec["configs"], cell["config"], "config")
    conf = load_json(bench_dir.parent / conf_entry["file"])
    mix = load_json(traffic_path(bench_dir, cell["traffic"]))
    devs = jax.devices()
    dev = devs[0]
    peaks = (peak_table.peaks_for(dev.device_kind)
             if dev.platform == "tpu" else {})

    t0 = time.perf_counter()
    engine = build_engine(conf, seed)
    build_s = time.perf_counter() - t0
    built = (dev.memory_stats() or {}).get("bytes_in_use")
    workload = traffic.generate(mix, conf["model"], seed, seconds)
    log(f"[setup] {cell_name}: engine built in {build_s:.3f} s, "
        f"bytes_in_use {built}")
    n_warm = warm_up(engine, workload, seed, log)
    recorder = Recorder(engine.executor) if trace else None
    window = Window(engine, workload, seconds, trace, recorder)
    setup_s = time.perf_counter() - t_start
    log(f"[setup] {cell_name}: build {build_s:.3f} s, {n_warm} warm-up "
        f"requests, set-up {setup_s:.3f} s | {len(workload.requests)} "
        f"requests generated")
    window.run()
    recs = window.records
    stats = window.stats_at_close or engine.stats()

    lateness = [r.submit - r.due for r in recs if r.submit is not None]
    mem = dev.memory_stats() or {}
    peak_bytes = int(mem.get("peak_bytes_in_use", 0))
    log(f"[window] compiles inside the window: {window.compiles}")
    log(f"[window] generator lateness p95: "
        f"{np.percentile(lateness, 95) * 1e3 if lateness else 0.0:.3f} ms "
        f"over {len(lateness)} submissions")
    log(f"[window] peak_bytes_in_use {peak_bytes} | bytes_limit "
        f"{mem.get('bytes_limit')} | pages {engine.executor.n_pages if engine.executor.paged else 0}")
    log(f"[window] device {dev.platform} {dev.device_kind} x{len(devs)}")

    if workload.closed:
        in_window = recs            # every request sent before the close
    else:
        in_window = [r for r in recs if r.due < seconds]
    latencies = [(r.done - r.due) * 1e3 if r.done is not None
                 else float("inf") for r in in_window]
    items = sum(1 for r in recs if r.done is not None and r.done < seconds)
    hist = sum(len(workload.requests[i]["tokens"])
               for i, r in enumerate(recs)
               if r.submit is not None and r.submit < seconds)

    tr = None
    if trace:
        tr = trace_mod.load(trace_mod.find_xplane(window.trace_dir))
        shutil.rmtree(window.trace_dir, ignore_errors=True)

    ctx = Context(model=conf["model"], engine=conf["engine"], peaks=peaks,
                  seconds=seconds, setup_s=setup_s, build_s=build_s,
                  latencies_ms=latencies, items_in_window=items,
                  history_tokens=hist, stats=stats,
                  calls=recorder.calls if recorder else [], trace=tr)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(spec, cell_name, kind):
        v = metric_reader(bench_dir, m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # the reference runs once the program's state is freed
    rows = sample_rows(recs, workload.requests,
                       conf["reference"]["sample_requests"], seed)
    del engine, window, recorder
    gc.collect()
    log(f"[reference] bytes_in_use once the engine is freed: "
        f"{(dev.memory_stats() or {}).get('bytes_in_use')}")
    checks = compare(conf, seed, rows, log, control)
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    answered = all(r.done is not None for r in in_window)
    result = {"correct": bool(correct and answered),
              "attempted": len(in_window),
              "failed": sum(1 for r in in_window if r.done is None),
              "metrics": metrics, "device": device}
    if tr is not None:
        device["busy_s"] = trace_mod.busy_s(tr)
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": trace_mod.top_ops(tr),
                               "idle_gaps": trace_mod.idle_gaps(tr)}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"[check] {name} {c['value']!r} limit {c['limit']!r} "
            f"({len(rows)} requests, {len(rows) * conf['model']['decode_len']}"
            f" served tokens)")
    return result
