"""One run of one cell: build the served engine from the cell's
configuration, warm the shapes its traffic uses, drive ``submit``/``step``
for the window, read the metrics, and check the served tokens against the
plain reference.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration (``configs/<config>.json``) and traffic mix
(``traffic/<mix>.json``); the configuration names its model family
(``"family"``, ``onerec`` where it names none), whose module
``families/<family>.py`` holds all that is particular to the model (see
``families/onerec.py``); and each metric is read by
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
from types import ModuleType
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
DRAIN_LIMIT_S = 60.0      # an answer later than this past the close failed
DEFAULT_FAMILY = "onerec"


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


def _load(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(bench_dir: Path, name: str) -> Callable:
    """``metrics/<name>.py``, else the reader of ``name``'s part before the
    first dot; the module's ``read(ctx)`` returns a number or None."""
    for stem in (name, name.split(".")[0]):
        path = bench_dir / "metrics" / f"{stem}.py"
        if path.exists():
            return _load(path, f"bench_metric_{stem.replace('.', '_')}").read
    raise KeyError(f"no reader for metric {name!r} under {bench_dir}/metrics")


def load_family(bench_dir: Path, name: str) -> ModuleType:
    """``families/<name>.py``: the model family's engine, requests,
    warm-up, call records, reference and costs."""
    path = bench_dir / "families" / f"{name}.py"
    if not path.exists():
        raise KeyError(f"no family {name!r} under {bench_dir}/families")
    return _load(path, f"bench_family_{name}")


def load_cell(spec: dict, cell_name: str, bench_dir: Path = BENCH):
    """(cell, configuration, traffic mix, family module) of a cell."""
    cell = find(spec["workloads"], cell_name, "workload")
    entry = find(spec["configs"], cell["config"], "config")
    conf = load_json(bench_dir.parent / entry["file"])
    mix = load_json(traffic_path(bench_dir, cell["traffic"]))
    family = load_family(bench_dir, conf.get("family", DEFAULT_FAMILY))
    return cell, conf, mix, family


def cell_metrics(spec: dict, cell: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
    return [m for m in spec[kind]
            if "workloads" not in m or cell in m["workloads"]]


# -- the engine ---------------------------------------------------------------

def build_engine(family: ModuleType, conf: dict, seed: int):
    """The served engine of ``conf`` with the weights of ``seed``, as the
    family builds it (the one place a run builds it)."""
    return family.build_engine(conf, seed)


# -- the window ---------------------------------------------------------------

@dataclasses.dataclass
class Record:
    due: float                      # due time, s from window start
    submit: Optional[float] = None
    done: Optional[float] = None
    item: Optional[np.ndarray] = None


class Recorder:
    """Host records of the prefill and decode calls the engine makes while
    the traced window is open (real tokens per row, decode positions):
    ``calls`` maps an executor method to the family's record of a call."""

    def __init__(self, executor, calls: Dict[str, Callable]):
        self.on = False
        self.calls: List[dict] = []
        for name, describe in calls.items():
            setattr(executor, name, self._wrap(getattr(executor, name),
                                               describe))

    def _wrap(self, fn, describe):
        def inner(*args, **kw):
            if self.on:
                self.calls.append(describe(*args, **kw))
            return fn(*args, **kw)
        return inner


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

def sample_answers(records: List[Record], requests: List[dict], n: int,
                   seed: int) -> List[tuple]:
    """Up to ``n`` finished requests drawn from ``seed``, the longest
    prompt among them: (request, served answer)."""
    done = [i for i, r in enumerate(records) if r.item is not None]
    if not done:
        return []
    longest = max(done, key=lambda i: len(requests[i]["tokens"]))
    rest = [i for i in done if i != longest]
    rng = np.random.default_rng(seed + 2)
    pick = [longest] + list(rng.choice(rest, size=min(n - 1, len(rest)),
                                       replace=False))
    return [(requests[i], records[i].item) for i in pick]


def reference_readings(family: ModuleType, conf: dict, seed: int,
                       answers: List[tuple],
                       control: bool = False) -> Dict[str, float]:
    """``reference.readings`` of the served tokens of ``answers`` against
    the family's plain reference.  With ``control`` the tokens judged are
    not the served ones but those the control puts first at each served
    position: the reference with its quantized weights rounded to
    ``control_weight_bits``."""
    from bench.reference import readings

    rows = [family.reference_row(req, item) for req, item in answers]
    ref = family.reference_logits(conf["model"], seed, rows)
    if control:
        tokens = family.reference_logits(
            conf["model"], seed, rows,
            weight_bits=conf["reference"]["control_weight_bits"]).argmax(-1)
    else:
        tokens = np.stack([family.answer_tokens(item)
                           for _, item in answers])
    return readings(ref, tokens)


def compare(family: ModuleType, conf: dict, seed: int, answers: List[tuple],
            log, control: bool = False) -> Dict[str, dict]:
    """The numbers that decide ``correct``, each with its limit (those the
    configuration's ``limits`` name); the others are logged."""
    limits = conf["limits"]
    if answers:
        got = reference_readings(family, conf, seed, answers, control)
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
    history_tokens: int           # prompt tokens submitted in the window
    stats: dict                   # engine stats at the close
    calls: List[dict]             # host records of the traced calls
    trace: object                 # bench.trace.Trace or None
    costs: ModuleType             # the family: prefill_flops, decode_flops,
                                  # paged_decode_cost of ``model``


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

    _, conf, mix, family = load_cell(spec, cell_name, bench_dir)
    devs = jax.devices()
    dev = devs[0]
    peaks = (peak_table.peaks_for(dev.device_kind)
             if dev.platform == "tpu" else {})

    t0 = time.perf_counter()
    engine = build_engine(family, conf, seed)
    build_s = time.perf_counter() - t0
    built = (dev.memory_stats() or {}).get("bytes_in_use")
    workload = traffic.generate(mix, family, conf["model"], seed, seconds)
    log(f"[setup] {cell_name}: engine built in {build_s:.3f} s, "
        f"bytes_in_use {built}")
    n_warm = family.warm_up(engine, workload, seed, log)
    recorder = Recorder(engine.executor, family.CALLS) if trace else None
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
                  calls=recorder.calls if recorder else [], trace=tr,
                  costs=family)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(spec, cell_name, kind):
        v = metric_reader(bench_dir, m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # the reference runs once the program's state is freed
    answers = sample_answers(recs, workload.requests,
                             conf["reference"]["sample_requests"], seed)
    del engine, window, recorder
    gc.collect()
    log(f"[reference] bytes_in_use once the engine is freed: "
        f"{(dev.memory_stats() or {}).get('bytes_in_use')}")
    checks = compare(family, conf, seed, answers, log, control)
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
    n_tokens = sum(np.size(family.answer_tokens(item))
                   for _, item in answers)
    for name, c in checks.items():
        log(f"[check] {name} {c['value']!r} limit {c['limit']!r} "
            f"({len(answers)} requests, {n_tokens} served tokens)")
    return result
