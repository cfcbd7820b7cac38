"""A tiny copy of the benchmark for CPU tests: the reduced onerec-v2
widths, a bench directory of its own under ``tmp_path`` (the real metric
readers, tiny traffic mixes), and a spec naming a few tiny cells."""

from __future__ import annotations

import copy
import json
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"


def tiny_conf(**engine) -> dict:
    conf = json.loads((DATA / "onerec-tiny.json").read_text())
    conf["engine"].update(engine)
    return conf


def tiny_bench(tmp_path: Path, conf: dict, extra_cells=()):
    """(spec, bench_dir) of a benchmark whose cells serve ``conf``."""
    bd = tmp_path / "bench"
    shutil.copytree(BENCH / "metrics", bd / "metrics")
    (bd / "traffic").mkdir()
    (bd / "configs").mkdir()
    for mix in ("tiny-open", "tiny-closed", "tiny-revisit"):
        shutil.copy(DATA / f"{mix}.json", bd / "traffic" / f"{mix}.json")
    (bd / "configs" / "tiny.json").write_text(json.dumps(conf))
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    spec = copy.deepcopy(spec)
    spec["configs"] = [{"name": "tiny", "file": "bench/configs/tiny.json"}]
    spec["workloads"] = [
        {"name": "tiny.open", "config": "tiny", "traffic": "tiny-open",
         "chips": 1},
        {"name": "tiny.closed", "config": "tiny", "traffic": "tiny-closed",
         "chips": 1},
        {"name": "tiny.revisit", "config": "tiny",
         "traffic": "tiny-revisit", "chips": 1}] + list(extra_cells)
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.open"]
    # a closed-loop cell reports throughput and its own split metrics
    closed = {"workloads": ["tiny.closed"], "source": "host_clock"}
    spec["end_to_end"].append(dict(closed, name="items_per_s",
                                   unit="items/s", better="higher",
                                   bound=0.25))
    for name in ("slot_occupancy.backlog", "mfu.backlog",
                 "idle_share.backlog"):
        spec["per_layer"].append(dict(closed, name=name, unit="%",
                                      better="higher", layer="test",
                                      moves="items_per_s"))
    return spec, bd


def run(spec, bd, cell, seed=1, seconds=2.0, trace=False, **kw):
    from bench.harness import run_cell

    return run_cell(spec, cell, seed, seconds, trace, time.perf_counter(),
                    bench_dir=bd, log=lambda s: print(s, file=sys.stderr),
                    **kw)
