#!/usr/bin/env python3
"""Readings that set a configuration's limits, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,.. --seconds 8 \
        [--control 1,2,3] [--witness 1,2]

For each seed it builds the cell's engine with that seed's weights, serves
a short window of the cell's own traffic at the cell's own load, samples
the finished requests as a benchmark run does, frees the engine and reads
the numbers against the family's plain reference
(``harness.reference_readings``).

``--control`` seeds also read the control on the same requests, as a run
puts it in the program's place (``harness.compare``): the reference with
the quantized weights rounded to the configuration's
``control_weight_bits``, judged by the token it puts first at each
position.
``--witness`` seeds serve the same requests again with an expert capacity
no token overflows, to show what the engine's capacity drops cost.
One JSON line per reading goes to standard output.
"""

from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def serve(family, conf, mix, seed, seconds):
    """Build, warm, serve one window; returns the sampled answers and
    stats."""
    from bench import harness, traffic

    engine = harness.build_engine(family, conf, seed)
    wl = traffic.generate(mix, family, conf["model"], seed, seconds)
    family.warm_up(engine, wl, seed)
    win = harness.Window(engine, wl, seconds, False, None)
    win.run()
    answers = harness.sample_answers(win.records, wl.requests,
                                     conf["reference"]["sample_requests"],
                                     seed)
    lat = sorted((r.done - r.due) for r in win.records if r.done is not None)
    info = {"served": len(lat), "failed": win.failed,
            "p95_ms": 1e3 * lat[int(0.95 * (len(lat) - 1))] if lat else None,
            "compiles": win.compiles}
    del engine, win
    gc.collect()
    return answers, info


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control", default="")
    ap.add_argument("--witness", default="")
    args = ap.parse_args()

    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    from bench import harness
    from repro.launch.serve import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    _, conf, mix, family = harness.load_cell(spec, args.workload)
    ints = lambda s: [int(x) for x in s.split(",") if x]
    control, witness = set(ints(args.control)), set(ints(args.witness))
    for seed in ints(args.seeds):
        t0 = time.perf_counter()
        answers, info = serve(family, conf, mix, seed, args.seconds)
        out = {"workload": args.workload, "seed": seed, "kind": "program",
               **harness.reference_readings(family, conf, seed, answers),
               **info,
               "seconds": time.perf_counter() - t0}
        print(json.dumps(out), flush=True)
        if seed in control:
            logged = []
            checks = harness.compare(family, conf, seed, answers,
                                     logged.append, control=True)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "kind": "control", "checks": checks,
                              "logged": logged}), flush=True)
        if seed in witness:
            w_conf = copy.deepcopy(conf)
            # n_experts / top_k: an expert holds every token of a program,
            # so no assignment is dropped; a smaller pool keeps the larger
            # expert buffers inside the chip (the pool size changes no value)
            m = w_conf["model"]
            m["capacity_factor"] = m["n_experts"] / m["top_k"]
            w_conf["engine"]["n_pages"] = min(w_conf["engine"]["n_pages"],
                                              2000)
            w_conf["engine"]["prefix_rows"] = 100
            w_answers, w_info = serve(family, w_conf, mix, seed,
                                      args.seconds)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "kind": "witness_no_capacity_drops",
                              **harness.reference_readings(
                                  family, conf, seed, w_answers),
                              **w_info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
