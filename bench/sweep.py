#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest Poisson rate the engine
sustains without a growing backlog, on the chip, in one process.

    python3 bench/sweep.py --workload <cell> --rates 12,14,16 --seconds 30

Each rate, lowest first, is served by an engine built and warmed afresh
for it, as in a benchmark run, over a window of the cell's own mix.  A
rate is sustained when the second half of its window completes at least
95% of the requests that fell due one median latency earlier (a queue
that grows through the window completes requests at the engine's own,
lower rate).  The knee is the last rate sustained before the first that
is not.  One line per rate goes to standard output, then the knee and 0.8
of it, the rate an open-loop cell is set to.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
KEEPS_UP = 0.95   # share of the offered rate the second half must complete
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    import jax
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    from bench import harness, traffic
    from repro.launch.serve import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    _, conf, mix, family = harness.load_cell(spec, args.workload)
    rates = [float(r) for r in args.rates.split(",")]
    knee, broken = None, False
    for i, rate in enumerate(sorted(rates)):
        # a fresh engine for each rate, as in a benchmark run: the prefix
        # store starts with the warm-up's histories only
        engine = harness.build_engine(family, conf, args.seed + i)
        wl = traffic.generate(dict(mix, rate_per_s=rate), family,
                              conf["model"], args.seed + i, args.seconds)
        family.warm_up(engine, wl, args.seed + i)
        win = harness.Window(engine, wl, args.seconds, False, None)
        win.run()
        recs = win.records
        lat = np.asarray([(r.done - r.due) * 1e3 for r in recs
                          if r.done is not None])
        half = args.seconds / 2
        backlog = sum(1 for r in recs if r.submit is not None
                      and (r.done is None or r.done > args.seconds))
        late_done = sum(1 for r in recs if r.done is not None
                        and half < r.done <= args.seconds)
        # the engine keeps up when the second half of the window completes
        # as many requests as fell due half a window earlier, shifted by
        # the median latency (a queue that grows completes them at the
        # engine's own rate, below the offered one)
        lag = float(np.percentile(lat, 50)) / 1e3
        due = sum(1 for r in recs if half - lag < r.due <= args.seconds - lag)
        keeps_up = late_done / max(due, 1)
        ok = keeps_up >= KEEPS_UP and win.failed == 0
        broken = broken or not ok
        if not broken:
            knee = rate
        print(json.dumps({
            "workload": args.workload, "rate_per_s": rate,
            "offered": len(recs), "done_second_half_per_s": late_done / half,
            "keeps_up": keeps_up,
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "backlog_at_close": backlog, "compiles": win.compiles,
            "sustained": ok}), flush=True)
        del engine, win
        gc.collect()
    print(json.dumps({"workload": args.workload, "knee_per_s": knee,
                      "cell_rate_per_s": None if knee is None
                      else 0.8 * knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
