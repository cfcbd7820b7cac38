#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip from start to end.  It exits non-zero, and
prints no result, when JAX finds no TPU or fewer chips than the cell asks
for.  The last line of standard output is the result object; the numbers
that decide ``correct`` are the last lines of standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is timed from process start

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the compile cache lives at a fixed path inside the checkout
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    from bench.harness import find, run_cell

    cell = find(spec["workloads"], args.workload, "workload")
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"bench: needs {cell['chips']} TPU chip(s), JAX found "
              f"{len(devs)} {devs[0].platform!r} device(s); no result",
              file=sys.stderr)
        return 2
    from repro.launch.serve import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"[setup] compile cache {enable_compile_cache()} | jax "
          f"{jax.__version__}", file=sys.stderr, flush=True)
    result = run_cell(spec, args.workload, args.seed, args.seconds,
                      bool(args.trace), T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
