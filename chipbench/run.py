#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process finds.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout on a machine with a TPU. The cell is an
entry of `BENCHMARK.json` (`chipbench/harness.py` says what a run does).
With `--trace 0` the result holds the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics from a profiled window. The last line
of standard output is one JSON object: `correct`, `attempted`, `failed`,
`metrics`, `device` (and `breakdown` when traced), then `checks`, each
number compared beside its limit; the same checks are the last lines of
standard error. Without a TPU, or with fewer chips than the cell asks
for, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU; JAX found "
                         f"{devices[0].platform!r} ({devices[0].device_kind})")

    from chipbench import harness

    cell = harness.load_cell(args.workload)
    if len(devices) < cell.chips:
        raise SystemExit(f"chipbench: {args.workload} needs {cell.chips} "
                         f"chips; JAX found {len(devices)}")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(BENCH_DIR, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    line = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                       T_START)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
