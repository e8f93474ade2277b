#!/usr/bin/env python3
"""Find a cell's knee: one deployment, one process, rising offered rates.

    python chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 1 2 4 8

on a machine with a TPU. Each rate drives one window of the cell's
traffic at that rate, with the persistent compilation cache off as in a
run, and prints one JSON line: offered and completed rate, requests
refused, requests still open when the window closed, mean batch size,
p50 and p95. The knee is the highest rate at which the completed rate
keeps up with the offered rate and requests do not pile up across the
window. The benchmark's own runs do not call this; its result is written
as a number into the cell's mix file.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--detail", default=None,
                    help="also write one JSON line per request here")
    args = ap.parse_args()

    import jax
    import numpy as np

    from chipbench import harness

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("sweep: needs a TPU")
    cell = harness.load_cell(args.workload)
    session = harness.Session(cell, args.seed)
    harness.log(f"setup {session.shares}")
    harness.set_persistent_cache(False)
    try:
        for i, rate in enumerate(args.rates):
            mix = {**cell.mix, "rate_qps": rate}
            requests = session.requests(harness.WINDOW + 1 + i,
                                        args.seconds, mix)
            session.reset_counts()
            n0, secs0 = session.compiles.n, session.compiles.secs
            outcomes = session.drive(requests, args.seconds)
            close = outcomes[0].due + args.seconds
            done = [o for o in outcomes if o.answered]
            lat = np.array([o.done - o.due for o in done]) * 1e3
            stats = session.frontend.stats
            spans = session.backend.spans
            completed_qps = sum(o.done <= close for o in done) / args.seconds
            print(json.dumps({
                "rate_qps": rate,
                "requests": len(outcomes),
                "completed_qps": completed_qps,
                "refused": sum(o.refused for o in outcomes),
                "failed": len(outcomes) - len(done),
                "open_at_close": sum(o.done is None or o.done > close
                                     for o in outcomes if not o.refused),
                "mean_batch": float(np.mean(stats.batch_sizes))
                if stats.batch_sizes else 0.0,
                "p50_ms": float(np.percentile(lat, 50)) if len(lat) else None,
                "p95_ms": float(np.percentile(lat, 95)) if len(lat) else None,
                "compiles": session.compiles.n - n0,
                "compile_s": session.compiles.secs - secs0,
                "batch_ms": float(np.mean([s.end - s.start for s in spans]))
                * 1e3 if spans else 0.0,
                "batches": len(spans),
            }), flush=True)
            if args.detail:
                with open(args.detail, "a") as f:
                    for o in outcomes:
                        f.write(json.dumps({
                            "rate_qps": rate, "text": o.request.text,
                            "due": o.due - outcomes[0].due,
                            "latency_ms": (o.done - o.due) * 1e3
                            if o.answered else None}) + "\n")
            if len(done) < len(outcomes) or completed_qps < 0.7 * rate:
                break            # past the knee: a backlog would spill over
    finally:
        session.close()
        shutil.rmtree(harness.DATA_DIR, ignore_errors=True)


if __name__ == "__main__":
    main()
