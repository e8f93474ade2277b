#!/usr/bin/env python3
"""The control of `correct`: the reference in the program's place, with
the guarantee that every returned document matches its query broken.

    python chipbench/control.py --workload <cell> --seeds 1 2 3 --seconds <s>

runs, for each seed, a whole run of the cell at its own size and load
with the served path replaced by `serve_control`: the reference's scan
answering each request from its unverified candidates (phrases as the
AND of their words, negations dropped), which is what skipping round-2
verification would serve. Nothing is built. Each run prints its result
line; `correct` has to come out false. The benchmark's own runs never
call this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import zlib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from repro.index import QueryResult, QueryStats  # noqa: E402


def serve_control(session):
    """(call, close) answering from the reference without verification;
    a top-K answer is a sample of the candidates, seeded by the text."""
    ref, refs, docs = session.ref, session.refs, session.docs

    def answer(text: str, top_k):
        cand = ref.matches(session.trees[text], verified=False)
        if top_k is not None:
            rng = np.random.default_rng(zlib.crc32(text.encode()))
            cand = np.sort(rng.permutation(cand)[:top_k])
        return QueryResult(refs=[refs[i] for i in cand],
                           texts=[docs[i] for i in cand], stats=QueryStats())

    def call(texts, top_k):
        return [answer(t, top_k) for t in texts]

    return call, None


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()

    from chipbench import harness

    cell = harness.load_cell(args.workload)
    for seed in args.seeds:
        line = harness.run(cell, seed, args.seconds, False,
                           time.perf_counter(), serve=serve_control,
                           build=False)
        print(json.dumps({"seed": seed, **line}), flush=True)


if __name__ == "__main__":
    main()
