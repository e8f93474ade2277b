#!/usr/bin/env python3
"""Smoke run of Airphant's served search path on one TPU chip.

Builds an HDFS-shaped log deployment (after Loghub's HDFS_v1: 11,175,629
lines, cut to 1,000,000 for the run's time limit) on a disk blob store,
indexes it twice — one `Index` and one 16-shard `ShardedIndex` — and
serves one batch of the query-engine benchmark's mix (terms, AND/OR/NOT,
phrases) three ways:

  (a) `Frontend(SearchService(index))` — the default host path;
  (b) `SearchService.search_batch(..., impl="bitmap")` — the batched
      engine on the `intersect_batch` / `combine_batch` kernels;
  (c) `cluster.searcher(fused=True).query_batch(...)` — the cluster-fused
      combine on the `combine_cluster` kernel.

Every leg's document refs must equal `impl="sorted"` on the single index
and a brute-force scan of the corpus. Before that, each of the four
intersect kernels is compiled on the device (its text must hold a Mosaic
`tpu_custom_call`) and checked bit-exact against its jnp reference.

Usage, from the repository root on a machine with a TPU:

    python chip_smoke.py [--seed N]

It runs in this one process, exits non-zero without a result line when
JAX finds no TPU or any check fails, and prints as its last line

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}

This is a smoke run: its wall times say the path ran, they are not a
benchmark.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from typing import NoReturn

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.query_engine import _boolean_workload, _workload  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.data import make_logs_like, write_corpus  # noqa: E402
from repro.data.tokenizer import parse_words  # noqa: E402
from repro.index import (And, BuilderConfig, Index, Not, Or,  # noqa: E402
                         Phrase, Regex, Term)
from repro.kernels import intersect as ki  # noqa: E402
from repro.serving import (Frontend, FrontendConfig,  # noqa: E402
                           SearchService, ShardedIndex)
from repro.storage import BlobStoreTransport, LocalBlobStore  # noqa: E402

N_DOCS = 1_000_000        # HDFS_v1 has 11,175,629 lines
N_SHARDS = 16
DATA_DIR = os.path.join(ROOT, ".smoke_data")


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> NoReturn:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


class Compiles:
    """Backend compilations (persistent-cache hits included) and their
    seconds, from JAX's monitoring events."""

    def __init__(self) -> None:
        self.n = self.hits = 0
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> tuple[int, int, float]:
        return self.n, self.hits, self.secs

    def since(self, snap: tuple[int, int, float]) -> str:
        n, hits, secs = snap
        return (f"compiles={self.n - n} cache_hits={self.hits - hits} "
                f"compile_s={self.secs - secs:.3f}")


# --------------------------------------------------------------- kernels
def _random_bitmaps(rng, shape) -> np.ndarray:
    """Dense-ish uint32 bitsets (each bit set with p = 7/8), so a 16-way
    AND still leaves survivors to count."""
    r = lambda: rng.integers(0, 1 << 32, shape, dtype=np.uint32)  # noqa: E731
    return r() | r() | r()


def _random_programs(rng, n: int, n_layers: int) -> np.ndarray:
    progs = []
    for _ in range(n):
        steps = []
        for s in range(int(rng.integers(0, n_layers))):
            op = int(rng.choice([ki.OP_AND, ki.OP_OR, ki.OP_ANDNOT]))
            a = n_layers + s - 1 if s else int(rng.integers(0, n_layers))
            steps.append((op, a, int(rng.integers(0, n_layers + s))))
        progs.append(steps)
    return ki.pack_programs(progs, n_layers)


def check_kernels(seed: int) -> None:
    """Compile each intersect op on the chip at a 1M-document width and
    compare it bit-exact with its jnp reference."""
    if ki.interpret_mode():
        fail("intersect kernels would run interpreted on this backend")
    rng = np.random.default_rng(seed)
    W, Q, L, G = (N_DOCS + 31) // 32, 64, 16, 16
    bm = _random_bitmaps(rng, (Q, L, W))
    progs = _random_programs(rng, Q, L)
    cl_bm = _random_bitmaps(rng, (G, 8, 4, W))
    cl_progs = _random_programs(rng, G * 8, 4).reshape(G, 8, -1, 3)
    cases = {
        "intersect": (ki.intersect, (bm[0],)),
        "intersect_batch": (ki.intersect_batch, (bm,)),
        "combine_batch": (ki.combine_batch, (bm, progs)),
        "combine_cluster": (ki.combine_cluster, (cl_bm, cl_progs)),
    }
    for name, (op, args) in cases.items():
        compiled = jax.jit(op).lower(*args).compile()
        mosaic = "tpu_custom_call" in compiled.as_text()
        got = compiled(*args)
        want = op(*args, impl="ref")
        exact = all(bool(jnp.array_equal(g, w)) for g, w in zip(got, want))
        log(f"kernel {name}: shapes={[a.shape for a in args]} "
            f"tpu_custom_call={mosaic} bit_exact_vs_ref={exact}")
        if not (mosaic and exact):
            fail(f"kernel {name} did not run compiled and exact")


# ------------------------------------------------------------ deployment
def _has_regex(q) -> bool:
    if isinstance(q, Regex):
        return True
    if isinstance(q, (And, Or)):
        return any(_has_regex(s) for s in q.items)
    return isinstance(q, Not) and _has_regex(q.item)


def corpus_postings(docs: list[str]) -> dict[str, set[int]]:
    """word → doc ids, by scanning every document's tokens."""
    post: dict[str, set[int]] = {}
    for i, doc in enumerate(docs):
        for w in set(parse_words(doc)):
            post.setdefault(w, set()).add(i)
    return post


def _phrase_at(toks: list[str], words: tuple[str, ...], slop: int) -> bool:
    """In-order occurrence of `words` with at most `slop` extra tokens."""
    for s, t in enumerate(toks):
        if t != words[0]:
            continue
        i, good = s, True
        for w in words[1:]:
            nxt = [j for j in range(i + 1, len(toks)) if toks[j] == w]
            if not nxt:
                good = False
                break
            i = nxt[0]
        if good and (i - s + 1) - len(words) <= slop:
            return True
    return False


def oracle(q, post: dict, docs: list[str]) -> set[int]:
    """Documents matching `q`, by set algebra over the scanned postings
    and a token scan of each candidate for phrases."""
    if isinstance(q, Term):
        return post.get(q.word, set())
    if isinstance(q, Phrase):
        cand = set.intersection(*(post.get(w, set()) for w in q.words))
        return {i for i in cand
                if _phrase_at(parse_words(docs[i]), q.words, q.slop)}
    if isinstance(q, Or):
        return set().union(*(oracle(s, post, docs) for s in q.items))
    assert isinstance(q, And), q
    pos = [s for s in q.items if not isinstance(s, Not)]
    out = set.intersection(*(oracle(s, post, docs) for s in pos))
    for s in q.items:
        if isinstance(s, Not):
            out = out - oracle(s.item, post, docs)
    return out


def build(seed: int, n_docs: int, n_shards: int):
    shutil.rmtree(DATA_DIR, ignore_errors=True)
    store = LocalBlobStore(DATA_DIR)
    t0 = time.perf_counter()
    docs = make_logs_like(n_docs, seed=seed)
    corpus = write_corpus(store, "corpus/hdfs", docs, n_blobs=n_shards)
    cfg = BuilderConfig(F0=1.0, seed=seed)
    Index.build(corpus, cfg, store, "index/hdfs")
    t1 = time.perf_counter()
    ShardedIndex.build(corpus, cfg, store, "cluster/hdfs", n_shards=n_shards)
    t2 = time.perf_counter()
    log(f"deployment: hdfs-like logs n_docs={n_docs} "
        f"index_build_s={t1 - t0:.3f} sharded_build_s={t2 - t1:.3f} "
        f"(n_shards={n_shards})")
    return store, docs, corpus


def serve_and_check(store, docs, corpus, compiles: Compiles) -> None:
    post = corpus_postings(docs)
    queries = [q for q in _workload(post)
               + [q for _label, q in _boolean_workload(post, docs)]
               if not _has_regex(q)]
    if len(queries) < 64:
        fail(f"only {len(queries)} queries in the batch")
    expect = [{corpus.refs[i] for i in oracle(q, post, docs)}
              for q in queries]
    del post

    svc = SearchService(BlobStoreTransport(store), "index/hdfs")
    reference = [r.refs for r in svc.search_batch(queries, impl="sorted")]
    for q, refs, want in zip(queries, reference, expect):
        if len(refs) != len(want) or set(refs) != want:
            fail(f"impl='sorted' differs from the corpus scan on {q}")
    n_refs = sum(len(r) for r in reference)
    log(f"reference impl=sorted: queries={len(queries)} refs={n_refs} "
        "identical_to_corpus_scan=True")

    def leg(name: str, run) -> None:
        snap = compiles.snapshot()
        t0 = time.perf_counter()
        got = [r.refs for r in run()]
        wall = time.perf_counter() - t0
        same = got == reference
        log(f"leg {name}: queries={len(queries)} wall_s={wall:.3f} "
            f"{compiles.since(snap)} identical_to_sorted_and_scan={same}")
        if not same:
            fail(f"leg {name} returned different document refs")

    def frontend():
        fe = Frontend(svc, FrontendConfig(max_queue=len(queries),
                                          max_batch=64)).start()
        futures = [fe.submit(q) for q in queries]
        results = [f.result() for f in futures]
        fe.close()
        return results

    leg("a frontend(sorted)", frontend)
    leg("b search_batch(bitmap)",
        lambda: svc.search_batch(queries, impl="bitmap"))
    svc.close()

    cluster = ShardedIndex.open(BlobStoreTransport(store), "cluster/hdfs")
    searcher = cluster.searcher(fused=True)
    leg("c cluster(fused)", lambda: searcher.query_batch(queries))
    searcher.close()
    cluster.close()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the corpus and the kernel inputs")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{dev.platform!r} ({dev.device_kind})")
    count = len(jax.devices())
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={count}")
    log(f"compile cache: {enable_compile_cache()}")
    compiles = Compiles()
    t0 = time.perf_counter()

    snap = compiles.snapshot()
    check_kernels(args.seed)
    log(f"kernels: {compiles.since(snap)}")
    try:
        store, docs, corpus = build(args.seed, N_DOCS, N_SHARDS)
        serve_and_check(store, docs, corpus, compiles)
    finally:
        shutil.rmtree(DATA_DIR, ignore_errors=True)
    log(f"total: wall_s={time.perf_counter() - t0:.3f} "
        f"{compiles.since((0, 0, 0.0))}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))


if __name__ == "__main__":
    main()
