"""The kernel wrappers' shape policy (`kernels/intersect/ops.py`).

`combine_batch` and `combine_cluster` pad every batch to its launch
class — Q a power of two, L and S powers of two of at
least 4, W the class of the index unit's document count — and cut the
result back to the batch's own rows and words. Here: each wrapper's
padded result equals the unpadded reference; a stream of varied served
batches reaches only the Q classes at the kernels' entry points; and
the warm step launches exactly those classes, when a service opens its
searcher on a backend that compiles.
"""

import numpy as np
import pytest

from repro.data import make_logs_like, write_corpus
from repro.index import BuilderConfig, Index
from repro.kernels.intersect import (OP_AND, OP_ANDNOT, OP_OR,
                                     combine_batch, combine_cluster,
                                     launch_class, unit_words,
                                     warm_shape_classes)
from repro.kernels.intersect import ops
from repro.serving import (Frontend, FrontendConfig, SearchService,
                           ShardedIndex)
from repro.serving import search_service
from repro.storage import InMemoryBlobStore

Q_CLASSES = [1, 2, 4, 8, 16]
CFG = BuilderConfig(B=2000, F0=1.0, seed=5)


def _bitmaps(rng, lead, W):
    """Random words, with the bits past a W-word universe's last key
    left as the build leaves them: set only where a key lies."""
    return rng.integers(0, 1 << 32, (*lead, W), dtype=np.uint64) \
        .astype(np.uint32)


def _programs(rng, n, L, S):
    """n random well-formed programs of exactly S steps over L layers."""
    out = np.empty((n, S, 3), np.int32)
    for q in range(n):
        for s in range(S):
            hi = L + s
            a = L + s - 1 if s else int(rng.integers(0, hi))
            out[q, s] = (int(rng.choice([OP_AND, OP_OR, OP_ANDNOT])), a,
                         int(rng.integers(0, hi)))
    return out


# (Q, L, S, W, n_docs): ragged Q, L and S; W below, at and (a unit
# narrower than the batch) past the unit's bound; L = 1
CASES = [(1, 1, 1, 3, 2000), (3, 2, 3, 63, 2000), (5, 3, 2, 1250, 40_000),
         (2, 1, 5, 1024, 32_768), (6, 5, 6, 40, 1280), (9, 4, 4, 700, 100)]


def _and_chains(n, L, S):
    """n AND chains of exactly S steps over L layers: every layer ANDed
    in (S >= L - 1 in every case), the rest ANDing layers in again."""
    chain = [(OP_AND, L + s - 1 if s else 0, (s + 1) % L) for s in range(S)]
    return np.array([chain] * n, np.int32)


@pytest.mark.parametrize("program", ["random", "and_chain"])
@pytest.mark.parametrize("Q,L,S,W,n_docs", CASES)
def test_combine_batch_padded_equals_ref(Q, L, S, W, n_docs, program):
    """Padded with zero layers, which no program reads: an AND chain is
    the L-way intersection, and at L = 1 its one layer."""
    if program == "random":
        rng = np.random.default_rng(Q * 100 + L * 10 + S + 1)
        bm = _bitmaps(rng, (Q, L), W)
        progs = _programs(rng, Q, L, S)
    else:
        rng = np.random.default_rng(Q * 100 + L * 10 + S)
        bm = _bitmaps(rng, (Q, L), W)
        progs = _and_chains(Q, L, S)
    out, cnt = combine_batch(bm, progs, n_docs=n_docs)
    want, want_cnt = combine_batch(bm, progs, impl="ref",
                                   n_docs=n_docs)
    assert out.shape == (Q, W) and cnt.shape == (Q,)
    np.testing.assert_array_equal(out, np.asarray(want))
    np.testing.assert_array_equal(cnt, np.asarray(want_cnt))
    if program == "and_chain":
        np.testing.assert_array_equal(out, np.bitwise_and.reduce(bm, axis=1))


@pytest.mark.parametrize("Q,L,S,W,n_docs", CASES)
def test_combine_cluster_padded_equals_ref(Q, L, S, W, n_docs):
    rng = np.random.default_rng(Q * 100 + L * 10 + S + 2)
    G = 3
    bm = _bitmaps(rng, (G, Q, L), W)
    progs = _programs(rng, G * Q, L, S).reshape(G, Q, S, 3)
    out, cnt = combine_cluster(bm, progs, n_docs=n_docs)
    want, want_cnt = combine_cluster(bm, progs, impl="ref",
                                     n_docs=n_docs)
    assert out.shape == (G, Q, W) and cnt.shape == (G, Q)
    assert out.flags["C_CONTIGUOUS"]
    np.testing.assert_array_equal(out, np.asarray(want))
    np.testing.assert_array_equal(cnt, np.asarray(want_cnt))


@pytest.mark.parametrize("n_docs,words", [(0, 1024), (31, 1024),
                                          (32_768, 1024), (32_769, 2048),
                                          (62_500, 2048),
                                          (1_000_000, 32_768)])
def test_unit_width_class(n_docs, words):
    """W comes from the unit: a power of two of at least 1,024 words."""
    assert unit_words(n_docs) == words
    assert launch_class(1, 1, 1, 1, n_docs=n_docs) == dict(
        Qc=1, Lc=4, Wc=words, Sc=4)


# ------------------------------------------------------------ served path
def _record(monkeypatch):
    """Record the operand shapes at the kernels' entry points."""
    seen = []
    for name in ("combine_batch_pallas", "combine_cluster_pallas"):
        real = getattr(ops, name)

        def entry(*args, _real=real, _name=name, **kw):
            seen.append((_name, tuple(a.shape for a in args)))
            return _real(*args, **kw)

        monkeypatch.setattr(ops, name, entry)
    return seen


@pytest.fixture(scope="module")
def small_index():
    store = InMemoryBlobStore()
    corpus = write_corpus(store, "corpus/p", make_logs_like(2000, seed=11),
                          n_blobs=2)
    Index.build(corpus, CFG, store, "index/p").close()
    return store, corpus


POOL = ["info block", "task stage", "in stage executor task",
        "packetresponder for block", "info OR warn", "block -error",
        '"in stage"', "(info OR error) stage", "task -node3",
        '"for block" terminating', "stored OR failed", "error shuffle"]


def test_served_stream_reaches_only_the_q_classes(small_index, monkeypatch):
    """50 batches of 1 to 16 queries over one small Index on the bitmap
    path, each holding a query of more than one leaf, launch
    `combine_batch` once a batch at most at one shape per Q class, all
    at the unit's L, S and W classes, and answer as the NumPy path
    does."""
    store, _corpus = small_index
    svc = SearchService(Index.open(store, "index/p"))
    rng = np.random.default_rng(4)
    nodes = [f"stage node{n}" for n in range(0, 200, 7)]
    seen = _record(monkeypatch)
    for _ in range(50):
        texts = list(rng.choice(POOL + nodes, int(rng.integers(1, 17))))
        got = svc.search_batch(texts, top_k=10, impl="bitmap")
        want = svc.search_batch(texts, top_k=10, impl="sorted")
        assert [r.refs for r in got] == [r.refs for r in want]
    svc.close()
    shapes = set(seen)
    kernels = {name for name, _s in shapes}
    assert kernels == {"combine_batch_pallas"}
    assert len(seen) == 50         # one launch a batch
    assert len(shapes) <= len(Q_CLASSES) * len(kernels)
    words = unit_words(svc.searcher.n_docs)
    for _name, (bm, *prog) in shapes:
        assert bm[0] in Q_CLASSES and bm[1:] == (4, words)
        assert all(p == (bm[0], 4, 3) for p in prog)


# ------------------------------------------------------------- warm step
def test_warm_step_runs_exactly_the_q_classes(small_index, monkeypatch):
    store, _corpus = small_index
    n_docs = Index.open(store, "index/p").searcher().n_docs
    words = unit_words(n_docs)
    seen = _record(monkeypatch)
    assert warm_shape_classes(n_docs, 16) == [(q,) for q in Q_CLASSES]
    assert seen == [("combine_batch_pallas", ((q, 4, words), (q, 4, 3)))
                    for q in Q_CLASSES]
    seen.clear()
    assert warm_shape_classes(n_docs, 4, groups=3) == [(3, 1), (3, 2),
                                                        (3, 4)]
    assert seen == [("combine_cluster_pallas",
                     ((3, q, 4, words), (3, q, 4, 3))) for q in (1, 2, 4)]


def test_service_warms_at_open_only_where_kernels_compile(small_index,
                                                          monkeypatch):
    """Opening a service warms its units' classes up to the frontend's
    default `max_batch` on a compiling backend — per unit for an Index;
    for a ShardedIndex the fused kernel over every shard unit and the
    per-unit kernels of the shard legs — and nothing under the
    interpreter."""
    store, corpus = small_index
    calls = []
    monkeypatch.setattr(search_service, "warm_shape_classes",
                        lambda *a, **kw: calls.append((a, kw)))
    SearchService(Index.open(store, "index/p")).close()
    assert calls == []

    monkeypatch.setattr(search_service, "compiles", lambda: True)
    svc = SearchService(Index.open(store, "index/p"))
    assert calls == [((svc.searcher.n_docs, 16), {})]
    svc.close()
    calls.clear()
    ShardedIndex.build(corpus, CFG, store, "cluster/p", n_shards=4).close()
    svc = SearchService(ShardedIndex.open(store, "cluster/p"))
    units = [r[0].reader for r in svc.searcher.shard_replicas]
    assert calls[0] == ((max(u.n_docs for u in units), 16), {"groups": 4})
    legs = calls[1:]
    assert {unit_words(n) for (n, _q), _kw in legs} == \
        {unit_words(u.n_docs) for u in units}
    assert all(q == 16 and kw == {} for (_n, q), kw in legs)
    svc.close()


@pytest.mark.parametrize("max_batch", [4, 64])
def test_frontend_warms_its_service_at_its_own_batch(small_index,
                                                     monkeypatch, max_batch):
    """A `Frontend` has its service compile the Q classes of its own
    `max_batch`, not the default's; a reopened searcher is warmed the
    same way."""
    store, _corpus = small_index
    calls = []
    monkeypatch.setattr(search_service, "warm_shape_classes",
                        lambda *a, **kw: calls.append(a))
    monkeypatch.setattr(search_service, "compiles", lambda: True)
    svc = SearchService(Index.open(store, "index/p"))
    calls.clear()
    Frontend(svc, FrontendConfig(max_batch=max_batch))
    assert calls == [(svc.searcher.n_docs, max_batch)]
    calls.clear()
    svc._open_searcher()
    assert calls == [(svc.searcher.n_docs, max_batch)]
    svc.close()
