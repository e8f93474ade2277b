"""Batched query engine: cross-query fetch planning, range coalescing,
superpost caching, and AND chains on the batched Pallas combine kernel.

The load-bearing invariant everywhere: batching/coalescing/caching may
only change *when bytes move*, never *which bytes* a query sees — every
optimized path must be result-identical to the serial seed engine."""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.compat import DeprecatedAPIError
from repro.core.sketch import intersect_sorted
from repro.data import make_logs_like, write_corpus
from repro.data.tokenizer import distinct_words
from repro.index import (And, Builder, BuilderConfig, Or, Regex, Searcher,
                         Term, coalesce_requests, slice_payloads)
from repro.kernels.intersect import (OP_AND, combine_batch, intersect,
                                     pack_programs, postings_to_bitmap,
                                     postings_to_bitmap_batch)
from repro.serving import SearchService
from repro.storage import (InMemoryBlobStore, LRUCache, RangeRequest,
                           SimCloudStore, SimCloudTransport,
                           SuperpostCache)


# ------------------------------------------------------------- coalescing
def test_coalesce_merges_adjacent_and_overlapping():
    reqs = [RangeRequest("b", 0, 10), RangeRequest("b", 10, 10),
            RangeRequest("b", 15, 10), RangeRequest("b", 100, 5)]
    merged, slices = coalesce_requests(reqs, gap=0)
    assert [(m.blob, m.offset, m.length) for m in merged] == \
        [("b", 0, 25), ("b", 100, 5)]
    assert slices == [(0, 0), (0, 10), (0, 15), (1, 0)]


def test_coalesce_gap_and_blob_isolation():
    reqs = [RangeRequest("a", 0, 10), RangeRequest("a", 30, 10),
            RangeRequest("b", 12, 4)]
    merged0, _ = coalesce_requests(reqs, gap=0)
    assert len(merged0) == 3                       # gap 20 > 0: no merge
    merged, slices = coalesce_requests(reqs, gap=20)
    assert [(m.blob, m.offset, m.length) for m in merged] == \
        [("a", 0, 40), ("b", 12, 4)]
    assert slices[1] == (0, 30)


def test_coalesce_slices_recover_exact_payloads():
    rng = np.random.default_rng(0)
    data = bytes(rng.integers(0, 256, 4096, dtype=np.uint8))
    store = InMemoryBlobStore()
    store.put("blob", data)
    reqs = [RangeRequest("blob", int(o), int(n))
            for o, n in zip(rng.integers(0, 3800, 40),
                            rng.integers(1, 200, 40))]
    merged, slices = coalesce_requests(reqs, gap=64)
    merged_payloads = [store.get_range(m) for m in merged]
    out = slice_payloads(reqs, merged_payloads, slices)
    for req, payload in zip(reqs, out):
        assert payload == store.get_range(req)
    assert len(merged) < len(reqs)


def test_coalesce_passes_unbounded_through():
    reqs = [RangeRequest("b"), RangeRequest("b", 0, 8)]
    merged, slices = coalesce_requests(reqs, gap=1 << 30)
    assert len(merged) == 2 and merged[slices[0][0]].length == -1


# ---------------------------------------------------------------- fixtures
@pytest.fixture(scope="module")
def engine():
    store = InMemoryBlobStore()
    docs = make_logs_like(2500, seed=11)
    corpus = write_corpus(store, "corpus/be", docs, n_blobs=3)
    Builder(BuilderConfig(B=1500, F0=1.0, index_ngrams=3,
                          hedge_layers=1)).build(corpus, store, "index/be")
    truth: dict[str, set[int]] = {}
    for i, d in enumerate(docs):
        for w in distinct_words(d):
            truth.setdefault(w, set()).add(i)
    return store, docs, truth


MIXED = [
    "error", "info", "block",                       # plain/common terms
    And((Term("error"), Term("node42"))),
    And((Term("info"), Term("block"), Term("from"))),
    Or((Term("warn"), Term("node7"))),
    Or((And((Term("error"), Term("block"))), Term("node9"))),
    Regex(r"blk_4[0-9]1\b"),
]


# --------------------------------------------- batched == serial, bytewise
def test_lookup_batch_identical_to_per_query_lookup(engine):
    store, _docs, truth = engine
    serial = Searcher(SimCloudTransport(SimCloudStore(store, seed=5)), "index/be",
                      coalesce_gap=None)                # seed engine
    batched = Searcher(SimCloudTransport(SimCloudStore(store, seed=5)), "index/be")
    queries = [And((Term("error"), Term("block"))), Term("info"),
               Term("error"), Or((Term("node4"), Term("error")))]
    outs, _stats = batched.lookup_batch(queries)
    for q, per_word in zip(queries, outs):
        ref, _ = serial.lookup(q)
        assert set(per_word) == set(ref)
        for w in ref:
            np.testing.assert_array_equal(per_word[w][0], ref[w][0])
            np.testing.assert_array_equal(per_word[w][1], ref[w][1])


def test_query_batch_identical_to_serial(engine):
    store, docs, truth = engine
    serial = Searcher(SimCloudTransport(SimCloudStore(store, seed=5)), "index/be",
                      coalesce_gap=None)
    expect = [serial.regex_query(q.pattern) if isinstance(q, Regex)
              else serial.query(q) for q in MIXED]
    batched = Searcher(SimCloudTransport(SimCloudStore(store, seed=5)), "index/be")
    got = batched.query_batch(MIXED)
    for q, a, b in zip(MIXED, expect, got):
        assert a.texts == b.texts, q
        assert a.refs == b.refs, q
        assert a.stats.n_candidates == b.stats.n_candidates
        assert a.stats.n_false_positives == b.stats.n_false_positives
    # ground truth for one of them, for good measure
    r = got[3]
    assert set(r.texts) == {docs[i]
                            for i in truth["error"] & truth["node42"]}


def test_query_batch_topk_identical_to_serial(engine):
    store, _docs, truth = engine
    serial = Searcher(SimCloudTransport(SimCloudStore(store, seed=5)), "index/be",
                      coalesce_gap=None)
    batched = Searcher(SimCloudTransport(SimCloudStore(store, seed=5)), "index/be")
    queries = ["error", "info", "block", "node1"]
    expect = [serial.query(q, top_k=5) for q in queries]
    got = batched.query_batch(queries, top_k=5)
    for a, b in zip(expect, got):
        assert a.texts == b.texts
        assert a.refs == b.refs


def test_query_batch_fewer_requests_and_lower_clock(engine):
    store, _docs, _truth = engine
    serial_cloud = SimCloudStore(store, seed=5)
    serial = Searcher(SimCloudTransport(serial_cloud), "index/be", coalesce_gap=None)
    for q in MIXED:
        (serial.regex_query(q.pattern) if isinstance(q, Regex)
         else serial.query(q))
    batched_cloud = SimCloudStore(store, seed=5)
    Searcher(SimCloudTransport(batched_cloud), "index/be").query_batch(MIXED)
    assert batched_cloud.totals.n_requests < 0.7 * serial_cloud.totals.n_requests
    assert batched_cloud.clock_s < serial_cloud.clock_s


def test_query_batch_hedged_is_superset_and_batches(engine):
    store, docs, truth = engine
    batched = Searcher(SimCloudTransport(SimCloudStore(store, seed=5)), "index/be")
    got = batched.query_batch(["error", "node3"], hedge=True)
    for q, res in zip(["error", "node3"], got):
        assert {docs[i] for i in truth[q]} == set(res.texts)


# -------------------------------------------------------- superpost cache
def test_superpost_cache_result_identical_fewer_requests(engine):
    store, _docs, _truth = engine
    plain_cloud = SimCloudStore(store, seed=5)
    plain = Searcher(SimCloudTransport(plain_cloud), "index/be")
    expect = [plain.query_batch(MIXED[:7]) for _ in range(3)]

    cached_cloud = SimCloudStore(store, seed=5)
    cached = Searcher(SimCloudTransport(cached_cloud), "index/be", cache=SuperpostCache(16 << 20))
    got = [cached.query_batch(MIXED[:7]) for _ in range(3)]
    for round_e, round_g in zip(expect, got):
        for a, b in zip(round_e, round_g):
            assert a.texts == b.texts and a.refs == b.refs
    assert cached_cloud.totals.n_requests < plain_cloud.totals.n_requests
    assert cached.cache.hits > 0
    assert cached.cache.bytes_saved > 0
    # hits are threaded into the per-round FetchStats
    assert got[1][0].stats.lookup.cache_hits > 0


def test_lru_cache_eviction_and_weighting():
    lru = LRUCache(3)
    for k in "abc":
        lru.put(k, k)
    lru.get("a")                        # refresh a
    lru.put("d", "d")                   # evicts b (LRU), not a (FIFO-head)
    assert "a" in lru and "b" not in lru and len(lru) == 3

    by_bytes = LRUCache(100, weigh=len)
    by_bytes.put("x", b"a" * 60)
    by_bytes.put("y", b"b" * 60)        # 120 > 100: x evicted
    assert "x" not in by_bytes and by_bytes.weight == 60
    by_bytes.put("huge", b"c" * 1000)   # heavier than the bound: rejected
    assert "huge" not in by_bytes


def test_search_service_result_cache_is_lru(engine):
    store, _docs, _truth = engine
    svc = SearchService(SimCloudTransport(SimCloudStore(store, seed=2)), "index/be",
                        cache_size=4)
    svc.search("error")
    for i in range(3):
        svc.search(f"node{i}")          # cache now full: error,node0,1,2
    svc.search("error")                 # hit — and refreshes recency
    assert svc.cache_hits == 1
    svc.search("node5")                 # evicts LRU entry node0, NOT error
    n = svc.stats.summary()["n"]
    svc.search("error")                 # still cached under LRU
    assert svc.cache_hits == 2
    assert svc.stats.summary()["n"] == n          # no new fetch observed
    assert svc.stats.summary()["cache_hit_rate"] > 0
    assert len(svc._cache) <= 4


# ------------------------------------------------------ service batch path
def test_service_search_batch_identical_and_faster(engine):
    store, _docs, _truth = engine
    serial_cloud = SimCloudStore(store, seed=9)
    serial_svc = SearchService(SimCloudTransport(serial_cloud), "index/be")
    expect = serial_svc.search_batch(MIXED, batched=False)

    batched_cloud = SimCloudStore(store, seed=9)
    batched_svc = SearchService(SimCloudTransport(batched_cloud), "index/be",
                                superpost_cache_bytes=16 << 20)
    got = batched_svc.search_batch(MIXED)
    for a, b in zip(expect, got):
        assert a.texts == b.texts and a.refs == b.refs
    assert batched_cloud.clock_s < serial_cloud.clock_s
    assert batched_cloud.totals.n_requests < serial_cloud.totals.n_requests


def test_service_search_batch_uses_result_cache(engine):
    store, _docs, _truth = engine
    svc = SearchService(SimCloudTransport(SimCloudStore(store, seed=9)), "index/be",
                        cache_size=16)
    r1 = svc.search_batch(["error", "info"])
    r2 = svc.search_batch(["error", "info"])
    assert svc.cache_hits == 2
    assert [r.texts for r in r1] == [r.texts for r in r2]


# ---------------------------------------- batched AND chains on the kernel
def _random_ragged_batch(rng, Q, n_docs):
    batch = []
    for _ in range(Q):
        L = int(rng.integers(1, 5))
        batch.append([np.unique(rng.integers(0, n_docs,
                                             int(rng.integers(1, n_docs))))
                      .astype(np.uint32) for _ in range(L)])
    return batch


def _and_chains(batch):
    """Each query's AND chain over its own layers, its steps numbered
    past the batch's widest query (the kernel's slot layout)."""
    L = max(len(posts) for posts in batch)
    return pack_programs([[(OP_AND, L + s - 1 if s else 0, s + 1)
                           for s in range(len(posts) - 1)]
                          for posts in batch], L)


@pytest.mark.parametrize("Q,n_docs", [(1, 100), (3, 4096), (5, 33_000)])
def test_combine_batch_and_chain_matches_single_and_oracle(Q, n_docs):
    rng = np.random.default_rng(Q * 7 + n_docs)
    batch = _random_ragged_batch(rng, Q, n_docs)
    bitmaps = postings_to_bitmap_batch(batch, n_docs)
    progs = _and_chains(batch)
    out_p, cnt_p = combine_batch(bitmaps, progs, impl="pallas",
                                 n_docs=n_docs)
    out_r, cnt_r = combine_batch(bitmaps, progs, impl="ref", n_docs=n_docs)
    np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_r))
    np.testing.assert_array_equal(np.asarray(cnt_p), np.asarray(cnt_r))
    for q, posts in enumerate(batch):
        single, cnt_s = intersect(postings_to_bitmap(posts, n_docs),
                                  impl="pallas")
        np.testing.assert_array_equal(np.asarray(out_p)[q],
                                      np.asarray(single))
        oracle = intersect_sorted(posts)
        assert int(cnt_p[q]) == int(cnt_s) == len(oracle)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**16))
def test_combine_batch_and_chain_property_ragged(seed):
    rng = np.random.default_rng(seed)
    n_docs = int(rng.integers(32, 2000))
    batch = _random_ragged_batch(rng, int(rng.integers(1, 6)), n_docs)
    bitmaps = postings_to_bitmap_batch(batch, n_docs)
    out, counts = combine_batch(bitmaps, _and_chains(batch), impl="pallas",
                                n_docs=n_docs)
    out = np.asarray(out)
    for q, posts in enumerate(batch):
        oracle = intersect_sorted(posts)
        bits = np.unpackbits(out[q].view(np.uint8), bitorder="little")
        got = np.flatnonzero(bits).astype(np.uint32)
        np.testing.assert_array_equal(got, oracle)
        assert int(counts[q]) == len(oracle)


def test_query_batch_bitmap_impl_identical(engine):
    store, _docs, _truth = engine
    sorted_res = Searcher(SimCloudTransport(SimCloudStore(store, seed=5)),
                          "index/be").query_batch(MIXED)
    bitmap_res = Searcher(SimCloudTransport(SimCloudStore(store, seed=5)),
                          "index/be").query_batch(MIXED, impl="bitmap")
    for a, b in zip(sorted_res, bitmap_res):
        assert a.texts == b.texts and a.refs == b.refs


# ---------------------------------------------------------- O(1) exists
def test_blobstore_exists_direct(tmp_path):
    from repro.storage import LocalBlobStore
    mem = InMemoryBlobStore()
    mem.put("x/y", b"1")
    assert mem.exists("x/y") and not mem.exists("x/z")
    loc = LocalBlobStore(str(tmp_path))
    loc.put("a/b", b"1")
    assert loc.exists("a/b") and not loc.exists("a/c")
    # names that escape the root are rejected, same as get/put
    with pytest.raises(ValueError):
        loc.exists("../escape")


# --------------------------------------------- vectorized core fast paths
@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**16))
def test_intersect_sorted_matches_sets(seed):
    rng = np.random.default_rng(seed)
    lists = [np.unique(rng.integers(0, 500, int(rng.integers(0, 300))))
             .astype(np.uint64) for _ in range(int(rng.integers(1, 5)))]
    got = intersect_sorted(lists)
    expect = set(lists[0].tolist())
    for l in lists[1:]:
        expect &= set(l.tolist())
    assert set(got.tolist()) == expect
    assert (np.diff(got.astype(np.int64)) > 0).all()  # sorted unique


# ------------------------------------------ serving-path bugfix regressions
def test_batched_latency_not_overcounted_vs_serial(engine):
    """A shared-round batch is ONE service event: recording its wall
    clock once per member used to make batched mean/p50/p99 incomparable
    with serial runs of the same workload."""
    store, _docs, _truth = engine
    serial_cloud = SimCloudStore(store, seed=31)
    serial_svc = SearchService(SimCloudTransport(serial_cloud), "index/be")
    serial_svc.search_batch(MIXED, batched=False)
    serial = serial_svc.stats.summary()

    batched_cloud = SimCloudStore(store, seed=31)
    batched_svc = SearchService(SimCloudTransport(batched_cloud), "index/be")
    t0 = batched_cloud.clock_s
    batched_svc.search_batch(MIXED)
    wall = batched_cloud.clock_s - t0
    batched = batched_svc.stats.summary()

    # both summaries account every query...
    assert serial["n_queries"] == batched["n_queries"] == len(MIXED)
    assert serial["n"] == len(MIXED) and batched["n"] == 1
    assert batched["mean_batch_size"] == len(MIXED)
    # ...but the batch contributes its wall clock ONCE, so the recorded
    # time equals the clock advance instead of ~N times it
    assert sum(batched_svc.stats.samples_s) == pytest.approx(wall)
    assert sum(batched_svc.stats.samples_s) < \
        sum(serial_svc.stats.samples_s)
    # and the sampled latencies stay comparable with serial samples
    assert batched["p99_ms"] < serial["p99_ms"] * len(MIXED)


def test_search_batch_dedupes_duplicate_queries(engine):
    """Duplicate queries in ONE cold batch (same normalized cache key)
    must be planned/fetched once, the result fanned back out."""
    store, _docs, _truth = engine
    once_cloud = SimCloudStore(store, seed=33)
    once = SearchService(SimCloudTransport(once_cloud), "index/be")
    once.search_batch(["error"])

    dup_cloud = SimCloudStore(store, seed=33)
    dup = SearchService(SimCloudTransport(dup_cloud), "index/be")
    # same key under normalization: a duplicate string AND a reordered
    # equivalent tree of it
    res = dup.search_batch(["error", Term("error"), "error"])
    assert dup_cloud.totals.n_requests == once_cloud.totals.n_requests
    assert res[0] is res[1] is res[2]
    assert dup.stats.summary()["n_queries"] == 1

    eq_cloud = SimCloudStore(store, seed=34)
    eq = SearchService(SimCloudTransport(eq_cloud), "index/be")
    tree = And((Term("error"), Term("block")))
    nested = And((Term("error"), And((Term("block"), Term("error")))))
    out = eq.search_batch([tree, nested])   # normalize flattens + dedupes
    assert out[0] is out[1]


def test_search_regex_removed_raises_typed_error(engine):
    store, _docs, _truth = engine
    svc = SearchService(SimCloudTransport(SimCloudStore(store, seed=35)),
                        "index/be", cache_size=8)
    with pytest.raises(DeprecatedAPIError, match="search_regex"):
        svc.search_regex(r"blk_4[0-9]1\b")
    assert svc.stats.cache_lookups == 0      # rejected before the planner


def test_search_regex_shim_routes_through_cache_and_topk(engine,
                                                         monkeypatch):
    monkeypatch.setenv("REPRO_ALLOW_DEPRECATED", "1")
    store, _docs, _truth = engine
    svc = SearchService(SimCloudTransport(SimCloudStore(store, seed=35)),
                        "index/be", cache_size=8)
    with pytest.warns(DeprecationWarning, match="search_regex"):
        r1 = svc.search_regex(r"blk_4[0-9]1\b")
    # the shim is the planner path: cached, counted, equal to search()
    r2 = svc.search(Regex(r"blk_4[0-9]1\b"))
    assert svc.cache_hits == 1 and svc.stats.cache_lookups == 2
    assert r1.texts == r2.texts and r1.refs == r2.refs
    with pytest.warns(DeprecationWarning):
        limited = svc.search_regex(r"blk_4[0-9]1\b", top_k=1)
    assert len(limited.texts) <= 1
