"""A 16-shard `ShardedIndex` served through `SearchService.search_batch`
on the cluster-fused gather (`impl="fused"`), against a plain scan of the
corpus.

The corpus is 4,000 HDFS_v1-shaped log lines from the benchmark's own
generator (`chipbench/corpora/hdfs_v1.py`, loaded by path). `scan`
below answers queries from the lines alone, with its own copy of the
word rule: it shares no code with the index. Also here: the gather reads
the kernel's result whatever layout the chip returns it in, its
completion returns exactly what the exhaustive rule it replaced
returned, and its spans.
"""

import importlib.util
import json
import os
import re

import numpy as np
import pytest

from repro.analysis import spans
from repro.data import write_corpus
from repro.index import BuilderConfig, Index, Phrase, Term
from repro.index.planner import combine_cluster_planned, physical_plan
from repro.index.query import And
from repro.serving import SearchService, ShardedIndex
from repro.serving import cluster as cluster_mod
from repro.storage import InMemoryBlobStore

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = BuilderConfig(B=2000, F0=1.0, seed=7)
N_SHARDS = 16


def _hdfs_lines(n: int, seed: int) -> list[str]:
    path = os.path.join(ROOT, "chipbench", "corpora", "hdfs_v1.py")
    spec = importlib.util.spec_from_file_location("hdfs_v1_lines", path)
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "hdfs-1m.json")) as f:
        params = json.load(f)["corpus"]
    return gen.make_lines(n, np.random.default_rng(seed), params)


# ------------------------------------------------------------ plain scan
_WORD = re.compile(r"[a-z0-9_\-./]+")


def _tokens(line: str) -> list[str]:
    return _WORD.findall(line.lower())


def scan(docs: list[str], q) -> set[int]:
    """Lines matching `q`, a tuple tree: ("w", word), ("p", w1, w2) for
    two adjacent words, ("and", a, b, ...), ("or", a, b, ...), ("not", a)
    inside an "and"."""
    op = q[0]
    if op == "w":
        return {i for i, d in enumerate(docs) if q[1] in _tokens(d)}
    if op == "p":
        return {i for i, d in enumerate(docs)
                if any(a == q[1] and b == q[2]
                       for a, b in zip(_tokens(d), _tokens(d)[1:]))}
    if op == "or":
        return set().union(*(scan(docs, s) for s in q[1:]))
    assert op == "and"
    pos = [scan(docs, s) for s in q[1:] if s[0] != "not"]
    out = set.intersection(*pos)
    for s in q[1:]:
        if s[0] == "not":
            out -= scan(docs, s[1])
    return out


def _text(q) -> str:
    op = q[0]
    if op == "w":
        return q[1]
    if op == "p":
        return f'"{q[1]} {q[2]}"'
    if op == "not":
        return f"NOT {_text(q[1])}"
    inner = f" {op.upper()} ".join(_text(s) for s in q[1:])
    return f"({inner})"


@pytest.fixture(scope="module")
def deployment():
    docs = _hdfs_lines(4000, seed=2026)
    store = InMemoryBlobStore()
    corpus = write_corpus(store, "corpus/hdfs", docs, n_blobs=4)
    cluster = ShardedIndex.build(corpus, CFG, store, "cluster/hdfs",
                                 n_shards=N_SHARDS)
    counts: dict[str, int] = {}
    for d in docs:
        for w in set(_tokens(d)):
            counts[w] = counts.get(w, 0) + 1
    blocks = sorted((w for w in counts if w.startswith("blk_")),
                    key=lambda w: (-counts[w], w))
    nodes = sorted((w for w in counts if re.fullmatch(r"[0-9.]+\.[0-9]+", w)
                    and w.count(".") == 3), key=lambda w: (-counts[w], w))
    trees = [
        ("w", blocks[0]), ("w", nodes[0]), ("w", "packetresponder"),
        ("w", "served"),
        ("and", ("w", nodes[0]), ("w", "receiving")),
        ("and", ("w", nodes[1]), ("w", nodes[2])),
        ("or", ("w", blocks[1]), ("w", nodes[3])),
        ("and", ("w", "info"), ("not", ("w", nodes[0]))),
        ("and", ("w", nodes[1]), ("not", ("w", "served"))),
        ("p", "received", "block"), ("p", "served", "block"),
        # both words in every PacketResponder line, never adjacent: the
        # completion walks every candidate and accepts none
        ("p", "block", "terminating"),
        ("and", ("w", "info"), ("p", "for", "block")),
        ("or", ("and", ("w", nodes[2]), ("not", ("w", "info"))),
         ("w", blocks[2])),
    ]
    svc = SearchService(cluster)
    yield dict(docs=docs, corpus=corpus, cluster=cluster, svc=svc,
               trees=trees, texts=[_text(t) for t in trees])
    svc.close()


@pytest.mark.parametrize("top_k", [None, 100])
def test_fused_answers_equal_a_corpus_scan(deployment, top_k):
    docs, corpus = deployment["docs"], deployment["corpus"]
    at = {(r.blob, r.offset): i for i, r in enumerate(corpus.refs)}
    svc = deployment["svc"]
    out = svc.search_batch(deployment["texts"], top_k=top_k, impl="fused")
    assert svc.searcher.last_scatter.fused
    assert svc.searcher.last_scatter.budget == \
        (None if top_k is None else "global")
    for tree, res in zip(deployment["trees"], out):
        match = scan(docs, tree)
        got = [at[(r.blob, r.offset)] for r in res.refs]
        assert len(set(got)) == len(got)
        assert set(got) <= match, tree
        assert res.texts == [docs[i] for i in got]
        want = len(match) if top_k is None else min(top_k, len(match))
        assert len(got) == want, tree
    assert any(len(scan(docs, t)) > 100 for t in deployment["trees"])
    assert any(not scan(docs, t) for t in deployment["trees"])


def test_fused_answers_equal_the_per_shard_path(deployment):
    """Every match: byte-identical to the per-shard legs and their merge.
    Top-K: the same documents wherever K holds every match (which K of
    more the two paths pick differs: the first K of the gather's
    canonical order, against the merge of each shard's first K)."""
    svc, texts = deployment["svc"], deployment["texts"]
    for top_k in (None, 100):
        fused = svc.search_batch(texts, top_k=top_k, impl="fused")
        legs = svc.search_batch(texts, top_k=top_k, impl="bitmap")
        assert not svc.searcher.last_scatter.fused
        for a, b in zip(fused, legs):
            if top_k is None:
                assert (a.refs, a.texts) == (b.refs, b.texts)
            elif len(b.refs) < top_k:
                assert set(a.refs) == set(b.refs)


def test_fused_is_a_path_of_a_sharded_index_only(deployment):
    store = InMemoryBlobStore()
    corpus = write_corpus(store, "corpus/one", deployment["docs"][:500])
    svc = SearchService(Index.build(corpus, CFG, store, "index/one"))
    with pytest.raises(ValueError, match="ShardedIndex"):
        svc.search_batch(["info"], impl="fused")
    svc.close()


# ------------------------------------------------------------- the layout
@pytest.mark.parametrize("n_queries", [1, 3])
def test_cluster_combine_reads_any_result_layout(monkeypatch, n_queries):
    """On the chip `combine_cluster` can hand back its (G, Q, W) result
    with W outermost (for 16 shards, 1 or 2 queries and 5 words): a
    row's words are then not adjacent. The unpack must read it by value."""
    from repro.kernels import intersect
    real = intersect.combine_cluster

    def w_outermost(bitmaps, programs, **kw):
        inter, counts = real(bitmaps, programs, **kw)
        inter = np.asfortranarray(np.asarray(inter))
        assert not inter[0, 0].flags["C_CONTIGUOUS"]
        return inter, counts

    monkeypatch.setattr(intersect, "combine_cluster", w_outermost)
    rng = np.random.default_rng(3)
    plans, words, commons, expect = [], [], [], []
    q = And((Term("a"), Term("b")))
    for _g in range(N_SHARDS):
        plans.append([physical_plan(q, ())] * n_queries)
        words.append([])
        expect.append([])
        for _q in range(n_queries):
            a = np.unique(rng.integers(0, 150, 40)).astype(np.uint64)
            b = np.unique(rng.integers(0, 150, 40)).astype(np.uint64)
            words[-1].append({"a": (a, a + 1), "b": (b, b + 1)})
            expect[-1].append(np.intersect1d(a, b))
        commons.append(lambda w: False)
    results, counts = combine_cluster_planned(plans, words, commons,
                                              n_docs=150)
    for g in range(N_SHARDS):
        for j in range(n_queries):
            keys, lengths = results[g][j]
            np.testing.assert_array_equal(keys, expect[g][j])
            np.testing.assert_array_equal(lengths, expect[g][j] + 1)
            assert counts[g, j] == len(expect[g][j])


def test_cluster_combine_counts_pairs_without_candidates():
    """A (shard, query) pair whose words have no candidates counts 0,
    wherever it lies on the grid: after a pair with candidates, before
    one, and as a whole group's."""
    rng = np.random.default_rng(5)
    q = And((Term("a"), Term("b")))
    empty = np.empty(0, np.uint64)
    G, Q = 4, 3
    plans, words, commons = [], [], []
    for g in range(G):
        plans.append([physical_plan(q, ())] * Q)
        words.append([])
        for j in range(Q):
            if g == 3 or (g + j) % 2:
                words[-1].append({"a": (empty, empty), "b": (empty, empty)})
                continue
            a = np.unique(rng.integers(0, 150, 60)).astype(np.uint64)
            b = np.unique(rng.integers(0, 150, 60)).astype(np.uint64)
            words[-1].append({"a": (a, a + 1), "b": (b, b + 1)})
        commons.append(lambda w: False)
    results, counts = combine_cluster_planned(plans, words, commons,
                                              n_docs=150)
    for g in range(G):
        for j in range(Q):
            keys, _lengths = results[g][j]
            a, b = words[g][j]["a"][0], words[g][j]["b"][0]
            np.testing.assert_array_equal(keys, np.intersect1d(a, b))
            assert counts[g, j] == len(keys), (g, j)
    assert counts[3].sum() == 0 and counts[:3].sum() > 0


def test_fused_candidate_totals_equal_the_per_shard_legs(deployment):
    """The gather's round-1 candidate totals, per query and per shard,
    are those the per-shard legs count on the host; most (shard, query)
    pairs of a 16-shard index hold no candidates."""
    svc, texts = deployment["svc"], deployment["texts"]
    fused = svc.search_batch(texts, top_k=100, impl="fused")
    fused_shards = list(svc.searcher.last_scatter.shard_candidates)
    legs = svc.search_batch(texts, top_k=100, impl="bitmap")
    leg_shards = list(svc.searcher.last_scatter.shard_candidates)
    assert [r.stats.n_candidates for r in fused] == \
        [r.stats.n_candidates for r in legs]
    assert fused_shards == leg_shards
    assert sum(fused_shards) == sum(r.stats.n_candidates for r in fused)
    assert 0 in fused_shards or any(
        r.stats.n_candidates == 0 for r in fused)


# ------------------------------------------------------------ completion
HALF = Phrase(("alpha", "beta"))     # about half of its candidates match
NONE = Phrase(("beta", "alpha"))     # candidates, and not one match
ENOUGH = Term("gamma")               # every candidate matches
JOBS = [HALF, NONE, ENOUGH, And((Term("gamma"), HALF))]


def _fallback_docs(n):
    out = []
    for i in range(n):
        if i % 4 == 0:
            out.append(f"INFO base alpha beta gamma n{i % 37} blk_{i}")
        elif i % 4 == 1:
            out.append(f"WARN base beta delta alpha n{i % 37} blk_{i}")
        else:
            out.append(f"INFO base gamma delta n{i % 37} blk_{i}")
    return out


def exhaustive(self, top_k):
    """The completion the walk replaced: with fewer than `top_k`
    accepted, fetch every candidate left; else those ranked before the
    `top_k`-th accepted."""
    if top_k is None:
        return set()
    canon = list(self.walk())
    accepted = [i for i in canon if self.verdict.get(i)]
    if len(accepted) < top_k:
        return {i for i in canon if i not in self.verdict}
    cut = canon.index(accepted[top_k - 1])
    return {i for i in canon[:cut] if i not in self.verdict}


@pytest.fixture(scope="module")
def fallback_cluster():
    store = InMemoryBlobStore()
    corpus = write_corpus(store, "corpus/fb", _fallback_docs(2400),
                          n_blobs=3)
    cluster = ShardedIndex.build(corpus, CFG, store, "cluster/fb",
                                 n_shards=N_SHARDS)
    cs = cluster.searcher(fused=True)
    yield cs
    cs.close()


def _completion(cs, q, top_k, budget):
    spans.start()
    res, = cs.query_batch([q], top_k=top_k, budget=budget)
    done = [s for s in spans.stop() if s.name == "cluster.completion"]
    return res, done


@pytest.mark.parametrize("top_k", [5, 100, 610])
@pytest.mark.parametrize("budget", ["global", "per_shard"])
def test_completion_equals_the_exhaustive_rule(fallback_cluster,
                                               monkeypatch, budget, top_k):
    cs = fallback_cluster
    walked = [_completion(cs, q, top_k, budget)[0] for q in JOBS]
    monkeypatch.setattr(cluster_mod._Canonical, "next_pending", exhaustive)
    for q, res in zip(JOBS, walked):
        want, _ = _completion(cs, q, top_k, budget)
        assert (res.refs, res.texts) == (want.refs, want.texts), q
    assert len(walked[0].refs) == min(top_k, 600)   # 600 lines match
    assert walked[1].refs == []


def test_completion_fetches_a_prefix(fallback_cluster):
    res, (done,) = _completion(fallback_cluster, HALF, 100, "global")
    assert len(res.refs) == 100
    assert done.attrs["jobs"] == 1 and done.attrs["rounds"] >= 1
    assert 0 < done.attrs["docs"] * 4 < done.attrs["remaining"]
    # a sample that finds nothing leaves nothing to estimate from: the
    # whole remainder, in one round
    res, (done,) = _completion(fallback_cluster, NONE, 100, "global")
    assert res.refs == []
    assert done.attrs["rounds"] == 1
    assert done.attrs["docs"] == done.attrs["remaining"] > 0
    # the quotas prove a top-K whose every candidate matches
    _res, (done,) = _completion(fallback_cluster, ENOUGH, 100, "global")
    assert (done.attrs["jobs"], done.attrs["docs"]) == (0, 0)


# ------------------------------------------------------------------ spans
def test_fused_gather_spans(deployment):
    svc = deployment["svc"]
    spans.start()
    svc.search_batch(deployment["texts"], top_k=100, impl="fused")
    got = spans.stop()
    by_name: dict[str, list] = {}
    for s in got:
        by_name.setdefault(s.name, []).append(s)
    batch, = by_name["service.search_batch"]
    r1, = by_name["cluster.round1"]
    assert r1.attrs["shards"] == N_SHARDS and r1.attrs["requests"] > 0
    for name in ("combine.build", "combine.call", "combine.unpack"):
        s, = by_name[name]
        assert s.attrs["kernel"] == "combine_cluster"
        assert s.attrs["G"] == N_SHARDS
        # the batch's own shape, not the class the kernel runs it in
        assert s.attrs["Q"] == len(deployment["texts"]) == 14
        assert {"L", "W", "S"} <= set(s.attrs)
        assert s.parent == batch.id
    order, = by_name["cluster.order"]
    assert order.attrs["candidates"] > 0
    done, = by_name["cluster.completion"]
    assert set(done.attrs) == {"jobs", "docs", "rounds", "remaining"}
    rounds = by_name["cluster.round2"]
    assert len(rounds) == len(by_name["cluster.verify"]) \
        == 1 + done.attrs["rounds"]
    assert all(s.attrs["requests"] > 0 for s in rounds)
    verify = by_name["cluster.verify"]
    assert sum(s.attrs["docs"] for s in verify[1:]) == done.attrs["docs"]
    assert all(0 <= s.attrs["accepted"] <= s.attrs["docs"] for s in verify)
    for s in (r1, order, done, rounds[0], verify[0]):
        assert s.parent == batch.id


# ------------------------------------------------- the canonical order
class _Unit:
    """A group's unit: candidate keys are line numbers of one blob."""

    def __init__(self, blob):
        self.blob = blob

    def _refs(self, keys, lengths):
        from repro.data.corpus import DocRef
        return [DocRef(self.blob, int(k), int(n))
                for k, n in zip(keys, lengths)]


def _canonical(sizes, quotas, shared=()):
    """Groups of `sizes` candidates each, group g's rank r being line
    `r` of blob `b<g>`; `shared` lists (g, r, g2, r2): group g's rank r
    is group g2's rank r2 document."""
    cands = []
    for g, n in enumerate(sizes):
        keys = np.arange(n, dtype=np.uint64) + 100 * g
        cands.append((keys, np.ones(n, np.uint64)))
    groups = [(g, _Unit("b")) for g in range(len(sizes))]
    for g, r, g2, r2 in shared:
        cands[g][0][r] = cands[g2][0][r2]
    return cluster_mod._Canonical(groups, cands, quotas)


def test_canonical_order_is_rank_then_group_without_repeats():
    st = _canonical([3, 1, 2], [0, 0, 0], shared=[(2, 1, 0, 0)])
    # (0,0) (0,1) (0,2) (1,0) (1,2) (2,0); group 2's rank 1 is group 0's
    # rank 0 document, listed at its smaller priority only
    assert [i[1] for i in st.walk()] == [0, 100, 200, 1, 2]
    assert st.n_positions == 6


def test_canonical_walk_takes_chunks_and_stops_when_proven():
    st = _canonical([400] * 4, [10] * 4)
    assert len(st.pending) == 40
    for ident in st.pending:                  # half of the quotas match
        st.verified(ident, ident[1] % 2 == 0)
    st.pending = st.next_pending(100)
    # need 80 at p = 0.5: 1.25 x 80 / 0.5 = 200 candidates, in order
    assert len(st.pending) == 200
    listed = [i for i in st.walk() if i not in st.verdict][:200]
    assert st.pending == set(listed)
    for ident in st.pending:
        st.verified(ident, True)
    st.pending = st.next_pending(100)
    assert st.pending == set()                # the prefix proves it
    assert len(st.first_accepted(100)) == 100
    assert st.unfetched() == 1600 - 240
