"""The top-K fallback stops once K documents are proven.

When a top-K job's §IV-D samples hold fewer than K unique accepted
documents, its answer is the first K unique accepted documents of the
sequence u0.sample, u0.rest, u1.sample, u1.rest, ... (each unit's
candidates in its `topk_order` permutation). `exhaustive` below is a
test-local copy of the rule that verified every remaining candidate to
get there; the reader must return exactly its refs and texts while
fetching only a prefix of the remainder. Checked on one unit, on a base
plus an appended segment plus an in-memory segment that re-indexes base
documents, and on each leg of a cluster's per-shard path; under the
default chunk rule and under a tight one that forces many small rounds
across unit boundaries.
"""

import numpy as np
import pytest

from repro.analysis import spans
from repro.core.topk import sample_size
from repro.data import write_corpus
from repro.data.corpus import Corpus, DocRef
from repro.index import BuilderConfig, Index, MultiSegmentSearcher, Phrase
from repro.index import searcher as searcher_mod
from repro.index.planner import DocContent, plan_batch
from repro.index.query import Term
from repro.index.searcher import (_combine_jobs, _Completion,
                                  _filter_unit_candidates, lookup_units,
                                  topk_order)
from repro.serving import ShardedIndex
from repro.serving.cluster import _topk_select
from repro.storage import InMemoryBlobStore

CFG = BuilderConfig(B=2000, F0=1.0)
# the base holds "alpha beta" in a quarter of its lines and both words
# apart in another quarter: a sample of about 130 for K = 100 finds about
# 65, the fallback has to complete the answer
HALF = Phrase(("alpha", "beta"))
NONE = Phrase(("beta", "alpha"))        # candidates, and not one match
ENOUGH = Term("gamma")                  # every candidate matches
JOBS = {"half": (HALF, 100), "none": (NONE, 100), "enough": (ENOUGH, 100),
        # more than the base's 600 matches: the walk runs out of the
        # base and ends in the appended segment
        "half-deep": (HALF, 610)}


def _docs(n, tag, adjacent_every):
    """n lines: every `adjacent_every`-th holds "alpha beta", a quarter
    hold "beta ... alpha", the rest neither."""
    out = []
    for i in range(n):
        if i % adjacent_every == 0:
            out.append(f"INFO {tag} alpha beta gamma n{i % 37} blk_{i}")
        elif i % 4 == 1:
            out.append(f"WARN {tag} beta delta alpha n{i % 37} blk_{i}")
        else:
            out.append(f"INFO {tag} gamma delta n{i % 37} blk_{i}")
    return out


@pytest.fixture(scope="module")
def readers():
    """name -> (reader, its units, every document's text by identity).

    "multi" is the base, a durable segment where the phrase is rare, and
    an in-memory segment that indexes 104 base lines again (4 of them
    matches)."""
    store = InMemoryBlobStore()
    docs1, docs2 = _docs(2400, "base", 4), _docs(600, "delta", 40)
    c1 = write_corpus(store, "corpus/f1", docs1, n_blobs=3)
    c2 = write_corpus(store, "corpus/f2", docs2, n_blobs=2)
    text = {(r.blob, r.offset, r.length): t
            for c in (c1, c2) for r, t in zip(c.refs, c.texts)}

    one = Index.build(c1, CFG, store, "index/f-one").searcher()
    seg = Index.build(c1, CFG, store, "index/f-seg")
    w = seg.writer()
    w.append(c2)
    w.commit()
    again = [0, 4, 8, 12] + [i for i in range(400) if i % 4 == 1]
    seg.writer().add(Corpus(store=store, refs=[c1.refs[i] for i in again],
                            texts=[docs1[i] for i in again]))
    multi = seg.searcher()
    assert isinstance(multi, MultiSegmentSearcher) and multi.n_units == 3
    cluster = ShardedIndex.build(c1, CFG, store, "cluster/f", n_shards=2)
    cs = cluster.searcher()
    yield {"one": (one, [one], text), "multi": (multi, multi.units, text),
           "cluster": (cs, None, text)}
    cs.close()


def exhaustive(units, q, top_k, impl, text):
    """The exhaustive Eq. 6 fallback: verify every remaining candidate,
    keep the first `top_k` unique accepted docs of the unit-major
    sequence. Returns (refs, texts, remaining candidates)."""
    job, = plan_batch([q], units=tuple(units), top_k=top_k)
    outs, _ = lookup_units(units, [job.lookup_q], units[0]._fetcher)

    def accepted(r):
        return job.accepts(DocContent(text[(r.blob, r.offset, r.length)]))

    samples, wholes, remaining = [], [], 0
    for unit, out in zip(units, outs):
        (keys, lengths), = _combine_jobs([job], out, impl, unit)
        keys, lengths = _filter_unit_candidates(unit, keys, lengths)
        if not len(keys):
            continue
        order = topk_order(keys)
        rk = sample_size(len(keys), top_k, unit.F0, job.delta)
        refs = unit._refs(keys[order], lengths[order])
        ok = [accepted(r) for r in refs]
        samples.append([r for r, a in zip(refs[:rk], ok[:rk]) if a])
        wholes.append([r for r, a in zip(refs, ok) if a])
        remaining += len(keys) - rk

    def first(lists):
        seen, got = set(), []
        for r in (r for rl in lists for r in rl):
            ident = (r.blob, r.offset, r.length)
            if ident not in seen and len(got) < top_k:
                seen.add(ident)
                got.append(r)
        return got, len(seen)

    picked, n_sample = first(samples)
    if n_sample >= top_k:
        remaining = 0               # no fallback at all
    else:
        picked, _ = first(wholes)
    return picked, [text[(r.blob, r.offset, r.length)] for r in picked], \
        remaining


@pytest.fixture(params=["default", "tight"])
def rule(request, monkeypatch):
    """The chunk rule as shipped, or one whose chunks start at one
    candidate and double: many rounds, crossing units mid-walk."""
    if request.param == "tight":
        monkeypatch.setattr(searcher_mod, "FALLBACK_MIN_CHUNK", 1)
        monkeypatch.setattr(searcher_mod, "FALLBACK_MARGIN", 0.01)
    return request.param


def _query(reader, q, top_k, impl):
    spans.start()
    res, = reader.query_batch([q], top_k=top_k, impl=impl)
    fallbacks = [s for s in spans.stop() if s.name == "searcher.fallback"]
    return res, fallbacks


@pytest.mark.parametrize("impl", ["sorted", "bitmap"])
@pytest.mark.parametrize("job", sorted(JOBS))
@pytest.mark.parametrize("path", ["one", "multi"])
def test_answer_equals_the_exhaustive_fallback(readers, path, job, impl,
                                               rule):
    reader, units, text = readers[path]
    q, top_k = JOBS[job]
    refs, texts, remaining = exhaustive(units, q, top_k, impl, text)
    res, (fb,) = _query(reader, q, top_k, impl)
    assert (res.refs, res.texts) == (refs, texts)
    assert fb.attrs["remaining"] == remaining
    assert fb.attrs["docs"] <= remaining
    if job == "enough":
        assert len(refs) == top_k
        assert (fb.attrs["rounds"], fb.attrs["docs"]) == (0, 0)
    elif job == "none":
        assert refs == [] and remaining > 0
        # nothing to estimate from: the whole remainder, in one round
        assert (fb.attrs["rounds"], fb.attrs["docs"]) == (1, remaining)
    elif job == "half":
        assert len(refs) == top_k and remaining > 0
        if rule == "default":
            assert fb.attrs["docs"] * 4 < remaining
        else:
            assert fb.attrs["rounds"] > 1
    else:
        # the base's 600 matches, then (multi) the segment's first ten
        assert fb.attrs["rounds"] >= 1
        assert len(refs) == {"one": 600, "multi": 610}[path]
        assert {r.blob.split("/")[1] for r in refs[600:]} <= {"f2"}


@pytest.mark.parametrize("job", sorted(JOBS))
def test_cluster_per_shard_legs(readers, job, rule):
    cs, _units, text = readers["cluster"]
    q, top_k = JOBS[job]
    legs = []
    for replicas in cs.shard_replicas:
        reader = replicas[0].reader
        units = reader.units if isinstance(reader, MultiSegmentSearcher) \
            else [reader]
        legs.append(exhaustive(units, q, top_k, "bitmap", text))
    refs, texts = _topk_select([leg[0] for leg in legs],
                               [leg[1] for leg in legs], top_k)
    spans.start()
    res, = cs.query_batch([q], top_k=top_k, impl="bitmap", fused=False)
    fallbacks = [s for s in spans.stop() if s.name == "searcher.fallback"]
    assert (res.refs, res.texts) == (refs, texts)
    assert sorted(fb.attrs["remaining"] for fb in fallbacks) == \
        sorted(leg[2] for leg in legs)
    docs = sum(fb.attrs["docs"] for fb in fallbacks)
    remaining = sum(leg[2] for leg in legs)
    if job == "none":
        assert docs == remaining > 0
        assert all(fb.attrs["rounds"] == 1 for fb in fallbacks)
    elif job == "half" and rule == "default":
        assert 0 < docs * 2 < remaining


# ------------------------------------------------------------ the chunk rule
def _walk(units, want):
    """Drive `_Completion` over synthetic units, each `(n_candidates,
    n_sampled, accepted keys)` with the identity permutation; returns
    the chunks taken, as lists of `(unit, n)`, and the accepted count
    before each round and at the end."""
    cands = [(np.arange(n, dtype=np.uint64), np.ones(n, np.uint64))
             for n, _s, _a in units]

    def ok(u, keys):
        return [DocRef(f"b{u}", int(k), 1) for k in keys
                if int(k) in units[u][2]]

    walk = _Completion(0, want, cands, [np.arange(n) for n, _s, _a in units],
                       [s for _n, s, _a in units],
                       [ok(u, np.arange(s)) for u, (_n, s, _a)
                        in enumerate(units)])
    chunks, counts = [], []
    while not walk.done():
        counts.append(len(walk.seen))
        parts = walk.take()
        chunks.append([(u, len(keys)) for u, keys, _l in parts])
        for u, keys, _l in parts:
            assert list(keys) == list(range(keys[0], keys[0] + len(keys)))
            walk.verified(u, len(keys), ok(u, keys))
    return chunks, counts + [len(walk.seen)]


ODD = set(range(1, 20000, 2))
CHUNK_CASES = {
    # sample 130 finds 65; about 1.25 x 35 / 0.5 more prove the answer
    "proven in one chunk": ([(10000, 130, ODD)], 100, [[(0, 88)]]),
    # the first chunk leaves it one short: the next is twice as large
    "one short": ([(10000, 130, ODD - set(range(199, 219)))], 100,
                  [[(0, 88)], [(0, 176)]]),
    "sample found nothing": ([(10000, 130, set())], 100, [[(0, 9870)]]),
    "little left": ([(200, 130, ODD)], 100, [[(0, 70)]]),
    "the floor": ([(10000, 130, set(range(95)) | (ODD - set(range(130))))],
                  100, [[(0, 32)]]),
    # the base's rest is all false; the segment's sample completes the
    # answer as the second chunk reaches it, before its rest
    "into the next unit": ([(300, 130, set(range(0, 130, 2))),
                            (500, 130, set(range(40)))], 100,
                           [[(0, 88)], [(0, 82)]]),
    "run out": ([(300, 130, set(range(0, 300, 2))), (50, 50, set())],
                200, [[(0, 170)]]),
}


@pytest.mark.parametrize("case", sorted(CHUNK_CASES))
def test_chunks_follow_the_rule(case):
    units, want, expect = CHUNK_CASES[case]
    chunks, counts = _walk(units, want)
    assert chunks == expect
    # every round but the last starts short; the walk stops at the first
    # round that proves the answer, or when the candidates run out
    assert all(c < want for c in counts[:-1])
    taken = sum(n for chunk in chunks for _u, n in chunk)
    assert counts[-1] >= want or taken == sum(n - s for n, s, _a in units)
