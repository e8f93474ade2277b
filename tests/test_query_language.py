"""Composable query language + logical/physical planner.

Three layers of guarantees:

  1. the LANGUAGE: normalization rewrites are semantics-preserving and
     canonical, and `parse(to_string(q)) == normalize(q)` round-trips;
  2. the PLANNER: every executable tree returns EXACTLY the documents a
     brute-force corpus scan returns (the scan is an independent
     re-implementation, not the planner's own verifier), on several
     seeded corpora, monolithic and segmented, sorted and bitmap;
  3. the KERNEL: the batched AND/OR/ANDNOT program evaluator matches
     its jnp reference and a Python-set oracle.
"""

import re

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.data import make_logs_like, write_corpus
from repro.data.tokenizer import parse_words
from repro.index import (And, Builder, BuilderConfig, Index, Not, Or,
                         Phrase, PureNegationError, Query, QuerySyntaxError,
                         Regex, Searcher, Term, normalize, parse,
                         physical_plan, query_words, to_string)
from repro.index.builder import NGRAM_PREFIX
from repro.index.planner import DocContent, make_job, matches, plan_batch
from repro.kernels.intersect import (OP_AND, OP_ANDNOT, OP_OR,
                                     bitmap_to_docs, combine_batch,
                                     pack_programs, postings_to_bitmap_batch)
from repro.serving import SearchService
from repro.storage import InMemoryBlobStore, SimCloudStore, SimCloudTransport


# ===================================================================== AST
def test_operator_sugar():
    a, b = Term("a"), Term("b")
    assert a & b == And((a, b))
    assert a | b == Or((a, b))
    assert ~a == Not(a)
    assert ~(a & b) == Not(And((a, b)))


def test_normalize_flatten_and_dedupe():
    a, b, c = Term("a"), Term("b"), Term("c")
    assert normalize(And((a, And((b, c))))) == And((a, b, c))
    assert normalize(Or((Or((a, b)), c))) == Or((a, b, c))
    assert normalize(And((a, a))) == a                 # dedupe + collapse
    assert normalize(And((a, b, a))) == And((a, b))    # stable order
    assert normalize(And((a,))) == a


def test_normalize_negation_rewrites():
    a, b = Term("a"), Term("b")
    assert normalize(Not(Not(a))) == a
    assert normalize(Not(And((a, b)))) == Or((Not(a), Not(b)))
    assert normalize(Not(Or((a, b)))) == And((Not(a), Not(b)))
    # De Morgan output flattens into an enclosing And
    q = And((Term("c"), Not(Or((a, b)))))
    assert normalize(q) == And((Term("c"), Not(a), Not(b)))
    # idempotent
    for tree in (q, Not(Not(Not(a))), Or((a, Not(And((a, b)))))):
        assert normalize(normalize(tree)) == normalize(tree)


def test_normalize_phrase():
    assert normalize(Phrase(("x",))) == Term("x")
    assert normalize(Phrase(("x", "y"), slop=2)) == Phrase(("x", "y"), 2)
    with pytest.raises(ValueError):
        normalize(Phrase(()))


def test_query_words_typeerror_and_regex_dedupe():
    with pytest.raises(TypeError):
        query_words(And((Term("a"), "oops")))        # type: ignore[arg-type]
    with pytest.raises(TypeError):
        normalize(Or((Term("a"), 3)))                # type: ignore[arg-type]
    # overlapping n-gram expansions dedupe across Regex nodes
    q = And((Regex("abcd"), Regex("bcde"), Term("abc")))
    ws = query_words(q)
    assert ws == [NGRAM_PREFIX + g for g in ("abc", "bcd", "cde")] + ["abc"]
    assert len(ws) == len(set(ws))
    # Not and Phrase contribute their words
    assert query_words(And((Phrase(("p", "q")), Not(Term("n"))))) == \
        ["p", "q", "n"]


# ================================================================== parsing
def test_parse_grammar():
    a, b, c = Term("a"), Term("b"), Term("c")
    assert parse("hello") == Term("hello")
    assert parse("a b") == And((a, b))
    assert parse("a AND b") == And((a, b))
    assert parse("a and b") == And((a, b))           # case-insensitive
    assert parse("a OR b c") == Or((a, And((b, c))))  # AND binds tighter
    assert parse("(a OR b) c") == And((Or((a, b)), c))
    assert parse("a NOT b") == And((a, Not(b)))
    assert parse("a -b") == And((a, Not(b)))
    assert parse("a NOT (b OR c)") == And((a, Not(b), Not(c)))  # De Morgan
    assert parse('"disk full"') == Phrase(("disk", "full"))
    assert parse('"disk full"~3') == Phrase(("disk", "full"), slop=3)
    assert parse('"one"') == Term("one")             # 1-word phrase = term
    assert parse("re:/blk_[0-9]+/") == Regex("blk_[0-9]+")
    assert parse(r"re:/a\/b/") == Regex("a/b")       # escaped slash
    assert parse("x re:/err/ y") == And((Term("x"), Regex("err"), Term("y")))


def test_parse_uses_document_tokenizer():
    # same analyzer as the Builder: lowercased, punctuation splits words
    assert parse("Node-7,x") == And((Term("node-7"), Term("x")))
    assert parse("ERROR") == Term("error")
    assert parse('"Disk FULL!"') == Phrase(("disk", "full"))


def test_parse_errors():
    for bad in ("", "   ", "(a", "a)", '"unterminated', "re:/open",
                "a OR", "AND"):
        with pytest.raises(QuerySyntaxError):
            parse(bad)


_WORDS = ["alpha", "bravo", "cat-5", "d.e", "under_score", "n0de", "xyz"]


def _random_tree(rng, depth=0) -> Query:
    roll = rng.random()
    if depth >= 3 or roll < 0.35:
        return Term(_WORDS[rng.randrange(len(_WORDS))])
    if roll < 0.45:
        n = rng.randrange(2, 4)
        return Phrase(tuple(_WORDS[rng.randrange(len(_WORDS))]
                            for _ in range(n)),
                      slop=rng.randrange(0, 3))
    if roll < 0.55:
        return Regex("blk_[0-9]+" if rng.random() < 0.5 else "shuffle_7")
    if roll < 0.65:
        return Not(_random_tree(rng, depth + 1))
    kind = And if rng.random() < 0.5 else Or
    n = rng.randrange(2, 4)
    return kind(tuple(_random_tree(rng, depth + 1) for _ in range(n)))


@settings(max_examples=60, deadline=None)
@given(st.integers(0, 2**32))
def test_round_trip_property(seed):
    import random
    rng = random.Random(seed)
    q = _random_tree(rng)
    assert parse(to_string(q)) == normalize(q)
    # and printing the normalized form is a fixed point
    assert parse(to_string(normalize(q))) == normalize(q)


def test_round_trip_quotes_keyword_terms():
    q = Term("and")
    assert parse(to_string(q)) == q


def test_phrase_routes_through_analyzer():
    # directly-constructed phrases analyze like parse() and the Builder
    assert Phrase(("Failed", "fetch")) == Phrase(("failed", "fetch"))
    assert Phrase(("disk full!",)) == Phrase(("disk", "full"))
    assert normalize(Phrase(("one!",))) == Term("one")


def test_regex_backslash_round_trip():
    for pat in (r"a\d+", "a/b", r"a\/b", "trailing\\", r"\\literal"):
        q = Regex(pat)
        assert parse(to_string(q)) == q, pat


def test_to_string_rejects_unanalyzable_terms():
    # such terms could never match an indexed document; printing them
    # would produce unparseable or lossy text
    for w in ("!!!", "Error", "a b"):
        with pytest.raises(ValueError):
            to_string(Term(w))


# ================================================================== planner
def test_pure_negation_rejected():
    a, b = Term("a"), Term("b")
    for bad in (Not(a), Or((a, Not(b))), And((Not(a), Not(b))),
                Not(And((a, b)))):
        with pytest.raises(PureNegationError):
            physical_plan(normalize(bad))
        with pytest.raises(PureNegationError):
            make_job(bad)
    # parse-level spellings reject too
    for text in ("NOT a", "-a", "a OR NOT b", "NOT (a b)"):
        with pytest.raises(PureNegationError):
            make_job(parse(text))


def test_gramless_regex():
    # alone: un-prefilterable, rejected (paper §IV-F policy)
    with pytest.raises(ValueError):
        make_job(Regex("[0-9]+"))
    # under And with a positive sibling: rides the sibling's candidates
    job = make_job(And((Term("a"), Regex("[0-9]+"))))
    assert job.plan is not None
    assert job.plan.lookup_words == ["a"]


def test_gramful_regex_on_gramless_index_raises_typed():
    """ROADMAP known-wart regression: a regex with literal n-gram runs
    against an index built WITHOUT index_ngrams used to silently return
    misses (the never-inserted gram terms hash to unrelated bins and
    intersect to nothing); the planner now raises a typed error."""
    from repro.index import GramlessIndexError

    store = InMemoryBlobStore()
    docs = make_logs_like(150, seed=4)
    corpus = write_corpus(store, "corpus/gl", docs, n_blobs=1)
    plain = Index.build(corpus, BuilderConfig(B=900, F0=1.0), store,
                        "index/gl")
    searcher = plain.searcher()
    for call in (lambda: searcher.query(Regex(r"blk_1[0-9]2")),
                 lambda: searcher.query_batch([Regex(r"blk_1[0-9]2")]),
                 lambda: searcher.query(
                     And((Term("error"), Regex(r"blk_1[0-9]2")))),
                 lambda: searcher.regex_query(r"blk_1[0-9]2")):
        with pytest.raises(GramlessIndexError, match="index_ngrams"):
            call()
    # a mismatched gram size is the same silent miss — also typed
    grammed = Index.build(corpus, BuilderConfig(B=900, F0=1.0,
                                                index_ngrams=4),
                          store, "index/gl4")
    with pytest.raises(GramlessIndexError, match="ngram=4"):
        grammed.searcher().query(Regex(r"blk_1[0-9]2", ngram=3))
    # the matching size works, and gramless-pattern rejection is intact
    res = grammed.searcher().query(Regex(r"blk_1[0-9]2", ngram=4))
    assert all("blk_1" in t for t in res.texts)
    with pytest.raises(ValueError):
        grammed.searcher().query(Regex("[0-9]+", ngram=4))


def test_lookup_set_skips_unbounded_or_branch():
    # Or(b, NOT c) bounds nothing — its words need no superpost fetches
    q = And((Term("a"), Or((Term("b"), Not(Term("c"))))))
    plan = physical_plan(normalize(q))
    assert plan.lookup_words == ["a"]


def test_classic_shapes_compile_to_classic_jobs():
    """Every shape is one kind of job: a plan, the lookup of its words
    and `matches` over the document as its one predicate. A Term/And/Or
    tree looks up `query_words` of the tree, in its order, and a
    standalone regex the n-grams of its prefilter."""
    a, b, c = Term("a"), Term("b"), Term("c")
    classic = (a, And((a, b)), Or((And((a, b)), c)),
               Or((c, And((b, Or((a, c)))))))
    regex = Regex("blk_[0-9]+")
    docs = [DocContent("a b info"), DocContent("c blk_12 z"),
            DocContent("a z")]
    for q in classic + (regex, And((a, Not(b)))):
        job = make_job(q)
        tree = normalize(q)
        assert job.plan.tree == tree
        assert query_words(job.lookup_q) == job.plan.lookup_words
        assert [job.accepts(d) for d in docs] == \
            [matches(tree, d) for d in docs]
    for q in classic:
        assert make_job(q).plan.lookup_words == query_words(normalize(q))
    assert make_job(regex).plan.lookup_words == query_words(regex)
    with pytest.raises(ValueError, match="no literal run"):
        make_job(Regex("[0-9]+"))


# ------------------------------------------------- the brute-force oracle
def _scan(q: Query, text: str, tokens: list[str]) -> bool:
    """Independent re-implementation of query semantics for the oracle."""
    if isinstance(q, Term):
        return q.word in tokens
    if isinstance(q, And):
        return all(_scan(s, text, tokens) for s in q.items)
    if isinstance(q, Or):
        return any(_scan(s, text, tokens) for s in q.items)
    if isinstance(q, Not):
        return not _scan(q.item, text, tokens)
    if isinstance(q, Regex):
        return re.search(q.pattern, text) is not None
    assert isinstance(q, Phrase)
    k = len(q.words)
    for s in range(len(tokens)):
        if tokens[s] != q.words[0]:
            continue
        i = s
        good = True
        for w in q.words[1:]:
            nxt = [j for j in range(i + 1, len(tokens)) if tokens[j] == w]
            if not nxt:
                good = False
                break
            i = nxt[0]
        if good and (i - s + 1) - k <= q.slop:
            return True
    return False


def _oracle(q: Query, docs: list[str]) -> set[str]:
    return {d for d in docs if _scan(q, d, parse_words(d))}


def _mixed_queries(docs: list[str]) -> list[Query]:
    """Composable shapes over words that actually occur in the corpus."""
    toks = parse_words(docs[0])
    w0, w1 = toks[0], toks[1]
    return [
        And((Term("info"), Not(Term("block")))),          # NOT common word
        And((Term("error"), Not(Term("starting")))),
        And((Term("error"), Not(Phrase((w0, w1))))),      # NOT phrase
        Phrase((w0, w1)),
        Phrase(("received", "block"), slop=2),
        And((Term("info"), Phrase((w0, w1)))),
        Or((Phrase(("received", "block")), Term("error"))),
        And((Term("info"), Regex(r"blk_4[0-9]+"))),       # Regex under And
        And((Regex(r"blk_[0-9]+"), Not(Term("info")))),
        Or((And((Term("info"), Not(Term("from")))), Term("error"))),
        And((Term("info"), Or((Term("block"), Not(Term("error")))))),
        parse("info NOT block starting"),
        parse('"received block"~1 OR error'),
        parse("info -(from OR block)"),
    ]


@pytest.mark.parametrize("seed,n_docs,B", [(11, 1200, 1000),
                                           (29, 1500, 1600),
                                           (47, 900, 1400)])
def test_planner_exact_vs_corpus_scan(seed, n_docs, B):
    """Acceptance: every composable query returns exactly the brute-force
    scan's documents, on several seeded corpora."""
    store = InMemoryBlobStore()
    docs = make_logs_like(n_docs, seed=seed)
    corpus = write_corpus(store, "corpus/ql", docs, n_blobs=3)
    Builder(BuilderConfig(B=B, F0=1.0, index_ngrams=3)).build(
        corpus, store, "index/ql")
    s = Searcher(SimCloudTransport(SimCloudStore(store, seed=5)), "index/ql")
    queries = _mixed_queries(docs)
    # serial, batched-sorted, and batched-bitmap all agree with the scan
    batched = s.query_batch(queries)
    bitmap = s.query_batch(queries, impl="bitmap")
    for q, rb, rbm in zip(queries, batched, bitmap):
        expect = _oracle(normalize(q), docs)
        assert set(rb.texts) == expect, to_string(q)
        assert rb.texts == rbm.texts and rb.refs == rbm.refs, to_string(q)
        single = s.query(q)
        assert single.texts == rb.texts and single.refs == rb.refs


def test_planner_exact_through_service_and_topk():
    store = InMemoryBlobStore()
    docs = make_logs_like(1000, seed=3)
    corpus = write_corpus(store, "corpus/qs", docs, n_blobs=2)
    Builder(BuilderConfig(B=1800, F0=1.0, index_ngrams=3)).build(
        corpus, store, "index/qs")
    svc = SearchService(SimCloudTransport(SimCloudStore(store, seed=2)),
                        "index/qs", cache_size=8)
    q = parse("info NOT block")
    expect = _oracle(q, docs)
    assert set(svc.search(q).texts) == expect
    assert set(svc.search("info NOT block").texts) == expect   # text form
    got = svc.search_batch([q, "error", parse('"received block" OR error')])
    assert set(got[0].texts) == expect
    # top-K returns verified matches only, k of them when available
    k = min(3, len(expect))
    topk = svc.search(q, top_k=3)
    assert len(topk.texts) == k and set(topk.texts) <= expect


def test_service_cache_keys_normalize():
    store = InMemoryBlobStore()
    docs = make_logs_like(400, seed=8)
    corpus = write_corpus(store, "corpus/qn", docs, n_blobs=2)
    Builder(BuilderConfig(B=600, F0=1.0)).build(corpus, store, "index/qn")
    svc = SearchService(SimCloudTransport(SimCloudStore(store, seed=2)),
                        "index/qn", cache_size=8)
    a, b, c = Term("info"), Term("block"), Term("from")
    svc.search(And((a, And((b, c)))))
    assert svc.cache_hits == 0
    svc.search(And((a, b, c)))                   # equivalent spelling
    assert svc.cache_hits == 1
    svc.search(parse("info block from"))         # parsed spelling
    assert svc.cache_hits == 2


def test_phrase_order_and_slop_semantics():
    store = InMemoryBlobStore()
    docs = ["alpha beta gamma", "beta alpha gamma", "alpha x beta",
            "alpha x y beta", "beta gamma alpha beta x", "gamma delta"]
    corpus = write_corpus(store, "corpus/ph", docs, n_blobs=1)
    Builder(BuilderConfig(B=256, F0=0.5)).build(corpus, store, "index/ph")
    s = Searcher(SimCloudTransport(SimCloudStore(store, seed=1)), "index/ph")

    def texts(q):
        return set(s.query(q).texts)

    assert texts(Phrase(("alpha", "beta"))) == {docs[0], docs[4]}
    assert texts(Phrase(("alpha", "beta"), slop=1)) == \
        {docs[0], docs[2], docs[4]}
    assert texts(Phrase(("alpha", "beta"), slop=2)) == \
        {docs[0], docs[2], docs[3], docs[4]}
    assert texts(Phrase(("beta", "gamma"))) == {docs[0], docs[4]}
    assert texts(And((Term("gamma"), Not(Phrase(("alpha", "beta")))))) == \
        {docs[1], docs[5]}
    for q, expect in [
            (Phrase(("alpha", "beta")), {docs[0], docs[4]}),
            (Phrase(("alpha", "beta"), slop=1), {docs[0], docs[2], docs[4]}),
    ]:
        assert _oracle(q, docs) == expect        # oracle agrees with itself


def test_segmented_matches_monolithic_for_new_shapes():
    """Base + delta segments answer composable queries exactly like a
    monolithic rebuild of the concatenated corpus."""
    store = InMemoryBlobStore()
    base_docs = make_logs_like(700, seed=21)
    delta_docs = make_logs_like(300, seed=22)
    all_docs = base_docs + delta_docs
    cfg = BuilderConfig(B=1800, F0=1.0, index_ngrams=3)

    base_corpus = write_corpus(store, "corpus/sg-base", base_docs, n_blobs=2)
    index = Index.build(base_corpus, cfg,
                        SimCloudTransport(SimCloudStore(store, seed=4)),
                        "index/sg")
    w = index.writer()
    w.append(write_corpus(store, "corpus/sg-delta", delta_docs, n_blobs=1))
    w.commit()
    seg = index.searcher()
    assert seg.n_units == 2

    mono_store = InMemoryBlobStore()
    mono_corpus = write_corpus(mono_store, "corpus/sg-all", all_docs,
                               n_blobs=3)
    Builder(cfg).build(mono_corpus, mono_store, "index/sg-all")
    mono = Searcher(SimCloudTransport(SimCloudStore(mono_store, seed=4)),
                    "index/sg-all")

    queries = _mixed_queries(all_docs)
    seg_res = seg.query_batch(queries)
    mono_res = mono.query_batch(queries)
    for q, a, b in zip(queries, seg_res, mono_res):
        expect = _oracle(normalize(q), all_docs)
        assert set(a.texts) == expect, to_string(q)
        assert set(b.texts) == expect, to_string(q)
        assert sorted(a.texts) == sorted(b.texts)


def test_common_word_negation_prunes_candidates():
    store = InMemoryBlobStore()
    docs = make_logs_like(1500, seed=11)
    corpus = write_corpus(store, "corpus/cn", docs, n_blobs=2)
    report = Builder(BuilderConfig(B=1200, F0=1.0)).build(
        corpus, store, "index/cn")
    s = Searcher(SimCloudTransport(SimCloudStore(store, seed=5)),
                 "index/cn")
    common_w = "block"
    assert common_w in report.common_words
    q = And((Term("info"), Not(Term(common_w))))
    plan = physical_plan(normalize(q), units=(s,))
    assert plan.subtract_words == frozenset({common_w})
    assert plan.lookup_words == ["info", common_w]
    pruned = s.query(q)
    plain = s.query(Term("info"))
    assert set(pruned.texts) == _oracle(normalize(q), docs)
    # the exact ANDNOT prune removed the negated docs BEFORE the doc round
    assert pruned.stats.n_candidates < plain.stats.n_candidates
    assert pruned.stats.n_false_positives == 0
    # hashed (non-common) negation must NOT subtract — unsound
    q2 = And((Term("info"), Not(Term("node42"))))
    plan2 = physical_plan(normalize(q2), units=(s,))
    assert plan2.subtract_words == frozenset()
    assert plan2.lookup_words == ["info"]
    assert set(s.query(q2).texts) == _oracle(normalize(q2), docs)


def test_plan_batch_mixed_with_classic_byte_path():
    """A batch mixing Term/And/Or, regex and negation/phrase shapes:
    every member is planned, Term/And/Or members look up `query_words`
    of their tree, and all members stay exact under both combines."""
    store = InMemoryBlobStore()
    docs = make_logs_like(800, seed=17)
    corpus = write_corpus(store, "corpus/mx", docs, n_blobs=2)
    Builder(BuilderConfig(B=1500, F0=1.0, index_ngrams=3)).build(
        corpus, store, "index/mx")
    s = Searcher(SimCloudTransport(SimCloudStore(store, seed=9)), "index/mx")
    queries = [Term("error"), And((Term("info"), Term("block"))),
               Regex(r"blk_1[0-9]+"),
               And((Term("info"), Not(Term("block")))),
               Phrase(("received", "block"))]
    jobs = plan_batch(queries, units=(s,))
    assert all(j.plan is not None for j in jobs)
    for q, j in zip(queries[:2], jobs):
        assert j.plan.lookup_words == query_words(normalize(q))
    for impl in ("sorted", "bitmap"):
        for q, r in zip(queries, s.query_batch(queries, impl=impl)):
            assert set(r.texts) == _oracle(normalize(q), docs), to_string(q)


# =================================================================== kernel
def _set_eval(posts, steps):
    slots = [set(p.tolist()) for p in posts]
    for op, a, b in steps:
        if op == OP_AND:
            slots.append(slots[a] & slots[b])
        elif op == OP_OR:
            slots.append(slots[a] | slots[b])
        else:
            slots.append(slots[a] - slots[b])
    return slots[-1]


def _random_program(rng, n_leaves):
    steps = []
    n_slots = n_leaves
    for s in range(rng.integers(1, 6)):
        op = int(rng.integers(0, 3))
        a = int(rng.integers(0, n_slots))
        b = int(rng.integers(0, n_slots))
        steps.append((op, a, b))
        n_slots += 1
    # final step must consume the running frontier to be a sane program;
    # for oracle purposes any DAG is fine — result is the last slot
    return steps


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**16))
def test_combine_batch_matches_ref_and_sets(seed):
    rng = np.random.default_rng(seed)
    n_docs = int(rng.integers(40, 3000))
    Q = int(rng.integers(1, 6))
    batch, programs = [], []
    for _ in range(Q):
        L = int(rng.integers(1, 5))
        posts = [np.unique(rng.integers(0, n_docs,
                                        int(rng.integers(1, n_docs))))
                 .astype(np.uint32) for _ in range(L)]
        batch.append(posts)
        programs.append(_random_program(rng, L))
    L_max = max(len(p) for p in batch)
    W = (n_docs + 31) // 32
    bitmaps = np.zeros((Q, L_max, W), dtype=np.uint32)
    for q, posts in enumerate(batch):
        bitmaps[q, :len(posts)] = postings_to_bitmap_batch(
            [posts], n_docs)[0]
    padded = [[(op, a + (L_max - len(batch[q]) if a >= len(batch[q]) else 0),
                b + (L_max - len(batch[q]) if b >= len(batch[q]) else 0))
               for op, a, b in prog]
              for q, prog in enumerate(programs)]
    progs = pack_programs(padded, L_max)
    out_p, cnt_p = combine_batch(bitmaps, progs, impl="pallas",
                                 n_docs=n_docs)
    out_r, cnt_r = combine_batch(bitmaps, progs, impl="ref",
                                 n_docs=n_docs)
    np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_r))
    np.testing.assert_array_equal(np.asarray(cnt_p), np.asarray(cnt_r))
    for q in range(Q):
        expect = np.array(sorted(_set_eval(batch[q], programs[q])),
                          dtype=np.uint32)
        got = bitmap_to_docs(np.asarray(out_p)[q])
        np.testing.assert_array_equal(got, expect)
        assert int(cnt_p[q]) == len(expect)


def test_pack_programs_pads_with_identity():
    progs = pack_programs([[(OP_AND, 0, 1)],
                           [(OP_OR, 0, 1), (OP_ANDNOT, 2, 0)]],
                          n_layers=2)
    assert progs.shape == (2, 2, 3)
    # the padded step re-ANDs the previous result with itself
    assert tuple(progs[0, 1]) == (OP_AND, 2, 2)
