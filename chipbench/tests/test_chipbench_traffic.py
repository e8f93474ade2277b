"""The corpus and traffic generators: deterministic per seed, the same
amount of work on every seed, and text the program parses to the
generator's tree."""

import os

import numpy as np
import pytest

from chipbench import harness
from chipbench.corpus import Reference
from repro.index import And, Not, Or, Phrase, Term, parse

N_DOCS = 3000


CONFIG = harness.load_cell("hdfs-1m.mixed").config


def _lines(seed, n_docs=N_DOCS):
    return harness.make_lines({**CONFIG, "n_docs": n_docs},
                              np.random.default_rng(seed))


@pytest.fixture(scope="module")
def ref():
    return Reference(_lines(5))


@pytest.fixture(scope="module", params=["hdfs-1m.mixed"])
def mix(request):
    return harness.load_json(os.path.join(harness.BENCH_DIR, "traffic",
                                          f"{request.param}.json"))


def _schedule(mix, ref, seed, stream, seconds=30.0):
    gen = harness.load_module("traffic", mix["generator"])
    return gen.schedule(mix, ref, harness.rng_for(seed, stream), seconds)


def test_same_seed_same_stream(mix, ref):
    a = _schedule(mix, ref, 2**31 + 7, harness.WINDOW)
    b = _schedule(mix, ref, 2**31 + 7, harness.WINDOW)
    assert a == b


def test_window_and_warmup_streams_differ(mix, ref):
    window = _schedule(mix, ref, 11, harness.WINDOW)
    warm = _schedule(mix, ref, 11, harness.WARMUP, mix["warmup_s"])
    assert [r.text for r in window] != [r.text for r in warm]
    assert [r.due_s for r in window[:len(warm)]] != [r.due_s for r in warm]


def _terms(tree):
    if tree[0] == "term":
        return [tree[1]]
    if tree[0] == "phrase":
        return []
    if tree[0] == "not":
        return _terms(tree[1])
    return [w for s in tree[1] for w in _terms(s)]


def test_every_seed_gets_the_same_work(mix, ref):
    """Same request count, same shape counts, same set of gaps, and the
    same document frequencies of the event words, rank by rank (words of
    equal frequency may trade places)."""
    def shape(tree):
        if tree[0] in ("term", "phrase"):
            return tree[0] + (str(tree[2]) if tree[0] == "phrase" else "")
        if tree[0] == "not":
            return ("not", shape(tree[1]))
        return (tree[0], tuple(shape(s) for s in tree[1]))

    a, b = (_schedule(mix, ref, s, harness.WINDOW) for s in (1, 2))
    assert len(a) == len(b) == round(mix["rate_qps"] * 30.0)
    assert sorted(map(repr, (shape(r.tree) for r in a))) == \
        sorted(map(repr, (shape(r.tree) for r in b)))
    gaps = [np.sort(np.diff([r.due_s for r in s] + [30.0])) for s in (a, b)]
    np.testing.assert_allclose(gaps[0], gaps[1])
    assert [r.due_s for r in a] != [r.due_s for r in b]
    event = [sorted(int(ref.df[ref.vocab[w]]) for r in s
                    for w in _terms(r.tree)
                    if not any(c.isdigit() for c in w)) for s in (a, b)]
    assert event[0] == event[1] and len(event[0]) > 10


def test_every_seed_gets_the_same_requests_on_one_corpus(mix, ref):
    """On one corpus, two traffic seeds send the same requests, word by
    word and in the same order, at other times."""
    a, b = (_schedule(mix, ref, s, harness.WINDOW) for s in (2**31 + 3, 9))
    assert [r.text for r in a] == [r.text for r in b]
    assert [r.due_s for r in a] != [r.due_s for r in b]


def test_requests_of_one_size_lie_apart(mix, ref):
    """The largest requests, by the lines their words select (ties by
    text), are dealt far apart: no two of the three largest within a
    tenth of the window's requests."""
    for seed in (1, 2, 2**32 + 1):
        reqs = _schedule(mix, ref, seed, harness.WINDOW)
        key = [(-len(ref.matches(r.tree, verified=False)), r.text)
               for r in reqs]
        top = np.sort(sorted(range(len(reqs)), key=key.__getitem__)[:3])
        assert np.diff(top).min() >= len(reqs) / 10, (seed, top)


def test_arrivals_do_not_bunch(mix, ref):
    """The gaps are dealt evenly: no tenth of the window takes more than
    1.4 times its share of the arrivals (a random order of the same gaps
    puts 1.7 times in some tenth on most seeds)."""
    for seed in (1, 2, 2**32 + 1):
        due = np.array([r.due_s for r in _schedule(mix, ref, seed,
                                                   harness.WINDOW)])
        most = max(((due >= d) & (due < d + 3.0)).sum() for d in due)
        assert most <= 1.4 * len(due) / 10, (seed, most)


def _df_drawn(ref, requests):
    """Document frequency of every word and phrase pair drawn, sorted."""
    out = []
    for r in requests:
        stack = [r.tree]
        while stack:
            t = stack.pop()
            if t[0] == "term":
                out.append(len(ref.docs_with(t[1])))
            elif t[0] == "phrase":
                out.append(len(ref.phrase_docs(tuple(t[1]), 0)))
            elif t[0] == "not":
                stack.append(t[1])
            else:
                stack.extend(t[1])
    return np.sort(out)


def test_every_corpus_seed_gets_the_same_work(mix):
    """Two corpora of other seeds, each with its own traffic seed: the
    words drawn hold the same documents to within a few percent, rank by
    rank."""
    dfs = []
    for seed in (2**31 + 21, 2**31 + 22):
        ref = Reference(_lines(seed, 20_000))
        dfs.append(_df_drawn(ref, _schedule(mix, ref, seed, harness.WINDOW)))
    assert len(dfs[0]) == len(dfs[1])
    np.testing.assert_allclose(dfs[0], dfs[1], rtol=0.15, atol=3)
    assert abs(dfs[0].sum() / dfs[1].sum() - 1) < 0.03


def test_corpus_counts_are_the_same_on_every_seed():
    """Exactly n_docs lines; the same number of lines of each template,
    of blocks and of block ids per line on every seed."""
    def census(seed):
        lines = _lines(seed)
        kinds = sorted(" ".join(w for w in line.split()[4:6]
                                if not any(c.isdigit() for c in w))
                       for line in lines)
        blocks = Reference(lines)
        n_blocks = sum(w.startswith("blk_") for w in blocks.words)
        return len(lines), kinds, n_blocks

    a, b = census(1), census(2**33 + 5)
    assert a[0] == b[0] == N_DOCS
    assert a[1] == b[1]
    assert a[2] == b[2] == round(N_DOCS * CONFIG["corpus"]["blocks_per_line"])


def test_corpus_is_shaped_like_hdfs_v1():
    """Every block's life is in the corpus (19 lines or more), the
    source's share of blocks log two exception lines each, and there are
    19.4 lines a block on average."""
    ref = Reference(_lines(7, 20_000))
    blocks = [i for i, w in enumerate(ref.words) if w.startswith("blk_")]
    per_block = ref.df[blocks]
    assert per_block.min() >= 19
    assert per_block.mean() == pytest.approx(
        1 / CONFIG["corpus"]["blocks_per_line"], rel=0.01)
    # one word that only its anomaly template has, for each
    failed = sum(len(ref.docs_with(w)) for w in (
        "writeblock", "receiveblock", "interrupted.", "empty", "pipe"))
    assert failed == 2 * round(
        len(blocks) * CONFIG["corpus"]["anomalous_block_share"])


def _as_query(tree):
    kind = tree[0]
    if kind == "term":
        return Term(tree[1])
    if kind == "phrase":
        return Phrase(tuple(tree[1]), tree[2])
    if kind == "not":
        return Not(_as_query(tree[1]))
    items = tuple(_as_query(s) for s in tree[1])
    return And(items) if kind == "and" else Or(items)


def test_text_parses_to_the_tree(mix, ref):
    from repro.index import normalize

    for r in _schedule(mix, ref, 3, harness.WINDOW):
        assert parse(r.text) == normalize(_as_query(r.tree)), r.text
