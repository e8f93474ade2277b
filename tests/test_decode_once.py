"""Round 1 decodes each superpost once and searches no word's own keys.

A word for which one layer was decoded (a common word, which has one
pointer, or a word whose other layers a hedge dropped) takes that
layer's `(keys, lengths)` as they are; a word of several layers keeps
the intersection of its layers and the lengths looked up in its first.
Both must equal what every word got when each went through
`intersect_sorted` and a `searchsorted` of its lengths, which
`_intersection_path` below repeats from the raw superposts.

The pinned figures were recorded on the reader that ran every word that
way: for the HDFS-shaped corpus of `test_one_combine` and its batch of
every `hdfs-1m.mixed` query shape at the cells' top_k of 100, each
query's result count, a digest of its refs and texts, and its round-1
and round-2 GETs, on a `SearchService` over one index (`impl` "sorted"
and "bitmap") and over four shards (`impl` "fused").
"""

import numpy as np
import pytest
from test_one_combine import BATCH, _digest, _lines

from repro.core.sketch import intersect_sorted
from repro.data import write_corpus
from repro.index import BuilderConfig, Index, Term, codec
from repro.index.searcher import lookup_units
from repro.serving import SearchService, ShardedIndex
from repro.storage import InMemoryBlobStore

CFG = BuilderConfig(B=3300, F0=1.0, seed=7, common_frac=0.0016)
# common words, rare words of several layers, and a word of no line
WORDS = ["info", "block", "dfs.datanode", "served", "exception",
         "blk_-89", "10.250.4.4", "terminating", "nosuchword"]


@pytest.fixture(scope="module")
def store():
    return _build()


def _build():
    store = InMemoryBlobStore()
    corpus = write_corpus(store, "corpus/do", _lines(), n_blobs=12)
    Index.build(corpus, CFG, store, "index/do").close()
    ShardedIndex.build(corpus, CFG, store, "cluster/do", n_shards=4)
    return store


def _intersection_path(store, unit, word, layers=None):
    """`word`'s candidates the way every word got them before: its
    layers' superposts (`layers` of them, default all) through
    `intersect_sorted`, each key's length from the first layer."""
    ptrs, _common = unit._pointers_for_word(word)
    posts = [codec.decode_superpost(store.get_range(unit._request(p)))
             for i, p in enumerate(ptrs) if layers is None or i in layers]
    keys = intersect_sorted([k for k, _l in posts])
    k0, l0 = posts[0]
    return keys, l0[np.searchsorted(k0, keys)]


def _same(got, want):
    for g, w in zip(got, want):
        assert g.dtype == w.dtype == np.uint64
        assert np.array_equal(g, w)


def test_one_and_several_layer_words_equal_the_intersection_path(store):
    unit = Index.open(store, "index/do").searcher()
    layers = {w: len(unit._pointers_for_word(w)[0]) for w in WORDS}
    # both rules run: common words of one layer, rare words of several
    assert {1, unit.L_total} == set(layers.values())
    outs, _stats = lookup_units([unit], [Term(w) for w in WORDS],
                                unit._fetcher)
    for w, out in zip(WORDS, outs[0]):
        _same(out[w], _intersection_path(store, unit, w))
        assert len(out[w][0]) or w == "nosuchword"


def test_a_word_left_one_layer_by_the_hedge_takes_it_as_it_is(
        store, monkeypatch):
    """Drop every layer but the second of a rare word, as a hedge that
    abandoned their requests would: the word's candidates are that
    layer's superpost, and hold the full intersection."""
    unit = Index.open(store, "index/do").searcher()
    word = "blk_-89"
    ptrs, common = unit._pointers_for_word(word)
    assert not common and len(ptrs) > 1
    dropped = {unit._request(p) for i, p in enumerate(ptrs) if i != 1}
    fetch = unit._fetcher.fetch_ranges

    def hedged(requests, **kw):
        payloads, stats = fetch(requests, **kw)
        return [None if r in dropped else p
                for r, p in zip(requests, payloads)], stats

    monkeypatch.setattr(unit._fetcher, "fetch_ranges", hedged)
    outs, _stats = lookup_units([unit], [Term(word)], unit._fetcher)
    got = outs[0][0][word]
    _same(got, _intersection_path(store, unit, word, layers={1}))
    _same(got, codec.decode_superpost(
        store.get_range(unit._request(ptrs[1]))))
    full, _lengths = _intersection_path(store, unit, word)
    assert np.isin(full, got[0]).all() and len(got[0]) >= len(full)


# per query: results, digest of (refs, texts), and the round-1 and
# round-2 GETs its result reports
PINNED = {
    "sorted": [
        (7, "c972ede15b4d31b6", 9, 12),
        (100, "fd02d58db90bb4e7", 9, 12),
        (15, "6a1dd2a86231b27f", 9, 12),
        (1, "c808822a8aae19b6", 9, 12),
        (47, "c2bb6e8e4f339c9a", 9, 12),
        (21, "f725b31c0936ca0a", 9, 12),
        (10, "d6975a016e90bca8", 9, 12),
        (100, "abffbea5dd787c63", 9, 12),
        (100, "787f32780ab3bdbd", 9, 12),
        (100, "7d49978f64fe1a9d", 9, 12),
        (100, "787f32780ab3bdbd", 9, 12),
        (100, "787f32780ab3bdbd", 9, 12),
        (12, "12eb48b303ea4977", 9, 12),
        (100, "b043076833847635", 9, 12),
        (9, "c7ba31fefdde8a45", 9, 12),
        (6, "93ec9307fd65f819", 9, 12),
        (15, "26a5c8b717dd9141", 9, 12),
        (100, "0b9375c17401028d", 9, 12),
    ],
    "bitmap": [
        (7, "c972ede15b4d31b6", 9, 12),
        (100, "fd02d58db90bb4e7", 9, 12),
        (15, "6a1dd2a86231b27f", 9, 12),
        (1, "c808822a8aae19b6", 9, 12),
        (47, "c2bb6e8e4f339c9a", 9, 12),
        (21, "f725b31c0936ca0a", 9, 12),
        (10, "d6975a016e90bca8", 9, 12),
        (100, "abffbea5dd787c63", 9, 12),
        (100, "787f32780ab3bdbd", 9, 12),
        (100, "7d49978f64fe1a9d", 9, 12),
        (100, "787f32780ab3bdbd", 9, 12),
        (100, "787f32780ab3bdbd", 9, 12),
        (12, "12eb48b303ea4977", 9, 12),
        (100, "b043076833847635", 9, 12),
        (9, "c7ba31fefdde8a45", 9, 12),
        (6, "93ec9307fd65f819", 9, 12),
        (15, "26a5c8b717dd9141", 9, 12),
        (100, "0b9375c17401028d", 9, 12),
    ],
    "fused": [
        (7, "b928a08469311614", 8, 52),
        (100, "7fdaacf5e7bf41ef", 8, 52),
        (15, "5c0f66e3070a2af7", 8, 52),
        (1, "c808822a8aae19b6", 8, 52),
        (47, "ca0cfbd1949e5c4f", 8, 52),
        (21, "dc60cd7cffe47286", 8, 52),
        (10, "1134c6a17f10b70e", 8, 52),
        (100, "480b7a95834b0b13", 8, 52),
        (100, "f330558b5c2c89bf", 8, 52),
        (100, "714053144a0a96a4", 8, 52),
        (100, "f330558b5c2c89bf", 8, 52),
        (100, "f330558b5c2c89bf", 8, 52),
        (12, "b457941ea3dfa9bf", 8, 52),
        (100, "9ab2aec083eba887", 8, 52),
        (9, "c23795d16807e54e", 8, 52),
        (6, "84111356ee5d577e", 8, 52),
        (15, "e633f074d387b12b", 8, 52),
        (100, "5ed29d861effb666", 8, 52),
    ],
}


def _served(store, impl):
    index = ShardedIndex.open(store, "cluster/do") if impl == "fused" \
        else Index.open(store, "index/do")
    svc = SearchService(index)
    try:
        results = svc.search_batch(BATCH, top_k=100, impl=impl)
    finally:
        svc.close()
    return [(len(r.refs), _digest(r.refs, r.texts), r.stats.lookup.n_requests,
             r.stats.docs.n_requests) for r in results]


@pytest.mark.parametrize("impl", list(PINNED))
def test_batch_answers_and_requests_are_the_pinned_ones(store, impl):
    assert _served(store, impl) == PINNED[impl]
