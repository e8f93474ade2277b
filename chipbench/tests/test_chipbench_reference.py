"""The benchmark's reference agrees with the program on a small corpus
(exact sets with top_k=None) over the single index on the host path
(`impl="sorted"`), and its vectorized phrase scan agrees with a plain
per-line scan."""

import numpy as np
import pytest

from chipbench import harness
from chipbench.corpus import Reference
from chipbench.corpus import _TOKEN_RE
from repro.index import BuilderConfig, Index
from repro.serving import SearchService
from repro.storage import InMemoryBlobStore

N_DOCS = 3000


def _docs():
    cell = harness.load_cell("hdfs-1m.mixed")
    return harness.make_lines({**cell.config, "n_docs": N_DOCS},
                              np.random.default_rng(9))


@pytest.fixture(scope="module")
def world():
    docs = _docs()
    store = InMemoryBlobStore()
    corpus, layout = harness.write_corpus(store, docs, 4)
    cfg = BuilderConfig(B=2500, F0=1.0)
    Index.build(corpus, cfg, store, "index/t").close()
    ref = Reference(docs)
    gen = harness.load_module("traffic", "boolean_mix")
    mix = harness.load_cell("hdfs-1m.mixed").mix
    requests = gen.schedule(mix, ref, harness.rng_for(4, harness.WINDOW),
                            40.0)
    return store, layout, ref, requests


def _ids(result, layout):
    return sorted(layout[(r.blob, r.offset)] for r in result.refs)


def test_reference_matches_sorted_path_single_index(world):
    store, layout, ref, requests = world
    svc = SearchService(Index.open(store, "index/t"))
    try:
        got = svc.search_batch([r.text for r in requests], top_k=None,
                               impl="sorted")
    finally:
        svc.close()
    for r, res in zip(requests, got):
        assert _ids(res, layout) == ref.matches(r.tree).tolist(), r.text


def _phrase_at(toks, words, slop):
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


@pytest.mark.parametrize("words,slop", [
    (("received", "block"), 0), (("packetresponder", "block"), 2),
    (("block", "src"), 1), (("info", "block", "terminating"), 7),
    (("block", "block"), 3)])
def test_phrase_scan_matches_per_line_scan(world, words, slop):
    docs = _docs()
    ref = world[2]
    want = [i for i, d in enumerate(docs)
            if _phrase_at([t for t in _TOKEN_RE.findall(d.lower())],
                          words, slop)]
    assert ref.phrase_docs(words, slop).tolist() == want
