"""One kind of job and one combine on the single-index path.

Every query, a lone term or regex included, is planned; a job whose
program is one leaf takes that leaf with no kernel launch, and every
other job of a batch rides one `combine_batch` launch under
`impl="bitmap"`. None of that may change an answer or a request.

The pinned figures below were recorded on the engine that ran
Term/And/Or trees and lone regexes as unplanned jobs (host `_combine`,
an AND-only kernel): for a fixed HDFS-shaped corpus and a batch holding
every query shape of the `hdfs-1m.mixed` traffic, each query's result
count, a digest of its refs and texts, its candidate count, and its
round-1 and round-2 GETs when sent alone, plus the whole batch's GETs.
Both `impl`s must still read exactly those. The corpus is built from
arithmetic, not a random generator, and its five common words differ in
document frequency, so the order of their postings, and with it every
request, does not depend on the hash seed of the process.
"""

import hashlib

import pytest

from repro.analysis import spans
from repro.data import write_corpus
from repro.index import (And, BuilderConfig, Index, Not, Or, Phrase, Term,
                         normalize, query_words)
from repro.index.planner import make_job
from repro.index.searcher import BatchStats
from repro.storage import InMemoryBlobStore

N_LINES = 1200


def _lines() -> list[str]:
    """HDFS_v1-shaped lines: 181 blocks, 35 nodes, seven templates."""
    out = []
    for i in range(N_LINES):
        blk = f"blk_{(i * 7919) % 181 - 90}"
        n1 = f"10.250.{i % 5}.{i % 7}"
        n2 = f"10.250.{(i + 2) % 5}.{(i * 3) % 7}"
        head = f"081109 {203515 + i} {i % 143} INFO dfs."
        t = (i + i // 35) % 7
        if t == 0:
            body = (f"DataNode$DataXceiver: Receiving block {blk} "
                    f"src: {n1}:50010 dest: {n2}:50010")
        elif t == 1:
            body = (f"DataNode$PacketResponder: PacketResponder {i % 3} "
                    f"for block {blk} terminating")
        elif t == 2:
            body = (f"DataNode$PacketResponder: Received block {blk} "
                    f"of size 67108864 from {n1}")
        elif t == 3:
            body = ("FSNamesystem: BLOCK* NameSystem.addStoredBlock: "
                    f"blockMap updated: {n1}:50010 is added to {blk} "
                    "size 67108864")
        elif t == 4:
            body = (f"DataNode$DataXceiver: {n1}:50010 Served block {blk} "
                    f"to {n2}")
        elif t == 5:
            head = head.replace("INFO", "WARN")
            body = (f"DataNode$DataXceiver: {n1}:50010:Got exception while "
                    f"serving {blk} to {n2}:")
        else:
            body = f"DataBlockScanner: Verification succeeded for {blk}"
        out.append(head + body)
    return out


T = Term
B1, B2, B3 = T("blk_-89"), T("blk_3"), T("blk_-50")
N1, N2, N3 = T("10.250.4.4"), T("10.250.1.5"), T("10.250.3.3")
W1, W2, W3 = T("served"), T("received"), T("exception")

# every shape of the hdfs-1m.mixed traffic, in its order, then a second
# draw of three of them
BATCH = [
    B1,                                                    # block
    W1,                                                    # word
    And((N1, N2)),                                         # node node
    And((W2, N3, B3)),                                     # word node block
    Or((B1, N3)),                                          # block | node
    Or((And((N1, N2)), B3)),                               # node node | blk
    And((N2, Not(T("info")))),                             # node -word
    And((W1, Not(N3))),                                    # word -node
    Phrase(("received", "block")),                         # "pair"
    Phrase(("for", "terminating"), 2),                     # "pair"~2
    Phrase(("block", "of"), 1),                            # "pair"~1
    And((W2, Phrase(("of", "size")))),                     # word "pair"
    Or((And((N3, Not(T("dfs.datanode")))), B2)),           # node -word | blk
    Or((Phrase(("got", "exception")), B1)),                # "pair" | block
    And((N1, Not(Or((T("info"), T("block")))))),           # node -(w | w)
    B3,
    And((T("10.250.1.6"), T("10.250.3.4"))),
    And((W3, Not(N2))),
]
# the jobs whose program is its one leaf: a term, a word under a negated
# node (hashed, so not subtracted), and the same twice more
ONE_LEAF = [0, 1, 7, 15, 17]

# per query: results, digest of (refs, texts), candidates, and round-1
# and round-2 GETs of the query sent alone
PINNED = {
    None: [
        (7, "82c71b10ac86667a", 7, 2, 7),
        (171, "b065d9d3402bbfd7", 171, 2, 12),
        (15, "e072015826785a19", 15, 4, 12),
        (1, "8241836674b060bf", 1, 5, 1),
        (47, "c7f21942b1e93f21", 47, 4, 14),
        (21, "1f8cb01d856764a9", 21, 5, 14),
        (10, "63b74ea8060096a4", 10, 3, 5),
        (161, "e5f212fa81695c83", 171, 2, 12),
        (171, "3c3a801ab57d5731", 171, 3, 12),
        (172, "50afbba69e82c355", 172, 3, 12),
        (171, "3c3a801ab57d5731", 171, 3, 12),
        (171, "3c3a801ab57d5731", 171, 5, 12),
        (12, "80d8ff0a5523ab6d", 12, 3, 10),
        (177, "797075d27a4be71d", 177, 4, 12),
        (9, "2d693b781b7c7fc9", 9, 3, 9),
        (6, "dddfc97142f801ed", 6, 2, 6),
        (15, "11f80811c8e7db38", 15, 3, 12),
        (161, "9efb1a6932c0c04c", 171, 2, 12),
    ],
    10: [
        (7, "15afd8e704772a91", 7, 2, 7),
        (10, "ed0a19721dd0fa19", 171, 2, 15),
        (10, "ae4a8179e11cc10c", 15, 4, 12),
        (1, "8241836674b060bf", 1, 5, 1),
        (10, "5901769ba1e30afc", 47, 4, 17),
        (10, "d48dc80cff47e55c", 21, 5, 14),
        (10, "bb04bf2b673c6ea9", 10, 3, 5),
        (10, "ed0a19721dd0fa19", 171, 2, 15),
        (10, "a1dc1382b77501ab", 171, 3, 13),
        (10, "6fbbde6846e78ca1", 172, 3, 15),
        (10, "a1dc1382b77501ab", 171, 3, 13),
        (10, "a1dc1382b77501ab", 171, 5, 13),
        (10, "92457b3746e0475d", 12, 3, 10),
        (10, "f3be637f7de430ec", 177, 4, 14),
        (9, "39cc0f0f276f5a37", 9, 3, 9),
        (6, "872ea2a18f0c4e33", 6, 2, 6),
        (10, "a58b3f0ef80f3c1c", 15, 3, 12),
        (10, "2300b86b7eb99cda", 171, 2, 16),
    ],
}
# the whole batch's round-1 and round-2 GETs
PINNED_BATCH = {None: (9, 12), 10: (9, 12)}


@pytest.fixture(scope="module")
def store():
    store = InMemoryBlobStore()
    corpus = write_corpus(store, "corpus/oc", _lines(), n_blobs=12)
    Index.build(corpus, BuilderConfig(B=3300, F0=1.0, seed=7,
                                      common_frac=0.0016),
                store, "index/oc").close()
    return store


def _searcher(store):
    return Index.open(store, "index/oc").searcher()


def _digest(refs, texts) -> str:
    h = hashlib.sha256()
    for r, t in zip(refs, texts):
        h.update(f"{r.blob}|{r.offset}|{r.length}|{t}\n".encode())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("top_k", [None, 10])
@pytest.mark.parametrize("impl", ["sorted", "bitmap"])
def test_answers_and_requests_are_the_pinned_ones(store, impl, top_k):
    s = _searcher(store)
    stats = BatchStats()
    results = s.query_batch(BATCH, top_k=top_k, impl=impl,
                            batch_stats=stats)
    alone = [s.query_batch([q], top_k=top_k, impl=impl)[0] for q in BATCH]
    got = [(len(r.refs), _digest(r.refs, r.texts), r.stats.n_candidates,
            a.stats.lookup.n_requests, a.stats.docs.n_requests)
           for r, a in zip(results, alone)]
    assert got == PINNED[top_k]
    assert (stats.lookup.n_requests, stats.docs.n_requests) == \
        PINNED_BATCH[top_k]


def test_term_and_or_trees_look_up_their_words_in_order(store):
    """Every job is planned; a tree of terms under And/Or looks up
    `query_words` of the tree, so round 1 asks for what it asked for."""
    s = _searcher(store)
    for q in BATCH:
        job = make_job(q, units=(s,))
        assert query_words(job.lookup_q) == job.plan.lookup_words
    for q in (B1, And((N1, N2)), And((W2, N3, B3)), Or((B1, N3)),
              Or((And((N1, N2)), B3))):
        assert make_job(q, units=(s,)).plan.lookup_words == \
            query_words(normalize(q))


def _combine_spans(store, queries):
    s = _searcher(store)
    spans.start()
    try:
        s.query_batch(queries, top_k=10, impl="bitmap")
    finally:
        out = spans.stop()
    return [sp for sp in out if sp.name.startswith("combine.")]


def test_one_leaf_jobs_launch_nothing(store):
    """A batch of one-leaf jobs builds, calls and unpacks nothing; add
    one job of several leaves and the batch launches `combine_batch`
    once, for that job alone."""
    assert _combine_spans(store, [BATCH[j] for j in ONE_LEAF]) == []
    combine = _combine_spans(store, [BATCH[j] for j in ONE_LEAF]
                             + [BATCH[4]])
    assert [sp.name for sp in combine] == ["combine.build", "combine.call",
                                           "combine.unpack"]
    assert all(sp.attrs["kernel"] == "combine_batch" and sp.attrs["Q"] == 1
               for sp in combine)
