"""The span recorder (`repro.analysis.spans`): off it records nothing and
opens no annotation; on it records nesting, batch ids, attributes and
compiles; the served path's span tree, with results byte-identical to an
unrecorded batch."""

import threading

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import spans
from repro.data import make_logs_like, write_corpus
from repro.index import BuilderConfig, Index, parse, query_words, searcher
from repro.serving import SearchService
from repro.storage import InMemoryBlobStore


@pytest.fixture(autouse=True)
def _clean_recorder():
    spans.stop()
    yield
    spans.stop()


class _Forbidden:
    is_enabled = staticmethod(jax.profiler.TraceAnnotation.is_enabled)

    def __init__(self, *_a, **_kw):
        raise AssertionError("an annotation was opened while off")


def test_off_records_nothing_and_opens_no_annotation(monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Forbidden)
    with spans.span("frontend.serve", starts_batch=True) as s:
        s.set(n=3)
        with spans.span("searcher.plan"):
            pass
    assert s is spans.NOOP
    assert spans.stop() == []


def test_on_records_nesting_batches_and_attributes():
    spans.start()
    with spans.span("frontend.serve", starts_batch=True) as serve:
        serve.set(n=2)
        with spans.span("service.search_batch") as batch:
            with spans.span("searcher.verify") as verify:
                verify.set(docs=5, accepted=4)
    with spans.span("loose"):
        pass
    out = spans.stop()
    assert [s.name for s in out] == ["searcher.verify",
                                     "service.search_batch",
                                     "frontend.serve", "loose"]
    loose = out[-1]
    assert serve.parent is None and serve.batch == serve.id
    assert batch.parent == serve.id and batch.batch == serve.id
    assert verify.parent == batch.id and verify.batch == serve.id
    assert loose.parent is None and loose.batch is None
    assert verify.attrs == {"docs": 5, "accepted": 4}
    assert serve.attrs == {"n": 2}
    assert serve.start_ns <= batch.start_ns <= verify.start_ns \
        <= verify.end_ns <= batch.end_ns <= serve.end_ns
    assert len({s.id for s in out}) == 4


def test_threads_keep_their_own_stacks():
    spans.start()
    seen = {}

    def work():
        with spans.span("other") as s:
            seen["other"] = s

    with spans.span("outer") as outer:
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    spans.stop()
    assert seen["other"].parent is None and outer.parent is None


def test_compile_is_parented_to_the_innermost_span():
    def thrice_plus_one(x):
        return x * 3 + 1

    x = jnp.arange(7.0).block_until_ready()
    spans.start()
    with spans.span("combine.call") as call:
        call.set(kernel="combine_batch", Q=1, L=2, W=7)
        jax.jit(thrice_plus_one)(x).block_until_ready()
    out = spans.stop()
    compiles = [s for s in out if s.name == spans.COMPILE]
    assert len(compiles) == 1, [(s.name, s.attrs) for s in out]
    rec = compiles[0]
    assert rec.parent == call.id
    assert "thrice_plus_one" in rec.attrs["fun_name"]
    assert rec.attrs["seconds"] > 0
    assert call.start_ns <= rec.start_ns <= rec.end_ns <= call.end_ns


def test_cap_counts_dropped_spans(monkeypatch):
    monkeypatch.setattr(spans, "CAP", 3)
    spans.start()
    for _ in range(5):
        with spans.span("x"):
            pass
    assert len(spans.stop()) == 3
    assert spans.dropped() == 2
    spans.start()
    assert spans.dropped() == 0


def test_a_span_open_across_stop_is_not_kept():
    spans.start()
    with spans.span("late"):
        spans.stop()
        spans.start()
    assert spans.stop() == []


def test_recording_follows_the_profiler(tmp_path):
    assert spans.span("before") is spans.NOOP
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("traced"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert spans.span("after") is spans.NOOP
    assert [s.name for s in spans.stop()] == ["traced"]


# ------------------------------------------------------------ served path
QUERIES = ["error", "error block", "info OR warn", "block -error",
           '"info block"', "node42 error"]
CHILDREN = {"searcher.plan", "searcher.round1", "searcher.decode",
            "combine.build", "combine.call", "combine.unpack",
            "searcher.sample", "searcher.round2", "searcher.verify",
            "searcher.fallback", "searcher.merge"}


@pytest.fixture(scope="module")
def store():
    store = InMemoryBlobStore()
    docs = make_logs_like(3000, seed=3)
    corpus = write_corpus(store, "corpus/s", docs, n_blobs=2)
    Index.build(corpus, BuilderConfig(B=2000, F0=1.0), store,
                "index/s").close()
    return store


@pytest.fixture(scope="module")
def service(store):
    svc = SearchService(Index.open(store, "index/s"))
    yield svc
    svc.close()


def test_decode_span_counts_the_superposts_and_their_bytes(store):
    """`searcher.decode` carries the unique superposts it decoded and
    their payload bytes: one per planned request, summed lengths."""
    unit = Index.open(store, "index/s").searcher()
    queries = [parse(q) for q in QUERIES]
    plan = unit._plan_words([query_words(q) for q in queries])
    spans.start()
    searcher.lookup_units([unit], queries, unit._fetcher)
    decode, = (s for s in spans.stop() if s.name == "searcher.decode")
    payloads = [store.get_range(r) for r in plan.requests]
    assert decode.attrs["superposts"] == len(set(plan.requests)) \
        == len(payloads) > len(QUERIES)
    assert decode.attrs["bytes"] == sum(len(p) for p in payloads) > 0


def test_served_batch_span_tree_and_identical_results(service):
    off = service.search_batch(QUERIES, top_k=5, impl="bitmap")
    spans.start()
    on = service.search_batch(QUERIES, top_k=5, impl="bitmap")
    again = service.search_batch(QUERIES[:2], top_k=5, impl="bitmap")
    out = spans.stop()
    assert [(r.refs, r.texts) for r in on] == \
        [(r.refs, r.texts) for r in off]
    assert [(r.refs, r.texts) for r in again] == \
        [(r.refs, r.texts) for r in off[:2]]

    roots = [s for s in out if s.name == "service.search_batch"]
    assert [r.attrs for r in roots] == [{"n": 6, "cold": 6},
                                        {"n": 2, "cold": 2}]
    tree = {r.id: [s for s in out if s.parent == r.id] for r in roots}
    first = tree[roots[0].id]
    names = {s.name for s in first}
    assert CHILDREN - {"searcher.fallback"} <= names <= CHILDREN
    assert all(r.start_ns <= s.start_ns <= s.end_ns <= r.end_ns
               for r in roots for s in tree[r.id])
    calls = [s for s in first if s.name == "combine.call"]
    assert [c.attrs["kernel"] for c in calls] == ["combine_batch"]
    # the batch's own shape, and beside it the class it launched at
    shape = {"Q", "L", "W", "S"}
    for c in calls:
        assert set(c.attrs) == {"kernel"} | shape | {k + "c" for k in shape}
        assert all(c.attrs[k + "c"] >= c.attrs[k] for k in shape)
    verify = [s for s in out if s.name == "searcher.verify"]
    assert all(0 <= v.attrs["accepted"] <= v.attrs["docs"] for v in verify)
    # no line holds the phrase "info block": its sample comes up short
    # and, with nothing accepted to estimate from, every remaining
    # candidate goes through the fallback in one round
    fallbacks = [s for s in out if s.name == "searcher.fallback"]
    assert any(fb.attrs["docs"] for fb in fallbacks)
    assert any(fb.attrs["rounds"] == 1
               and fb.attrs["docs"] == fb.attrs["remaining"] > 0
               for fb in fallbacks)
    _check_fallbacks(out)


def _check_fallbacks(out):
    """Each fallback span: one `round2` and one `verify` inside it per
    round, its `docs` the sum of theirs, no more than `remaining`."""
    for fb in (s for s in out if s.name == "searcher.fallback"):
        inner = [s for s in out if s.parent == fb.id]
        assert {s.name for s in inner} <= {"searcher.round2",
                                           "searcher.verify"}
        assert fb.attrs["docs"] == sum(s.attrs["docs"] for s in inner
                                       if s.name == "searcher.verify")
        for name in ("searcher.round2", "searcher.verify"):
            assert fb.attrs["rounds"] == sum(s.name == name for s in inner)
        assert fb.attrs["docs"] <= fb.attrs["remaining"]
        assert (fb.attrs["jobs"] == 0) == (fb.attrs["rounds"] == 0)


def test_fallback_span_over_several_rounds(monkeypatch):
    """A phrase that half its candidates hold, K above what its sample
    finds: the fallback completes it from a part of the remainder, in
    several rounds when the chunks start small, with the same answer."""
    lines = [f"INFO alpha beta gamma n{i}" if i % 2 else
             f"WARN beta delta alpha n{i}" for i in range(1200)]
    store = InMemoryBlobStore()
    corpus = write_corpus(store, "corpus/r", lines, n_blobs=2)
    Index.build(corpus, BuilderConfig(B=2000, F0=1.0), store,
                "index/r").close()
    svc = SearchService(Index.open(store, "index/r"))
    try:
        found = {}
        for rule, (chunk, margin) in {"default": (32, 1.25),
                                      "tight": (1, 0.01)}.items():
            monkeypatch.setattr(searcher, "FALLBACK_MIN_CHUNK", chunk)
            monkeypatch.setattr(searcher, "FALLBACK_MARGIN", margin)
            spans.start()
            res, = svc.search_batch(['"alpha beta"'], top_k=100,
                                    impl="bitmap")
            out = spans.stop()
            _check_fallbacks(out)
            fb, = (s for s in out if s.name == "searcher.fallback")
            found[rule] = (res.refs, res.texts, fb.attrs)
    finally:
        svc.close()
    assert found["tight"][:2] == found["default"][:2]
    assert len(found["default"][0]) == 100
    default, tight = found["default"][2], found["tight"][2]
    assert default["jobs"] == tight["jobs"] == 1
    assert 0 < default["docs"] * 4 < default["remaining"]
    assert tight["rounds"] > 1
    assert tight["remaining"] == default["remaining"]
