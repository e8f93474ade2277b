"""The readers of the program's own spans: on synthetic spans, on none,
on a program without the recorder, on the profiler's clock, and in a
whole traced run on the CPU at 4,000 lines."""

import sys
import time
from types import SimpleNamespace

import jax
import pytest

import repro.analysis
from chipbench import harness, program, trace
from chipbench.tests.test_chipbench_correct import SEED, _cell
from repro.analysis import spans
from repro.data import make_logs_like, write_corpus
from repro.index import BuilderConfig, Index
from repro.serving import SearchService
from repro.storage import InMemoryBlobStore

MS = 1_000_000


def _span(id, name, parent, start_ms, end_ms, **attrs):
    return SimpleNamespace(id=id, name=name, parent=parent,
                           start_ns=start_ms * MS, end_ns=end_ms * MS,
                           attrs=attrs)


# two batches: 100 ms and 50 ms; a 15 ms compile under the first one's
# kernel call; a 40 ms fallback whose verification takes 30 ms of it
SPANS = [
    _span(1, "service.search_batch", None, 0, 100, n=2, cold=2),
    _span(3, "searcher.decode", 1, 0, 10, candidates=500),
    _span(4, "combine.build", 1, 10, 15, kernel="combine_batch"),
    _span(5, "combine.call", 1, 15, 35, kernel="combine_batch"),
    _span(6, "jit.compile", 5, 16, 31, fun_name="combine_batch_pallas",
          seconds=0.015),
    _span(7, "combine.unpack", 1, 35, 37),
    _span(8, "searcher.verify", 1, 37, 45, docs=10, accepted=5),
    _span(9, "searcher.fallback", 1, 45, 85, jobs=1, docs=100),
    _span(10, "searcher.round2", 9, 45, 55, requests=100),
    _span(11, "searcher.verify", 9, 55, 85, docs=100, accepted=25),
    _span(2, "service.search_batch", None, 200, 250, n=1, cold=1),
    _span(12, "searcher.decode", 2, 200, 204),
    _span(13, "searcher.verify", 2, 204, 206, docs=10, accepted=10),
]
EXPECTED = {
    "searcher.decode_ms": (10 + 4) / 2,
    "combine.build_ms": 5 / 2,
    "combine.call_ms": 20 / 2,
    "combine.unpack_ms": 2 / 2,
    "searcher.verify_ms": (8 + 30 + 2) / 2,
    "searcher.fallback_ms": 40,
    "searcher.fallback_docs": 100,
    "searcher.verify_precision": 100 * (5 + 25 + 10) / (10 + 100 + 10),
    "jit.compile_ms": 15,
    "service.span_coverage": 100 * (10 + 5 + 20 + 2 + 8 + 40 + 4 + 2)
    / (100 + 50),
}


def _records(program_spans=None):
    rec = harness.Records(setup_s=1.0, outcomes=[],
                          frontend=harness.FrontendStats(), spans=[],
                          gets=0, compiles=0)
    if program_spans is not None:
        rec.program = program_spans
    return rec


def _read(name, rec):
    return harness.load_module("metrics", name).read(rec)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader(name):
    assert _read(name, _records(SPANS)) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_without_spans_reads_nothing(name):
    assert _read(name, _records([])) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_of_a_program_without_the_recorder(name, monkeypatch):
    monkeypatch.delattr(repro.analysis, "spans")
    monkeypatch.setitem(sys.modules, "repro.analysis.spans", None)
    rec = _records()
    assert _read(name, rec) is None
    assert rec.program == []


def test_spans_are_taken_from_the_recorder_once():
    spans.start()
    with spans.span(program.BATCH):
        pass
    rec = _records()
    assert [s.name for s in program.spans(rec)] == [program.BATCH]
    assert program.spans(rec) is rec.program
    assert spans.stop() == []


def test_spans_share_the_profilers_clock(tmp_path):
    """Every span recorded inside a `jax.profiler` trace on the CPU is a
    host event of `trace.load`, of the same name and duration."""
    store = InMemoryBlobStore()
    corpus = write_corpus(store, "corpus/c", make_logs_like(2000, seed=5),
                          n_blobs=2)
    Index.build(corpus, BuilderConfig(B=1500, F0=1.0), store,
                "index/c").close()
    svc = SearchService(Index.open(store, "index/c"))
    try:
        queries = ["error block", "info OR warn", '"info block"']
        svc.search_batch(queries, top_k=5, impl="bitmap")   # compiles
        jax.profiler.start_trace(str(tmp_path))
        try:
            spans.start()
            svc.search_batch(queries, top_k=5, impl="bitmap")
            recorded = [s for s in spans.stop()
                        if s.name != spans.COMPILE]
        finally:
            jax.profiler.stop_trace()
    finally:
        svc.close()
    _device, host = trace.load(trace.find_xplane(str(tmp_path)))
    assert len(recorded) >= 10
    for name in {s.name for s in recorded}:
        ours = sorted(s.end_ns - s.start_ns for s in recorded
                      if s.name == name)
        theirs = sorted(e.dur_ns for e in host if e.name == name)
        assert len(theirs) == len(ours), name
        # the annotation opens just before the clock is read and closes
        # just after; a busy test machine may deschedule the thread
        # between the two, so allow a little over 1%
        for a, b in zip(ours, theirs):
            assert b == pytest.approx(a, rel=0.01, abs=2_000_000), name


def test_traced_run_reports_every_span_metric(monkeypatch, tmp_path):
    # a directory of its own: other test files run the harness in other
    # worker processes at the same time
    monkeypatch.setattr(harness, "DATA_DIR", str(tmp_path / "data"))
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    taken = []
    take = program.spans

    def keep(rec):
        got = take(rec)
        taken.append(got)
        return got

    monkeypatch.setattr(program, "spans", keep)
    spans.stop()
    line = harness.run(_cell("hdfs-1m.mixed"), SEED, 3.0, True,
                       time.perf_counter())
    assert line["correct"], line["checks"]
    assert set(EXPECTED) <= set(line["metrics"])
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert got["service.span_coverage"] >= 90
    assert 0 < got["searcher.verify_precision"] <= 100
    recorded = taken[0]
    assert all(t is recorded for t in taken)
    compiles = [s for s in recorded if s.name == spans.COMPILE]
    assert len(compiles) == got["jit.compiles_in_window"]
    names = {s.name for s in recorded}
    assert {"frontend.serve", "service.search_batch", "searcher.plan",
            "searcher.round1", "searcher.decode", "searcher.sample",
            "searcher.round2", "searcher.verify", "searcher.merge",
            "combine.build", "combine.call"} <= names
    by_id = {s.id: s for s in recorded}
    for s in recorded:
        if s.name == "service.search_batch":
            serve = by_id[s.parent]
            assert serve.name == "frontend.serve" and s.batch == serve.id
