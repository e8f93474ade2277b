"""The reader of the fallback's fetch rounds (`searcher.fallback_rounds`):
on synthetic spans, on spans of a program whose fallback records no
rounds, on none, and in a whole traced run on the CPU at 4,000 lines."""

import time

from chipbench import harness
from chipbench.tests.test_chipbench_correct import SEED, _cell
from chipbench.tests.test_chipbench_program import SPANS, _read, _records, \
    _span
from repro.analysis import spans

NAME = "searcher.fallback_rounds"
# SPANS's two batches, their fallbacks with rounds: the first batch's
# job took three rounds, the second batch had none to complete
ROUNDS = [s for s in SPANS if s.name != "searcher.fallback"] + [
    _span(9, "searcher.fallback", 1, 45, 85, jobs=1, docs=100, rounds=3,
          remaining=5000),
    _span(14, "searcher.fallback", 2, 206, 206, jobs=0, docs=0, rounds=0,
          remaining=0),
]


def test_reads_the_window_sum():
    assert _read(NAME, _records(ROUNDS)) == 3


def test_a_fallback_without_rounds_reads_nothing():
    assert _read(NAME, _records(SPANS)) is None


def test_no_spans_read_nothing():
    assert _read(NAME, _records([])) is None


def test_traced_run_reports_the_rounds(monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "DATA_DIR", str(tmp_path / "data"))
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    spans.stop()
    line = harness.run(_cell("hdfs-1m.mixed"), SEED, 3.0, True,
                       time.perf_counter())
    assert line["correct"], line["checks"]
    rounds = line["metrics"][NAME]
    assert rounds["unit"] == "rounds" and rounds["value"] >= 0
