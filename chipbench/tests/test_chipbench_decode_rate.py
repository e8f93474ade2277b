"""The reader of the round-1 decoder's rate (`searcher.decode_ns_per_byte`):
on synthetic spans, on decode spans of a program that records no
`bytes`, on none, and in whole traced runs of both cells on the CPU at
4,000 lines."""

import time

import pytest

from chipbench import harness
from chipbench.tests.test_chipbench_correct import SEED, _cell
from chipbench.tests.test_chipbench_program import SPANS, _read, _records, \
    _span
from repro.analysis import spans

NAME = "searcher.decode_ns_per_byte"
MS = 1_000_000
# SPANS's two batches, their decode spans with the superposts and bytes
# they decoded: 10 ms over 2,000,000 bytes and 4 ms over 500,000
RATED = [s for s in SPANS if s.name != "searcher.decode"] + [
    _span(3, "searcher.decode", 1, 0, 10, candidates=500, superposts=6,
          bytes=2_000_000),
    _span(12, "searcher.decode", 2, 200, 204, candidates=20, superposts=3,
          bytes=500_000),
]


def test_reads_the_window_time_over_the_window_bytes():
    assert _read(NAME, _records(RATED)) == pytest.approx(
        (10 + 4) * MS / 2_500_000)


def test_decode_spans_without_bytes_read_nothing():
    assert _read(NAME, _records(SPANS)) is None


def test_no_spans_read_nothing():
    assert _read(NAME, _records([])) is None


@pytest.mark.parametrize("cell", ["hdfs-1m.mixed", "hdfs-1m-16shard.mixed"])
def test_traced_run_reports_the_rate(cell, monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "DATA_DIR", str(tmp_path / "data"))
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    spans.stop()
    line = harness.run(_cell(cell), SEED, 3.0, True, time.perf_counter())
    assert line["correct"], line["checks"]
    rate = line["metrics"][NAME]
    assert rate["unit"] == "ns/B" and rate["value"] > 0
