"""The 16-shard cell `hdfs-1m-16shard.mixed` on the CPU at 4,000 lines:
`correct` for the program and false for a planted fault, a program
without the fused gather stopped in the warm-up, every cluster metric in
a traced run, and the roofline's bytes and share on synthetic calls."""

import time
from types import SimpleNamespace

import pytest

from chipbench import harness, kernel_cost, program
from chipbench.tests.test_chipbench_correct import (SEED, _cell, _run,
                                                    answer_altered)
from chipbench.trace import KERNEL_MARKER, Event, TraceSummary
from repro.analysis import spans
from repro.serving import SearchService

CELL = "hdfs-1m-16shard.mixed"
CLUSTER = ["cluster.round1_ms", "cluster.combine_ms", "cluster.order_ms",
           "cluster.round2_ms", "cluster.completion_docs"]


@pytest.fixture(autouse=True)
def own_dirs(monkeypatch, tmp_path):
    """Directories of this file's own: other test files run the harness
    in other worker processes at the same time."""
    monkeypatch.setattr(harness, "DATA_DIR", str(tmp_path / "data"))
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))


def test_program_is_correct():
    line = _run(CELL)
    assert line["correct"], line["checks"]
    assert line["attempted"] == 9 and line["failed"] == 0
    assert set(line["metrics"]) == {"p95_ms", "gets_per_query", "setup_s"}


def test_fault_is_not_correct():
    line = _run(CELL, serve=answer_altered)
    assert not line["correct"], line["checks"]


def test_a_program_without_the_fused_gather_fails_the_warmup(monkeypatch):
    """A service that answers `impl="fused"` on the per-shard legs (as
    one without the gather would) stops the run before the window."""
    plain = SearchService.search_batch

    def per_shard(self, queries, top_k=None, batched=True, impl="sorted"):
        return plain(self, queries, top_k=top_k, batched=batched,
                     impl="sorted")

    monkeypatch.setattr(SearchService, "search_batch", per_shard)
    with pytest.raises(RuntimeError, match="warm-up failed"):
        _run(CELL)


def test_traced_run_reports_every_cluster_metric():
    spans.stop()
    line = harness.run(_cell(CELL), SEED, 3.0, True, time.perf_counter())
    assert line["correct"], line["checks"]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(CLUSTER) <= set(got)
    assert all(got[k] > 0 for k in CLUSTER if k.endswith("_ms"))
    assert got["cluster.completion_docs"] >= 0
    # the interpreter runs no custom call on the CPU: nothing to read
    assert "kernel.combine_cluster_roofline" not in got


# ------------------------------------------------------------ the roofline
MS = 1_000_000
SHAPE = dict(G=16, Q=2, L=3, W=2000, S=4)


def _records(calls, kernel_ns):
    rec = harness.Records(setup_s=1.0, outcomes=[],
                          frontend=harness.FrontendStats(), spans=[],
                          gets=0, compiles=0)
    rec.program = [SimpleNamespace(id=1, name=program.BATCH, parent=None,
                                   start_ns=0, end_ns=100 * MS, attrs={})]
    rec.program += [SimpleNamespace(id=2 + i, name="combine.call", parent=1,
                                    start_ns=0, end_ns=MS,
                                    attrs={"kernel": "combine_cluster",
                                           **SHAPE})
                    for i in range(calls)]
    kernel = (f"%combine_cluster_pallas.1 = (u32[32,8,128]) custom-call(), "
              f"{KERNEL_MARKER}")
    other = f"%combine_batch_pallas.1 = (u32[2,8,128]) custom-call(), " \
            f"{KERNEL_MARKER}"
    rec.trace = TraceSummary(
        window_s=1.0, busy_s=1e-3,
        device_events=[Event(kernel, 0.0, kernel_ns),
                       Event(other, 0.0, 1e6), Event("%pad.0 = u32", 0, 1e6)],
        idle_gaps=[])
    return rec


def test_bytes_and_ops():
    G, Q, L, W, S = SHAPE.values()
    assert kernel_cost.combine_cluster_bytes(**SHAPE) == \
        4 * G * Q * (L * W + 3 * S + W + 1)
    assert kernel_cost.combine_cluster_ops(**SHAPE) == \
        G * Q * W * (7 * S + 13)
    assert kernel_cost.hbm_bytes_per_s("TPU v5 lite") == 819e9


def test_roofline_share(monkeypatch):
    read = harness.load_module("metrics",
                               "kernel.combine_cluster_roofline").read
    monkeypatch.setattr(
        "jax.devices", lambda: [SimpleNamespace(device_kind="TPU v5 lite")])
    moved = 3 * kernel_cost.combine_cluster_bytes(**SHAPE)
    # the bytes of three calls in the time the peak would take, and ten
    # times that time: 100% and 10%; the other kernel and ops not counted
    at_peak_ns = moved / 819e9 * 1e9
    assert read(_records(3, at_peak_ns)) == pytest.approx(100.0)
    assert read(_records(3, 10 * at_peak_ns)) == pytest.approx(10.0)
    assert read(_records(0, at_peak_ns)) is None
    rec = _records(3, at_peak_ns)
    rec.trace = None
    assert read(rec) is None
    monkeypatch.setattr(
        "jax.devices", lambda: [SimpleNamespace(device_kind="cpu")])
    assert read(_records(3, at_peak_ns)) is None
