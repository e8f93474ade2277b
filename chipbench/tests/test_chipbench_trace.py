"""The trace reduction and the kernel readers, on a small trace recorded
on one TPU v5e: the bitmap path serving a few requests over 20,000 lines
(one `intersect_batch` and three `combine_batch` calls)."""

import os

import pytest

from chipbench import harness, probes, trace

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "v5e_bitmap_small.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(FIXTURE)


def test_busy_and_idle_cover_the_window(summary):
    assert 0 < summary.busy_s < summary.window_s
    idle = sum(s for _name, s in summary.idle_gaps)
    assert idle == pytest.approx(summary.window_s - summary.busy_s)


def test_device_ops_are_named_by_instruction(summary):
    ops = dict(summary.device_ops(20))
    assert {"intersect_batch_pallas.1", "combine_batch_pallas.1"} <= set(ops)
    assert ops["combine_batch_pallas.1"] == pytest.approx(761e-9)


def test_idle_gaps_are_named_by_host_activity(summary):
    names = [name for name, _s in summary.idle_gaps]
    assert names[0] == probes.BATCH_SPAN
    assert "PJRT_Client_Compile" in names


def test_kernel_calls_are_found(summary):
    calls = [e for e in summary.device_events if e.is_kernel]
    assert [e.op for e in calls] == ["intersect_batch_pallas.1"] \
        + ["combine_batch_pallas.1"] * 3


def _records(summary, batches):
    span = probes.BatchSpan(0.0, 1.0, 0.1, 0.2)
    return harness.Records(setup_s=1.0, outcomes=[],
                           frontend=harness.FrontendStats(),
                           spans=[span] * batches, gets=0, compiles=0,
                           trace=summary)


def test_kernel_readers(summary):
    rec = _records(summary, batches=4)
    ms = harness.load_module("metrics", "kernel.combine_ms").read(rec)
    assert ms == pytest.approx((22 + 3 * 253.67) * 1e-6 / 4, rel=1e-3)
    idle = harness.load_module("metrics", "device.idle_share").read(rec)
    assert 99.9 < idle < 100


def test_readers_without_a_trace_read_nothing(summary):
    rec = _records(None, batches=1)
    for name in ("kernel.combine_ms", "device.idle_share"):
        assert harness.load_module("metrics", name).read(rec) is None
