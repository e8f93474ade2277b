"""`correct` on whole runs at a small size on the CPU, with the harness's
look for a chip skipped: true for the program as it stands, false for
the control (the reference with verification skipped in the program's
place) and for each fault planted under the timed path."""

import time

import numpy as np
import pytest

from chipbench import harness
from chipbench.control import serve_control
from repro.data.corpus import DocRef
from repro.index import QueryResult, QueryStats

SEED = 2**31 + 11


def _cell(name, path=None):
    cell = harness.load_cell(name)
    if path is not None:
        cell.config["path"] = path
    cell.config["n_docs"] = 4000
    cell.config["builder"]["B"] = 4000
    cell.mix["rate_qps"] = 3.0
    cell.mix["warmup_s"] = 1.0
    return cell


def _run(name, serve=None, build=True, seconds=3.0, path=None):
    return harness.run(_cell(name, path), SEED, seconds, False,
                       time.perf_counter(), serve=serve, build=build)


def _own(session):
    return session.deployment.serve(session.transport, session.cell.config)


def half_batch_left_out(session):
    """The program serves the first half of each micro-batch; the rest
    get empty answers."""
    call, close = _own(session)

    def half(texts, top_k):
        keep = len(texts) // 2
        empty = [QueryResult(refs=[], texts=[], stats=QueryStats())
                 for _ in texts[keep:]]
        return (call(texts[:keep], top_k) if keep else []) + empty

    return half, close


def answer_altered(session):
    """Each answer's first document is moved one byte along its blob."""
    call, close = _own(session)

    def altered(texts, top_k):
        out = call(texts, top_k)
        for r in out:
            if r.refs:
                d = r.refs[0]
                r.refs[0] = DocRef(d.blob, d.offset + 1, d.length)
        return out

    return altered, close


@pytest.mark.parametrize("cell,path", [("hdfs-1m.mixed", None)])
def test_program_is_correct(cell, path):
    line = _run(cell, path=path)
    assert line["correct"], line["checks"]
    assert line["attempted"] == 9 and line["failed"] == 0
    assert set(line["metrics"]) == {
        m["name"] for m in _cell(cell).end_to_end}
    assert {"p95_ms", "gets_per_query", "setup_s"} \
        <= set(line["metrics"])
    assert list(line)[-1] == "checks"


def test_control_is_not_correct():
    line = _run("hdfs-1m.mixed", serve=serve_control, build=False,
                seconds=12.0)
    assert not line["correct"]
    assert line["checks"]["wrong_docs"]["value"] > 0


@pytest.mark.parametrize("cell,fault", [
    ("hdfs-1m.mixed", half_batch_left_out),
    ("hdfs-1m.mixed", answer_altered)],
    ids=lambda x: x if isinstance(x, str) else x.__name__)
def test_fault_is_not_correct(cell, fault):
    line = _run(cell, serve=fault)
    assert not line["correct"], line["checks"]


def test_rng_streams_are_independent():
    a = harness.rng_for(SEED, harness.WINDOW).random(4)
    b = harness.rng_for(SEED, harness.WARMUP).random(4)
    assert not np.allclose(a, b)
