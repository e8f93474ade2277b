"""One run of one cell, found by name in `BENCHMARK.json`.

A cell names a configuration (`configs/<config>.json`: the corpus, the
deployment, its sizes and its served path) and a traffic mix
(`traffic/<mix>.json`, read by `traffic/<generator>.py`). The corpus is
made by `corpora/<generator>.py`, a deployment kind
(`deployments/<kind>.py`) builds and serves one layout, and every metric
is a reader `metrics/<metric>.py` with `read(records)`. Adding a
cell adds files and its entry in `BENCHMARK.json`; nothing here changes.

A run, in order: generate the corpus from the seed on a disk blob store
under `chipbench/.data`, scan it for the reference, build the deployment,
open it as a reader would behind a `Frontend`, warm up on a short stream
of the cell's traffic under its own sub-seed, turn the persistent
compilation cache off, drive the window as an open loop, drain, and
compare every answer with the reference. Everything before the window
but the reference's scan is `setup_s`.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field

import jax
import numpy as np

from chipbench import check, openloop, trace as tracing
from chipbench.corpus import Reference
from chipbench.probes import Adapter, Compiles, CountingStore
from repro.data.corpus import Corpus, DocRef
from repro.serving import Frontend, FrontendConfig, Overloaded
from repro.serving.frontend import FrontendStats
from repro.storage import BlobStoreTransport, LocalBlobStore

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
DATA_DIR = os.path.join(BENCH_DIR, ".data")
TRACE_DIR = os.path.join(BENCH_DIR, ".trace")
CORPUS_PREFIX = "corpus/logs"
# how long past the window's close an answer is waited for; a request
# that fails counts at this latency in the percentiles
DRAIN_S = 60.0
# sub-seed streams drawn from --seed
CORPUS, BUILD, WARMUP, WINDOW = range(4)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        [seed % (1 << 64), stream]))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """`chipbench/<kind>/<name>.py`, loaded by path."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def load_cell(name: str) -> Cell:
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    files = {c["name"]: c["file"] for c in bench["configs"]}

    def applies(m: dict) -> bool:
        return name in m.get("workloads", [name])

    return Cell(name=name, chips=int(w["chips"]),
                config=load_json(os.path.join(ROOT, files[w["config"]])),
                mix=load_json(os.path.join(BENCH_DIR, "traffic",
                                           f"{w['traffic']}.json")),
                end_to_end=[m for m in bench["end_to_end"] if applies(m)],
                per_layer=[m for m in bench["per_layer"] if applies(m)])


def make_lines(config: dict, rng: np.random.Generator) -> list[str]:
    """The configuration's corpus, by its generator `corpora/<name>.py`."""
    params = config["corpus"]
    return load_module("corpora", params["generator"]).make_lines(
        config["n_docs"], rng, params)


def write_corpus(store, docs: list[str], n_blobs: int,
                 ) -> tuple[Corpus, dict[tuple[str, int], int]]:
    """Newline-delimited lines in `n_blobs` blobs, and the benchmark's
    own map from (blob, offset) to line index."""
    refs: list[DocRef] = []
    layout: dict[tuple[str, int], int] = {}
    per_blob = -(-len(docs) // n_blobs)
    for b in range(n_blobs):
        name = f"{CORPUS_PREFIX}/part-{b:05d}.txt"
        chunk = [d.encode() for d in docs[b * per_blob:(b + 1) * per_blob]]
        offset = 0
        for data in chunk:
            layout[(name, offset)] = len(refs)
            refs.append(DocRef(name, offset, len(data)))
            offset += len(data) + 1
        store.put(name, b"".join(c + b"\n" for c in chunk))
    return Corpus(store=store, refs=refs, texts=docs), layout


def set_persistent_cache(enabled: bool) -> None:
    """Turn JAX's persistent compilation cache on or off from the next
    compile on; programs compiled in this process stay in memory."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@dataclass
class Records:
    """What a run measured, for the metric readers."""

    setup_s: float
    outcomes: list[openloop.Outcome]
    frontend: FrontendStats
    spans: list
    gets: int
    compiles: int
    trace: tracing.TraceSummary | None = None
    shares: dict = field(default_factory=dict)

    @property
    def completed(self) -> int:
        return sum(o.answered for o in self.outcomes)

    def latencies_s(self) -> np.ndarray:
        """Due time to answer of every request due in the window; one
        that failed counts as `DRAIN_S`."""
        return np.array([o.done - o.due if o.answered else DRAIN_S
                         for o in self.outcomes])


class Session:
    """A deployment built from the seed and opened behind a `Frontend`,
    warmed up and ready for windows: steps one to five of a run.

    `serve(session) -> (call, close)` stands in for the deployment's own
    `serve` (the control, a planted fault); with `build=False` nothing is
    built. `close` stops every thread the session started."""

    def __init__(self, cell: Cell, seed: int, serve=None,
                 build: bool = True) -> None:
        config = cell.config
        self.cell, self.seed = cell, seed
        self.shares: dict[str, float] = {}
        self.compiles = Compiles()
        now = time.perf_counter
        shutil.rmtree(DATA_DIR, ignore_errors=True)
        t = now()
        store = LocalBlobStore(DATA_DIR)
        self.docs = make_lines(config, rng_for(seed, CORPUS))
        corpus, self.layout = write_corpus(store, self.docs,
                                           config["corpus_blobs"])
        self.refs = corpus.refs
        self.trees: dict[str, tuple] = {}
        self.shares["corpus_s"] = now() - t

        t = now()
        self.ref = Reference(self.docs)
        self.shares["reference_s"] = now() - t
        # the benchmark's corpus copy, layout and reference are millions
        # of objects that a reader's process would not hold: keep the
        # collector's passes off them; what the program makes from here
        # on is collected as usual
        gc.collect()
        gc.freeze()

        self.deployment = deployment = load_module("deployments",
                                                   config["deployment"])
        t = now()
        if build:
            deployment.build(store, corpus, config,
                             int(rng_for(seed, BUILD).integers(1 << 31)))
        self.shares["build_s"] = now() - t

        t = now()
        self.store = CountingStore(store)
        self.transport = BlobStoreTransport(self.store)
        if serve is None:
            call, close = deployment.serve(self.transport, config)
        else:
            call, close = serve(self)
        self.backend = Adapter(call, close)
        self.frontend = Frontend(self.backend,
                                 FrontendConfig(**config["frontend"]))
        self.frontend.start()
        self.shares["open_s"] = now() - t

        t = now()
        n0 = self.compiles.n
        self.generator = load_module("traffic", cell.mix["generator"])
        warm = self.requests(WARMUP, cell.mix["warmup_s"])
        outcomes = self.drive(warm, cell.mix["warmup_s"])
        bad = [o.error for o in outcomes if not o.answered]
        if bad:
            raise RuntimeError(f"warm-up failed {len(bad)} of "
                               f"{len(outcomes)} requests: {bad[0]}")
        self.shares["warmup_s"] = now() - t
        self.shares["warmup_compiles"] = self.compiles.n - n0

    def requests(self, stream: int, seconds: float,
                 mix: dict | None = None) -> list[openloop.Request]:
        out = self.generator.schedule(mix or self.cell.mix, self.ref,
                                      rng_for(self.seed, stream), seconds)
        self.trees.update((r.text, r.tree) for r in out)
        return out

    def reset_counts(self) -> None:
        """Start the frontend's, the adapter's and the store's counts
        afresh; call it while nothing is in flight."""
        self.frontend.stats = FrontendStats()
        self.backend.spans.clear()
        self.store.gets = 0

    def drive(self, requests: list[openloop.Request], seconds: float):
        outcomes, _t0 = openloop.drive(
            lambda text, k: self.frontend.submit(text, top_k=k), requests,
            drain_s=DRAIN_S, window_s=seconds, refused=(Overloaded,))
        return outcomes

    def close(self) -> None:
        gc.unfreeze()
        if self.frontend is None:
            return
        self.frontend.close()
        self.backend.close()
        self.transport.close()
        self.frontend = None


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        serve=None, build: bool = True) -> dict:
    """One run; returns the result line. `serve` and `build` as for a
    `Session`."""
    cache_was = jax.config.jax_enable_compilation_cache
    session = None
    try:
        session = Session(cell, seed, serve, build)
        requests = session.requests(WINDOW, seconds)
        set_persistent_cache(False)
        session.reset_counts()
        compiles = session.compiles
        n0, hits0, secs0 = compiles.n, compiles.hits, compiles.secs
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(
                TRACE_DIR, profiler_options=_trace_options())
        # the reference's scan runs before the window only because the
        # traffic draws its words from it; it is not the system's set-up
        setup_s = time.perf_counter() - t_start \
            - session.shares["reference_s"]

        outcomes = session.drive(requests, seconds)

        summary = None
        if trace:
            jax.profiler.stop_trace()
            summary = tracing.reduce(tracing.find_xplane(TRACE_DIR))
        gets = session.store.gets
        n_compiles = compiles.n - n0
        session.shares["window_compile_s"] = compiles.secs - secs0
        if compiles.hits != hits0:
            raise RuntimeError("the window read the persistent cache")
        peak = _memory_peak()
        stats = session.frontend.stats
        session.close()
        checks = check.compare(outcomes, session.ref, session.layout,
                               session.docs)
    finally:
        if session is not None:
            session.close()
        set_persistent_cache(cache_was)
        shutil.rmtree(DATA_DIR, ignore_errors=True)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    records = Records(setup_s=setup_s, outcomes=outcomes,
                      frontend=stats,
                      spans=session.backend.spans, gets=gets,
                      compiles=n_compiles, trace=summary,
                      shares=session.shares)
    _report(records)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = load_module("metrics", m["name"]).read(records)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev), "memory_peak_bytes": peak}
    line = {"correct": check.passed(checks),
            "attempted": len(outcomes),
            "failed": sum(not o.answered for o in outcomes),
            "metrics": metrics, "device": device}
    if summary is not None:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        line["breakdown"] = {"device_ops": summary.device_ops(10),
                             "idle_gaps": summary.idle_gaps[:10]}
    for name, c in checks.items():
        log(f"check {name} = {c['value']} (limit {c['limit']})")
    line["checks"] = checks
    return line


def _trace_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def _memory_peak() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def _report(rec: Records) -> None:
    """Set-up shares, the window's counts and the generator's lateness,
    on standard error."""
    shares = dict(rec.shares)
    compile_s = shares.pop("window_compile_s", 0.0)
    log("setup " + " ".join(f"{k}={v:.3f}" for k, v in shares.items())
        + f" total_s={rec.setup_s:.3f}")
    batch_ms = np.mean([s.end - s.start for s in rec.spans]) * 1e3 \
        if rec.spans else 0.0
    late = np.array([o.sent - o.due for o in rec.outcomes])
    refused = sum(o.refused for o in rec.outcomes)
    log(f"window requests={len(rec.outcomes)} completed={rec.completed} "
        f"refused={refused} batches={len(rec.spans)} gets={rec.gets} "
        f"compiles={rec.compiles} compile_s={compile_s:.3f} "
        f"batch_ms={batch_ms:.3f} generator_late_p99_ms="
        f"{np.percentile(late, 99) * 1e3:.3f} max_ms={late.max() * 1e3:.3f}")
