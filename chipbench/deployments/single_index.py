"""One Airphant `Index` served by a `SearchService`.

The config's `path` names the served path; `PATHS` maps it to today's
call, and is the one place that knows it.
"""

from __future__ import annotations

from repro.index import BuilderConfig, Index
from repro.serving import SearchService

PREFIX = "index/logs"
# config path -> SearchService.search_batch(impl=...)
PATHS = {"bitmap": "bitmap"}


def build(store, corpus, config: dict, seed: int) -> None:
    Index.build(corpus, BuilderConfig(**config["builder"], seed=seed),
                store, PREFIX).close()


def serve(transport, config: dict):
    """(call(texts, top_k) -> results, close) over an opened reader."""
    impl = PATHS[config["path"]]
    svc = SearchService(Index.open(transport, PREFIX))

    def call(texts, top_k):
        return svc.search_batch(texts, top_k=top_k, impl=impl)

    return call, svc.close
