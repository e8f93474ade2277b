"""A doc-hash `ShardedIndex` of `n_shards` shards served by a
`SearchService`.

The config's `path` names the served path; `PATHS` maps it to today's
call, and is the one place that knows it. Every batch checks that the
service ran the gather the path names: a program without that gather
fails its first batch, in the warm-up, instead of measuring another path.
"""

from __future__ import annotations

from repro.index import BuilderConfig
from repro.serving import SearchService, ShardedIndex

PREFIX = "cluster/logs"
# config path -> SearchService.search_batch(impl=...)
PATHS = {"fused": "fused"}


def build(store, corpus, config: dict, seed: int) -> None:
    ShardedIndex.build(corpus, BuilderConfig(**config["builder"], seed=seed),
                       store, PREFIX, n_shards=config["n_shards"]).close()


def serve(transport, config: dict):
    """(call(texts, top_k) -> results, close) over an opened reader."""
    impl = PATHS[config["path"]]
    svc = SearchService(ShardedIndex.open(transport, PREFIX))

    def call(texts, top_k):
        out = svc.search_batch(texts, top_k=top_k, impl=impl)
        if not getattr(svc.searcher.last_scatter, "fused", False):
            raise RuntimeError(f"search_batch(impl={impl!r}) did not run "
                               "the cluster-fused gather")
        return out

    return call, svc.close
