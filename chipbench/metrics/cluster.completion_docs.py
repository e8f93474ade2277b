"""Candidate documents the cluster-fused gather's completion fetched and
verified after the quota round (`docs` on `cluster.completion`), summed
over the window. A program without the span reads nothing."""

from chipbench import program


def read(rec):
    if not program.named(rec, "cluster.completion"):
        return None
    return program.attr_sum(rec, "cluster.completion", "docs")
