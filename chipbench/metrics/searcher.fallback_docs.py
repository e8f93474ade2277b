"""Candidate documents the round-2 fallback fetched and verified
(`docs` on `searcher.fallback`), summed over the window."""

from chipbench import program


def read(rec):
    return program.attr_sum(rec, "searcher.fallback", "docs")
