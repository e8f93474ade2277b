"""Share of verified documents that matched: accepted over checked,
summed over every `searcher.verify` (the fallback's included). The base
is one check per candidate of each query: a document two queries wanted
counts twice."""

from chipbench import program


def read(rec):
    docs = program.attr_sum(rec, "searcher.verify", "docs")
    if not docs:
        return None
    return 100.0 * program.attr_sum(rec, "searcher.verify",
                                    "accepted") / docs
