"""Fetch rounds of the round-2 fallback (`rounds` on `searcher.fallback`:
the chunks in which short top-K jobs fetched their remaining candidates
until each answer was proven), summed over the window. A program whose
fallback spans carry no `rounds` reads nothing."""

from chipbench import program


def read(rec):
    if not any("rounds" in s.attrs
               for s in program.named(rec, "searcher.fallback")):
        return None
    return program.attr_sum(rec, "searcher.fallback", "rounds")
