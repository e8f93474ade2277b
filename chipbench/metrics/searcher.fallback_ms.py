"""Host time in the round-2 fallback (`searcher.fallback`: the Eq. 6
fetch and verification of every candidate left when a top-K sample came
up short), summed over the window."""

from chipbench import program


def read(rec):
    return program.window_ms(rec, "searcher.fallback")
